"""Kernels: device time under the `moe/*` scopes (router, held experts,
shared expert, all expert layers) per execution of the decode-step
program in the traced slice."""

from benchmark import scope_times


def read(facts):
    return scope_times.moe_ms(facts)
