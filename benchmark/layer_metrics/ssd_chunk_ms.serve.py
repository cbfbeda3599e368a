"""Kernels: device time under the `ssd/*` scopes (all Mamba-2 layers,
the chunked form of the recurrence) per execution of the prefill-chunk
program in the traced slice. Nothing where no operation of the program
carries such a scope."""

from benchmark import group_scopes


def read(facts):
    return group_scopes.group_ms(facts, "ssd", "prefill_chunk")
