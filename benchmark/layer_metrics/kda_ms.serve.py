"""Kernels: device time under the `kda/*` scopes (projections and
gates, convolution, the state's read, update and write, head norm, gate
and output projection; all Kimi Delta Attention layers) per execution
of the decode-step program in the traced slice."""

from benchmark import kda_scopes


def read(facts):
    return kda_scopes.kda_ms(facts, "decode_step")
