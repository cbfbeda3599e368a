"""Kernels: device time under the `kda/*` scopes (all Kimi Delta
Attention layers, the chunkwise form of the recurrence) per execution
of the prefill-chunk program in the traced slice."""

from benchmark import kda_scopes


def read(facts):
    return kda_scopes.kda_ms(facts, "prefill_chunk")
