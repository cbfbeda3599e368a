"""Kernels: device time under the `kv_read` scope (the gather of every
slot's window of K and V cells, all layers) per execution of the
decode-step program in the traced slice."""

from benchmark import timeline


def read(facts):
    return timeline.scope_ms(facts, "decode_step",
                             lambda scope: scope == "kv_read")
