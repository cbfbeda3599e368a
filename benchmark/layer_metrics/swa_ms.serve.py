"""Kernels: device time under the `swa/*` scopes (projections, gate
logits and rotary; the ring's write and read; scores, softmax and
values; the gate and output projection; all sliding-window layers) per
execution of the decode-step program in the traced slice. Nothing
where no operation of the program carries such a scope."""

from benchmark import swa_scopes


def read(facts):
    return swa_scopes.swa_ms(facts, "decode_step")
