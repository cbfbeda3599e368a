"""Kernels: device time under the `ssd/*` scopes (norm and input
projection; the convolution's taps with the tail's read and write; the
step, the decay and the read, update and write-back of the state with
its products; the gate, norm and output projection; all Mamba-2
layers) per execution of the decode-step program in the traced slice.
Nothing where no operation of the program carries such a scope."""

from benchmark import group_scopes


def read(facts):
    return group_scopes.group_ms(facts, "ssd", "decode_step")
