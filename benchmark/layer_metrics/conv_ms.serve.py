"""Kernels: device time under the `conv/*` scopes (norm and input
projection, the two gates and the taps with the tail's read and write,
output projection; all gated short-convolution layers) per execution
of the decode-step program in the traced slice. `timeline.py`'s table
keeps `conv/<part>` apart (two tokens name a scope under `conv`, as for
the train step's convolutions); nothing where no operation of the
program carries such a scope."""

from benchmark import timeline


def read(facts):
    ms = timeline.scope_ms(facts, "decode_step",
                           lambda scope: scope.startswith("conv/"))
    return ms or None
