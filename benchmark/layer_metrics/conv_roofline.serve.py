"""Kernels: the least time the chip could take for the gated
short-convolution layers of one decode step — each layer's two matrices
read once and every active row through them, the gates and taps, each
active row's tail read and written once in float32 (the reference
module's `conv_step`) — over `conv_ms.serve`. The rows are the
window's, a step; the time is the traced slice's."""

from benchmark import timeline
from benchmark.roofline import roofline_seconds


def read(facts):
    d = facts["delta"]
    ms = timeline.scope_ms(facts, "decode_step",
                           lambda scope: scope.startswith("conv/"))
    if not ms or not d.get("steps"):
        return None
    flops, nbytes = facts["reference"].conv_step(
        facts["config"], d["tokens_total"] / d["steps"])
    least = roofline_seconds(flops, nbytes, facts["peaks"], facts["chips"])
    return 100.0 * least / (ms * 1e-3)
