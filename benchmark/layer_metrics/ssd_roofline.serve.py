"""Kernels: the least time the chip could take for the Mamba-2 layers
of one decode step — each layer's two matrices read once and every
active row through them, the recurrence's own arithmetic, each active
row's state (the matrices and the convolution's tail) read and written
once in float32 (the reference module's `ssd_step`) — over
`ssd_ms.serve`. The rows are the window's, a step; the time is the
traced slice's. Nothing where the reference counts no such layer."""

from benchmark import group_scopes
from benchmark.roofline import roofline_seconds


def read(facts):
    d = facts["delta"]
    ms = group_scopes.group_ms(facts, "ssd", "decode_step")
    ssd_step = getattr(facts["reference"], "ssd_step", None)
    if not ms or not d.get("steps") or ssd_step is None:
        return None
    flops, nbytes = ssd_step(facts["config"], d["tokens_total"] / d["steps"])
    least = roofline_seconds(flops, nbytes, facts["peaks"], facts["chips"])
    return 100.0 * least / (ms * 1e-3)
