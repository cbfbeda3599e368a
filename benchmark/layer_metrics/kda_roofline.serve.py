"""Kernels: the least time the chip could take for the Kimi Delta
Attention layers of one decode step — each layer's matrices read once
and every active row through them, each active row's state read and
written once in float32 and the recurrence over it (the reference
module's `kda_step`) — over `kda_ms.serve`. The rows are the window's,
a step; the time is the traced slice's."""

from benchmark import kda_scopes
from benchmark.roofline import roofline_seconds


def read(facts):
    d = facts["delta"]
    ms = kda_scopes.kda_ms(facts, "decode_step")
    if not ms or not d.get("steps"):
        return None
    flops, nbytes = facts["reference"].kda_step(
        facts["config"], d["tokens_total"] / d["steps"])
    least = roofline_seconds(flops, nbytes, facts["peaks"], facts["chips"])
    return 100.0 * least / (ms * 1e-3)
