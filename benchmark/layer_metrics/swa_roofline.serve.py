"""Kernels: the least time the chip could take for the sliding-window
layers of one decode step — each layer's five matrices read once and
every active row through them, the ring cells the active rows attended
(the program's `window_cells_live`, summed over the window layers) read
once and attended over, one K row and one V row written a row and
layer (the reference module's `swa_step`) — over `swa_ms.serve`. The
counts are the window's, a step; the time is the traced slice's.
Nothing where the program does not count its rings' cells."""

from benchmark import swa_scopes
from benchmark.roofline import roofline_seconds


def read(facts):
    d = facts["delta"]
    ms = swa_scopes.swa_ms(facts, "decode_step")
    if not ms or not d.get("steps") or d.get("window_cells_live") is None:
        return None
    steps = d["steps"]
    flops, nbytes = facts["reference"].swa_step(
        facts["config"], d["tokens_total"] / steps,
        d["window_cells_live"] / steps)
    least = roofline_seconds(flops, nbytes, facts["peaks"], facts["chips"])
    return 100.0 * least / (ms * 1e-3)
