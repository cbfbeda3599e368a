"""Kernels: the least time the chip could take for the grouped-query
attention of one decode step — the four projections' weights read once
and every active row through them, the live K and V rows of the active
slots read once and attended over (64 + 64 numbers a query head a
position), one K row and one V row written a slot (the reference
module's `gqa_step`) — over the device time under `qkv`, `kv_write`,
`kv_read`, `attn` and `attn_out` per execution of the decode-step
program."""

from benchmark import scope_times
from benchmark.roofline import roofline_seconds

SCOPES = ("qkv", "kv_write", "kv_read", "attn", "attn_out")


def read(facts):
    d = facts["delta"]
    ms = scope_times.scope_ms(facts, "decode_step",
                              lambda scope: scope in SCOPES)
    if not ms or not d.get("steps"):
        return None
    rows = d["tokens_total"] / d["steps"]
    flops, nbytes = facts["reference"].gqa_step(
        facts["config"], rows, rows * facts["mean_context"])
    least = roofline_seconds(flops, nbytes, facts["peaks"], facts["chips"])
    return 100.0 * least / (ms * 1e-3)
