"""Kernels: the least time the chip could take for the latent attention
of one decode step — the five projections' weights read once and every
active row through them, the live latent rows of the active slots read
once and attended over (192 + 128 numbers a head a position, as the
definition has it), one row written a slot — over the device time under
`q_proj`, `kv_proj`, `kv_read`, `attn` and `attn_out` per execution of
the decode-step program."""

from benchmark import scope_times
from benchmark.roofline import roofline_seconds

SCOPES = ("q_proj", "kv_proj", "kv_read", "attn", "attn_out")


def read(facts):
    d = facts["delta"]
    ms = scope_times.scope_ms(facts, "decode_step",
                              lambda scope: scope in SCOPES)
    if not ms or not d.get("steps"):
        return None
    rows = d["tokens_total"] / d["steps"]
    flops, nbytes = facts["reference"].mla_step(
        facts["config"], rows, rows * facts["mean_context"])
    least = roofline_seconds(flops, nbytes, facts["peaks"], facts["chips"])
    return 100.0 * least / (ms * 1e-3)
