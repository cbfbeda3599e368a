"""Kernels (the decode-step program as one unit): the least time the
chip could take for what one step must do — weights read once, the live
K/V cells of the active slots read once, one cell written per slot;
counted from live lengths, not max_ctx — over the decode-step program's
device time per execution. Of the expert layers' held experts it counts
those the window's rows chose, a step, as the program counted them
(`moe_experts_hit`, the count `moe_roofline.serve` takes); a program
with no such counter is counted as before."""

from benchmark.roofline import roofline_seconds


def read(facts):
    prog = facts["trace"]["programs"].get(
        facts["config"]["programs"]["decode_step"])
    d = facts["delta"]
    if not prog or not prog["n"] or not d["steps"]:
        return None
    ref, cfg, ctx = facts["reference"], facts["config"], facts["mean_context"]
    active = d["tokens_total"] / d["steps"]
    counted = {} if "moe_experts_hit" not in d \
        else {"experts_hit": d["moe_experts_hit"] / d["steps"]}
    least = roofline_seconds(active * ref.flops_per_token(cfg, ctx),
                             ref.decode_step_bytes(cfg, active * ctx, active,
                                                   **counted),
                             facts["peaks"], facts["chips"])
    return 100.0 * least / prog["median_s"]
