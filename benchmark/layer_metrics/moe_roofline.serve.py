"""Kernels: the least time the chip could take for the expert layers of
one decode step — the operations of the token-expert pairs the program
counted on its held experts, plus router and shared expert for every
row; the weights of the held experts that got a token, read once, plus
router and shared expert — over `moe_ms.serve`. The counts are the
window's, a step; the time is the traced slice's."""

from benchmark import scope_times
from benchmark.roofline import roofline_seconds


def read(facts):
    d, cfg = facts["delta"], facts["config"]
    ms = scope_times.moe_ms(facts)
    if not ms or not d.get("steps") or not d.get("moe_assignments"):
        return None
    n_moe = int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])
    steps = d["steps"]
    rows = d["moe_assignments"] / (int(cfg["num_experts_per_tok"]) * n_moe
                                   * steps)
    flops, nbytes = facts["reference"].moe_step(
        cfg, rows, d["moe_assignments_held"] / steps,
        d["moe_experts_hit"] / steps)
    least = roofline_seconds(flops, nbytes, facts["peaks"], facts["chips"])
    return 100.0 * least / (ms * 1e-3)
