"""Kernels: over the window's decode steps and expert layers, the held
experts whose weights the expert layer read, over all it holds (1: a
product that reads every held expert whatever the routing; on the hit
list, the share some active row chose). Nothing where the program does
not count what it read (`moe_experts_read`)."""


def read(facts):
    d, cfg = facts["delta"], facts["config"]
    if d.get("moe_experts_read") is None or not d.get("steps"):
        return None
    n_moe = int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])
    held = len(cfg["experts_held"])
    return d["moe_experts_read"] / (held * n_moe * d["steps"])
