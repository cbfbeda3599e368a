"""Program: over the window's decode steps and expert layers, the most
loaded held expert's token count over the mean of the held experts'
(1: even; 16: every pair on one expert). The step waits for its most
loaded expert once the experts are compute-bound."""


def read(facts):
    d = facts["delta"]
    if not d.get("moe_assignments_held"):
        return None
    held = len(facts["config"]["experts_held"])
    return d["moe_max_held_load"] * held / d["moe_assignments_held"]
