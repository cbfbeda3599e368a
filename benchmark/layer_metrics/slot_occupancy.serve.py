"""Scheduler: tokens emitted over decode steps x slots, window deltas."""


def read(facts):
    d = facts["delta"]
    if not d["steps"]:
        return None
    return 100.0 * d["tokens_total"] / (d["steps"] * facts["max_slots"])
