"""Scheduler: prefill chunk dispatches per decode step, window deltas
(the engine allows `max_prefills_per_step`)."""


def read(facts):
    d = facts["delta"]
    if not d["steps"]:
        return None
    return d["prefill_chunks"] / d["steps"]
