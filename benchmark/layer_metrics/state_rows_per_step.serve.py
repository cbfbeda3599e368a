"""Scheduler: slot-steps that advanced a per-slot state (decode rows
and the rows prefill chunks absorbed) per decode step, window deltas;
nothing where the engine keeps no state."""


def read(facts):
    d = facts["delta"]
    if not d.get("steps") or "state_rows" not in d:
        return None
    return d["state_rows"] / d["steps"]
