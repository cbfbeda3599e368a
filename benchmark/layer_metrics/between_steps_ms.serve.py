"""Harness: mean over the window's engine steps of the `between_steps`
phase of the engine's step records: from one `step_once`'s return to
the next call, the caller's turn (here the benchmark's clients)."""

from benchmark import timeline


def read(facts):
    return timeline.analysis(facts).get("between_steps_ms")
