"""Scheduler: 99th percentile over the window's engine steps of the
time from one step's `harvest` mark to the next's: the gap a streaming
client sees between two tokens. The log names the longest and the phase
that held it."""

from benchmark import timeline


def read(facts):
    return timeline.analysis(facts).get("step_interval_p99_ms")
