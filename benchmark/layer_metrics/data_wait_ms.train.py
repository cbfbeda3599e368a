"""Harness: what a step waited for its batch, from StepPhaseProfiler's
`data_wait` phase over the window's steps (traced run only)."""


def read(facts):
    steps = facts.get("phase_steps")
    if not steps:
        return None
    return facts["phase_seconds"].get("data_wait", 0.0) / steps * 1e3
