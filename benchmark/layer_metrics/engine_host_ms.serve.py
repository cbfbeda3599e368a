"""Scheduler: mean over the window's engine steps of the time inside
`DecodeEngine.step_once` outside its `fetch` phase (the host blocked on
the device): sweep, admit, prepare_cells, tables, dispatch, harvest,
emit and journal of the engine's step records."""

from benchmark import timeline


def read(facts):
    return timeline.analysis(facts).get("engine_host_ms")
