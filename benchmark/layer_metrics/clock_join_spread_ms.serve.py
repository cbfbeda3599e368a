"""Harness: how well the traced slice's step records lie on the device
trace's clock: over the slice, the widest less the narrowest difference
between a record's `fetch` end and its decode step's end on the device
(`observability.tracing.clock_offset` through `timeline.join`). Under
0.5 ms the slice's table of idle gaps by host phase stands; None where
the join found no records. The log has the shift the join chose."""

from benchmark import timeline


def read(facts):
    from benchmark.run import log

    clock = timeline.analysis(facts).get("clock")
    if not clock or not clock["n"]:
        return None
    log(f"clock join: {clock['n']} pairs, executions the trace has past "
        f"the slice's last record (`shift`): {clock.get('shift', 'not told')}")
    return clock["spread_ns"] * 1e-6
