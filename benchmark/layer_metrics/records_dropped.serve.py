"""Scheduler: records the program's timeline ring
(`observability.perf.get_timeline()`) has pushed out since the process
began, read at the run's end. Must read 0: the window's phase table and
every reader of step and request records then saw the whole run. None
on a program that does not count them."""


def read(facts):
    from deeplearning4j_tpu.observability import perf

    dropped = getattr(perf, "timeline_dropped", None)
    return None if dropped is None else dropped()
