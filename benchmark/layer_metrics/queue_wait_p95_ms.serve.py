"""Scheduler: 95th percentile of `t_placed - t_submit` over the request
records of the requests submitted in the window: the part of TTFT spent
waiting for a slot. The log gives placement to first token beside it."""

from benchmark import timeline


def read(facts):
    return timeline.analysis(facts).get("queue_wait_p95_ms")
