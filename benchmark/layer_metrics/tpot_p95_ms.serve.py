"""Scheduler: the window's time per output token at the 95th percentile,
`(t_last_token - t_first_token) / (tokens - 1)` over the requests
finished in the window, as `DecodeEngine` stamps a handle: the number
`tpot_p95_ms` is end to end, read here in the cells whose steps are so
short that a stall of the host moves it past any bound (the GPT-2 cells:
PERF.md, section 2). None where no request finished in the window."""

import math


def read(facts):
    v = facts["end_to_end"].get("tpot_p95_ms")
    return None if v is None or math.isnan(v) else v
