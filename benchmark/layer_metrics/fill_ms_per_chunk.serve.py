"""Scheduler: what a chunk of its prompt costs a request, from the
window's request records: the median over the requests submitted in the
window of `t_first_token - t_placed` over the chunks dispatched for it,
in ms. One chunk rides in a turn (`max_prefills_per_step`), so it is the
turns a request's chunk waits for among the other slots' chunks, times
the turn, with the first token's own step spread over them; with
`queue_wait_p95_ms.serve` it is what a TTFT is made of. None under
`MIN_TURNS` requests that had a chunk, and on a program whose request
records do not carry the count. The log has the TTFT, the wait for
placement and the filling by chunks dispatched and pages the trie
mapped, with the mean prompt tokens of each kind."""

import numpy as np

from benchmark import window_turns
from benchmark.window_turns import PAGES_MAPPED, PROMPT_TOKENS, REQUEST_CHUNKS

SHOWN = 12      # kinds of request in the log's table, the commonest


def read(facts):
    from benchmark.run import log

    mine = window_turns.requests(facts)
    if mine is None:
        return None
    kinds = {}
    for r in mine:
        kinds.setdefault((r[REQUEST_CHUNKS], r[PAGES_MAPPED]), []).append(r)
    log(f"what the first token of the window's {len(mine)} requests waited "
        f"for, by chunks dispatched and pages the trie mapped (requests, "
        "median ms of TTFT, of the wait for placement, of placement to "
        "first token, mean prompt tokens):")
    for kind in sorted(kinds, key=lambda k: -len(kinds[k]))[:SHOWN]:
        rs = kinds[kind]
        ttft, wait, fill = (np.median([(r[b] - r[a]) * 1e3 for r in rs])
                            for a, b in ((3, 5), (3, 4), (4, 5)))
        log(f"    {kind[0]:3d} {kind[1]:4d}: {len(rs):6d} {ttft:10.3f} "
            f"{wait:10.3f} {fill:10.3f} "
            f"{np.mean([r[PROMPT_TOKENS] for r in rs]):8.1f}")
    return window_turns.median_ms(
        [(r[5] - r[4]) / r[REQUEST_CHUNKS] for r in mine
         if r[REQUEST_CHUNKS]])
