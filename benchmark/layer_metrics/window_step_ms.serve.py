"""Program: the device's time for a decode step, read from the program's
own clock over the window itself (`benchmark/window_turns.py`: a TURN
is the time from a step record's `harvest` mark to the next record's,
which with a step always in flight is the device's time for the `work`
the record says its call dispatched). The value is the median over the
window's turns whose call ran ahead and dispatched a decode step ALONE
(no chunk, no copy) at the window's steady width: the widest it ran
alone in `MIN_TURNS` turns or more, which the log names. None where no
width has that many, and on a program whose records carry no `work`.
The log has the same median for every kind of turn the window ran: step
width, chunks and copies beside it, with the count and the mean rows
and live pages, and the records' sums beside the window's counters.
`window_chunk_ms.serve` reads the same turns."""

from benchmark import window_turns


def read(facts):
    from benchmark.run import log

    found = window_turns.turns(facts)
    width = window_turns.steady_width(found) if found else None
    if width is None:
        return None
    step = window_turns.median_ms(window_turns.alone(found, width))
    log(f"a decode step alone at the window's steady width of {width} "
        f"pages: {step:.4f} ms")
    return step
