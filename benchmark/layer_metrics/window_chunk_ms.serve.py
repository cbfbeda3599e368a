"""Program: what a prefill chunk adds to the turn it rides in, read from
the program's own clock over the window itself: the median `harvest` to
`harvest` time of the window's turns whose call dispatched exactly one
chunk and no copy beside a decode step of the window's steady width,
less `window_step_ms.serve` (the same step alone). At the window's own
mix of chunk widths; the log has it by chunk width, and at the mean of
the turns, which is what a sum over the cycle sees. None where either
kind has under `MIN_TURNS` turns (a window whose every turn holds a
chunk cannot part the two), and on records without `work`."""

from benchmark import window_turns
from benchmark.window_turns import CHUNKS, COPIES, WIDTH, median_ms


def read(facts):
    from benchmark.run import log

    found = window_turns.turns(facts)
    width = window_turns.steady_width(found) if found else None
    if width is None:
        return None
    step = median_ms(window_turns.alone(found, width))
    # (seconds, the chunk) of the turns with one chunk and no copy
    ones = [(s, w[CHUNKS][0]) for s, w in found
            if w[WIDTH] == width and len(w[CHUNKS]) == 1 and not w[COPIES]]
    both = median_ms([s for s, _ in ones])
    if both is None:
        return None
    mean = sum(s for s, _ in ones) / len(ones) * 1e3
    log(f"a chunk beside a step of {width} pages adds {both - step:.4f} ms "
        f"to the step's {step:.4f} at the median of its turns, "
        f"{mean - step:.4f} at their mean (what a cycle's sum sees); by the "
        "chunk's own width in pages (turns, ms added, mean pages filled, "
        "mean tokens):")
    for cw in sorted({c[0] for _, c in ones}):
        mine = [(s, c) for s, c in ones if c[0] == cw]
        ms = median_ms([s for s, _ in mine])
        log(f"    {cw:4d}: {len(mine):6d} "
            + (f"{ms - step:9.4f}" if ms is not None else "        -")
            + f" {sum(c[1] for _, c in mine) / len(mine):7.2f}"
            f" {sum(c[2] for _, c in mine) / len(mine):8.1f}")
    return both - step
