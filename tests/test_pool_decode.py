"""The decode step of the models whose page layers read the pool
themselves (`decode_from_pool`: LFM2's, Laguna's full and Granite's
attention layers): one step program at the whole window, compiled in
the warm-up and never again under churn, however the chunk's ladder of
widths is walked, and every page it reads a live one."""

import numpy as np
import pytest

from deeplearning4j_tpu.engine.decode_program import DecodeProgram
from deeplearning4j_tpu.serving.continuous import (
    DecodeEngine,
    sequential_decode,
)

pytestmark = pytest.mark.serving

SLOTS, PAGE, CTX = 3, 16, 1024


def _program(which):
    from deeplearning4j_tpu.zoo import (
        MambaMoETransformer,
        ShortConvMoETransformer,
        WindowMoETransformer,
    )

    cls = {"lfm2": ShortConvMoETransformer, "laguna": WindowMoETransformer,
           "granite": MambaMoETransformer}[which]
    prog = DecodeProgram(cls(max_ctx=CTX, seed=5).init(), max_slots=SLOTS,
                         page_size=PAGE)
    prog.warmup(prog.init_kv())
    return prog


@pytest.mark.parametrize("which", ["lfm2", "laguna", "granite"])
def test_one_step_program_reads_the_live_pages_under_churn(which):
    """Staggered requests whose prompts end on both sides of the
    ladder's first width (512 positions), over 3 slots: the chunk runs
    at both widths, every step at the whole window; nothing compiles
    after the warm-up (`compiles_in_window` 0); the pages counted as
    read are the live ones; and every stream is its solo decode's."""
    prog = _program(which)
    assert prog.from_pool
    assert prog.widths == (32, 64)
    assert prog.step_widths == (prog.pages_per_slot,) == (64,)
    counts = prog.model._jit_cache.trace_counts()
    assert [k for k in counts if "decode_step" in k] == [
        str(prog.decode_key())]
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, 64, n).tolist(), 5)
            for n in (9, 530, 40, 700, 3)]
    d0 = prog.trace_stats()["dispatches"]
    eng = DecodeEngine(program=prog, queue_limit=16)
    handles, steps = [], 0
    for i in range(3 * len(reqs)):
        if i % 3 == 0:
            handles.append(eng.submit(*reqs[i // 3]))
        eng.step_once()
        steps += 1
    while any(not h.done for h in handles):
        eng.step_once()
        steps += 1
        assert steps < 1000, "engine made no progress"
    assert prog.model._jit_cache.trace_counts() == counts
    st = eng.stats()
    assert st["kv_pages_gathered"] == st["kv_pages_live"] > 0
    d1 = st["dispatches"]
    assert set(d1["step_by_width"]) == {64}
    assert d1["step"] - d0["step"] == st["steps"]
    assert all(d1["chunk_by_width"][w] > d0["chunk_by_width"][w]
               for w in prog.widths)
    got = [h.result(timeout_s=0) for h in handles]
    assert got == [sequential_decode(prog, p, n)[1] for p, n in reqs]
    # ids of a ladder width narrower than the step's are padded with
    # scratch: the same stream, and nothing compiled
    p, n = reqs[2]
    assert sequential_decode(prog, p, n, width=prog.widths[0])[1] == got[2]
    assert prog.model._jit_cache.trace_counts() == counts
