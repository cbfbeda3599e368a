"""Continuous-batching decode engine (serving/continuous.py +
engine/decode_program.py + zoo/decoder.py).

The load-bearing pins:
  * continuous-batched output is BYTE-IDENTICAL to the sequential
    per-request decode oracle under slot churn — staggered joins and
    leaves, and mid-soak forced evictions (serving.slot_evict chaos);
  * ONE decode compile serves arbitrary join/leave traffic (JitCache
    trace counters: zero new traces after warmup);
  * KV-cache donation is honored (prog-unhonored-donation over the
    decode/prefill ProgramRecords — no silent per-token copy of the
    [n_layers, 2, n_pages, page_size, d_model] buffer);
  * the serving surface: /v1/models/<m>/generate over HTTP on BOTH
    wires (npz with variable-length token outputs, legacy JSON),
    admission 429 + Retry-After on slot exhaustion;
  * decode metrics (dl4j_decode_*) registered/emitted/exposed and the
    dashboard "decode — N slots · tok/s" line.
"""

import random
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.engine.decode_program import (
    DecodeProgram,
    next_pow2,
)
from deeplearning4j_tpu.observability import metrics as _obs
from deeplearning4j_tpu.observability.metrics import (
    REGISTERED_METRICS,
    get_registry,
)
from deeplearning4j_tpu.resilience.errors import (
    QuotaExceededError,
    ServingError,
)
from deeplearning4j_tpu.resilience.faults import (
    REGISTERED_POINTS,
    injector,
)
from deeplearning4j_tpu.serving.continuous import (
    DecodeEngine,
    sequential_decode,
)
from deeplearning4j_tpu.zoo.decoder import CausalTransformer

pytestmark = pytest.mark.serving

VOCAB, CTX, SLOTS, PAGE = 64, 64, 4, 8


@pytest.fixture(scope="module")
def program():
    model = CausalTransformer(vocab_size=VOCAB, d_model=32, n_heads=4,
                              n_layers=2, max_ctx=CTX, seed=3).init()
    prog = DecodeProgram(model, max_slots=SLOTS, page_size=PAGE)
    # serving warmup discipline: compiles land before traffic
    kv = prog.init_kv()
    prog.warmup(kv, buckets=(8, 16, 32))
    return prog


def _requests(n, seed=0, max_prompt=20, max_new=12):
    rng = random.Random(seed)
    return [([rng.randrange(VOCAB)
              for _ in range(rng.randrange(2, max_prompt))],
             rng.randrange(2, max_new)) for _ in range(n)]


def _oracle(program, reqs, eos=None):
    kv = program.init_kv()
    out = []
    for prompt, mx in reqs:
        kv, toks = sequential_decode(program, prompt, mx, eos_id=eos)
        out.append(toks)
    return out


def _drive_churn(program, reqs, stagger=2, eos=None, queue_limit=64,
                 max_prefills_per_step=2, max_steps=2000):
    """Deterministic churn: submit one request every `stagger` engine
    steps (requests join mid-flight, leave on completion) and drive
    `step_once` manually — no loop thread, no timing dependence."""
    eng = DecodeEngine(program=program, queue_limit=queue_limit,
                       max_prefills_per_step=max_prefills_per_step)
    handles = []
    i = 0
    steps = 0
    while i < len(reqs) or any(not h.done for h in handles):
        if i < len(reqs) and steps % stagger == 0:
            prompt, mx = reqs[i]
            handles.append(eng.submit(prompt, mx, eos_id=eos))
            i += 1
        eng.step_once()
        steps += 1
        assert steps < max_steps, "engine made no progress"
    return eng, [h.result(timeout_s=0) for h in handles]


# ===================================================== program shapes
def test_chunk_schedule_is_page_aligned(program):
    """Chunked prefill replaced pow2 prefill buckets, and a chunk is
    the whole pages a token budget holds: with a window of 64 that is
    all 8 pages, so every prompt of this fixture is ONE chunk from
    position 0, whatever the prefix trie covered (`from_token`, always
    page-aligned or total); `block_pages` are the pages it fills.
    (tests/test_decode_paged.py has the schedule where a window holds
    several chunks.)"""
    assert (program.chunk_pages, program.chunk_tokens) == (CTX // PAGE,
                                                           CTX)
    assert program.chunk_starts(1) == [0]
    assert program.chunk_starts(PAGE) == [0]
    assert program.chunk_starts(PAGE + 1) == [0]
    assert program.chunk_starts(CTX) == [0]
    assert program.chunk_starts(21, from_token=PAGE) == [0]
    assert program.block_pages(21, 0) == range(0, 3)
    for n in range(1, CTX + 1):
        assert program.chunk_starts(n) == [0]
        assert program.block_pages(n, 0) == range(0, -(-n // PAGE))
        assert program.state_rows(n, 0) == n - 1
    with pytest.raises(ValueError):
        program.chunk_starts(CTX + 1)
    with pytest.raises(ValueError):
        program.chunk_starts(0)


def test_kv_pool_is_pages_of_token_rows(program):
    """The physical pool: [n_layers, 2, n_pages, page_size, n_heads *
    head_dim] — page-major (one page id addresses every layer), a page
    `page_size` consecutive token rows, a row every head of one
    position (the one layout the chip's compiler works in: PERF.md,
    PR 29). Default n_pages matches the PR 15 contiguous per-slot HBM
    budget + the scratch page."""
    m = program.model
    assert program.pages_per_slot == CTX // PAGE
    assert program.n_pages == SLOTS * program.pages_per_slot + 1
    assert program.kv_shape == (m.n_layers, 2, program.n_pages, PAGE,
                                m.n_heads * m.head_dim)
    assert program.init_kv().shape == program.kv_shape
    assert m.kv_page_axis == 2 and m.kv_dtype == np.float32


def test_window_pages_ring_order(program):
    """Host-side virtual->physical translation: the slot's page ids in
    ring order, so cell c = ring * page_size + offset of the gathered
    window holds the position congruent to c modulo the window — the
    single reduction-order definition the bitwise contract rests on —
    and ring entries with no live cell park on scratch."""
    from deeplearning4j_tpu.engine.decode_program import SCRATCH_PAGE

    pps = program.pages_per_slot
    table = [10 + r for r in range(pps)]

    def position_of(cell, pos):
        # the newest position <= pos that the ring keeps in `cell`
        return pos - (pos - cell) % CTX

    # mid-fill: positions 0..20 live — logical order, cell c holds
    # position c, and the pages past the newest cell are scratch
    ids = program.window_pages(table, 20)
    assert ids.shape == (pps,) and ids.dtype == np.int32
    live = 21
    assert [position_of(c, 20) for c in range(live)] == list(range(21))
    assert list(ids[:3]) == [10 + (q // PAGE) % pps for q in (0, 8, 16)]
    assert set(ids[3:]) == {SCRATCH_PAGE}
    # wrapped: position CTX + 3 — every cell live, the table as it is;
    # the window is the last CTX positions, rotated: cells 0..3 hold
    # the newest four, cell 4 the oldest RETAINED position
    ids = program.window_pages(table, CTX + 3)
    assert list(ids) == table
    held = [position_of(c, CTX + 3) for c in range(CTX)]
    assert sorted(held) == list(range(4, CTX + 4))
    assert held[:5] == [CTX, CTX + 1, CTX + 2, CTX + 3, 4]
    assert all(ids[c // PAGE] == table[(q // PAGE) % pps]
               for c, q in enumerate(held))
    # nothing live yet (the first chunk's prior context)
    ids = program.window_pages(table, -1)
    assert set(ids) == {SCRATCH_PAGE}
    # a live position's page must be mapped
    with pytest.raises(TypeError):
        program.window_pages([None] * pps, 3)


def test_sequential_oracle_contract(program):
    _, toks = sequential_decode(program, [5, 9, 11], 6)
    assert len(toks) == 6
    assert all(0 <= t < VOCAB for t in toks)
    # eos cuts the sequence at its FIRST occurrence and IS included
    eos = toks[3]
    expect = toks[:toks.index(eos) + 1]
    _, cut = sequential_decode(program, [5, 9, 11], 6, eos_id=eos)
    assert cut == expect and cut[-1] == eos


# ============================================= byte-identity under churn
def test_continuous_matches_oracle_under_staggered_churn(program):
    """THE correctness bar: staggered joins/leaves over 4 slots, every
    request's output bitwise equal to its solo sequential decode."""
    reqs = _requests(12, seed=1)
    oracle = _oracle(program, reqs)
    eng, got = _drive_churn(program, reqs, stagger=2)
    assert got == oracle
    stats = eng.stats()
    assert stats["completed"] == len(reqs)
    assert stats["tokens_total"] == sum(len(t) for t in oracle)
    assert stats["active_slots"] == 0 and stats["pending"] == 0


def test_churn_with_eos_leaves_match_oracle(program):
    """EOS leaves (variable-length outputs) under churn: pick an eos
    id that actually occurs so streams leave early."""
    reqs = _requests(8, seed=2)
    free_run = _oracle(program, reqs)
    eos = free_run[0][-1]
    oracle = _oracle(program, reqs, eos=eos)
    assert any(len(a) < len(b) for a, b in zip(oracle, free_run))
    _, got = _drive_churn(program, reqs, stagger=3, eos=eos)
    assert got == oracle


def test_one_decode_compile_serves_join_leave_traffic(program):
    """The compile-once pin: after warmup, arbitrary join/leave
    traffic advances ZERO JitCache trace counters — requests joining
    and leaving slots is data, never a recompile."""
    reqs = _requests(10, seed=3)
    before = program.model._jit_cache.trace_counts()
    _oracle(program, reqs)
    _drive_churn(program, reqs, stagger=1)
    after = program.model._jit_cache.trace_counts()
    assert after == before
    key = str(program.decode_key())
    assert after[key] == 1


# ========================================================= eviction chaos
@pytest.mark.chaos
def test_slot_eviction_drill_byte_identical(program):
    """serving.slot_evict: a forced mid-generation eviction re-prefills
    the request on a free slot and replays its recorded tokens through
    the shared decode loop — output byte-identical to the never-evicted
    oracle, eviction counted on the handle and the engine."""
    reqs = _requests(10, seed=4)
    oracle = _oracle(program, reqs)
    inj = injector()
    inj.inject("serving.slot_evict", mode="raise", at_hit=6, times=1)
    inj.inject("serving.slot_evict", mode="raise", at_hit=14, times=2)
    eng, got = _drive_churn(program, reqs, stagger=2)
    assert got == oracle
    assert eng.stats()["evictions"] == 3
    assert injector().hits("serving.slot_evict") > 0


@pytest.mark.chaos
def test_eviction_storm_mid_soak_still_byte_identical(program):
    """Eviction storm: every 5th engine iteration evicts (including
    evictions of streams still REPLAYING a previous eviction) — the
    recovery composes, output stays byte-identical."""
    reqs = _requests(8, seed=5, max_prompt=16, max_new=10)
    oracle = _oracle(program, reqs)
    inj = injector()
    inj.inject("serving.slot_evict", mode="raise", at_hit=5, times=1)
    inj.inject("serving.slot_evict", mode="raise", at_hit=10, times=1)
    inj.inject("serving.slot_evict", mode="raise", at_hit=15, times=1)
    inj.inject("serving.slot_evict", mode="raise", at_hit=20, times=1)
    inj.inject("serving.slot_evict", mode="raise", at_hit=25, times=1)
    eng, got = _drive_churn(program, reqs, stagger=2, max_steps=4000)
    assert got == oracle
    assert eng.stats()["evictions"] == 5


# ===================================================== streaming + admission
def test_streaming_accumulation_mid_generation(program):
    """Per-token accumulation is readable mid-flight: tokens_so_far
    grows step by step; wait_for_tokens unblocks at the threshold."""
    eng = DecodeEngine(program=program)
    h = eng.submit([1, 2, 3, 4], max_new_tokens=8)
    assert h.tokens_so_far() == []
    # one engine iteration = admit + chunk-prefill the short prompt +
    # the uniform first-token decode dispatch — a join on a one-page
    # prompt has its first token dispatched by the call that admits
    # it, and harvested by the next (the engine runs one step ahead)
    eng.step_once()
    assert h.tokens_so_far() == []
    eng.step_once()
    assert len(h.tokens_so_far()) == 1
    eng.step_once()
    assert len(h.tokens_so_far()) == 2
    eng.step_once()
    assert len(h.tokens_so_far()) == 3
    got_then = h.tokens_so_far()
    while not h.done:
        eng.step_once()
    final = h.result(timeout_s=0)
    assert final[:3] == got_then and len(final) == 8
    assert h.finish_reason == "length"
    assert h.wait_for_tokens(3, timeout_s=0.1) == final


def test_submit_validation_and_slot_exhaustion_429(program):
    eng = DecodeEngine(program=program, queue_limit=1)
    with pytest.raises(ValueError):
        eng.submit([], 4)
    with pytest.raises(ValueError):
        eng.submit([1], 0)
    with pytest.raises(ValueError):
        eng.submit([1] * (CTX + 1), 4)   # prompt exceeds the window
    # capacity = max_slots resident + queue_limit waiting; the engine
    # is not stepping, so submissions pile up deterministically
    for _ in range(SLOTS + 1):
        eng.submit([1, 2], 4)
    with pytest.raises(QuotaExceededError) as ei:
        eng.submit([1, 2], 4)
    assert ei.value.retry_after_s > 0
    # draining the queue frees capacity again
    while eng._in_flight():
        eng.step_once()
    eng.submit([1, 2], 4)
    # generation PAST the window is legal now — ring wrap recycles
    # the slot's oldest pages (no prompt+max_new cap)
    eng.submit([1] * 10, CTX)
    while eng._in_flight():
        eng.step_once()


def test_admission_controller_fronts_the_engine(program):
    from deeplearning4j_tpu.serving import (
        AdmissionController,
        TenantConfig,
    )

    adm = AdmissionController(
        {"metered": TenantConfig("metered", rate=0.1, burst=1.0)})
    eng = DecodeEngine(program=program, admission=adm)
    eng.submit([1, 2], 2, tenant="metered")       # burst token
    with pytest.raises(QuotaExceededError):
        eng.submit([1, 2], 2, tenant="metered")   # bucket empty -> 429
    eng.submit([1, 2], 2, tenant="unmetered")     # default rides on


def test_engine_loop_thread_lifecycle(program):
    eng = DecodeEngine(program=program)
    eng.start()
    assert eng.running
    h = eng.generate([3, 1, 4, 1, 5], max_new_tokens=6, timeout_s=30.0)
    assert len(h.result(timeout_s=0)) == 6
    # stop() fails whatever is still queued, loudly
    eng2 = DecodeEngine(program=program, queue_limit=8)
    stuck = eng2.submit([1, 2, 3], 4)
    eng2.stop()
    with pytest.raises(Exception):
        stuck.result(timeout_s=0)
    eng.stop()
    assert not eng.running


# ============================================================= HTTP surface
def test_generate_over_http_npz_json_and_429(program):
    """ModelClient.generate end to end: npz wire (variable-length
    int32 token payload), JSON wire parity, oracle parity, /status
    decode facts, and 429 + Retry-After on slot exhaustion."""
    from deeplearning4j_tpu.parallel.serving import (
        ModelClient,
        ModelServer,
    )

    eng = DecodeEngine(program=program, queue_limit=0)
    server = ModelServer(port=0, decode_engine=eng,
                         model_name="decoder").start()
    try:
        client = ModelClient(f"http://127.0.0.1:{server.port}",
                             breaker=None)
        prompt = [5, 9, 11, 2, 7]
        resp = client.generate(prompt, max_new_tokens=6,
                               model="decoder")
        _, oracle = sequential_decode(program, prompt, 6)
        assert resp["tokens"] == oracle
        assert resp["finish_reason"] == "length"
        jclient = ModelClient(f"http://127.0.0.1:{server.port}",
                              wire="json", breaker=None)
        jresp = jclient.generate(prompt, max_new_tokens=6,
                                 model="decoder")
        assert jresp["tokens"] == oracle
        # variable-length wire: an eos id cuts the returned array at
        # its first occurrence
        eos = oracle[2]
        expect = oracle[:oracle.index(eos) + 1]
        cut = client.generate(prompt, max_new_tokens=6, eos_id=eos,
                              model="decoder")
        assert cut["tokens"] == expect and len(cut["tokens"]) < 6
        assert cut["finish_reason"] == "eos"
        facts = client.status()
        assert facts["decode"]["decoder"]["completed"] >= 3
        assert facts["decode"]["decoder"]["max_slots"] == SLOTS
        # page-table occupancy replaced the misleading per-slot
        # max_ctx capacity: /status reports the real pool state
        pages = facts["decode"]["decoder"]["pages"]
        assert pages["total"] == SLOTS * (CTX // PAGE)
        assert 0 <= pages["free"] <= pages["total"]
        assert "max_ctx" not in facts["decode"]["decoder"]
        assert facts["decode"]["decoder"]["window"] == CTX
        # slot exhaustion: stop the loop, queue a long generation per
        # slot (queue_limit=0 -> capacity == max_slots; a stopped
        # engine holds them pending deterministically), then one more
        # request must bounce 429 with Retry-After — the handler's
        # lazy restart races 4x40 sequential decode dispatches and
        # always loses
        eng.stop()
        slow = [eng.submit([1, 2, 3], 40) for _ in range(SLOTS)]
        # a no-retry client: the default Retry treats 429 as "try
        # again later" and would paper over the shed once slots free
        from deeplearning4j_tpu.resilience.retry import Retry

        oneshot = ModelClient(f"http://127.0.0.1:{server.port}",
                              breaker=None,
                              retry=Retry(max_attempts=1))
        with pytest.raises(ServingError) as ei:
            oneshot.generate(prompt, max_new_tokens=4, model="decoder")
        assert ei.value.status == 429
        assert ei.value.retry_after_s is not None
        assert ei.value.error_class == "QuotaExceededError"
        for h in slow:
            h.result(timeout_s=30.0)
        # capacity restored
        ok = client.generate(prompt, max_new_tokens=4, model="decoder")
        assert len(ok["tokens"]) == 4
        # unknown model -> 404
        with pytest.raises(ServingError) as e404:
            client.generate(prompt, max_new_tokens=2, model="absent")
        assert e404.value.status == 404
    finally:
        server.stop()
    # the server started the engine lazily, so it must stop it too
    assert not eng.running


# ================================================== metrics + dashboard
def test_decode_metrics_registered_and_emitted(program):
    """The decode metric domain, pinned like every other domain:
    dl4j_decode_active_slots, dl4j_decode_tokens_total,
    dl4j_decode_tokens_per_s, dl4j_decode_prefill_chunks_total,
    dl4j_decode_prefill_pages_total, dl4j_decode_slot_evictions_total
    registered; traffic emits them;
    the fault point serving.slot_evict is registered. (PR 26 took
    dl4j_decode_prefill_seconds away: it timed an asynchronous
    dispatch; chunks are counted, and their device time is the device
    trace's.)"""
    names = {"dl4j_decode_active_slots", "dl4j_decode_tokens_total",
             "dl4j_decode_tokens_per_s",
             "dl4j_decode_prefill_chunks_total",
             "dl4j_decode_prefill_pages_total",
             "dl4j_decode_slot_evictions_total"}
    assert "dl4j_decode_prefill_seconds" not in REGISTERED_METRICS
    assert names <= set(REGISTERED_METRICS)
    assert "serving.slot_evict" in REGISTERED_POINTS
    reg = get_registry()
    tokens_before = reg.counter_value("dl4j_decode_tokens_total")
    chunks_before = reg.counter_value(
        "dl4j_decode_prefill_chunks_total")
    pages_before = reg.counter_value("dl4j_decode_prefill_pages_total")
    evicts_before = reg.counter_value(
        "dl4j_decode_slot_evictions_total")
    reqs = _requests(4, seed=6)
    injector().inject("serving.slot_evict", mode="raise", at_hit=4)
    eng, got = _drive_churn(program, reqs, stagger=2)
    emitted = sum(len(t) for t in got)
    assert reg.counter_value("dl4j_decode_tokens_total") \
        == tokens_before + emitted
    assert reg.counter_value("dl4j_decode_slot_evictions_total") \
        == evicts_before + 1
    assert reg.counter_value("dl4j_decode_prefill_chunks_total") \
        == chunks_before + eng.stats()["prefill_chunks"]
    assert reg.counter_value("dl4j_decode_prefill_pages_total") \
        == pages_before + eng.stats()["prefill_pages"]
    assert eng.stats()["prefill_pages"] >= eng.stats()["prefill_chunks"]
    snap = reg.snapshot()
    assert "dl4j_decode_prefill_seconds" not in snap["histograms"]
    gauges = snap["gauges"]
    assert "dl4j_decode_active_slots" in gauges
    assert "dl4j_decode_tokens_per_s" in gauges


def test_dashboard_decode_line(program):
    from deeplearning4j_tpu.stats.dashboard import telemetry_lines

    snapshot = {
        "counters": {"dl4j_decode_tokens_total": {(): 420.0},
                     "dl4j_decode_slot_evictions_total": {(): 2.0}},
        "gauges": {"dl4j_decode_active_slots": {(): 3.0},
                   "dl4j_decode_tokens_per_s": {(): 123.4}},
        "histograms": {},
    }
    lines = telemetry_lines(snapshot)
    decode = [l for l in lines if l.startswith("decode — ")]
    assert decode == [
        "decode — 3 slots · 123.4 tok/s · 420 tokens · 2 evictions"]
    # paged-KV extension: prefix-hit rate (trie pages vs pages the
    # chunks filled; a chunk fills several, so its dispatches do not
    # enter) and pool headroom join the line when the metrics move
    snapshot["counters"]["dl4j_decode_prefix_hits_total"] = {(): 30.0}
    snapshot["counters"]["dl4j_decode_prefill_chunks_total"] = {
        (): 2.0}
    snapshot["counters"]["dl4j_decode_prefill_pages_total"] = {
        (): 10.0}
    snapshot["gauges"]["dl4j_decode_pages_free"] = {(): 7.0}
    decode = [l for l in telemetry_lines(snapshot)
              if l.startswith("decode — ")]
    assert decode == [
        "decode — 3 slots · 123.4 tok/s · 420 tokens · 2 evictions"
        " · prefix hit 75% · 7 pages free"]
    # absent domain -> no line
    assert not [l for l in telemetry_lines({"counters": {}})
                if l.startswith("decode")]


def test_metrics_exposed_on_http_scrape(program):
    from deeplearning4j_tpu.parallel.serving import (
        ModelClient,
        ModelServer,
    )

    eng = DecodeEngine(program=program)
    server = ModelServer(port=0, decode_engine=eng,
                         model_name="decoder").start()
    try:
        client = ModelClient(f"http://127.0.0.1:{server.port}",
                             breaker=None)
        client.generate([2, 4, 6], max_new_tokens=3, model="decoder")
        text = client.metrics_text()
        assert "dl4j_decode_tokens_total" in text
        assert "dl4j_decode_prefill_chunks_total" in text
    finally:
        server.stop()


# ============================================================ program lint
@pytest.mark.analysis
def test_program_lint_decode_records_clean():
    """The decode/prefill programs join the --programs representative
    set CLEAN — in particular prog-unhonored-donation proves the
    [n_layers, 2, n_pages, page_size, d_model] KV pool is genuinely
    aliased in-place (a silent copy would double decode memory and pay
    a full-cache copy per token), and prog-transpose-churn stays quiet
    on the token-row layout."""
    from deeplearning4j_tpu.analysis import program_lint
    from deeplearning4j_tpu.analysis.programs import _decode_records

    records = _decode_records()
    names = {r.name for r in records}
    assert any(n.startswith("decode_step_s") for n in names)
    assert any(n.startswith("decode_prefill_c") for n in names)
    assert "decode_page_copy" in names
    # donation of the physical pool is DECLARED on every record, so
    # prog-unhonored-donation checks the executable alias map
    assert all(r.donate_argnums for r in records)
    findings = program_lint.run(records)
    assert findings == [], "; ".join(f.render() for f in findings)


def test_pool_is_gathered_by_whole_pages():
    """The structural pin on the lowered decode and chunk programs:
    every gather from the page pool takes slices of one whole
    [page_size, n_heads * head_dim] page of one layer's K or V plane —
    one address a page — and none takes a single row (the per-cell
    gather was bound by its 524,288 addresses a layer, not by its
    bytes: PERF.md)."""
    import re

    import jax

    model = CausalTransformer(vocab_size=VOCAB, d_model=32, n_heads=4,
                              n_layers=2, max_ctx=CTX, seed=3).init()
    prog = DecodeProgram(model, max_slots=SLOTS, page_size=PAGE)
    pool_type = "x".join(map(str, prog.kv_shape)) + "xf32"
    page = (1, 1, 1, PAGE, model.n_heads * model.head_dim)
    gather = re.compile(
        r'"stablehlo\.gather".*slice_sizes = array<i64: ([0-9, ]+)>'
        r".* : \(tensor<" + pool_type + ">")
    step, chunk, _ = prog.lint_records()
    # a chunk only parks K/V: its last layer's attention feeds nothing
    # and is traced away with its two reads
    for rec, reads in ((step, 2 * model.n_layers),
                       (chunk, 2 * (model.n_layers - 1))):
        text = jax.jit(rec.fn, donate_argnums=rec.donate_argnums).lower(
            *rec.example_args).as_text()
        sizes = [tuple(int(n) for n in m.group(1).split(","))
                 for m in map(gather.search, text.splitlines()) if m]
        # K and V of every layer, and nothing else reads the pool
        assert len(sizes) == reads, (rec.name, sizes)
        assert set(sizes) == {page}, (rec.name, sizes)


@pytest.mark.parametrize("shape", ["decode", "chunk"])
def test_cache_contractions_match_plain_per_head_attention(shape):
    """Both cache contractions run over token rows with the heads
    merged (a block-diagonal query, the block diagonal of the weighted
    rows); held here to softmax attention a head at a time in float64
    numpy, through the model's own scatter and gather. Every cell that
    is not live — the newest page's tail, the scratch page the dead
    ring entries point at — holds NaN, so a dead cell that reaches a
    contraction, or a lane of another head's that is not multiplied
    by an exact zero, fails it."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.attention import (
        chunk_prefill_attention,
        paged_decode_attention,
    )

    h, d, ps, p = 4, 8, PAGE, 4
    model = CausalTransformer(vocab_size=VOCAB, d_model=h * d, n_heads=h,
                              n_layers=1, max_ctx=p * ps)
    rng = np.random.default_rng(7)
    lives = [1, 13, p * ps] if shape == "decode" else [13]
    pool = jnp.full(model.kv_shape(1 + len(lives) * p, ps), np.nan,
                    np.float32)
    page_ids = np.zeros((len(lives), p), np.int32)     # 0 is scratch
    cached = []
    for s, live in enumerate(lives):
        n = -(-live // ps)
        # the slot's pages in an order that is not the pool's
        page_ids[s, :n] = 1 + s * p + rng.permutation(p)[:n]
        k, v = rng.standard_normal((2, live, h, d)).astype(np.float32)
        cells = np.arange(live)
        pool = model.write_cells(pool, 0, (k, v),
                                 page_ids[s, cells // ps], cells % ps)
        cached.append((k, v))

    def plain(q, k, v):
        """q [H, D] over keys and values [N, H, D], a head at a time."""
        q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
        out = np.empty((h, d))
        for i in range(h):
            sc = k[:, i] @ q[i] / np.sqrt(d)
            w = np.exp(sc - sc.max())
            out[i] = (w / w.sum()) @ v[:, i]
        return out.reshape(h * d)

    if shape == "decode":
        q = rng.standard_normal((len(lives), h, d)).astype(np.float32)
        got = paged_decode_attention(
            q, *model.read_window(pool, 0, page_ids), np.asarray(lives))
        want = [plain(q[s], *cached[s]) for s in range(len(lives))]
    else:
        q, k, v = rng.standard_normal((3, ps, h, d)).astype(np.float32)
        got = chunk_prefill_attention(
            q, k, v, *model.read_window(pool, 0, page_ids[0]), lives[0])
        pk, pv = cached[0]
        want = [plain(q[t], np.concatenate([pk, k[:t + 1]]),
                      np.concatenate([pv, v[:t + 1]])) for t in range(ps)]
    got = np.asarray(got)
    assert got.shape == np.shape(want)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-5


def test_decode_records_in_default_program_set():
    """The representative set build includes the decode family (the
    CLI's --programs mode lints them on every sweep)."""
    import ast
    import pathlib

    import deeplearning4j_tpu

    src = (pathlib.Path(deeplearning4j_tpu.__file__).parent
           / "analysis" / "programs.py").read_text()
    tree = ast.parse(src)
    build = next(n for n in ast.walk(tree)
                 if isinstance(n, ast.FunctionDef)
                 and n.name == "build_default_records")
    called = {c.func.id for c in ast.walk(build)
              if isinstance(c, ast.Call)
              and isinstance(c.func, ast.Name)}
    assert "_decode_records" in called
