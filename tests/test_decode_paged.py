"""Paged KV virtual memory (serving/continuous.py PagePool/PrefixTrie
+ engine/decode_program.py paged programs).

The load-bearing pins:
  * shared-prefix output is BYTE-IDENTICAL to its unshared twin, and
    the Kth identical prompt skips prefill entirely (zero new chunk
    dispatches);
  * copy-on-write divergence MID-PAGE (a trie-registered partial page
    forked by the owner's first generation write) changes nothing
    byte-wise and is observable via the cow_copies counter;
  * ring wrap past the window is byte-identical to a never-recycling
    contiguous-cache oracle driven over the same compiled step (fresh
    page per block, window gathers only) — recycling a slot's oldest
    page IS sliding-window attention;
  * eviction-replay and cross-replica migration survive against the
    paged cache (with prefix sharing active) byte-identically;
  * refcount EXACTNESS under join/leave/evict churn: PagePool.audit()
    shows zero leaked pages and no double-frees, and pool-pressure
    reclaim (trie LRU eviction, then slot eviction) keeps serving;
  * the paged metrics are registered and emitted:
    dl4j_decode_prefix_hits_total, dl4j_decode_prefix_pages_shared,
    dl4j_decode_pages_free, dl4j_decode_prefill_chunks_total,
    dl4j_decode_prefill_pages_total, dl4j_decode_ctx_wraps_total;
  * a prefill chunk spans the whole pages a token budget holds
    (`chunk_tokens`), aligned on the prompt: the schedule, the bitwise
    contract with trie coverage that ends inside a chunk's block, a
    pool that runs dry in the middle of one. `program`'s window (64)
    is ONE chunk; `blocks` (512 positions, four chunks of 128) and
    `wide` (2,048 at two pages a chunk) give a chunk a prior window
    beside its own rows, as on the chip.
"""

import random

import numpy as np
import pytest

from deeplearning4j_tpu.engine.decode_program import (
    SCRATCH_PAGE,
    DecodeProgram,
)
from deeplearning4j_tpu.observability import metrics as _obs
from deeplearning4j_tpu.observability.metrics import (
    REGISTERED_METRICS,
    get_registry,
)
from deeplearning4j_tpu.resilience.faults import injector
from deeplearning4j_tpu.serving.continuous import (
    DecodeEngine,
    PagePool,
    PrefixTrie,
    sequential_decode,
)
from deeplearning4j_tpu.zoo.decoder import CausalTransformer

pytestmark = pytest.mark.serving

VOCAB, CTX, SLOTS, PAGE = 64, 64, 4, 8


@pytest.fixture(scope="module")
def program():
    model = CausalTransformer(vocab_size=VOCAB, d_model=32, n_heads=4,
                              n_layers=2, max_ctx=CTX, seed=11).init()
    prog = DecodeProgram(model, max_slots=SLOTS, page_size=PAGE)
    prog.warmup(prog.init_kv())
    return prog


def _drain(eng, handles, max_steps=4000):
    steps = 0
    while any(not h.done for h in handles):
        eng.step_once()
        steps += 1
        assert steps < max_steps, "engine made no progress"
    return [h.result(timeout_s=0) for h in handles]


# ==================================================== prefix sharing
def test_shared_prefix_bitwise_and_prefill_skipped(program):
    """N requests with a common prompt: the first computes the pages,
    every later twin MAPS them — byte-identical output, and the Kth
    identical prompt costs ZERO chunk dispatches."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3]
    _, oracle = sequential_decode(program, prompt, 10)

    eng = DecodeEngine(program=program)
    first = eng.submit(prompt, 10)
    _drain(eng, [first])
    chunks_after_first = eng.stats()["prefill_chunks"]
    assert chunks_after_first == len(program.chunk_starts(len(prompt)))
    assert first.result(timeout_s=0) == oracle

    twins = [eng.submit(prompt, 10) for _ in range(3)]
    got = _drain(eng, twins)
    assert got == [oracle] * 3
    s = eng.stats()
    # identical prompts: full trie coverage, zero new chunk dispatches
    assert s["prefill_chunks"] == chunks_after_first
    assert s["prefix_requests_hit"] == 3
    assert s["prefix_hits"] >= 3 * len(program.chunk_starts(len(prompt)))
    assert s["cow_copies"] >= 1  # generation writes forked the tail page


def test_shared_prefix_divergent_tails_bitwise(program):
    """Common system prefix + unique user tails: shared pages serve
    the prefix, chunks only run for the uncovered tail, and every
    stream stays byte-identical to its unshared sequential twin."""
    system = list(range(1, 1 + 2 * PAGE))          # two full blocks
    rng = random.Random(7)
    prompts = [system + [rng.randrange(VOCAB) for _ in range(5 + i)]
               for i in range(4)]
    oracle = [sequential_decode(program, p, 8)[1] for p in prompts]

    eng = DecodeEngine(program=program)
    handles = [eng.submit(p, 8) for p in prompts]
    got = _drain(eng, handles)
    assert got == oracle
    s = eng.stats()
    assert s["prefix_requests_hit"] >= 3     # every twin mapped blocks
    # the shared blocks were filled once, only tails after: a twin's
    # chunk runs its block whole, the two shared pages' rows parked in
    # scratch beside the pages past the prompt's end
    assert s["prefill_chunks"] == len(prompts)
    pages_unshared = sum(-(-len(p) // PAGE) for p in prompts)
    assert s["prefill_pages"] == pages_unshared - s["prefix_hits"]
    assert s["prefill_pages"] * PAGE + s["prefill_rows_padded"] \
        == s["prefill_chunks"] * program.chunk_tokens


def test_cow_divergence_mid_page(program):
    """The CoW pin, mid-page: a prompt whose tail is NOT page-aligned
    registers a partial page in the trie; the owner's FIRST generation
    write lands inside that shared page and must fork it (cow_copies
    moves) without disturbing the twin that mapped it — both streams
    byte-identical to the sequential oracle."""
    prompt = [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4]     # 11 tokens: 8 + 3
    assert len(prompt) % PAGE != 0
    _, oracle = sequential_decode(program, prompt, 9)

    eng = DecodeEngine(program=program)
    a = eng.submit(prompt, 9)
    _drain(eng, [a])
    cow_after_a = eng.stats()["cow_copies"]
    assert cow_after_a >= 1          # a's own write forked the
    #                                  trie-registered partial page
    b = eng.submit(prompt, 9)        # maps the ORIGINAL partial page
    _drain(eng, [b])
    assert a.result(timeout_s=0) == oracle
    assert b.result(timeout_s=0) == oracle
    assert eng.stats()["cow_copies"] > cow_after_a


# ========================================================= ring wrap
def test_ring_wrap_vs_contiguous_window_oracle(program):
    """Drive the SAME compiled step two ways: (a) the engine's ring
    table (pages_per_slot pages recycled in place), (b) a
    never-recycling oracle that takes a FRESH page per logical block
    in a large pool: before a block's first write it `copy_page`s the
    block it displaces from the ring (the positions that block still
    holds inside the window) and maps the fresh page in its place.
    Identical cell values in identical ring order => bitwise equal
    tokens — page recycling IS sliding-window attention."""
    model = program.model
    big = DecodeProgram(model, max_slots=1, page_size=PAGE,
                        n_pages=64)   # never recycles within the run
    big.warmup(big.init_kv())
    prompt = [5, 3, 8, 13, 21, 34, 55, 29, 26, 12]
    n_new = CTX + 25                  # deep into wrap territory
    ps, pps = PAGE, big.pages_per_slot

    # (b) contiguous oracle: logical table grows forever
    kv = big.init_kv()
    logical = {}                      # block index -> physical page
    copies = 0

    def page_for(block):
        """The block's own page, taken at its first write."""
        nonlocal kv, copies
        if block not in logical:
            logical[block] = len(logical) + 1
            if block >= pps:          # it displaces block - pps
                kv = big.copy_page(kv, logical[block - pps],
                                   logical[block])
                copies += 1
        return logical[block]

    def ring_table(pos):
        """Ring entry r -> the page of the newest block <= pos's that
        maps to it."""
        top = pos // ps
        return [logical.get(top - (top - r) % pps) for r in range(pps)]

    for start in big.chunk_starts(len(prompt)):
        wp = [page_for(b) for b in big.block_pages(len(prompt), start)]
        kv = big.prefill_chunk(
            kv, prompt[start:start + big.chunk_tokens], start,
            big.window_pages(ring_table(start), start - 1), wp)
    oracle_toks = []
    pos, tok, suppress = len(prompt) - 1, prompt[-1], True
    while len(oracle_toks) < n_new:
        wp = np.array([SCRATCH_PAGE], np.int32)
        wo = np.zeros(1, np.int32)
        if not suppress:
            wp[0] = page_for(pos // ps)
            wo[0] = pos % ps
        ids = big.window_pages(ring_table(pos), pos)
        kv, nxt, _ = big.step(kv, np.array([tok], np.int32),
                              np.array([pos], np.int32),
                              ids[None], wp, wo)
        tok = int(np.asarray(nxt)[0])
        oracle_toks.append(tok)
        pos += 1
        suppress = False
    assert len(logical) > pps          # the oracle really outgrew a ring
    assert copies == len(logical) - pps

    # (a) the engine: ring table, pages recycled in place
    eng = DecodeEngine(program=big)
    h = eng.submit(prompt, n_new)
    _drain(eng, [h])
    assert h.tokens_so_far() == oracle_toks
    assert eng.stats()["ctx_wraps"] >= 1
    # positions wrapped past the window but the stream finished whole
    assert len(h.tokens_so_far()) == n_new


def _window_forward(params, tokens, n_heads, window, max_ctx):
    """The plain reference: a float32 sliding-window causal decoder
    forward over the whole sequence at once, written with jax.numpy
    alone — position i attends to positions i - window < j <= i, the
    learned positional table wraps with the position. Returns the
    logits of every position."""
    import jax
    import jax.numpy as jnp

    def norm(x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    n = len(tokens)
    at = jnp.arange(n)
    x = params["tok_emb"][jnp.asarray(tokens)] + params["pos_emb"][
        at % max_ctx]
    seen = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None]
                                           - window)
    for lp in params["layers"]:
        h = norm(x, lp["ln1_g"], lp["ln1_b"])
        q, k, v = (jnp.reshape(h @ lp[w], (n, n_heads, -1))
                   for w in ("wq", "wk", "wv"))
        s = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        att = jnp.reshape(jnp.einsum("hij,jhd->ihd", w, v), (n, -1))
        x = x + att @ lp["wo"]
        h = norm(x, lp["ln2_g"], lp["ln2_b"])
        x = x + jax.nn.gelu(h @ lp["w1"] + lp["b1"],
                            approximate=True) @ lp["w2"] + lp["b2"]
    return norm(x, params["lnf_g"], params["lnf_b"]) @ params["tok_emb"].T


@pytest.mark.parametrize("fixture,n_prompt,chunks", [
    ("program", 2 * PAGE + 5, 1),    # three pages, one chunk
    ("blocks", 2 * 128 + 5, 3),      # 33 pages in three chunks of 16
])
def test_engine_matches_plain_sliding_window_forward(request, fixture,
                                                     n_prompt, chunks):
    """Against mathematics, not against the same programs: a run that
    prefills several pages (in `blocks` several chunks, each attending
    the ones before through the gathered window), decodes, and wraps
    the ring emits the tokens a plain float32 sliding-window causal
    forward picks — the page gather in ring order changes where a cell
    sits in the reduction, never which cells are in it."""
    program = request.getfixturevalue(fixture)
    ctx = program.window
    rng = random.Random(41)
    prompt = [rng.randrange(VOCAB) for _ in range(n_prompt)]
    n_new = ctx - n_prompt + 4 * PAGE + 8   # wraps, and recycles pages
    eng = DecodeEngine(program=program)
    h = eng.submit(prompt, n_new)
    toks = _drain(eng, [h])[0]
    st = eng.stats()
    assert st["prefill_chunks"] == chunks and st["ctx_wraps"] >= 2
    assert st["prefill_pages"] == -(-n_prompt // PAGE)
    assert len(toks) == n_new
    model = program.model
    logits = np.asarray(_window_forward(
        model.params, prompt + toks[:-1], model.n_heads, ctx,
        model.max_ctx))[len(prompt) - 1:]
    assert logits.shape == (n_new, VOCAB)
    # the served token's logit lies within 1e-4 of the reference's
    # best at every step, and is the reference's own pick
    best = logits.max(axis=-1)
    served = logits[np.arange(n_new), toks]
    assert float(np.max(best - served)) <= 1e-4
    assert toks == [int(t) for t in logits.argmax(axis=-1)]


def test_kv_page_counters_follow_a_hand_worked_schedule(program):
    """`stats()["kv_pages_gathered"]` counts every page id a decode
    step hands the program (slots x pages_per_slot a step) and
    `["kv_pages_live"]` those off scratch: pages holding a live cell
    of a decoding slot. Worked by hand for two requests."""
    pps = program.pages_per_slot
    eng = DecodeEngine(program=program, prefix_cache=False,
                       max_prefills_per_step=1)
    assert eng.stats()["kv_pages_gathered"] == 0
    assert eng.stats()["kv_pages_live"] == 0
    # A: 10 tokens (2 pages, one chunk), 3 new. Call 1 prefills the
    # chunk and A decodes at position 9 (2 pages live), then 10 and
    # 11. The pages are counted as a step is dispatched, the step as
    # it is harvested, by the call after (the engine runs one step
    # ahead)
    a = eng.submit(list(range(1, 11)), 3)
    for k in (1, 2, 3):
        assert eng.step_once()
        st = eng.stats()
        assert st["steps"] == k - 1
        assert st["kv_pages_gathered"] == k * SLOTS * pps
        assert st["kv_pages_live"] == 2 * k
    assert not a.done
    assert eng.step_once()                    # the drain: step 3's harvest
    assert eng.stats()["steps"] == 3
    assert eng.stats()["kv_pages_gathered"] == 3 * SLOTS * pps
    assert a.done
    # B: 2 * PAGE - 1 tokens, 4 new: positions 14, 15 (2 pages live),
    # then 16, 17 (a third page)
    b = eng.submit(list(range(3, 2 * PAGE + 2)), 4)
    _drain(eng, [b])
    st = eng.stats()
    assert st["steps"] == 7
    assert st["kv_pages_gathered"] == 7 * SLOTS * pps
    assert st["kv_pages_live"] == 6 + 2 + 2 + 3 + 3


# ========================================== durability on paged cache
def test_eviction_replay_with_prefix_sharing(program):
    """serving.slot_evict chaos against the paged cache WITH prefix
    sharing active: evicted requests re-enter through the trie (their
    prompt pages are usually still cached), replay force-feeds the
    recorded tokens, and every stream stays byte-identical."""
    system = list(range(2, 2 + PAGE))
    rng = random.Random(13)
    reqs = [(system + [rng.randrange(VOCAB) for _ in range(3 + i % 5)],
             4 + i % 6) for i in range(8)]
    kv_oracle = [sequential_decode(program, p, mx)[1]
                 for p, mx in reqs]
    inj = injector()
    inj.inject("serving.slot_evict", mode="raise", at_hit=4, times=1)
    inj.inject("serving.slot_evict", mode="raise", at_hit=9, times=1)
    inj.inject("serving.slot_evict", mode="raise", at_hit=14, times=1)
    eng = DecodeEngine(program=program, queue_limit=64,
                       max_prefills_per_step=2)
    handles = []
    for i, (p, mx) in enumerate(reqs):
        handles.append(eng.submit(p, mx))
        eng.step_once()
    got = _drain(eng, handles)
    assert got == kv_oracle
    assert eng.stats()["evictions"] == 3
    audit = eng._pool.audit()
    assert audit["leaked"] == 0 and not audit["double_freed"]


def test_migration_resume_on_paged_cache(program):
    """Cross-replica migration's wire contract (prompt + resume_tokens
    re-prefill + forced replay) lands on the paged cache: the
    continuation is byte-identical to the uninterrupted run, and the
    source engine's pages are fully reclaimed."""
    prompt = [9, 8, 7, 6, 5, 4, 3, 2, 1, 9, 8, 7]
    _, full = sequential_decode(program, prompt, 12)

    src = DecodeEngine(program=program)
    h = src.submit(prompt, 12)
    while len(h.tokens_so_far()) < 5:
        src.step_once()
    partial = h.tokens_so_far()[:5]
    src.stop()
    audit = src._pool.audit()
    assert audit["leaked"] == 0 and not audit["double_freed"]

    dst = DecodeEngine(program=program)
    resumed = dst.submit(prompt, 12, resume_tokens=partial)
    _drain(dst, [resumed])
    assert resumed.result(timeout_s=0) == full


# ================================================ refcount exactness
def test_refcount_exactness_under_churn(program):
    """Join/leave/evict churn with sharing, CoW, and wrap all active:
    after the engine drains, every page is free, trie-referenced, or
    quarantined — zero leaks, zero double-frees — and disabling the
    prefix cache (prefix_cache=False) leaves NOTHING referenced."""
    rng = random.Random(29)
    reqs = [([rng.randrange(VOCAB)
              for _ in range(rng.randrange(2, 3 * PAGE))],
             rng.randrange(2, 14)) for _ in range(12)]
    inj = injector()
    inj.inject("serving.slot_evict", mode="raise", at_hit=7, times=1)

    eng = DecodeEngine(program=program, queue_limit=64)
    handles = []
    for p, mx in reqs:
        handles.append(eng.submit(p, mx))
        eng.step_once()
    _drain(eng, handles)
    audit = eng._pool.audit()
    assert audit["leaked"] == 0 and not audit["double_freed"]
    # every remaining reference is a trie registration (slots are
    # empty), and each registered page holds exactly one trie ref
    assert audit["referenced"] == len(eng._trie)
    for page in list(eng._trie._where):
        assert int(eng._pool.ref[page]) == 1
    # trie teardown releases everything
    eng._trie.clear(eng._pool)
    audit = eng._pool.audit()
    assert audit["referenced"] == 0 and audit["leaked"] == 0

    off = DecodeEngine(program=program, prefix_cache=False,
                       queue_limit=64)
    handles = [off.submit(p, mx) for p, mx in reqs[:6]]
    _drain(off, handles)
    audit = off._pool.audit()
    assert audit["referenced"] == 0 and audit["leaked"] == 0
    assert off.stats()["prefix_requests_hit"] == 0


def test_pool_pressure_reclaims_trie_then_slots(program):
    """A pool too small for every tenant's working set: allocation
    falls back to trie LRU eviction, then to slot eviction (replay) —
    the engine keeps serving, byte-identically, and never leaks."""
    model = program.model
    tight = DecodeProgram(model, max_slots=3, page_size=PAGE,
                          n_pages=3 * (CTX // PAGE) // 2 + 1)
    tight.warmup(tight.init_kv())
    rng = random.Random(31)
    reqs = [([rng.randrange(VOCAB)
              for _ in range(rng.randrange(PAGE, 4 * PAGE))],
             rng.randrange(4, 20)) for _ in range(9)]
    oracle = [sequential_decode(tight, p, mx)[1] for p, mx in reqs]
    eng = DecodeEngine(program=tight, queue_limit=64)
    handles = []
    for p, mx in reqs:
        handles.append(eng.submit(p, mx))
        eng.step_once()
    got = _drain(eng, handles)
    assert got == oracle
    audit = eng._pool.audit()
    assert audit["leaked"] == 0 and not audit["double_freed"]


# ======================================================= unit pieces
def test_page_pool_audit_catches_leak_and_double_free():
    pool = PagePool(6)
    a, b = pool.alloc(), pool.alloc()
    pool.retain(a)
    pool.release(a)
    pool.release(b)
    assert pool.audit()["leaked"] == 0
    assert not pool.audit()["double_freed"]
    pool.release(b)                    # misuse: b re-enters free list
    assert pool.audit()["double_freed"]
    pool2 = PagePool(4)
    pool2.alloc()
    pool2.ref[1] = 0                   # corrupt: referenced page lost
    assert pool2.audit()["leaked"] == 1


def test_prefix_trie_match_register_evict():
    pool = PagePool(12)
    trie = PrefixTrie(page_size=4)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 9]      # 2 blocks + tail
    table = [pool.alloc() for _ in range(3)]
    inserted = trie.register(prompt, table, pool)
    assert inserted == table and len(trie) == 3
    pages, covered = trie.match(prompt)
    assert pages == table and covered == len(prompt)
    # block-aligned prefix of a DIFFERENT prompt shares the blocks
    pages, covered = trie.match([1, 2, 3, 4, 5, 6, 7, 8, 1, 1, 1])
    assert pages == table[:2] and covered == 8
    # a partial page never matches an extension that is not the tail
    pages, covered = trie.match(prompt + [1])
    assert pages == table[:2] and covered == 8
    # eviction is leaf-only: with the slot refs dropped, the tail and
    # then the deepest block go first; the ROOT block holds until last
    for p in table:
        pool.release(p)
    assert trie.evict_lru(pool) and len(trie) == 2
    assert trie.evict_lru(pool) and len(trie) == 1
    assert trie.evict_lru(pool) and len(trie) == 0
    assert not trie.evict_lru(pool)
    assert pool.audit()["leaked"] == 0


def test_trie_purge_quarantines_chains():
    """Purging a mid-chain block (poison) drops the stranded subtree
    and parks trie-only pages in quarantine — never back on the free
    list."""
    pool = PagePool(12)
    trie = PrefixTrie(page_size=2)
    prompt = [1, 2, 3, 4, 5, 6]
    table = [pool.alloc() for _ in range(3)]
    trie.register(prompt, table, pool)
    for p in table:
        pool.release(p)                # trie holds them alone
    trie.purge([table[1]], pool)       # mid-chain: drops table[2] too
    assert len(trie) == 1
    assert table[1] in pool.quarantined
    assert pool.audit()["leaked"] == 0
    assert pool.free_count == (pool.n_pages - 1) - 2 - 1


# ============================================================ metrics
def test_paged_metrics_registered_and_emitted(program):
    for name in ("dl4j_decode_prefix_hits_total",
                 "dl4j_decode_prefix_pages_shared",
                 "dl4j_decode_pages_free",
                 "dl4j_decode_prefill_chunks_total",
                 "dl4j_decode_prefill_pages_total",
                 "dl4j_decode_ctx_wraps_total"):
        assert name in REGISTERED_METRICS
    reg = get_registry()
    reg.reset()
    try:
        eng = DecodeEngine(program=program)
        prompt = [6, 2, 8, 3, 1, 7, 4, 4, 9]
        h1 = eng.submit(prompt, CTX + 10)   # wraps
        h2 = eng.submit(prompt, 4)          # prefix twin
        _drain(eng, [h1, h2])
        assert reg.counter_value(
            "dl4j_decode_prefill_chunks_total") == 1
        assert reg.counter_value(
            "dl4j_decode_prefill_pages_total") == 2
        assert reg.counter_value("dl4j_decode_prefix_hits_total") > 0
        assert reg.counter_value("dl4j_decode_ctx_wraps_total") > 0
        snap = reg.snapshot()
        assert "dl4j_decode_pages_free" in snap["gauges"]
        assert "dl4j_decode_prefix_pages_shared" in snap["gauges"]
    finally:
        reg.reset()


# ============================================ the ladder of window widths
# a window past WINDOW_FLOOR positions: pages of 64, 32 a slot, so the
# programs are compiled at 8, 16 and 32 pages (512, 1,024 and 2,048
# positions)
W_CTX, W_PAGE = 2048, 64
W_WIDTHS = (8, 16, 32)


@pytest.fixture(scope="module")
def wide():
    model = CausalTransformer(vocab_size=VOCAB, d_model=32, n_heads=4,
                              n_layers=2, max_ctx=W_CTX, seed=13).init()
    prog = DecodeProgram(model, max_slots=SLOTS, page_size=W_PAGE)
    prog.warmup(prog.init_kv())
    return prog


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


# a window of FOUR chunks at small pages: 512 positions, a chunk of 128
# tokens = 16 pages of 8 (`blocks`) or 32 of 4 (`blocks4`); one width
B_CTX, B_TOKENS = 512, 128


def _blocks(page_size):
    model = CausalTransformer(vocab_size=VOCAB, d_model=32, n_heads=4,
                              n_layers=2, max_ctx=B_CTX, seed=17).init()
    prog = DecodeProgram(model, max_slots=SLOTS, page_size=page_size)
    assert prog.chunk_tokens == B_TOKENS
    assert prog.chunk_pages == B_TOKENS // page_size
    prog.warmup(prog.init_kv())
    return prog


@pytest.fixture(scope="module")
def blocks():
    return _blocks(PAGE)


@pytest.fixture(scope="module")
def blocks4():
    return _blocks(4)


@pytest.mark.parametrize("max_ctx,page_size,widths", [
    (64, 8, (8,)),                  # every window of 512 positions or
    (512, 16, (32,)),               # less: one width, as before
    (256, 256, (1,)),
    (1024, 16, (32, 64)),           # GPT-2's
    (4096, 128, (4, 8, 16, 32)),    # the latent cell's
    (W_CTX, W_PAGE, W_WIDTHS),
    (2048, 1024, (1, 2)),           # a page longer than the floor
])
def test_ladder_is_powers_of_two_from_the_floor_to_the_window(
        max_ctx, page_size, widths):
    model = CausalTransformer(vocab_size=VOCAB, d_model=8, n_heads=2,
                              n_layers=1, max_ctx=max_ctx)
    model.params = {}               # shapes only: nothing compiles
    prog = DecodeProgram(model, max_slots=2, page_size=page_size)
    assert prog.widths == widths
    assert prog.widths[-1] == prog.pages_per_slot
    for n in range(prog.pages_per_slot + 1):
        w = prog.width_for(n)
        assert w in widths and w >= n
        assert not [v for v in widths if n <= v < w]
    with pytest.raises(ValueError):
        prog.width_for(prog.pages_per_slot + 1)
    # every width has a key of its own; no width means the whole window
    keys = {prog.decode_key(w) for w in widths}
    assert len(keys) == len(widths)
    assert prog.decode_key() == prog.decode_key(widths[-1])
    assert prog.chunk_key() == prog.chunk_key(widths[-1])
    assert {prog.chunk_key(w) for w in widths}.isdisjoint(keys)


@pytest.mark.parametrize("pos,live,width", [
    (-1, 0, 8),             # a chunk at start 0: no prior page
    (0, 1, 8),
    (510, 8, 8), (511, 8, 8),       # the last cell of 8 pages
    (512, 9, 16),                   # the first past them
    (1023, 16, 16), (1024, 17, 32),
    (W_CTX - 1, 32, 32),
    (W_CTX, 32, 32),                # wrapped: every page live
    (W_CTX + 70, 32, 32),
])
def test_window_pages_width_ring_order_and_scratch_padding(wide, pos,
                                                           live, width):
    table = list(range(101, 101 + wide.pages_per_slot))
    assert wide.live_pages(pos) == live
    ids = wide.window_pages(table, pos)
    assert ids.dtype == np.int32 and ids.shape == (width,)
    assert ids[:live].tolist() == table[:live]       # ring order
    assert (ids[live:] == SCRATCH_PAGE).all()
    # a width that is asked for: the same ids, padded further
    for w in W_WIDTHS:
        if w < live:
            with pytest.raises(ValueError):
                wide.window_pages(table, pos, w)
            continue
        wider = wide.window_pages(table, pos, w)
        assert wider.shape == (w,)
        assert wider[:live].tolist() == table[:live]
        assert (wider[live:] == SCRATCH_PAGE).all()


@pytest.mark.parametrize("call", ["step", "chunk", "oracle", "key"])
def test_a_width_off_the_ladder_is_refused_not_compiled(wide, call):
    """A program of another width would compile under traffic."""
    before = dict(wide.trace_stats()["trace_counts"])
    zs = np.zeros(SLOTS, np.int32)
    with pytest.raises(ValueError):
        if call == "step":
            wide.step(None, zs, zs, np.zeros((SLOTS, 12), np.int32),
                      zs, zs)
        elif call == "chunk":
            wide.prefill_chunk(None, [1, 2], 0, np.zeros(3, np.int32), 1)
        elif call == "oracle":
            sequential_decode(wide, [1, 2, 3], 2, width=12)
        else:
            wide.decode_key(12)
    assert wide.trace_stats()["trace_counts"] == before


# (prompt length, new tokens) of the long request, by what it passes
GROWTH = {
    "within-the-floor": (40, 12),
    "across-512": (500, 30),        # 8 pages -> 9: width 8 -> 16
    "across-1024": (1000, 40),      # width 16 -> 32
    "wraps-past-2048": (2030, 50),  # every page live, the ring turns
}


@pytest.mark.parametrize("case", sorted(GROWTH))
def test_growth_across_a_width_and_past_the_window_is_the_oracles(
        wide, case):
    """A request that grows from one ladder width into the next (and
    one that wraps past the window) emits the oracle's tokens through
    the engine while short requests join and leave around it, and
    nothing is traced after `warmup`: a width is data."""
    n_prompt, n_new = GROWTH[case]
    rng = random.Random(n_prompt)
    long_prompt = _prompt(n_prompt, n_prompt)
    shorts = [(_prompt(rng.randint(3, 90), 7 + i), rng.randint(2, 9))
              for i in range(7)]
    want_long = sequential_decode(wide, long_prompt, n_new)[1]
    want_short = [sequential_decode(wide, p, n)[1] for p, n in shorts]
    traces = dict(wide.trace_stats()["trace_counts"])
    d0 = wide.trace_stats()["dispatches"]

    eng = DecodeEngine(program=wide, prefix_cache=False)
    handles = [eng.submit(long_prompt, n_new)]
    todo = list(shorts)
    steps = 0
    while todo or any(not h.done for h in handles):
        if todo and steps % 5 == 0:         # churn: one joins every 5
            handles.append(eng.submit(*todo.pop(0)))
        eng.step_once()
        steps += 1
        assert steps < 4000
    assert handles[0].tokens_so_far() == want_long
    for h, want in zip(handles[1:], want_short):
        assert h.tokens_so_far() == want
    st = eng.stats()
    assert st["trace_counts"] == traces
    assert set(traces.values()) == {1}
    assert len(traces) == 2 * len(W_WIDTHS) + 1
    if case == "wraps-past-2048":
        assert st["ctx_wraps"] >= 1
    # the widths the long request's steps need were the ones run
    d1 = st["dispatches"]
    ran = {w for w in W_WIDTHS
           if d1["step_by_width"][w] > d0["step_by_width"][w]}
    first = wide.width_for(wide.live_pages(n_prompt - 1))
    last = wide.width_for(wide.live_pages(n_prompt + n_new - 2))
    assert {first, last} <= ran
    assert max(ran) == last


@pytest.mark.parametrize("width", W_WIDTHS)
def test_oracle_at_a_pinned_width_is_the_engines_beside_a_longer_slot(
        wide, width):
    """The engine's step is as wide as its longest decoding slot
    needs, so a short request decoding beside a long one runs at the
    long one's width: the oracle pinned to that width (`width=`) emits
    the same tokens, and so does the oracle at the short one's own."""
    n_long = {8: 30, 16: 600, 32: 1100}[width]
    long_prompt, short = _prompt(n_long, 40 + width), _prompt(21, width)
    eng = DecodeEngine(program=wide, prefix_cache=False,
                       max_prefills_per_step=4)
    h_long = eng.submit(long_prompt, 40)
    while h_long.t_first_token is None:
        eng.step_once()
    d0 = eng.stats()["dispatches"]["step_by_width"]
    h = eng.submit(short, 12)
    while not h.done:
        eng.step_once()
    d1 = eng.stats()["dispatches"]["step_by_width"]
    assert not h_long.done
    # every step of the short request's life ran at the long one's width
    assert {w for w in W_WIDTHS if d1[w] > d0[w]} == {width}
    assert h.tokens_so_far() == sequential_decode(
        wide, short, 12, width=width)[1]
    assert h.tokens_so_far() == sequential_decode(wide, short, 12)[1]
    _drain(eng, [h_long])
    assert h_long.tokens_so_far() == sequential_decode(
        wide, long_prompt, 40)[1]


@pytest.mark.parametrize("n_prompt,n_new,chunks,steps,pages,padded", [
    # a chunk is two pages of 64; chunks: {width: dispatches}; starts
    # 0, 128, ...: a chunk with n prior pages gathers the narrowest
    # width >= n
    # one token: a page filled, the chunk's other page to scratch
    (1, 2, {8: 1}, {8: 2}, 1, 64),
    (100, 3, {8: 1}, {8: 3}, 2, 0),
    # 5 chunks with 0, 2, 4, 6, 8 prior pages, all at width 8; the
    # last fills page 8 alone, its other page is past the prompt's end
    (520, 2, {8: 5}, {16: 2}, 9, 64),
    # the same five chunks, the last fills both its pages; then
    # positions 599..602 hold 10 live pages
    (600, 4, {8: 5}, {16: 4}, 10, 0),
    # 8 chunks, 0, 2, .. 14 prior: 5 at 8, 3 at 16; positions 1023 (16
    # pages) then 1024, 1025 (17)
    (1024, 3, {8: 5, 16: 3}, {16: 1, 32: 2}, 16, 0),
])
def test_chunk_page_counters_and_dispatches_by_width_add_up(
        wide, n_prompt, n_new, chunks, steps, pages, padded):
    """`chunk_pages_gathered` / `chunk_pages_live` count the prior
    context the prefill chunks read as `kv_pages_*` count the decode
    steps', `prefill_pages` the pages the chunks filled and
    `prefill_rows_padded` the rows they parked in scratch, and
    `trace_stats()["dispatches"]` says how often each width was the
    one chosen. Worked by hand for one request."""
    d0 = wide.trace_stats()["dispatches"]
    eng = DecodeEngine(program=wide, prefix_cache=False)
    st = eng.stats()
    assert st["chunk_pages_gathered"] == st["chunk_pages_live"] == 0
    _drain(eng, [eng.submit(_prompt(n_prompt, 3), n_new)])
    st = eng.stats()
    d1 = st["dispatches"]
    assert (wide.chunk_pages, wide.chunk_tokens) == (2, 2 * W_PAGE)
    n_chunks = -(-n_prompt // wide.chunk_tokens)
    assert st["prefill_chunks"] == n_chunks == sum(chunks.values())
    assert st["prefill_pages"] == pages == -(-n_prompt // W_PAGE)
    assert st["prefill_rows_padded"] == padded \
        == n_chunks * wide.chunk_tokens - pages * W_PAGE
    assert st["steps"] == n_new == sum(steps.values())
    for kind, want in (("chunk", chunks), ("step", steps)):
        by = {w: d1[f"{kind}_by_width"][w] - d0[f"{kind}_by_width"][w]
              for w in W_WIDTHS}
        assert by == {w: want.get(w, 0) for w in W_WIDTHS}
        assert d1[kind] - d0[kind] == sum(want.values())
        assert d1[kind] == sum(d1[f"{kind}_by_width"].values())
    assert st["chunk_pages_gathered"] == sum(
        w * n for w, n in chunks.items())
    assert st["chunk_pages_live"] == sum(
        2 * i for i in range(n_chunks))
    assert st["kv_pages_gathered"] == SLOTS * sum(
        w * n for w, n in steps.items())
    assert st["kv_pages_live"] == sum(
        wide.live_pages(p) for p in range(n_prompt - 1,
                                          n_prompt - 1 + n_new))


def test_lint_records_declare_the_narrowest_and_the_widest_width(wide,
                                                                 program):
    """One record a program where the ladder is one width; a ladder
    declares both its ends. The chunk's record is named by its length
    in tokens (`chunk_tokens`: the window's 64 here, 128 in `wide`),
    and takes that many tokens and a page id a page of them."""
    assert [r.name for r in program.lint_records()] == [
        f"decode_step_s{SLOTS}", f"decode_prefill_c{CTX}",
        "decode_page_copy"]
    recs = {r.name: r for r in wide.lint_records()}
    assert sorted(recs) == sorted([
        f"decode_step_s{SLOTS}_w8", "decode_prefill_c128_w8",
        f"decode_step_s{SLOTS}", "decode_prefill_c128",
        "decode_page_copy"])
    for name in ("decode_prefill_c128_w8", "decode_prefill_c128"):
        assert recs[name].example_args[2].shape == (128,)
        assert recs[name].example_args[5].shape == (2,)
    assert recs[f"decode_step_s{SLOTS}_w8"].example_args[4].shape == (
        SLOTS, 8)
    assert recs[f"decode_step_s{SLOTS}"].example_args[4].shape == (
        SLOTS, 32)
    assert recs["decode_prefill_c128_w8"].example_args[4].shape \
        == (8,)


# ================================== a chunk of several pages (PR 37)
@pytest.mark.parametrize("max_ctx,page_size,chunk_pages,chunk_tokens", [
    (64, 8, 8, 64),             # the tier-1 fixtures: the whole window
    (B_CTX, 8, 16, 128), (B_CTX, 4, 32, 128),
    (1024, 16, 8, 128),         # GPT-2's
    (W_CTX, W_PAGE, 2, 128),
    (4096, 128, 1, 128),        # the latent cells': the chunk they had
    (256, 256, 1, 256),         # a page past the budget: one page
    (2048, 1024, 1, 1024),
])
def test_chunk_starts_are_chunk_aligned_and_honour_from_token(
        max_ctx, page_size, chunk_pages, chunk_tokens):
    """A chunk is the whole pages `CHUNK_TOKENS` holds, at least one
    and never past the window, and the schedule is a function of the
    position alone: the aligned blocks that hold a token at or after
    `from_token`, the first of them run from its aligned START even
    where the trie's coverage ends inside it."""
    model = CausalTransformer(vocab_size=VOCAB, d_model=8, n_heads=2,
                              n_layers=1, max_ctx=max_ctx)
    model.params = {}               # shapes only: nothing compiles
    prog = DecodeProgram(model, max_slots=2, page_size=page_size)
    assert (prog.chunk_pages, prog.chunk_tokens) == (chunk_pages,
                                                     chunk_tokens)
    assert chunk_tokens in prog.chunk_key()
    b, ps = chunk_tokens, page_size
    for n in {1, ps - 1, ps, ps + 1, b - 1, b, b + 1, 2 * b + ps + 3,
              max_ctx - 1, max_ctx} - {0}:
        if n > max_ctx:
            continue
        whole = prog.chunk_starts(n)
        assert whole == list(range(0, n, b))
        filled = [p for st in whole for p in prog.block_pages(n, st)]
        assert filled == list(range(-(-n // ps)))   # each page once
        assert sum(prog.state_rows(n, st) for st in whole) == n - 1
        for covered in range(0, n, ps):     # the trie's: page-aligned
            starts = prog.chunk_starts(n, from_token=covered)
            assert starts == [st for st in whole if st + b > covered]
            assert starts[0] <= covered < starts[0] + b
            assert starts[0] % b == 0
    with pytest.raises(ValueError):
        prog.chunk_starts(max_ctx + 1)


@pytest.mark.parametrize("n_prompt", [1, 100, B_TOKENS, B_TOKENS + 1,
                                      300])
@pytest.mark.parametrize("fixture", ["blocks4", "blocks"])
def test_chunks_of_several_pages_are_the_oracles_bitwise(
        request, fixture, n_prompt):
    """Pages of 4 and of 8 under a chunk of 128 tokens: a prompt of one
    token, one that ends inside a chunk's block, at its edge, a token
    past it and in the third block, with short requests joining and
    leaving beside it. The streams are `sequential_decode`'s, the
    counters add up, and no chunk shape compiles after `warmup`."""
    prog = request.getfixturevalue(fixture)
    ps = prog.page_size
    long_prompt = _prompt(n_prompt, n_prompt)
    shorts = [(_prompt(5 + 9 * i, 70 + i), 3 + i) for i in range(3)]
    reqs = [(long_prompt, 11)] + shorts
    want = [sequential_decode(prog, p, n)[1] for p, n in reqs]
    traces = dict(prog.trace_stats()["trace_counts"])
    d0 = prog.trace_stats()["dispatches"]["chunk"]
    eng = DecodeEngine(program=prog, prefix_cache=False)
    handles, todo, steps = [], list(reqs), 0
    while todo or any(not h.done for h in handles):
        if todo and steps % 2 == 0:
            handles.append(eng.submit(*todo.pop(0)))
        eng.step_once()
        steps += 1
        assert steps < 500
    assert [h.tokens_so_far() for h in handles] == want
    st = eng.stats()
    assert st["trace_counts"] == traces
    assert set(traces.values()) == {1} and len(traces) == 3
    assert st["prefill_chunks"] == sum(
        -(-len(p) // B_TOKENS) for p, _ in reqs)
    assert st["prefill_pages"] == sum(-(-len(p) // ps) for p, _ in reqs)
    assert st["prefill_pages"] * ps + st["prefill_rows_padded"] \
        == st["prefill_chunks"] * B_TOKENS
    # a chunk is one dispatch whatever it holds
    assert st["dispatches"]["chunk"] - d0 == st["prefill_chunks"]
    audit = eng._pool.audit()
    assert audit["leaked"] == 0 and not audit["double_freed"]


@pytest.mark.parametrize("fixture", ["blocks4", "blocks"])
def test_trie_coverage_that_ends_inside_a_block_is_bitwise(request,
                                                           fixture):
    """A tenant's prefix of one whole chunk and 5 pages of the next:
    the twin maps all of them, skips the first block and runs the
    second WHOLE from its aligned start, the 5 shared pages' rows
    parked in scratch. Its stream is its unshared twin's bit for bit,
    and the shared pages keep their bytes and their references."""
    prog = request.getfixturevalue(fixture)
    ps, cp = prog.page_size, prog.chunk_pages
    n_shared = cp + 5
    system = _prompt(n_shared * ps, 5)
    first = system + _prompt(30, 6)
    twin = system + _prompt(50, 7)
    want = sequential_decode(prog, twin, 40)[1]

    eng = DecodeEngine(program=prog)
    _drain(eng, [eng.submit(first, 4)])
    st0 = eng.stats()
    assert st0["prefill_chunks"] == 2
    shared, covered = eng._trie.match(twin)
    assert len(shared) == n_shared and covered == n_shared * ps
    assert covered % B_TOKENS != 0          # inside the second block
    bytes_before = np.asarray(eng.kv[:, :, np.asarray(shared)])
    assert [int(eng._pool.ref[p]) for p in shared] == [1] * n_shared

    h = eng.submit(twin, 40)
    eng.step_once()
    # placed: one reference more a shared page, and ONE chunk filled
    # the pages past them (the block's later pages lie past the
    # prompt's end)
    assert [int(eng._pool.ref[p]) for p in shared] == [2] * n_shared
    st = eng.stats()
    n_pages = -(-len(twin) // ps)
    assert st["prefix_hits"] - st0["prefix_hits"] == n_shared
    assert st["prefill_chunks"] - st0["prefill_chunks"] == 1
    assert st["prefill_pages"] - st0["prefill_pages"] \
        == n_pages - n_shared
    assert st["prefill_rows_padded"] - st0["prefill_rows_padded"] \
        == (cp - (n_pages - n_shared)) * ps
    assert st["chunk_pages_live"] - st0["chunk_pages_live"] == cp
    assert _drain(eng, [h]) == [want]
    assert np.array_equal(
        bytes_before, np.asarray(eng.kv[:, :, np.asarray(shared)]))
    assert [int(eng._pool.ref[p]) for p in shared] == [1] * n_shared
    # and with no trie at all: the same stream
    off = DecodeEngine(program=prog, prefix_cache=False)
    assert _drain(off, [off.submit(twin, 40)]) == [want]
    audit = eng._pool.audit()
    assert audit["leaked"] == 0 and not audit["double_freed"]


def test_a_pool_dry_in_the_middle_of_a_block_resumes(blocks):
    """The pool gives 3 of a block's 13 pages and then nothing more
    this step: no chunk is dispatched, the 3 stay in the slot's table,
    and the next step takes the other 10 and fills the block. No page
    is lost or handed out twice, and the stream is the oracle's."""
    prompt = _prompt(100, 9)                # 13 pages of the first block
    want = sequential_decode(blocks, prompt, 9)[1]
    eng = DecodeEngine(program=blocks, prefix_cache=False)
    free0 = eng._pool.free_count
    real, asked = eng._alloc_page, []

    def dry_at_the_fourth(for_slot):
        asked.append(for_slot)
        return None if len(asked) == 4 else real(for_slot)

    eng._alloc_page = dry_at_the_fourth
    h = eng.submit(prompt, 9)
    assert eng.step_once()                  # placed, nothing dispatched
    st = eng.stats()
    assert st["prefill_chunks"] == 0 and st["prefill_pages"] == 0
    assert st["active_slots"] == 1 and eng._inflight is None
    held = [p for p in eng._table[0] if p is not None]
    assert len(held) == 3 and eng._pool.free_count == free0 - 3
    assert int(eng._fill_next[0]) == 0
    assert eng.step_once()                  # the block, tried again
    st = eng.stats()
    assert st["prefill_chunks"] == 1 and st["prefill_pages"] == 13
    assert len(asked) == 4 + 10             # the 3 were not asked again
    filled = [p for p in eng._table[0] if p is not None]
    assert filled[:3] == held and len(set(filled)) == 13
    assert eng._pool.free_count == free0 - 13
    assert _drain(eng, [h]) == [want]
    audit = eng._pool.audit()
    assert audit["leaked"] == 0 and not audit["double_freed"]
    assert eng._pool.free_count == free0
