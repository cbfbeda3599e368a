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
    dl4j_decode_ctx_wraps_total.
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
    # the shared blocks were computed once; only tails chunked after
    total_chunks_unshared = sum(len(program.chunk_starts(len(p)))
                                for p in prompts)
    assert s["prefill_chunks"] < total_chunks_unshared


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
        wp = page_for(start // ps)
        kv = big.prefill_chunk(kv, prompt[start:start + ps], start,
                               big.window_pages(ring_table(start),
                                                start - 1), wp)
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


def test_engine_matches_plain_sliding_window_forward(program):
    """Against mathematics, not against the same programs: a run that
    prefills several chunks, decodes, and wraps the ring emits the
    tokens a plain float32 sliding-window causal forward picks — the
    page gather in ring order changes where a cell sits in the
    reduction, never which cells are in it."""
    rng = random.Random(41)
    prompt = [rng.randrange(VOCAB) for _ in range(2 * PAGE + 5)]
    n_new = CTX + 2 * PAGE + 3           # wraps, and recycles 2 pages
    eng = DecodeEngine(program=program)
    h = eng.submit(prompt, n_new)
    toks = _drain(eng, [h])[0]
    st = eng.stats()
    assert st["prefill_chunks"] == 3 and st["ctx_wraps"] >= 2
    assert len(toks) == n_new
    model = program.model
    logits = np.asarray(_window_forward(
        model.params, prompt + toks[:-1], model.n_heads, CTX,
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
    # A: 10 tokens (2 chunks), 3 new. One chunk a step, so step 1
    # prefills chunk 0 and no slot decodes; step 2 prefills chunk 1
    # and A decodes at position 9 (2 pages live), then 10 and 11
    a = eng.submit(list(range(1, 11)), 3)
    assert eng.step_once()
    assert eng.stats()["steps"] == 0          # nothing decoded yet
    assert eng.stats()["kv_pages_gathered"] == 0
    for k in (1, 2, 3):
        assert eng.step_once()
        st = eng.stats()
        assert st["steps"] == k
        assert st["kv_pages_gathered"] == k * SLOTS * pps
        assert st["kv_pages_live"] == 2 * k
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
            "dl4j_decode_prefill_chunks_total") > 0
        assert reg.counter_value("dl4j_decode_prefix_hits_total") > 0
        assert reg.counter_value("dl4j_decode_ctx_wraps_total") > 0
        snap = reg.snapshot()
        assert "dl4j_decode_pages_free" in snap["gauges"]
        assert "dl4j_decode_prefix_pages_shared" in snap["gauges"]
    finally:
        reg.reset()
