"""KV-aware causal self-attention primitives over a PAGED cache.

The decode-serving arc (ROADMAP item 2) needs a transformer forward
that exists in TWO compiled shapes over ONE set of weights:

  chunk prefill   one aligned block [T, d_model] of a prompt, T the
             whole pages a token budget holds (`DecodeProgram.
             chunk_tokens`), processed in parallel: causal within the
             chunk, attending to the PRIOR context through gathered
             pages, emitting the chunk's K/V so the caller parks them
             in the block's physical pages. A block always starts at
             its aligned position and is run whole, so a cell is the
             work of the same row over the same split of prior window
             and own chunk whoever fills it. Chunks interleave with
             decode steps, so a long prompt never stalls resident
             generations.
  decode     ONE new position per slot, batched over the engine's
             [max_slots] axis, attending against whole pages GATHERED
             in ring order — the page-table indirection that makes
             the cache a virtual address space: shared prefix pages,
             copy-on-write forks, and ring wrap past max_ctx are all
             host page-table edits, never a new compiled shape.

Both build from the same per-layer parameter dict (see
zoo/decoder.CausalTransformer), so the math of a position is defined
once; engine/decode_program.py owns where K/V land in the page pool.

Layout discipline: the window arrives as the pool stores it, TOKEN
ROWS [..., cells, n_heads * head_dim] — one row of d_model numbers a
cached position, pages flattened in ring order — and neither cache
contraction splits a row into (n_heads, head_dim). Measured on the
v5e (PERF.md, PR 29): a minor dimension of head_dim 64 fills half of
a 128-lane tile, so the head-major page that stood here had the
compiler convert the whole pool in and out of every program (31 of a
77 ms step) and move the window at half width. Scores are the rows
times a BLOCK-DIAGONAL query (`_head_blocks`), values the weights
times the rows with the block diagonal taken after: n_heads times
the multiply-adds on an idle MXU, and nothing beside the window's
own bytes read.

Bitwise discipline: attention is commutative but NOT associative over
keys, so the engine and the sequential oracle must present identical
operand values in an identical reduction order. Gathering pages in
RING order (cell c = page * page_size + offset holds the position
congruent to c modulo the window — a function of the position alone,
and logical token order until the ring wraps) is that mechanism — a
wrapped ring, a shared prefix page, and a fresh contiguous fill all
reduce over the same [cells] axis in the same order, whatever
physical pages the table names. A dead cell lies in the unwritten
tail of a page or on the shared scratch page, whose bytes other slots
scribble. Its KEY reaches one number alone, its own score (a row of
the window times the query), and the mask replaces that score whatever
it is: a select takes nothing from the operand it drops, so a dead key
needs no zeroing, and zeroing it was a pass over the whole window
(PERF.md, PR 37). Its VALUE is zeroed BEFORE the weighted sum (not
just weighted by nothing): exp(MASK_VALUE - max) underflows the weight
to exactly 0.0, and 0·garbage is safe only where the garbage is 0 —
the zeroed value keeps 0·NaN out of the sum.

Everything here is pure jax on traced values — no host syncs, no
Python branching on data — so the functions compose into donated,
compile-once programs.
"""

from __future__ import annotations

# large finite "masked" score: exp(x - max) underflows to exactly 0.0
# for masked lanes while never producing inf/NaN arithmetic
MASK_VALUE = -1e30


def layer_norm(x, gain, bias, eps: float = 1e-5):
    """LayerNorm over the trailing (feature) axis."""
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gain + bias


def rms_norm(x, gain, eps: float = 1e-5):
    """RMSNorm over the trailing axis, in float32 whatever comes in."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * gain


def mm(x, w):
    """x @ w with the operands in the weight's stored dtype and the
    sum in float32: bfloat16 weights make it the MXU's native product,
    float32 weights (the CPU tests) leave it a float32 one."""
    import jax.numpy as jnp

    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def gated_mlp(x, w_gate, w_up, w_down):
    """W_down(silu(W_gate x) * W_up x): the feed-forward half of the
    gated blocks, dense layer and single expert alike."""
    import jax

    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def qkv_heads(lp: dict, x, n_heads: int):
    """Project hidden states to per-head q/k/v: [..., d_model] ->
    three [..., n_heads, head_dim] tensors (head_dim innermost)."""
    import jax.numpy as jnp

    def split(w):
        y = x @ w
        return jnp.reshape(y, y.shape[:-1] + (n_heads, -1))

    return split(lp["wq"]), split(lp["wk"]), split(lp["wv"])


def _head_blocks(q):
    """E [n_heads * head_dim, n_heads] of 1.0 where lane c belongs to
    head h: `merged q[..., :, None] * E` is the block-diagonal query
    whose product with a token row is every head's score at once, and
    `sum(x[..., h, c] * E.T, axis=-2)` takes head h's lanes of x."""
    import jax.numpy as jnp

    h, d = q.shape[-2:]
    return (jnp.arange(h * d)[:, None] // d
            == jnp.arange(h)[None, :]).astype(q.dtype)


def paged_decode_attention(q, k_rows, v_rows, live):
    """Single-position attention against GATHERED pages (the DECODE
    shape): `q` is [S, n_heads, head_dim] (one new position per slot),
    `k_rows`/`v_rows` are [S, cells, n_heads * head_dim] — the slot's
    window gathered from the physical page pool a whole page at a
    time, in RING order: cell c = page * page_size + offset holds the
    position congruent to c modulo the window, with the new position's
    K/V already written at its cell. `live[s]` counts the slot's
    readable cells; cells beyond it (the unwritten tail of the newest
    page, and whole pages that point at scratch) have their scores
    masked and their values zeroed (see the module docstring). Both
    contractions
    run over the rows as stored (module docstring, "Layout
    discipline"). Returns [S, n_heads * head_dim], heads merged."""
    import jax.numpy as jnp

    n = k_rows.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    mask = jnp.arange(n)[None, :] < live[:, None]          # [S, N]
    v_rows = jnp.where(mask[:, :, None], v_rows, 0.0)
    e = _head_blocks(q)
    qb = merge_heads(q)[:, :, None] * e                    # [S, C, H]
    scores = jnp.einsum("snc,sch->shn", k_rows, qb) * scale
    scores = jnp.where(mask[:, None], scores, MASK_VALUE)
    # one softmax over the window's cells in ring-cell order
    w = _softmax(scores)
    full = jnp.einsum("shn,snc->shc", w, v_rows)           # [S, H, C]
    return jnp.sum(full * e.T, axis=1)


def chunk_prefill_attention(q, k, v, k_rows, v_rows, n_prior):
    """One prompt chunk attending jointly to its PRIOR context and to
    itself (the CHUNK-PREFILL shape): `q`/`k`/`v` are [T, n_heads,
    head_dim] for chunk positions n_prior..n_prior+T-1; `k_rows`/
    `v_rows` are [cells, n_heads * head_dim] — the already-prefilled
    positions 0..n_prior-1 gathered a whole page at a time in ring
    order, which before a wrap (and a prompt never wraps) is logical
    order: cell c holds position c (cells >= n_prior are scratch:
    scores masked, values zeroed). ONE softmax spans
    [prior cells ; chunk] so the reduction order is fixed regardless
    of how the prior pages were produced — computed by an earlier
    chunk, or mapped read-only from the prefix trie. Returns
    [T, n_heads * head_dim], heads merged."""
    import jax.numpy as jnp

    t, n = q.shape[0], k_rows.shape[0]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    prior = jnp.arange(n) < n_prior                        # [N]
    v_rows = jnp.where(prior[:, None], v_rows, 0.0)
    e = _head_blocks(q)
    qb = merge_heads(q)[:, :, None] * e                    # [T, C, H]
    sp = jnp.einsum("nc,tch->htn", k_rows, qb) * scale     # [H, T, N]
    sp = jnp.where(prior[None, None], sp, MASK_VALUE)
    si = jnp.einsum("thd,uhd->htu", q, k) * scale          # [H, T, T]
    causal = jnp.tril(jnp.ones((t, t), bool))
    si = jnp.where(causal[None, :, :], si, MASK_VALUE)
    w = _softmax(jnp.concatenate([sp, si], axis=-1))
    full = jnp.einsum("htn,nc->thc", w[..., :n], v_rows)   # [T, H, C]
    own = jnp.einsum("htu,uhd->thd", w[..., n:], v)
    return jnp.sum(full * e.T, axis=1) + merge_heads(own)


def _softmax(scores):
    import jax.numpy as jnp

    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def mlp_block(lp: dict, x):
    """The position-wise feed-forward half of a decoder block (GELU)."""
    import jax

    h = jax.nn.gelu(x @ lp["w1"] + lp["b1"], approximate=True)
    return h @ lp["w2"] + lp["b2"]


def block_chunk_prefill(lp: dict, x, n_heads: int, k_rows, v_rows,
                        n_prior, qkv=None):
    """One decoder block over a prompt CHUNK: x [T, d_model] -> x'.
    The chunk's q/k/v are pre-attention projections of the ln1 stream
    — exactly what the decode shape recomputes per position, so a
    chunk-prefilled cell and a decoded cell hold the same quantity.
    The caller usually passes `qkv` precomputed via `decode_qkv` (it
    parks k/v into a physical page BEFORE attention — the
    scatter-then-gather order that keeps the pool update in place);
    `k_rows`/`v_rows`/`n_prior` carry the prior context per
    `chunk_prefill_attention`."""
    import jax

    if qkv is None:
        with jax.named_scope("qkv"):
            qkv = decode_qkv(lp, x, n_heads)
    q, k, v = qkv
    with jax.named_scope("attn"):
        att = chunk_prefill_attention(q, k, v, k_rows, v_rows,
                                      n_prior)
        x = x + att @ lp["wo"]
    with jax.named_scope("mlp"):
        x = x + mlp_block(lp, layer_norm(x, lp["ln2_g"], lp["ln2_b"]))
    return x


def decode_qkv(lp: dict, x, n_heads: int):
    """First half of a decode-shape block: the current position's
    q/k/v projections off the ln1 stream — the same quantities
    block_chunk_prefill parks in pages, so a prefilled cell and a
    decoded cell hold identical values. The caller writes k/v into
    the slot's write cell BEFORE calling `block_decode_finish` (the
    position must attend to itself)."""
    h = layer_norm(x, lp["ln1_g"], lp["ln1_b"])
    return qkv_heads(lp, h, n_heads)


def block_decode_finish(lp: dict, x, q, k_rows, v_rows, live):
    """Second half of a decode-shape block: attend `q` [S, H, Dh]
    against the gathered window's token rows [S, cells, H * Dh]
    (current position's K/V already written at its ring cell) and run
    the residual + feed-forward tail. Returns x' [S, d_model]."""
    import jax

    with jax.named_scope("attn"):
        att = paged_decode_attention(q, k_rows, v_rows, live)
        x = x + att @ lp["wo"]
    with jax.named_scope("mlp"):
        x = x + mlp_block(lp, layer_norm(x, lp["ln2_g"], lp["ln2_b"]))
    return x


def merge_heads(x):
    """[..., n_heads, head_dim] -> [..., n_heads * head_dim]: the row
    a cached position is stored as."""
    import jax.numpy as jnp

    return jnp.reshape(x, x.shape[:-2] + (-1,))


def lm_logits(x, tok_emb):
    """Tied LM head: [..., d_model] x [vocab, d_model] -> [..., vocab]
    via a direct contraction over d_model — no authored `tok_emb.T`
    materialization (dot_general contracts either operand side)."""
    import jax.numpy as jnp

    return jnp.einsum("...d,vd->...v", x, tok_emb)
