"""KV-aware causal self-attention primitives over a PAGED cache.

The decode-serving arc (ROADMAP item 2) needs a transformer forward
that exists in TWO compiled shapes over ONE set of weights:

  chunk prefill   one page_size-aligned slice [T, d_model] of a prompt
             processed in parallel: causal within the chunk, attending
             to the PRIOR context through gathered page cells, emitting
             the chunk's K/V so the caller parks them in a physical
             page. Chunks interleave with decode steps, so a long
             prompt never stalls resident generations.
  decode     ONE new position per slot, batched over the engine's
             [max_slots] axis, attending against page cells GATHERED
             in logical token order — the per-cell (page, offset)
             indirection that makes the cache a virtual address space:
             shared prefix pages, copy-on-write forks, and ring wrap
             past max_ctx are all host page-table edits, never a new
             compiled shape.

Both build from the same per-layer parameter dict (see
zoo/decoder.CausalTransformer), so the math of a position is defined
once; engine/decode_program.py owns where K/V land in the page pool.

Layout discipline (Tensor Processing Primitives, arXiv 2104.05755):
head_dim rides innermost everywhere (the contraction axis of both
attention matmuls stays in the minor/lane dimension), and gathered
cells arrive HEAD-MAJOR [..., n_heads, cells, head_dim] so both cache
contractions keep (slot, head) as leading batch dims — XLA contracts
in place instead of materializing a transposed cache copy per step
(the transpose-churn finding the program lint raised against the
first slot-major layout — PERF.md "Decode program layout").

Bitwise discipline: attention is commutative but NOT associative over
keys, so the engine and the sequential oracle must present identical
operand values in an identical reduction order. Gathering cells in
LOGICAL token order (cell j = j-th oldest position in the window) is
that mechanism — a wrapped ring, a shared prefix page, and a fresh
contiguous fill all reduce over the same [cells] axis in the same
order. Dead cells are zeroed BEFORE the score contraction (not just
masked after): a dead cell points at the shared scratch page, whose
bytes other slots scribble, and 0·garbage is the only value that can
never leak — exp(MASK_VALUE - max) underflows the weight to exactly
0.0, and the zeroed value keeps 0·NaN out of the weighted sum.

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


def qkv_heads(lp: dict, x, n_heads: int):
    """Project hidden states to per-head q/k/v: [..., d_model] ->
    three [..., n_heads, head_dim] tensors (head_dim innermost)."""
    import jax.numpy as jnp

    def split(w):
        y = x @ w
        return jnp.reshape(y, y.shape[:-1] + (n_heads, -1))

    return split(lp["wq"]), split(lp["wk"]), split(lp["wv"])


def paged_decode_attention(q, k_cells, v_cells, live):
    """Single-position attention against GATHERED page cells (the
    DECODE shape): `q` is [S, n_heads, head_dim] (one new position per
    slot), `k_cells`/`v_cells` are HEAD-MAJOR
    [S, n_heads, cells, head_dim] — the slot's window gathered from
    the physical page pool in LOGICAL token order (cell j = j-th
    oldest live position), with the new position's K/V already written
    at cell live[s]-1. `live[s]` counts the slot's readable cells;
    cells beyond it point at the scratch page and are zeroed before
    the score contraction (see the module docstring). Head-major cell
    layout is load-bearing: BOTH contractions run with (slot, head) as
    leading batch dims and the contraction axis minor, so XLA never
    materializes a transposed copy of the gathered cells (the 40%
    transpose-churn the program lint flagged on the first slot-major
    attempt — PERF.md). Returns [S, n_heads, head_dim]."""
    import jax.numpy as jnp

    c = k_cells.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    mask = jnp.arange(c)[None, :] < live[:, None]          # [S, C]
    m4 = mask[:, None, :, None]
    k_cells = jnp.where(m4, k_cells, 0.0)
    v_cells = jnp.where(m4, v_cells, 0.0)
    scores = jnp.einsum("shd,shcd->shc", q, k_cells) * scale
    scores = jnp.where(mask[:, None, :], scores, MASK_VALUE)
    w = _softmax(scores)
    return jnp.einsum("shc,shcd->shd", w, v_cells)


def chunk_prefill_attention(q, k, v, k_cells, v_cells, n_prior):
    """One prompt chunk attending jointly to its PRIOR context and to
    itself (the CHUNK-PREFILL shape): `q`/`k`/`v` are [T, n_heads,
    head_dim] for chunk positions n_prior..n_prior+T-1; `k_cells`/
    `v_cells` are HEAD-MAJOR [n_heads, cells, head_dim] — the already-
    prefilled positions 0..n_prior-1 gathered from their pages in
    logical order (cells >= n_prior are scratch: zeroed + masked).
    ONE softmax spans [prior cells ; chunk] so the reduction order is
    fixed regardless of how the prior pages were produced — computed
    by an earlier chunk, or mapped read-only from the prefix trie.
    Returns [T, n_heads, head_dim]."""
    import jax.numpy as jnp

    t = q.shape[0]
    c = k_cells.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    prior = jnp.arange(c) < n_prior                        # [C]
    m3 = prior[None, :, None]
    k_cells = jnp.where(m3, k_cells, 0.0)
    v_cells = jnp.where(m3, v_cells, 0.0)
    sp = jnp.einsum("thd,hcd->htc", q, k_cells) * scale    # [H, T, C]
    sp = jnp.where(prior[None, None, :], sp, MASK_VALUE)
    si = jnp.einsum("thd,uhd->htu", q, k) * scale          # [H, T, T]
    causal = jnp.tril(jnp.ones((t, t), bool))
    si = jnp.where(causal[None, :, :], si, MASK_VALUE)
    w = _softmax(jnp.concatenate([sp, si], axis=-1))
    return (jnp.einsum("htc,hcd->thd", w[..., :c], v_cells)
            + jnp.einsum("htu,uhd->thd", w[..., c:], v))


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


def block_chunk_prefill(lp: dict, x, n_heads: int, k_cells, v_cells,
                        n_prior, qkv=None):
    """One decoder block over a prompt CHUNK: x [T, d_model] -> x'.
    The chunk's q/k/v are pre-attention projections of the ln1 stream
    — exactly what the decode shape recomputes per position, so a
    chunk-prefilled cell and a decoded cell hold the same quantity.
    The caller usually passes `qkv` precomputed via `decode_qkv` (it
    parks k/v into a physical page BEFORE attention — the
    scatter-then-gather order that keeps the pool update in place);
    `k_cells`/`v_cells`/`n_prior` carry the prior context per
    `chunk_prefill_attention`."""
    import jax

    if qkv is None:
        with jax.named_scope("qkv"):
            qkv = decode_qkv(lp, x, n_heads)
    q, k, v = qkv
    with jax.named_scope("attn"):
        att = chunk_prefill_attention(q, k, v, k_cells, v_cells,
                                      n_prior)
        x = x + _merge_heads(att) @ lp["wo"]
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


def block_decode_finish(lp: dict, x, q, k_cells, v_cells, live):
    """Second half of a decode-shape block: attend `q` [S, H, Dh]
    against the gathered window cells [S, H, cells, Dh] (current
    position's K/V already written at cell live[s]-1) and run the
    residual + feed-forward tail. Returns x' [S, d_model]."""
    import jax

    with jax.named_scope("attn"):
        att = paged_decode_attention(q, k_cells, v_cells, live)
        x = x + _merge_heads(att) @ lp["wo"]
    with jax.named_scope("mlp"):
        x = x + mlp_block(lp, layer_norm(x, lp["ln2_g"], lp["ln2_b"]))
    return x


def _merge_heads(att):
    import jax.numpy as jnp

    return jnp.reshape(att, att.shape[:-2] + (-1,))


def lm_logits(x, tok_emb):
    """Tied LM head: [..., d_model] x [vocab, d_model] -> [..., vocab]
    via a direct contraction over d_model — no authored `tok_emb.T`
    materialization (dot_general contracts either operand side)."""
    import jax.numpy as jnp

    return jnp.einsum("...d,vd->...v", x, tok_emb)
