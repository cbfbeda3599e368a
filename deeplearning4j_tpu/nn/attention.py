"""KV-aware causal self-attention primitives over a PAGED cache.

The decode-serving arc (ROADMAP item 2) needs a transformer forward
that exists in TWO compiled shapes over ONE set of weights:

  chunk prefill   one page_size-aligned slice [T, d_model] of a prompt
             processed in parallel: causal within the chunk, attending
             to the PRIOR context through gathered pages, emitting
             the chunk's K/V so the caller parks them in a physical
             page. Chunks interleave with decode steps, so a long
             prompt never stalls resident generations.
  decode     ONE new position per slot, batched over the engine's
             [max_slots] axis, attending against whole pages GATHERED
             in ring order — the page-table indirection that makes
             the cache a virtual address space: shared prefix pages,
             copy-on-write forks, and ring wrap past max_ctx are all
             host page-table edits, never a new compiled shape.

Both build from the same per-layer parameter dict (see
zoo/decoder.CausalTransformer), so the math of a position is defined
once; engine/decode_program.py owns where K/V land in the page pool.

Layout discipline (Tensor Processing Primitives, arXiv 2104.05755):
head_dim rides innermost everywhere (the contraction axis of both
attention matmuls stays in the minor/lane dimension), and the window
arrives as the pool stores it, [..., pages, n_heads, page_size,
head_dim]: a page is HEAD-MAJOR, so both cache contractions batch
over (slot, head) and take pages and offsets as free or contracted
dims where they lie — no transposed copy of the window is authored
(the transpose-churn finding the program lint raised against the
first slot-major layout — PERF.md "Decode program layout").

Bitwise discipline: attention is commutative but NOT associative over
keys, so the engine and the sequential oracle must present identical
operand values in an identical reduction order. Gathering pages in
RING order (cell c = page * page_size + offset holds the position
congruent to c modulo the window — a function of the position alone,
and logical token order until the ring wraps) is that mechanism — a
wrapped ring, a shared prefix page, and a fresh contiguous fill all
reduce over the same [cells] axis in the same order, whatever
physical pages the table names. Dead cells are zeroed BEFORE the
score contraction (not just masked after): a dead cell lies in the
unwritten tail of a page or on the shared scratch page, whose bytes
other slots scribble, and 0·garbage is the only value that can never
leak — exp(MASK_VALUE - max) underflows the weight to exactly
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


def paged_decode_attention(q, k_pages, v_pages, live):
    """Single-position attention against GATHERED pages (the DECODE
    shape): `q` is [S, n_heads, head_dim] (one new position per slot),
    `k_pages`/`v_pages` are [S, pages, n_heads, page_size, head_dim] —
    the slot's window gathered from the physical page pool a whole
    page at a time, in RING order: cell c = page * page_size + offset
    holds the position congruent to c modulo the window, with the new
    position's K/V already written at its cell. `live[s]` counts the
    slot's readable cells; cells beyond it (the unwritten tail of the
    newest page, and whole pages that point at scratch) are zeroed
    before the score contraction (see the module docstring). Both
    contractions take the page layout as it is gathered — (slot, head)
    batch dims, head_dim minor, pages and offsets free or contracted
    in place — so no transposed copy of the window is authored (the
    40% transpose-churn the program lint flagged on the first
    slot-major attempt — PERF.md). Returns [S, n_heads, head_dim]."""
    import jax.numpy as jnp

    s, p, h, t, _ = k_pages.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    mask = jnp.reshape(jnp.arange(p * t)[None, :] < live[:, None],
                       (s, p, t))                          # [S, P, T]
    m5 = mask[:, :, None, :, None]
    k_pages = jnp.where(m5, k_pages, 0.0)
    v_pages = jnp.where(m5, v_pages, 0.0)
    scores = jnp.einsum("shd,sphtd->shpt", q, k_pages) * scale
    scores = jnp.where(mask[:, None], scores, MASK_VALUE)
    # one softmax over the window's cells in ring-cell order
    w = _softmax(jnp.reshape(scores, (s, h, p * t)))
    return jnp.einsum("shpt,sphtd->shd",
                      jnp.reshape(w, (s, h, p, t)), v_pages)


def chunk_prefill_attention(q, k, v, k_pages, v_pages, n_prior):
    """One prompt chunk attending jointly to its PRIOR context and to
    itself (the CHUNK-PREFILL shape): `q`/`k`/`v` are [T, n_heads,
    head_dim] for chunk positions n_prior..n_prior+T-1; `k_pages`/
    `v_pages` are [pages, n_heads, page_size, head_dim] — the already-
    prefilled positions 0..n_prior-1 gathered a whole page at a time
    in ring order, which before a wrap (and a prompt never wraps) is
    logical order: cell c holds position c (cells >= n_prior are
    scratch: zeroed + masked). ONE softmax spans [prior cells ; chunk]
    so the reduction order is fixed regardless of how the prior pages
    were produced — computed by an earlier chunk, or mapped read-only
    from the prefix trie. Returns [T, n_heads, head_dim]."""
    import jax.numpy as jnp

    t = q.shape[0]
    p, h, ps, _ = k_pages.shape
    c = p * ps
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    prior = jnp.reshape(jnp.arange(c) < n_prior, (p, ps))  # [P, ps]
    m4 = prior[:, None, :, None]
    k_pages = jnp.where(m4, k_pages, 0.0)
    v_pages = jnp.where(m4, v_pages, 0.0)
    sp = jnp.einsum("thd,phcd->htpc", q, k_pages) * scale
    sp = jnp.reshape(jnp.where(prior[None, None], sp, MASK_VALUE),
                     (h, t, c))                            # [H, T, C]
    si = jnp.einsum("thd,uhd->htu", q, k) * scale          # [H, T, T]
    causal = jnp.tril(jnp.ones((t, t), bool))
    si = jnp.where(causal[None, :, :], si, MASK_VALUE)
    w = _softmax(jnp.concatenate([sp, si], axis=-1))
    wp = jnp.reshape(w[..., :c], (h, t, p, ps))
    return (jnp.einsum("htpc,phcd->thd", wp, v_pages)
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


def block_chunk_prefill(lp: dict, x, n_heads: int, k_pages, v_pages,
                        n_prior, qkv=None):
    """One decoder block over a prompt CHUNK: x [T, d_model] -> x'.
    The chunk's q/k/v are pre-attention projections of the ln1 stream
    — exactly what the decode shape recomputes per position, so a
    chunk-prefilled cell and a decoded cell hold the same quantity.
    The caller usually passes `qkv` precomputed via `decode_qkv` (it
    parks k/v into a physical page BEFORE attention — the
    scatter-then-gather order that keeps the pool update in place);
    `k_pages`/`v_pages`/`n_prior` carry the prior context per
    `chunk_prefill_attention`."""
    import jax

    if qkv is None:
        with jax.named_scope("qkv"):
            qkv = decode_qkv(lp, x, n_heads)
    q, k, v = qkv
    with jax.named_scope("attn"):
        att = chunk_prefill_attention(q, k, v, k_pages, v_pages,
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


def block_decode_finish(lp: dict, x, q, k_pages, v_pages, live):
    """Second half of a decode-shape block: attend `q` [S, H, Dh]
    against the gathered window pages [S, pages, H, page_size, Dh]
    (current position's K/V already written at its ring cell) and run
    the residual + feed-forward tail. Returns x' [S, d_model]."""
    import jax

    with jax.named_scope("attn"):
        att = paged_decode_attention(q, k_pages, v_pages, live)
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
