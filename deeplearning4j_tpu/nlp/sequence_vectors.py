"""SequenceVectors: the generic embedding trainer
(parity: models/sequencevectors/SequenceVectors.java — buildVocab :103,207,
fit :187, worker loop :289; elements-learning algorithms SkipGram.java:31
(iterateSample :224, HS :238, negative sampling :258) and CBOW.java).

TPU-native redesign: the reference trains with multithreaded hogwild over
a shared host table. Here the tables live in HBM and train with
jit-compiled batched scatter-add updates, in one of two tiers:

- scan tier (small vocab, default < 2048): lax.scan over small chunks
  approximates the reference's sequential per-pair SGD — in-batch
  duplicate updates would collapse tiny vocabularies otherwise.
- dense tier (large vocab / mode='dense'): the native single-pass epoch
  builder (native/dl4j_tpu_native.cpp, the AggregateSkipGram role)
  packs [center, positive, K alias-sampled negatives] rows in corpus
  order; fixed-shape slabs of batches upload once and train in a single
  lax.scan dispatch of pure gather->VPU->scatter updates. See
  _DenseSteps for the measured design rationale.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.nlp.vocab import AbstractCache, build_huffman


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _chunk_of(batch: int, chunk: int) -> int:
    """Largest divisor of `batch` that is <= chunk (scan needs equal splits)."""
    c = min(chunk, batch)
    while batch % c:
        c -= 1
    return max(c, 1)


class _NegSamplingStep:
    """jit'd skip-gram negative-sampling update.

    The reference applies per-pair SGD updates one at a time
    (SkipGram.java:258-272). Summing a whole large batch of updates
    computed at the same stale table values multiplies the effective lr
    for in-batch duplicate rows and collapses embeddings on small vocabs.
    We approximate the sequential semantics with `lax.scan` over fixed
    sub-batches: updates inside a chunk are batched einsums (MXU), chunks
    see each other's fresh values.
    """

    def __init__(self, chunk: int = 32):
        self.chunk = chunk
        self._fn = None

    def __call__(self, syn0, syn1neg, center, ctx, labels, lr):
        import jax
        import jax.numpy as jnp

        if self._fn is None:
            chunk = self.chunk

            def step(syn0, syn1neg, center, ctx, labels, lr):
                B, K1 = ctx.shape
                c = _chunk_of(B, chunk)
                S = B // c

                def body(carry, xs):
                    syn0, syn1neg = carry
                    cen, cx, lab = xs
                    v = syn0[cen]                       # [c,D]
                    u = syn1neg[cx]                     # [c,K+1,D]
                    logits = jnp.einsum("bd,bkd->bk", v, u)
                    p = jax.nn.sigmoid(logits)
                    g = (lab - p) * lr                  # [c,K+1]
                    dv = jnp.einsum("bk,bkd->bd", g, u)
                    du = jnp.einsum("bk,bd->bkd", g, v)
                    syn0 = syn0.at[cen].add(dv)
                    syn1neg = syn1neg.at[cx.reshape(-1)].add(
                        du.reshape(-1, du.shape[-1]))
                    eps = 1e-7
                    loss = -jnp.mean(
                        lab * jnp.log(p + eps)
                        + (1 - lab) * jnp.log(1 - p + eps))
                    return (syn0, syn1neg), loss

                (syn0, syn1neg), losses = jax.lax.scan(
                    body, (syn0, syn1neg),
                    (center.reshape(S, c), ctx.reshape(S, c, K1),
                     labels.reshape(S, c, K1)))
                return syn0, syn1neg, jnp.mean(losses)

            self._fn = jax.jit(step, donate_argnums=(0, 1))
        return self._fn(syn0, syn1neg, center, ctx, labels, lr)


class _HierarchicSoftmaxStep:
    """jit'd skip-gram hierarchical-softmax update (SkipGram.java:238).

    Same scan-over-sub-batches sequential semantics as _NegSamplingStep.
    """

    def __init__(self, chunk: int = 32):
        self.chunk = chunk
        self._fn = None

    def __call__(self, syn0, syn1, center, points, codes, mask, lr):
        import jax
        import jax.numpy as jnp

        if self._fn is None:
            chunk = self.chunk

            def step(syn0, syn1, center, points, codes, mask, lr):
                B, L = points.shape
                c = _chunk_of(B, chunk)
                S = B // c

                def body(carry, xs):
                    syn0, syn1 = carry
                    cen, pts, cds, msk = xs
                    v = syn0[cen]                       # [c,D]
                    u = syn1[pts]                       # [c,L,D]
                    logits = jnp.einsum("bd,bld->bl", v, u)
                    p = jax.nn.sigmoid(logits)
                    # target: 1 - code
                    g = ((1.0 - cds) - p) * msk * lr
                    dv = jnp.einsum("bl,bld->bd", g, u)
                    du = jnp.einsum("bl,bd->bld", g, v)
                    syn0 = syn0.at[cen].add(dv)
                    syn1 = syn1.at[pts.reshape(-1)].add(
                        du.reshape(-1, du.shape[-1]))
                    eps = 1e-7
                    tgt = 1.0 - cds
                    ll = (tgt * jnp.log(p + eps)
                          + (1 - tgt) * jnp.log(1 - p + eps))
                    loss = (-jnp.sum(ll * msk)
                            / jnp.maximum(jnp.sum(msk), 1.0))
                    return (syn0, syn1), loss

                (syn0, syn1), losses = jax.lax.scan(
                    body, (syn0, syn1),
                    (center.reshape(S, c), points.reshape(S, c, L),
                     codes.reshape(S, c, L), mask.reshape(S, c, L)))
                return syn0, syn1, jnp.mean(losses)

            self._fn = jax.jit(step, donate_argnums=(0, 1))
        return self._fn(syn0, syn1, center, points, codes, mask, lr)


class _CbowNegSamplingStep:
    """jit'd CBOW negative-sampling update (ref CBOW.java + word2vec.c
    cbow-mean path): input = masked mean of the context vectors, targets
    = center + negatives; the input gradient is applied to every context
    word unscaled, matching the reference. Same scan-chunked sequential
    semantics as the skip-gram steps."""

    def __init__(self, chunk: int = 32):
        self.chunk = chunk
        self._fn = None

    def __call__(self, syn0, syn1neg, ctx_words, ctx_mask, targets,
                 labels, lr):
        import jax
        import jax.numpy as jnp

        if self._fn is None:
            chunk = self.chunk

            def step(syn0, syn1neg, cw, cm, tgt, lab, lr):
                B, W = cw.shape
                K1 = tgt.shape[1]
                c = _chunk_of(B, chunk)
                S = B // c

                def body(carry, xs):
                    syn0, syn1neg = carry
                    cw, cm, tgt, lab = xs
                    counts = jnp.maximum(jnp.sum(cm, axis=1), 1.0)
                    h = (jnp.einsum("bwd,bw->bd", syn0[cw], cm)
                         / counts[:, None])                  # [c,D]
                    u = syn1neg[tgt]                          # [c,K+1,D]
                    p = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, u))
                    g = (lab - p) * lr
                    du = jnp.einsum("bk,bd->bkd", g, h)
                    dh = jnp.einsum("bk,bkd->bd", g, u)
                    syn1neg = syn1neg.at[tgt.reshape(-1)].add(
                        du.reshape(-1, du.shape[-1]))
                    dctx = dh[:, None, :] * cm[:, :, None]    # [c,W,D]
                    syn0 = syn0.at[cw.reshape(-1)].add(
                        dctx.reshape(-1, dctx.shape[-1]))
                    eps = 1e-7
                    loss = -jnp.mean(
                        lab * jnp.log(p + eps)
                        + (1 - lab) * jnp.log(1 - p + eps))
                    return (syn0, syn1neg), loss

                (syn0, syn1neg), losses = jax.lax.scan(
                    body, (syn0, syn1neg),
                    (cw.reshape(S, c, W), cm.reshape(S, c, W),
                     tgt.reshape(S, c, K1), lab.reshape(S, c, K1)))
                return syn0, syn1neg, jnp.mean(losses)

            self._fn = jax.jit(step, donate_argnums=(0, 1))
        return self._fn(syn0, syn1neg, ctx_words, ctx_mask, targets,
                        labels, lr)


class _CbowHierarchicSoftmaxStep:
    """jit'd CBOW hierarchical-softmax update (ref CBOW.java HS branch):
    context-mean input against the CENTER word's Huffman path."""

    def __init__(self, chunk: int = 32):
        self.chunk = chunk
        self._fn = None

    def __call__(self, syn0, syn1, ctx_words, ctx_mask, points, codes,
                 mask, lr):
        import jax
        import jax.numpy as jnp

        if self._fn is None:
            chunk = self.chunk

            def step(syn0, syn1, cw, cm, pts, cds, msk, lr):
                B, W = cw.shape
                L = pts.shape[1]
                c = _chunk_of(B, chunk)
                S = B // c

                def body(carry, xs):
                    syn0, syn1 = carry
                    cw, cm, pts, cds, msk = xs
                    counts = jnp.maximum(jnp.sum(cm, axis=1), 1.0)
                    h = (jnp.einsum("bwd,bw->bd", syn0[cw], cm)
                         / counts[:, None])
                    u = syn1[pts]                             # [c,L,D]
                    p = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", h, u))
                    g = ((1.0 - cds) - p) * msk * lr
                    du = jnp.einsum("bl,bd->bld", g, h)
                    dh = jnp.einsum("bl,bld->bd", g, u)
                    syn1 = syn1.at[pts.reshape(-1)].add(
                        du.reshape(-1, du.shape[-1]))
                    dctx = dh[:, None, :] * cm[:, :, None]
                    syn0 = syn0.at[cw.reshape(-1)].add(
                        dctx.reshape(-1, dctx.shape[-1]))
                    eps = 1e-7
                    tgt = 1.0 - cds
                    ll = (tgt * jnp.log(p + eps)
                          + (1 - tgt) * jnp.log(1 - p + eps))
                    loss = (-jnp.sum(ll * msk)
                            / jnp.maximum(jnp.sum(msk), 1.0))
                    return (syn0, syn1), loss

                (syn0, syn1), losses = jax.lax.scan(
                    body, (syn0, syn1),
                    (cw.reshape(S, c, W), cm.reshape(S, c, W),
                     pts.reshape(S, c, L), cds.reshape(S, c, L),
                     msk.reshape(S, c, L)))
                return syn0, syn1, jnp.mean(losses)

            self._fn = jax.jit(step, donate_argnums=(0, 1))
        return self._fn(syn0, syn1, ctx_words, ctx_mask, points, codes,
                        mask, lr)


_DUP_CAP = 8.0


def _dedup_scatter_add(table, idx_flat, rows):
    """table[idx] += capped-sum-of-duplicates(rows): rows with up to
    _DUP_CAP in-batch occurrences apply their full summed gradient
    (approximating the sequential hogwild's total movement); beyond
    that the sum is rescaled to the cap. A plain summed scatter
    multiplies the head word's effective lr by its duplicate count —
    under a zipf vocabulary that is thousands per batch and the table
    NaNs within an epoch; a plain mean starves moderate-frequency
    words of their sequential-equivalent step size."""
    import jax.numpy as jnp

    counts = jnp.zeros((table.shape[0],), rows.dtype).at[idx_flat].add(
        1.0)
    acc = jnp.zeros_like(table).at[idx_flat].add(rows)
    scale = _DUP_CAP / jnp.maximum(counts, _DUP_CAP)
    return table + acc * scale[:, None]


class _DenseSteps:
    """Dense batched updates for large vocabularies (SURVEY §7 step 9 —
    the role of the reference's native AggregateSkipGram op behind
    SkipGram.java:224's hot loop, redesigned for the TPU).

    Differences from the scan tier above, chosen for throughput:

    - One batched update per batch of B pairs; in-batch duplicate rows
      apply a CAPPED SUM of their gradients: full summed gradient up
      to _DUP_CAP occurrences, rescaled to the cap beyond (see
      _dedup_scatter_add — an uncapped summed scatter multiplies the
      head words' effective lr by their in-batch count and NaNs the
      table on zipf vocabularies, while a plain mean starves them).
      At small vocab the chunk-sequential scan tier remains the
      default (see SequenceVectors._ensure_steps).
    - The device step is pure gather -> VPU elementwise -> scatter-add:
      logits/grads are broadcast-multiply-reduce, NOT batched dot_general
      (a [B]-batched [1,D]x[D,K] dot pads each tiny matmul to an MXU
      tile and loses ~an order of magnitude).
    - Negative sampling happens on HOST (native single-pass alias
      builder; see native/dl4j_tpu_native.cpp dl4j_w2v_sg_pack).
      Profiling showed both jnp.searchsorted and per-scalar alias-table
      gathers lower to multi-millisecond loops on TPU.
    - A whole SLAB of batches ships as one [nb, B, cols] int32 upload
      and trains in one dispatch (lax.scan over batches): one transfer
      and one dispatch per slab instead of one per batch, and the
      scan's xs double-buffering hides the slice loads.
    - Negatives that collide with the row's positive have their gradient
      masked on device (same effect as the reference's resample loop:
      no contradictory label on one index).
    - Tables are donated buffers: the update aliases in place, and the
      host never fetches until the lazy table properties are read.
    """

    def __init__(self, negative: int = 5):
        self.negative = negative
        self._sg_ns = None
        self._sg_hs = None
        self._cbow_ns = None
        self._cbow_hs = None

    @staticmethod
    def _sg_ns_body(syn0, syn1neg, pack, lr):
        """pack [B, K+2] int32: col 0 center, col 1 positive, rest
        negatives."""
        import jax
        import jax.numpy as jnp

        cen = pack[:, 0]
        tgt = pack[:, 1:]
        B, K1 = tgt.shape
        D = syn0.shape[1]
        lab = jnp.zeros((B, K1)).at[:, 0].set(1.0)
        ok = jnp.concatenate(
            [jnp.ones((B, 1), bool), tgt[:, 1:] != tgt[:, :1]], axis=1)
        v = syn0[cen]                        # [B,D]
        u = syn1neg[tgt]                     # [B,K+1,D]
        p = jax.nn.sigmoid(jnp.sum(v[:, None, :] * u, axis=-1))
        g = jnp.where(ok, (lab - p) * lr, 0.0)
        dv = jnp.sum(g[:, :, None] * u, axis=1)
        du = (g[:, :, None] * v[:, None, :]).reshape(-1, D)
        syn0 = _dedup_scatter_add(syn0, cen, dv)
        syn1neg = _dedup_scatter_add(syn1neg, tgt.reshape(-1), du)
        return syn0, syn1neg

    @staticmethod
    def _sg_hs_body(syn0, syn1, pts_tab, cds_tab, msk_tab, pack, lr):
        """pack [B, 2] int32: col 0 center, col 1 positive."""
        import jax
        import jax.numpy as jnp

        cen, pos = pack[:, 0], pack[:, 1]
        D = syn0.shape[1]
        pts, cds, msk = pts_tab[pos], cds_tab[pos], msk_tab[pos]
        v = syn0[cen]                        # [B,D]
        u = syn1[pts]                        # [B,L,D]
        p = jax.nn.sigmoid(jnp.sum(v[:, None, :] * u, axis=-1))
        g = ((1.0 - cds) - p) * msk * lr
        dv = jnp.sum(g[:, :, None] * u, axis=1)
        du = (g[:, :, None] * v[:, None, :]).reshape(-1, D)
        syn0 = _dedup_scatter_add(syn0, cen, dv)
        syn1 = _dedup_scatter_add(syn1, pts.reshape(-1), du)
        return syn0, syn1

    @staticmethod
    def _cbow_ns_body(syn0, syn1neg, pack, W, lr):
        """pack [B, W+K+1] int32: cols 0..W-1 context (-1 = empty
        slot), col W center/positive, rest negatives."""
        import jax
        import jax.numpy as jnp

        cw_raw = pack[:, :W]
        cm = (cw_raw >= 0).astype(jnp.float32)
        cw = jnp.maximum(cw_raw, 0)
        tgt = pack[:, W:]
        B, K1 = tgt.shape
        D = syn0.shape[1]
        lab = jnp.zeros((B, K1)).at[:, 0].set(1.0)
        ok = jnp.concatenate(
            [jnp.ones((B, 1), bool), tgt[:, 1:] != tgt[:, :1]], axis=1)
        counts = jnp.maximum(jnp.sum(cm, axis=1), 1.0)
        ctx_v = syn0[cw]                     # [B,W,D]
        h = (jnp.sum(ctx_v * cm[:, :, None], axis=1)
             / counts[:, None])              # [B,D]
        u = syn1neg[tgt]                     # [B,K+1,D]
        p = jax.nn.sigmoid(jnp.sum(h[:, None, :] * u, axis=-1))
        g = jnp.where(ok, (lab - p) * lr, 0.0)
        du = (g[:, :, None] * h[:, None, :]).reshape(-1, D)
        dh = jnp.sum(g[:, :, None] * u, axis=1)
        syn1neg = _dedup_scatter_add(syn1neg, tgt.reshape(-1), du)
        dctx = dh[:, None, :] * cm[:, :, None]
        syn0 = _dedup_scatter_add(syn0, cw.reshape(-1),
                                  dctx.reshape(-1, D))
        return syn0, syn1neg

    @staticmethod
    def _cbow_hs_body(syn0, syn1, pts_tab, cds_tab, msk_tab, pack, W,
                      lr):
        """pack [B, W+1] int32: cols 0..W-1 context (-1 = empty), col W
        center."""
        import jax
        import jax.numpy as jnp

        cw_raw = pack[:, :W]
        cm = (cw_raw >= 0).astype(jnp.float32)
        cw = jnp.maximum(cw_raw, 0)
        cen = pack[:, W]
        D = syn0.shape[1]
        pts, cds, msk = pts_tab[cen], cds_tab[cen], msk_tab[cen]
        counts = jnp.maximum(jnp.sum(cm, axis=1), 1.0)
        ctx_v = syn0[cw]
        h = (jnp.sum(ctx_v * cm[:, :, None], axis=1)
             / counts[:, None])
        u = syn1[pts]                        # [B,L,D]
        p = jax.nn.sigmoid(jnp.sum(h[:, None, :] * u, axis=-1))
        g = ((1.0 - cds) - p) * msk * lr
        du = (g[:, :, None] * h[:, None, :]).reshape(-1, D)
        dh = jnp.sum(g[:, :, None] * u, axis=1)
        syn1 = _dedup_scatter_add(syn1, pts.reshape(-1), du)
        dctx = dh[:, None, :] * cm[:, :, None]
        syn0 = _dedup_scatter_add(syn0, cw.reshape(-1),
                                  dctx.reshape(-1, D))
        return syn0, syn1

    # --------------------------------------------------- slab dispatch
    def sg_ns(self, syn0, syn1neg, packs, lrs):
        """packs [nb, B, K+2] int32, lrs [nb] f32: one dispatch trains
        the whole slab via lax.scan."""
        import jax

        if self._sg_ns is None:
            body = self._sg_ns_body

            def slab(syn0, syn1neg, packs, lrs):
                def step(carry, xs):
                    return body(*carry, *xs), None
                (syn0, syn1neg), _ = jax.lax.scan(
                    step, (syn0, syn1neg), (packs, lrs))
                return syn0, syn1neg

            self._sg_ns = jax.jit(slab, donate_argnums=(0, 1))
        return self._sg_ns(syn0, syn1neg, packs, lrs)

    def sg_hs(self, syn0, syn1, pts_tab, cds_tab, msk_tab, packs, lrs):
        import jax

        if self._sg_hs is None:
            body = self._sg_hs_body

            def slab(syn0, syn1, pts_tab, cds_tab, msk_tab, packs, lrs):
                def step(carry, xs):
                    return body(*carry, pts_tab, cds_tab, msk_tab,
                                *xs), None
                (syn0, syn1), _ = jax.lax.scan(
                    step, (syn0, syn1), (packs, lrs))
                return syn0, syn1

            self._sg_hs = jax.jit(slab, donate_argnums=(0, 1))
        return self._sg_hs(syn0, syn1, pts_tab, cds_tab, msk_tab, packs,
                           lrs)

    def cbow_ns(self, syn0, syn1neg, packs, W, lrs):
        import jax

        if self._cbow_ns is None:
            body = self._cbow_ns_body

            def slab(syn0, syn1neg, packs, lrs):
                def step(carry, xs):
                    pack, lr = xs
                    return body(*carry, pack, W, lr), None
                (syn0, syn1neg), _ = jax.lax.scan(
                    step, (syn0, syn1neg), (packs, lrs))
                return syn0, syn1neg

            self._cbow_ns = jax.jit(slab, donate_argnums=(0, 1))
        return self._cbow_ns(syn0, syn1neg, packs, lrs)

    def cbow_hs(self, syn0, syn1, pts_tab, cds_tab, msk_tab, packs, W,
                lrs):
        import jax

        if self._cbow_hs is None:
            body = self._cbow_hs_body

            def slab(syn0, syn1, pts_tab, cds_tab, msk_tab, packs, lrs):
                def step(carry, xs):
                    pack, lr = xs
                    return body(*carry, pts_tab, cds_tab, msk_tab, pack,
                                W, lr), None
                (syn0, syn1), _ = jax.lax.scan(
                    step, (syn0, syn1), (packs, lrs))
                return syn0, syn1

            self._cbow_hs = jax.jit(slab, donate_argnums=(0, 1))
        return self._cbow_hs(syn0, syn1, pts_tab, cds_tab, msk_tab,
                             packs, lrs)


class SequenceVectors:
    """Generic embedding trainer over token sequences.

    The syn0/syn1/syn1neg tables are lazily-fetched properties: after a
    dense fit they stay device-resident (HBM) and only materialize to
    numpy when read — queries and serialization trigger one transfer.
    """

    @staticmethod
    def _lazy(host, dev):
        if host is None and dev is not None:
            host = np.asarray(dev)
        return host

    @property
    def syn0(self):
        self._syn0_host = self._lazy(self._syn0_host, self._syn0_dev)
        return self._syn0_host

    @syn0.setter
    def syn0(self, v):
        self._syn0_host, self._syn0_dev = v, None

    @property
    def syn1(self):
        self._syn1_host = self._lazy(self._syn1_host, self._syn1_dev)
        return self._syn1_host

    @syn1.setter
    def syn1(self, v):
        self._syn1_host, self._syn1_dev = v, None

    @property
    def syn1neg(self):
        self._syn1neg_host = self._lazy(self._syn1neg_host,
                                        self._syn1neg_dev)
        return self._syn1neg_host

    @syn1neg.setter
    def syn1neg(self, v):
        self._syn1neg_host, self._syn1neg_dev = v, None

    def __init__(self, layer_size: int = 100, window: int = 5,
                 negative: int = 5, use_hierarchic_softmax: bool = False,
                 min_word_frequency: int = 1, learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4, epochs: int = 1,
                 batch_size: int = 512, sampling: float = 0.0,
                 use_cbow: bool = False, seed: int = 42,
                 chunk: Optional[int] = None,
                 mode: Optional[str] = None,
                 dense_batch_size: int = 16384):
        self.layer_size = layer_size
        self.window = window
        self.negative = negative
        self.use_hs = use_hierarchic_softmax or negative <= 0
        self.min_word_frequency = min_word_frequency
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.epochs = epochs
        self.sampling = sampling
        self.use_cbow = use_cbow
        self.seed = seed

        self.vocab = AbstractCache(min_word_frequency)
        self.syn0 = None
        self.syn1 = None      # HS inner nodes
        self.syn1neg = None
        self._unigram: Optional[np.ndarray] = None
        self._max_code_len = 0
        # One chunk constant shared by all jit steps; batch_size is
        # rounded up to a chunk multiple so full batches never need
        # padding (padding replicates pairs -> over-trains them) and
        # _chunk_of never degrades for prime batch sizes.
        # The chunk trades fidelity to the reference's one-pair-at-a-time
        # SGD against device efficiency (each chunk is one scan
        # iteration): tiny vocabularies need small chunks or in-batch
        # duplicate updates collapse embeddings; large vocabularies
        # almost never repeat a word within a chunk, so big chunks are
        # safe and ~10-30x faster. chunk=None (default) resolves at
        # fit() time from the vocab size.
        self._chunk_param = chunk
        self._raw_batch_size = batch_size
        self._chunk = None
        self.batch_size = batch_size
        self._neg_step = None
        self._hs_step = None
        self._cbow_neg_step = None
        self._cbow_hs_step = None
        # mode: None = auto (dense when the vocab is large enough that
        # in-batch duplicate updates are noise, scan otherwise);
        # 'scan' / 'dense' force a tier. An explicit chunk implies scan.
        if mode not in (None, "scan", "dense"):
            raise ValueError(f"mode must be None|'scan'|'dense': {mode}")
        self._mode = mode
        self.dense_batch_size = int(dense_batch_size)
        self._dense = False
        self._dense_steps = None
        self._hs_tables = None
        # External lr-schedule hooks for chunked/distributed drivers
        # (nlp/distributed.py): lr_total_epochs overrides self.epochs
        # in the linear-decay denominator and turns on the _lr_seen
        # carry (the examples-seen numerator persists across fit()
        # calls — counted AFTER subsampling, so chunked and unchunked
        # anneals stay aligned even with sampling>0), so k-epoch fit()
        # calls continue ONE global anneal instead of each decaying
        # learning_rate->min and snapping back. _fit_rng, when set,
        # persists the shuffle/negative-sampling stream across fit()
        # calls (and decorrelates processes) instead of replaying
        # seed+1 every call.
        self.lr_total_epochs = 0
        self._lr_seen = 0
        self._fit_rng = None

    def _ensure_steps(self):
        if self._neg_step is not None or self._dense_steps is not None:
            return
        V = self.vocab.num_words()
        if self._mode == "dense":
            self._dense = True
        elif self._mode == "scan" or self._chunk_param is not None:
            self._dense = False
        else:
            self._dense = V >= 2048
        if self._dense:
            self._dense_steps = _DenseSteps(negative=self.negative)
            return
        if self._chunk_param is not None:
            self._chunk = int(self._chunk_param)
        else:
            self._chunk = 32 if V < 2048 else 512
        self.batch_size = (-(-self._raw_batch_size // self._chunk)
                           * self._chunk)
        self._neg_step = _NegSamplingStep(chunk=self._chunk)
        self._hs_step = _HierarchicSoftmaxStep(chunk=self._chunk)
        self._cbow_neg_step = _CbowNegSamplingStep(chunk=self._chunk)
        self._cbow_hs_step = _CbowHierarchicSoftmaxStep(chunk=self._chunk)

    # ------------------------------------------------------------- vocab
    def build_vocab(self, sequences: Iterable[Sequence[str]]):
        for seq in sequences:
            for tok in seq:
                self.vocab.add_token(tok)
        self.vocab.finalize_vocab()
        if self.use_hs:
            self._max_code_len = build_huffman(self.vocab)
        V = self.vocab.num_words()
        rng = np.random.default_rng(self.seed)
        self.syn0 = ((rng.random((V, self.layer_size)) - 0.5)
                     / self.layer_size).astype(np.float32)
        if self.use_hs:
            self.syn1 = np.zeros((max(V - 1, 1), self.layer_size), np.float32)
        if self.negative > 0:
            self.syn1neg = np.zeros((V, self.layer_size), np.float32)
            counts = self.vocab.counts() ** 0.75
            self._unigram = (counts / counts.sum()).astype(np.float64)
            # inverse-CDF sampling (searchsorted) is O(log V) per draw vs
            # rng.choice(p=...)'s per-call setup — the negative-sampling
            # hot path
            self._unigram_cdf = np.cumsum(self._unigram)
        return self

    def _draw_negatives(self, rng, shape):
        u = rng.random(shape)
        return np.searchsorted(self._unigram_cdf, u).astype(np.int64)

    # ----------------------------------------------------------- pairs
    def _sequence_indices(self, seq, rng):
        idxs = [self.vocab.index_of(t) for t in seq]
        idxs = [i for i in idxs if i >= 0]
        if self.sampling > 0 and self.vocab.total_word_count > 0:
            counts = self.vocab.counts()
            total = counts.sum()
            keep = []
            for i in idxs:
                f = counts[i] / total
                p_keep = min(1.0, (np.sqrt(f / self.sampling) + 1)
                             * self.sampling / f)
                if rng.random() < p_keep:
                    keep.append(i)
            idxs = keep
        return idxs

    def _gen_pairs(self, sequences, rng):
        """Yield (center, context) index pairs with the reference's random
        reduced-window trick."""
        for seq in sequences:
            idxs = self._sequence_indices(seq, rng)
            n = len(idxs)
            for pos, center in enumerate(idxs):
                b = rng.integers(1, self.window + 1)
                for off in range(-b, b + 1):
                    if off == 0:
                        continue
                    j = pos + off
                    if 0 <= j < n:
                        yield center, idxs[j]

    def _gen_cbow_examples(self, sequences, rng):
        """Yield (center, [context indices]) with the reduced-window
        trick — one CBOW example per position (ref CBOW.java)."""
        for seq in sequences:
            idxs = self._sequence_indices(seq, rng)
            n = len(idxs)
            for pos, center in enumerate(idxs):
                b = rng.integers(1, self.window + 1)
                ctx = [idxs[pos + off] for off in range(-b, b + 1)
                       if off != 0 and 0 <= pos + off < n]
                if ctx:
                    yield center, ctx

    # ------------------------------------------------- dense host side
    def _index_corpus(self, seqs) -> List[np.ndarray]:
        """Translate token sequences to vocab-index arrays once (reused
        across epochs; only subsampling/windows are re-drawn)."""
        out = []
        for seq in seqs:
            idxs = [self.vocab.index_of(t) for t in seq]
            arr = np.asarray([i for i in idxs if i >= 0], np.int32)
            if arr.size:
                out.append(arr)
        return out

    def _subsample_flat(self, idx_arrays, rng):
        """Concatenate the corpus with per-sequence ids, applying the
        subsampling keep-test vectorized (same formula as
        _sequence_indices)."""
        arr = np.concatenate(idx_arrays)
        sid = np.concatenate([np.full(a.size, i, np.int32)
                              for i, a in enumerate(idx_arrays)])
        if self.sampling > 0 and self.vocab.total_word_count > 0:
            counts = self.vocab.counts().astype(np.float64)
            f = counts / counts.sum()
            with np.errstate(divide="ignore", invalid="ignore"):
                keep_p = np.minimum(
                    1.0, (np.sqrt(f / self.sampling) + 1)
                    * self.sampling / np.maximum(f, 1e-300))
            m = rng.random(arr.size) < keep_p[arr]
            arr, sid = arr[m], sid[m]
        return arr, sid

    def _context_slots(self, arr, sid, rng, p0, p1):
        """[-1-padded] context-candidate matrix for centers [p0, p1) of
        the full epoch stream: rows see neighbors across the chunk edge
        because `arr`/`sid` are the whole arrays. Shared by both numpy
        fallbacks."""
        n = arr.size
        W2 = 2 * self.window
        p1 = min(p1, n)
        m = p1 - p0
        if m <= 0:
            return np.zeros((0, W2), np.int32), arr[:0]
        b = rng.integers(1, self.window + 1, size=m)
        pos = np.arange(p0, p1)
        cand = np.full((m, W2), -1, np.int32)
        slot = 0
        for off in range(-self.window, self.window + 1):
            if off == 0:
                continue
            j = pos + off
            jc = np.clip(j, 0, n - 1)
            valid = ((j >= 0) & (j < n) & (abs(off) <= b)
                     & (sid[jc] == sid[pos]))
            cand[:, slot] = np.where(valid, arr[jc], -1)
            slot += 1
        return cand, arr[p0:p1]

    def _pairs_from_flat(self, arr, sid, rng, p0=0, p1=None):
        """NumPy fallback for the native sg builder: (center, context)
        skip-gram pairs for centers [p0, p1) with the reduced-window
        trick, vectorized one pass per window offset and emitted in
        CORPUS ORDER (position-major) — the same streaming order the
        reference trains in (SequenceVectors.java:289), so the linear
        lr decay sees the corpus the same way and no O(P log P) shuffle
        is paid."""
        if p1 is None:
            p1 = arr.size
        cand, centers = self._context_slots(arr, sid, rng, p0, p1)
        if centers.size == 0:
            return (np.zeros(0, np.int32),) * 2
        flat = cand.ravel()
        m = flat >= 0
        c = np.repeat(centers, cand.shape[1])[m]
        x = flat[m]
        return c, x

    def _cbow_from_flat(self, arr, sid, rng, p0=0, p1=None):
        """NumPy fallback for the native cbow builder: one example per
        position [p0, p1) in corpus order, fixed-width [N, 2*window]
        context with -1 marking empty slots."""
        if p1 is None:
            p1 = arr.size
        cw, centers = self._context_slots(arr, sid, rng, p0, p1)
        keep = (cw >= 0).any(axis=1)
        return cw[keep], centers[keep]

    def _hs_device_tables(self):
        """[V, L] Huffman (points, codes, mask) tables for device-side
        gather (built once; the scan tier packs per-batch on host)."""
        if self._hs_tables is None:
            V = self.vocab.num_words()
            L = max(self._max_code_len, 1)
            words = self.vocab.vocab_words()
            pts = np.zeros((V, L), np.int32)
            cds = np.zeros((V, L), np.float32)
            msk = np.zeros((V, L), np.float32)
            for i in range(V):
                w = words[i]
                l = len(w.codes)
                pts[i, :l] = w.points
                cds[i, :l] = w.codes
                msk[i, :l] = 1.0
            self._hs_tables = (pts, cds, msk)
        return self._hs_tables

    def _alias_tables(self):
        """Vose alias tables for the unigram^0.75 negative distribution.
        Sampling = two uniform draws + two table lookups, all vectorized
        on host (np.searchsorted over the CDF costs ~log V per draw and
        profiles ~8x slower at word2vec batch sizes)."""
        if getattr(self, "_alias", None) is None:
            p = self._unigram
            V = p.size
            prob = np.zeros(V)
            alias = np.zeros(V, np.int32)
            scaled = (p * V).astype(np.float64).copy()
            small = [i for i in range(V) if scaled[i] < 1.0]
            large = [i for i in range(V) if scaled[i] >= 1.0]
            while small and large:
                s, l = small.pop(), large.pop()
                prob[s] = scaled[s]
                alias[s] = l
                scaled[l] -= 1.0 - scaled[s]
                (small if scaled[l] < 1.0 else large).append(l)
            for i in small + large:
                prob[i] = 1.0
            self._alias = (prob.astype(np.float32), alias)
        return self._alias

    def _host_negatives(self, rng, positives):
        """[B, K+1] targets (positive first) via the alias method.
        Collisions with the positive are handled by a gradient mask on
        device (see _DenseSteps)."""
        B = positives.size
        K = self.negative
        prob, alias = self._alias_tables()
        # one f32 uniform per draw: the integer part picks the bucket,
        # the fractional remainder (still uniform given the bucket)
        # runs the alias coin-flip — one RNG pass for the hot path.
        # f32 resolution bounds the vocab at 2^24; larger vocabularies
        # get f64 draws.
        dt = np.float32 if prob.size < (1 << 24) else np.float64
        r = rng.random((B, K), dtype=dt) * prob.size
        u1 = r.astype(np.int32)
        neg = np.where(r - u1 < prob[u1], u1, alias[u1])
        return np.concatenate(
            [positives.astype(np.int32)[:, None], neg], axis=1)

    # Slab size: batches per dispatch. One compiled scan shape per
    # model — epoch tails are neutralized with lr=0 batches rather than
    # a second compile. 64 * 16384 * 7 int16 ~ 15 MB on the wire
    # (measured optimum: batch 16384 beats 8k/32k/64k on v5e — small
    # enough to keep the dedup sort cheap, large enough to fill the
    # VPU; see PERF.md word2vec).
    _DENSE_SLAB = 64

    def _epoch_pack_chunk(self, arr, sid, rng, p0, p1):
        """Packed rows for centers in positions [p0, p1) of the full
        epoch stream (native builder with numpy fallback) — windows see
        across chunk boundaries because the whole arrays are passed."""
        from deeplearning4j_tpu import native

        K = self.negative if self.negative > 0 else 0
        if K:
            prob, alias = self._alias_tables()
        else:
            prob = alias = None
        seed = int(rng.integers(0, 2 ** 63))
        fn = (native.w2v_cbow_pack if self.use_cbow
              else native.w2v_sg_pack)
        pk = fn(arr, sid, self.window, K, prob, alias, seed, p0, p1)
        if pk is not None:
            return pk
        if self.use_cbow:
            cw, cen = self._cbow_from_flat(arr, sid, rng, p0, p1)
            parts = [cw, cen[:, None].astype(np.int32)]
            if K:
                parts.append(self._host_negatives(rng, cen)[:, 1:])
            return np.concatenate(parts, axis=1)
        cen, ctx = self._pairs_from_flat(arr, sid, rng, p0, p1)
        if K:
            return np.concatenate(
                [cen[:, None].astype(np.int32),
                 self._host_negatives(rng, ctx)], axis=1)
        return np.stack([cen, ctx], axis=1).astype(np.int32)

    # Pipelined host packing (the reference overlaps its VectorCalculations
    # workers with the trainer thread, SkipGram.java:224's hot loop running
    # on a thread pool; here the ONE packer thread runs the native epoch
    # builders — ctypes releases the GIL — while the main thread keeps the
    # async device queue fed, so pack / h2d / device scan overlap).
    pipeline_packing = True
    _PREFETCH_SLABS = 2

    def _prefetched(self, gen):
        """Drain `gen` on a daemon thread through a bounded queue (the
        AsyncPrefetchThread pattern, datasets/iterators.py) when
        pipeline_packing is on; otherwise pass it through inline.
        Exceptions on the packer thread re-raise at the consumer."""
        if not self.pipeline_packing:
            return gen

        import queue as _qm
        import threading

        q: _qm.Queue = _qm.Queue(maxsize=self._PREFETCH_SLABS)
        DONE, ERR = object(), object()
        stop = threading.Event()   # consumer gone: packer must not
                                   # park forever on a full queue
                                   # (AsyncDataSetIterator._start's
                                   # timed-put pattern)

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except _qm.Full:
                    continue
            return False

        def run():
            try:
                for item in gen:
                    if not put(item):
                        return
                put(DONE)
            except BaseException as e:   # re-raised below
                put((ERR, e))

        packer = threading.Thread(target=run, daemon=True,
                                  name="w2v-slab-packer")
        packer.start()

        def drain():
            try:
                while True:
                    item = q.get()
                    if item is DONE:
                        return
                    if isinstance(item, tuple) and len(item) == 2 \
                            and item[0] is ERR:
                        raise item[1]
                    yield item
            finally:
                stop.set()
                # stop flag makes every pending put() bail within one
                # timeout tick, so this join is bounded
                packer.join(timeout=2.0)

        return drain()

    def _dispatch_slab(self, tables, rows, lrs, W, hs_tabs):
        """Ship one [S*Bp, cols] row block + per-batch lrs and run the
        scan-slab step(s). Returns updated tables.

        Rows may arrive as int16 (the halved wire format the packer
        uses when the vocabulary fits — half the bytes to move to the
        device); they are widened back to int32 by a trivial on-device
        convert before entering the compiled steps."""
        import jax.numpy as jnp

        def ship(r):
            # one explicit widening convert per slab: feeding int16
            # straight into the jit steps measured SLOWER (the scan
            # then re-widens per iteration inside the gather pipeline;
            # 277-299k vs 325-396k words/s across draws)
            d = jnp.asarray(r)
            return d.astype(jnp.int32) if r.dtype != np.int32 else d

        syn0, syn1, syn1neg = tables
        S = lrs.size
        Bp = rows.shape[0] // S
        cols = rows.shape[1]
        lrs_d = jnp.asarray(lrs)
        if self.use_cbow:
            if self.use_hs:
                packs = ship(np.ascontiguousarray(
                    rows[:, :W + 1]).reshape(S, Bp, W + 1))
                syn0, syn1 = self._dense_steps.cbow_hs(
                    syn0, syn1, *hs_tabs, packs, W, lrs_d)
            if self.negative > 0:
                packs = ship(rows.reshape(S, Bp, cols))
                syn0, syn1neg = self._dense_steps.cbow_ns(
                    syn0, syn1neg, packs, W, lrs_d)
        else:
            if self.use_hs:
                packs = ship(np.ascontiguousarray(
                    rows[:, :2]).reshape(S, Bp, 2))
                syn0, syn1 = self._dense_steps.sg_hs(
                    syn0, syn1, *hs_tabs, packs, lrs_d)
            if self.negative > 0:
                packs = ship(rows.reshape(S, Bp, cols))
                syn0, syn1neg = self._dense_steps.sg_ns(
                    syn0, syn1neg, packs, lrs_d)
        return syn0, syn1, syn1neg

    def _fit_dense(self, seqs):
        """Streamed dense training: the corpus is processed in
        position-chunks whose packed rows accumulate in a host buffer;
        every full slab (fixed [S, Bp, cols] shape, ONE compile) ships
        as a single scan dispatch. With pipeline_packing (default) the
        packing runs on a prefetch thread (double-buffered), so pack /
        slab h2d / device scan genuinely overlap instead of
        serializing. The epoch tail pads to the slab shape with
        wrap-around rows; fully-padded batches get lr=0 (no update)
        instead of a second compiled shape."""
        import jax.numpy as jnp

        idx_arrays = self._index_corpus(seqs)
        if not idx_arrays:
            return self
        rng = self._fit_rng or np.random.default_rng(self.seed + 1)
        W = 2 * self.window

        def take_dev(host_attr, dev_attr):
            """Device-resident table if present (ownership transferred:
            the jit steps donate it), else upload the host copy."""
            dev = getattr(self, dev_attr)
            if dev is not None:
                setattr(self, dev_attr, None)
                return dev
            host = getattr(self, host_attr)
            return None if host is None else jnp.asarray(host)

        tables = (take_dev("_syn0_host", "_syn0_dev"),
                  take_dev("_syn1_host", "_syn1_dev"),
                  take_dev("_syn1neg_host", "_syn1neg_dev"))
        hs_tabs = None
        if self.use_hs:
            pts, cds, msk = self._hs_device_tables()
            hs_tabs = (jnp.asarray(pts), jnp.asarray(cds),
                       jnp.asarray(msk))
        per_pos = 1 if self.use_cbow else self.window
        positions = sum(a.size for a in idx_arrays)
        chunked = int(self.lr_total_epochs) > 0
        total_ep = int(self.lr_total_epochs) or self.epochs
        approx = max(1, positions * per_pos * total_ep)
        S = self._DENSE_SLAB
        seen0 = self._lr_seen if chunked else 0
        # halved wire format: every packed value is a word index (or the
        # -1 CBOW empty-slot sentinel), so a sub-32k vocabulary ships
        # int16 rows and widens on device (half the h2d bytes)
        wire_dt = (np.int16 if self.vocab.num_words() < 32768
                   else np.int32)

        def slabs():
            """Host production pipeline: yields (rows, lrs, n_real)
            fixed-shape slabs. Runs on the packer thread when
            pipeline_packing is on — all rng use (subsample, pack,
            negatives) lives here in the exact serial order, so the
            pipelined and inline paths are bit-identical."""
            seen = seen0
            for _ in range(self.epochs):
                arr, sid = self._subsample_flat(idx_arrays, rng)
                n = arr.size
                if n == 0:
                    continue
                Bp = self.dense_batch_size
                slab_rows = S * Bp
                # chunk sized to produce ~1.25 slabs of rows so the
                # buffer drains about once per chunk
                pos_chunk = max(1, int(slab_rows * 1.25
                                       / max(per_pos, 1)))
                buf: list = []
                buffered = 0
                first_rows = None
                for a in range(0, n, pos_chunk):
                    pk = self._epoch_pack_chunk(
                        arr, sid, rng, a, min(a + pos_chunk, n))
                    pk = pk.astype(wire_dt, copy=False)
                    if first_rows is None and pk.shape[0]:
                        first_rows = pk[:Bp].copy()
                    buf.append(pk)
                    buffered += pk.shape[0]
                    while buffered >= slab_rows:
                        block = np.concatenate(buf, axis=0)
                        rows, rest = (block[:slab_rows],
                                      block[slab_rows:])
                        buf, buffered = [rest], rest.shape[0]
                        lrs = np.asarray(
                            [self._lr(seen + i * Bp, approx)
                             for i in range(S)], np.float32)
                        yield rows, lrs, slab_rows
                        seen += slab_rows
                # epoch tail: top up to the fixed slab shape; whole
                # pad batches get lr=0, the boundary batch wraps
                # epoch-head rows
                rest = (np.concatenate(buf, axis=0) if buf
                        else np.zeros((0, 2), wire_dt))
                if rest.shape[0]:
                    n_real = rest.shape[0]
                    nb_real = -(-n_real // Bp)
                    pad_src = (first_rows if first_rows is not None
                               else rest)
                    need = nb_real * Bp - n_real
                    reps = (-(-need // max(pad_src.shape[0], 1))
                            if need else 0)
                    pad = (np.concatenate([pad_src] * reps,
                                          axis=0)[:need]
                           if reps else rest[:0])
                    filler = np.zeros(
                        ((S - nb_real) * Bp, rest.shape[1]), wire_dt)
                    rows = np.concatenate([rest, pad, filler], axis=0)
                    lrs = np.asarray(
                        [self._lr(seen + i * Bp, approx)
                         if i < nb_real else 0.0 for i in range(S)],
                        np.float32)
                    yield rows, lrs, n_real
                    seen += n_real

        seen_total = seen0
        for rows, lrs, n_real in self._prefetched(slabs()):
            tables = self._dispatch_slab(tables, rows, lrs, W, hs_tabs)
            seen_total += n_real
        if chunked:
            self._lr_seen = seen_total
        syn0, syn1, syn1neg = tables
        # Leave the tables device-resident: queries (similarity/
        # words_nearest) and serialization fetch lazily through the
        # syn0/syn1/syn1neg properties — fit() does not pay the d2h
        # fetch of the tables eagerly.
        self._syn0_host = None
        self._syn0_dev = syn0
        if syn1 is not None:
            self._syn1_host, self._syn1_dev = None, syn1
        if syn1neg is not None:
            self._syn1neg_host, self._syn1neg_dev = None, syn1neg
        return self

    # ------------------------------------------------------------- fit
    def fit(self, sequences: Iterable[Sequence[str]]):
        seqs = [list(s) for s in sequences]
        if self._syn0_host is None and self._syn0_dev is None:
            self.build_vocab(seqs)
        self._ensure_steps()
        if self._dense:
            return self._fit_dense(seqs)
        import jax.numpy as jnp

        rng = self._fit_rng or np.random.default_rng(self.seed + 1)
        syn0 = jnp.asarray(self.syn0)
        syn1 = None if self.syn1 is None else jnp.asarray(self.syn1)
        syn1neg = (None if self.syn1neg is None
                   else jnp.asarray(self.syn1neg))

        # rough total example count for the linear lr decay: skip-gram
        # emits ~window pairs per position, CBOW one example per position
        per_pos = 1 if self.use_cbow else self.window
        chunked = int(self.lr_total_epochs) > 0
        total_ep = int(self.lr_total_epochs) or self.epochs
        approx_pairs = max(
            1, sum(len(s) for s in seqs) * per_pos * total_ep)
        seen = self._lr_seen if chunked else 0
        gen = (self._gen_cbow_examples if self.use_cbow
               else self._gen_pairs)
        flush = self._flush_cbow if self.use_cbow else self._flush
        for _ in range(self.epochs):
            order = rng.permutation(len(seqs))
            buf_c, buf_x = [], []
            for si in order:
                for c, x in gen([seqs[si]], rng):
                    buf_c.append(c)
                    buf_x.append(x)
                    if len(buf_c) >= self.batch_size:
                        syn0, syn1, syn1neg = flush(
                            syn0, syn1, syn1neg, buf_c, buf_x, rng,
                            seen, approx_pairs)
                        seen += len(buf_c)
                        buf_c, buf_x = [], []
            if buf_c:
                syn0, syn1, syn1neg = flush(
                    syn0, syn1, syn1neg, buf_c, buf_x, rng, seen,
                    approx_pairs)
                seen += len(buf_c)
        if chunked:
            self._lr_seen = seen
        self.syn0 = np.asarray(syn0)
        self.syn1 = None if syn1 is None else np.asarray(syn1)
        self.syn1neg = None if syn1neg is None else np.asarray(syn1neg)
        return self

    def _lr(self, seen, total):
        frac = min(1.0, seen / total)
        return max(self.min_learning_rate,
                   self.learning_rate * (1.0 - frac))

    def _pad_batch_lists(self, *bufs):
        """Pad the final ragged batch to the fixed batch size so the jit
        step compiles exactly once (padding replicates the last example;
        the few duplicated updates there are negligible). batch_size is
        already a chunk multiple (__init__), so full batches need none."""
        B = self.batch_size
        out = []
        for buf in bufs:
            if len(buf) < B:
                buf = buf + [buf[-1]] * (B - len(buf))
            out.append(buf)
        return out

    def _pack_hs(self, targets):
        """Pack the targets' Huffman (points, codes, mask) arrays."""
        B = self.batch_size
        L = max(self._max_code_len, 1)
        words = self.vocab.vocab_words()
        pts = np.zeros((B, L), np.int32)
        cds = np.zeros((B, L), np.float32)
        msk = np.zeros((B, L), np.float32)
        for i, x in enumerate(targets):
            w = words[x]
            l = len(w.codes)
            pts[i, :l] = w.points
            cds[i, :l] = w.codes
            msk[i, :l] = 1.0
        return pts, cds, msk

    def _sample_negatives(self, positives, rng):
        """[B, K+1] targets (positive first) + [B, K+1] labels.
        Negatives colliding with the row's positive are resampled — the
        reference resamples on collision (SkipGram.java:258); a collision
        would label the same index 1 and 0 in one update."""
        B = self.batch_size
        K = self.negative
        pos = np.asarray(positives, np.int64)[:, None]
        neg = self._draw_negatives(rng, (B, K))
        for _ in range(16):
            coll = neg == pos
            n_coll = int(coll.sum())
            if not n_coll:
                break
            neg[coll] = self._draw_negatives(rng, n_coll)
        targets = np.concatenate([pos, neg], axis=1)
        labels = np.zeros((B, K + 1), np.float32)
        labels[:, 0] = 1.0
        return targets, labels

    def _flush(self, syn0, syn1, syn1neg, buf_c, buf_x, rng, seen, total):
        import jax.numpy as jnp

        buf_c, buf_x = self._pad_batch_lists(buf_c, buf_x)
        center = jnp.asarray(np.asarray(buf_c, np.int32))
        lr = jnp.float32(self._lr(seen, total))
        if self.use_hs:
            pts, cds, msk = self._pack_hs(buf_x)
            syn0, syn1, _ = self._hs_step(
                syn0, syn1, center, jnp.asarray(pts), jnp.asarray(cds),
                jnp.asarray(msk), lr)
        if self.negative > 0:
            ctx, labels = self._sample_negatives(buf_x, rng)
            syn0, syn1neg, _ = self._neg_step(
                syn0, syn1neg, center, jnp.asarray(ctx, jnp.int32),
                jnp.asarray(labels), lr)
        return syn0, syn1, syn1neg

    def _flush_cbow(self, syn0, syn1, syn1neg, buf_c, buf_x, rng, seen,
                    total):
        """CBOW batch: buf_c = center indices, buf_x = context lists."""
        import jax.numpy as jnp

        buf_c, buf_x = self._pad_batch_lists(buf_c, buf_x)
        B = self.batch_size
        W = 2 * self.window
        cw = np.zeros((B, W), np.int32)
        cm = np.zeros((B, W), np.float32)
        for i, ctx in enumerate(buf_x):
            n = min(len(ctx), W)
            cw[i, :n] = ctx[:n]
            cm[i, :n] = 1.0
        cw_j = jnp.asarray(cw)
        cm_j = jnp.asarray(cm)
        lr = jnp.float32(self._lr(seen, total))
        if self.use_hs:
            pts, cds, msk = self._pack_hs(buf_c)
            syn0, syn1, _ = self._cbow_hs_step(
                syn0, syn1, cw_j, cm_j, jnp.asarray(pts),
                jnp.asarray(cds), jnp.asarray(msk), lr)
        if self.negative > 0:
            tgt, labels = self._sample_negatives(buf_c, rng)
            syn0, syn1neg, _ = self._cbow_neg_step(
                syn0, syn1neg, cw_j, cm_j, jnp.asarray(tgt, jnp.int32),
                jnp.asarray(labels), lr)
        return syn0, syn1, syn1neg

    # ------------------------------------------------------- query API
    def get_word_vector(self, word: str) -> Optional[np.ndarray]:
        i = self.vocab.index_of(word)
        return None if i < 0 else self.syn0[i]

    def has_word(self, word: str) -> bool:
        return self.vocab.contains_word(word)

    def similarity(self, a: str, b: str) -> float:
        va, vb = self.get_word_vector(a), self.get_word_vector(b)
        if va is None or vb is None:
            return float("nan")
        denom = np.linalg.norm(va) * np.linalg.norm(vb)
        return float(va @ vb / denom) if denom else 0.0

    def words_nearest(self, word_or_vec, top_n: int = 10) -> List[str]:
        if isinstance(word_or_vec, str):
            v = self.get_word_vector(word_or_vec)
            exclude = {word_or_vec}
            if v is None:
                return []
        else:
            v = np.asarray(word_or_vec)
            exclude = set()
        norms = np.linalg.norm(self.syn0, axis=1) * np.linalg.norm(v)
        sims = self.syn0 @ v / np.maximum(norms, 1e-12)
        order = np.argsort(-sims)
        out = []
        for i in order:
            w = self.vocab.word_at_index(int(i))
            if w not in exclude:
                out.append(w)
            if len(out) >= top_n:
                break
        return out

    wordsNearest = words_nearest
