"""CausalTransformer: the zoo's minimal decoder-only transformer.

The first genuinely new compiled shape since the CNN flagship — a
GPT-style causal LM decoder whose ONLY job is to feed the continuous-
batching serving arc (ROADMAP items 3a/4) a real autoregressive
workload: token embedding + learned positions, N pre-LN decoder blocks
(causal self-attention + GELU MLP, residual throughout), tied LM head.

Unlike the classification zoo entries it does NOT build a
NeuralNetConfiguration — generation is served, not fit: the model owns
a plain parameter pytree plus the package-standard `JitCache`
(recompile forensics, precision-policy registration), and
engine/decode_program.DecodeProgram compiles its prefill/decode
programs from the model's own description of itself (the block at the
end of the class: the page pool's shape, and the per-layer functions
over the nn/attention.py primitives). Greedy (argmax) sampling keeps
every emitted token a deterministic function of the prompt — the
property the byte-identical slot-churn oracle in tests/test_decode.py
pins.

Dims default MXU-friendly (d_model/head_dim multiples of 8, vocab a
pow2) but stay CPU-lintable; `compute_dtype` mirrors the rest of the
zoo ("bfloat16" for MXU serving — the DecodeProgram registers the
resulting policy with the program lint).
"""

from __future__ import annotations

import numpy as np

from deeplearning4j_tpu.nn.jit_cache import JitCache


class CausalTransformer:
    """Decoder-only causal transformer LM (weights + forensics cache;
    compiled programs live in engine/decode_program.DecodeProgram)."""

    def __init__(self, vocab_size: int = 256, d_model: int = 64,
                 n_heads: int = 4, n_layers: int = 2,
                 d_ff: int = 0, max_ctx: int = 128, seed: int = 123,
                 compute_dtype=None):
        if d_model % n_heads != 0:
            raise ValueError(
                f"d_model {d_model} not divisible by n_heads {n_heads}")
        if max_ctx & (max_ctx - 1):
            raise ValueError(f"max_ctx must be a power of two "
                             f"(pow2 prefill buckets): {max_ctx}")
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.n_heads = int(n_heads)
        self.n_layers = int(n_layers)
        self.d_ff = int(d_ff) if d_ff else 4 * self.d_model
        self.max_ctx = int(max_ctx)
        self.seed = int(seed)
        self.compute_dtype = compute_dtype
        self.dtype = np.float32
        self.params = None
        self._jit_cache = JitCache()

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    # ------------------------------------------------------------ init
    def init(self) -> "CausalTransformer":
        """Initialize the parameter pytree (0.02-std normals for
        projections/embeddings, unit gains / zero biases for norms —
        the small-GPT convention)."""
        import jax
        import jax.numpy as jnp

        key = jax.random.PRNGKey(self.seed)
        d, f, std = self.d_model, self.d_ff, 0.02

        def normal(key, shape):
            return (jax.random.normal(key, shape, jnp.float32) * std)

        key, ke, kp = jax.random.split(key, 3)
        params = {
            "tok_emb": normal(ke, (self.vocab_size, d)),
            "pos_emb": normal(kp, (self.max_ctx, d)),
            "lnf_g": jnp.ones((d,), jnp.float32),
            "lnf_b": jnp.zeros((d,), jnp.float32),
        }
        layers = []
        for _ in range(self.n_layers):
            key, kq, kk, kv, ko, k1, k2 = jax.random.split(key, 7)
            layers.append({
                "ln1_g": jnp.ones((d,), jnp.float32),
                "ln1_b": jnp.zeros((d,), jnp.float32),
                "wq": normal(kq, (d, d)),
                "wk": normal(kk, (d, d)),
                "wv": normal(kv, (d, d)),
                "wo": normal(ko, (d, d)),
                "ln2_g": jnp.ones((d,), jnp.float32),
                "ln2_b": jnp.zeros((d,), jnp.float32),
                "w1": normal(k1, (d, f)),
                "b1": jnp.zeros((f,), jnp.float32),
                "w2": normal(k2, (f, d)),
                "b2": jnp.zeros((d,), jnp.float32),
            })
        params["layers"] = tuple(layers)
        self.params = params
        return self

    # ----------------------------------- what DecodeProgram builds from
    # (the contract is in engine/decode_program.py's docstring)
    kv_dtype = np.float32
    kv_page_axis = 2
    step_counters = ()

    def kv_shape(self, n_pages: int, page_size: int):
        """Page-major so one page id addresses every layer's K and V;
        a page is `page_size` TOKEN ROWS of n_heads * head_dim lanes.
        Rows in whole 128-lane tiles are the one layout the chip's
        compiler works in: with head_dim 64 minor it converted the
        whole pool in and out of every program (PERF.md, PR 29)."""
        return (self.n_layers, 2, n_pages, page_size, self.d_model)

    def embed(self, params, tokens, positions):
        # logical positions grow unbounded past max_ctx (ring wrap);
        # the learned positional table wraps with them
        return (params["tok_emb"][tokens]
                + params["pos_emb"][positions % self.max_ctx])

    def project(self, lp, x, positions):
        """(q, (k, v)) of the positions in `x`; learned positions are
        in the stream already."""
        import jax

        from deeplearning4j_tpu.nn.attention import decode_qkv

        with jax.named_scope("qkv"):
            q, k, v = decode_qkv(lp, x, self.n_heads)
        return q, (k, v)

    def write_cells(self, pool, li, cell, page, offset):
        """pool[li, io, page, offset] = k or v as one row, heads merged
        (`page` and `offset` per slot in the decode step; from a chunk
        a page id a page with `offset` the whole page, or one page and
        its offsets: the advanced indices broadcast). A row is written
        whole, so no cell is scattered into the lanes of another's."""
        from deeplearning4j_tpu.nn.attention import merge_heads

        k, v = cell
        pool = pool.at[li, 0, page, offset].set(merge_heads(k))
        return pool.at[li, 1, page, offset].set(merge_heads(v))

    def read_window(self, pool, li, page_ids):
        """[.., cells, n_heads * head_dim] each of K and V: the pages'
        rows in ring-cell order, as stored (splitting a row into heads
        is what the compiler materializes at 64 of 128 lanes: PERF.md,
        PR 29). ONE gather over the whole pool (layer and K/V plane
        are constant indices of it): `pool[li, 0][page_ids]` makes the
        chip's compiler copy the layer's plane out first (PR 27)."""
        import jax.numpy as jnp

        def rows(io):
            pages = pool[li, io, page_ids]       # [.., P, page_size, C]
            return jnp.reshape(pages, pages.shape[:-3] + (-1,)
                               + pages.shape[-1:])

        return rows(0), rows(1)

    def decode_finish(self, lp, x, q, window, live, active):
        from deeplearning4j_tpu.nn.attention import block_decode_finish

        del active
        return block_decode_finish(lp, x, q, *window, live), None

    def chunk_finish(self, lp, x, q, cell, window, start):
        from deeplearning4j_tpu.nn.attention import block_chunk_prefill

        return block_chunk_prefill(lp, x, self.n_heads, *window, start,
                                   qkv=(q,) + cell)

    def head(self, params, x):
        from deeplearning4j_tpu.nn.attention import layer_norm, lm_logits

        return lm_logits(layer_norm(x, params["lnf_g"], params["lnf_b"]),
                         params["tok_emb"])

    # ----------------------------------------------------------- facts
    def num_params(self) -> int:
        import jax

        if self.params is None:
            return 0
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(self.params))

    def config(self) -> dict:
        return {"vocab_size": self.vocab_size, "d_model": self.d_model,
                "n_heads": self.n_heads, "n_layers": self.n_layers,
                "d_ff": self.d_ff, "max_ctx": self.max_ctx,
                "seed": self.seed}
