"""GPT-2 (Radford et al. 2019; `gpt2-medium` config.json), plain.

Full causal forward pass over whole sequences in float32 `jax.numpy` at
`highest` matmul precision: no cache, no pages, no chunks, no batching
tricks. Pre-LN blocks, learned positions, tanh-approximated GELU
(GPT-2's `gelu_new`), tied head. Departure shared with the program: the
four attention projections carry no bias.

Also here: the operations one output token requires and the bytes one
decode step must move. It imports nothing of the program.
"""

from __future__ import annotations

LN_EPS = 1e-5


def param_shapes(cfg: dict) -> dict:
    d, f = int(cfg["n_embd"]), int(cfg["n_inner"])
    layer = {"ln1_g": (d,), "ln1_b": (d,), "wq": (d, d), "wk": (d, d),
             "wv": (d, d), "wo": (d, d), "ln2_g": (d,), "ln2_b": (d,),
             "w1": (d, f), "b1": (f,), "w2": (f, d), "b2": (d,)}
    return {"tok_emb": (int(cfg["vocab_size"]), d),
            "pos_emb": (int(cfg["n_positions"]), d),
            "lnf_g": (d,), "lnf_b": (d,),
            "layers": [dict(layer) for _ in range(int(cfg["n_layer"]))]}


# ------------------------------------------------------------- counts
def matmul_params(cfg: dict) -> int:
    """Parameters every token multiplies through: the blocks and the
    tied head (not the embedding look-ups)."""
    d, f = int(cfg["n_embd"]), int(cfg["n_inner"])
    return int(cfg["n_layer"]) * (4 * d * d + 2 * d * f) \
        + int(cfg["vocab_size"]) * d


def flops_per_token(cfg: dict, context: float) -> float:
    """One position attending over `context` live positions: 2 per
    matmul parameter, and QK^T and AV over the context in each layer."""
    d = int(cfg["n_embd"])
    return 2.0 * matmul_params(cfg) \
        + int(cfg["n_layer"]) * 4.0 * d * context


def decode_step_bytes(cfg: dict, live_cells: float, slots: int,
                      bytes_per: int = 4) -> float:
    """What one decode step must move whatever implements it: the
    matmul weights once, the live K and V cells of the active slots
    once, one new K and V cell per slot."""
    d = int(cfg["n_embd"])
    kv = int(cfg["n_layer"]) * 2 * d * bytes_per
    return matmul_params(cfg) * bytes_per + (live_cells + slots) * kv


# ---------------------------------------------------------- the model
def _ln(x, g, b):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def fp8(a):
    """Round to float8 e4m3 and back: the precision below bfloat16."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def logits_fn(params, tokens, n_head: int, control=None):
    """tokens [N, T] -> logits [N, T, vocab]. `control` is None for the
    reference; "bfloat16" casts weights and activations, storage and
    arithmetic alike; "fp8" keeps float32 and rounds the operands of
    every matrix multiplication (weights, activations, keys and values)
    to float8 e4m3."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    q = fp8 if control == "fp8" else (lambda a: a)
    if control == "bfloat16":
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params)

    def mm(a, b):
        return jnp.matmul(q(a), q(b), precision=hp)

    n, t = tokens.shape
    x = params["tok_emb"][tokens] + params["pos_emb"][:t][None]
    causal = jnp.tril(jnp.ones((t, t), bool))
    layers = params["layers"]
    for i in range(len(layers)):
        lp = layers[i]
        h = _ln(x, lp["ln1_g"], lp["ln1_b"])
        qh, k, v = (mm(h, lp[w]).reshape(n, t, n_head, -1)
                    for w in ("wq", "wk", "wv"))
        s = jnp.einsum("nthd,nuhd->nhtu", q(qh), q(k), precision=hp) \
            / jnp.sqrt(jnp.asarray(qh.shape[-1], x.dtype))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        att = jnp.einsum("nhtu,nuhd->nthd", q(jax.nn.softmax(s, axis=-1)),
                         q(v), precision=hp).reshape(n, t, -1)
        x = x + mm(att, lp["wo"])
        h = _ln(x, lp["ln2_g"], lp["ln2_b"])
        h = jax.nn.gelu(mm(h, lp["w1"]) + lp["b1"], approximate=True)
        x = x + mm(h, lp["w2"]) + lp["b2"]
    x = _ln(x, params["lnf_g"], params["lnf_b"])
    return jnp.einsum("ntd,vd->ntv", q(x), q(params["tok_emb"]),
                      precision=hp)


def served_gaps(params, tokens, n_head: int, control=None):
    """For each position p < T-1 of each row: how far the reference's
    logit of the token at p+1 lies below the reference's best logit at
    p. With a `control`, the token judged is the one the lower
    precision puts first instead of the one in `tokens`. [N, T-1]."""
    import jax.numpy as jnp

    ref = logits_fn(params, tokens, n_head)[:, :-1].astype(jnp.float32)
    if control is None:
        judged = tokens[:, 1:]
    else:
        low = logits_fn(params, tokens, n_head, control)[:, :-1]
        judged = jnp.argmax(low, axis=-1)
    got = jnp.take_along_axis(ref, judged[..., None], axis=-1)[..., 0]
    return jnp.max(ref, axis=-1) - got
