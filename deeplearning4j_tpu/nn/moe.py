"""A sparse-expert layer that is told which experts it holds.

Expert parallelism spreads a layer's routed experts over chips; each
chip routes every token over ALL experts (the router keeps its full
width and its experts per token) and computes the part of the result
that the experts it holds give, plus the shared expert that every chip
computes alike. This module is that one chip's layer: no exchange, and
nothing that stands in for the absent chips — what their experts would
have added is simply not in the result.

    s = sigmoid(x W_g)                     all experts, float32
    top-k of s (of s + b where the router has a selection bias);
    w = scale * s_top / (sum(s_top) + norm_eps)
    y = sum_{i in top-k, held} w_i E_i(x)  +  E_shared(x)
    E(x) = W_down(silu(W_gate x) * W_up x)

What varies between published layers of this kind is data: the shared
expert is computed where the layer has one (`"sg" in lp`; with none and
every expert held the result is the model's own layer and no partial
sum), `norm_eps` is the epsilon some routers put under the
renormalisation (0: none, and the program that was traced without it),
and `score` is the squashing of the router's logits: "sigmoid" as
above, or "softmax" over all experts (Laguna's router: s = softmax(x
W_g), the rest as written).

One product (nn/helpers/pallas_moe.py): a kernel reads only the held
experts on the hit list, those some row chose (in the decode step,
some active row: an inactive row's routed part is 0), adds them in
ascending id order, and a row adds an exact 0 (a `where`) for a
listed expert it did not choose. Where every held expert is hit it
takes the time of einsums over every held expert to 1.7% at the
benchmark cells' shapes, and less for every expert it skips (PERF.md,
PR 39), so there is no second product to choose.

Per-row independence (the property the decode oracle's byte identity
rests on, engine/decode_program.py): no token is dropped and no
capacity is shared, and a row's result is a function of that row
alone, whatever the other rows route to: the other rows decide only
which zeros are added, so the engine's row and the oracle's agree bit
for bit though their lists differ.
With 16 experts of 94 MB held and 32 rows a step, the experts' weights
are what a step moves; the hit list reads the 9-10 of a layer that a
step's rows reach. With all 64 experts of 28 MB held and 128 rows a
step (benchmark cell `lfm2-moe-chat-closed128`: 8 rows an expert at
the mean, four fifths of the experts hit) the weights are still what a
step moves, and a listed expert runs over every row, 16 times the
operations the routing asked for (ROADMAP R11).
"""

from __future__ import annotations

# over the active rows of a step, summed over the expert layers: the
# token-expert pairs routed (all experts), those that fell on a held
# expert, the most loaded held expert's, the held experts that got at
# least one, and the held experts whose weights the layer read (the
# kernel's hit list: those hit)
COUNTERS = ("moe_assignments", "moe_assignments_held",
            "moe_max_held_load", "moe_experts_hit", "moe_experts_read")


def route(x, router_w, top_k: int, scale: float, bias=None,
          norm_eps: float = 0.0, score: str = "sigmoid"):
    """(expert ids [.., k], weights [.., k]) of each row: sigmoid
    scores over all experts in float32 at the highest matmul precision
    (2M parameters: nothing beside the experts, and a near-tie between
    the k-th and the next expert should turn on the stream's rounding,
    not on the router's own), the k largest, renormalised and scaled.
    `score="softmax"` takes a softmax over all experts' logits in place
    of the sigmoid (a Python string: the sigmoid's program is the one
    it always was). A `bias` [n_experts] moves the choice only: the k
    largest of `scores + bias` are kept, weighted by their own scores.
    `norm_eps` is added to the renormalisation's denominator where it
    is not 0 (a Python number: at 0 the traced program has no such
    add)."""
    import jax
    import jax.numpy as jnp

    if score not in ("sigmoid", "softmax"):
        raise ValueError(f"router scores are 'sigmoid' or 'softmax': "
                         f"{score!r}")
    squash = jax.nn.sigmoid if score == "sigmoid" \
        else lambda a: jax.nn.softmax(a, axis=-1)
    scores = squash(jnp.matmul(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    if bias is None:
        top_s, top_i = jax.lax.top_k(scores, top_k)
    else:
        _, top_i = jax.lax.top_k(scores + bias, top_k)
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    top_w = scale * top_s
    total = jnp.sum(top_s, axis=-1, keepdims=True)
    if norm_eps:
        total = total + norm_eps
    return top_i, top_w / total


def held_weights(top_i, top_w, held):
    """[.., k] routing -> [.., len(held)]: each row's weight for each
    held expert, 0 where the row did not choose it."""
    import jax.numpy as jnp

    ids = jnp.asarray(held, jnp.int32)
    hit = top_i[..., :, None] == ids                    # [.., k, E]
    return jnp.sum(jnp.where(hit, top_w[..., :, None], 0.0), axis=-2)


def expert_layer(lp: dict, x, held, top_k: int, scale: float,
                 active=None, norm_eps: float = 0.0, score: str = "sigmoid"):
    """x [N, h] (normed, float32) -> (y [N, h], counts). `lp` has the
    router `router` [h, n_experts] (and, where it has one, its
    selection bias `router_bias`), the held experts stacked in the
    order of `held` (`eg`, `eu` [E, h, f]; `ed` [E, f, h]) and, where
    the layer has one, the shared expert (`sg`, `su`, `sd`). `counts`
    is the int32 vector of COUNTERS over the rows `active` marks (None:
    no counts); `norm_eps` and `score` are `route`'s."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.attention import gated_mlp
    from deeplearning4j_tpu.nn.helpers.pallas_moe import (
        grouped_experts,
        hit_list,
    )

    with jax.named_scope("moe/router"):
        top_i, top_w = route(x, lp["router"], top_k, scale,
                             lp.get("router_bias"), norm_eps, score)
        w = held_weights(top_i, top_w, held)            # [N, E]
    with jax.named_scope("moe/experts"):
        if active is not None:
            # an inactive row's output goes to scratch: it adds nothing
            w = jnp.where(active[:, None], w, 0.0)
        ids, n_hit = hit_list(w)
        y = grouped_experts(x.astype(lp["eg"].dtype), w, ids, n_hit,
                            lp["eg"], lp["eu"], lp["ed"])
    if "sg" in lp:
        with jax.named_scope("moe/shared"):
            y = y + gated_mlp(x, lp["sg"], lp["su"], lp["sd"])
    if active is None:
        return y, None
    with jax.named_scope("moe/router"):
        load = jnp.sum(w > 0, axis=0, dtype=jnp.int32)  # [E]
        counts = jnp.stack([
            jnp.sum(active, dtype=jnp.int32) * top_k,
            jnp.sum(load), jnp.max(load),
            jnp.sum(load > 0, dtype=jnp.int32),
            n_hit[0]])
    return y, counts
