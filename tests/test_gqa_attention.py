"""Grouped-query attention over paged token rows
(nn/gqa_attention.py): the decode and the chunk form against plain
causal attention with a norm a head and rotary positions, at groups of
1 and 4 with the window across pages, and the pin that with one query
head a K/V head both forms ARE nn/attention.py's, bit for bit."""

import numpy as np
import pytest

from deeplearning4j_tpu.nn import attention as old
from deeplearning4j_tpu.nn import gqa_attention as gqa

pytestmark = pytest.mark.serving

HID, D, PAGE = 32, 8, 8
THETA, EPS = 1e6, 1e-5


def _layer(n_heads, n_kv, seed=0):
    import jax

    key = jax.random.PRNGKey(seed)
    n = lambda i, shape: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape)
    return {"norm_in": 1.0 + 0.1 * n(0, (HID,)),
            "wq": n(1, (HID, n_heads * D)) / np.sqrt(HID),
            "wk": n(2, (HID, n_kv * D)) / np.sqrt(HID),
            "wv": n(3, (HID, n_kv * D)) / np.sqrt(HID),
            "q_norm": 1.0 + 0.1 * n(4, (D,)),
            "k_norm": 1.0 + 0.1 * n(5, (D,))}


def _plain(lp, x, n_heads, n_kv):
    """Causal attention over a whole sequence [T, h] as the equations
    have it: keys and values repeated over their group, per-head norms,
    half-split rotary pairs. Returns merged heads [T, H * D]."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    rms = lambda a, g: a / jnp.sqrt(  # noqa: E731
        jnp.mean(jnp.square(a), axis=-1, keepdims=True) + EPS) * g
    u = rms(x, lp["norm_in"])
    q = rms((u @ lp["wq"]).reshape(t, n_heads, D), lp["q_norm"])
    k = rms((u @ lp["wk"]).reshape(t, n_kv, D), lp["k_norm"])
    v = (u @ lp["wv"]).reshape(t, n_kv, D)

    def rot(a):
        half = D // 2
        inv = THETA ** (-jnp.arange(half) / half)
        ang = jnp.arange(t)[:, None] * inv
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        a1, a2 = a[..., :half], a[..., half:]
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], -1)

    q, k = rot(q), rot(k)
    k, v = (jnp.repeat(a, n_heads // n_kv, axis=1) for a in (k, v))
    s = jnp.einsum("thd,uhd->htu", q, k) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    out = jnp.einsum("htu,uhd->thd", jax.nn.softmax(s, axis=-1), v)
    return out.reshape(t, n_heads * D)


def _stream(t, seed=1):
    import jax

    return jax.random.normal(jax.random.PRNGKey(seed), (t, HID))


@pytest.mark.parametrize("n_heads,n_kv", [(4, 4), (8, 2)],
                         ids=["group1", "group4"])
def test_chunks_then_decode_are_plain_attention(n_heads, n_kv):
    """21 positions by chunks of a page (the last padded) over the rows
    the chunks before it wrote, then 14 more one at a time over a window
    of five pages of which the last cells are dead: every row is plain
    causal attention's, with keys normed and rotated before they are
    stored."""
    import jax.numpy as jnp

    lp = _layer(n_heads, n_kv)
    n_prompt, total, cells = 21, 35, 5 * PAGE
    x = _stream(total)
    want = np.asarray(_plain(lp, x, n_heads, n_kv))
    k_pool = jnp.full((cells, n_kv * D), jnp.nan)    # dead cells: garbage
    v_pool = jnp.full((cells, n_kv * D), jnp.nan)
    for start in range(0, n_prompt, PAGE):
        rows = min(PAGE, n_prompt - start)
        chunk = jnp.zeros((PAGE, HID)).at[:rows].set(x[start:start + rows])
        q, (k, v) = gqa.project(lp, chunk, start + jnp.arange(PAGE),
                                n_heads, n_kv, THETA, EPS)
        att = gqa.gqa_chunk_attention(q, k, v, k_pool, v_pool, start, n_kv)
        np.testing.assert_allclose(np.asarray(att[:rows]),
                                   want[start:start + rows], atol=2e-5)
        k_pool = k_pool.at[start:start + rows].set(k[:rows])
        v_pool = v_pool.at[start:start + rows].set(v[:rows])
    for pos in range(n_prompt, total):
        q, (k, v) = gqa.project(lp, x[pos:pos + 1], jnp.asarray([pos]),
                                n_heads, n_kv, THETA, EPS)
        k_pool = k_pool.at[pos].set(k[0])
        v_pool = v_pool.at[pos].set(v[0])
        att = gqa.gqa_decode_attention(q, k_pool[None], v_pool[None],
                                       jnp.asarray([pos + 1]), n_kv)
        np.testing.assert_allclose(np.asarray(att[0]), want[pos], atol=2e-5)


def test_the_stored_key_is_normed_and_rotated_by_the_logical_position():
    """A key's row depends on its own token and position alone, and the
    position is logical: past any window the rotation goes on, and only
    differences of positions reach a score."""
    import jax.numpy as jnp

    lp = _layer(8, 2)
    x = _stream(2, seed=3)
    q0, (k0, _) = gqa.project(lp, x, jnp.asarray([3, 1]), 8, 2, THETA, EPS)
    q1, (k1, _) = gqa.project(lp, x, jnp.asarray([5003, 5001]), 8, 2,
                              THETA, EPS)
    assert float(jnp.max(jnp.abs(k0 - k1))) > 1e-3
    k0, k1 = (jnp.repeat(a.reshape(2, 2, D), 4, axis=1) for a in (k0, k1))
    np.testing.assert_allclose(
        np.asarray(jnp.sum(q0[0] * k0[1], axis=-1)),
        np.asarray(jnp.sum(q1[0] * k1[1], axis=-1)), atol=2e-3)
    # each head's key has the norm's length, sqrt(D) times the gain
    norms = jnp.sqrt(jnp.mean(jnp.square(k0[:, 0]), axis=-1))
    assert float(jnp.max(jnp.abs(norms - 1.0))) < 0.3


def _old_case(seed=5, s=3, h=4, n=3 * PAGE):
    import jax

    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(key, 0), (s, h, D))
    k_rows = jax.random.normal(jax.random.fold_in(key, 1), (s, n, h * D))
    v_rows = jax.random.normal(jax.random.fold_in(key, 2), (s, n, h * D))
    return q, k_rows, v_rows


def test_a_group_of_one_is_todays_decode_primitive_bitwise():
    """One query head a K/V head: `gqa_decode_attention` gives
    `paged_decode_attention`'s result bit for bit, jitted as the
    programs run them (float32 rows, as GPT-2's pool stores them)."""
    import jax
    import jax.numpy as jnp

    q, k_rows, v_rows = _old_case()
    live = jnp.asarray([1, 11, 3 * PAGE])
    want = jax.jit(old.paged_decode_attention)(q, k_rows, v_rows, live)
    got = jax.jit(lambda *a: gqa.gqa_decode_attention(*a, 4))(
        q, k_rows, v_rows, live)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_group_of_one_is_todays_chunk_primitive_bitwise():
    import jax
    import jax.numpy as jnp

    _, k_rows, v_rows = _old_case(seed=6)
    key = jax.random.PRNGKey(7)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (PAGE, 4, D))
               for i in range(3))
    for n_prior in (0, 5, 2 * PAGE):
        want = jax.jit(old.chunk_prefill_attention)(
            q, k, v, k_rows[0], v_rows[0], jnp.int32(n_prior))
        got = jax.jit(lambda q, k, v, kr, vr, n: gqa.gqa_chunk_attention(
            q, old.merge_heads(k), old.merge_heads(v), kr, vr, n, 4))(
            q, k, v, k_rows[0], v_rows[0], jnp.int32(n_prior))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bfloat16_rows_are_never_raised_and_stay_near_float32():
    """A bfloat16 window goes into both contractions as stored: no
    float32 array of the window's shape is made, and the result is the
    float32 one to bfloat16's rounding."""
    import jax
    import jax.numpy as jnp

    q, k_rows, v_rows = _old_case(seed=8, h=8, n=4 * PAGE)
    k_rows, v_rows = k_rows[..., :2 * D], v_rows[..., :2 * D]
    live = jnp.asarray([7, 20, 4 * PAGE])
    want = gqa.gqa_decode_attention(q, k_rows, v_rows, live, 2)
    fn = lambda q, k, v: gqa.gqa_decode_attention(  # noqa: E731
        q, k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), live, 2)
    got = fn(q, k_rows, v_rows)
    assert str(got.dtype) == "float32"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.06)
    window = tuple(k_rows.shape)
    raised = [v for eqn in jax.make_jaxpr(
        lambda q, k, v: gqa.gqa_decode_attention(q, k, v, live, 2))(
            q, k_rows.astype(jnp.bfloat16),
            v_rows.astype(jnp.bfloat16)).jaxpr.eqns
        for v in eqn.outvars
        if tuple(v.aval.shape) == window and str(v.aval.dtype) == "float32"]
    assert not raised
