"""nn/delta_attention.py: the chunk form of the gated delta rule
against the recurrence it stands for, and what rows that are not real
leave alone. Small sizes, float32, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import delta_attention as da

T, H, D = 16, 2, 8


def _inputs(seed, t=T, decay=3.0):
    """q, k of unit length as the layer makes them, decays down to
    e^-9 a token: a chunk's running decay leaves float32's range."""
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    q = unit(rng.normal(size=(t, H, D))) / np.sqrt(D)
    k = unit(rng.normal(size=(t, H, D)))
    v = rng.normal(size=(t, H, D))
    g = -np.abs(rng.normal(size=(t, H, D))) * decay
    beta = rng.uniform(size=(t, H))
    s0 = rng.normal(size=(H, D, D))
    return tuple(f32(a) for a in (q, k, v, g, beta)), f32(s0)


def _iterated(xs, s0, n_state):
    """`kda_step` a token at a time: every row answered from the state
    that absorbed the rows before it and itself, the state kept after
    the first `n_state`."""
    q, k, v, g, beta = xs
    s, outs, kept = s0[None], [], s0
    on = jnp.array([True])
    for t in range(q.shape[0]):
        o, s = da.kda_step(q[t][None], k[t][None], v[t][None], g[t][None],
                           beta[t][None], s, on)
        outs.append(o[0])
        if t + 1 == n_state:
            kept = s[0]
    return jnp.stack(outs), kept


chunk = jax.jit(da.kda_chunk, static_argnames="sub")


@pytest.mark.parametrize("sub", [2, 4, 16])
@pytest.mark.parametrize("n_state", [T, 7, 1])
@pytest.mark.parametrize("from_zero", [True, False],
                         ids=["from_zero", "from_a_state"])
def test_chunk_form_is_the_recurrence_iterated(from_zero, n_state, sub):
    """Outputs of the rows up to and with row `n_state` and the state
    after `n_state` rows, to float32 rounding (sums of 16 terms of
    order one: 1e-5 is a hundred ulps), at decays of up to e^-9 a
    token, where e^{-G} of the whole chunk is past float32."""
    xs, s0 = _inputs(3)
    if from_zero:
        s0 = jnp.zeros_like(s0)
    want_o, want_s = _iterated(xs, s0, n_state)
    o, s = chunk(*xs, s0, jnp.int32(n_state), sub=sub)
    rows = min(n_state + 1, T)
    np.testing.assert_allclose(o[:rows], want_o[:rows], atol=1e-5)
    np.testing.assert_allclose(s, want_s, atol=1e-5)


def test_strong_decay_does_not_overflow():
    """128 tokens at a decay of e^-30 a token a channel: the running
    sum reaches -3,840 and no factor is formed from its negative."""
    xs, s0 = _inputs(5, t=128, decay=30.0)
    o, s = chunk(*xs, s0, jnp.int32(128), sub=16)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(s)))
    want_o, want_s = _iterated(xs, s0, 128)
    # the running sum is float32: at 3,840 its last place is 2.4e-4,
    # and that is the relative error of a factor e^{G_i - G_j}
    np.testing.assert_allclose(o, want_o, rtol=2e-3, atol=1e-4)
    np.testing.assert_allclose(s, want_s, rtol=2e-3, atol=1e-4)


def test_a_chunk_of_no_rows_is_the_identity():
    """`n_state` 0 (a prompt of one token): the state comes back
    bitwise, and so does the convolution's tail."""
    xs, s0 = _inputs(7)
    _, s = chunk(*xs, s0, jnp.int32(0), sub=4)
    assert np.array_equal(np.asarray(s), np.asarray(s0))
    lp, qkv, tail = _conv_case(7)
    *_, after = da.conv_chunk(lp, qkv, tail, jnp.int32(0), H)
    assert np.array_equal(np.asarray(after), np.asarray(tail))


def test_pad_rows_change_neither_state_nor_outputs_before_them():
    """Rows past `n_state` are padding: whatever they hold, the state
    after `n_state` rows and the outputs up to row `n_state` are the
    same bits."""
    xs, s0 = _inputs(9)
    n = 5
    junk = tuple(a.at[n + 1:].set(a[n + 1:] * -7.0 + 3.0) for a in xs)
    o1, s1 = chunk(*xs, s0, jnp.int32(n), sub=4)
    o2, s2 = chunk(*junk, s0, jnp.int32(n), sub=4)
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    assert np.array_equal(np.asarray(o1[:n + 1]), np.asarray(o2[:n + 1]))


def _conv_case(seed, t=T):
    rng = np.random.default_rng(seed)
    c = H * D
    lp = {f"conv_{n}": jnp.asarray(rng.normal(size=(4, c)), jnp.float32)
          for n in "qkv"}
    return (lp, jnp.asarray(rng.normal(size=(t, 3 * c)), jnp.float32),
            jnp.asarray(rng.normal(size=(3, 3 * c)), jnp.float32))


@pytest.mark.parametrize("n_state", [0, 1, 2, 3, 9, T])
def test_convolution_chunk_is_the_step_iterated(n_state):
    """The causal convolution over a chunk against a token at a time,
    and the tail after `n_state` rows: the last three inputs before
    row `n_state`, from the old tail where the chunk has fewer."""
    lp, qkv, tail = _conv_case(11)
    q, k, v, after = da.conv_chunk(lp, qkv, tail, jnp.int32(n_state), H)
    on, cur, want = jnp.array([True]), tail[None], tail
    for t in range(T):
        qt, kt, vt, cur = da.conv_step(lp, qkv[t][None], cur, on, H)
        np.testing.assert_allclose(q[t], qt[0], atol=1e-6)
        np.testing.assert_allclose(k[t], kt[0], atol=1e-6)
        np.testing.assert_allclose(v[t], vt[0], atol=1e-6)
        if t + 1 == n_state:
            want = cur[0]
    assert np.array_equal(np.asarray(after), np.asarray(want))


def test_step_leaves_a_masked_row_as_it_was():
    """A row the mask leaves out keeps its state and its tail bitwise,
    NaN in its inputs or not, and changes no other row."""
    xs, s0 = _inputs(13, t=3)
    s = jnp.stack([s0, s0 * 2.0, s0 * 3.0])
    on = jnp.array([True, False, True])
    o, new = da.kda_step(*xs, s, on)
    poisoned = tuple(a.at[1].set(jnp.nan) for a in xs)
    o2, new2 = da.kda_step(*poisoned, s, on)
    assert np.array_equal(np.asarray(new[1]), np.asarray(s[1]))
    assert np.array_equal(np.asarray(new2[1]), np.asarray(s[1]))
    for row in (0, 2):
        assert np.array_equal(np.asarray(new[row]), np.asarray(new2[row]))
        assert np.array_equal(np.asarray(o[row]), np.asarray(o2[row]))
        assert not np.array_equal(np.asarray(new[row]), np.asarray(s[row]))
    lp, qkv, tail = _conv_case(13, t=3)
    tails = jnp.stack([tail, tail * 2.0, tail * 3.0])
    *_, after = da.conv_step(lp, qkv, tails, on, H)
    assert np.array_equal(np.asarray(after[1]), np.asarray(tails[1]))
    assert np.array_equal(np.asarray(after[0, -1]), np.asarray(qkv[0]))
