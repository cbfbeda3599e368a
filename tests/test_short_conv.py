"""The gated short convolution (nn/short_conv.py): the step form and
the chunk form over a per-slot tail against the convolution written as
three shifted products of the whole sequence, what a chunk's returned
tail holds, and the rows that are not real."""

import numpy as np
import pytest

from deeplearning4j_tpu.nn import short_conv as sc

pytestmark = pytest.mark.serving

H, TAPS, PAGE = 16, 3, 128
EPS = 1e-5


def _layer(seed=0):
    import jax

    key = jax.random.PRNGKey(seed)
    n = lambda i, shape: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape)
    return {"norm_in": 1.0 + 0.1 * n(0, (H,)),
            "w_in": n(1, (H, 3 * H)) / np.sqrt(H),
            "conv_w": n(2, (TAPS, H)), "w_out": n(3, (H, H)) / np.sqrt(H)}


def _plain(lp, x):
    """The layer over a whole sequence [T, h] as the equations have it:
    three shifted products of `B * z` behind two rows of zeros."""
    import jax.numpy as jnp

    xn = x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                      + EPS) * lp["norm_in"]
    b, c, z = jnp.split(xn @ lp["w_in"], 3, axis=-1)
    s = b * z
    t = x.shape[0]
    pad = jnp.pad(s, ((TAPS - 1, 0), (0, 0)))
    conv = sum(lp["conv_w"][j] * pad[j:j + t] for j in range(TAPS))
    return (c * conv) @ lp["w_out"], s


def _stream(t, seed=1):
    import jax

    return jax.random.normal(jax.random.PRNGKey(seed), (t, H))


@pytest.mark.parametrize("n_prompt", [1, 2, 3, 127, 128, 129])
def test_chunks_then_steps_are_the_three_shifted_products(n_prompt):
    """A prompt of `n_prompt` tokens by chunks of 128 (the last padded,
    the tail told to absorb all but the prompt's last token, as the
    engine has it), then that token and eight more by the step form:
    every answered row is the whole-sequence convolution's, and the
    tail is always the last two rows of `s`."""
    import jax.numpy as jnp

    lp = _layer()
    total = n_prompt + 8
    x = _stream(total)
    want, s = _plain(lp, x)
    tail = jnp.zeros((TAPS - 1, H))
    got = np.zeros((total, H), np.float32)
    for start in range(0, n_prompt, PAGE):
        rows = min(PAGE, n_prompt - start)
        chunk = jnp.zeros((PAGE, H)).at[:rows].set(x[start:start + rows])
        n_state = max(0, min(PAGE, n_prompt - 1 - start))
        out, tail = sc.chunk_mix(lp, chunk, tail, n_state, EPS)
        got[start:start + rows] = np.asarray(out[:rows])
        absorbed = start + n_state
        keep = np.zeros((TAPS - 1, H), np.float32)
        have = np.asarray(s[max(0, absorbed - 2):absorbed])
        keep[TAPS - 1 - len(have):] = have
        np.testing.assert_allclose(np.asarray(tail), keep, atol=1e-6)
    np.testing.assert_allclose(got[:n_prompt], np.asarray(want[:n_prompt]),
                               atol=2e-5)
    # the first-token step absorbs the prompt's last token, the rest
    # one token a step; a second row of the batch sits the steps out
    state = jnp.zeros((1, 2, TAPS - 1, H)).at[0, 0].set(tail) \
        .at[0, 1].set(7.0)
    active = jnp.asarray([True, False])
    for pos in range(n_prompt - 1, total):
        out, state = sc.decode_mix(
            lp, jnp.stack([x[pos], x[pos]]), state, 0, active, EPS)
        np.testing.assert_allclose(np.asarray(out[0]),
                                   np.asarray(want[pos]), atol=2e-5)
        np.testing.assert_allclose(np.asarray(state[0, 0]),
                                   np.asarray(s[pos - 1:pos + 1])
                                   if pos else np.asarray(
                                       jnp.pad(s[:1], ((1, 0), (0, 0)))),
                                   atol=1e-6)
    # the row the mask left out kept its entry
    assert bool(jnp.all(state[0, 1] == 7.0))


def test_step_equals_chunk_row_for_row():
    """The same tokens by the step form and by one chunk from the same
    tail: the same outputs and the same tail after them."""
    import jax.numpy as jnp

    lp = _layer(3)
    x = _stream(16, seed=4)
    tail0 = _stream(TAPS - 1, seed=5)
    out_c, tail_c = sc.chunk_mix(lp, x, tail0, 16, EPS)
    state = tail0[None, None]
    outs = []
    for t in range(16):
        o, state = sc.decode_mix(lp, x[t:t + 1], state, 0,
                                 jnp.asarray([True]), EPS)
        outs.append(o[0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs)),
                               np.asarray(out_c), atol=2e-5)
    np.testing.assert_allclose(np.asarray(state[0, 0]), np.asarray(tail_c),
                               atol=1e-6)


def test_pad_rows_stay_out_of_the_tail_and_rows_are_answered_alike():
    """What lies past `n_state` in a chunk never reaches the returned
    tail, whatever it is; the rows before it are answered the same."""
    import jax.numpy as jnp

    lp = _layer(6)
    x = _stream(16, seed=7)
    junk = x.at[5:].set(1e3)
    tail0 = jnp.zeros((TAPS - 1, H))
    out_a, tail_a = sc.chunk_mix(lp, x, tail0, 5, EPS)
    out_b, tail_b = sc.chunk_mix(lp, junk, tail0, 5, EPS)
    np.testing.assert_array_equal(np.asarray(tail_a), np.asarray(tail_b))
    np.testing.assert_array_equal(np.asarray(out_a[:5]),
                                  np.asarray(out_b[:5]))
    _, s = _plain(lp, x)
    np.testing.assert_allclose(np.asarray(tail_a), np.asarray(s[3:5]),
                               atol=1e-6)
    # nothing absorbed: the tail comes back as it was found
    _, same = sc.chunk_mix(lp, junk, tail_a, 0, EPS)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(tail_a))


def test_a_chunk_at_position_zero_starts_from_zero():
    """The program hands a chunk at position 0 a zero tail whatever the
    slot held (engine/decode_program.py's select): from a zero tail the
    chunk is the whole-sequence convolution, from another it is not."""
    import jax.numpy as jnp

    lp = _layer(8)
    x = _stream(8, seed=9)
    want, _ = _plain(lp, x)
    out, _ = sc.chunk_mix(lp, x, jnp.zeros((TAPS - 1, H)), 8, EPS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    stale, _ = sc.chunk_mix(lp, x, jnp.ones((TAPS - 1, H)), 8, EPS)
    assert float(jnp.max(jnp.abs(stale[:2] - want[:2]))) > 1e-2
    np.testing.assert_allclose(np.asarray(stale[2:]), np.asarray(want[2:]),
                               atol=2e-5)


def test_state_shape_leads_with_layer_and_slot():
    assert sc.state_shape(7, 128, 3, 2048) == (7, 128, 2, 2048)
