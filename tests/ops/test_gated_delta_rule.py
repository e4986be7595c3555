"""ops/gated_delta_rule.py: the chunked form over a packed ragged batch
against the token-by-token recurrence, and the convolution with its carried
state against one pass over the whole sequence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.ops import gated_delta_rule as gdr

HV, DK, DV = 4, 16, 32


def _inputs(key, t, decay=1.0):
    ks = jax.random.split(key, 5)
    q = gdr.l2_normalize(jax.random.normal(ks[0], (t, HV, DK))) * DK ** -0.5
    k = gdr.l2_normalize(jax.random.normal(ks[1], (t, HV, DK)))
    v = jax.random.normal(ks[2], (t, HV, DV))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (t, HV)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, HV)))
    return q, k, v, g, beta


def _pack(lens, align=8):
    """Segment starts as the runner packs them: aligned to 8 rows."""
    cu = [0]
    for n in lens:
        cu.append(cu[-1] + -(-n // align) * align)
    return cu


# tolerance: both sides are float32 with products at full precision; they
# differ by the order of summation over up to 64 tokens of a chunk and 150
# tokens of state, a few float32 roundings (1e-7 relative) on values of
# order 1.  A bfloat16 state (8 bits) would read 1e-2.
TOL = dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("lens", [[150, 1, 64, 7], [1, 1, 1], [65], [200, 3]])
def test_chunked_form_agrees_with_the_recurrence_on_ragged_segments(lens):
    """Lengths that are no multiple of 64, single-token rows between
    prefill rows, a padded tail, and more sequence slots than sequences;
    every sequence starts from a state of its own."""
    cu = _pack(lens)
    s_max = len(lens) + 2
    t = cu[-1] + 16
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(len(lens)), t)
    state = jax.random.normal(jax.random.PRNGKey(7), (s_max, HV, DK, DV))
    cu_arr = jnp.asarray(cu + [cu[-1]] * (s_max + 1 - len(cu)), jnp.int32)
    q_lens = jnp.asarray(lens + [0] * (s_max - len(lens)), jnp.int32)
    o, new_state = jax.jit(gdr.gated_delta_rule_ragged)(
        q, k, v, g, beta, state, cu_arr, q_lens)
    for i, n in enumerate(lens):
        sl = slice(cu[i], cu[i] + n)
        want_o, want_s = gdr.gated_delta_rule_scan(
            q[sl], k[sl], v[sl], g[sl], beta[sl], state[i])
        np.testing.assert_allclose(o[sl], want_o, **TOL)
        np.testing.assert_allclose(new_state[i], want_s, **TOL)
    # a slot with no sequence keeps its state to the bit
    np.testing.assert_array_equal(new_state[len(lens):], state[len(lens):])


def test_a_chunk_whose_decay_underflows_gives_zero_not_nan():
    """exp(cumsum(g)) over 64 tokens of g = -8 is exp(-512): zero in
    float32.  The chunked form only ever exponentiates differences, so the
    outputs stay finite and equal the recurrence's."""
    lens = [130]
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(3), 136, decay=8.0)
    assert float(jnp.sum(g[:64, 0])) < -300
    state = jnp.ones((1, HV, DK, DV))
    o, new_state = gdr.gated_delta_rule_ragged(
        q, k, v, g, beta, state, jnp.asarray([0, 136], jnp.int32),
        jnp.asarray(lens, jnp.int32))
    want_o, want_s = gdr.gated_delta_rule_scan(
        q[:130], k[:130], v[:130], g[:130], beta[:130], state[0])
    assert bool(jnp.all(jnp.isfinite(o)))
    np.testing.assert_allclose(o[:130], want_o, **TOL)
    np.testing.assert_allclose(new_state[0], want_s, **TOL)


def _conv_whole(x, w):
    """sum of K shifted products over one whole sequence, zeros before."""
    k = w.shape[0]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(xp[j:j + x.shape[0]] * w[j] for j in range(k))


def test_convolution_carries_its_state_across_steps_and_rows():
    """Two sequences convolved in three packed steps (a long chunk, a
    chunk shorter than the kernel, single tokens through the decode form)
    equal one pass over each whole sequence."""
    c, k = 24, 4
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    w = jax.random.normal(kw, (k, c))
    xs = [jax.random.normal(jax.random.fold_in(kx, i), (n, c))
          for i, n in enumerate((21, 14))]
    want = [_conv_whole(x, w) for x in xs]
    state = jnp.zeros((3, k - 1, c))
    got = [[], []]
    # steps: (tokens of sequence 0, tokens of sequence 1)
    done = [0, 0]
    for step in ((17, 9), (2, 1), (1, 3)):
        cu = _pack(step)
        x = jnp.zeros((cu[-1] + 8, c))
        for i, n in enumerate(step):
            x = x.at[cu[i]:cu[i] + n].set(xs[i][done[i]:done[i] + n])
        y, state = gdr.causal_conv_ragged(
            x, w, state, jnp.asarray(cu + [cu[-1]], jnp.int32),
            jnp.asarray(list(step) + [0], jnp.int32))
        for i, n in enumerate(step):
            got[i].append(y[cu[i]:cu[i] + n])
            done[i] += n
    # the last token of each through the one-token form
    x = jnp.stack([xs[0][done[0]], xs[1][done[1]]])
    y, new_state = gdr.causal_conv_update(x, w, state[:2])
    for i in range(2):
        got[i].append(y[i:i + 1])
        np.testing.assert_allclose(jnp.concatenate(got[i]), want[i],
                                   atol=1e-5, rtol=1e-5)
        # the state is the last K - 1 inputs, oldest first
        np.testing.assert_array_equal(new_state[i],
                                      xs[i][done[i] - 2:done[i] + 1])


def test_the_gathered_history_is_the_scattered_one_and_stays_in_range():
    """``carried="gather"`` (the gated short convolution's form: PERF.md,
    PR 39) gives the rows and the state that the scatter-add gives, on
    sequences shorter than the kernel, empty slots and a packed tail of
    padding, and every index it forms lies inside its array (the scatter's
    sentinel row does not: ``checkify`` reads it as out of bounds)."""
    from jax.experimental import checkify

    c, k = 16, 3
    step = (1, 5, 1, 2, 0, 0)
    cu = _pack(step)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (cu[-1] + 8, c))
    w = jax.random.normal(keys[1], (k, c))
    state = jax.random.normal(keys[2], (len(step), k - 1, c))
    args = (x, w, state, jnp.asarray(cu, jnp.int32),
            jnp.asarray(step, jnp.int32))
    want_y, want_state = gdr.causal_conv_ragged(*args)
    err, (y, new_state) = checkify.checkify(
        lambda *a: gdr.causal_conv_ragged(*a, carried="gather"),
        errors=checkify.index_checks)(*args)
    assert err.get() is None, err.get()
    real = np.zeros(x.shape[0], bool)
    for start, n in zip(cu, step):
        real[start:start + n] = True
    np.testing.assert_allclose(y[real], want_y[real], atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(new_state, want_state)
    assert bool(jnp.isfinite(y).all())
    # a one-token sequence: the taps on (carried, carried, token)
    np.testing.assert_allclose(
        y[cu[0]], state[0, 0] * w[0] + state[0, 1] * w[1] + x[cu[0]] * w[2],
        atol=1e-5, rtol=1e-5)
    err, _ = checkify.checkify(gdr.causal_conv_ragged,
                               errors=checkify.index_checks)(*args)
    assert "out-of-bounds" in str(err.get())
