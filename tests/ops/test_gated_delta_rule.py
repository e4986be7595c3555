"""ops/gated_delta_rule.py: the chunked form over a packed ragged batch
(the ``jax.numpy`` form, and the kernel in interpret mode at a head of 128
lanes) against the token-by-token recurrence, and the convolution with its
carried state against one pass over the whole sequence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.ops import gated_delta_rule as gdr

#: (Hv, Dk, Dv) a form is tested at: the kernel takes heads of whole
#: 128-lane tiles, all of them in a grid step or eight
DIMS = {"jax_numpy": (4, 16, 32), "kernel": (2, 128, 128),
        "kernel_two_blocks": (16, 128, 128)}
FORMS = {
    "jax_numpy": jax.jit(gdr.gated_delta_rule_ragged),
    "kernel": lambda *a: gdr.gated_delta_rule_ragged(*a, use_pallas=True),
}
FORMS["kernel_two_blocks"] = FORMS["kernel"]


def _inputs(key, t, decay=1.0, dims=DIMS["jax_numpy"]):
    hv, dk, dv = dims
    ks = jax.random.split(key, 5)
    q = gdr.l2_normalize(jax.random.normal(ks[0], (t, hv, dk))) * dk ** -0.5
    k = gdr.l2_normalize(jax.random.normal(ks[1], (t, hv, dk)))
    v = jax.random.normal(ks[2], (t, hv, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (t, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, hv)))
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


@pytest.mark.parametrize("form,lens", [
    ("jax_numpy", [150, 1, 64, 7]), ("jax_numpy", [1, 1, 1]),
    ("jax_numpy", [65]), ("jax_numpy", [200, 3]),
    # the kernel: every length around a chunk's 64 in one batch; a decode
    # row between two sequences of several chunks; decode rows alone; a
    # sequence with no token between two that have some; a grid over heads
    ("kernel", [1, 8, 63, 64, 65, 200]), ("kernel", [150, 1, 64, 7]),
    ("kernel", [1, 1, 1]), ("kernel", [70, 0, 3]),
    ("kernel_two_blocks", [65, 1, 70]),
])
def test_chunked_form_agrees_with_the_recurrence_on_ragged_segments(
        form, lens):
    """Lengths that are no multiple of 64, single-token rows between
    prefill rows, a padded tail (so chunk slots that no chunk uses), and
    more sequence slots than sequences; every sequence starts from a state
    of its own.  The kernel also agrees with the ``jax.numpy`` form it
    replaces where the platform compiles it."""
    dims = DIMS[form]
    cu = _pack(lens)
    s_max = len(lens) + 2
    t = cu[-1] + 16
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(len(lens)), t, dims=dims)
    state = jax.random.normal(jax.random.PRNGKey(7), (s_max, *dims))
    cu_arr = jnp.asarray(cu + [cu[-1]] * (s_max + 1 - len(cu)), jnp.int32)
    q_lens = jnp.asarray(lens + [0] * (s_max - len(lens)), jnp.int32)
    o, new_state = FORMS[form](q, k, v, g, beta, state, cu_arr, q_lens)
    if form != "jax_numpy":
        plain_o, plain_s = FORMS["jax_numpy"](
            q, k, v, g, beta, state, cu_arr, q_lens)
        np.testing.assert_allclose(new_state, plain_s, **TOL)
    for i, n in enumerate(lens):
        sl = slice(cu[i], cu[i] + n)
        if n == 0:
            np.testing.assert_array_equal(new_state[i], state[i])
            continue
        want_o, want_s = gdr.gated_delta_rule_scan(
            q[sl], k[sl], v[sl], g[sl], beta[sl], state[i])
        np.testing.assert_allclose(o[sl], want_o, **TOL)
        np.testing.assert_allclose(new_state[i], want_s, **TOL)
        if form != "jax_numpy":
            np.testing.assert_allclose(o[sl], plain_o[sl], **TOL)
    # a slot with no sequence keeps its state to the bit
    np.testing.assert_array_equal(new_state[len(lens):], state[len(lens):])
    assert bool(jnp.isfinite(o).all())


@pytest.mark.parametrize("form", ["jax_numpy", "kernel"])
def test_a_chunk_whose_decay_underflows_gives_zero_not_nan(form):
    """exp(cumsum(g)) over 64 tokens of g = -8 is exp(-512): zero in
    float32.  The chunked form only ever exponentiates differences, so the
    outputs stay finite and equal the recurrence's."""
    lens = [130]
    dims = DIMS[form]
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(3), 136, decay=8.0,
                               dims=dims)
    assert float(jnp.sum(g[:64, 0])) < -300
    state = jnp.ones((1, *dims))
    o, new_state = FORMS[form](
        q, k, v, g, beta, state, jnp.asarray([0, 136], jnp.int32),
        jnp.asarray(lens, jnp.int32))
    want_o, want_s = gdr.gated_delta_rule_scan(
        q[:130], k[:130], v[:130], g[:130], beta[:130], state[0])
    assert bool(jnp.all(jnp.isfinite(o)))
    np.testing.assert_allclose(o[:130], want_o, **TOL)
    np.testing.assert_allclose(new_state[0], want_s, **TOL)


@pytest.mark.parametrize("form", ["jax_numpy", "kernel"])
def test_a_run_of_one_key_is_solved_as_the_recurrence_solves_it(form):
    """One token repeated gives every key of a chunk the same direction:
    with beta near 1 and hardly any decay ``A`` is all ones under the
    diagonal, its powers grow to 1e17 and ``(I + A)^-1 = (I - A)(I + A^2)
    ... (I + A^32)``, exact on paper, is off by 1e10 in float32.
    Substitution and the block merges stay at the recurrence's rounding."""
    dims = DIMS[form]
    t, n = 136, 130
    q, k, v, _, _ = _inputs(jax.random.PRNGKey(5), t, dims=dims)
    k = jnp.broadcast_to(k[:1], k.shape)
    g = jnp.full((t, dims[0]), -1e-3)
    beta = jnp.full((t, dims[0]), 0.999)
    state = jax.random.normal(jax.random.PRNGKey(6), (1, *dims))
    o, new_state = FORMS[form](
        q, k, v, g, beta, state, jnp.asarray([0, t], jnp.int32),
        jnp.asarray([n], jnp.int32))
    want_o, want_s = gdr.gated_delta_rule_scan(
        q[:n], k[:n], v[:n], g[:n], beta[:n], state[0])
    np.testing.assert_allclose(o[:n], want_o, **TOL)
    np.testing.assert_allclose(new_state[0], want_s, **TOL)


@pytest.mark.parametrize("dims,state_dtype,chunk,why", [
    (DIMS["kernel"], jnp.float32, 64, ""),
    (DIMS["jax_numpy"], jnp.float32, 64, "off the 128-lane tiling"),
    (DIMS["kernel"], jnp.bfloat16, 64, "not float32"),
    (DIMS["kernel"], jnp.float32, 48, "no power of two"),
])
def test_where_pallas_compiles_only_a_shape_the_kernel_refuses_falls_back(
        dims, state_dtype, chunk, why, monkeypatch):
    """On a platform that compiles Pallas the kernel takes every call it
    can; one it cannot runs the ``jax.numpy`` form and is counted as a
    native fallback of ``"gated_delta_rule"`` with the reason."""
    from vllm_omni_tpu.ops import _dispatch

    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
    monkeypatch.setattr(_dispatch, "native_fallbacks",
                        type(_dispatch.native_fallbacks)())
    taken = []
    monkeypatch.setattr(gdr, "_chunked_kernel",
                        lambda *a, **kw: taken.append(kw) or "kernel")
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(1), 72, dims=dims)
    state = jnp.zeros((1, *dims), state_dtype)
    got = gdr.gated_delta_rule_ragged(
        q, k, v, g, beta, state, jnp.asarray([0, 72], jnp.int32),
        jnp.asarray([70], jnp.int32), chunk=chunk)
    reasons = [r for (op, r) in _dispatch.native_fallbacks
               if op == "gated_delta_rule"]
    if not why:
        assert got == "kernel" and taken == [{"chunk": chunk}]
        assert not reasons
    else:
        assert not taken and got[0].shape == (72, dims[0], dims[2])
        assert len(reasons) == 1 and why in reasons[0]


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
