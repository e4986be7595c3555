"""ops/ssd.py: the chunked form over a packed ragged batch and the one-token
form, both in place on the pool of slots, against the token-by-token scan;
the recurrent kernel (interpret mode) against the gather form; the
convolution with its bias across steps; ``chunk_slots`` as counted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.ops import ssd

H, P, N = 4, 8, 128
SLOTS = 6

# tolerance: both sides are float32 with products at full precision; they
# differ by the order of summation over up to a chunk's tokens and by
# exp(x) exp(y) against exp(x + y), a few float32 roundings (1e-7 relative)
# on values of order 1 to 10.  A bfloat16 state (8 bits) would read 1e-2.
TOL = dict(atol=5e-5, rtol=5e-5)


def _inputs(key, t, decay=1.0):
    ks = jax.random.split(key, 5)
    u = jax.random.normal(ks[0], (t, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, H)) - 2.0)
    a = -decay * jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0,
                                            maxval=2.7)) * dt
    b = jax.random.normal(ks[3], (t, N)) * N ** -0.5
    c = jax.random.normal(ks[4], (t, N))
    return u, dt, a, b, c


def _pack(lens, align=8):
    """Segment starts as the runner packs them: aligned to 8 rows."""
    cu = [0]
    for n in lens:
        cu.append(cu[-1] + -(-n // align) * align)
    return cu


def _ragged_case(lens, slots, fresh, s_max=None, tail=16, chunk=16,
                 use_pallas=False, seed=0):
    """Run ``ssd_ragged`` on sequences of ``lens`` tokens in ``slots`` and
    check outputs, touched slots and untouched slots against the scan."""
    cu = _pack(lens)
    s_max = s_max or len(lens) + 2
    t = cu[-1] + tail
    x = _inputs(jax.random.PRNGKey(seed + len(lens)), t)
    pool = jax.random.normal(jax.random.PRNGKey(7), (SLOTS, H, P, N))
    pad = s_max - len(lens)
    y, new = ssd.ssd_ragged(
        *x, pool, jnp.asarray(slots + [SLOTS] * pad, jnp.int32),
        jnp.asarray(fresh + [False] * pad),
        jnp.asarray(cu + [cu[-1]] * pad, jnp.int32),
        jnp.asarray(lens + [0] * pad, jnp.int32), chunk=chunk,
        use_pallas=use_pallas)
    for i, n in enumerate(lens):
        sl = slice(cu[i], cu[i] + n)
        s0 = jnp.zeros_like(pool[0]) if fresh[i] else pool[slots[i]]
        want_y, want_s = ssd.ssd_scan(*(v[sl] for v in x), s0)
        np.testing.assert_allclose(y[sl], want_y, **TOL)
        np.testing.assert_allclose(new[slots[i]], want_s, **TOL)
    for s in set(range(SLOTS)) - set(slots):
        # a slot no sequence of the step holds keeps its state to the bit
        np.testing.assert_array_equal(new[s], pool[s])
    assert bool(jnp.all(jnp.isfinite(y)))
    return y, new


@pytest.mark.parametrize("lens,slots,fresh", [
    # a chunk and a half, one-token rows beside it, a short tail
    ([40, 1, 16, 7, 1], [3, 0, 5, 1, 4], [False, False, True, False, True]),
    # one-token rows only: no chunk runs
    ([1, 1, 1], [2, 5, 0], [False, True, False]),
    # one sequence whose last chunk ends at the batch's end
    ([65], [4], [False]),
    # a prompt that starts its sequence beside a continued one
    ([33, 3], [1, 2], [True, False]),
])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_ragged_form_agrees_with_the_scan(lens, slots, fresh, use_pallas):
    _ragged_case(lens, slots, fresh, use_pallas=use_pallas)


def test_a_window_that_ends_at_the_batchs_end_reads_no_neighbour():
    """No padding rows behind the last chunk: its window begins before it,
    inside the sequence in front, whose rows it must neither read nor
    write."""
    _ragged_case([24, 20], [0, 1], [False, False], tail=0, chunk=16)
    _ragged_case([8, 9], [3, 2], [True, False], tail=0, chunk=16)
    # a batch shorter than a chunk: one window holds it all
    _ragged_case([5, 1], [3, 2], [False, False], tail=0, chunk=64)


def test_a_state_is_carried_across_two_steps_and_into_decode():
    """A sequence served as a chunk, a second chunk and two decode rows
    equals one scan over all its tokens; a padding row of the decode batch
    writes nothing."""
    n = 70
    x = _inputs(jax.random.PRNGKey(11), n)
    want_y, want_s = ssd.ssd_scan(*x, jnp.zeros((H, P, N)))
    pool = jax.random.normal(jax.random.PRNGKey(5), (SLOTS, H, P, N))
    got, done = [], 0
    for step, fresh in ((40, True), (28, False)):
        rows = [v[done:done + step] for v in x]
        rows = [jnp.concatenate([v, jnp.zeros((8,) + v.shape[1:])])
                for v in rows]
        y, pool = ssd.ssd_ragged(
            *rows, pool, jnp.asarray([2, SLOTS], jnp.int32),
            jnp.asarray([fresh, False]),
            jnp.asarray([0, step, step], jnp.int32),
            jnp.asarray([step, 0], jnp.int32), chunk=16)
        got.append(y[:step])
        done += step
    before = pool
    for use_pallas in (False, True):
        # the decode batch: the sequence's row, another's, a padding row
        rows = [jnp.stack([v[done], v[0], v[1]]) for v in x]
        y, pool = ssd.ssd_decode(
            *rows, pool, jnp.asarray([2, 4, SLOTS], jnp.int32),
            jnp.asarray([False, False, False]), use_pallas=use_pallas)
        got.append(y[:1])
        done += 1
        assert not np.asarray(y[2]).any()
        for s in (0, 1, 3, 5):
            np.testing.assert_array_equal(pool[s], before[s])
    np.testing.assert_allclose(jnp.concatenate(got), want_y, **TOL)
    np.testing.assert_allclose(pool[2], want_s, **TOL)


@pytest.mark.parametrize("live", [
    [True, True, False, True, False, False],   # padding between and after
    [False, False, True, True, False, True],   # rows before the first live
    [False] * 6,                               # no live row: nothing moves
    [True] * 6,
])
def test_recurrent_kernel_agrees_with_the_gather_form(live):
    """The kernel's rows share blocks where they are not live
    (``_redirect``): whatever the pattern, each live slot is written once
    and every other slot keeps its bits."""
    r = len(live)
    u, dt, a, b, c = _inputs(jax.random.PRNGKey(3), r)
    pool = jax.random.normal(jax.random.PRNGKey(9), (SLOTS + 2, H, P, N))
    slots = jnp.asarray([5, 0, 7, 3, 1, 6], jnp.int32)
    slots = jnp.where(jnp.asarray(live), slots, SLOTS + 2)  # padding: past
    fresh = jnp.asarray([False, True, False, False, True, False])
    want_y, want_pool = ssd.ssd_decode(u, dt, a, b, c, pool, slots, fresh,
                                       use_pallas=False)
    got_y, got_pool = ssd.ssd_decode(u, dt, a, b, c, pool, slots, fresh,
                                     use_pallas=True)
    np.testing.assert_allclose(got_y, want_y, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(got_pool, want_pool, atol=1e-6, rtol=1e-6)
    for s in range(SLOTS + 2):
        if s not in [int(x) for x, on in zip(slots, live) if on]:
            np.testing.assert_array_equal(got_pool[s], pool[s])


def test_a_decay_that_underflows_gives_zero_not_nan():
    """exp(sum a) over a chunk of a = -40 a token underflows float32; the
    chunked form only exponentiates differences that are <= 0."""
    u, dt, a, b, c = _inputs(jax.random.PRNGKey(2), 40, decay=200.0)
    assert float(jnp.sum(a[:16, 0])) < -100
    pool = jnp.ones((SLOTS, H, P, N))
    y, new = ssd.ssd_ragged(
        u, dt, a, b, c, pool, jnp.asarray([1], jnp.int32),
        jnp.asarray([False]), jnp.asarray([0, 40], jnp.int32),
        jnp.asarray([36], jnp.int32), chunk=16)
    want_y, want_s = ssd.ssd_scan(u[:36], dt[:36], a[:36], b[:36], c[:36],
                                  pool[1])
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_allclose(y[:36], want_y, **TOL)
    np.testing.assert_allclose(new[1], want_s, **TOL)


def test_chunk_slots_counts_what_the_chunked_form_runs():
    """Tokens over chunk slots: one-token rows are in neither count, a
    sequence's last chunk is counted whole."""
    assert ssd.chunk_slots([1] * 128 + [1024], 256) == (1024, 1024)
    assert ssd.chunk_slots([1] * 100 + [700, 300, 0, 0], 256) == (
        1000, 5 * 256)
    assert ssd.chunk_slots([1, 1, 1, 0], 256) == (0, 0)
    assert ssd.chunk_slots([40, 1], 64) == (40, 64)
    # and the plan the device runs agrees with it
    lens = jnp.asarray([1, 40, 1, 17, 0, 0], jnp.int32)
    total, seq, start, count = ssd._chunk_plan(
        jnp.asarray([0, 8, 48, 56, 80, 80, 80], jnp.int32), lens,
        jnp.asarray([0, 1, 2, 3, 9, 9], jnp.int32), 6, 96, 16)
    tokens, slots = ssd.chunk_slots(np.asarray(lens), 16)
    assert int(total) * 16 == slots == 5 * 16 and tokens == 57
    assert [int(x) for x in seq[:5]] == [1, 1, 1, 3, 3]
    assert [int(x) for x in start[:5]] == [8, 24, 40, 56, 72]
    assert [int(x) for x in count[:5]] == [16, 16, 8, 16, 1]


def test_the_mixer_steps_carry_convolution_and_rule_together():
    """``mamba2_ragged`` then ``mamba2_decode`` over one sequence equal
    the convolution over the whole sequence (with its bias), silu, the
    scan and the skip."""
    k, n_tok = 4, 21
    c_dim = H * P + 2 * N
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    xbc = jax.random.normal(ks[0], (n_tok, c_dim))
    w = jax.random.normal(ks[1], (k, c_dim)) * 0.5
    bias = jax.random.normal(ks[2], (c_dim,)) * 0.5
    d_skip = jax.random.normal(ks[3], (H,))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (n_tok, H)) - 2.0)
    a = -dt * 3.0
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(padded[j:j + n_tok] * w[j] for j in range(k)) + bias
    u, b, c = ssd._split(jax.nn.silu(conv), (H, P, N))
    want, _ = ssd.ssd_scan(u, dt, a, b, c, jnp.zeros((H, P, N)))
    want = want + d_skip[None, :, None] * u
    conv_state = jnp.zeros((1, k - 1, c_dim))
    pool = jnp.ones((SLOTS, H, P, N))
    got = []
    rows = 24
    y, conv_state, pool = ssd.mamba2_ragged(
        jnp.pad(xbc[:17], ((0, rows - 17), (0, 0))),
        jnp.pad(dt[:17], ((0, rows - 17), (0, 0))),
        jnp.pad(a[:17], ((0, rows - 17), (0, 0))), w, bias, d_skip,
        conv_state, pool, jnp.asarray([3], jnp.int32), jnp.asarray([True]),
        jnp.asarray([0, rows], jnp.int32), jnp.asarray([17], jnp.int32),
        (H, P, N), chunk=8)
    got.append(y[:17])
    for i in range(17, n_tok):
        y, conv_state, pool = ssd.mamba2_decode(
            xbc[i:i + 1], dt[i:i + 1], a[i:i + 1], w, bias, d_skip,
            conv_state, pool, jnp.asarray([3], jnp.int32),
            jnp.asarray([False]), (H, P, N))
        got.append(y)
    np.testing.assert_allclose(jnp.concatenate(got), want, **TOL)
