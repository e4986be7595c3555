"""ops/ssd.py with several groups of B and C (Nemotron-H has eight; head h
reads group h // (heads / groups)): the one-token form (the recurrent
kernel in interpret mode and the gather form) and the ragged form against
the token-by-token recurrence written out here, a head at a time; and with
ONE group the program of today (granite's, pinned by the digest of its
jaxpr as the parent traces it), which a group axis of one reproduces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.ops import ssd

H, P, N = 16, 8, 128
SLOTS = 6

# tolerance: as tests/ops/test_ssd.py: both sides float32 with products at
# full precision, differing by the order of summation over a chunk's tokens
# and by exp(x) exp(y) against exp(x + y).  A head that read ANOTHER
# group's B and C reads of order 1, and a bfloat16 state 1e-2.
TOL = dict(atol=5e-5, rtol=5e-5)


def _inputs(key, t, groups):
    ks = jax.random.split(key, 5)
    u = jax.random.normal(ks[0], (t, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, H)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0,
                                    maxval=2.7)) * dt
    b = jax.random.normal(ks[3], (t, groups, N)) * N ** -0.5
    c = jax.random.normal(ks[4], (t, groups, N))
    return u, dt, a, b, c


def _recurrence(u, dt, a, b, c, state):
    """The rule a head at a time in numpy, float64: head ``h`` reads the B
    and C of group ``h // (H / G)``.  Independent of ``ops/ssd.py``."""
    u, dt, a, b, c, s = (np.asarray(x, np.float64)
                         for x in (u, dt, a, b, c, state))
    t, groups = b.shape[:2]
    y = np.zeros((t, H, P))
    for h in range(H):
        g = h // (H // groups)
        for i in range(t):
            s[h] = np.exp(a[i, h]) * s[h] \
                + dt[i, h] * np.outer(u[i, h], b[i, g])
            y[i, h] = s[h] @ c[i, g]
    return y, s


@pytest.mark.parametrize("groups", [2, 8])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_one_token_form_with_groups_agrees_with_the_recurrence(
        groups, use_pallas):
    """Six rows, two of them padding and one fresh: each live row's state
    is its slot's, decayed and updated with ITS GROUP's B, and read with
    its group's C; the others' slots keep their bits."""
    x = _inputs(jax.random.PRNGKey(groups), 6, groups)
    pool = jax.random.normal(jax.random.PRNGKey(3), (SLOTS, H, P, N))
    slots = jnp.asarray([4, SLOTS, 0, 2, SLOTS, 5], jnp.int32)
    fresh = jnp.asarray([False, False, True, False, False, False])
    y, new = ssd.ssd_decode(*x, pool, slots, fresh, use_pallas=use_pallas)
    for r, s in enumerate(np.asarray(slots)):
        if s == SLOTS:
            assert not np.asarray(y[r]).any()
            continue
        s0 = np.zeros((H, P, N)) if fresh[r] else pool[s]
        want_y, want_s = _recurrence(*(v[r:r + 1] for v in x), s0)
        np.testing.assert_allclose(y[r], want_y[0], **TOL)
        np.testing.assert_allclose(new[s], want_s, **TOL)
    for s in (1, 3):
        np.testing.assert_array_equal(new[s], pool[s])


@pytest.mark.parametrize("groups", [2, 8])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_ragged_form_with_groups_agrees_with_the_recurrence(groups,
                                                            use_pallas):
    """A chunk and a half, one-token rows beside it, a fresh prompt and a
    short tail, in chunks of 16: the chunked form's three products (C B^T,
    C S^T and the state's update) each take a head's own group."""
    lens, slots = [40, 1, 16, 7, 1], [3, 0, 5, 1, 4]
    fresh = [False, False, True, False, True]
    cu = [0]
    for n in lens:
        cu.append(cu[-1] + -(-n // 8) * 8)
    t = cu[-1] + 16
    x = _inputs(jax.random.PRNGKey(10 + groups), t, groups)
    pool = jax.random.normal(jax.random.PRNGKey(7), (SLOTS, H, P, N))
    pad = 2
    y, new = ssd.ssd_ragged(
        *x, pool, jnp.asarray(slots + [SLOTS] * pad, jnp.int32),
        jnp.asarray(fresh + [False] * pad),
        jnp.asarray(cu + [cu[-1]] * pad, jnp.int32),
        jnp.asarray(lens + [0] * pad, jnp.int32), chunk=16,
        use_pallas=use_pallas)
    for i, n in enumerate(lens):
        sl = slice(cu[i], cu[i] + n)
        s0 = np.zeros((H, P, N)) if fresh[i] else pool[slots[i]]
        want_y, want_s = _recurrence(*(v[sl] for v in x), s0)
        np.testing.assert_allclose(y[sl], want_y, **TOL)
        np.testing.assert_allclose(new[slots[i]], want_s, **TOL)
    np.testing.assert_array_equal(new[2], pool[2])
    # the module's own oracle takes the group axis too
    sl = slice(cu[0], cu[0] + lens[0])
    scan_y, scan_s = ssd.ssd_scan(*(v[sl] for v in x), pool[slots[0]])
    np.testing.assert_allclose(scan_y, y[sl], **TOL)
    np.testing.assert_allclose(scan_s, new[slots[0]], **TOL)


#: sha256[:16] of ``str(jax.make_jaxpr(...))`` of the one-token form, the
#: ragged form (gather and kernel) and the two steps around them with ONE
#: group, B and C as ``[T, N]``, taken on d8fc78e, the parent of PR 46: the
#: program granite-4.0-h-small runs, which the group axis had to leave as
#: it was, to the operation.
ONE_GROUP = {
    "decode": "fda42bca142f0de4", "ragged": "df55d8532aa2681c",
    "decode_kernel": "346f9a510582cf36", "ragged_kernel": "98026b7655a18d79",
    "mamba2_decode": "60f1958bf1cc58fa", "mamba2_ragged": "f88197c602fc6587",
}


def test_one_group_is_todays_program():
    import hashlib

    f32, i32, k = jnp.float32, jnp.int32, 4
    conv_dim = H * P + 2 * N

    def sds(dims, dtype=f32):
        return jax.ShapeDtypeStruct(dims, dtype)

    def rows(t):
        return (sds((t, H, P)), sds((t, H)), sds((t, H)), sds((t, N)),
                sds((t, N)), sds((SLOTS, H, P, N)))

    one_token = (sds((4,), i32), sds((4,), jnp.bool_))
    packed = (sds((3,), i32), sds((3,), jnp.bool_), sds((4,), i32),
              sds((3,), i32))

    def around(t, s):
        return (sds((t, conv_dim)), sds((t, H)), sds((t, H)),
                sds((k, conv_dim)), sds((conv_dim,)), sds((H,)),
                sds((s, k - 1, conv_dim)), sds((SLOTS, H, P, N)))

    programs = {
        "decode": jax.make_jaxpr(lambda *a: ssd.ssd_decode(*a))(
            *rows(4), *one_token),
        "ragged": jax.make_jaxpr(lambda *a: ssd.ssd_ragged(*a, chunk=16))(
            *rows(64), *packed),
        "decode_kernel": jax.make_jaxpr(
            lambda *a: ssd.ssd_decode(*a, use_pallas=True))(
            *rows(4), *one_token),
        "ragged_kernel": jax.make_jaxpr(
            lambda *a: ssd.ssd_ragged(*a, chunk=16, use_pallas=True))(
            *rows(64), *packed),
        "mamba2_decode": jax.make_jaxpr(
            lambda *a: ssd.mamba2_decode(*a, (H, P, N), groups=1))(
            *around(4, 4), *one_token),
        "mamba2_ragged": jax.make_jaxpr(
            lambda *a: ssd.mamba2_ragged(*a, (H, P, N), chunk=16, groups=1))(
            *around(64, 3), *packed),
    }
    assert {name: hashlib.sha256(str(x).encode()).hexdigest()[:16]
            for name, x in programs.items()} == ONE_GROUP


@pytest.mark.parametrize("use_pallas", [False, True])
def test_a_group_axis_of_one_gives_the_ungrouped_numbers(use_pallas):
    """The same numbers given with a group axis of one, ``[T, 1, N]``,
    take the grouped path: the one-token form (elementwise, the same
    arithmetic a head) gives the ungrouped bits, the ragged form the same
    numbers to the order of its sums."""
    u, dt, a, b, c = _inputs(jax.random.PRNGKey(1), 64, 1)
    pool = jax.random.normal(jax.random.PRNGKey(2), (SLOTS, H, P, N))
    slots = jnp.asarray([4, SLOTS, 0, 2], jnp.int32)
    fresh = jnp.asarray([False, False, True, False])
    one = ssd.ssd_decode(u[:4], dt[:4], a[:4], b[:4, 0], c[:4, 0], pool,
                         slots, fresh, use_pallas=use_pallas)
    grouped = ssd.ssd_decode(u[:4], dt[:4], a[:4], b[:4], c[:4], pool,
                             slots, fresh, use_pallas=use_pallas)
    for got, want in zip(grouped, one):
        np.testing.assert_array_equal(got, want)
    ragged = (jnp.asarray([3, 1, SLOTS], jnp.int32),
              jnp.asarray([False, True, False]),
              jnp.asarray([0, 40, 48, 48], jnp.int32),
              jnp.asarray([40, 1, 0], jnp.int32))
    one = ssd.ssd_ragged(u, dt, a, b[:, 0], c[:, 0], pool, *ragged,
                         chunk=16, use_pallas=use_pallas)
    grouped = ssd.ssd_ragged(u, dt, a, b, c, pool, *ragged, chunk=16,
                             use_pallas=use_pallas)
    for got, want in zip(grouped, one):
        np.testing.assert_allclose(got[:48], want[:48], **TOL)


def test_the_kernel_takes_whole_groups_or_lies_within_one():
    """A head block of the recurrent kernel holds whole groups (Nemotron-H:
    32 heads are two groups of 16) or lies inside one (granite: four blocks
    in its one group); a split that does neither is refused by name and the
    gather form runs."""
    pool = jnp.zeros((2, 128, 64, 128), jnp.float32)
    assert ssd.kernel_reason(pool) == ""
    assert ssd.kernel_reason(pool, groups=8) == ""
    assert ssd._group_blocks(128, 32, 8) == (2, 1)
    assert ssd._group_blocks(128, 32, 1) == (1, 4)
    assert ssd._group_blocks(128, 32, 2) == (1, 2)
    # 96 heads in 2 groups of 48: a block of 32 straddles a group's edge
    assert "2 groups of B and C" in ssd.kernel_reason(
        jnp.zeros((2, 96, 64, 128), jnp.float32), groups=2)


def test_the_two_steps_split_u_b_and_c_a_group():
    """``mamba2_decode`` cuts silu(conv + bias) into u, then B a group,
    then C a group, and agrees with the rule on those pieces."""
    groups, k = 4, 4
    conv_dim = H * P + 2 * groups * N
    key = jax.random.split(jax.random.PRNGKey(5), 6)
    xbc = jax.random.normal(key[0], (3, conv_dim))
    dt = jax.nn.softplus(jax.random.normal(key[1], (3, H)) - 2.0)
    a = -dt
    conv_w = jax.random.normal(key[2], (k, conv_dim)) * 0.5
    conv_b = jax.random.normal(key[3], (conv_dim,)) * 0.1
    conv_state = jax.random.normal(key[4], (3, k - 1, conv_dim))
    pool = jax.random.normal(key[5], (SLOTS, H, P, N))
    slots = jnp.asarray([1, 3, 5], jnp.int32)
    fresh = jnp.zeros((3,), bool)
    d_skip = jnp.full((H,), 0.5)
    y, new_conv, new = ssd.mamba2_decode(
        xbc, dt, a, conv_w, conv_b, d_skip, conv_state, pool, slots, fresh,
        (H, P, N), groups=groups)
    window = jnp.concatenate([conv_state, xbc[:, None]], axis=1)
    act = jax.nn.silu(jnp.einsum("rkc,kc->rc", window, conv_w) + conv_b)
    u = act[:, :H * P].reshape(3, H, P)
    b = act[:, H * P:H * P + groups * N].reshape(3, groups, N)
    c = act[:, H * P + groups * N:].reshape(3, groups, N)
    for r in range(3):
        want_y, want_s = _recurrence(u[r:r + 1], dt[r:r + 1], a[r:r + 1],
                                     b[r:r + 1], c[r:r + 1], pool[slots[r]])
        np.testing.assert_allclose(y[r], want_y[0] + 0.5 * u[r], **TOL)
        np.testing.assert_allclose(new[slots[r]], want_s, **TOL)
    np.testing.assert_array_equal(new_conv, window[:, 1:])
