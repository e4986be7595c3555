"""A learned sink in the softmax and a key wider than its value, through
the one paged-attention kernel and its XLA references (ISSUE 55: MiMo-V2.5's
sliding layers): 2 kv heads x 8 query heads, keys of 192 in K pools of 256
lanes (64 of them zeros) beside V pools of 128, ``sinks [H]`` float32 as
the initial state ``(b_h, 1)`` of the online softmax.  Interpret mode, tiny
tiles (``pages_per_block`` 2: 32 keys a KV block; a q block of 32 tokens),
windows below, at and above a q block, so that a 120-token chunk's tiles
start their walk INSIDE the chunk; the oracle is the definition, a dense
masked softmax in numpy over each sequence's own keys with the sink in its
denominator and no value.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.ops.test_ragged_paged_attention import _positions
from vllm_omni_tpu.ops import (
    paged_attention,
    paged_attention_ref,
    ragged_paged_attention,
    ragged_paged_attention_ref,
    write_kv_cache,
)
from vllm_omni_tpu.ops.paged_attention import (
    init_kv_cache,
    key_row_width,
    pad_lanes,
    pair_queries,
    sink_softmax,
)
from vllm_omni_tpu.ops.ragged_paged_attention import align_to_block

HKV, GROUP, D, DV, PAGE = 2, 8, 192, 128, 16
H = HKV * GROUP
SCALE = D ** -0.5
#: decode rows under, at and over the windows; a chunk that ends a long
#: context and is four q blocks long; a fresh prompt; one that ends on a
#: page and a block boundary, one a token past it
MIXED = [(20, 1), (37, 1), (100, 1), (200, 120), (50, 50), (64, 17),
         (65, 9)]


def _pack(specs, s_max=8, max_pages=14, seed=0):
    """A token-packed batch over a K pool of ``key_row_width(192) = 256``
    lanes and a V pool of 128, written through ``write_kv_cache`` (which
    pads a key's row); ``dense`` holds each sequence's K [ctx, Hkv, 192]
    and V [ctx, Hkv, 128] for the oracle."""
    rng = np.random.default_rng(seed)
    n = len(specs)
    cu = np.zeros(s_max + 1, np.int32)
    q_lens, seq_lens = np.zeros(s_max, np.int32), np.zeros(s_max, np.int32)
    tables = np.zeros((s_max, max_pages), np.int32)
    num_pages = 2 + sum(-(-c // PAGE) for c, _ in specs)
    (kc, vc), = init_kv_cache(1, num_pages, PAGE, HKV, key_row_width(D),
                              jnp.float32, v_head_dim=DV)
    assert kc.shape[-1] == 256 and vc.shape[-1] == DV
    total, next_page, dense = 0, 1, []
    for i, (ctx, qn) in enumerate(specs):
        cu[i], q_lens[i], seq_lens[i] = total, qn, ctx
        total += align_to_block(qn)
        pn = -(-ctx // PAGE)
        ids = list(range(next_page, next_page + pn))
        next_page += pn
        tables[i, :pn] = ids
        k = rng.standard_normal((ctx, HKV, D)).astype(np.float32)
        v = rng.standard_normal((ctx, HKV, DV)).astype(np.float32)
        dense.append((k, v))
        slots = np.asarray([ids[p // PAGE] * PAGE + p % PAGE
                            for p in range(ctx)], np.int32)
        kc, vc = write_kv_cache(kc, vc, jnp.asarray(k), jnp.asarray(v),
                                jnp.asarray(slots))
    cu[n:] = total
    q = np.zeros((align_to_block(total), H, D), np.float32)
    for i, (ctx, qn) in enumerate(specs):
        q[cu[i]: cu[i] + qn] = 2.0 * rng.standard_normal((qn, H, D))
    sinks = rng.uniform(1.0, 4.0, H).astype(np.float32)
    return (jnp.asarray(q), kc, vc, jnp.asarray(tables), jnp.asarray(cu),
            jnp.asarray(q_lens), jnp.asarray(seq_lens), n, dense,
            jnp.asarray(sinks))


def _definition(q, dense, cu, q_lens, seq_lens, n, window, sinks):
    """[T, H, 128]: every real row by the definition, query head j on kv
    head j // 8, key s iff t - window < s <= t (every s <= t without one),
    ``p = exp(s - m) / (exp(b - m) + sum exp(s - m))`` with the head's
    sink ``b`` (a plain softmax without sinks); zeros elsewhere."""
    out = np.zeros((q.shape[0], H, DV), np.float32)
    pos = _positions(cu, q_lens, seq_lens, n, q.shape[0])
    for i in range(n):
        keys, values = dense[i]
        s = np.arange(keys.shape[0])
        for j in range(int(q_lens[i])):
            row = int(cu[i]) + j
            ok = s <= pos[row]
            if window:
                ok &= s > pos[row] - window
            for head in range(H):
                g = head // GROUP
                sc = np.asarray(q)[row, head] @ keys[ok][:, g].T * SCALE
                b = -np.inf if sinks is None else float(sinks[head])
                m = max(sc.max(), b)
                p = np.exp(sc - m) / (np.exp(b - m) + np.exp(sc - m).sum())
                out[row, head] = p @ values[ok][:, g]
    return out


@pytest.mark.parametrize("window", [21, 32, 70, None])
def test_a_mixed_step_with_sinks_is_the_definition(window):
    """Windows below, at and above the 32-token q block, and none (a full
    layer with a sink would read so): kernel and XLA reference against the
    definition, over a K pool of 256 lanes and a V pool of 128."""
    q, kc, vc, tables, cu, q_lens, seq_lens, n, dense, sinks = _pack(MIXED)
    args = (q, kc, vc, tables, cu, q_lens, seq_lens, n)
    want = _definition(q, dense, cu, q_lens, seq_lens, n, window, sinks)
    kw = dict(sinks=sinks, window=window)
    ref = ragged_paged_attention_ref(
        *pair_queries(q, kc)[:1], *args[1:], scale=SCALE, **kw)
    got = ragged_paged_attention(*args, use_pallas=True, pages_per_block=2,
                                 q_block=32, **kw)
    assert got.shape == (q.shape[0], H, DV)
    np.testing.assert_allclose(np.asarray(ref), want, atol=3e-5)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5)
    # the sink is not idle: a row's weights sum to less than one
    plain = _definition(q, dense, cu, q_lens, seq_lens, n, window, None)
    assert np.abs(plain - want).max() > 1e-2
    free = ragged_paged_attention(*args, use_pallas=True, pages_per_block=2,
                                  q_block=32, window=window)
    np.testing.assert_allclose(np.asarray(free), plain, atol=3e-5)


@pytest.mark.parametrize("q_block", [8, 32])
def test_tiles_of_one_sub_block_and_of_four_start_at_the_sink(q_block):
    """The narrow path (a q block of 8 tokens: every tile a sub-block) and
    the wide one read the sinks' block alike."""
    q, kc, vc, tables, cu, q_lens, seq_lens, n, dense, sinks = _pack(
        MIXED, seed=5)
    want = _definition(q, dense, cu, q_lens, seq_lens, n, 21, sinks)
    got = ragged_paged_attention(
        q, kc, vc, tables, cu, q_lens, seq_lens, n, use_pallas=True,
        pages_per_block=2, q_block=q_block, sinks=sinks, window=21)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5)


@pytest.mark.parametrize("window", [21, 32, None])
def test_decode_rows_with_sinks_through_the_decode_entry(window):
    """One token a sequence through ``paged_attention``: contexts under,
    at and over the window, on a page's and a block's boundary and one
    past it, and a padding row of context 0."""
    specs = [(20, 1), (21, 1), (22, 1), (64, 1), (65, 1), (100, 1),
             (200, 1)]
    q, kc, vc, tables, cu, q_lens, seq_lens, n, dense, sinks = _pack(
        specs, seed=3)
    want = _definition(q, dense, cu, q_lens, seq_lens, n, window, sinks)
    rows = np.asarray(cu[:n])
    ctx = jnp.concatenate([seq_lens[:n], jnp.zeros((1,), jnp.int32)])
    qd = jnp.concatenate([q[rows], q[rows[:1]]])
    td = jnp.concatenate([tables[:n], tables[:1]])
    got = paged_attention(qd, kc, vc, td, ctx, window=window, sinks=sinks,
                          use_pallas=True)
    ref = paged_attention_ref(pad_lanes(qd, 256), kc, vc, td, ctx,
                              scale=SCALE, window=window, sinks=sinks)
    assert got.shape == (n + 1, H, DV)
    np.testing.assert_allclose(np.asarray(got)[:n], want[rows], atol=3e-5)
    np.testing.assert_allclose(np.asarray(ref)[:n], want[rows], atol=3e-5)
    assert not np.asarray(got)[n].any()


def test_a_key_of_192_lies_in_256_lanes_and_meets_a_padded_query():
    """``key_row_width``: whole lane tiles for a head wider than one
    (192 -> 256), every other width as it is; the write pads a key's row
    with zeros, the query is padded alike and keeps the head's own scale;
    a value's row is never padded."""
    assert [key_row_width(d) for d in (16, 64, 128, 192, 256, 320)] == [
        16, 64, 128, 256, 256, 384]
    (kc, vc), = init_kv_cache(1, 4, PAGE, HKV, 256, jnp.float32,
                              v_head_dim=DV)
    k = jnp.ones((3, HKV, D)), jnp.ones((3, HKV, DV))
    kc, vc = write_kv_cache(kc, vc, *k, jnp.asarray([16, 17, 40]))
    assert float(kc[:, 1, :2, :D].min()) == 1.0
    assert not np.asarray(kc[..., D:]).any()
    assert float(vc[:, 2, 8].min()) == 1.0
    q = jnp.ones((5, H, D))
    wide, scale = pair_queries(q, kc)
    assert wide.shape == (5, H, 256) and scale == D ** -0.5
    assert not np.asarray(wide[..., D:]).any()
    np.testing.assert_array_equal(np.asarray(pad_lanes(q, 256)),
                                  np.asarray(wide))


def test_the_sink_takes_mass_and_gives_no_value():
    """``sink_softmax``: the weights of a row sum to ``1 - the sink's
    share``; a sink far below the scores is a plain softmax; a row that
    admits no key weighs nothing."""
    s = jax.random.normal(jax.random.PRNGKey(0), (4, 3, 50))
    mask = jnp.ones_like(s, bool).at[1].set(False)
    sink = jnp.asarray([0.5, -1.0, 2.0]).reshape(1, 3, 1)
    p = sink_softmax(jnp.where(mask, s, -1e30), mask, sink)
    total = np.asarray(p.sum(-1))
    want = 1.0 - np.asarray(
        jnp.exp(sink[..., 0]) / (jnp.exp(sink[..., 0])
                                 + jnp.exp(s).sum(-1)))
    np.testing.assert_allclose(total[[0, 2, 3]], want[[0, 2, 3]], rtol=1e-5)
    assert not total[1].any()
    np.testing.assert_allclose(
        np.asarray(sink_softmax(s, jnp.ones_like(s, bool),
                                jnp.full((1, 3, 1), -1e30))),
        np.asarray(jax.nn.softmax(s, -1)), atol=1e-6)


def test_the_tile_of_the_sliding_layers():
    """What the picker gives 8 kv heads x 8 query heads over rows of 256
    lanes on 16-token bfloat16 pages when told of the sinks and the
    128-key window: 32 tokens (256 rows) a tile, 8 pages (the window) a KV
    block, so that the whole counts under Mosaic's own 16 MB and the
    kernel asks for nothing; every call that hands no sinks keeps the
    tile it had."""
    from vllm_omni_tpu.ops.autotune import (
        MOSAIC_VMEM,
        auto_ragged_blocks,
        ragged_vmem_limit,
    )

    shape = dict(head_dim=256, page_size=PAGE, group=8, kv_itemsize=2,
                 q_itemsize=2, kv_heads=8)
    assert auto_ragged_blocks(**shape) == (64, 32, 2)
    assert auto_ragged_blocks(**shape, window=128) == (64, 32, 2)
    blocks = auto_ragged_blocks(**shape, window=128, sinks=True)
    assert blocks == (32, 8, 2)
    assert ragged_vmem_limit(256, PAGE, 8, 2, 2, False, 2, kv_heads=8,
                             blocks=blocks) is None
    assert MOSAIC_VMEM == 16 * 2 ** 20
    # the full layers: 4 kv heads x 16 query heads, no sinks, no window
    full = dict(shape, group=16, kv_heads=4)
    assert auto_ragged_blocks(**full) == (32, 32, 2)
    assert ragged_vmem_limit(256, PAGE, 16, 2, 2, False, 2, kv_heads=4,
                             blocks=(32, 32, 2)) is None
