"""The window of the one paged-attention kernel over PER-HEAD (K, V) pages
at command-a-plus-05-2026's geometry: 8 kv heads x 16 query heads of 128
(ISSUE 50).  Every caller that passed ``window`` before had ONE kv head (a
latent row); since PR 49 a KV block comes in once for all kv heads and the
heads attend it in a loop, so the window's bound on a tile's walk meets
the head loop here for the first time.  Interpret mode, tiny tiles
(``pages_per_block`` 2: 32 keys a KV block), a window of 37 keys that is
neither a multiple of the page (16) nor of the block; the oracle is the
definition, a dense masked softmax in numpy over each sequence's own keys.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tests.ops.test_ragged_paged_attention import _pack, _plain, _positions
from vllm_omni_tpu.ops import (
    paged_attention,
    paged_attention_ref,
    ragged_paged_attention,
    ragged_paged_attention_ref,
)

HKV, GROUP, D, PAGE, WINDOW = 8, 16, 128, 16, 37
H = HKV * GROUP
SCALE = D ** -0.5


def _definition(q, dense, cu, q_lens, seq_lens, n, window):
    """[T, H, D]: every real row by the plain definition, query head j on
    kv head j // 16, key s iff t - window < s <= t; zeros elsewhere."""
    out = np.zeros(q.shape, np.float32)
    pos = _positions(cu, q_lens, seq_lens, n, q.shape[0])
    for i in range(n):
        keys, values = dense[i]
        s = np.arange(keys.shape[0])
        for j in range(int(q_lens[i])):
            row = int(cu[i]) + j
            allowed = (s <= pos[row]) & (s > pos[row] - window)
            for head in range(HKV):
                sl = slice(head * GROUP, (head + 1) * GROUP)
                out[row, sl] = _plain(np.asarray(q)[row, sl], keys[:, head],
                                      values[:, head], allowed, SCALE)
    return out


#: decode rows under, at and just over the window, and far over it; a
#: chunk that ends a long context (its tiles start at different blocks),
#: a fresh prompt longer than the window, one that ends on a page and a
#: block boundary, one a token past it
MIXED = [(20, 1), (37, 1), (38, 1), (100, 1), (200, 40), (50, 50),
         (64, 17), (65, 9)]


@pytest.mark.parametrize("q_block", [8, 32])
def test_a_windowed_mixed_step_is_the_dense_masked_softmax(q_block):
    q, kc, vc, tables, cu, q_lens, seq_lens, n, dense = _pack(
        MIXED, H, HKV, D, PAGE, 8, 14)
    args = (q, kc, vc, tables, cu, q_lens, seq_lens, n)
    want = _definition(q, dense, cu, q_lens, seq_lens, n, WINDOW)
    ref = ragged_paged_attention_ref(*args, scale=SCALE, window=WINDOW)
    got = ragged_paged_attention(*args, scale=SCALE, window=WINDOW,
                                 use_pallas=True, pages_per_block=2,
                                 q_block=q_block)
    np.testing.assert_allclose(np.asarray(ref), want, atol=3e-5)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5)
    # and the window is not idle: without it the long rows read otherwise
    free = ragged_paged_attention(*args, scale=SCALE, use_pallas=True,
                                  pages_per_block=2, q_block=q_block)
    over = int(cu[3])
    assert np.abs(np.asarray(free)[over] - want[over]).max() > 1e-2
    under = int(cu[0])
    np.testing.assert_allclose(np.asarray(free)[under], want[under],
                               atol=3e-5)


def test_windowed_decode_rows_through_the_decode_entry():
    """One token a sequence through ``paged_attention``: contexts under,
    at and over the window, on a page's and a block's boundary and one
    past it, and a padding row of context 0."""
    specs = [(20, 1), (37, 1), (38, 1), (64, 1), (65, 1), (100, 1),
             (200, 1)]
    q, kc, vc, tables, cu, q_lens, seq_lens, n, dense = _pack(
        specs, H, HKV, D, PAGE, 8, 14, seed=3)
    want = _definition(q, dense, cu, q_lens, seq_lens, n, WINDOW)
    rows = np.asarray(cu[:n])
    ctx = jnp.concatenate([seq_lens[:n], jnp.zeros((1,), jnp.int32)])
    qd = jnp.concatenate([q[rows], q[rows[:1]]])
    td = jnp.concatenate([tables[:n], tables[:1]])
    got = paged_attention(qd, kc, vc, td, ctx, scale=SCALE, window=WINDOW,
                          use_pallas=True)
    ref = paged_attention_ref(qd, kc, vc, td, ctx, scale=SCALE,
                              window=WINDOW)
    np.testing.assert_allclose(np.asarray(got)[:n], want[rows], atol=3e-5)
    np.testing.assert_allclose(np.asarray(ref)[:n], want[rows], atol=3e-5)
    assert not np.asarray(got)[n].any()


def test_pages_given_back_reach_no_row_of_any_head():
    """Keys below the window lie in pages that were given back: the table
    then points at page 0, and whatever finite rows that page holds (1e3
    everywhere, in K and in V, for every kv head) reach no row."""
    specs = [(200, 1), (200, 40), (100, 1)]
    q, kc, vc, tables, cu, q_lens, seq_lens, n, dense = _pack(
        specs, H, HKV, D, PAGE, 4, 14, seed=7)
    want = _definition(q, dense, cu, q_lens, seq_lens, n, WINDOW)
    # the pages wholly below each sequence's lowest reachable key
    tables = np.asarray(tables).copy()
    for i, (ctx, qn) in enumerate(specs):
        tables[i, :(ctx - qn - WINDOW + 1) // PAGE] = 0
    assert (tables[:3, 0] == 0).all()
    tables = jnp.asarray(tables)
    kc, vc = kc.at[:, 0].set(1e3), vc.at[:, 0].set(1e3)
    for use_pallas in (True, False):
        got = ragged_paged_attention(
            q, kc, vc, tables, cu, q_lens, seq_lens, n, scale=SCALE,
            window=WINDOW, use_pallas=use_pallas, pages_per_block=2)
        np.testing.assert_allclose(np.asarray(got), want, atol=3e-5)
    rows = np.asarray(cu[:n:2])
    got = paged_attention(q[rows], kc, vc, tables[:n:2], seq_lens[:n:2],
                          scale=SCALE, window=WINDOW, use_pallas=True)
    np.testing.assert_allclose(np.asarray(got), want[rows], atol=3e-5)


def test_the_tile_of_a_16_head_group_at_width_128():
    """What the picker gives 8 kv heads x 16 query heads of 128 on 16-token
    bfloat16 pages: 32 tokens x 16 heads are the 512 rows a tile aims for,
    512 keys a KV block, two slots; every kv head's buffers count 14.7 MB,
    under Mosaic's own 16 MB, so the kernel asks for nothing
    (``vmem_limit_bytes`` has stopped step programs: PERF.md, PR 49); and
    the pool's rows are whole 128-lane tiles."""
    from vllm_omni_tpu.ops.autotune import (
        MOSAIC_VMEM,
        _ragged_need,
        auto_ragged_blocks,
        ragged_vmem_limit,
    )
    from vllm_omni_tpu.ops.paged_attention import (
        heads_a_row,
        paged_tiling_reason,
    )

    blocks = auto_ragged_blocks(head_dim=D, page_size=PAGE, group=GROUP,
                                kv_itemsize=2, q_itemsize=2, kv_heads=HKV)
    assert blocks == (32, 32, 2)
    need = _ragged_need(*blocks, D, PAGE, GROUP, 2, 2, False, 2, HKV)
    assert 14.6e6 < need < 14.8e6 < MOSAIC_VMEM
    assert ragged_vmem_limit(D, PAGE, GROUP, 2, 2, False, 2, kv_heads=HKV,
                             blocks=blocks) is None
    assert paged_tiling_reason(D, PAGE, HKV) == ""
    assert heads_a_row(D, HKV) == 1
