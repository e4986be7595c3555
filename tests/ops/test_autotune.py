"""Shared per-shape block selection (ops/autotune.py): the dense
kernel's (block_q, block_k) picker and the ragged paged kernel's
(q_block, pages_per_block, dma_slots) picker, guaranteed-fit fallbacks
included."""

import jax
import jax.numpy as jnp
import numpy as np

import pytest

from vllm_omni_tpu.ops.autotune import (
    RAGGED_KV_BLOCK,
    RAGGED_MAX_PAGES_PER_BLOCK,
    RAGGED_MAX_Q_BLOCK,
    RAGGED_ROWS,
    RAGGED_VMEM_CAP,
    auto_blocks,
    auto_ragged_blocks,
)


def test_auto_blocks_alias_preserved():
    """ops/attention.py keeps its historical private names as aliases
    of the shared helper — the dense kernel's behavior is unchanged."""
    from vllm_omni_tpu.ops.attention import _SCORE_CAP, _auto_blocks

    assert _auto_blocks is auto_blocks
    assert _SCORE_CAP == 2_097_152
    # the measured-on-chip DiT shape keeps its tuned blocks
    assert auto_blocks(4608, 4608, 128) == (2304, 768)


def test_auto_blocks_guaranteed_fit():
    """A cap below every candidate product shrinks instead of crashing
    (huge head dim / wide inputs)."""
    bq, bk = auto_blocks(4096, 4096, 4096, itemsize=4)
    assert bq >= 8 and bk >= 8
    cap = 2_097_152 * 128 // 4096 * 2 // 4
    assert bq * bk <= cap


def test_auto_ragged_blocks_thinker_shape():
    """The benchmark's shape (32 q / 4 kv heads x 128, 16-token bf16
    pages): the row the chip sweep chose (PERF.md, PR 26) — 64-token q
    tiles (512 rows), 32 pages (512 keys) an iteration, double
    buffering — and the same for the int8 layout."""
    assert auto_ragged_blocks(head_dim=128, page_size=16, group=8,
                              kv_itemsize=2, q_itemsize=2) == (64, 32, 2)
    assert auto_ragged_blocks(head_dim=128, page_size=16, group=8,
                              kv_itemsize=1, q_itemsize=2,
                              quantized=True) == (64, 32, 2)


@pytest.mark.parametrize("group", [1, 2, 4, 8, 16, 64])
@pytest.mark.parametrize("page_size", [8, 16, 128])
def test_auto_ragged_blocks_derives_the_tile_from_the_shapes(group,
                                                             page_size):
    """q_block comes from the arithmetic (rows = q_block x group), not
    from a constant: a power-of-two multiple of the 8-token packing
    alignment, the widest at or under RAGGED_ROWS rows and
    RAGGED_MAX_Q_BLOCK tokens; the KV block aims at RAGGED_KV_BLOCK
    keys whatever the page size, in at most RAGGED_MAX_PAGES_PER_BLOCK
    page copies."""
    bq, ppb, slots = auto_ragged_blocks(head_dim=128, page_size=page_size,
                                        group=group, kv_itemsize=2,
                                        q_itemsize=2)
    assert bq % 8 == 0 and bq & (bq - 1) == 0
    assert bq == max(8, min(RAGGED_MAX_Q_BLOCK, RAGGED_ROWS // group))
    assert ppb == min(RAGGED_MAX_PAGES_PER_BLOCK,
                      RAGGED_KV_BLOCK // page_size)
    assert slots == 2


#: (head_dim, group, kv pools, kv heads) of every cell's attention, and
#: the tile it ran before the kernel held every kv head in one grid cell
#: (ISSUE 49): a narrower tile would give the gain back
CELL_TILES = {
    "thinker_and_lfm2_pairs": ((128, 8, 2, 4), (64, 32, 2)),
    "qwen3next": ((256, 8, 2, 2), (64, 32, 2)),
    "granite4h": ((128, 4, 2, 8), (128, 32, 2)),
    "nemotron3super": ((128, 16, 2, 2), (32, 32, 2)),
    "glm47flash": ((640, 20, 1, 1), (16, 32, 2)),
    "dots3_full": ((640, 128, 1, 1), (8, 32, 2)),
    "dots3_sliding": ((1152, 64, 1, 1), (8, 32, 2)),
}


@pytest.mark.parametrize("cell", sorted(CELL_TILES))
def test_the_head_axis_is_counted_and_keeps_every_cells_tile(cell):
    """``_ragged_need`` counts the q / output blocks, the accumulator and
    the KV buffers once a kv head, the score block once; the picker
    still gives every cell the tile it had; and what the kernel then
    asks Mosaic for covers the whole under ``RAGGED_VMEM_MAX``."""
    from vllm_omni_tpu.ops.autotune import (
        MOSAIC_VMEM,
        RAGGED_VMEM_MAX,
        _ragged_need,
        ragged_vmem_limit,
    )
    (d, group, pools, hkv), tile = CELL_TILES[cell]
    geo = dict(head_dim=d, page_size=16, group=group, kv_itemsize=2,
               q_itemsize=2, kv_pools=pools)
    assert auto_ragged_blocks(**geo) == tile
    assert auto_ragged_blocks(**geo, kv_heads=hkv) == tile
    bq, ppb, slots = tile
    one, whole = (_ragged_need(bq, ppb, slots, d, 16, group, 2, 2, False,
                               pools, heads) for heads in (1, hkv))
    scores = 4 * bq * group * ppb * 16 * 4
    assert whole - scores == hkv * (one - scores)
    # asking Mosaic for VMEM is not free (LFM2's 2 048-token step never
    # returned with 14 MB asked: PERF.md, PR 49): only dots3's tiles ask
    limit = ragged_vmem_limit(d, 16, group, 2, 2, False, pools, hkv, tile)
    assert (limit is None) == (not cell.startswith("dots3"))
    assert limit is None and whole <= MOSAIC_VMEM or (
        whole < limit <= RAGGED_VMEM_MAX)
    # the thinker's four heads: 512 KB of KV buffers (two pools, two
    # slots of 512 keys) and 768 KB of q, output and accumulator a head,
    # 9 MB in all
    if cell == "thinker_and_lfm2_pairs":
        assert whole - one == 3 * (2 * 2 * 512 * 128 * 2 + 512 * 128 * 12)
        assert whole == 9 * 2 ** 20


def test_a_head_count_past_the_most_vmem_narrows_the_tile():
    """128 kv heads of 128 lanes with no GQA would hold 96 MB at the
    one-head tile: the picker halves the KV block until half as much
    again fits ``RAGGED_VMEM_MAX``; the benchmark's head counts never
    get there."""
    from vllm_omni_tpu.ops.autotune import RAGGED_VMEM_MAX, _ragged_need
    geo = dict(head_dim=128, page_size=16, group=1, kv_itemsize=2,
               q_itemsize=2)
    one = auto_ragged_blocks(**geo)
    many = auto_ragged_blocks(**geo, kv_heads=128)
    assert many[0] == one[0] and many[1] < one[1]
    assert _ragged_need(*many, 128, 16, 1, 2, 2, False, 2,
                        128) * 3 // 2 <= RAGGED_VMEM_MAX


def test_auto_ragged_blocks_guaranteed_fit():
    """A VMEM budget below every candidate degrades to the smallest
    working set the kernel supports (one 8-token sub-block, one page,
    classic double buffering) instead of failing."""
    assert auto_ragged_blocks(head_dim=4096, page_size=512, group=16,
                              kv_itemsize=4, q_itemsize=4,
                              vmem_cap_bytes=1 << 16) == (8, 1, 2)


def test_auto_ragged_blocks_budget_monotone():
    """Shrinking the budget never grows the tile: the KV block halves
    first, then the q block."""
    picks = [auto_ragged_blocks(head_dim=256, page_size=128, group=8,
                                kv_itemsize=2, q_itemsize=2,
                                vmem_cap_bytes=cap)
             for cap in (RAGGED_VMEM_CAP, RAGGED_VMEM_CAP // 8,
                         RAGGED_VMEM_CAP // 64, RAGGED_VMEM_CAP // 512)]
    for (bq0, ppb0, _), (bq1, ppb1, _) in zip(picks, picks[1:]):
        assert bq1 <= bq0 and ppb1 <= ppb0
    assert picks[0] != picks[-1]
    # the q block gives way only once the KV block is down to a page
    assert all(ppb == 1 for bq, ppb, _ in picks if bq < picks[0][0])
    assert all(slots >= 2 for _, _, slots in picks)


def test_ragged_kernel_matches_ref_at_deeper_dma(monkeypatch):
    """The N-deep KV-block DMA pipeline (interpret mode) is numerically
    identical to the XLA reference at every supported depth, over
    one-page KV blocks so that a context is several of them."""
    from vllm_omni_tpu.ops.ragged_paged_attention import (
        ragged_paged_attention,
        ragged_paged_attention_ref,
    )

    monkeypatch.setenv("OMNI_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(0)
    hkv, group, d, page = 2, 2, 128, 8
    h = hkv * group
    s_max, pages = 4, 3
    q_lens = np.array([1, 5, 8, 0], np.int32)     # decode + ragged rows
    seq_lens = np.array([9, 13, 8, 0], np.int32)
    cu = np.array([0, 8, 16, 24, 24], np.int32)   # 8-aligned starts
    t = 24
    q = jnp.asarray(rng.standard_normal((t, h, d)), jnp.float32)
    k_cache = jnp.asarray(
        rng.standard_normal((hkv, 16, page, d)), jnp.float32)
    v_cache = jnp.asarray(
        rng.standard_normal((hkv, 16, page, d)), jnp.float32)
    tables = jnp.asarray(
        rng.integers(0, 16, (s_max, pages)), jnp.int32)
    args = (q, k_cache, v_cache, tables, jnp.asarray(cu),
            jnp.asarray(q_lens), jnp.asarray(seq_lens), 3)
    want = ragged_paged_attention_ref(*args)
    for slots in (2, 3, 4):
        got = ragged_paged_attention(*args, use_pallas=True,
                                     pages_per_block=1, dma_slots=slots)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5,
            err_msg=f"dma_slots={slots}")
        # rows past each segment's real tokens stay exactly zero
        pad = np.asarray(got)[int(cu[0]) + 1: 8]
        assert np.all(pad == 0.0), f"dma_slots={slots}"
