"""Per-shape kernel block selection, shared across the attention family.

``auto_blocks`` is the dense flash kernel's (block_q, block_k) picker —
lifted out of ``ops/attention.py`` so the ragged paged kernel can reuse
the same methodology ("The Anatomy of a Triton Attention Kernel",
PAPERS.md: per-shape tile choice is where the MFU lives; a fixed grid
ran 13% MFU on the DiT joint sequence, the tuned one 68%).

``auto_ragged_blocks`` applies it to the ragged paged-attention kernel's
three — the q tile in tokens (``q_block``), the pages a loop iteration
fetches and multiplies at once (``pages_per_block``) and the KV-block
DMA pipeline depth (``dma_slots``) — under the VMEM budget a grid cell
actually has.  Both pickers preserve the guaranteed-fit fallback: a cap
below every candidate shrinks the choice instead of crashing.
"""

from __future__ import annotations

import functools
from typing import Optional

#: f32 score-block element budget for the dense kernel (~8 MB)
SCORE_CAP = 2_097_152

#: VMEM byte budget for one ragged grid cell's working set (q block +
#: f32 accumulator + output + KV DMA buffers + score block and its
#: temporaries).  Mosaic's scoped VMEM limit is 16 MiB a kernel on a
#: v5e; the budget leaves half of it to what the arithmetic below does
#: not see (a 64 x 8-row tile over 512 keys counts 5.3 MiB and runs).
RAGGED_VMEM_CAP = 8 * 1024 * 1024


def auto_blocks(sq: int, skv: int, d: int,
                itemsize: int = 2) -> tuple[int, int]:
    """Pick (block_q, block_k) for the dense kernel by minimizing padded
    MXU work under the score-block VMEM cap.

    Measured on the chip (v5 lite, DiT joint seq 4608, d=128): the old
    fixed (256, 256) grid ran 15552 tiny kernel invocations at 13% MFU —
    per-step overhead dominated; (2048, 1024) hit 56%, and (2304, 768) —
    both dividing the sequence exactly — 68%.  Large q blocks also cut
    HBM traffic (KV is re-read once per q block), so ties prefer the
    bigger bq.  Callers passing explicit block sizes bypass this.

    The cap scales down with head dim and input width: q/k/v blocks and
    the accumulator share VMEM with the score block, and f32 inputs
    double their footprint (measured: (2304, 768) fits at bf16 d=128,
    OOMs by 2.2 MB at f32)."""
    cap = SCORE_CAP * 128 // max(d, 128) * 2 // max(itemsize, 2)

    def padded(s, b):
        return -(-s // b) * b

    best = None
    for bq in (2304, 2048, 1792, 1536, 1280, 1024, 768, 512, 256):
        bq_c = min(bq, max(8, sq))
        for bk in (1024, 896, 768, 640, 512, 384, 256):
            bk_c = min(bk, max(8, skv))
            if bq_c * bk_c > cap:
                continue
            cand = (padded(sq, bq_c) * padded(skv, bk_c), -bq_c, -bk_c)
            if best is None or cand < best[0]:
                best = (cand, bq_c, bk_c)
    if best is None:
        # cap below even the smallest candidate product (huge head dim /
        # wide inputs shrink it past 256*256): fall back instead of
        # crashing on best[1].  Start from the smallest candidate pair
        # and keep halving the larger side until the score block honors
        # the cap too (floor 8 — the minimum tile).
        bq = min(256, max(8, sq))
        bk = min(256, max(8, skv))
        while bq * bk > cap and (bq > 8 or bk > 8):
            if bq >= bk and bq > 8:
                bq = max(8, bq // 2)
            else:
                bk = max(8, bk // 2)
        return bq, bk
    return best[1], best[2]


#: q-tile rows (tokens x GQA group) and keys per KV block the ragged
#: kernel aims for.  Timed on the chip (scripts/ragged_kernel_sweep.py,
#: PERF.md section 6, PR 26): an iteration costs ~0.5 us beside its
#: matrix and softmax work (the DMA wait -> QK -> softmax -> PV chain
#: does not overlap across iterations), so the kernel wants few, large
#: iterations; 512 x 512 is the last step that pays on every shape of
#: the benchmark's cells (1024 keys time 4% slower; 1024 rows gain 13%
#: on 2048-token chunks, nothing on chat steps, and need a whole
#: 128-token block inside one segment).
RAGGED_ROWS = 512
RAGGED_KV_BLOCK = 512

#: page copies one loop iteration may issue per cache half (the loop
#: body unrolls them)
RAGGED_MAX_PAGES_PER_BLOCK = 32

#: widest q block in tokens, whatever the group: a block that holds
#: several sequences walks its 8-token sub-blocks one by one
RAGGED_MAX_Q_BLOCK = 128


#: (kv head, sub-block) rows of the int8 layout's scale blocks a grid
#: cell: two ``[rows, max_pages]`` float32 blocks, double-buffered, in a
#: v5e's 1 MB of SMEM beside the page tables (the thinker's decode entry
#: runs 4 heads x 16 rows over 528 table columns: 528 KB)
RAGGED_SCALE_ROWS = 64

#: what Mosaic gives a kernel that asks for nothing (a v5e)
MOSAIC_VMEM = 16 * 1024 * 1024

#: the most VMEM the ragged kernel asks Mosaic for, where its smallest
#: tile does not fit ``RAGGED_VMEM_CAP`` (a v5e has 128 MiB; Mosaic's own
#: limit is 16 MiB until a kernel asks for more)
RAGGED_VMEM_MAX = 64 * 1024 * 1024


def _ragged_need(bq, ppb, slots, head_dim, page_size, group, kv_itemsize,
                 q_itemsize, quantized, kv_pools, kv_heads=1) -> int:
    """Bytes of VMEM the ragged kernel's buffers take at these blocks.
    A grid cell holds a q block's rows of EVERY kv head and a KV block's
    rows of every kv head (one strided copy a page brings them all), so
    the q and output blocks, the accumulator and the KV buffers count
    ``kv_heads`` times; the score block is one head's, whose turn it
    is."""
    rows, kvb = bq * group, ppb * page_size
    # q block and output block (double-buffered by the grid
    # pipeline) + f32 accumulator.  (A head's running max and sum, two
    # [rows, 1] f32 columns of 512 B a row, are left to the margin, as
    # when they were the loop's carry: counting them moves the tile at
    # 258 of 3840 geometries.)
    need = kv_heads * rows * head_dim * (4 * q_itemsize + 4)
    # f32 score block and its live temporaries (mask, p, p in the
    # matmul dtype)
    need += 4 * rows * kvb * 4
    # K and V buffers, or the one buffer of a shared pool
    need += kv_heads * kv_pools * slots * kvb * head_dim * kv_itemsize
    if quantized:
        # the float staging copy of the block being consumed (k, v)
        need += 2 * kvb * head_dim * 4
    return need


def ragged_vmem_limit(head_dim: int, page_size: int, group: int = 1,
                      kv_itemsize: int = 2, q_itemsize: int = 4,
                      quantized: bool = False, kv_pools: int = 2,
                      kv_heads: int = 1, blocks=None):
    """The bytes the kernel asks Mosaic for (``vmem_limit_bytes``), or
    None: it then asks for nothing and Mosaic's own ``MOSAIC_VMEM``
    stands.  None where the kernel's smallest q tile (the packer's 8
    tokens x ``group`` heads) of one kv head fits ``RAGGED_VMEM_CAP``
    with whole KV blocks, as at every geometry served before PR 34;
    else what that tile needs, which ``auto_ragged_blocks`` budgets a
    head's tile with: 128 latent heads on one kv head are 1024 rows of
    640 lanes a tile, which at the cap left one page an iteration.
    ``blocks``, the (q_block, pages_per_block, dma_slots) the kernel
    runs with its ``kv_heads``, adds the one case the head axis brings:
    the whole past ``MOSAIC_VMEM`` (no cell's shape: the thinker's four
    heads count 9 MB, granite's eight 14.5, and both compile with
    nothing asked, ``tests/ops/test_ragged_tpu_compile.py``).  **Asking
    is not free**: with ``vmem_limit_bytes`` of 14 MB on this kernel
    LFM2's 2 048-token step program never returned on the chip (libtpu
    0.0.34) and with nothing asked the same program did (PERF.md, PR
    49; ROADMAP D21), so the kernel asks only where it must."""
    def need(tile, heads):
        return _ragged_need(*tile, head_dim, page_size, max(group, 1),
                            kv_itemsize, q_itemsize, quantized, kv_pools,
                            heads)

    def asked(bytes_):
        # half as much again: Mosaic's own temporaries beside the model's
        return min(bytes_ + bytes_ // 2, RAGGED_VMEM_MAX)

    one = need((8, max(1, min(RAGGED_MAX_PAGES_PER_BLOCK,
                              RAGGED_KV_BLOCK // page_size)), 2), 1)
    limit = None if one <= RAGGED_VMEM_CAP else asked(one)
    if blocks is not None and need(blocks, kv_heads) > MOSAIC_VMEM:
        limit = max(limit or 0, asked(need(blocks, kv_heads)))
    return limit


@functools.lru_cache(maxsize=64)
def auto_ragged_blocks(
    head_dim: int,
    page_size: int,
    group: int = 1,
    kv_itemsize: int = 2,
    q_itemsize: int = 4,
    vmem_cap_bytes: int = RAGGED_VMEM_CAP,
    quantized: bool = False,
    kv_pools: int = 2,
    kv_heads: int = 1,
    window: Optional[int] = None,
    sinks: bool = False,
) -> tuple[int, int, int]:
    """(q_block, pages_per_block, dma_slots) for the ragged
    paged-attention kernel.

    The tile is ONE kv head's: the budget counts a head's q rows,
    accumulator, score block and share of the KV buffers, which is what
    a turn of the kernel's head loop works on and what the sweep timed.
    ``kv_heads`` multiplies the buffers, not the tile: the kernel asks
    Mosaic for the whole (``ragged_vmem_limit``), and only a head count
    whose whole overruns ``RAGGED_VMEM_MAX`` narrows the choice (no
    shape of the benchmark's cells: 1, 2, 4 and 8 kv heads keep the
    tile they had).

    The search runs PER LAYOUT (the lru key includes ``quantized``): the
    int8 layout halves the KV DMA buffers (kv_itemsize is the int8
    data's) but adds a float staging copy of the block being consumed
    (its per-page scales live in SMEM, not VMEM), and the warmup log
    shows which choice each layout got.

    ``q_block`` is the kernel's q tile in TOKENS, a power-of-two
    multiple of the packer's 8-token segment alignment
    (``DEFAULT_TOKEN_BLOCK``, which it does NOT change: a decode row
    still costs 8 packed rows).  A q block inside one sequence's
    segment attends as one ``q_block x group``-row tile, the widest at
    or under ``RAGGED_ROWS`` rows: group 8 takes 64 tokens, group 1
    ``RAGGED_MAX_Q_BLOCK``.  Each q block re-reads its sequence's paged
    context once, so wider also means fewer HBM reads.

    ``pages_per_block`` is the pages one loop iteration fetches into a
    contiguous VMEM buffer and multiplies at once: ``RAGGED_KV_BLOCK``
    keys (32 pages of 16), at least one page.

    ``dma_slots`` is the HBM->VMEM pipeline depth in KV blocks
    (``slots - 1`` in flight while one is consumed; the kernel takes
    any depth >= 2).  At these block sizes a third slot timed within
    2% of two on every shape, either way, so the picker stays at
    classic double buffering.

    A budget below the first choice halves the KV block, then the q
    tile; the guaranteed-fit fallback is the smallest working set the
    kernel supports, (8, 1, 2), rather than a failure.

    ``sinks`` (a call whose softmax has a learned sink a head; ``window``
    is then its window, where it has one) is the one kind of call that
    tells the picker more than its shapes, and only such a call's tile
    follows it: every other keeps the tile it had.  A window under
    ``RAGGED_KV_BLOCK`` keys caps the KV block at the window (a power of
    two of pages): a tile of ``q_block`` tokens reaches ``q_block +
    window - 1`` keys, and at 128 keys a block of 512 would be mostly
    keys that no row admits.  And the q tile is halved until the WHOLE,
    every kv head's buffers and the ``[rows, 1]`` float32 columns
    (running maximum, sum and the sinks' block: 512 B a row each in
    VMEM, a column of 128-lane registers), fits ``MOSAIC_VMEM``, so that
    the kernel asks Mosaic for nothing (``ragged_vmem_limit``: asking
    stopped a step program once): 8 kv heads of 8 query heads over rows
    of 256 lanes count 18.5 MB at 64 tokens and 10 MB at 32."""
    group = max(group, 1)
    bq = 8
    while bq * 2 * group <= RAGGED_ROWS and bq < RAGGED_MAX_Q_BLOCK:
        bq *= 2
    while quantized and kv_heads * (bq // 8) > RAGGED_SCALE_ROWS and bq > 8:
        bq //= 2  # the scale blocks of every kv head lie in SMEM
    ppb = max(1, min(RAGGED_MAX_PAGES_PER_BLOCK,
                     RAGGED_KV_BLOCK // page_size))
    if sinks and window is not None:
        reach = 1
        while reach * page_size < window:
            reach *= 2
        ppb = min(ppb, reach)
    slots = 2
    if vmem_cap_bytes == RAGGED_VMEM_CAP:
        # a geometry whose smallest tile overruns the cap budgets with
        # what the kernel then asks Mosaic for (``ragged_vmem_limit``)
        vmem_cap_bytes = ragged_vmem_limit(
            head_dim, page_size, group, kv_itemsize, q_itemsize, quantized,
            kv_pools) or vmem_cap_bytes

    def fits(bq, ppb):
        def need(heads):
            return _ragged_need(bq, ppb, slots, head_dim, page_size, group,
                                kv_itemsize, q_itemsize, quantized,
                                kv_pools, heads)
        return (need(1) <= vmem_cap_bytes
                and need(kv_heads) * 3 // 2 <= RAGGED_VMEM_MAX)

    while not fits(bq, ppb):
        if ppb > 1:
            ppb //= 2
        elif bq > 8:
            bq //= 2
        else:
            break

    def whole(bq):
        # the q and output blocks of every kv head, the accumulators, the
        # buffers, and four columns a row (maximum, sum, the sinks' block
        # twice: the grid pipeline double-buffers an input)
        return _ragged_need(bq, ppb, slots, head_dim, page_size, group,
                            kv_itemsize, q_itemsize, quantized, kv_pools,
                            kv_heads) + 4 * kv_heads * bq * group * 512

    while sinks and bq > 8 and whole(bq) > MOSAIC_VMEM:
        bq //= 2
    return bq, ppb, slots
