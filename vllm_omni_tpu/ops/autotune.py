"""Per-shape kernel block selection, shared across the attention family.

``auto_blocks`` is the dense flash kernel's (block_q, block_k) picker —
lifted out of ``ops/attention.py`` so the ragged paged kernel can reuse
the same methodology ("The Anatomy of a Triton Attention Kernel",
PAPERS.md: per-shape tile choice is where the MFU lives; a fixed grid
ran 13% MFU on the DiT joint sequence, the tuned one 68%).

``auto_ragged_blocks`` applies it to the ragged paged-attention kernel's
two knobs — the per-sequence q block (``token_block``) and the page-DMA
pipeline depth (``dma_slots``) — under the VMEM budget a grid cell
actually has.  Both pickers preserve the guaranteed-fit fallback: a cap
below every candidate shrinks the choice instead of crashing.
"""

from __future__ import annotations

import functools

#: f32 score-block element budget for the dense kernel (~8 MB)
SCORE_CAP = 2_097_152

#: VMEM byte budget for one ragged grid cell's working set (q block +
#: f32 accumulator + output + KV DMA buffers + score block).  VMEM is
#: ~16 MiB/core; the budget leaves headroom for compiler temporaries
#: and the second grid cell XLA may keep in flight.
RAGGED_VMEM_CAP = 4 * 1024 * 1024


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


@functools.lru_cache(maxsize=64)
def auto_ragged_blocks(
    head_dim: int,
    page_size: int,
    group: int = 1,
    kv_itemsize: int = 2,
    q_itemsize: int = 4,
    decode_heavy: bool = True,
    vmem_cap_bytes: int = RAGGED_VMEM_CAP,
    quantized: bool = False,
) -> tuple[int, int]:
    """(token_block, dma_slots) for the ragged paged-attention kernel.

    The search runs PER LAYOUT (the lru key includes ``quantized``): the
    int8 layout halves the page DMA buffers (kv_itemsize is the int8
    data's) but adds an f32 dequant staging copy of the in-flight page
    (its per-page scales live in SMEM, not VMEM) — a deeper pipeline may
    fit quantized where bf16 took 2 slots, and the warmup log shows
    which choice each layout got.

    ``token_block`` is the per-sequence q block in TOKENS and doubles as
    the host packer's segment alignment — every (packed) decode row
    costs ``token_block`` rows, so a decode-heavy serving mix
    (``decode_heavy=True``, the engine default) pins it at 8 (the f32
    sublane tile at group=1); a prefill-dominated deployment may take 16
    to halve the number of q blocks — each block re-reads its
    sequence's whole paged context, so fewer blocks = half the HBM
    traffic — at 16 rows/decode-row padding cost.

    ``dma_slots`` is the HBM→VMEM page pipeline depth: ``slots - 1``
    pages are in flight while one is being consumed, so deeper pipelines
    hide more HBM latency (the decode inner loop is DMA-bound — the
    whole context streams through VMEM once per q block).  Deeper costs
    ``2 * page_size * head_dim * kv_itemsize`` bytes per extra slot;
    the picker takes the deepest of (4, 3, 2) that fits the cell
    budget, and the guaranteed-fit fallback degrades to classic double
    buffering (2) rather than failing."""
    for tb in ((8, 16) if decode_heavy else (16, 8)):
        rows = tb * max(group, 1)
        # q block (input itemsize) + f32 accumulator + output block
        fixed = rows * head_dim * (q_itemsize + 4 + kv_itemsize)
        # f32 score block per page
        fixed += rows * page_size * 4
        if quantized:
            # the f32 dequant staging copy of the page being consumed
            # (k and v)
            fixed += 2 * page_size * head_dim * 4
        for slots in (4, 3, 2):
            kv = 2 * slots * page_size * head_dim * kv_itemsize
            if fixed + kv <= vmem_cap_bytes:
                return tb, slots
    # guaranteed fit: the smallest working set the kernel supports
    return 8, 2
