"""Ragged paged attention — one token-packed kernel for prefill + decode,
and the one block loop over paged KV the package has.

The unified-batching counterpart of the chunked path of
``ops/attention.py`` (padded [B, S] chunks): variable-length prefill
chunks and 1-token decode rows ride the SAME token-packed launch, so a
mixed engine step is ONE device dispatch instead of the
fresh/chunk/decode triple ("Ragged Paged Attention: A High-Performance
and Flexible LLM Inference Kernel for TPU", PAPERS.md).  A pure decode
step enters the same kernel through ``paged_attention`` below: one
token a segment, ``token_block = 1`` (until PR 33 a kernel of its own
that took one page an iteration).

Token-packed layout (the metadata contract, see docs/ragged_batching.md):

- ``q [T, H, D]`` — queries of every scheduled sequence, concatenated.
  Sequence ``i``'s ``q_lens[i]`` query tokens occupy rows
  ``cu_q_lens[i] .. cu_q_lens[i] + q_lens[i])``.  Segment starts are
  aligned to ``token_block`` rows (the PACKING alignment, 8: what the
  packer honours), so a ``token_block``-token sub-block belongs to at
  most one sequence — the alignment gap is at most ``token_block - 1``
  rows per sequence, replacing the ``batch_bucket x seq_bucket``
  padding of the split path.  The kernel's q block (``q_block``, a
  multiple of ``token_block``) is NOT part of the contract: the kernel
  derives it from the shapes and the packer never sees it.
- ``cu_q_lens [S+1]`` — aligned segment starts (row offsets into ``q``);
  ``cu_q_lens[num_seqs]`` is the packed end.  NOT simply the cumsum of
  ``q_lens`` — alignment rounds each segment up.
- ``q_lens [S]`` — real (unaligned) query-token count per sequence;
  0 for padding rows of the metadata arrays.
- ``seq_lens [S]`` — context length per sequence INCLUDING this chunk
  (the ``context_lens`` convention of ``forward_decode``).
- ``page_tables [S, max_pages]`` — KV page ids covering each context.
- ``v_cache=None`` — the shared layout of ``ops/paged_attention.py`` (a
  latent cache): one pool whose row is the key and, in its first
  ``v_dim`` lanes, the value; the kernel brings a KV block in once and
  reads both from it, and the output is ``[T, H, v_dim]``.
- ``num_seqs`` — sequences actually present (rows past it are padding).
- a V pool may be narrower than the K pool (a key of 192 in a row of 256
  lanes beside a value of 128): the value's width is the V pool's, and
  the output is ``[T, H, that]``.
- ``sinks [H]`` float32 — one learned logit a query head in the
  softmax's DENOMINATOR, with no value: the online softmax's running
  maximum and sum start at ``(sinks[h], 1)`` where they otherwise start
  at ``(-inf, 0)``, and nothing else changes.  It is no key of the cache
  and costs no page.

Causality is per-token global positions: query ``j`` of sequence ``i``
sits at ``seq_lens[i] - q_lens[i] + j`` and attends keys at positions
``<= `` that (the ``q_offsets`` semantics of ``ops/attention.py``).  A
decode row is the degenerate ``q_lens[i] == 1`` case — last position,
full context — so decodes and prefill chunks need no special-casing.

The kernel: the grid is (global q block of ``q_block`` tokens,), a
cell holding the block's rows of EVERY kv head (``[Hkv, nsub,
token_block * group, D]``), and an in-jit SMEM lookup
(``_block_owners``) says what each block holds.  A block inside ONE
sequence's segment — nearly every block of a prefill chunk — attends,
kv head by kv head, as one ``q_block x group``-row tile;
a block that holds several sequences (decode rows, a segment's head or
tail: the packer puts decodes first, so a chunk starts at a multiple of
8, not of ``q_block``) walks its ``token_block``-token sub-blocks, each
against its own sequence's pages.  Either way the cell owns its output
rows exclusively.  A tile online-softmaxes over KV blocks of
``pages_per_block`` pages — that many page DMAs into one contiguous
double-buffered VMEM buffer ``[dma_slots, Hkv, keys, D]``, each ONE
strided copy of the page's rows of all kv heads (``pool[:, page_id]``:
until PR 49 the grid had a kv-head axis and every head issued its own),
one wait, then for each kv head in turn (a ``fori_loop``, each head's
running max and sum in scratch beside its accumulator; one kv head
needs neither) one ``rows x D x keys`` product and one back, in the
stored dtype with float32 accumulation — and its loop ends at the
tile's last causal page; only the blocks crossing the diagonal are
masked.  A head's arithmetic and its order of blocks do not depend on
the other heads.  The sub-block
tiles are short (a decode row's context is a KV block or a few), so
each starts the first KV block of the tile after it (the next
sub-block, then the next q block: a tile is a sub-block's rows of every
kv head) while it still computes, across grid cells too: no such tile
waits for a copy it has only just asked for, but the first.
``auto_ragged_blocks`` (ops/autotune.py) derives
(q_block, pages_per_block, dma_slots) from group, head dim, page size
and cache dtype; ``ragged_paged_attention_ref`` is the XLA fallback /
test oracle.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_omni_tpu.ops._dispatch import auto_use_pallas, interpret_flag
from vllm_omni_tpu.ops.autotune import (
    RAGGED_SCALE_ROWS,
    auto_ragged_blocks,
    ragged_vmem_limit,
)
from vllm_omni_tpu.ops.paged_attention import (
    cache_data,
    cache_is_quantized,
    cache_shape,
    gather_pages,
    own_half,
    paged_attention_ref,
    pair_queries,
    row_tiling_reason,
    shared_tiling_reason,
    sink_softmax,
)

_NEG_INF = -1e30

# Segment alignment in TOKENS that the packer must honor, and the
# kernel's sub-block.  8 keeps the f32 sublane tile exact at group=1 and
# bounds per-sequence alignment waste at 7 rows — a decode row costs 8
# packed rows, vs the full (batch, seq) bucket pad of the split path.
# The q block the kernel tiles with is a multiple of it that
# ``auto_ragged_blocks`` (ops/autotune.py) derives per shape.
DEFAULT_TOKEN_BLOCK = 8


# Page copies a turn of the kernel's DMA-issue loop, per cache half.
# Timed on the chip at 32 pages a KV block (PERF.md, PR 26): one copy a
# turn 3.97 ms on a 2048-token chunk, two 3.74, eight 3.69, all 32
# unrolled 3.39 — but the unrolled copies were most of the kernel's
# program: +0.6 s to lower and +5 s to compile EVERY executable that
# holds it (10 s of a 57 s warm set-up).
_PAGE_COPY_UNROLL = 2


def align_to_block(n: int, token_block: int = DEFAULT_TOKEN_BLOCK) -> int:
    """Rows a ``n``-token segment occupies in the packed layout."""
    return -(-n // token_block) * token_block


def ragged_paged_attention_ref(
    q: jax.Array,            # [T, H, D] token-packed queries
    k_cache: jax.Array,      # [Hkv, P, page, D]
    v_cache: jax.Array,
    page_tables: jax.Array,  # [S, max_pages] int32
    cu_q_lens: jax.Array,    # [S+1] int32 aligned segment starts
    q_lens: jax.Array,       # [S] int32
    seq_lens: jax.Array,     # [S] int32 (context incl. this chunk)
    num_seqs,                # int | [] | [1]
    scale: Optional[float] = None,
    v_dim: Optional[int] = None,
    window: Optional[int] = None,
    keep: Optional[jax.Array] = None,  # [T, >= max_ctx] bool
    sinks: Optional[jax.Array] = None,  # [H] float32
):
    """Pure-XLA reference with identical semantics (fp32 softmax).

    Gathers each TOKEN's full context — O(T * max_ctx) memory — so it is
    the oracle and the CPU/interpret fallback for test-scale shapes, not
    a production path (production shapes satisfy the kernel's tiling
    requirements: D % 128 == 0, page_size % 8 == 0)."""
    t, h, d = q.shape
    hkv, _, page, _ = cache_shape(k_cache)
    s_max = q_lens.shape[0]
    group = h // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    nseq = jnp.asarray(num_seqs, jnp.int32).reshape(())
    rows = jnp.arange(t)
    starts = cu_q_lens[:s_max]
    live = jnp.arange(s_max) < nseq
    in_seq = (
        (rows[None, :] >= starts[:, None])
        & (rows[None, :] < (starts + q_lens)[:, None])
        & live[:, None]
    )
    seq_of = jnp.argmax(in_seq, axis=0)          # [T] (0 when padding)
    valid = jnp.any(in_seq, axis=0)              # [T] real-token rows
    tok = rows - starts[seq_of]                  # index within the chunk
    ctx = seq_lens[seq_of]
    q_pos = ctx - q_lens[seq_of] + tok           # global query position

    max_ctx = page_tables.shape[1] * page
    # [Hkv, S, P, page, D] -> [S, max_ctx, Hkv, D] -> per-token [T, ...]
    # (gather_pages dequantizes int8 pages with their per-page scales)
    kg = jnp.transpose(
        gather_pages(k_cache, page_tables), (1, 2, 3, 0, 4)
    ).reshape(s_max, max_ctx, hkv, d)[seq_of]
    if v_cache is None:  # shared: the key row's first v_dim lanes
        vg = kg[..., :v_dim]
    else:
        vg = jnp.transpose(
            gather_pages(v_cache, page_tables), (1, 2, 3, 0, 4)
        ).reshape(s_max, max_ctx, hkv, -1)[seq_of]
    qg = q.reshape(t, hkv, group, d).astype(jnp.float32)
    s = jnp.einsum("thgd,tlhd->thgl", qg, kg.astype(jnp.float32)) * scale
    k_pos = jnp.arange(max_ctx)
    mask = (
        (k_pos[None, :] < ctx[:, None])
        & (k_pos[None, :] <= q_pos[:, None])
        & valid[:, None]
    )
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    if keep is not None:
        mask &= keep[:, :max_ctx]
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    if sinks is None:
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(mask[:, None, None, :], jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        p = p / jnp.where(l == 0.0, 1.0, l)
    else:
        p = sink_softmax(s, mask[:, None, None, :],
                         sinks.reshape(1, hkv, group, 1))
    o = jnp.einsum("thgl,tlhd->thgd", p, vg.astype(jnp.float32))
    return o.reshape(t, h, vg.shape[-1]).astype(q.dtype)


def wide_q_tokens(q_lens, q_block: int,
                  token_block: int = DEFAULT_TOKEN_BLOCK) -> int:
    """New tokens of a packed step that the kernel attends as whole
    ``q_block``-token tiles (the wide path of ``_ragged_kernel``).

    Host arithmetic on the scheduler's ``q_lens`` in packing order —
    the same classification ``_block_owners`` makes inside the jit: a q
    block is wide when every ``token_block``-token sub-block it holds
    belongs to the sequence of its first one (or to nobody)."""
    nsub = q_block // token_block
    subs: list[int] = []  # real tokens per sub-block, in packed order
    owner: list[int] = []
    for i, n in enumerate(q_lens):
        n = int(n)
        for lo in range(0, n, token_block):
            subs.append(min(token_block, n - lo))
            owner.append(i)
    total = 0
    for b in range(0, len(subs), nsub):
        if all(o == owner[b] for o in owner[b: b + nsub]):
            total += sum(subs[b: b + nsub])
    return total


def _block_owners(cu_q_lens, q_lens, num_seqs, nbw: int, nsub: int,
                  token_block: int):
    """(wide_seq [NBW], sub_seq [NBW * nsub]) — the kernel's lookup.

    ``sub_seq`` is the owning sequence of each ``token_block``-token
    sub-block (-1 = padding): segment starts are ``token_block``-
    aligned, so a sub-block belongs to at most one sequence.
    ``wide_seq`` is the owner of a q block that lies inside ONE
    sequence's segment (every sub-block its first one's, or nobody's),
    else -1: such a block attends as one tile, any other sub-block by
    sub-block."""
    s_max = q_lens.shape[0]
    nseq = jnp.asarray(num_seqs, jnp.int32).reshape(())
    starts = cu_q_lens[:s_max].astype(jnp.int32)
    bs = jnp.arange(nbw * nsub, dtype=jnp.int32) * token_block
    in_seq = (
        (bs[None, :] >= starts[:, None])
        & (bs[None, :] < (starts + q_lens.astype(jnp.int32))[:, None])
        & (jnp.arange(s_max)[:, None] < nseq)
    )
    sub_seq = jnp.where(jnp.any(in_seq, axis=0),
                        jnp.argmax(in_seq, axis=0), -1).astype(jnp.int32)
    sub = sub_seq.reshape(nbw, nsub)
    first = sub[:, :1]
    wide = (first >= 0) & jnp.all((sub == first) | (sub < 0), axis=1,
                                  keepdims=True)
    return jnp.where(wide, first, -1)[:, 0], sub_seq


def _ragged_kernel(
    # scalar prefetch (SMEM)
    wide_seq_ref,  # [NBW] owner of a one-sequence q block, else -1
    sub_seq_ref,   # [NBW * nsub] owner per sub-block (-1 = padding)
    cu_ref,        # [S+1] aligned segment starts
    qlen_ref,      # [S]
    slen_ref,      # [S] context lengths
    tables_ref,    # [S, max_pages]
    # inputs
    q_ref,         # [Hkv, nsub, token_block * group, D] VMEM: one q block
    k_hbm,         # [Hkv, P, page, D] ANY/HBM (int8 when quantized)
    # v_hbm likewise, unless the layout is shared (the value is then the
    # first Dv lanes of the key block: one copy of a page, not two);
    # quantized only: k_sc_ref/v_sc_ref [Hkv * nsub, max_pages] SMEM —
    # per (kv head, sub-block), row ``head * nsub + sub``, the
    # sub-block's sequence's per-page scales in page-table order — then
    # outputs o_ref [Hkv, nsub, token_block * group, Dv] and scratch
    # k_buf/v_buf [dma_slots, Hkv, ppb * page, D] (no v_buf when shared),
    # sems [dma_slots, 2], acc_scr [Hkv, nsub * token_block * group, Dv],
    # m_scr/l_scr [Hkv, nsub * token_block * group, 1] where Hkv > 1
    # (each head's running max and sum, which outlive its turn of a KV
    # block; one kv head's are the KV loop's carry),
    # pipe [2] SMEM: what one narrow tile leaves the next (see _narrow).
    # With a selection (``has_keep``): keep_hbm [T / token_block,
    # token_block * keep_rep, L] f32 ANY/HBM after the pools (0 where a
    # token admits a key, -1e30 where not; a sub-block's rows are one
    # slab; a row a token where the group is whole sublane tiles, else
    # one a (token, head) as the scores lie) and keep_buf [dma_slots,
    # nsub | 1, token_block * keep_rep, ppb * page] after the KV buffers:
    # a KV block's columns come in with the block, once for every kv
    # head; sems then [dma_slots, 3].
    # With sinks (``has_sinks``): sink_ref [Hkv, tile rows, 1] f32 VMEM
    # after them, a tile's rows of every kv head's running maximum as
    # they START (row r of kv head h holds the sink of query head h *
    # group + r % group)
    *refs,
    page_size: int,
    token_block: int,
    nsub: int,
    group: int,
    scale: float,
    ppb: int,
    dma_slots: int,
    quantized: bool,
    shared: bool,
    wide: bool,
    window: Optional[int] = None,
    has_keep: bool = False,
    keep_rep: int = 1,
    has_sinks: bool = False,
):
    refs = list(refs)
    v_hbm = None if shared else refs.pop(0)
    keep_hbm = refs.pop(0) if has_keep else None
    sink_ref = refs.pop(0) if has_sinks else None
    k_sc_ref, v_sc_ref = (refs.pop(0), refs.pop(0)) if quantized \
        else (None, None)
    o_ref, k_buf = refs.pop(0), refs.pop(0)
    v_buf = None if shared else refs.pop(0)
    keep_buf = refs.pop(0) if has_keep else None
    sems, acc_scr, *ml_scr, pipe = refs
    m_scr, l_scr = ml_scr or (None, None)  # several kv heads only
    # a window or a selection can hide a whole KV block from a row, so
    # every block is masked and a hidden key's probability is set to 0
    # (without either, block 0 holds key 0, which every row sees)
    bounded = window is not None or has_keep
    # the pools a KV block is copied from, each into its buffer
    halves = ((k_hbm, k_buf),) if shared else ((k_hbm, k_buf),
                                               (v_hbm, v_buf))
    j = pl.program_id(0)   # GLOBAL q block of nsub sub-blocks: the grid
    #                        is (NBW,), run in order (_narrow needs it)
    nbw = pl.num_programs(0)
    hkv = q_ref.shape[0]
    sub_rows = token_block * group
    kvb = ppb * page_size  # keys per KV block (one loop iteration)
    unroll = math.gcd(ppb, _PAGE_COPY_UNROLL)
    d = o_ref.shape[-1]  # the value's width (the key's, unless shared)

    def extent(i, tok0, ntok):
        """(last page, end of the KV blocks, first KV block) of the tile
        of ``ntok`` packed tokens of sequence ``i`` from its chunk token
        ``tok0``."""
        q_len = qlen_ref[i]
        # the loop ends at the last causal key of the tile's last real
        # token, not at the context's end: a chunk's early tiles never
        # fetch the pages above their diagonal
        kv_end = slen_ref[i] - q_len + jnp.minimum(tok0 + ntok, q_len)
        first = 0
        if window is not None:
            # and starts at the block that holds the lowest key the
            # tile's FIRST token admits
            first = jax.lax.div(jnp.maximum(
                slen_ref[i] - q_len + tok0 - (window - 1), 0), kvb)
        return (jax.lax.div(kv_end + page_size - 1, page_size) - 1,
                jax.lax.div(kv_end + kvb - 1, kvb), first)

    def page_idx(last_page, blk, p):
        # the ragged last block refetches the last page into its spare
        # slots: every slot holds real cache rows (finite, masked by
        # position), and the loop body needs no branch
        return jnp.minimum(blk * ppb + p, last_page)

    def each_head(fn):
        # one kv head needs no loop
        if hkv == 1:
            fn(0)
        else:
            jax.lax.fori_loop(0, hkv, lambda h, c: (fn(h), c)[1], 0)

    def start_block(i, last_page, slot, blk, sub0, subs):
        """Start the copies of KV block ``blk`` of sequence ``i`` into
        ``slot``, a page's rows of EVERY kv head in one strided copy
        (``[:, page_id]`` of the pool, ``[Hkv, page, D]``); with a
        selection, also the block's columns of the tile's ``subs``
        sub-blocks from sub-block ``sub0`` on."""
        if has_keep:
            pltpu.make_async_copy(
                keep_hbm.at[pl.ds(sub0, subs), :,
                            pl.ds(pl.multiple_of(blk * kvb, kvb), kvb)],
                keep_buf.at[slot, pl.ds(0, subs)],
                sems.at[slot, 2]).start()

        def copy_pages(g, carry):
            for u in range(unroll):
                p = g * unroll + u
                page_id = tables_ref[i, page_idx(last_page, blk, p)]
                dst = pl.ds(pl.multiple_of(p * page_size, page_size),
                            page_size)
                for half, (hbm, buf) in enumerate(halves):
                    pltpu.make_async_copy(
                        hbm.at[:, page_id], buf.at[slot, :, dst],
                        sems.at[slot, half]).start()
            return carry

        jax.lax.fori_loop(0, ppb // unroll, copy_pages, 0)

    def wait_block(slot, subs):
        # one wait per half for the block's ppb page copies: a DMA
        # semaphore counts bytes, and the descriptor only sizes it
        for half, (_, buf) in enumerate(halves):
            pltpu.make_async_copy(
                buf.at[slot], buf.at[slot], sems.at[slot, half]
            ).wait()
        if has_keep:
            part = keep_buf.at[slot, pl.ds(0, subs)]
            pltpu.make_async_copy(part, part, sems.at[slot, 2]).wait()

    def attend(i, tok0, sub, ntok, q_of, write, sub0, base=0, primed=None,
               hand_off=None):
        """Online softmax of one q tile — ``ntok`` packed tokens of
        sequence ``i`` from its chunk token ``tok0`` (rows pack
        ``group`` query heads per token, token-major), every kv head's
        — over KV blocks of ``ppb`` pages: a block comes in once, and
        each kv head attends it in turn.  ``q_of(head)`` is the head's
        [ntok * group, D] rows and ``write(head, out)`` takes its f32
        [ntok * group, Dv] result.  ``sub`` names the tile's row of a
        head's scale block.
        Block ``b`` lives in DMA slot ``(base + b) % dma_slots``; where
        ``primed`` holds, block 0 is already on its way there (the tile
        before started it), and ``hand_off(slot)`` is called once, when
        the pipeline would reach past this tile's last block, with the
        slot that block would take: the next tile's to start in.
        ``sub0`` is the tile's first sub-block among the packed ones
        (where its rows of the selection lie).  Under a window the
        tile's first block is ``blk0``, not 0, and slots count from
        there."""
        rows = ntok * group
        subs = ntok // token_block
        q_len = qlen_ref[i]
        ctx_len = slen_ref[i]
        last_page, n_blk, blk0 = extent(i, tok0, ntok)

        def slot_of(blk):
            if window is not None:
                blk = blk - blk0
            if isinstance(base, int) and isinstance(blk, int):
                return (base + blk) % dma_slots
            return jax.lax.rem(base + blk, dma_slots)

        def fetch_ahead(blk):
            # block ``blk`` of this tile, or what follows its last one
            @pl.when(blk < n_blk)
            def _own():
                start_block(i, last_page, slot_of(blk), blk, sub0, subs)

            if hand_off is not None:
                @pl.when(blk == n_blk)
                def _next():
                    hand_off(slot_of(blk))

        def page_scales(sc_ref, head, blk):
            # [1, kvb] per-key scale of the block's pages (int8 layout):
            # scalar SMEM reads at each page's table position
            col_page = jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, (1, kvb), 1),
                page_size)
            out = jnp.zeros((1, kvb), jnp.float32)
            for p in range(ppb):
                out = jnp.where(col_page == p,
                                sc_ref[head * nsub + sub,
                                       page_idx(last_page, blk, p)],
                                out)
            return out

        @pl.when(n_blk > 0)
        def _run():
            # prime the pipeline: up to ``dma_slots - 1`` KV blocks in
            # flight before the loop body consumes block 0
            if primed is None:
                start_block(i, last_page, slot_of(blk0), blk0, sub0, subs)
            else:
                @pl.when(jnp.logical_not(primed))
                def _cold():
                    start_block(i, last_page, slot_of(blk0), blk0, sub0,
                                subs)
            for ahead in range(1, dma_slots - 1):
                fetch_ahead(blk0 + ahead)

            # global position per q row (ascending); rows past the
            # segment's real tokens (alignment tail) are zeroed at the
            # end instead of masked in every block
            row_tok = tok0 + jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), group)
            q_pos = ctx_len - q_len + row_tok
            # KV blocks wholly at or below the FIRST row's position are
            # visible to every row: they run without the mask, which
            # only the diagonal blocks need
            n_full = blk0 if bounded else jnp.minimum(
                jax.lax.div(ctx_len - q_len + tok0 + 1, kvb), n_blk)
            def turn(masked, blk, slot, head, m_prev, l_prev):
                """Kv head ``head``'s turn of KV block ``blk``: its
                accumulator in place, its new running (max, sum) back."""
                q = q_of(head)
                acc = acc_scr.at[head, pl.ds(0, rows)]
                k = k_buf[slot, head]
                v = k[:, :d] if shared else v_buf[slot, head]
                if k.dtype != q.dtype:
                    # int8 values are exact in any float dtype: the
                    # page's (head, page) scale multiplies its score and
                    # probability columns, not its 128-wide rows
                    k = k.astype(jnp.float32).astype(q.dtype)
                    v = v.astype(jnp.float32).astype(q.dtype)
                # the MXU takes the stored dtype (bf16 on the chip) and
                # accumulates in f32, as ops/attention.py does
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if quantized:
                    s = s * page_scales(k_sc_ref, head, blk)
                if has_keep:
                    # the selection holds causality too (a token keeps
                    # no key above itself): its 0 / -1e30 is added, a
                    # token's row to each of its ``group`` heads
                    keep = keep_buf[slot, pl.ds(0, subs)]
                    if keep_rep == 1:
                        s = (s.reshape(ntok, group, kvb)
                             + keep.reshape(ntok, 1, kvb)).reshape(rows, kvb)
                    else:
                        s = s + keep.reshape(rows, kvb)
                elif masked:
                    k_pos = blk * kvb + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 1)
                    ok = k_pos <= q_pos
                    if window is not None:
                        ok &= k_pos > q_pos - window
                    s = jnp.where(ok, s, _NEG_INF)
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # a masked key underflows to an exact zero: block 0
                # holds key 0, which every real row sees, so m_new is a
                # real score from the first iteration on
                p = jnp.exp(s - m_new)
                if bounded:
                    # not so under a window or a selection: a row that
                    # sees nothing of this block (nor of those before)
                    # has m_new = -1e30 and would count its keys as 1
                    p = jnp.where(s > 0.5 * _NEG_INF, p, 0.0)
                l_new = l_prev * alpha + jnp.sum(p, axis=-1,
                                                 keepdims=True)
                if quantized:
                    p = p * page_scales(v_sc_ref, head, blk)
                acc[...] = acc[...] * alpha + jnp.dot(
                    p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
                return m_new, l_new

            def body(masked, blk, carry):
                slot = slot_of(blk)
                # keep the pipeline ``dma_slots - 1`` blocks deep: the
                # slot being refilled is the one consumed longest ago
                fetch_ahead(blk + dma_slots - 1)
                wait_block(slot, subs)
                if hkv == 1:
                    # one kv head: its running (max, sum) is the carry,
                    # as it always was (in scratch they cost GLM's and
                    # dots3's calls 4-12% on the chip: PERF.md, PR 49)
                    return turn(masked, blk, slot, 0, *carry)

                def head_turn(head):
                    # several: each head's outlive its turn in scratch
                    # (the heads are a loop, so that the program holds
                    # the turn once whatever Hkv is: ``_PAGE_COPY_UNROLL``
                    # has what an unrolled body costs every executable
                    # at set-up)
                    at = (head, pl.ds(0, rows))
                    m_new, l_new = turn(masked, blk, slot, head, m_scr[at],
                                        l_scr[at])
                    m_scr[at] = m_new
                    l_scr[at] = l_new

                each_head(head_turn)
                return carry

            def finish(head, l_fin):
                write(head, jnp.where(
                    row_tok < q_len,
                    acc_scr[head, pl.ds(0, rows)] / l_fin, 0.0))

            acc_scr[:, pl.ds(0, rows)] = jnp.zeros((hkv, rows, d),
                                                   jnp.float32)
            m0 = jnp.full((rows, 1), _NEG_INF, jnp.float32)
            # the first real block's alpha is an exact 0, so the ones
            # never count; they keep the discarded alignment-tail rows
            # away from 0 / 0
            l0 = jnp.ones((rows, 1), jnp.float32)
            if has_sinks:
                # a sink: the running maximum starts at the head's
                # learned logit and the sum at its ``exp(b - b) = 1``,
                # which now DOES count (no value goes with it)
                m0 = sink_ref[:, pl.ds(0, rows)]
            carry = (m0[0], l0) if has_sinks else (m0, l0)
            if hkv > 1:
                m_scr[:, pl.ds(0, rows)] = m0 if has_sinks else \
                    jnp.broadcast_to(m0, (hkv, rows, 1))
                l_scr[:, pl.ds(0, rows)] = jnp.broadcast_to(
                    l0, (hkv, rows, 1))
                carry = 0
            if not bounded:
                carry = jax.lax.fori_loop(
                    0, n_full, functools.partial(body, False), carry)
            carry = jax.lax.fori_loop(
                n_full, n_blk, functools.partial(body, True), carry)
            if hkv == 1:
                finish(0, carry[1])
            else:
                each_head(lambda head: finish(
                    head, l_scr[head, pl.ds(0, rows)]))

        @pl.when(n_blk <= 0)
        def _empty():
            # the defensive empty-context case owns its rows too
            each_head(lambda head: write(
                head, jnp.zeros((rows, d), jnp.float32)))

    @pl.when(j == 0)
    def _first_cell():
        pipe[0] = 0  # scratch starts undefined: nothing is in flight

    def _wide(w):
        # the whole block inside one sequence's segment (nearly every
        # block of a prefill chunk): all its rows attend as ONE tile
        def write(head, out):
            o_ref[head] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)

        attend(w, j * (nsub * token_block) - cu_ref[w], 0,
               nsub * token_block,
               lambda head: q_ref[head].reshape(nsub * sub_rows,
                                                q_ref.shape[-1]),
               write, j * nsub if has_keep else 0)

    def _narrow():
        # several sequences (decode rows, a segment's head or tail) or
        # padding: each sub-block against its own sequence's pages.
        # Such tiles are short (a decode row's whole context is a block
        # or a few), so a tile that primed its own pipeline would spend
        # most of its time waiting for its first block: each instead
        # starts the FIRST block of the tile after it, in grid order
        # (sub-block, q block; a tile is a sub-block's rows of every kv
        # head; across grid cells too: scratch outlives a cell), as the
        # block that follows its own last one,
        # and says so in ``pipe`` ([0]: block 0 of the coming tile is in
        # flight, [1]: in which slot; that tile then counts its slots
        # from there).  The slot is the one its own pipeline would fill
        # next, so no wait of this tile counts the copy's bytes.
        cells = nbw * nsub  # sub-blocks of the packed step

        def tile_of(at):
            """(sequence or -1, its chunk token) of sub-block ``at``,
            -1 too where its q block attends as one tile."""
            i = sub_seq_ref[at]
            if wide:
                i = jnp.where(wide_seq_ref[jax.lax.div(at, nsub)] < 0, i, -1)
            return i, at * token_block - cu_ref[jnp.maximum(i, 0)]

        def sub_block(sub, carry):
            at = j * nsub + sub
            i, tok0 = tile_of(at)
            # clamp for SMEM reads; the tile below runs only when active
            i_safe = jnp.maximum(i, 0)

            def write(head, out):
                o_ref[head, sub] = out.astype(o_ref.dtype)

            def hand_off(slot):
                nxt = jnp.minimum(at + 1, cells - 1)  # clamp: SMEM reads
                i_nxt, tok0_nxt = tile_of(nxt)
                i_nxt_safe = jnp.maximum(i_nxt, 0)
                last_page, n_blk, first = extent(i_nxt_safe, tok0_nxt,
                                                 token_block)

                @pl.when((at < cells - 1) & (i_nxt >= 0) & (n_blk > 0))
                def _start():
                    start_block(i_nxt_safe, last_page, slot, first, nxt, 1)
                    pipe[0] = 1
                    pipe[1] = slot

            @pl.when(i >= 0)
            def _tile():
                primed = pipe[0] == 1
                base = jnp.where(primed, pipe[1], 0)
                pipe[0] = 0
                attend(i_safe, tok0, sub, token_block,
                       lambda head: q_ref[head, sub], write, at, base,
                       primed, hand_off)

            @pl.when(i < 0)
            def _padding():
                # padding sub-blocks own their rows too — zero them so
                # padded rows of the packed hidden state stay zero
                o_ref[:, sub] = jnp.zeros((hkv, sub_rows, d), o_ref.dtype)

            return carry

        jax.lax.fori_loop(0, nsub, sub_block, 0)

    if wide:
        w = wide_seq_ref[j]
        pl.when(w >= 0)(functools.partial(_wide, w))
        pl.when(w < 0)(_narrow)
    else:
        _narrow()


@functools.partial(
    jax.jit,
    static_argnames=("scale", "token_block", "use_pallas", "q_block",
                     "pages_per_block", "dma_slots", "v_dim", "max_q_len",
                     "window"))
def _ragged_attention(
    q, k_cache, v_cache, page_tables, cu_q_lens, q_lens, seq_lens,
    num_seqs, scale, token_block, use_pallas, q_block, pages_per_block,
    dma_slots, v_dim=None, max_q_len=None, window=None, keep=None,
    sinks=None,
):
    """``max_q_len``: the longest segment the caller can hand in, where
    it knows (the decode entry: 1).  At ``token_block`` or under, no
    sequence spans two sub-blocks, so every q block goes sub-block by
    sub-block and the one-tile path is not traced into the kernel.
    ``window`` (static) and ``keep`` ([T, L] bool, L at least the page
    tables' reach) as ``ragged_paged_attention`` has them; without
    either the kernel traces as it did before they existed.  ``sinks``
    ``[H]`` float32 likewise; the value's width is the V pool's."""
    t, h, d = q.shape
    quantized = isinstance(k_cache, tuple)
    shared = v_cache is None
    k_data, k_scale = k_cache if quantized else (k_cache, None)
    v_data, v_scale = v_cache if quantized else (v_cache, None)
    dv = v_dim if shared else v_data.shape[-1]
    hkv, _, page_size, _ = k_data.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if not use_pallas:
        return ragged_paged_attention_ref(
            q, k_cache, v_cache, page_tables, cu_q_lens, q_lens,
            seq_lens, num_seqs, scale, v_dim, window, keep, sinks,
        )
    if t % token_block or q_block % token_block:
        raise ValueError(
            f"packed length {t} and q block {q_block} must be multiples "
            f"of token_block {token_block}")
    group = h // hkv
    nsub = q_block // token_block
    sub_rows = token_block * group
    wide = max_q_len is None or max_q_len > token_block
    nbw = -(-t // q_block)
    t_pad = nbw * q_block
    max_pages = page_tables.shape[1]
    ppb = max(1, min(pages_per_block, max_pages))
    # [T, H, D] -> [Hkv, NBW, nsub, token_block * group, D]: token-major
    # rows, so row r of sub-block b is (token b*tb + r // group, head
    # r % group); the packed tail pads to a whole q block
    qx = jnp.pad(q, ((0, t_pad - t), (0, 0), (0, 0)))
    qx = jnp.transpose(
        qx.reshape(t_pad, hkv, group, d), (1, 0, 2, 3)
    ).reshape(hkv, nbw, nsub, sub_rows, d)
    wide_seq, sub_seq = _block_owners(
        cu_q_lens, q_lens, num_seqs, nbw, nsub, token_block)
    tile_rows = (nsub if wide else 1) * sub_rows  # of a kv head

    def q_spec(width):
        # a q block's rows of every kv head: one grid cell
        return pl.BlockSpec((hkv, None, nsub, sub_rows, width),
                            lambda j, *_: (0, j, 0, 0, 0),
                            memory_space=pltpu.VMEM)

    in_specs = [q_spec(d)] + [pl.BlockSpec(memory_space=pl.ANY)] * (
        1 if shared else 2)
    operands = [
        wide_seq,
        sub_seq,
        cu_q_lens.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
        page_tables.astype(jnp.int32),
        qx,
        k_data,
    ] + ([] if shared else [v_data])
    keep_scratch = []
    if keep is not None:
        # 0 where the token admits the key, -1e30 where not or past what
        # the caller gave; a sub-block's rows one slab, L whole KV blocks
        # past the tables' reach.  A token's row serves its whole group
        # where that is whole sublane tiles (the kernel broadcasts it);
        # a narrower group gets a row a (token, head), as the scores lie
        keep_rep = 1 if group % 8 == 0 else group
        kvb = ppb * page_size
        reach = -(-max_pages * page_size // kvb) * kvb
        bias = jnp.where(keep[:, :reach], 0.0, _NEG_INF).astype(jnp.float32)
        bias = jnp.pad(bias, ((0, t_pad - t), (0, reach - bias.shape[1])),
                       constant_values=_NEG_INF)
        if keep_rep > 1:
            bias = jnp.repeat(bias, keep_rep, axis=0)
        operands.append(bias.reshape(t_pad // token_block,
                                     token_block * keep_rep, reach))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        keep_scratch = [pltpu.VMEM(
            (dma_slots, nsub if wide else 1, token_block * keep_rep, kvb),
            jnp.float32)]
    if sinks is not None:
        # a tile's rows of every kv head, token-major as the scores lie:
        # row r of kv head n starts at the sink of query head n * group
        # + r % group.  One block, the same for every grid cell
        operands.append(jnp.tile(
            sinks.astype(jnp.float32).reshape(hkv, 1, group),
            (1, tile_rows // group, 1)).reshape(hkv, tile_rows, 1))
        in_specs.append(pl.BlockSpec((hkv, tile_rows, 1),
                                     lambda j, *_: (0, 0, 0),
                                     memory_space=pltpu.VMEM))
    if quantized:
        # per (kv head, SUB-BLOCK), the sub-block's sequence's scales in
        # page-table order (a q block may hold several sequences):
        # [NBW, Hkv * nsub, max_pages], one (Hkv * nsub, max_pages) SMEM
        # block a grid cell.  Padding sub-blocks never touch theirs.
        sub_tables = page_tables.astype(jnp.int32)[jnp.maximum(sub_seq, 0)]
        sc_spec = pl.BlockSpec((None, hkv * nsub, max_pages),
                               lambda j, *_: (j, 0, 0),
                               memory_space=pltpu.SMEM)
        in_specs += [sc_spec, sc_spec]
        sub_tables = sub_tables.reshape(nbw, nsub, max_pages)
        operands += [
            jnp.swapaxes(sc[:, sub_tables], 0, 1).reshape(
                nbw, hkv * nsub, max_pages)
            for sc in (k_scale, v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(nbw,),
        in_specs=in_specs,
        out_specs=q_spec(dv),
        scratch_shapes=[
            pltpu.VMEM((dma_slots, hkv, ppb * page_size, width),
                       k_data.dtype)
            for width in ((d,) if shared else (d, dv))
        ] + keep_scratch + [
            pltpu.SemaphoreType.DMA((dma_slots, 3 if keep_scratch else 2)),
            pltpu.VMEM((hkv, tile_rows, dv), jnp.float32),
        ] + [pltpu.VMEM((hkv, tile_rows, 1), jnp.float32)] * (
            2 if hkv > 1 else 0) + [
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    # None where every kv head's buffers at these blocks fit the
    # picker's cap
    vmem_limit = ragged_vmem_limit(
        d, page_size, group, k_data.dtype.itemsize, q.dtype.itemsize,
        quantized, 1 if shared else 2, kv_heads=hkv,
        blocks=(q_block, ppb, dma_slots))
    out = pl.pallas_call(
        functools.partial(
            _ragged_kernel,
            page_size=page_size,
            token_block=token_block,
            nsub=nsub,
            group=group,
            scale=scale,
            ppb=ppb,
            dma_slots=dma_slots,
            quantized=quantized,
            shared=shared,
            wide=wide,
            **({} if window is None else {"window": window}),
            **({"has_keep": True, "keep_rep": keep_rep}
               if keep_scratch else {}),
            **({} if sinks is None else {"has_sinks": True}),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qx.shape[:-1] + (dv,), q.dtype),
        interpret=interpret_flag(),
        **({} if vmem_limit is None else {
            "compiler_params": pltpu.CompilerParams(
                vmem_limit_bytes=vmem_limit)}),
    )(*operands)
    # [Hkv, NBW, nsub, tb*group, Dv] -> [T, H, Dv]
    out = out.reshape(hkv, t_pad, group, dv)[:, :t]
    return jnp.transpose(out, (1, 0, 2, 3)).reshape(t, h, dv)


def ragged_paged_attention(
    q: jax.Array,            # [T, H, D] token-packed queries
    k_cache,                 # [Hkv, P, page, D] or quantized tuple
    v_cache,
    page_tables: jax.Array,  # [S, max_pages]
    cu_q_lens: jax.Array,    # [S+1] aligned segment starts
    q_lens: jax.Array,       # [S]
    seq_lens: jax.Array,     # [S]
    num_seqs,                # int | [] | [1]
    scale: Optional[float] = None,
    token_block: int = DEFAULT_TOKEN_BLOCK,
    use_pallas: Optional[bool] = None,
    q_block: Optional[int] = None,
    pages_per_block: Optional[int] = None,
    dma_slots: Optional[int] = None,
    v_dim: Optional[int] = None,
    window: Optional[int] = None,
    keep: Optional[jax.Array] = None,
    sinks: Optional[jax.Array] = None,
):
    """Mixed prefill+decode paged attention over a token-packed batch.

    See the module docstring for the layout/metadata contract.  Auto
    dispatch mirrors ``paged_attention``: the Pallas kernel needs the
    geometry ``paged_tiling_reason`` accepts and a ``token_block``-aligned
    packed length; anything else (CPU tests, tiny shapes) takes the XLA
    reference.  An explicit ``use_pallas=True`` is honored as-is and
    fails loudly if unsupported.  ``q_block`` (tokens a grid cell),
    ``pages_per_block`` (pages a loop iteration) and ``dma_slots`` (KV
    blocks in the DMA pipeline) default to the per-shape
    ``auto_ragged_blocks`` choice.

    ``window`` (a Python int): the query at position ``t`` admits key
    ``s`` iff ``t - window < s <= t``; a tile's walk then starts at the
    KV block that holds its first token's lowest key, so a decode row
    reads ``window`` keys' blocks at any context.  ``keep`` ``[T, L]``
    bool (``L`` at least ``max_pages * page_size``): packed row ``t``
    admits key ``s`` iff ``keep[t, s]`` (a selection; it holds causality
    itself: no key above the token is kept); its columns come into VMEM
    with each KV block.  ``sinks`` ``[H]`` float32: a learned logit a
    query head in the softmax's denominator (module docstring).  None of
    them changes what the kernel is without them."""
    quantized = cache_is_quantized(k_cache)
    k_data = cache_data(k_cache)
    # heads of 64 lie two to a row of the pool: the kernel runs the row,
    # at the head's own scale
    head_dim = q.shape[-1]
    q, scale = pair_queries(q, k_data, scale)
    if use_pallas is None:
        reason = (row_tiling_reason(q.shape[-1], k_data.shape[2])
                  or shared_tiling_reason(v_cache, v_dim))
        if not reason and q.shape[0] % token_block:
            reason = (f"packed length {q.shape[0]} % token_block "
                      f"{token_block} != 0")
        use_pallas = auto_use_pallas("ragged_paged_attention", reason)
    if None in (q_block, pages_per_block, dma_slots):
        auto = auto_ragged_blocks(
            head_dim=q.shape[-1], page_size=k_data.shape[2],
            group=q.shape[1] // k_data.shape[0],
            kv_itemsize=k_data.dtype.itemsize,
            q_itemsize=q.dtype.itemsize,
            quantized=quantized, kv_pools=1 if v_cache is None else 2,
            kv_heads=k_data.shape[0], **_tile_hints(window, sinks))
        q_block, pages_per_block, dma_slots = (
            a if x is None else x
            for x, a in zip((q_block, pages_per_block, dma_slots), auto))
    num_seqs = jnp.asarray(num_seqs, jnp.int32)
    return own_half(_ragged_attention(
        q, k_cache, v_cache, page_tables, cu_q_lens, q_lens, seq_lens,
        num_seqs, scale, token_block, use_pallas, q_block,
        pages_per_block, dma_slots, v_dim, window=window, keep=keep,
        sinks=sinks,
    ), k_data, head_dim)


def _tile_hints(window, sinks) -> dict:
    """What ``auto_ragged_blocks`` is told of a call beyond its shapes:
    nothing for a call without sinks, whose tile is the one it had."""
    return {} if sinks is None else {"window": window, "sinks": True}


#: decode rows a grid cell of the decode entry holds.  A cell is only the
#: unit in which queries come in and outputs go out (the KV pipeline
#: runs on from one cell's last row into the next cell's first), so it
#: is kept small: the int8 layout's per-row scale tables are SMEM blocks
#: a cell, two of ``[Hkv * rows, max_pages]`` float32.
DECODE_ROWS_A_CELL = 16


@functools.partial(
    jax.jit,
    static_argnames=("scale", "use_pallas", "v_dim", "q_block",
                     "pages_per_block", "dma_slots", "window"))
def _paged_attention(
    q, k_cache, v_cache, block_tables, context_lens, scale, use_pallas,
    v_dim, q_block, pages_per_block, dma_slots, window=None, keep=None,
    sinks=None,
):
    b, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if not use_pallas:
        return paged_attention_ref(
            q, k_cache, v_cache, block_tables, context_lens, scale, v_dim,
            window, keep, sinks)
    hkv = cache_shape(k_cache)[0]
    group = h // hkv
    # a row's query heads of one kv head are a tile's rows: whole
    # sublane tiles of them (20 heads on one latent kv head take 24)
    group_p = -(-group // 8) * 8
    qx = jnp.pad(q.reshape(b, hkv, group, d),
                 ((0, 0), (0, 0), (0, group_p - group), (0, 0)))
    if sinks is not None:  # the padding heads' rows are dropped below
        sinks = jnp.pad(sinks.reshape(hkv, group),
                        ((0, 0), (0, group_p - group))).reshape(-1)
    lens = context_lens.astype(jnp.int32)
    out = _ragged_attention(
        qx.reshape(b, hkv * group_p, d), k_cache, v_cache, block_tables,
        jnp.arange(b + 1, dtype=jnp.int32), (lens > 0).astype(jnp.int32),
        lens, jnp.asarray(b, jnp.int32), scale, 1, True, q_block,
        pages_per_block, dma_slots, v_dim, max_q_len=1, window=window,
        keep=keep, sinks=sinks)
    return out.reshape(b, hkv, group_p, -1)[:, :, :group].reshape(b, h, -1)


def paged_attention(
    q: jax.Array,            # [B, H, D]: one decode token a sequence
    k_cache,                 # [Hkv, P, page, D] or quantized tuple
    v_cache,
    block_tables: jax.Array,  # [B, max_pages]
    context_lens: jax.Array,  # [B] (including the new token)
    scale: Optional[float] = None,
    use_pallas: Optional[bool] = None,
    v_dim: Optional[int] = None,
    window: Optional[int] = None,
    keep: Optional[jax.Array] = None,  # [B, L] bool
    sinks: Optional[jax.Array] = None,  # [H] float32
):
    """Single-token-per-sequence paged decode attention: the decode entry
    of the ragged kernel.  The batch is laid out, inside the jit, as
    ``B`` segments of ONE token with ``token_block = 1``: sequence ``i``
    is packed row ``i``, its query heads of a kv head (padded to whole
    sublane tiles) are a tile's rows of that head, and the tile walks
    its context in the kernel's KV blocks, every kv head attending a
    block in turn, with the next row's first block in flight
    (``_ragged_kernel``'s narrow path; a row of context 0 is a padding
    row and reads zeros).  Callers keep ``B`` rows: nothing is packed
    eightfold.  An explicit ``use_pallas=True`` is honored as-is (kernel
    tests rely on it; unsupported shapes then fail loudly at compile).
    ``v_cache=None`` is the shared layout: the value is the first
    ``v_dim`` lanes of the key row, read from the key's tile, and the
    output is ``[B, H, v_dim]``.  ``window``, ``keep`` and ``sinks`` as
    ``ragged_paged_attention`` has them (the row's token sits at
    ``context_lens - 1``)."""
    k_data = cache_data(k_cache)
    head_dim = q.shape[-1]
    # heads of 64 lie two to a row of the pool
    q, scale = pair_queries(q, k_data, scale)
    if use_pallas is None:
        use_pallas = auto_use_pallas(
            "paged_attention",
            row_tiling_reason(q.shape[-1], k_data.shape[2])
            or shared_tiling_reason(v_cache, v_dim))
    hkv = k_data.shape[0]
    group = q.shape[1] // hkv
    quantized = cache_is_quantized(k_cache)
    _, pages_per_block, dma_slots = auto_ragged_blocks(
        head_dim=q.shape[-1], page_size=k_data.shape[2],
        group=-(-group // 8) * 8, kv_itemsize=k_data.dtype.itemsize,
        q_itemsize=q.dtype.itemsize, quantized=quantized,
        kv_pools=1 if v_cache is None else 2, kv_heads=hkv,
        **_tile_hints(window, sinks))
    rows = min(q.shape[0], DECODE_ROWS_A_CELL)
    if quantized:  # a scale row a (kv head, decode row) in SMEM
        rows = min(rows, max(1, RAGGED_SCALE_ROWS // hkv))
    return own_half(_paged_attention(
        q, k_cache, v_cache, block_tables, context_lens, scale, use_pallas,
        v_dim, rows, pages_per_block, dma_slots, window=window, keep=keep,
        sinks=sinks),
        k_data, head_dim)
