"""Ragged paged attention — one token-packed kernel for prefill + decode.

The unified-batching counterpart of ``ops/paged_attention.py`` (single
token per sequence) and the chunked path of ``ops/attention.py`` (padded
[B, S] chunks): variable-length prefill chunks and 1-token decode rows
ride the SAME token-packed launch, so a mixed engine step is ONE device
dispatch instead of the fresh/chunk/decode triple ("Ragged Paged
Attention: A High-Performance and Flexible LLM Inference Kernel for
TPU", PAPERS.md).

Token-packed layout (the metadata contract, see docs/ragged_batching.md):

- ``q [T, H, D]`` — queries of every scheduled sequence, concatenated.
  Sequence ``i``'s ``q_lens[i]`` query tokens occupy rows
  ``cu_q_lens[i] .. cu_q_lens[i] + q_lens[i])``.  Segment starts are
  aligned to ``token_block`` rows (the per-sequence q-block size), so a
  (sequence, q-block) grid cell owns an EXCLUSIVE output region — the
  alignment gap is at most ``token_block - 1`` rows per sequence,
  replacing the ``batch_bucket x seq_bucket`` padding of the split path.
- ``cu_q_lens [S+1]`` — aligned segment starts (row offsets into ``q``);
  ``cu_q_lens[num_seqs]`` is the packed end.  NOT simply the cumsum of
  ``q_lens`` — alignment rounds each segment up.
- ``q_lens [S]`` — real (unaligned) query-token count per sequence;
  0 for padding rows of the metadata arrays.
- ``seq_lens [S]`` — context length per sequence INCLUDING this chunk
  (the ``context_lens`` convention of ``forward_prefill_chunked``).
- ``page_tables [S, max_pages]`` — KV page ids covering each context.
- ``num_seqs`` — sequences actually present (rows past it are padding).

Causality is per-token global positions: query ``j`` of sequence ``i``
sits at ``seq_lens[i] - q_lens[i] + j`` and attends keys at positions
``<= `` that (the ``q_offsets`` semantics of ``ops/attention.py``).  A
decode row is the degenerate ``q_lens[i] == 1`` case — last position,
full context — so decodes and prefill chunks need no special-casing.

The kernel follows ``_paged_decode_kernel``'s structure: each grid cell
owns one (sequence, q-block) pair — the grid is (kv-head, global
q-block) and the owning sequence comes from a host-computed SMEM lookup
(alignment makes the mapping unique) — and online-softmaxes over
double-buffered HBM→VMEM page DMAs; ``ragged_paged_attention_ref`` is
the XLA fallback / test oracle.
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
from vllm_omni_tpu.ops.autotune import auto_ragged_blocks
from vllm_omni_tpu.ops.paged_attention import (
    _page_scale_spec,
    _table_order_scales,
    cache_data,
    cache_is_quantized,
    cache_shape,
    gather_pages,
    paged_tiling_reason,
)

_NEG_INF = -1e30

# Per-sequence q-block size in TOKENS (also the segment alignment the
# packer must honor).  8 keeps the f32 sublane tile exact at group=1 and
# bounds per-sequence alignment waste at 7 rows — a decode row costs 8
# packed rows, vs the full (batch, seq) bucket pad of the split path.
# ``auto_ragged_blocks`` (ops/autotune.py) picks the per-shape
# (token_block, dma_slots) pair; this stays the packing contract's
# default.
DEFAULT_TOKEN_BLOCK = 8


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
    vg = jnp.transpose(
        gather_pages(v_cache, page_tables), (1, 2, 3, 0, 4)
    ).reshape(s_max, max_ctx, hkv, d)[seq_of]
    qg = q.reshape(t, hkv, group, d).astype(jnp.float32)
    s = jnp.einsum("thgd,tlhd->thgl", qg, kg.astype(jnp.float32)) * scale
    k_pos = jnp.arange(max_ctx)
    mask = (
        (k_pos[None, :] < ctx[:, None])
        & (k_pos[None, :] <= q_pos[:, None])
        & valid[:, None]
    )
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask[:, None, None, :], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("thgl,tlhd->thgd", p / l_safe, vg.astype(jnp.float32))
    return o.reshape(t, h, d).astype(q.dtype)


def _ragged_kernel(
    # scalar prefetch (SMEM)
    block_seq_ref,  # [NB] owning sequence per q block (-1 = padding)
    cu_ref,       # [S+1] aligned segment starts
    qlen_ref,     # [S]
    slen_ref,     # [S] context lengths
    tables_ref,   # [S, max_pages]
    # inputs
    q_ref,        # [1, 1, token_block * group, D] VMEM
    k_hbm,        # [Hkv, P, page, D] ANY/HBM (int8 when quantized)
    v_hbm,
    # quantized only: k_sc_ref/v_sc_ref [1, max_pages] SMEM — the owning
    # sequence's per-page scales in page-table order — then outputs
    # o_ref [1, 1, token_block * group, D] and scratch
    # k_buf/v_buf [dma_slots, page, D], sems [dma_slots, 2],
    # acc_scr [token_block * group, D]
    *refs,
    page_size: int,
    token_block: int,
    group: int,
    scale: float,
    dma_slots: int,
    quantized: bool,
):
    if quantized:
        k_sc_ref, v_sc_ref, o_ref, k_buf, v_buf, sems, acc_scr = refs
    else:
        o_ref, k_buf, v_buf, sems, acc_scr = refs
        k_sc_ref = v_sc_ref = None
    kvh = pl.program_id(0)
    j = pl.program_id(1)   # GLOBAL q block: segment alignment means it
    #                        belongs to exactly one sequence — the grid
    #                        is (Hkv, NB), no per-sequence dimension and
    #                        no inactive cells beyond the packed tail
    i = block_seq_ref[j]
    # clamp for SMEM reads; every use below is masked by ``active``
    i_safe = jnp.maximum(i, 0)
    q_len = qlen_ref[i_safe]
    ctx_len = slen_ref[i_safe]
    active = i >= 0
    num_pages = jax.lax.div(ctx_len + page_size - 1, page_size)

    def page_dma(slot, p_idx):
        page_id = tables_ref[i_safe, p_idx]
        return (
            pltpu.make_async_copy(
                k_hbm.at[kvh, page_id], k_buf.at[slot], sems.at[slot, 0]
            ),
            pltpu.make_async_copy(
                v_hbm.at[kvh, page_id], v_buf.at[slot], sems.at[slot, 1]
            ),
        )

    rows = token_block * group

    @pl.when(jnp.logical_and(active, num_pages > 0))
    def _run():
        # prime the page pipeline: up to ``dma_slots - 1`` pages in
        # flight before the loop body consumes page 0 (dma_slots == 2
        # is classic double buffering; deeper pipelines hide more HBM
        # latency — ops/autotune.py picks the depth per shape)
        for dma in page_dma(0, 0):
            dma.start()
        for ahead in range(1, dma_slots - 1):
            @pl.when(ahead < num_pages)
            def _prime(ahead=ahead):
                for dma in page_dma(ahead, ahead):
                    dma.start()

        # token index within the chunk / global position per q row
        # (rows pack ``group`` query heads per token, token-major);
        # this block's first packed row is j*tb, so its first chunk
        # token is j*tb - cu[i]
        row_tok = j * token_block - cu_ref[i_safe] + jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0),
            group)
        q_pos = ctx_len - q_len + row_tok
        row_valid = row_tok < q_len

        def body(p_idx, carry):
            m_prev, l_prev, _ = carry  # acc lives in scratch
            slot = jax.lax.rem(p_idx, dma_slots)
            # keep the pipeline ``dma_slots - 1`` pages deep: the slot
            # being refilled is the one consumed longest ago
            pre = p_idx + dma_slots - 1
            nxt = jax.lax.rem(pre, dma_slots)

            @pl.when(pre < num_pages)
            def _prefetch():
                for dma in page_dma(nxt, pre):
                    dma.start()

            for dma in page_dma(slot, p_idx):
                dma.wait()

            q = q_ref[0, 0].astype(jnp.float32)
            k = k_buf[slot].astype(jnp.float32)
            v = v_buf[slot].astype(jnp.float32)
            if quantized:
                # dequantize in-register: the page's int8 bytes were
                # DMAed; its (head, page) f32 scale is a scalar read at
                # the page's table position
                k = k * k_sc_ref[0, p_idx]
                v = v * v_sc_ref[0, p_idx]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            k_pos = p_idx * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            mask = (k_pos < ctx_len) & (k_pos <= q_pos) & row_valid
            s = jnp.where(mask, s, _NEG_INF)

            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            # explicit zero for fully-masked rows (segment-tail padding):
            # there s == m_new == _NEG_INF and exp(0) would count them
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32,
            )
            return m_new, l_new, 0

        acc_scr[:] = jnp.zeros_like(acc_scr)
        m0 = jnp.full((rows, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((rows, 1), jnp.float32)
        _, l_fin, _ = jax.lax.fori_loop(0, num_pages, body, (m0, l0, 0))
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(jnp.logical_and(active, num_pages > 0)))
    def _padding():
        # trailing padding blocks (and the defensive empty-context
        # case) own their output block too — zero it so padded rows of
        # the packed hidden state stay exactly zero
        o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])


@functools.partial(
    jax.jit,
    static_argnames=("scale", "token_block", "use_pallas", "dma_slots"))
def _ragged_attention(
    q, k_cache, v_cache, page_tables, cu_q_lens, q_lens, seq_lens,
    num_seqs, scale, token_block, use_pallas, dma_slots,
):
    t, h, d = q.shape
    quantized = isinstance(k_cache, tuple)
    k_data, k_scale = k_cache if quantized else (k_cache, None)
    v_data, v_scale = v_cache if quantized else (v_cache, None)
    hkv, _, page_size, _ = k_data.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if not use_pallas:
        return ragged_paged_attention_ref(
            q, k_cache, v_cache, page_tables, cu_q_lens, q_lens,
            seq_lens, num_seqs, scale,
        )
    if t % token_block:
        raise ValueError(
            f"packed length {t} not a multiple of token_block "
            f"{token_block}")
    group = h // hkv
    s_max = q_lens.shape[0]
    nb = t // token_block
    rows = token_block * group
    # [T, H, D] -> [Hkv, NB, token_block * group, D]: token-major rows
    # so q row r of block b is (token b*tb + r // group, head r % group)
    qx = jnp.transpose(
        q.reshape(t, hkv, group, d), (1, 0, 2, 3)
    ).reshape(hkv, nb, rows, d)

    # Owning sequence per GLOBAL q block (-1 = packed-tail padding):
    # segment starts are token_block-aligned, so every block belongs to
    # at most one sequence — the grid is (Hkv, NB) with no dead
    # per-sequence dimension, and the block specs need no
    # prefetch-dependent index math.
    nseq = jnp.asarray(num_seqs, jnp.int32).reshape(())
    starts = cu_q_lens[:s_max].astype(jnp.int32)
    bs = jnp.arange(nb, dtype=jnp.int32) * token_block
    in_seq = (
        (bs[None, :] >= starts[:, None])
        & (bs[None, :] < (starts + q_lens.astype(jnp.int32))[:, None])
        & (jnp.arange(s_max)[:, None] < nseq)
    )
    block_seq = jnp.where(jnp.any(in_seq, axis=0),
                          jnp.argmax(in_seq, axis=0), -1).astype(jnp.int32)

    in_specs = [
        pl.BlockSpec((1, 1, rows, d),
                     lambda kvh, j, *_: (kvh, j, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [
        block_seq,
        cu_q_lens.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        seq_lens.astype(jnp.int32),
        page_tables.astype(jnp.int32),
        qx,
        k_data,
        v_data,
    ]
    if quantized:
        # the block's owning sequence comes from the prefetched lookup
        # (clamped like the kernel's own SMEM reads; padding blocks
        # never touch the scales)
        sc_spec = _page_scale_spec(
            page_tables.shape[1],
            lambda kvh, j, bs, *_: (kvh, jnp.maximum(bs[j], 0)))
        in_specs += [sc_spec, sc_spec]
        operands += [_table_order_scales(k_scale, page_tables),
                     _table_order_scales(v_scale, page_tables)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(hkv, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rows, d),
                               lambda kvh, j, *_: (kvh, j, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((dma_slots, page_size, d), k_data.dtype),
            pltpu.VMEM((dma_slots, page_size, d), v_data.dtype),
            pltpu.SemaphoreType.DMA((dma_slots, 2)),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _ragged_kernel,
            page_size=page_size,
            token_block=token_block,
            group=group,
            scale=scale,
            dma_slots=dma_slots,
            quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hkv, nb, rows, d), q.dtype),
        interpret=interpret_flag(),
    )(*operands)
    # [Hkv, NB, tb*group, D] -> [T, H, D]
    out = out.reshape(hkv, t, group, d)
    return jnp.transpose(out, (1, 0, 2, 3)).reshape(t, h, d)


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
    dma_slots: Optional[int] = None,
):
    """Mixed prefill+decode paged attention over a token-packed batch.

    See the module docstring for the layout/metadata contract.  Auto
    dispatch mirrors ``paged_attention``: the Pallas kernel needs the
    geometry ``paged_tiling_reason`` accepts and a ``token_block``-aligned
    packed length; anything else (CPU tests, tiny shapes) takes the XLA
    reference.  An explicit ``use_pallas=True`` is honored as-is and
    fails loudly if unsupported.  ``dma_slots`` (page-DMA pipeline depth)
    defaults to the per-shape ``auto_ragged_blocks`` choice."""
    quantized = cache_is_quantized(k_cache)
    k_data = cache_data(k_cache)
    if use_pallas is None:
        reason = paged_tiling_reason(q.shape[-1], k_data.shape[2])
        if not reason and q.shape[0] % token_block:
            reason = (f"packed length {q.shape[0]} % token_block "
                      f"{token_block} != 0")
        use_pallas = auto_use_pallas("ragged_paged_attention", reason)
    if dma_slots is None:
        _, dma_slots = auto_ragged_blocks(
            head_dim=q.shape[-1], page_size=k_data.shape[2],
            group=q.shape[1] // k_data.shape[0],
            kv_itemsize=k_data.dtype.itemsize,
            q_itemsize=q.dtype.itemsize,
            quantized=quantized)
    num_seqs = jnp.asarray(num_seqs, jnp.int32)
    return _ragged_attention(
        q, k_cache, v_cache, page_tables, cu_q_lens, q_lens, seq_lens,
        num_seqs, scale, token_block, use_pallas, dma_slots,
    )
