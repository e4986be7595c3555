"""Paged KV-cache attention — Pallas replacement for vLLM's PagedAttention
CUDA kernels (SURVEY.md §2.10; used by the reference through
GPUModelRunner's attention metadata, worker/gpu_ar_model_runner.py:243-255).

Cache layout (TPU-first): ``[Hkv, num_pages, page_size, D]`` — fixing the
head and page indices yields a *contiguous* (page_size, D) tile, so the
decode kernel's HBM→VMEM page DMAs are dense (the CUDA layout
[pages, page_size, Hkv, D] would stride every row on TPU).

Quantized layout (``--kv-cache-dtype int8``): each cache half becomes a
2-tuple ``(data int8 [Hkv, P, page_size, D], scale f32 [Hkv, P])`` — one
absmax scale per (kv-head, page), rounding shared with
``kvcache/quant.py``.  The pytree structure carries the layout through
jit, so every op here branches statically on ``isinstance(half, tuple)``
and the kernels dequantize in-register after the page DMA (the math
stays f32; only HBM bytes shrink ~2x).

Three ops:
- ``write_kv_cache``  — slot-mapping scatter of new K/V into the paged cache
  (the quantized branch grows per-page scales monotonically within a
  page's tenancy and rescales the page's prior rows in-place)
- ``paged_attention_ref`` — gather-based XLA fallback (also the test oracle)
- ``paged_attention`` — Pallas decode kernel: per (seq, kv-head) grid cell,
  double-buffered page DMAs + online softmax over pages.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_omni_tpu.kvcache.quant import QMAX, SCALE_EPS
from vllm_omni_tpu.ops._dispatch import auto_use_pallas, interpret_flag

_NEG_INF = -1e30


def init_kv_cache(
    num_layers: int,
    num_pages: int,
    page_size: int,
    num_kv_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
    quantized: bool = False,
):
    """Allocate per-layer (k, v) caches.

    ``quantized`` allocates the int8 layout: each half is
    ``(data int8, scale f32 [Hkv, P])``; zero scales mean "never
    written" and dequantize to the same zeros the bf16 pool starts
    with."""
    shape = (num_kv_heads, num_pages, page_size, head_dim)
    if quantized:
        return [
            ((jnp.zeros(shape, jnp.int8),
              jnp.zeros((num_kv_heads, num_pages), jnp.float32)),
             (jnp.zeros(shape, jnp.int8),
              jnp.zeros((num_kv_heads, num_pages), jnp.float32)))
            for _ in range(num_layers)
        ]
    return [
        (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
        for _ in range(num_layers)
    ]


def cache_is_quantized(cache_half) -> bool:
    """True for the (data, scale) int8 layout of one cache half."""
    return isinstance(cache_half, tuple)


def cache_data(cache_half) -> jax.Array:
    """The [Hkv, P, page_size, D] data array of either layout."""
    return cache_half[0] if isinstance(cache_half, tuple) else cache_half


def cache_shape(cache_half) -> tuple:
    return cache_data(cache_half).shape


def gather_pages(cache_half, page_ids: jax.Array) -> jax.Array:
    """Dequantizing page gather: ``cache[:, page_ids]`` for either
    layout.  Returns ``[Hkv, *page_ids.shape, page_size, D]`` — float32
    when quantized, the cache dtype otherwise."""
    if isinstance(cache_half, tuple):
        data, scale = cache_half
        return (data[:, page_ids].astype(jnp.float32)
                * scale[:, page_ids][..., None, None])
    return cache_half[:, page_ids]


def _write_kv_quant(cache_half, x_new, slot_mapping):
    """Quantized slot scatter for one cache half.

    Per touched page: (1) a page whose FIRST row is being written is a
    fresh tenancy — its old scale (a previous sequence's) is treated as
    zero so stale scales never leak across the page pool's reuse; (2)
    the scale grows monotonically, ``new = max(old, absmax(new)/127)``,
    and the page's prior int8 rows are rescaled onto the grown scale
    in-place (cost O(T * page_size), never O(cache)); (3) the new rows
    quantize with the settled scale and scatter through the flat-slot
    view exactly like the dense path (slot -1 drops)."""
    data, scale = cache_half  # int8 [Hkv,P,ps,D], f32 [Hkv,P]
    hkv, p, ps, d = data.shape
    xn = jnp.moveaxis(x_new, 1, 0).astype(jnp.float32)  # [Hkv, T, D]
    slots = jnp.where(slot_mapping < 0, p * ps, slot_mapping)
    pages = slots // ps  # p (out of range -> dropped) for padding rows
    offs = slots % ps
    ones = jnp.ones_like(pages, jnp.int32)
    fresh = jnp.zeros((p,), jnp.int32).at[pages].max(
        jnp.where(offs == 0, ones, 0), mode="drop")
    touched = jnp.zeros((p,), jnp.int32).at[pages].max(ones, mode="drop")
    old = jnp.where(fresh[None, :] > 0, 0.0, scale)
    cand = jnp.zeros((hkv, p), jnp.float32).at[:, pages].max(
        jnp.max(jnp.abs(xn), axis=-1), mode="drop") / QMAX
    new_scale = jnp.where(
        touched[None, :] > 0,
        jnp.maximum(jnp.maximum(old, cand), SCALE_EPS), scale)
    # rescale what the touched pages already hold onto the grown scale
    # (fresh pages get ratio 0: the previous tenant's rows zero out)
    ratio = (old / jnp.maximum(new_scale, SCALE_EPS))[:, pages]
    pg = data[:, pages].astype(jnp.float32) * ratio[..., None, None]
    pg = jnp.clip(jnp.round(pg), -QMAX, QMAX).astype(jnp.int8)
    data = data.at[:, pages].set(pg, mode="drop")
    # quantize + scatter the step's rows
    s_tok = jnp.maximum(new_scale[:, pages], SCALE_EPS)  # [Hkv, T]
    qn = jnp.clip(jnp.round(xn / s_tok[..., None]),
                  -QMAX, QMAX).astype(jnp.int8)
    flat = data.reshape(hkv, p * ps, d).at[:, slots].set(qn, mode="drop")
    return flat.reshape(data.shape), new_scale


@jax.jit
def write_kv_cache(
    k_cache,  # [Hkv, P, page, D] or quantized (data, scale) tuple
    v_cache,
    k_new: jax.Array,  # [T, Hkv, D]
    v_new: jax.Array,
    slot_mapping: jax.Array,  # [T] int32, flat slot = page*page_size + offset
):
    """Scatter new KV into the paged cache at the given flat slots.

    Padded tokens use slot -1: they scatter out of bounds, which XLA drops
    (mode=drop), matching the CUDA kernel's ignore-negative-slot contract.
    The quantized (data, scale) layout dispatches on pytree structure —
    static under jit, so both layouts share one entry point.
    """
    if isinstance(k_cache, tuple):  # omnilint: disable=OL1 - pytree STRUCTURE branch (tuple vs array), static at trace time: jit specializes per layout by design
        return (_write_kv_quant(k_cache, k_new, slot_mapping),
                _write_kv_quant(v_cache, v_new, slot_mapping))
    hkv, p, ps, d = k_cache.shape
    kc = k_cache.reshape(hkv, p * ps, d)
    vc = v_cache.reshape(hkv, p * ps, d)
    kn = jnp.moveaxis(k_new, 1, 0).astype(k_cache.dtype)  # [Hkv, T, D]
    vn = jnp.moveaxis(v_new, 1, 0).astype(v_cache.dtype)
    # Negative slots would wrap Python-style; push them out of bounds so
    # mode="drop" discards them.
    slots = jnp.where(slot_mapping < 0, p * ps, slot_mapping)
    kc = kc.at[:, slots].set(kn, mode="drop")
    vc = vc.at[:, slots].set(vn, mode="drop")
    return kc.reshape(k_cache.shape), vc.reshape(v_cache.shape)


def paged_attention_ref(
    q: jax.Array,  # [B, H, D] (one decode token per sequence)
    k_cache,  # [Hkv, P, page, D] or quantized (data, scale) tuple
    v_cache,
    block_tables: jax.Array,  # [B, max_pages] int32 page ids
    context_lens: jax.Array,  # [B] int32
    scale: Optional[float] = None,
):
    b, h, d = q.shape
    hkv, _, page, _ = cache_shape(k_cache)
    group = h // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    max_pages = block_tables.shape[1]
    # Gather pages: [B, Hkv, max_pages, page, D] -> [B, Hkv, L, D]
    kg = jnp.moveaxis(gather_pages(k_cache, block_tables), 0, 1).reshape(
        b, hkv, max_pages * page, d
    )
    vg = jnp.moveaxis(gather_pages(v_cache, block_tables), 0, 1).reshape(
        b, hkv, max_pages * page, d
    )
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    s = jnp.einsum("bngd,bnld->bngl", qg, kg.astype(jnp.float32)) * scale
    pos = jnp.arange(max_pages * page)[None, None, None, :]
    mask = pos < context_lens[:, None, None, None]
    s = jnp.where(mask, s, _NEG_INF)
    p_ = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngl,bnld->bngd", p_, vg.astype(jnp.float32))
    return o.reshape(b, h, d).astype(q.dtype)


def _paged_decode_kernel(
    # scalar prefetch
    block_tables_ref,  # [B, max_pages] (SMEM)
    context_lens_ref,  # [B] (SMEM)
    # inputs
    q_ref,  # [1, 1, group_p, D] VMEM
    k_hbm,  # [Hkv, P, page, D] ANY/HBM (int8 when quantized)
    v_hbm,
    # quantized only: k_sc_ref/v_sc_ref [1, max_pages] SMEM — this
    # sequence's per-page scales in block-table order — then outputs
    # o_ref [1, 1, group_p, D] and scratch
    # k_buf/v_buf [2, page, D], sems [2, 2], acc_scr [group_p, D]
    *refs,
    page_size: int,
    scale: float,
    quantized: bool,
):
    if quantized:
        k_sc_ref, v_sc_ref, o_ref, k_buf, v_buf, sems, acc_scr = refs
    else:
        o_ref, k_buf, v_buf, sems, acc_scr = refs
        k_sc_ref = v_sc_ref = None
    b = pl.program_id(0)
    kvh = pl.program_id(1)
    ctx_len = context_lens_ref[b]
    num_pages = jax.lax.div(ctx_len + page_size - 1, page_size)

    def page_dma(slot, p_idx):
        page_id = block_tables_ref[b, p_idx]
        return (
            pltpu.make_async_copy(
                k_hbm.at[kvh, page_id], k_buf.at[slot], sems.at[slot, 0]
            ),
            pltpu.make_async_copy(
                v_hbm.at[kvh, page_id], v_buf.at[slot], sems.at[slot, 1]
            ),
        )

    @pl.when(num_pages > 0)
    def _run():
        for dma in page_dma(0, 0):
            dma.start()

        def body(p_idx, carry):
            m_prev, l_prev, _ = carry  # acc lives in scratch
            slot = jax.lax.rem(p_idx, 2)
            nxt = jax.lax.rem(p_idx + 1, 2)

            @pl.when(p_idx + 1 < num_pages)
            def _prefetch():
                for dma in page_dma(nxt, p_idx + 1):
                    dma.start()

            for dma in page_dma(slot, p_idx):
                dma.wait()

            q = q_ref[0, 0].astype(jnp.float32)
            k = k_buf[slot].astype(jnp.float32)
            v = v_buf[slot].astype(jnp.float32)
            if quantized:
                # dequantize in-register: one f32 scale per (head, page),
                # a scalar read at the page's table position
                k = k * k_sc_ref[0, p_idx]
                v = v * v_sc_ref[0, p_idx]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            pos = p_idx * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            s = jnp.where(pos < ctx_len, s, _NEG_INF)

            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32,
            )
            return m_new, l_new, 0

        group_p = q_ref.shape[2]
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m0 = jnp.full((group_p, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((group_p, 1), jnp.float32)
        m_fin, l_fin, _ = jax.lax.fori_loop(
            0, num_pages, body, (m0, l0, 0)
        )
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)

    @pl.when(num_pages == 0)
    def _empty():
        o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])


def paged_tiling_reason(head_dim: int, page_size: int) -> str:
    """Why Mosaic cannot tile the paged kernels' (page_size, head_dim)
    page buffers at this geometry ("" when it can): the lane dim must be
    a multiple of 128 and the sublane dim of 8, in either layout (bf16
    and int8 page tiles both compiled and matched the reference at page
    16 on a v5e, CHANGES.md PR 21).  The engine refuses such a geometry
    at construction on a platform that compiles the kernels; the ops
    count it (``_dispatch.auto_use_pallas``)."""
    if head_dim % 128:
        return f"head_dim {head_dim} % 128 != 0"
    if page_size % 8:
        return f"page_size {page_size} % 8 != 0"
    return ""


def _table_order_scales(scale, page_tables):
    """[Hkv, P] per-page scales -> [Hkv, S, 1, max_pages] in each
    sequence's page-table order.  Mosaic can neither tile a (1, P) block
    of an [Hkv, P] array, nor read a VMEM scalar at a dynamic lane
    index, nor broadcast a (1, 1) vector over a page tile — so the few
    scales one sequence needs travel as an SMEM block and the kernels
    read them as true scalars."""
    return scale[:, page_tables][:, :, None, :]


def _page_scale_spec(max_pages: int, head_seq):
    """SMEM block over ``_table_order_scales`` output: the (1, max_pages)
    row of the (kv-head, sequence) pair ``head_seq(*grid_idx,
    *prefetch_refs)`` names."""
    return pl.BlockSpec(
        (None, None, 1, max_pages),
        lambda *a: (*head_seq(*a), 0, 0),
        memory_space=pltpu.SMEM,
    )


@functools.partial(jax.jit, static_argnames=("scale", "use_pallas"))
def _paged_attention(
    q, k_cache, v_cache, block_tables, context_lens, scale, use_pallas
):
    b, h, d = q.shape
    quantized = isinstance(k_cache, tuple)
    k_data, k_scale = k_cache if quantized else (k_cache, None)
    v_data, v_scale = v_cache if quantized else (v_cache, None)
    hkv, _, page_size, _ = k_data.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if not use_pallas:
        return paged_attention_ref(
            q, k_cache, v_cache, block_tables, context_lens, scale
        )
    group = h // hkv
    group_p = max(8, group)  # sublane-align the per-kv-head q group
    qx = q.reshape(b, hkv, group, d)
    if group_p != group:
        qx = jnp.pad(qx, ((0, 0), (0, 0), (0, group_p - group), (0, 0)))
    max_pages = block_tables.shape[1]

    in_specs = [
        pl.BlockSpec(
            (1, 1, group_p, d),
            lambda b_, h_, *_: (b_, h_, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        qx,
        k_data,
        v_data,
    ]
    if quantized:
        sc_spec = _page_scale_spec(max_pages, lambda b_, h_, *_: (h_, b_))
        in_specs += [sc_spec, sc_spec]
        operands += [_table_order_scales(k_scale, block_tables),
                     _table_order_scales(v_scale, block_tables)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, group_p, d),
            lambda b_, h_, *_: (b_, h_, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((2, page_size, d), k_data.dtype),
            pltpu.VMEM((2, page_size, d), v_data.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((group_p, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel,
            page_size=page_size,
            scale=scale,
            quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group_p, d), q.dtype),
        interpret=interpret_flag(),
    )(*operands)
    return out[:, :, :group].reshape(b, h, d)


def paged_attention(
    q: jax.Array,
    k_cache,
    v_cache,
    block_tables: jax.Array,
    context_lens: jax.Array,
    scale: Optional[float] = None,
    use_pallas: Optional[bool] = None,
):
    """Single-token-per-sequence paged decode attention.  An explicit
    ``use_pallas=True`` is honored as-is (kernel tests rely on it;
    unsupported shapes then fail loudly at compile)."""
    if use_pallas is None:
        use_pallas = auto_use_pallas(
            "paged_attention",
            paged_tiling_reason(q.shape[-1], cache_shape(k_cache)[2]))
    return _paged_attention(
        q, k_cache, v_cache, block_tables, context_lens, scale, use_pallas
    )
