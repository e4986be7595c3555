"""Paged KV-cache attention — Pallas replacement for vLLM's PagedAttention
CUDA kernels (SURVEY.md §2.10; used by the reference through
GPUModelRunner's attention metadata, worker/gpu_ar_model_runner.py:243-255).

Cache layout (TPU-first): ``[Hkv, num_pages, page_size, D]`` — fixing the
head and page indices yields a *contiguous* (page_size, D) tile, so the
attention kernel's HBM→VMEM page DMAs are dense (the CUDA layout
[pages, page_size, Hkv, D] would stride every row on TPU).

Quantized layout (``--kv-cache-dtype int8``): each cache half becomes a
2-tuple ``(data int8 [Hkv, P, page_size, D], scale f32 [Hkv, P])`` — one
absmax scale per (kv-head, page), rounding shared with
``kvcache/quant.py``.  The pytree structure carries the layout through
jit, so every op here branches statically on ``isinstance(half, tuple)``
and the kernels dequantize in-register after the page DMA (the math
stays f32; only HBM bytes shrink ~2x).

Shared layout (a latent cache, ``v_cache is None``): a layer holds ONE
pool ``[1, P, page_size, W]`` whose row is at once the key (all ``W``
lanes) and the value (its first ``v_dim`` lanes) of the one kv head every
query head attends: multi-head latent attention in its absorbed form
(``W`` = the latent row and the shared rotary key, padded to a multiple of
128 lanes with zeros that the query's zeros meet).  Every op here takes it
as the same (k, v) pair with ``v`` absent: the write moves one pool's
pages, the kernels bring a page in once and read both operands from that
tile (a second copy of the page would double the bytes of a memory-bound
kernel).  The row's width and ``v_dim`` are shapes the ops read.

The ops here (the decode entry ``paged_attention`` lives beside the one
kernel that reads the pages, ``ops/ragged_paged_attention.py``):
- ``write_kv_cache``  — a step's new K/V rows into the paged cache at
  their flat slots, in the pool's own layout and in place (PR 31).  On
  the TPU a Pallas kernel brings each touched page ``[Hkv, page, D]``
  into VMEM once a block of tokens (128), lays the block's rows into it
  and writes it back, the pools aliased from input to output
  (``_write_pages``; a bfloat16 row is half a packed sublane, so a page,
  not a row, is what a DMA moves); elsewhere, and for a step of at most
  256 rows a half (a decode batch, whose executable is so spared the
  kernel's trace at every set-up), one XLA scatter of ``[D]`` rows with
  the head folded into the index (``_scatter_rows``).  Neither holds an
  operation of the pool's size.  The scatter this replaced ran
  on the pool viewed ``[Hkv, P*page, D]`` with the head as the leading
  axis of its window: for that XLA gave the operand a layout of its own
  and copied the whole pool in and out, 2.1 GB there and back in every
  step of every cell (13 ms on a v5e).  At ``Hkv = 1`` (a four-chip
  shard) the copies never existed, which is what showed that the
  window's head axis, not the scatter, not the reshape and not donation,
  was the cause; a window of one row needs none at any ``Hkv``, but XLA's
  scatter costs about 75 ns an index whatever the row holds, dropped or
  not, which at ``Hkv*T`` = 16 384 indices a half gives the gain back on
  a 4096-row step (``scripts/kv_write_sweep.py``, PERF.md section 6).
  Head widths 128 and 256 and 1, 2 and 4 kv heads are shapes the one
  write reads, not paths of their own.
  (The quantized branch grows per-page scales monotonically within a
  page's tenancy and rescales the page's prior rows in-place; its rows
  go through ``_scatter_rows``.)
- ``paged_attention_ref`` — gather-based XLA fallback of the decode entry
  (also the test oracle)
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
    shared: bool = False,
    v_head_dim: Optional[int] = None,
):
    """Allocate per-layer (k, v) caches; ``shared`` allocates the one-pool
    layout ``(pool, None)`` whose rows are key and value at once
    (``head_dim`` is then the row's whole width).  ``v_head_dim``: the V
    pool's rows where they are not as wide as the K pool's (a key of 192
    in a row of 256 lanes beside a value of 128).

    ``quantized`` allocates the int8 layout: each half is
    ``(data int8, scale f32 [Hkv, P])``; zero scales mean "never
    written" and dequantize to the same zeros the bf16 pool starts
    with."""
    shape = (num_kv_heads, num_pages, page_size, head_dim)
    v_shape = shape[:3] + (v_head_dim or head_dim,)
    if shared:
        if quantized:
            raise ValueError("the shared (latent) layout has no int8 form")
        return [(jnp.zeros(shape, dtype), None) for _ in range(num_layers)]
    if quantized:
        return [
            ((jnp.zeros(shape, jnp.int8),
              jnp.zeros((num_kv_heads, num_pages), jnp.float32)),
             (jnp.zeros(v_shape, jnp.int8),
              jnp.zeros((num_kv_heads, num_pages), jnp.float32)))
            for _ in range(num_layers)
        ]
    return [
        (jnp.zeros(shape, dtype), jnp.zeros(v_shape, dtype))
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
    quantize with the settled scale and scatter row by row like the
    dense path off the TPU (``_scatter_rows``; slot -1 drops)."""
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
    data = _scatter_rows(data, jnp.moveaxis(qn, 0, 1), slot_mapping)
    return data, new_scale


def _scatter_rows(cache, new, slot_mapping):
    """XLA row scatter, the head folded into the index: the pool viewed
    ``[Hkv*P*page, D]`` (a bitcast) takes ``T*Hkv`` rows of ``[D]`` at
    ``h*P*page + slot``.  A scatter whose window is one row leaves the
    pool in its own layout; with the head as the window's leading axis
    (``[Hkv, P*page, D].at[:, slots]``) XLA copies the whole pool into
    the scatter's layout and back for ``Hkv > 1``.  About 75 ns an index
    on a v5e (PERF.md, PR 31), whatever the row holds."""
    hkv, p, ps, d = cache.shape
    rows = new.astype(cache.dtype).reshape(-1, d)  # [T*Hkv, D] token-major
    idx = (slot_mapping[:, None]
           + jnp.arange(hkv, dtype=jnp.int32)[None] * (p * ps))
    # negative slots would wrap Python-style: out of bounds, so dropped
    idx = jnp.where(slot_mapping[:, None] < 0, hkv * p * ps, idx)
    flat = cache.reshape(hkv * p * ps, d).at[idx.reshape(-1)].set(
        rows, mode="drop")
    return flat.reshape(cache.shape)


#: rows of ``[D]`` a half (``Hkv*T``) up to which the XLA scatter writes
#: a step on the TPU too.  A trade of step time for set-up time, by the
#: sweep on a v5e (PERF.md, PR 31): a 64-row decode step's 16 writes take
#: 1.15 ms through the scatter and 0.95 through the kernel (0.2 ms of a
#: 50 ms step given up), and each executable spared the kernel's trace
#: and lowering starts 0.15-0.25 s sooner at every warm set-up, 2-3 s
#: over the 17 of 23 executables that are decode buckets (the kernel in
#: every executable had read ``setup_s`` 10-11% up at a bound of 10).
#: It counts rows, so the bucket at which the path turns differs with
#: ``Hkv``: T = 64 at four kv heads, 256 on a one-head shard.
_SCATTER_MAX_ROWS = 256
#: VMEM all the page-write kernel's buffers may take together (compiled
#: ahead of time for a v5e, 21 MB of them fit a kernel and 42 MB did not)
_WRITE_VMEM_BYTES = 8 * 2 ** 20
#: tokens a grid step of the page-write kernel takes at most (one page
#: buffer a token: a decode batch touches a page a row)
_WRITE_MAX_BLOCK = 128
#: tokens a step up to which the kernel's prefetched plans (the slots
#: and four ``[T]`` int32 tables) fit a v5e's 1 MB of SMEM: compiled
#: ahead of time at this length, 3 KB over at twice it (a served step is
#: bounded by ``max_num_batched_tokens``, 2048 in the cells)
_WRITE_MAX_TOKENS = 32768


def _write_block(cache, pools: int = 2) -> int:
    """Tokens a grid step of the page-write kernel takes at this pool's
    geometry (``pools`` of them a layer: K and V, or the one shared
    pool): the most, in sixteens up to ``_WRITE_MAX_BLOCK``, whose
    buffers fit ``_WRITE_VMEM_BYTES`` together; under 16, none do."""
    hkv, _, ps, d = cache.shape
    item = cache.dtype.itemsize
    # a token of a block: a page buffer of each pool, its row of the
    # float32 ``rows``, its row of the double-buffered ``new`` block
    per_token = pools * hkv * d * (ps * item + 4 + 2 * item)
    margins = pools * hkv * d * 2 * ps * 4  # of ``rows``
    fit = (_WRITE_VMEM_BYTES - margins) // per_token
    return min(fit // 16 * 16, _WRITE_MAX_BLOCK)


def _write_plan(slots, page_size: int, block: int):
    """What the page-write kernel does in each ``block``-token grid
    step, from the slot mapping alone (so one plan serves every layer of
    a step: XLA computes it once).  ``[T]`` int32 each, a block's part at
    ``[i*block, (i+1)*block)``: ``run_len`` (at a token that starts a run
    of consecutive slots inside one page, the run's length; else 0),
    ``buf`` (at a run's start, which page buffer holds its page: a page
    is brought in, and written back, once a block however its rows are
    ordered), ``held`` (the page in the block's r-th buffer); and
    ``n_pages`` ``[T / block]``, the block's distinct pages."""
    # lax primitives, not their jnp wrappers: every step executable
    # traces this once, and set-up is counted (PERF.md, PR 31)
    lax, i32 = jax.lax, jnp.int32
    s = lax.reshape(slots, (slots.shape[0] // block, block))
    shape, cube = s.shape, s.shape + (block,)

    def const(x, shape=shape):
        return lax.full(shape, x, i32)

    def along(x, axis):  # [blocks, block] -> [blocks, i, j], x over ``axis``
        return lax.broadcast_in_dim(x, cube, (0, axis))

    idx = lax.broadcasted_iota(i32, shape, 1)
    prev = lax.pad(s[:, :-1], i32(-2), ((0, 0, 0), (1, 0, 0)))
    valid = lax.ge(s, const(0))
    cont = lax.bitwise_and(
        lax.bitwise_and(valid, lax.eq(s, lax.add(prev, const(1)))),
        lax.ne(lax.rem(s, const(page_size)), const(0)))
    start = lax.bitwise_and(valid, lax.bitwise_not(cont))
    ends = lax.cummin(lax.select(cont, const(block), idx), axis=1,
                      reverse=True)
    ends = lax.pad(ends[:, 1:], i32(block), ((0, 0, 0), (0, 1, 0)))
    run_len = lax.select(start, lax.sub(ends, idx), const(0))
    # a page id where a run starts, elsewhere a value of the token's own
    page = lax.select(start, lax.div(s, const(page_size)),
                      lax.sub(const(-1), idx))
    same = lax.eq(along(page, 1), along(page, 2))  # [blocks, i, j]
    first = lax.argmax(lax.convert_element_type(same, i32), 1, i32)
    fetch = lax.bitwise_and(start, lax.eq(first, idx))
    fetch_i = lax.convert_element_type(fetch, i32)
    rank = lax.sub(lax.cumsum(fetch_i, axis=1), const(1))
    buf = lax.reduce_sum(
        lax.select(lax.bitwise_and(same, along(fetch, 1)), along(rank, 1),
                   const(0, cube)), (1,))
    # buffer r holds the page of the r-th fetching token
    held = lax.reduce_sum(
        lax.select(lax.bitwise_and(along(fetch, 2),
                                   lax.eq(along(rank, 2), along(idx, 1))),
                   along(page, 2), const(0, cube)), (2,))
    flat = (slots.shape[0],)
    return (lax.reshape(run_len, flat), lax.reshape(buf, flat),
            lax.reshape(held, flat), lax.reduce_sum(fetch_i, (1,)))


def _write_pages_kernel(
    # scalar prefetch (SMEM)
    slots_ref,    # [T]
    run_len_ref,  # [T]
    buf_ref,      # [T]
    held_ref,     # [T]
    n_pages_ref,  # [T / block]
    # inputs
    new_ref,  # [pools*Hkv, block, D] VMEM: the block's rows, pool by pool
    # then the ``pools`` pools (aliased to the outputs: read through
    # those), the outputs [Hkv, P, page, D] ANY/HBM (K and V, or the one
    # shared pool), and scratch:
    # pages [block, pools*Hkv, page, D]: a distinct page's part of each pool
    # rows [pools*Hkv, D / 128, page + block + page, 128] f32: the block's
    #   rows between two margins, so that a page-high window never leaves
    #   them, in columns one vreg wide (Mosaic loads a window that starts
    #   off the sublane tiling only from a buffer 128 lanes wide)
    # sems DMA [2]: pages in, pages out
    *refs,
    page_size: int,
    block: int,
    pools: int,
):
    # (lax primitives throughout, as in ``_write_plan``: set-up traces this)
    lax = jax.lax
    pools_hbm = refs[pools:2 * pools]
    pages, rows, sems = refs[2 * pools:]
    base = pl.program_id(0) * block
    n_pages = n_pages_ref[pl.program_id(0)]
    hkv = pages.shape[1] // pools

    def move_pages(out: bool):
        def start(r, carry):
            page, buffer = held_ref[base + r], pages.at[r]
            for i, hbm in enumerate(pools_hbm):
                pool, part = hbm.at[:, page], buffer.at[i * hkv:(i + 1) * hkv]
                pltpu.make_async_copy(
                    *((part, pool) if out else (pool, part)),
                    sems.at[int(out)]).start()
            return carry

        def wait(_, carry):
            # a DMA semaphore counts bytes: any whole page buffer sizes
            # the wait for one page's part of every pool
            pltpu.make_async_copy(
                pages.at[0], pages.at[0], sems.at[int(out)]).wait()
            return carry

        lax.fori_loop(0, n_pages, start, 0)
        lax.fori_loop(0, n_pages, wait, 0)

    @pl.when(n_pages > 0)
    def _block():
        move_pages(out=False)
        lane_cols = [pl.ds(c, 128) for c in range(0, pages.shape[-1], 128)]
        for c, lanes in enumerate(lane_cols):
            rows[:, c, page_size:page_size + block, :] = (
                lax.convert_element_type(new_ref[:, :, lanes], jnp.float32))
        tile = (pools * hkv, page_size, 128)
        row = lax.broadcasted_iota(jnp.int32, tile, 1)

        def merge(j, carry):
            n = run_len_ref[base + j]

            @pl.when(n > 0)
            def _():
                # page row r takes token j + (r - off): a page-high
                # window of the rows, masked to the run
                off = lax.rem(slots_ref[base + j], page_size)
                window = pl.ds(page_size + j - off, page_size)
                mine = lax.bitwise_and(
                    lax.ge(row, lax.broadcast(off, tile)),
                    lax.lt(row, lax.broadcast(off + n, tile)))
                b = buf_ref[base + j]
                for c, lanes in enumerate(lane_cols):
                    old = lax.convert_element_type(
                        pages[b, :, :, lanes], jnp.float32)
                    pages[b, :, :, lanes] = lax.convert_element_type(
                        lax.select(mine, rows[:, c, window, :], old),
                        pages.dtype)
            return carry

        lax.fori_loop(0, block, merge, 0)
        move_pages(out=True)


@jax.jit
def _write_pages(k_cache, v_cache, k_new, v_new, slot_mapping):
    """The Pallas page write: each block of tokens brings the pages it
    touches into VMEM (``[Hkv, page, D]`` of every pool of the layer: K
    and V, or the one shared pool where ``v_cache`` is None; once each),
    lays its rows into them and writes them back, the pools aliased from
    input to output.  Blocks run in order and a block's pages are back
    before the next block reads, so a page that two blocks touch keeps
    both blocks' rows and a slot written twice keeps the later row.
    Returns the pools it was given, as a tuple."""
    caches = (k_cache,) if v_cache is None else (k_cache, v_cache)
    news = (k_new,) if v_cache is None else (k_new, v_new)
    pools = len(caches)
    hkv, _, ps, d = caches[0].shape
    t = slot_mapping.shape[0]
    block = min(_write_block(caches[0], pools), -(-t // 16) * 16)
    pad = -t % block
    lax, dtype = jax.lax, caches[0].dtype
    slots = lax.pad(lax.convert_element_type(slot_mapping, jnp.int32),
                    jnp.int32(-1), ((0, pad, 0),))
    new = lax.transpose(lax.pad(  # [T, pools*Hkv, D] -> [.., T + pad, D]
        lax.concatenate([lax.convert_element_type(n, dtype) for n in news],
                        1),
        jnp.zeros((), dtype), ((0, pad, 0), (0, 0, 0), (0, 0, 0))),
        (1, 0, 2))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=((t + pad) // block,),
        in_specs=[pl.BlockSpec((pools * hkv, block, d),
                               lambda i, *_: (0, i, 0),
                               memory_space=pltpu.VMEM)]
        + [pool_spec] * pools,
        out_specs=[pool_spec] * pools,
        scratch_shapes=[
            pltpu.VMEM((block, pools * hkv, ps, d), dtype),
            pltpu.VMEM((pools * hkv, d // 128, block + 2 * ps, 128),
                       jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return tuple(pl.pallas_call(
        functools.partial(_write_pages_kernel, page_size=ps, block=block,
                          pools=pools),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(c.shape, dtype) for c in caches],
        # operands count the five prefetched plans: the pools start at 6
        input_output_aliases={6 + i: i for i in range(pools)},
        interpret=interpret_flag(),
    )(slots, *_write_plan(slots, ps, block), new, *caches))


def write_tiling_reason(cache, tokens: int = 1, pools: int = 2) -> str:
    """Why the page-write kernel cannot take a step of ``tokens`` rows
    into this pool ("" when it can): the paged kernels' geometry; a page
    that is whole packed tiles of the pool's dtype (16 rows of bfloat16,
    8 of float32), because a page is the unit it moves; a page so large
    (many kv heads, float32, a wide head) that sixteen tokens' buffers
    overrun its VMEM; a step whose plans overrun SMEM."""
    hkv, _, ps, d = cache.shape
    rows = 32 // cache.dtype.itemsize
    if row_tiling_reason(d, ps):
        return row_tiling_reason(d, ps)
    if ps % rows:
        return f"page_size {ps} % {rows} != 0 ({cache.dtype.name})"
    if _write_block(cache, pools) < 16:
        return (f"16 pages of [{pools * hkv}, {ps}, {d}] {cache.dtype.name} "
                f"> {_WRITE_VMEM_BYTES} bytes of VMEM")
    if tokens > _WRITE_MAX_TOKENS:
        return f"{tokens} tokens a step > {_WRITE_MAX_TOKENS} (SMEM plans)"
    return ""


@jax.jit
def write_kv_cache(
    k_cache,  # [Hkv, P, page, D] or quantized (data, scale) tuple
    v_cache,  # None: the shared (latent) layout, one pool a layer
    k_new: jax.Array,  # [T, Hkv, D]
    v_new: Optional[jax.Array],
    slot_mapping: jax.Array,  # [T] int32, flat slot = page*page_size + offset
):
    """Write new KV rows into the paged cache at the given flat slots, in
    the pool's own layout and, where the caller donates the pool, in
    place: no operation of the pool's size.

    Padded tokens use slot -1 and are dropped, matching the CUDA kernel's
    ignore-negative-slot contract.  On a platform that compiles Pallas
    the touched pages go through VMEM (``_write_pages``); elsewhere, for
    a geometry or a step the kernel cannot take (``write_tiling_reason``:
    counted, never silent) and for a step of at most ``_SCATTER_MAX_ROWS``
    rows a half (a decode batch: 0.2 ms of its step given up for 2-3 s of
    every set-up, see the constant), one XLA scatter of ``[D]`` rows does
    it (``_scatter_rows``).  The choice reads ``Hkv``, ``T``, ``D``, the
    page, the dtype and whether the layer holds two pools or the one
    shared pool, and nothing else: head widths 128, 256 and 640 and 1, 2
    and 4 kv heads are shapes of one write.  Until PR 31 the scatter
    ran on the pool viewed ``[Hkv, P*page, D]`` with the head as its
    window, and XLA copied the whole pool into the scatter's layout and
    back, 13 ms of every step for 2.1 GB of cache; at ``Hkv = 1`` it
    never did, which is what showed that the head axis of the window was
    the cause.
    The quantized (data, scale) layout dispatches on pytree structure —
    static under jit, so both layouts share one entry point.
    """
    if isinstance(k_cache, tuple):  # omnilint: disable=OL1 - pytree STRUCTURE branch (tuple vs array), static at trace time: jit specializes per layout by design
        return (_write_kv_quant(k_cache, paired_rows(k_new, k_cache),
                                slot_mapping),
                _write_kv_quant(v_cache, paired_rows(v_new, v_cache),
                                slot_mapping))
    # the shared (latent) layout is the same write over one pool
    shared = v_cache is None
    # heads of 64 lie two to a row: a reshape of the step's rows
    k_new = paired_rows(k_new, k_cache)
    if not shared:
        v_new = paired_rows(v_new, v_cache)
    tokens, hkv, _ = k_new.shape
    # a V pool narrower than the K pool (a key of 192 in 256 lanes beside
    # a value of 128) is a pool of its own to the page write, which lays
    # a layer's pools side by side only where their rows are one width
    apart = not shared and k_cache.shape[-1] != v_cache.shape[-1]
    pools = 1 if shared or apart else 2
    reason = write_tiling_reason(k_cache, tokens, pools) or (
        apart and write_tiling_reason(v_cache, tokens, pools)) or ""
    if tokens * hkv > _SCATTER_MAX_ROWS and auto_use_pallas(
            "write_kv_cache", reason):
        if apart:
            out = (_write_pages(k_cache, None, k_new, None, slot_mapping)
                   + _write_pages(v_cache, None, v_new, None, slot_mapping))
        else:
            out = _write_pages(k_cache, v_cache, k_new, v_new, slot_mapping)
    elif shared:
        out = (_scatter_rows(k_cache, k_new, slot_mapping),)
    else:
        out = (_scatter_rows(k_cache, k_new, slot_mapping),
               _scatter_rows(v_cache, v_new, slot_mapping))
    return (out[0], None) if shared else out


def paged_attention_ref(
    q: jax.Array,  # [B, H, D] (one decode token per sequence)
    k_cache,  # [Hkv, P, page, D] or quantized (data, scale) tuple
    v_cache,  # None: the value is the key row's first ``v_dim`` lanes
    block_tables: jax.Array,  # [B, max_pages] int32 page ids
    context_lens: jax.Array,  # [B] int32
    scale: Optional[float] = None,
    v_dim: Optional[int] = None,
    window: Optional[int] = None,
    keep: Optional[jax.Array] = None,  # [B, >= max_pages * page] bool
    sinks: Optional[jax.Array] = None,  # [H] float32
):
    """``window``: the row's token (at ``context_lens - 1``) admits the
    last ``window`` keys only; ``keep``: and only those it marks;
    ``sinks``: one logit a query head in the softmax's denominator, with
    no value (``sink_softmax``)."""
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
    if v_cache is None:
        vg = kg[..., :v_dim]
    else:
        vg = jnp.moveaxis(gather_pages(v_cache, block_tables), 0, 1).reshape(
            b, hkv, max_pages * page, -1
        )
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    s = jnp.einsum("bngd,bnld->bngl", qg, kg.astype(jnp.float32)) * scale
    pos = jnp.arange(max_pages * page)[None, None, None, :]
    mask = pos < context_lens[:, None, None, None]
    if window is not None:
        mask &= pos >= (context_lens - window)[:, None, None, None]
    if keep is not None:
        mask &= keep[:, None, None, :max_pages * page]
    s = jnp.where(mask, s, _NEG_INF)
    if sinks is None:
        p_ = jax.nn.softmax(s, axis=-1)
    else:
        p_ = sink_softmax(s, mask, sinks.reshape(1, hkv, group, 1))
    o = jnp.einsum("bngl,bnld->bngd", p_, vg.astype(jnp.float32))
    return o.reshape(b, h, vg.shape[-1]).astype(q.dtype)


def sink_softmax(s: jax.Array, mask: jax.Array, sink: jax.Array):
    """Softmax of the scores ``s`` over the last axis with one more logit
    a row, ``sink`` (broadcast against ``s`` with a last axis of 1), in
    the DENOMINATOR alone: ``exp(s - m) / (exp(sink - m) + sum exp(s -
    m))``, ``m = max(sink, max s)`` in float32.  The sink has no value:
    a row's weights sum to less than one, and a row that admits no key
    (``mask`` all false) weighs nothing."""
    sink = sink.astype(jnp.float32)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), sink)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    return p / (jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(sink - m))


#: the lanes of a row of a pool, and of a vector register
LANES = 128


def heads_a_row(head_dim: int, num_kv_heads: int = 2) -> int:
    """kv heads that share a row of the pool: 2 at a head of 64 (an even
    count of them), else 1.  A head of 64 padded to 128 lanes would double
    the cache's bytes, so two neighbouring kv heads lie side by side in
    one 128-lane row, ``[Hkv / 2, P, page, 128]``, which is a reshape of
    the step's ``[T, Hkv, 64]`` rows and leaves no lane unused.  The paged
    kernels then see a head of 128 whose group is the query heads of both
    (``pair_queries``)."""
    return 2 if 2 * head_dim == LANES and num_kv_heads % 2 == 0 else 1


def paired_rows(new: jax.Array, cache) -> jax.Array:
    """A step's ``[T, Hkv, D]`` rows as the pool ``cache`` lays them: two
    heads to a row where the pool's rows are twice a head's width."""
    width = cache_shape(cache)[-1]
    if new.shape[-1] == width:
        return new
    if width % new.shape[-1]:
        return pad_lanes(new, width)
    return new.reshape(new.shape[0], -1, width)


def pad_lanes(x: jax.Array, width: int) -> jax.Array:
    """``x`` with zeros after its last axis up to ``width``: a key of 192
    as it lies in a pool's row of 256 lanes (``key_row_width``), and the
    query that meets it (zeros meet zeros: the score is the head's own).
    Put together in blocks as wide as the two widths' common divisor on
    the axis before the last, then reshaped: a ``concatenate`` of a
    192-wide and a 64-wide piece on the last axis is the unaligned join
    that aborts the XLA TPU compiler (``ops/rope.py apply_rope_ref``)."""
    d = x.shape[-1]
    g = math.gcd(width, d)
    lead = x.shape[:-1]
    return jnp.concatenate(
        [x.reshape(*lead, d // g, g),
         jnp.zeros((*lead, (width - d) // g, g), x.dtype)],
        axis=-2).reshape(*lead, width)


def key_row_width(head_dim: int) -> int:
    """Lanes a K row takes in the pool: the head's width, but whole
    128-lane tiles for a head wider than one tile (192 -> 256, of which 64
    are zeros).  The paged kernels bring a page in as ``[page, width]``
    tiles, and one and a half lane tiles is no tiling; the padding costs
    its bytes in the pool and in every read (docs/kv_cache.md, "A key
    wider than its value"; ROADMAP, Speed)."""
    if head_dim > LANES and head_dim % LANES:
        return -(-head_dim // LANES) * LANES
    return head_dim


def _own_lanes(x: jax.Array, rows: int, pack: int, d: int):
    """``x [T, H, ...]`` viewed ``[T, rows, pack, group, ...]`` and the
    mask ``[1, 1, pack, 1, pack, 1]`` that is 1 where a head's position in
    its row (axis 2) meets a lane block of the row (axis 4)."""
    t, h = x.shape[:2]
    own = jnp.eye(pack, dtype=x.dtype)[None, None, :, None, :, None]
    return x.reshape(t, rows, pack, h // (rows * pack), -1, d), own


def pair_queries(q: jax.Array, k_cache, scale=None):
    """``q [T, H, D]`` and the softmax scale for a pool whose rows hold
    ``pack = width / D`` kv heads side by side (``heads_a_row``): every
    query head widened to the row, its numbers in its own kv head's lanes
    and zeros in the others', so that a score over the whole row is the
    score over its own head, at the head's own scale (``D ** -0.5`` where
    the caller gave none: the kernels would take the row's width).  The
    kernels then run a head of ``width`` whose group is ``pack`` times the
    model's (at 32 query and 8 kv heads of 64: 4 rows of 128, a group of
    8, the thinker's shape); the other head's lanes cost their products
    (twice the algorithm's), not their bytes.  Query head ``h`` attends kv
    head ``h // group``, lane block ``(h // group) % pack`` of row ``h //
    (group * pack)``.  ``(q, scale)`` as given where the pool's rows are a
    head's width."""
    rows, _, _, width = cache_shape(k_cache)
    t, h, d = q.shape
    if width == d:
        return q, scale
    own_scale = d ** -0.5 if scale is None else scale
    if width % d:
        # one head a row, narrower than the row's lane tiles
        # (``key_row_width``): zeros where the pool's rows hold zeros
        return pad_lanes(q, width), own_scale
    q6, own = _own_lanes(q, rows, width // d, d)
    return (q6 * own).reshape(t, h, width), own_scale


def own_half(o: jax.Array, k_cache, d: int) -> jax.Array:
    """Of the output ``[T, H, width]`` that the kernels give for paired
    queries, every head's own ``d`` lanes (the rest is the other head's
    values under this head's probabilities: dropped)."""
    rows, _, _, width = cache_shape(k_cache)
    t, h, _ = o.shape
    if width == d or width % d:  # one head a row: the output is its own
        return o
    o6, own = _own_lanes(o, rows, width // d, d)
    return jnp.sum(o6 * own, axis=4).reshape(t, h, d)


def row_tiling_reason(width: int, page_size: int) -> str:
    """Why Mosaic cannot tile the paged kernels' (page_size, width) page
    buffers for a pool of ``width``-lane rows ("" when it can): the lane
    dim must be a multiple of 128 and the sublane dim of 8, in either
    layout (bf16 and int8 page tiles both compiled and matched the
    reference at page 16 on a v5e, CHANGES.md PR 21).  The engine refuses
    such a geometry at construction on a platform that compiles the
    kernels; the ops count it (``_dispatch.auto_use_pallas``)."""
    if width % LANES:
        return (f"head_dim {width} % 128 != 0 (a head's width, a pair of "
                "64-wide heads, or a shared pool's row, takes whole "
                "128-lane tiles)")
    if page_size % 8:
        return f"page_size {page_size} % 8 != 0"
    return ""


def paged_tiling_reason(head_dim: int, page_size: int,
                        num_kv_heads: int = 2) -> str:
    """``row_tiling_reason`` of the pool that a model of this head width
    (or shared row width) is given: 128, 256 and 640 are served as they
    are, 64 two heads to a row (``heads_a_row``: an odd count of kv heads
    cannot pair); 96 is not."""
    return row_tiling_reason(
        head_dim * heads_a_row(head_dim, num_kv_heads), page_size)


def shared_tiling_reason(v_cache, v_dim) -> str:
    """Why the kernels cannot read the value out of a shared pool's key
    tile ("" when they can, or when the layout is not shared): the value
    must end on a lane tile."""
    if v_cache is None and v_dim % 128:
        return f"value width {v_dim} % 128 != 0"
    return ""
