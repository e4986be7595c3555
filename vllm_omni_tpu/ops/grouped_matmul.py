"""The experts' grouped matmul: rows sorted by expert times each expert's
matrix, ``jax.lax.ragged_dot``'s contract (``rows [P, K]``, ``w [E, K, N]``,
``group_sizes [E]`` -> ``[P, N]``; float32 accumulation, one rounding to the
rows' dtype).

Two forms of one product, chosen by what a call can observe (``choice``):

- ``jax.lax.ragged_dot``: XLA's, which tiles 512 rows an expert whatever
  arrives.  It serves a prompt's chunk, whose rows do not fit VMEM; where
  an expert sees a few rows (a decode step, a small mixed step) the layer
  is bound by the experts' weights and XLA reads them at a quarter to two
  thirds of the chip's bandwidth (PERF.md, PR 48).
- ``_few_rows``: one Pallas kernel for rows that fit VMEM.  The rows and
  the result stay there; the weights stream through the grid (tiles of
  N x experts, an expert's ``[K, tn]`` block double-buffered by the
  pipeline), each touched expert's read once.  An expert with no rows maps
  to the block already resident, so nothing is fetched for it.  An expert's
  rows are walked in windows of ``tm`` rows that start on a sublane tile;
  the rows of a window outside the group are masked out of the store.
  Rows behind the last group (an expert share's absent pairs) are zeros.

The kernel's entry is ONE jitted function: the layers of a model have the
same expert shapes, so a step program traces it twice (gate/up, down) and
lowers two functions its layers call, not two kernels a layer (a warm
set-up is Python tracing and lowering: PERF.md, PR 31 and PR 48).
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_omni_tpu.ops._dispatch import auto_use_pallas, interpret_flag

#: the kernel serves a call that expects at least this many rows an
#: expert (``P / router width``).  Under it the two forms tie (a call of
#: either is a launch: scripts/moe_grouped_sweep.py), and every step program
#: that holds the kernel pays for tracing and lowering it at set-up
#: (PERF.md, PR 48)
MIN_ROWS_AN_EXPERT = 1
#: VMEM the kernel may ask for: the rows once, a tile of the result and a
#: block of weights twice (``_vmem``).  A call that needs more keeps
#: ``ragged_dot``.  The v5e has 128 MB; the bound stands well under that
#: because of a stop whose CAUSE IS NOT KNOWN: a step program whose two
#: kernels asked for 61 and 64 MB (Nemotron's 256-token step) never
#: returned, twice, where the same kernels alone ran and were right, and
#: where other programs' kernels at 52-62 MB returned every time (the
#: thinker's, dots3's), so the kernel's VMEM is not shown to be what
#: separates them (libtpu 0.0.34; PERF.md, PR 48; ROADMAP D21: not
#: bisected).  48 MB is under everything that was seen to stop and over
#: what the cells' decode steps ask for (45 MB at most, Nemotron's).
VMEM_BUDGET = 48 * 2 ** 20
#: the weights' block ``[K, tn]`` stays under this many bytes
W_BLOCK_BYTES = 8 * 2 ** 20
#: windows start on a tile of 16 sublanes (a bfloat16 tile; two of float32)
SUBLANES = 16

#: calls that took the kernel, counted on the thread that traces them: a
#: step program whose trace moved the count runs the kernel
#: (worker/model_runner.py ``_run_jit``, ``moe.grouped_kernel_steps_total``)
_traced = threading.local()


def kernel_calls() -> int:
    return getattr(_traced, "calls", 0)


def _vmem(rows: int, k: int, tn: int, itemsize: int) -> int:
    """The rows once, a tile of the result and a block of weights twice."""
    return itemsize * (rows * k + 2 * rows * tn + 2 * k * tn)


def tiles(p: int, width: int, k: int, n: int, itemsize: int
          ) -> tuple[int, int, int]:
    """(tm, tn, VMEM bytes) of a call: ``tm`` the window of rows, the
    power of two times 16 that holds twice the expected group and a tile's
    lead; ``tn`` the widest whole-lane divisor of N whose ``[K, tn]`` block
    fits ``W_BLOCK_BYTES``."""
    want = 2 * p / width + SUBLANES
    tm = next((t for t in (16, 32, 64) if t >= want), 128)
    rows = max(-(-p // SUBLANES) * SUBLANES, tm)
    tn = next((n // d for d in range(1, n // 128 + 1)
               if n % d == 0 and (n // d) % 128 == 0
               and k * (n // d) * itemsize <= W_BLOCK_BYTES), 128)
    return tm, tn, _vmem(rows, k, tn, itemsize)


def kernel_reason(k: int, n: int) -> str:
    """Why a few-rows call cannot take the kernel ('' if it can): the
    weights' blocks are whole 128-lane tiles."""
    if k % 128 or n % 128:
        return f"K {k} or N {n} is not whole 128-lane tiles"
    return ""


def choice(p: int, width: int, k: int, n: int, itemsize: int = 2) -> str:
    """Which form a call of ``p`` rows over a router ``width`` wide takes on
    a platform that compiles Pallas: ``"kernel"`` from
    ``MIN_ROWS_AN_EXPERT`` expected rows an expert up, where the rows fit
    the VMEM budget, else ``"ragged_dot"``.  Shapes alone: no flag, no
    model's name."""
    if p < MIN_ROWS_AN_EXPERT * width:
        return "ragged_dot"
    if tiles(p, width, k, n, itemsize)[2] > VMEM_BUDGET:
        return "ragged_dot"
    return "kernel"


def grouped_matmul(rows, w, group_sizes, width=None, use_pallas=None):
    """``ragged_dot(rows, w, group_sizes)``.  ``width`` is the router's
    width where it is not ``w.shape[0]`` (an expert share holds some of a
    wider router's experts, and expects ``P / width`` rows each)."""
    p, k = rows.shape
    e, _, n = w.shape
    width = e if width is None else width
    if use_pallas is None:
        use_pallas = (
            choice(p, width, k, n, rows.dtype.itemsize) == "kernel"
            and auto_use_pallas("grouped_matmul", kernel_reason(k, n)))
    if not use_pallas:
        return jax.lax.ragged_dot(rows, w, group_sizes)
    _traced.calls = kernel_calls() + 1
    tm, tn, _ = tiles(p, width, k, n, rows.dtype.itemsize)
    return _few_rows(rows, w, group_sizes, tm=tm, tn=tn)


def _few_rows_kernel(start_ref, size_ref, _block_ref, rows_ref, w_ref,
                     out_ref, *, tm: int):
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    start, size = start_ref[e], size_ref[e]
    end = start + size
    first = start & -SUBLANES  # back to the sublane tile the group starts in
    last = rows_ref.shape[0] - tm
    # windows of tm rows (a power of two) from ``first`` to the group's end;
    # none for an expert without rows, whose block is whoever's was resident
    windows = jnp.where(
        size > 0, (end - first + tm - 1) >> (tm.bit_length() - 1), 0)

    def window(i, carry):
        # the last window is drawn back inside the rows: what it covers
        # twice is computed twice, to the same values
        r0 = pl.multiple_of(jnp.minimum(first + i * tm, last), SUBLANES)
        at = pl.ds(r0, tm)
        acc = jnp.dot(rows_ref[at, :], w_ref[...],
                      preferred_element_type=jnp.float32)
        row = r0 + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (row >= start) & (row < end)
        out_ref[at, :] = jnp.where(
            mine, acc, out_ref[at, :].astype(jnp.float32)
        ).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, windows, window, None)


@functools.partial(jax.jit, static_argnames=("tm", "tn"))
def _few_rows(rows, w, group_sizes, *, tm: int, tn: int):
    """The kernel's entry: traced once a shape, whatever calls it."""
    p, k = rows.shape
    e, _, n = w.shape
    padded = max(-(-p // SUBLANES) * SUBLANES, tm)
    if padded != p:  # a handful of rows: the smallest buckets
        rows = jnp.pad(rows, ((0, padded - p), (0, 0)))
    sizes = group_sizes.astype(jnp.int32)
    starts = jax.lax.cumsum(sizes) - sizes
    # an expert without rows reads the block of the last one that had some
    # (a leading run of them the first expert's): nothing is fetched for it
    block = jax.lax.cummax(jax.lax.select(
        sizes > 0, jax.lax.iota(jnp.int32, e), jnp.zeros_like(sizes)))
    need = _vmem(padded, k, tn, rows.dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(_few_rows_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, e),
            in_specs=[
                pl.BlockSpec((padded, k), lambda j, i, *_: (0, 0),
                             pipeline_mode=pl.Buffered(1)),
                pl.BlockSpec((None, k, tn),
                             lambda j, i, start, size, block:
                             (block[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((padded, tn), lambda j, i, *_: (0, j))),
        out_shape=jax.ShapeDtypeStruct((padded, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=need + 8 * 2 ** 20),
        interpret=interpret_flag(),
        name=f"grouped_matmul_{e}x{k}x{n}_rows",
    )(starts, sizes, block, rows, w)
    return out[:p] if padded != p else out
