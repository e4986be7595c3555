"""RMSNorm — Pallas replacement for vLLM's fused RMSNorm CUDA op
(SURVEY.md §2.10; used by every transformer block in the reference's models).

Supports the fused residual-add form (``x = x + residual`` then normalize,
returning both), matching the CUDA op's ``fused_add_rms_norm`` contract.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_omni_tpu.ops._dispatch import auto_use_pallas, interpret_flag


def rms_norm_ref(
    x: jax.Array,
    weight: jax.Array,
    eps: float = 1e-6,
    residual: Optional[jax.Array] = None,
):
    """Pure-JAX reference. x: [..., hidden]; weight: [hidden].

    Fused form accumulates the residual add in fp32 and normalizes the
    fp32 sum (the CUDA fused_add_rms_norm contract); the returned residual
    is the sum rounded to the activation dtype.
    """
    xf = x.astype(jnp.float32)
    residual_out = None
    if residual is not None:
        xf = xf + residual.astype(jnp.float32)
        residual_out = xf.astype(x.dtype)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    y = (y * weight.astype(jnp.float32)).astype(x.dtype)
    if residual is not None:
        return y, residual_out
    return y


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    xf = x_ref[:].astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * w_ref[0, :].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_fused_kernel(x_ref, r_ref, w_ref, o_ref, ro_ref, *, eps: float):
    xf = x_ref[:].astype(jnp.float32) + r_ref[:].astype(jnp.float32)
    ro_ref[:] = xf.astype(ro_ref.dtype)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * w_ref[0, :].astype(jnp.float32)).astype(o_ref.dtype)


def _block_rows(n_rows: int, hidden: int, dtype) -> int:
    # Keep the block within a conservative VMEM budget; hidden stays whole
    # (the reduction axis must be in one block).
    bytes_per = jnp.dtype(dtype).itemsize
    budget = 4 * 1024 * 1024
    rows = max(8, min(n_rows, budget // max(1, hidden * bytes_per * 3)))
    # round down to a multiple of 8 (f32 sublane)
    return max(8, (rows // 8) * 8)


@functools.partial(jax.jit, static_argnames=("eps", "use_pallas"))
def _rms_norm_2d(x, weight, residual, eps, use_pallas):
    n, h = x.shape
    if not use_pallas:
        return rms_norm_ref(x, weight, eps, residual)
    br = _block_rows(n, h, x.dtype)
    grid = (pl.cdiv(n, br),)
    x_spec = pl.BlockSpec((br, h), lambda i: (i, 0), memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)
    weight = weight.reshape(1, h)
    if residual is None:
        return pl.pallas_call(
            functools.partial(_rmsnorm_kernel, eps=eps),
            grid=grid,
            in_specs=[x_spec, w_spec],
            out_specs=x_spec,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret_flag(),
        )(x, weight)
    return pl.pallas_call(
        functools.partial(_rmsnorm_fused_kernel, eps=eps),
        grid=grid,
        in_specs=[x_spec, x_spec, w_spec],
        out_specs=(x_spec, x_spec),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(x.shape, x.dtype),
        ),
        interpret=interpret_flag(),
    )(x, residual, weight)


def rms_norm(
    x: jax.Array,
    weight: jax.Array,
    eps: float = 1e-6,
    residual: Optional[jax.Array] = None,
    use_pallas: Optional[bool] = None,
):
    """RMSNorm over the last axis. Any leading shape; optionally fused
    residual add (returns (normed, new_residual))."""
    if use_pallas is None:
        use_pallas = auto_use_pallas("rms_norm")
    lead = x.shape[:-1]
    h = x.shape[-1]
    x2 = x.reshape(-1, h)
    r2 = residual.reshape(-1, h) if residual is not None else None
    out = _rms_norm_2d(x2, weight, r2, eps, use_pallas)
    if residual is None:
        return out.reshape(*lead, h)
    y, r = out
    return y.reshape(*lead, h), r.reshape(*lead, h)
