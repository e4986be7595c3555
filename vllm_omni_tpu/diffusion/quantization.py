"""Weight-only quantization of param trees.

Reference: vllm_omni/diffusion/quantization/{base,fp8}.py —
``DiffusionQuantizationConfig`` applying FP8 W8A8 (Ada/Hopper) or
weight-only fallback to DiT linear layers, ~1.28x reported speedup
(docs/user_guide/diffusion_acceleration.md:19,46).

TPU paths: int8 weight-only (per-out-channel absmax scaling) and fp8
weight-only (float8_e4m3, per-out-channel scale to the e4m3 dynamic
range).  Either way weights live quantized in HBM (halved weight
bandwidth — the DiT denoise loop is bandwidth-bound at decode-scale
batches) and dequantize inline where the matmul consumes them
(models/common/nn.py ``linear``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_omni_tpu.logger import init_logger

logger = init_logger(__name__)


def quantize_linear_weight(w: jax.Array) -> dict:
    """[in, out] float -> {w_q int8 [in, out], w_scale f32 [out]}."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)  # [out]
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    w_q = jnp.clip(
        jnp.round(w.astype(jnp.float32) / scale[None, :]), -127, 127
    ).astype(jnp.int8)
    return {"w_q": w_q, "w_scale": scale}


_FP8_MAX = 448.0  # float8_e4m3 finite max


def quantize_linear_weight_int4(w: jax.Array) -> dict:
    """[in, out] float -> {w_q4 int8 [ceil(in/2), out], w_scale f32 [out]}.

    Two 4-bit values pack per byte along the IN dimension (row 2i in the
    low nibble, row 2i+1 in the high nibble); per-out-channel absmax
    scaling to [-7, 7].  Packed int8 rather than jnp.int4 storage: the
    same bytes on every backend and in checkpoints.  4x smaller than
    bf16 — the lever that fits the
    full 60-layer Qwen-Image DiT (41 GB bf16 -> 10.3 GB) resident in one
    16 GB chip's HBM."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=0)  # [out]
    scale = jnp.maximum(absmax / 7.0, 1e-12)
    q = jnp.clip(jnp.round(wf / scale[None, :]), -8, 7).astype(jnp.int8)
    if q.shape[0] % 2:
        q = jnp.pad(q, ((0, 1), (0, 0)))
    lo, hi = q[0::2], q[1::2]
    packed = jnp.bitwise_or(
        jnp.left_shift(hi, 4), jnp.bitwise_and(lo, jnp.int8(0x0F)))
    return {"w_q4": packed, "w_scale": scale}


def unpack_int4(packed: jax.Array, in_dim: int, dtype) -> jax.Array:
    """{[in//2, out] packed int8} -> [in, out] ``dtype`` values in
    [-8, 7] (the inverse of ``quantize_linear_weight_int4``'s packing,
    before the scale multiply).  Arithmetic shifts sign-extend both
    nibbles; the interleave restores the original row order."""
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)
    w = jnp.stack([lo, hi], axis=1).reshape(-1, packed.shape[1])
    return w[:in_dim].astype(dtype)


def quantize_linear_weight_fp8(w: jax.Array) -> dict:
    """[in, out] float -> {w_q float8_e4m3fn [in, out], w_scale f32 [out]}
    (reference: diffusion/quantization/fp8.py weight-only path)."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)  # [out]
    scale = jnp.maximum(absmax / _FP8_MAX, 1e-12)
    w_q = (w.astype(jnp.float32) / scale[None, :]).astype(
        jnp.float8_e4m3fn)
    return {"w_q": w_q, "w_scale": scale}


def _quantize_tree(tree, quantize_fn, min_size: int):
    """Shared walk: replace every linear-style leaf dict (2-D "w") with
    ``quantize_fn(w)``; "b" and 1-D norm weights pass through.
    ``min_size`` skips small matrices where dequant overhead outweighs
    the bandwidth win.

    Identity-memoized: bench trees alias repeated blocks to a few
    distinct host buffers (offload.host_tiled_init_aliased); quantizing
    each alias separately would materialize tens of GB of near-duplicate
    arrays and defeat the aliasing.  Aliased inputs stay aliased in the
    output.  Returns (new_tree, n_distinct_quantized)."""
    memo: dict[int, object] = {}
    n_quant = 0

    def walk(node):
        nonlocal n_quant
        if isinstance(node, dict):
            hit = memo.get(id(node))
            if hit is not None:
                return hit
            if "w" in node and getattr(node["w"], "ndim", 0) == 2 \
                    and node["w"].size >= min_size:
                n_quant += 1
                q = quantize_fn(node["w"])
                rest = {k: v for k, v in node.items() if k != "w"}
                out = {**rest, **q}
            else:
                out = {k: walk(v) for k, v in node.items()}
            memo[id(node)] = out
            return out
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node

    return walk(tree), n_quant


def quantize_params(tree, min_size: int = 0, mode: str = "int8"):
    """Quantize a DEVICE param tree in place of its float linears.
    ``mode``: "int8" | "fp8"."""
    quantize = {
        "int8": quantize_linear_weight,
        "fp8": quantize_linear_weight_fp8,
        "int4": quantize_linear_weight_int4,
    }[mode]
    out, n_quant = _quantize_tree(tree, quantize, min_size)
    logger.info("quantized %d linear weights to %s", n_quant, mode)
    return out


def quantize_linear_weight_host(w, mode: str = "int8") -> dict:
    """Host (numpy) twin of the device quantizers, for layerwise-streamed
    param trees that must stay in host memory: quantizing with jnp would
    round-trip every block through the device.  Same math, same rounding
    (IEEE f32 max/div + round-half-even), so streamed-quantized equals
    resident-quantized bit-for-bit."""
    import numpy as np

    wf = np.asarray(w, dtype=np.float32)
    absmax = np.max(np.abs(wf), axis=0)  # [out]
    if mode == "int8":
        scale = np.maximum(absmax / 127.0, 1e-12).astype(np.float32)
        w_q = np.clip(
            np.round(wf / scale[None, :]), -127, 127).astype(np.int8)
    elif mode == "int4":
        scale = np.maximum(absmax / 7.0, 1e-12).astype(np.float32)
        q = np.clip(np.round(wf / scale[None, :]), -8, 7).astype(np.int8)
        if q.shape[0] % 2:
            q = np.pad(q, ((0, 1), (0, 0)))
        lo, hi = q[0::2], q[1::2]
        packed = np.bitwise_or(
            np.left_shift(hi, 4),
            np.bitwise_and(lo, np.int8(0x0F))).astype(np.int8)
        return {"w_q4": packed, "w_scale": scale}
    elif mode == "fp8":
        import ml_dtypes

        scale = np.maximum(absmax / _FP8_MAX, 1e-12).astype(np.float32)
        w_q = (wf / scale[None, :]).astype(ml_dtypes.float8_e4m3fn)
    else:
        raise ValueError(f"unknown quantization mode {mode!r}")
    return {"w_q": w_q, "w_scale": scale}


def quantize_params_host(tree, min_size: int = 0, mode: str = "int8"):
    """``quantize_params`` for HOST trees (layerwise streaming).  int8
    halves the host->HBM bytes per streamed block — the streamed denoise
    walk is transfer-bound, so the step time drops near-proportionally."""
    out, n_quant = _quantize_tree(
        tree, lambda w: quantize_linear_weight_host(w, mode), min_size)
    logger.info("host-quantized %d distinct linear weights to %s",
                n_quant, mode)
    return out
