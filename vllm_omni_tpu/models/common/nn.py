"""Minimal functional NN primitives over parameter pytrees.

The framework keeps model parameters as nested dicts of jax.Arrays (pytrees)
and model code as pure functions — the idiomatic layout for pjit/shard_map
sharding (params are annotated with NamedSharding at load time, activations
with with_sharding_constraint inside the jitted step).  This replaces the
reference's torch ``nn.Module`` graph + forward hooks with compiler-visible
functions.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------- init utils
def linear_init(key, in_dim: int, out_dim: int, bias: bool = True, dtype=jnp.float32):
    kw, kb = jax.random.split(key)
    scale = 1.0 / math.sqrt(in_dim)
    p = {
        "w": jax.random.uniform(
            kw, (in_dim, out_dim), dtype, minval=-scale, maxval=scale
        )
    }
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def linear(p, x):
    if "w_q" in p:
        # int8 weight-only quantization: weights live in HBM as int8 +
        # per-out-channel scales; the dequant multiply fuses into the matmul
        # (XLA), halving weight bandwidth (reference FP8 path:
        # diffusion/quantization/fp8.py — TPU gets int8 first)
        w = p["w_q"].astype(x.dtype) * p["w_scale"].astype(x.dtype)
        y = x @ w
    elif "w_q4" in p:
        # int4 weight-only: two nibbles per stored byte, unpacked inline
        # (diffusion/quantization.py) — quarter weight bandwidth, and the
        # full 60-layer Qwen-Image DiT fits one chip's HBM resident
        from vllm_omni_tpu.diffusion.quantization import unpack_int4

        w = unpack_int4(p["w_q4"], x.shape[-1], x.dtype) \
            * p["w_scale"].astype(x.dtype)
        y = x @ w
    else:
        y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def out_proj(o, w, residual_in_fp32: bool):
    """A mixer's output projection, in the residual stream's precision."""
    if residual_in_fp32:
        return jnp.dot(o, w, preferred_element_type=jnp.float32)
    return o @ w


def embedding_init(key, vocab: int, dim: int, dtype=jnp.float32):
    return {"w": jax.random.normal(key, (vocab, dim), dtype) * 0.02}


def embedding(p, ids):
    return p["w"][ids]


def rmsnorm_init(dim: int, dtype=jnp.float32):
    return {"w": jnp.ones((dim,), dtype)}


def layernorm_init(dim: int, affine: bool = True, dtype=jnp.float32):
    if not affine:
        return {}
    return {"w": jnp.ones((dim,), dtype), "b": jnp.zeros((dim,), dtype)}


def layernorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if "w" in p:
        y = y * p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)
    return y.astype(x.dtype)


def conv2d_init(
    key, in_ch: int, out_ch: int, kernel: int, bias: bool = True, dtype=jnp.float32
):
    fan_in = in_ch * kernel * kernel
    scale = 1.0 / math.sqrt(fan_in)
    p = {
        "w": jax.random.uniform(
            key, (kernel, kernel, in_ch, out_ch), dtype, minval=-scale, maxval=scale
        )
    }
    if bias:
        p["b"] = jnp.zeros((out_ch,), dtype)
    return p


def conv2d(p, x, stride: int = 1, padding: str | Sequence = "SAME"):
    """x: [B, H, W, C] (NHWC — the TPU-native conv layout)."""
    y = jax.lax.conv_general_dilated(
        x,
        p["w"].astype(x.dtype),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def conv1d_init(
    key, in_ch: int, out_ch: int, kernel: int, bias: bool = True, dtype=jnp.float32
):
    fan_in = in_ch * kernel
    scale = 1.0 / math.sqrt(fan_in)
    p = {
        "w": jax.random.uniform(
            key, (kernel, in_ch, out_ch), dtype, minval=-scale, maxval=scale
        )
    }
    if bias:
        p["b"] = jnp.zeros((out_ch,), dtype)
    return p


def conv1d(p, x, stride: int = 1, padding: str | Sequence = "SAME",
           dilation: int = 1):
    """x: [B, T, C] (NWC — TPU-native 1-D conv layout)."""
    y = jax.lax.conv_general_dilated(
        x,
        p["w"].astype(x.dtype),
        window_strides=(stride,),
        padding=padding,
        rhs_dilation=(dilation,),
        dimension_numbers=("NWC", "WIO", "NWC"),
    )
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def conv1d_transpose(p, x, stride: int, padding: str = "SAME"):
    """Transposed 1-D conv (upsampling by ``stride``); x: [B, T, C]."""
    y = jax.lax.conv_transpose(
        x,
        p["w"].astype(x.dtype),
        strides=(stride,),
        padding=padding,
        dimension_numbers=("NWC", "WIO", "NWC"),
    )
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def groupnorm_init(channels: int, dtype=jnp.float32):
    return {"w": jnp.ones((channels,), dtype), "b": jnp.zeros((channels,), dtype)}


def groupnorm(p, x, groups: int = 32, eps: float = 1e-6):
    """x: [B, H, W, C]."""
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xf = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
    mu = jnp.mean(xf, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xf, axis=(1, 2, 4), keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(b, h, w, c)
    y = y * p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)
    return y.astype(x.dtype)


def sinusoid_positions(length: int, channels: int,
                       max_timescale: float = 10000.0):
    """Whisper SinusoidsPositionEmbedding table [length, channels]
    (shared by the Qwen3 AuT and Qwen2.5-Omni audio towers)."""
    import math

    import numpy as np

    log_inc = math.log(max_timescale) / (channels // 2 - 1)
    inv = np.exp(-log_inc * np.arange(channels // 2, dtype=np.float32))
    ang = np.arange(length, dtype=np.float32)[:, None] * inv[None, :]
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=1)


def timestep_embedding(t: jax.Array, dim: int, max_period: float = 10000.0):
    """Sinusoidal timestep embedding [B] -> [B, dim] (flip_sin_to_cos=True,
    matching diffusers' Timesteps used by the reference pipelines)."""
    half = dim // 2
    freqs = jnp.exp(
        -math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half
    )
    args = t.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def bias_attention(q, k, v, bias):
    """GQA attention with an additive bias mask, fp32 softmax.

    q [B, Sq, H, D] x k/v [B, Sk, Hkv, D], bias [B, 1, Sq, Sk] ->
    [B, Sq, H, D].  KV heads repeat up to the query head count
    (grouped-query attention); scores and softmax run in fp32 with the
    values' dtype restored on the way out.  Shared by the causal-MM
    generator families (Bagel, HunyuanImage-3) whose denoise attends a
    masked prefix context."""
    hq, hkv = q.shape[2], k.shape[2]
    if hq != hkv:
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(q.shape[-1])
    a = jax.nn.softmax(s + bias.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", a.astype(v.dtype), v)
