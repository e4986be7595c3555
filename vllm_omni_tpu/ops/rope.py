"""Rotary position embedding — Pallas replacement for vLLM's fused
RoPE/MRoPE CUDA op (SURVEY.md §2.10).

Frequencies (cos/sin tables) are computed host/XLA-side — including the
*sectioned multimodal* MRoPE layout where the rotary feature dims are split
among temporal/height/width position streams (the position math the
reference implements in model_executor/layers/rotary_embedding/mrope.py:25);
the Pallas kernel then applies the rotation to Q and K in one fused pass.

Convention: GPT-NeoX half-split rotation (x1 = x[..., :d/2], x2 = x[..., d/2:]),
the layout used by the Qwen model families.  A model whose rotary embedding
pairs NEIGHBOURING dims (GPT-J's: ``position_embedding_type`` ``rope_gptj``)
is served by the same rotation over q and k columns permuted once in the
weight tree (``pairs_to_halves``): the scores are the published ones, and
no activation is shuffled across lanes at run time.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_omni_tpu.ops._dispatch import auto_use_pallas, interpret_flag


def compute_rope_freqs(
    positions: jax.Array,  # [T] int
    head_dim: int,
    theta: float = 10000.0,
    scaling: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """Return (cos, sin), each [T, head_dim//2], float32."""
    half = head_dim // 2
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    angles = angles * scaling
    return jnp.cos(angles), jnp.sin(angles)


def compute_mrope_freqs(
    positions: jax.Array,  # [3, T] int — (temporal, height, width) streams
    head_dim: int,
    mrope_section: Sequence[int],  # splits of head_dim//2 among the streams
    theta: float = 10000.0,
) -> tuple[jax.Array, jax.Array]:
    """Sectioned multimodal RoPE frequencies.

    Each of the 3 position streams owns a contiguous slice of the rotary
    feature dims (sum(mrope_section) == head_dim//2), matching the
    interleaved 3D-RoPE the reference computes for image/video/audio
    positions (mrope.py:25).
    """
    assert positions.ndim == 2 and positions.shape[0] == len(mrope_section)
    half = head_dim // 2
    assert sum(mrope_section) == half, (mrope_section, half)
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    # angles per stream: [3, T, half]
    angles = positions.astype(jnp.float32)[:, :, None] * inv_freq[None, None, :]
    # select the owning stream per feature dim
    section_id = jnp.repeat(
        jnp.arange(len(mrope_section)),
        jnp.asarray(mrope_section),
        total_repeat_length=half,
    )  # [half]
    angles = jnp.take_along_axis(
        angles, section_id[None, None, :].repeat(positions.shape[1], 1), axis=0
    )[0]  # [T, half]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope_ref(
    x: jax.Array,  # [T, H, D]
    cos: jax.Array,  # [T, D//2]
    sin: jax.Array,  # [T, D//2]
) -> jax.Array:
    d = x.shape[-1]
    x1 = x[..., : d // 2].astype(jnp.float32)
    x2 = x[..., d // 2 :].astype(jnp.float32)
    c = cos[:, None, :]
    s = sin[:, None, :]
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    # stack + reshape, not concatenate on the last axis: joining two
    # 64-wide halves there aborts the XLA TPU compiler (libtpu 0.0.34,
    # fusion_emitter "IsFusibleUnalignedDUS" check) at [256, 32, 128]
    return jnp.stack([o1, o2], axis=-2).reshape(x.shape).astype(x.dtype)


def apply_rope_pairs_ref(x: jax.Array, cos: jax.Array, sin: jax.Array
                         ) -> jax.Array:
    """The neighbouring-pair rotation as published (GPT-J):
    ``(y[2i], y[2i+1]) = (x[2i] c_i - x[2i+1] s_i, x[2i+1] c_i + x[2i]
    s_i)`` over ``[T, H, D]`` with the tables of ``compute_rope_freqs``.
    The oracle ``pairs_to_halves`` is held to; nothing serves it (a
    stride-2 walk of the lanes is a relayout of every q and k row)."""
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def pairs_to_halves(w: jax.Array, head_dim: int) -> jax.Array:
    """The columns of a q or k projection ``[in, heads * head_dim]`` whose
    checkpoint rotates neighbouring pairs, reordered a head at a time to
    evens then odds: ``apply_rope`` (half-split) over ``x W'`` is then
    ``apply_rope_pairs_ref`` over ``x W`` with each head's dims in that
    order, and a score ``q . k`` does not depend on the order of the dims
    both share.  Done once where a weight tree is made (a loader; the
    benchmark's seeded draw), never on activations."""
    lead = w.shape[:-1]
    return jnp.swapaxes(
        w.reshape(*lead, -1, head_dim // 2, 2), -1, -2).reshape(w.shape)


def apply_partial_rope(x: jax.Array, cos: jax.Array, sin: jax.Array
                       ) -> jax.Array:
    """Rotary embedding on the first ``2 * cos.shape[-1]`` dims of every
    head, the rest passed through (``partial_rotary_factor`` < 1).  The
    head is cut into blocks as wide as the rotary part and put together
    again by stack + reshape: a ``concatenate`` of a 64-wide and a
    192-wide piece on the last axis is the unaligned join that aborts the
    XLA TPU compiler (see ``apply_rope_ref``).  Elementwise work that XLA
    fuses; there is no kernel to fall back from."""
    d, r = x.shape[-1], 2 * cos.shape[-1]
    if d % r:
        raise ValueError(f"head_dim {d} is no multiple of rotary dim {r}")
    blocks = x.reshape(*x.shape[:-1], d // r, r)
    rot = apply_rope_ref(blocks[..., 0, :], cos, sin)
    return jnp.stack(
        [rot] + [blocks[..., i, :] for i in range(1, d // r)],
        axis=-2).reshape(x.shape)


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, dim: int):
    # x_ref: [bt, H, D] 3D block — no in-kernel reshape (Mosaic layout
    # inference rejects 2D->3D shape casts for small head dims).
    half = dim // 2
    v = x_ref[:].astype(jnp.float32)
    x1 = v[..., :half]
    x2 = v[..., half:]
    c = cos_ref[:].astype(jnp.float32)[:, None, :]
    s = sin_ref[:].astype(jnp.float32)[:, None, :]
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    o_ref[:] = jnp.concatenate([o1, o2], axis=-1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _apply_rope(x, cos, sin, use_pallas):
    t, h, d = x.shape
    if not use_pallas:
        return apply_rope_ref(x, cos, sin)
    # Size the token block from a VMEM budget: the kernel holds several
    # fp32 intermediates of the block shape, so keep one copy ~<=1MB.
    budget_rows = (1 << 20) // (h * d * 4)
    bt = max(8, min(t, budget_rows, 512))
    bt = max(8, (bt // 8) * 8)
    grid = (pl.cdiv(t, bt),)
    return pl.pallas_call(
        functools.partial(_rope_kernel, dim=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, h, d), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bt, d // 2), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bt, d // 2), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (bt, h, d), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((t, h, d), x.dtype),
        interpret=interpret_flag(),
    )(x, cos, sin)


def apply_rope(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """Apply rotary embedding to [T, H, D] activations."""
    if use_pallas is None:
        # Pallas pays off only for MXU-aligned head dims; XLA fuses the
        # rest (elementwise, no gather)
        d = x.shape[-1]
        use_pallas = auto_use_pallas(
            "apply_rope", f"head_dim {d} % 64 != 0" if d % 64 else "")
    return _apply_rope(x, cos, sin, use_pallas)
