"""Qwen-Image MMDiT transformer — TPU-native (functional JAX) redesign.

Behavioral parity with the reference's ``QwenImageTransformer2DModel``
(vllm_omni/diffusion/models/qwen_image/qwen_image_transformer.py:818):
double-stream (text+image) blocks with AdaLayerNorm modulation from the
timestep embedding, joint attention with per-stream QKV projections +
per-head QK RMSNorm, 3-axis (frame/row/col) rotary embeddings on the image
stream, gated residuals, and an AdaLayerNormContinuous output head.

Differences by design (TPU-first):
- torch hooks / _sp_plan are replaced by shard_map sequence parallelism at
  the pipeline level (text stream replicated, image stream sharded — the
  joint text KV rides the ``joint_k/joint_v`` path of
  vllm_omni_tpu.parallel.context.usp_attention).
- attention is the Pallas flash kernel; modulation/MLP fuse under XLA.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common import nn
from vllm_omni_tpu.ops import flash_attention, rms_norm


@dataclass(frozen=True)
class QwenImageDiTConfig:
    patch_size: int = 2
    in_channels: int = 64  # 16 VAE latent channels x 2x2 packing
    out_channels: int = 16
    num_layers: int = 60
    num_heads: int = 24
    head_dim: int = 128
    joint_dim: int = 3584  # text-encoder feature dim
    axes_dims: tuple[int, int, int] = (16, 56, 56)  # frame/row/col rope dims
    theta: float = 10000.0
    mlp_ratio: float = 4.0
    # rotary pairing convention: False = half-split (TPU-native default),
    # True = interleaved pairs — the convention real checkpoints were
    # trained with (reference QwenEmbedRope builds torch.polar complex
    # freqs consumed by RotaryEmbedding(is_neox_style=False),
    # qwen_image_transformer.py:553,598-601); from_pretrained sets this
    rope_interleaved: bool = False

    @property
    def inner_dim(self) -> int:
        return self.num_heads * self.head_dim

    @staticmethod
    def tiny() -> "QwenImageDiTConfig":
        return QwenImageDiTConfig(
            in_channels=16,
            out_channels=4,
            num_layers=2,
            num_heads=4,
            head_dim=32,
            joint_dim=64,
            axes_dims=(8, 12, 12),
        )


def init_top(keys, cfg: QwenImageDiTConfig, dtype=jnp.float32):
    """Non-block params from the first 6 of ``init_params``' key array."""
    inner = cfg.inner_dim
    return {
        "img_in": nn.linear_init(keys[0], cfg.in_channels, inner, dtype=dtype),
        "txt_norm": nn.rmsnorm_init(cfg.joint_dim, dtype),
        "txt_in": nn.linear_init(keys[1], cfg.joint_dim, inner, dtype=dtype),
        "time_in1": nn.linear_init(keys[2], 256, inner, dtype=dtype),
        "time_in2": nn.linear_init(keys[3], inner, inner, dtype=dtype),
        "norm_out_mod": nn.linear_init(keys[4], inner, 2 * inner, dtype=dtype),
        "proj_out": nn.linear_init(
            keys[5], inner, cfg.patch_size**2 * cfg.out_channels, dtype=dtype
        ),
    }


def init_block(key, cfg: QwenImageDiTConfig, dtype=jnp.float32):
    """One MMDiT block from its per-block key (``init_params`` passes
    keys[i + 8]; blockwise quantized init reuses the SAME schedule so a
    quantized build is a quantization of the identical random model)."""
    inner = cfg.inner_dim
    mlp = int(inner * cfg.mlp_ratio)
    k = jax.random.split(key, 12)
    return {
        "img_mod": nn.linear_init(k[0], inner, 6 * inner, dtype=dtype),
        "txt_mod": nn.linear_init(k[1], inner, 6 * inner, dtype=dtype),
        "to_q": nn.linear_init(k[2], inner, inner, dtype=dtype),
        "to_k": nn.linear_init(k[3], inner, inner, dtype=dtype),
        "to_v": nn.linear_init(k[4], inner, inner, dtype=dtype),
        "add_q": nn.linear_init(k[5], inner, inner, dtype=dtype),
        "add_k": nn.linear_init(k[6], inner, inner, dtype=dtype),
        "add_v": nn.linear_init(k[7], inner, inner, dtype=dtype),
        "norm_q": nn.rmsnorm_init(cfg.head_dim, dtype),
        "norm_k": nn.rmsnorm_init(cfg.head_dim, dtype),
        "norm_added_q": nn.rmsnorm_init(cfg.head_dim, dtype),
        "norm_added_k": nn.rmsnorm_init(cfg.head_dim, dtype),
        "to_out": nn.linear_init(k[8], inner, inner, dtype=dtype),
        "to_add_out": nn.linear_init(k[9], inner, inner, dtype=dtype),
        "img_mlp1": nn.linear_init(k[10], inner, mlp, dtype=dtype),
        "img_mlp2": nn.linear_init(k[11], mlp, inner, dtype=dtype),
        "txt_mlp1": nn.linear_init(
            jax.random.fold_in(k[10], 1), inner, mlp, dtype=dtype
        ),
        "txt_mlp2": nn.linear_init(
            jax.random.fold_in(k[11], 1), mlp, inner, dtype=dtype
        ),
    }


def init_params(key, cfg: QwenImageDiTConfig, dtype=jnp.float32):
    keys = jax.random.split(key, cfg.num_layers + 8)
    p = init_top(keys, cfg, dtype=dtype)
    p["blocks"] = [
        init_block(keys[i + 8], cfg, dtype=dtype)
        for i in range(cfg.num_layers)
    ]
    return p


def rope_freqs(
    cfg: QwenImageDiTConfig,
    grid_h: int,
    grid_w: int,
    txt_len: int,
    frames: int = 1,
    cond_grids: tuple[tuple[int, int], ...] = (),
):
    """3-axis rotary frequencies for the image grid + continued positions
    for the text stream (reference QwenEmbedRope, scale_rope=True: row/col
    coordinates are centered).

    ``cond_grids``: (gh, gw) per VAE-encoded condition image appended to
    the token sequence (image edit).  Condition tokens share the centered
    row/col layout; their frame coordinate is the entry index, except the
    LAST condition which sits at frame -1 (reference
    _compute_condition_freqs, qwen_image_transformer.py:279-297)."""
    half_dims = [d // 2 for d in cfg.axes_dims]  # complex pairs per axis

    def axis_freqs(pos, half):
        inv = 1.0 / (
            cfg.theta ** (jnp.arange(half, dtype=jnp.float32) / half)
        )
        return pos.astype(jnp.float32)[:, None] * inv[None, :]

    def grid_angles(gh, gw, frame_coord, n_frames=1):
        f = jnp.full((n_frames,), frame_coord).repeat(gh * gw) if \
            n_frames == 1 else jnp.arange(n_frames).repeat(gh * gw)
        # centered rows/cols: -(g - g//2) .. g//2 - 1 (reference
        # _compute_video_freqs scale_rope concat of neg+pos positions —
        # for odd extents the extra row sits on the negative side)
        r = jnp.tile(jnp.arange(gh).repeat(gw), n_frames) - (gh - gh // 2)
        c = jnp.tile(jnp.arange(gw), n_frames * gh) - (gw - gw // 2)
        return jnp.concatenate(
            [
                axis_freqs(f, half_dims[0]),
                axis_freqs(r, half_dims[1]),
                axis_freqs(c, half_dims[2]),
            ],
            axis=-1,
        )  # [S, head_dim//2]

    parts = [grid_angles(grid_h, grid_w, 0, n_frames=frames)]
    for j, (ch, cw) in enumerate(cond_grids):
        frame_coord = -1 if j == len(cond_grids) - 1 else j + 1
        parts.append(grid_angles(ch, cw, frame_coord))
    img_angles = jnp.concatenate(parts, axis=0)

    # Text positions continue at the image extent on every axis
    # (reference: txt_freqs = pos_freqs[max_vid_index : max_vid_index +
    # max_len] — the first text token sits AT max_vid_index).
    extent = max(
        [grid_h // 2, grid_w // 2, len(cond_grids)]
        + [max(ch // 2, cw // 2) for ch, cw in cond_grids]
    )
    tpos = jnp.arange(txt_len) + extent
    txt_angles = jnp.concatenate(
        [axis_freqs(tpos, h) for h in half_dims], axis=-1
    )
    return (
        (jnp.cos(img_angles), jnp.sin(img_angles)),
        (jnp.cos(txt_angles), jnp.sin(txt_angles)),
    )


def _rope_apply(x, cos, sin, interleaved: bool = False):
    """x: [B, S, H, D]; cos/sin: [S, D//2].

    ``interleaved``: rotate (x0,x1),(x2,x3),... pairs — the trained
    checkpoint convention; default pairs (x_j, x_{j+D/2})."""
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    if interleaved:
        x1 = x[..., 0::2].astype(jnp.float32)
        x2 = x[..., 1::2].astype(jnp.float32)
        out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    d = x.shape[-1]
    x1 = x[..., : d // 2].astype(jnp.float32)
    x2 = x[..., d // 2 :].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


def _modulate(x, mod3):
    """mod3: [B, 3*dim] -> (modulated layernorm-ed x, gate)."""
    shift, scale, gate = jnp.split(mod3, 3, axis=-1)
    xn = nn.layernorm({}, x)
    return xn * (1.0 + scale[:, None, :]) + shift[:, None, :], gate[:, None, :]


def _heads(x, h):
    b, s, _ = x.shape
    return x.reshape(b, s, h, -1)


def block_forward(
    blk,
    cfg: QwenImageDiTConfig,
    img: jax.Array,  # [B, S_img, inner]
    txt: jax.Array,  # [B, S_txt, inner]
    temb_act: jax.Array,  # silu(temb) [B, inner]
    img_freqs,
    txt_freqs,
    attn_fn=None,
    kv_mask: Optional[jax.Array] = None,  # [B, S_txt + S_img]
):
    h = cfg.num_heads
    img_mod = nn.linear(blk["img_mod"], temb_act)
    txt_mod = nn.linear(blk["txt_mod"], temb_act)
    img_mod1, img_mod2 = jnp.split(img_mod, 2, axis=-1)
    txt_mod1, txt_mod2 = jnp.split(txt_mod, 2, axis=-1)

    img_n, img_gate1 = _modulate(img, img_mod1)
    txt_n, txt_gate1 = _modulate(txt, txt_mod1)

    qi = rms_norm(_heads(nn.linear(blk["to_q"], img_n), h), blk["norm_q"]["w"])
    ki = rms_norm(_heads(nn.linear(blk["to_k"], img_n), h), blk["norm_k"]["w"])
    vi = _heads(nn.linear(blk["to_v"], img_n), h)
    qt = rms_norm(
        _heads(nn.linear(blk["add_q"], txt_n), h), blk["norm_added_q"]["w"]
    )
    kt = rms_norm(
        _heads(nn.linear(blk["add_k"], txt_n), h), blk["norm_added_k"]["w"]
    )
    vt = _heads(nn.linear(blk["add_v"], txt_n), h)

    il = cfg.rope_interleaved
    qi = _rope_apply(qi, *img_freqs, interleaved=il)
    ki = _rope_apply(ki, *img_freqs, interleaved=il)
    qt = _rope_apply(qt, *txt_freqs, interleaved=il)
    kt = _rope_apply(kt, *txt_freqs, interleaved=il)

    if attn_fn is None:
        # Joint attention, text first (reference layout,
        # qwen_image_transformer.py:654-656).
        q = jnp.concatenate([qt, qi], axis=1)
        k = jnp.concatenate([kt, ki], axis=1)
        v = jnp.concatenate([vt, vi], axis=1)
        o = flash_attention(q, k, v, causal=False, kv_mask=kv_mask)
        s_txt = txt.shape[1]
        txt_o = o[:, :s_txt].reshape(*txt.shape[:2], -1)
        img_o = o[:, s_txt:].reshape(*img.shape[:2], -1)
    else:
        # Sequence-parallel path: image stream sharded, text KV joint.
        # The text part of the mask rides along so padded text tokens are
        # excluded on the distributed path too.
        txt_kv_mask = None if kv_mask is None else kv_mask[:, : txt.shape[1]]
        img_o, txt_o = attn_fn(qi, ki, vi, qt, kt, vt, txt_kv_mask)

    img = img + img_gate1 * nn.linear(blk["to_out"], img_o)
    txt = txt + txt_gate1 * nn.linear(blk["to_add_out"], txt_o)

    img_n2, img_gate2 = _modulate(img, img_mod2)
    img = img + img_gate2 * nn.linear(
        blk["img_mlp2"],
        jax.nn.gelu(nn.linear(blk["img_mlp1"], img_n2), approximate=True),
    )
    txt_n2, txt_gate2 = _modulate(txt, txt_mod2)
    txt = txt + txt_gate2 * nn.linear(
        blk["txt_mlp2"],
        jax.nn.gelu(nn.linear(blk["txt_mlp1"], txt_n2), approximate=True),
    )
    return img, txt


def forward(
    params,
    cfg: QwenImageDiTConfig,
    img_tokens: jax.Array,  # [B, S_img, in_channels] packed latents
    txt_states: jax.Array,  # [B, S_txt, joint_dim]
    timesteps: jax.Array,  # [B] in [0, 1000)
    grid_hw: tuple[int, int],
    attn_fn=None,
    txt_mask: Optional[jax.Array] = None,  # [B, S_txt] 1=real, 0=pad
    cond_grids: tuple[tuple[int, int], ...] = (),
    frames: int = 1,
) -> jax.Array:
    """Returns velocity prediction [B, S_img, patch^2 * out_channels].

    ``cond_grids``: grids of VAE-encoded condition images appended to
    ``img_tokens`` after the generated grid (image edit) — the caller
    slices the velocity back to the generated tokens."""
    img, txt, temb_act, img_freqs, txt_freqs, kv_mask = forward_prefix(
        params, cfg, img_tokens, txt_states, timesteps, grid_hw,
        txt_mask=txt_mask, cond_grids=cond_grids, frames=frames,
    )
    img, txt = walk_blocks(
        params.get("blocks_stacked", params.get("blocks")), cfg, img,
        txt, temb_act, img_freqs, txt_freqs, attn_fn, kv_mask,
    )
    return forward_suffix(params, img, temb_act)


def walk_blocks(blocks, cfg: QwenImageDiTConfig, img, txt, temb_act,
                img_freqs, txt_freqs, attn_fn=None, kv_mask=None):
    """Run the block stack: a Python loop over a LIST of per-block
    pytrees (unrolled — lets XLA fuse across adjacent small blocks), or
    lax.scan over a DICT stacked on a leading layer axis.

    The scan form keeps the compiled program at ONE block's HLO instead
    of L copies — at the real 60-layer geometry the unrolled program
    takes L times as long to compile — and pins
    quantized-weight dequant inside the loop body where LICM can't hoist
    L dequantized bf16 blocks out of the step loop (= 41 GB).  Same
    math, identical per-block MXU shapes."""
    if isinstance(blocks, dict):
        def body(carry, blk):
            c_img, c_txt = carry
            c_img, c_txt = block_forward(
                blk, cfg, c_img, c_txt, temb_act, img_freqs, txt_freqs,
                attn_fn, kv_mask,
            )
            return (c_img, c_txt), None

        (img, txt), _ = jax.lax.scan(body, (img, txt), blocks)
        return img, txt
    for blk in blocks:
        img, txt = block_forward(
            blk, cfg, img, txt, temb_act, img_freqs, txt_freqs,
            attn_fn, kv_mask,
        )
    return img, txt


def forward_prefix(
    params,
    cfg: QwenImageDiTConfig,
    img_tokens: jax.Array,
    txt_states: jax.Array,
    timesteps: jax.Array,
    grid_hw: tuple[int, int],
    txt_mask: Optional[jax.Array] = None,
    cond_grids: tuple[tuple[int, int], ...] = (),
    frames: int = 1,
):
    """Everything before the block stack: embeds, time conditioning,
    rope tables, joint KV mask.  Split out so block-streaming
    (diffusion/offload.py) and pipeline parallelism (parallel/pp.py) can
    schedule the stack themselves."""
    img = nn.linear(params["img_in"], img_tokens)
    txt = rms_norm(txt_states, params["txt_norm"]["w"])
    txt = nn.linear(params["txt_in"], txt)

    temb = nn.timestep_embedding(timesteps, 256)
    temb = nn.linear(
        params["time_in2"],
        jax.nn.silu(nn.linear(params["time_in1"], temb.astype(img.dtype))),
    )
    temb_act = jax.nn.silu(temb)

    img_freqs, txt_freqs = rope_freqs(
        cfg, grid_hw[0], grid_hw[1], txt_states.shape[1],
        cond_grids=cond_grids, frames=frames,
    )

    # Joint-attention KV mask: padded text tokens must not receive
    # attention mass (reference encoder_hidden_states_mask semantics,
    # qwen_image_transformer.py:746).
    kv_mask = None
    if txt_mask is not None:
        b, s_img = img.shape[:2]
        kv_mask = jnp.concatenate(
            [txt_mask.astype(jnp.int32), jnp.ones((b, s_img), jnp.int32)],
            axis=1,
        )
    return img, txt, temb_act, img_freqs, txt_freqs, kv_mask


def forward_suffix(params, img: jax.Array, temb_act: jax.Array):
    """AdaLayerNormContinuous output head."""
    mod = nn.linear(params["norm_out_mod"], temb_act)
    scale, shift = jnp.split(mod, 2, axis=-1)
    img = nn.layernorm({}, img) * (1.0 + scale[:, None, :]) + shift[:, None, :]
    return nn.linear(params["proj_out"], img)
