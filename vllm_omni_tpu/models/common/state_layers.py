"""What is particular to a kind of layer that carries a per-sequence state.

A Gated DeltaNet layer, a gated short convolution and a Mamba-2 layer differ
from an attention layer in the same way: a sequence holds a slot of a few
arrays where an attention layer holds pages, and the layer's rule reads the
slot at a chunk's start and writes it at the chunk's end.  Everything the
shared decoder (``transformer.py``) needs to know of such a kind is ONE
record of ``KINDS``, keyed by the published ``layer_types`` name: the
weights, the shapes of the slot, the mixer, and the ragged and the one-token
form of the rule.  ``transformer.py`` names no kind; a further kind is one
record here and one file of ``ops/``.

This module imports ``ops/`` and ``nn`` only, never ``transformer.py``:
``cfg`` is the model's ``TransformerConfig``, read by attribute.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common import nn
from vllm_omni_tpu.ops import gated_delta_rule as gdr
from vllm_omni_tpu.ops import ssd


class StateKind(NamedTuple):
    """One kind of layer that carries a state, as the forwards read it."""

    #: the key of the layer's weights in the parameters
    key: str
    #: the scope the slots' gathers and scatters run under
    scope: str
    #: ``init(keys, cfg, dtype)``: the mixer's weights, from the layer's
    #: eight keys
    init: Callable
    #: ``shapes(cfg)``: what one sequence carries through the layer, an
    #: array a shape (a slot is one row of each; layouts: ``ops/``)
    shapes: Callable
    #: ``mixer(weights, cfg, h, recur)``: the sequence mixer on normed rows
    #: ``h [T, hidden]``; ``recur(*operands)`` is what differs between the
    #: forwards (where the carried state comes from and goes to, the ragged
    #: or the one-token form of the rule)
    mixer: Callable
    #: ``ragged(cfg, cu_q_lens, q_lens)``: the rule of a token-packed step,
    #: ``rule(*the mixer's operands, *the state's arrays)`` -> (output,
    #: *the new state)
    ragged: Callable
    #: ``decode(cfg)``: the same of a step of one token a sequence
    decode: Callable
    #: of the state's arrays, how many (the last ones) the rule updates
    #: where they lie in the slots, instead of on a copy of the step's rows:
    #: the rule gets those pools themselves, then the rows' slots and which
    #: of them start a sequence, and returns the pools updated
    in_place: int = 0


def _gdn_init(k, cfg, dtype):
    """One Gated DeltaNet mixer's weights.  The published checkpoint fuses
    q, k, v and z into ``in_proj_qkvz`` and b, a into ``in_proj_ba``, both
    interleaved by key head; here they are kept apart in the order the
    convolution sees them ([q | k | v], then z; [b | a]) and the loader
    de-interleaves (models/qwen3_next).  ``A_log`` and ``dt_bias`` are drawn
    as the published initialiser draws them (A uniform in (0, 16), the time
    step log-uniform in [0.001, 0.1] through the inverse softplus), so that
    a token's decay exp(g) is neither 0 nor 1."""
    n_v = cfg.linear_num_value_heads
    v_dim = n_v * cfg.linear_value_head_dim
    ka, kd = jax.random.split(k[7])
    dt = jnp.exp(jax.random.uniform(kd, (n_v,), jnp.float32)
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    kc = cfg.linear_conv_kernel_dim
    return {
        "in_proj_qkv": nn.linear_init(k[0], cfg.hidden_size,
                                      cfg.linear_conv_dim, bias=False,
                                      dtype=dtype),
        "in_proj_z": nn.linear_init(k[1], cfg.hidden_size, v_dim,
                                    bias=False, dtype=dtype),
        "in_proj_ba": nn.linear_init(k[2], cfg.hidden_size, 2 * n_v,
                                     bias=False, dtype=dtype),
        # depthwise, no bias: w[K - 1] multiplies the current token
        "conv": {"w": jax.random.uniform(
            k[5], (kc, cfg.linear_conv_dim), dtype,
            minval=-kc ** -0.5, maxval=kc ** -0.5)},
        "A_log": jnp.log(jax.random.uniform(
            ka, (n_v,), jnp.float32, minval=1e-3, maxval=16.0)).astype(dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "norm": nn.rmsnorm_init(cfg.linear_value_head_dim, dtype),
        "out_proj": nn.linear_init(k[3], v_dim, cfg.hidden_size, bias=False,
                                   dtype=dtype),
    }


def _shortconv_init(k, cfg, dtype):
    """One gated short convolution's weights, under the checkpoint's names
    (``conv.in_proj``, ``conv.conv``, ``conv.out_proj``): ``in_proj``'s
    columns are [B | C | u]; the taps lie [K, hidden] with ``w[K - 1]`` on
    the current token, as ``ops/gated_delta_rule.py`` takes them (the
    checkpoint keeps [hidden, 1, K]: a transpose in a loader)."""
    kc = cfg.shortconv_kernel
    return {
        "in_proj": nn.linear_init(k[0], cfg.hidden_size,
                                  3 * cfg.hidden_size, bias=False,
                                  dtype=dtype),
        "conv": {"w": jax.random.uniform(
            k[5], (kc, cfg.hidden_size), dtype,
            minval=-kc ** -0.5, maxval=kc ** -0.5)},
        "out_proj": nn.linear_init(k[3], cfg.hidden_size, cfg.hidden_size,
                                   bias=False, dtype=dtype),
    }


def _mamba_init(k, cfg, dtype):
    """One Mamba-2 mixer's weights, under the checkpoint's names
    (``mamba.in_proj``, ``conv1d``, ``A_log``, ``dt_bias``, ``D``,
    ``norm``, ``out_proj``): ``in_proj``'s columns are [z | xBC | dt], xBC
    being u, then B a group, then C a group (``mamba_n_groups``); the
    taps lie [K, C] with ``w[K - 1]`` on the current token (the checkpoint
    keeps [C, 1, K]: a transpose in a loader), here with a bias.  ``A_log``,
    ``dt_bias`` and ``D`` are drawn as Mamba-2's own initialiser draws them
    (A uniform in (1, 16), the time step log-uniform in [0.001, 0.1]
    through the inverse softplus, D ones), so that a token's decay exp(a)
    is neither 0 nor 1 and a state is carried over hundreds of tokens."""
    n_h, d_h, n = cfg.mamba_dims
    d_inner, kc = n_h * d_h, cfg.mamba_conv_kernel
    ka, kd = jax.random.split(k[7])
    kw, kb = jax.random.split(k[5])
    dt = jnp.exp(jax.random.uniform(kd, (n_h,), jnp.float32)
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    return {
        "in_proj": nn.linear_init(k[0], cfg.hidden_size,
                                  d_inner + cfg.mamba_conv_dim + n_h,
                                  bias=False, dtype=dtype),
        # depthwise: w[K - 1] multiplies the current token; the published
        # initialiser's bias is small and not zero
        "conv": {"w": jax.random.uniform(
            kw, (kc, cfg.mamba_conv_dim), dtype,
            minval=-kc ** -0.5, maxval=kc ** -0.5),
                 "b": jax.random.uniform(
            kb, (cfg.mamba_conv_dim,), dtype,
            minval=-kc ** -0.5, maxval=kc ** -0.5)},
        "A_log": jnp.log(jax.random.uniform(
            ka, (n_h,), jnp.float32, minval=1.0, maxval=16.0)).astype(dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "D": jnp.ones((n_h,), dtype),
        "norm": nn.rmsnorm_init(d_inner, dtype),
        "out_proj": nn.linear_init(k[3], d_inner, cfg.hidden_size,
                                   bias=False, dtype=dtype),
    }


def _gdn_mixer(la, cfg, h, recur):
    """The Gated DeltaNet sequence mixer on normed rows h [T, hidden]:
    projections -> ``recur`` -> gated per-head norm -> out projection.
    ``recur(mixed, g, beta, conv_w)`` supplies what differs between the
    forwards (where the carried state comes from and goes to, and the
    chunked or the one-token form of the rule; ops/gated_delta_rule.py)
    and returns o [T, Hv, Dv] float32.  One body serves every forward, as
    ``_layer_step`` does for attention."""
    t = h.shape[0]
    n_v, d_v = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    with jax.named_scope("omni.proj"):
        # the mixer's inputs leave their matmuls in float32 and stay so up
        # to the out projection: everything between is float32 arithmetic
        # anyway, and a rounding in here reaches the residual stream at
        # full weight (see ``residual_in_fp32``)
        def f32_proj(name):
            return jnp.dot(h, la[name]["w"],
                           preferred_element_type=jnp.float32)

        mixed = f32_proj("in_proj_qkv")
        z = f32_proj("in_proj_z").reshape(t, n_v, d_v)
        ba = f32_proj("in_proj_ba")
    with jax.named_scope("omni.gdn"):
        beta = jax.nn.sigmoid(ba[:, :n_v])
        g = -jnp.exp(la["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[:, n_v:] + la["dt_bias"].astype(jnp.float32))
        o = recur(mixed, g, beta, la["conv"]["w"])
        # per head: w * o * rsqrt(mean(o^2) + eps), then the silu(z) gate
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_eps)
        o = (o * la["norm"]["w"].astype(jnp.float32)
             * jax.nn.silu(z)).astype(h.dtype)
    with jax.named_scope("omni.proj"):
        return nn.out_proj(o.reshape(t, -1), la["out_proj"]["w"],
                           cfg.residual_in_fp32)


def _shortconv_mixer(sc, cfg, h, recur):
    """The gated short convolution on normed rows h [T, hidden]: ``[B | C
    | u] = h W_in``; ``recur(B * u, taps)`` is the depth-wise causal
    convolution over each sequence's rows (float32, no activation: the
    silu after Gated DeltaNet's convolution is that path's own), which
    supplies what differs between the forwards (where the sequence's last
    inputs come from and go to); ``C`` gates it; then the out projection.
    The products ``B * u`` are exact in float32 (two bfloat16 factors),
    and that is what the state keeps."""
    with jax.named_scope("omni.proj"):
        bcu = nn.linear(sc["in_proj"], h)
    with jax.named_scope("omni.shortconv"):
        n = cfg.hidden_size
        gate_b, gate_c, u = (bcu[:, i * n:(i + 1) * n].astype(jnp.float32)
                             for i in range(3))
        y = (gate_c * recur(gate_b * u, sc["conv"]["w"])).astype(h.dtype)
    with jax.named_scope("omni.proj"):
        return nn.out_proj(y, sc["out_proj"]["w"], cfg.residual_in_fp32)


def _mamba_mixer(mb, cfg, h, recur):
    """The Mamba-2 sequence mixer on normed rows h [T, hidden]: ``[z | xBC
    | dt] = h W_in``; ``recur(xBC, dt, a, taps, bias, D)`` is convolution,
    silu, the state-space rule and the skip (ops/ssd.py), which supplies
    what differs between the forwards (where a sequence's carried inputs
    and state are, the chunked or the one-token form) and returns y [T,
    H, P] float32; then the gate ``silu(z)`` BEFORE an RMSNorm taken a
    group's channels at a time (all heads at once where there is one group;
    ``d_inner / mamba_n_groups`` channels where there are several, the
    weight being every channel's own), and the out projection.  Everything
    between the two projections is float32, as in ``_gdn_mixer`` and for
    its reason."""
    t = h.shape[0]
    n_h, d_h, _ = cfg.mamba_dims
    d_inner, groups = n_h * d_h, cfg.mamba_n_groups
    with jax.named_scope("omni.proj"):
        zxbcdt = jnp.dot(h, mb["in_proj"]["w"],
                         preferred_element_type=jnp.float32)
    with jax.named_scope("omni.ssd"):
        z = zxbcdt[:, :d_inner]
        xbc = zxbcdt[:, d_inner:d_inner + cfg.mamba_conv_dim]
        # no clamp of the time step: the family's limits are 0 and infinity
        dt = jax.nn.softplus(zxbcdt[:, d_inner + cfg.mamba_conv_dim:]
                             + mb["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(mb["A_log"].astype(jnp.float32)) * dt
        y = recur(xbc, dt, a, mb["conv"]["w"], mb["conv"]["b"], mb["D"])
        y = y.reshape(t, d_inner) * jax.nn.silu(z)
        if groups > 1:
            y = y.reshape(t, groups, d_inner // groups)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_eps)
        y = (y.reshape(t, d_inner)
             * mb["norm"]["w"].astype(jnp.float32)).astype(h.dtype)
    with jax.named_scope("omni.proj"):
        return nn.out_proj(y, mb["out_proj"]["w"], cfg.residual_in_fp32)


#: the kinds of layer whose sequences hold a state slot, by the name the
#: published ``layer_types`` gives them (slot layouts: ops/gated_delta_rule.py,
#: ops/ssd.py).  A Gated DeltaNet layer carries the convolution's last inputs
#: and the rule's matrix a value head; a gated short convolution its last
#: inputs alone; a Mamba-2 layer the convolution's last inputs and the rule's
#: [heads, head_dim, state_dim] state, 4 MB a sequence, which is updated in
#: its slot: a decode step would otherwise copy it out of the pool and back
KINDS = {
    "linear_attention": StateKind(
        key="linear_attn", scope="omni.gdn_state",
        init=_gdn_init, mixer=_gdn_mixer,
        shapes=lambda cfg: (
            (cfg.linear_conv_kernel_dim - 1, cfg.linear_conv_dim),
            (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
             cfg.linear_value_head_dim)),
        ragged=lambda cfg, cu_q_lens, q_lens: (
            lambda *a: gdr.gated_delta_net_ragged(
                *a, cu_q_lens, q_lens, cfg.gdn_heads)),
        decode=lambda cfg: (
            lambda *a: gdr.gated_delta_net_decode(*a, cfg.gdn_heads))),
    "conv": StateKind(
        key="shortconv", scope="omni.conv_state",
        init=_shortconv_init, mixer=_shortconv_mixer,
        shapes=lambda cfg: ((cfg.shortconv_kernel - 1, cfg.hidden_size),),
        ragged=lambda cfg, cu_q_lens, q_lens: (
            lambda *a: gdr.causal_conv_ragged(
                *a, cu_q_lens, q_lens, carried="gather")),
        decode=lambda cfg: gdr.causal_conv_update),
    "mamba": StateKind(
        key="mamba", scope="omni.ssd_state",
        init=_mamba_init, mixer=_mamba_mixer,
        shapes=lambda cfg: (
            (cfg.mamba_conv_kernel - 1, cfg.mamba_conv_dim), cfg.mamba_dims),
        ragged=lambda cfg, cu_q_lens, q_lens: (
            lambda *a: ssd.mamba2_ragged(
                *a, cu_q_lens, q_lens, cfg.mamba_dims,
                chunk=cfg.mamba_chunk_size, groups=cfg.mamba_n_groups)),
        decode=lambda cfg: (
            lambda *a: ssd.mamba2_decode(*a, cfg.mamba_dims,
                                         groups=cfg.mamba_n_groups)),
        in_place=1),
}
