"""Qwen3-Next-80B-A3B: a hybrid stack of Gated DeltaNet linear-attention
layers and gated softmax-attention layers (3 : 1), each followed by a
512-expert sparse MLP with a shared expert.

Reference: transformers ``Qwen3NextForCausalLM`` (modeling_qwen3_next.py)
and the model's public ``config.json``.  The TPU build runs it on the
shared functional transformer (models/common/transformer.py): the layer
pattern, the second sequence mixer (ops/gated_delta_rule.py), the output
gate of the attention layers, rotary embedding on the first quarter of a
head, zero-centred RMSNorm weights and the expert share are switches of
``TransformerConfig``; the engine gives it a per-sequence state cache
beside the paged KV cache (docs/kv_cache.md).

Not served: the checkpoint's multi-token-prediction module (``mtp.*``
tensors; speculative decoding cannot roll a recurrent state back yet and
is refused for this model at engine construction).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common.transformer import TransformerConfig, init_params

# Published Qwen3-Next-80B-A3B-Instruct geometry (config.json): hidden 2048,
# 48 layers of which every fourth is softmax attention (16 q / 2 kv heads x
# 256, output gate, rotary on 64 of 256 dims, theta 1e7) and the rest Gated
# DeltaNet (16 key / 32 value heads x 128, conv kernel 4); 512 experts x 512
# top-10 renormalised, one shared expert of width 512; vocabulary 151936.
QWEN3_NEXT_80B_A3B = TransformerConfig(
    vocab_size=151936,
    hidden_size=2048,
    num_layers=48,
    num_heads=16,
    num_kv_heads=2,
    head_dim=256,
    intermediate_size=5120,
    rope_theta=1e7,
    rms_eps=1e-6,
    qk_norm=True,
    moe=True,
    num_experts=512,
    num_experts_per_tok=10,
    moe_intermediate_size=512,
    moe_renormalize=True,
    shared_expert_size=512,
    full_attention_interval=4,
    linear_num_key_heads=16,
    linear_num_value_heads=32,
    linear_key_head_dim=128,
    linear_value_head_dim=128,
    linear_conv_kernel_dim=4,
    attn_output_gate=True,
    partial_rotary_factor=0.25,
    norm_zero_centered=True,
    residual_in_fp32=True,
)


def expert_share(cfg: TransformerConfig, held: int, offset: int = 0
                 ) -> TransformerConfig:
    """``cfg`` as one chip of an expert-parallel deployment runs it: the
    experts ``offset .. offset + held`` live here, the router keeps its
    whole width and its top-k (ops/moe.py ``routed_moe_share``)."""
    width = cfg.router_experts or cfg.num_experts
    if not 0 <= offset <= width - held:
        raise ValueError(f"experts {offset}..{offset + held} are no share "
                         f"of a {width}-wide router")
    if held == width:
        return dataclasses.replace(cfg, num_experts=width, router_experts=0,
                                   expert_offset=0)
    return dataclasses.replace(cfg, num_experts=held, router_experts=width,
                               expert_offset=offset)


def tiny_config(vocab_size: int = 128) -> TransformerConfig:
    """Tiny widths that keep every ratio of the published model: one
    period of 3 linear + 1 full layer, 2 key / 4 value heads of width 16
    and 32, 4 q / 2 kv attention heads of width 32 with rotary on 8 dims,
    8 experts top-2 with a shared one."""
    return TransformerConfig(
        vocab_size=vocab_size, hidden_size=64, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=32, intermediate_size=128, rope_theta=1e7,
        qk_norm=True, moe=True, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, shared_expert_size=32,
        full_attention_interval=4, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=32, linear_conv_kernel_dim=4,
        attn_output_gate=True, partial_rotary_factor=0.25,
        norm_zero_centered=True, residual_in_fp32=True,
    )


def tiny_factory():
    """model_factory for tests/dry-runs: random-weight tiny hybrid LM."""
    cfg = tiny_config()
    return init_params(jax.random.PRNGKey(0), cfg, jnp.float32), cfg, None


def real_factory(model_dir: str, dtype="bfloat16", **kw):
    """Arch-registry front door: load a Qwen3-Next checkpoint directory
    (model_loader/hf_qwen.py maps the tensor names, de-interleaving the
    fused ``in_proj_qkvz`` / ``in_proj_ba`` by key head)."""
    from vllm_omni_tpu.model_loader.hf_qwen import load_qwen_lm

    return load_qwen_lm(model_dir, dtype=dtype, **kw)
