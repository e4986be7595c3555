"""GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``, 30B-A3B): multi-head
latent attention, a leading dense layer, and sparse layers routed by
sigmoid scores with a learned correction bias, beside an ungated shared
expert.

Reference: the model's public ``config.json`` (``zai-org/GLM-4.7-Flash``)
and the DeepSeek-V2 / V3 papers' equations for the two mechanisms it takes
from them.  The TPU build runs it on the shared functional transformer
(models/common/transformer.py): the latent mixer (``_mla_mixer``, served
absorbed over a latent paged cache of one ``kv_lora_rank +
qk_rope_head_dim`` row a token, docs/kv_cache.md), the MLP that differs by
layer, the router's scoring and the ungated shared expert are switches of
``TransformerConfig`` and shapes of the weight tree; the paged-attention
ops read the latent pool as one kv head whose value is the key's first
``kv_lora_rank`` lanes (ops/paged_attention.py).

Not served, and refused or absent by name:

- the checkpoint's one multi-token-prediction module
  (``num_nextn_predict_layers`` 1): speculative decoding is refused for a
  latent cache at engine construction (a draft head would need the verify
  path over the shared pool), so the module would only cost;
- a loader for the checkpoint's tensor names: there is nothing here to
  test one on.  The weight tree keeps the checkpoint's names without their
  suffixes (``transformer._mla_init``), so a loader is a renaming;
- ``config.json`` does not say how rotary pairs the 64 rotary dims.  They
  are rotated as two halves (``ops/rope.py apply_rope_ref``); the
  interleaved pairing differs by a fixed permutation of the rotary columns
  of ``q_b`` and ``kv_a``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common.transformer import TransformerConfig, init_params

# Published GLM-4.7-Flash geometry (config.json): hidden 2048, 47 layers,
# 20 heads of latent attention (q through rank 768, keys and values through
# a 512-wide latent and 64 shared rotary dims; a head's key 192 + 64, its
# value 256), theta 1e6, eps 1e-5; layer 0 a dense SwiGLU of 10240, then 64
# experts x 1536 top-4 by sigmoid score plus correction bias, renormalised
# and scaled by 1.8, one ungated shared expert of 1536; vocabulary 154880.
GLM_4_7_FLASH = TransformerConfig(
    vocab_size=154880,
    hidden_size=2048,
    num_layers=47,
    num_heads=20,
    num_kv_heads=20,
    head_dim=256,
    intermediate_size=10240,
    rope_theta=1e6,
    rms_eps=1e-5,
    moe=True,
    num_experts=64,
    num_experts_per_tok=4,
    moe_intermediate_size=1536,
    moe_renormalize=True,
    shared_expert_size=1536,
    shared_expert_gate=False,
    first_k_dense_replace=1,
    router_scoring="sigmoid",
    router_bias=True,
    routed_scaling_factor=1.8,
    q_lora_rank=768,
    kv_lora_rank=512,
    qk_nope_head_dim=192,
    qk_rope_head_dim=64,
    v_head_dim=256,
)


def tiny_config(vocab_size: int = 128) -> TransformerConfig:
    """Tiny widths that keep the published model's ratios: 4 heads whose
    key is 24 + 8 (a rotary part a third of the rest) and whose value, 32,
    is wider than the key's un-rotated part; a 64-wide latent (72 numbers
    a token, 128 lanes); a dense first layer and two sparse ones of 8
    experts top-2 with the correction bias and an ungated shared expert."""
    return TransformerConfig(
        vocab_size=vocab_size, hidden_size=64, num_layers=3, num_heads=4,
        num_kv_heads=4, head_dim=32, intermediate_size=160, rope_theta=1e6,
        rms_eps=1e-5, moe=True, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, shared_expert_size=32,
        shared_expert_gate=False, first_k_dense_replace=1,
        router_scoring="sigmoid", router_bias=True,
        routed_scaling_factor=1.8, q_lora_rank=24, kv_lora_rank=64,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
    )


def tiny_factory():
    """model_factory for tests/dry-runs: random-weight tiny latent LM."""
    cfg = tiny_config()
    return init_params(jax.random.PRNGKey(0), cfg, jnp.float32), cfg, None


def real_factory(model_dir: str, dtype="bfloat16", **kw):
    """Arch-registry front door.  No loader maps the checkpoint's tensor
    names yet (module docstring): said here rather than served wrongly."""
    raise NotImplementedError(
        "Glm4MoeLiteForCausalLM: no checkpoint loader yet; serve seeded "
        "weights through a model_factory (vllm_omni_tpu.models."
        "glm4_moe_lite:tiny_factory, or the benchmark's "
        "benchmark/archs/glm4_moe_lite.py)")
