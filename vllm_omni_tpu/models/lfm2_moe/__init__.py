"""LFM2-8B-A1B (``model_type`` ``lfm2_moe``, 8.3B parameters, 1.5B active):
gated short convolutions as the sequence mixer of three layers in four,
grouped-query attention with 64-wide heads on the others, two leading
dense layers and then 32 experts routed top-4 by sigmoid score plus an
expert bias.

Reference: the model's public ``config.json`` (``LiquidAI/LFM2-8B-A1B``).
A block is ``x += mixer(rms(x)); x += mlp(rms(x))``, plain norm weights,
eps 1e-5, and after the last layer one more norm (the checkpoint's
``embedding_norm``), then the head, which is the embedding transposed.

The TPU build runs it on the shared functional transformer
(models/common/transformer.py): the layer kinds are data (``layer_kinds``:
``"conv"`` beside ``"full_attention"``), the third sequence mixer is
``state_layers._shortconv_mixer`` over ``ops/gated_delta_rule.py``'s causal
convolution, whose state (the last two rows of ``B * u``, ``[2, 2048]`` a
layer, nothing else) takes a slot of the engine's state cache as Qwen3-
Next's does (docs/kv_cache.md, "Three kinds of state"); the pages hold two
64-wide kv heads to a 128-lane row (``ops/paged_attention.py
heads_a_row``); the leading dense layers, the sigmoid router and its bias
are the switches GLM-4.7-Flash uses.

Not served, and refused or absent by name:

- what the engine refuses for any model with state slots (offload,
  transfer, speculation, an int8 cache, tensor parallelism) and a prefix
  hit, one by one (docs/kv_cache.md);
- a loader for the checkpoint's tensor names: there is nothing here to
  test one on.  The weight tree keeps the checkpoint's names without their
  suffixes (``state_layers._shortconv_init``), the taps transposed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common.transformer import TransformerConfig, init_params

# Published LFM2-8B-A1B geometry (config.json): hidden 2048, 24 layers of
# which 2, 6, 10, 14, 18 and 21 are attention (32 q / 8 kv heads x 64,
# per-head q/k norm, theta 1e6) and the rest gated short convolutions of 3
# taps; layers 0-1 a dense SwiGLU of 7168, then 32 experts x 1792 top-4 by
# sigmoid score plus expert bias, renormalised, times 1; vocabulary 65536,
# tied with the head; eps 1e-5.
LFM2_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))

LFM2_8B_A1B = TransformerConfig(
    vocab_size=65536,
    hidden_size=2048,
    num_layers=24,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    intermediate_size=7168,
    rope_theta=1e6,
    rms_eps=1e-5,
    qk_norm=True,
    tie_word_embeddings=True,
    moe=True,
    num_experts=32,
    num_experts_per_tok=4,
    moe_intermediate_size=1792,
    moe_renormalize=True,
    first_k_dense_replace=2,
    router_scoring="sigmoid",
    router_bias=True,
    routed_scaling_factor=1.0,
    layer_kinds=LFM2_LAYER_TYPES,
    shortconv_kernel=3,
)


def tiny_config(vocab_size: int = 128) -> TransformerConfig:
    """Tiny widths that keep what the published model forces: one period
    of conv conv full conv and a fifth layer, heads of 64 (4 q on 2 kv: one
    128-lane row whose two heads differ), two leading dense layers and
    three sparse ones of 8 experts top-2 with the expert bias, three taps,
    tied embeddings."""
    return TransformerConfig(
        vocab_size=vocab_size, hidden_size=128, num_layers=5, num_heads=4,
        num_kv_heads=2, head_dim=64, intermediate_size=192, rope_theta=1e6,
        rms_eps=1e-5, qk_norm=True, tie_word_embeddings=True, moe=True,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=64,
        first_k_dense_replace=2, router_scoring="sigmoid", router_bias=True,
        layer_kinds=("conv", "conv", "full_attention", "conv", "conv"),
    )


def tiny_factory():
    """model_factory for tests/dry-runs: random-weight tiny LFM2."""
    cfg = tiny_config()
    return init_params(jax.random.PRNGKey(0), cfg, jnp.float32), cfg, None


def real_factory(model_dir: str, dtype="bfloat16", **kw):
    """Arch-registry front door.  No loader maps the checkpoint's tensor
    names yet (module docstring): said here rather than served wrongly."""
    raise NotImplementedError(
        "Lfm2MoeForCausalLM: no checkpoint loader yet; serve seeded "
        "weights through a model_factory (vllm_omni_tpu.models."
        "lfm2_moe:tiny_factory, or the benchmark's "
        "benchmark/archs/lfm2_moe.py)")
