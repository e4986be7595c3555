"""command-a-plus-05-2026 (``model_type`` ``cohere2_moe``, "Command A+",
218B parameters, about 25B active): a parallel block under one LayerNorm,
three sliding-window layers with rotary positions to one full layer
without any, 128 query heads on 8 kv heads, 128 experts routed top-8 by
sigmoid score beside four shared experts whose outputs are averaged.

Reference: the model's public ``config.json``
(``CohereLabs/command-a-plus-05-2026``).  One layer, ``h = LN(x)`` (the
row's mean subtracted, a weight and no bias, eps 1e-5):
``x <- x + attn(h) + moe(h)``; after the last layer ``LN`` again, then the
head, which is the embedding transposed (``logit_scale`` 1).  The TPU
build runs it on the shared functional transformer
(models/common/transformer.py), each departure from the other models read
from this configuration or from the weight tree:

- ``parallel_block``: a layer's weights hold ONE norm (``input_norm``) and
  no ``post_norm``; ``_layer_step`` feeds mixer and MLP from it and joins
  both branches to the stream in one float32 sum;
- ``norm_subtract_mean``: the stack's norm is the LayerNorm above;
- a *sliding* layer (``layer_types[i] == "sliding_attention"``: three in
  four) attends the last 4 096 keys, the token itself among them, over
  per-head (K, V) rows that live in pages of the window kind, given back
  once they slid out of reach (docs/kv_cache.md, "Two kinds of page"; the
  kernel's ``window``); its queries and keys are rotated at base 50 000
  over all 128 dims.  A *full* layer attends every key and rotates
  nothing (``rope_kinds``);
- the rotary embedding pairs neighbouring dims (``rope_gptj``).  It is
  served by the half-split rotation over q and k columns permuted once in
  the weight tree (``ops/rope.py pairs_to_halves``: the scores are the
  published ones); a loader has to apply it to the sliding layers'
  ``q_proj`` and ``k_proj``, as the benchmark's seeded draw does;
- four shared experts of 4 096 are one shared SwiGLU of 16 384 whose
  output is times 1/4 (``shared_expert_scale``: the mean of the four, as
  ``shared_expert_combination_strategy`` "average" is read), added to the
  routed sum; the router is sigmoid scores over 128 experts in float32,
  the 8 largest kept and renormalised, no correction bias, no scale.

Not served, and refused or absent by name:

- the vision tower: the public ``config.json`` of the language model names
  no key of it; text alone;
- the prefix dense layers the family has: ``first_k_dense_replace`` is 0,
  so this model has none and ``prefix_dense_intermediate_size`` sizes
  nothing;
- prefix reuse (refused a hit and counted), ``kv_offload``, ``kv_transfer``,
  an int8 cache, speculative decoding, tensor parallelism
  (``kvcache/layouts.py``, "window pages");
- a loader for the checkpoint's tensor names: there is nothing here to
  test one on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common.transformer import TransformerConfig, init_params
from vllm_omni_tpu.models.qwen3_next import expert_share  # noqa: F401

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)

# Published geometry (config.json): hidden 4096, 32 layers (every fourth
# full, the others sliding at a window of 4096); 128 q / 8 kv heads x 128,
# no bias, no q/k norm; rotary base 50000 over the whole head on the
# sliding layers, neighbouring dims paired; 128 experts x 4096 top-8 by
# sigmoid score, renormalised, four shared experts x 4096 averaged; one
# LayerNorm a layer, eps 1e-5; vocabulary 262144, tied; logit scale 1.
COMMAND_A_PLUS_05_2026 = TransformerConfig(
    vocab_size=262144,
    hidden_size=4096,
    num_layers=32,
    num_heads=128,
    num_kv_heads=8,
    head_dim=128,
    intermediate_size=4096,
    rope_theta=50000.0,
    rms_eps=1e-5,
    tie_word_embeddings=True,
    moe=True,
    num_experts=128,
    num_experts_per_tok=8,
    moe_intermediate_size=4096,
    moe_renormalize=True,
    shared_expert_size=4 * 4096,
    shared_expert_gate=False,
    shared_expert_scale=0.25,
    router_scoring="sigmoid",
    layer_kinds=_PERIOD * 8,
    sliding_window=4096,
    rope_kinds=("sliding_attention",),
    parallel_block=True,
    norm_subtract_mean=True,
)


def tiny_config(vocab_size: int = 128) -> TransformerConfig:
    """Tiny widths that keep what the published model forces: one period
    (three sliding layers, then a full one) of a parallel block under a
    LayerNorm, 4 q on 2 kv heads of 16, a window of 21 keys (not a
    multiple of the 16-token page) with rotary on the sliding layers
    alone, 8 experts of 32 top-2 by renormalised sigmoid score, four
    shared experts of 24 averaged, tied embeddings."""
    return TransformerConfig(
        vocab_size=vocab_size, hidden_size=64, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=16, intermediate_size=32, rope_theta=5e4,
        rms_eps=1e-5, tie_word_embeddings=True, moe=True, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_size=4 * 24, shared_expert_gate=False,
        shared_expert_scale=0.25, router_scoring="sigmoid",
        layer_kinds=_PERIOD, sliding_window=21,
        rope_kinds=("sliding_attention",), parallel_block=True,
        norm_subtract_mean=True,
    )


def tiny_factory():
    """model_factory for tests/dry-runs: random-weight tiny Command A+."""
    cfg = tiny_config()
    return init_params(jax.random.PRNGKey(0), cfg, jnp.float32), cfg, None


def real_factory(model_dir: str, dtype="bfloat16", **kw):
    """Arch-registry front door.  No loader maps the checkpoint's tensor
    names yet (module docstring): said here rather than served wrongly."""
    raise NotImplementedError(
        "Cohere2MoeForCausalLM: no checkpoint loader yet; serve seeded "
        "weights through a model_factory (vllm_omni_tpu.models."
        "cohere2_moe:tiny_factory, or the benchmark's "
        "benchmark/archs/cohere2_moe.py)")
