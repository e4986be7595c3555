"""MiMo-V2.5's language model (``model_type`` ``mimo_v2``, the MiMo-V2-Flash
family: 309B parameters, about 15B active): five sliding-window layers of
128 keys with a learned sink in their softmax to one full layer, keys of
192 beside values of 128, 8 kv heads on the sliding kind and 4 on the full
kind, a rotary base a kind, a leading dense layer and then 256 experts
routed top-8 by sigmoid score.

Reference: the model's public ``config.json`` (``XiaomiMiMo/MiMo-V2.5``).
One layer, every norm an RMSNorm with a weight (eps 1e-5), pre-norm:
``x <- x + attn(n(x)); x <- x + mlp(n(x))``; a final norm and an untied
head.  The TPU build runs it on the shared functional transformer
(models/common/transformer.py), each departure from the other models read
from this configuration or from the weight tree:

- ``hybrid_layer_pattern[i]`` 0 is a *full* layer (``layer_kinds``
  ``"full_attention"``: 9 of 48), 1 a *sliding* one (39);
- either kind: 64 query heads, ``q`` and ``k`` heads of 192 (``head_dim``),
  ``v`` heads of 128 (``v_head_dim``) times 0.707
  (``attention_value_scale``) before they are cached, no bias, scores over
  ``sqrt(192)``; rotary on a head's first ``int(192 x 0.334) = 64`` dims
  (``partial_rotary_factor``), half-split;
- a full layer: 4 kv heads, rotary base 1e7, every key at or below the
  token, a plain softmax.  A sliding layer (``sliding_heads``, a
  ``HeadKind``): 8 kv heads, base 1e4, the last 128 keys (the token itself
  among them) and one learned logit a query head in the softmax's
  denominator with no value (``sinks`` in the layer's weights; the
  kernels start their online softmax at it:
  ops/ragged_paged_attention.py);
- the two kinds of layer keep their rows in two kinds of page whose pools
  differ in SHAPE: ``[4, P, 16, 256 | 128]`` under the full table and
  ``[8, Pw, 16, 256 | 128]`` under the window table (a key of 192 lies in
  a row of 256 lanes, 64 of them zeros: docs/kv_cache.md, "A key wider
  than its value");
- layer 0's MLP is a dense SwiGLU of 16 384 (``first_k_dense_replace``
  1); every other layer routes over 256 experts of 2 048: sigmoid scores
  in float32, the 8 largest of ``s + e_score_correction_bias`` kept,
  weights ``s_e / sum of the kept s``, no scaling factor, no shared
  expert, no group limit (``n_group`` 1).

Not served, and refused or absent by name:

- the vision tower, the audio encoder and the three multi-token-prediction
  layers of the family: the language model's ``config.json`` names no key
  of them; text alone, one token a step;
- prefix reuse (refused a hit and counted), ``kv_offload``, ``kv_transfer``,
  an int8 cache, speculative decoding, tensor parallelism
  (``kvcache/layouts.py``, "window pages");
- a loader for the checkpoint's tensor names (``fused_qkv`` is how the
  checkpoint lays its projections, not mathematics): there is nothing
  here to test one on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common.transformer import (
    HeadKind,
    TransformerConfig,
    init_params,
)
from vllm_omni_tpu.models.qwen3_next import expert_share  # noqa: F401

_KIND = {0: "full_attention", 1: "sliding_attention"}
#: ``hybrid_layer_pattern`` as published: a full layer, four sliding ones
#: and a full one, then seven periods of five sliding layers and a full one
HYBRID_LAYER_PATTERN = (0, 1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7


def layer_kinds(pattern) -> tuple[str, ...]:
    """``hybrid_layer_pattern`` -> the program's kind of each layer."""
    return tuple(_KIND[p] for p in pattern)


# Published geometry (config.json): hidden 4096, 48 layers by the pattern
# above; 64 q heads, k 192 / v 128, 4 kv heads on a full layer and 8 on a
# sliding one, no bias, no q/k norm; rotary on 64 dims, base 1e7 full /
# 1e4 sliding; window 128 with a sink on the sliding layers; value scale
# 0.707; a dense first layer of 16384, then 256 experts x 2048 top-8 by
# sigmoid score with a correction bias, renormalised; RMSNorm eps 1e-5;
# vocabulary 152576, untied.
MIMO_V2_5 = TransformerConfig(
    vocab_size=152576,
    hidden_size=4096,
    num_layers=48,
    num_heads=64,
    num_kv_heads=4,
    head_dim=192,
    v_head_dim=128,
    intermediate_size=16384,
    rope_theta=1e7,
    rms_eps=1e-5,
    partial_rotary_factor=0.334,
    attention_value_scale=0.707,
    moe=True,
    num_experts=256,
    num_experts_per_tok=8,
    moe_intermediate_size=2048,
    moe_renormalize=True,
    first_k_dense_replace=1,
    router_scoring="sigmoid",
    router_bias=True,
    layer_kinds=layer_kinds(HYBRID_LAYER_PATTERN),
    sliding_window=128,
    sliding_heads=HeadKind(num_kv_heads=8, rope_theta=1e4, sink=True),
)


def tiny_config(vocab_size: int = 128) -> TransformerConfig:
    """Tiny counts at the published HEAD widths, which are what the model
    forces: a full layer with a dense MLP, then three sliding layers and a
    full one; 4 q heads of 192 (64 rotated) on 2 kv heads (sliding) and 1
    (full), values of 128 times 0.707, a window of 21 keys (not a multiple
    of the 16-token page) with a sink, 8 experts of 32 top-2 by
    renormalised sigmoid score with a correction bias."""
    return TransformerConfig(
        vocab_size=vocab_size, hidden_size=64, num_layers=5, num_heads=4,
        num_kv_heads=1, head_dim=192, v_head_dim=128, intermediate_size=96,
        rope_theta=1e7, rms_eps=1e-5, partial_rotary_factor=0.334,
        attention_value_scale=0.707, moe=True, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32,
        first_k_dense_replace=1, router_scoring="sigmoid", router_bias=True,
        layer_kinds=layer_kinds((0, 1, 1, 1, 0)), sliding_window=21,
        sliding_heads=HeadKind(num_kv_heads=2, rope_theta=1e4, sink=True),
    )


def tiny_factory():
    """model_factory for tests/dry-runs: random-weight tiny MiMo-V2."""
    cfg = tiny_config()
    return init_params(jax.random.PRNGKey(0), cfg, jnp.float32), cfg, None


def real_factory(model_dir: str, dtype="bfloat16", **kw):
    """Arch-registry front door.  No loader maps the checkpoint's tensor
    names yet (module docstring): said here rather than served wrongly."""
    raise NotImplementedError(
        "MiMoV2ForCausalLM: no checkpoint loader yet; serve seeded weights "
        "through a model_factory (vllm_omni_tpu.models.mimo_v2:"
        "tiny_factory, or the benchmark's benchmark/archs/mimo_v2.py)")
