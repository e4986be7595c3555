"""dots3-note-prev (``model_type`` ``dots3_note``, 288B-A17B): latent
attention of two kinds, three layers in four over a sliding window and the
others over a learned selection of their context, and sparse layers routed
by sigmoid scores with a correction bias.

Reference: the model's public ``config.json``
(``dots-studio/dots3-note-prev``) and the papers its mechanisms come from
(DeepSeek-V2's latent attention, DeepSeek-V3.2's indexer, the
scale-corrected latents of the LongCat-Flash family, head-wise gated
attention).  The TPU build runs the language model on the shared
functional transformer (models/common/transformer.py):

- a *full* layer (``layer_types[i] == "full_attention"``: layers 0, 1, 5,
  9, ... 45) is GLM-4.7-Flash's latent mixer at 128 heads (``_mla_mixer``,
  absorbed over a 576-number row a token) with an *indexer* beside it: 64
  light heads score every cached token from a 128-wide index key the token
  keeps in a pool of its own, on the same pages as its row, and the 2 048
  best are attended (ops/indexer.py; the selection reaches the one paged
  attention kernel as its ``keep`` operand);
- a *sliding* layer is the same mixer at widths of its own
  (``TransformerConfig.sliding``: 64 heads, a 1 024-wide latent, rotary
  base 5e4) over the last 513 keys (the kernel's ``window``); its 1 088
  numbers a token live in pages of a second kind, which the cache manager
  gives back once they slid out of reach (docs/kv_cache.md, "Two kinds of
  page");
- both multiply their latents by sqrt(hidden / rank) after the norms
  (``mla_lora_rescale``) and gate each head's output by a sigmoid of the
  layer's input (``attn_head_gate``);
- layer 0's MLP is dense, the others route 8 of 256 experts by sigmoid
  score plus correction bias, beside one ungated shared expert.

Not served, and refused or absent by name:

- the vision and audio towers and the multi-token-prediction module: the
  public ``config.json`` of the language model names no key of theirs;
  speculative decoding is refused for this cache at engine construction;
- prefix reuse (a freed window page no longer holds the rows at a prefix's
  boundary: refused a hit and counted), ``kv_offload``, ``kv_transfer``, an
  int8 cache, tensor parallelism (``kvcache/layouts.py``);
- a loader for the checkpoint's tensor names: there is nothing here to
  test one on.  The weight tree keeps the checkpoint's names without their
  suffixes (``transformer._mla_init``, ``_indexer_init``);
- what ``config.json`` does not say is set as the cited papers have it:
  rotary pairs the rotary dims as two halves; the rescale follows the
  norms; the indexer reads the rescaled query latent, rotates the first
  64 dims of its queries and key, and scales its weights by
  ``heads^-0.5 * width^-0.5``; the gate reads the layer's normed input and
  multiplies a head's output before ``o_proj``; the window holds the token
  itself.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common.transformer import (
    LatentKind,
    TransformerConfig,
    init_params,
)
from vllm_omni_tpu.models.qwen3_next import expert_share  # noqa: F401

_PERIOD = ("full_attention",) + ("sliding_attention",) * 3

# Published geometry (config.json): hidden 5120, 46 layers (0 and every
# fourth from 1 full, the others sliding); a full layer 128 heads, q rank
# 1024, kv rank 512, a head's key 128 + 64 and value 128, theta 8e7, an
# indexer of 64 heads x 128 keeping 2048; a sliding layer 64 heads, q and
# kv rank 1024, key 192 + 64, value 128, theta 5e4, window 513; eps 1e-5;
# layer 0 a dense SwiGLU of 13824, then 256 experts x 1536 top-8 by
# sigmoid score plus correction bias, renormalised, times 1, one ungated
# shared expert of 1536; vocabulary 152064.
DOTS3_NOTE_PREV = TransformerConfig(
    vocab_size=152064,
    hidden_size=5120,
    num_layers=46,
    num_heads=128,
    num_kv_heads=128,
    head_dim=192,
    intermediate_size=13824,
    rope_theta=8e7,
    rms_eps=1e-5,
    moe=True,
    num_experts=256,
    num_experts_per_tok=8,
    moe_intermediate_size=1536,
    moe_renormalize=True,
    shared_expert_size=1536,
    shared_expert_gate=False,
    first_k_dense_replace=1,
    router_scoring="sigmoid",
    router_bias=True,
    routed_scaling_factor=1.0,
    q_lora_rank=1024,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    layer_kinds=("full_attention",) + _PERIOD * 11 + ("full_attention",),
    sliding=LatentKind(num_heads=64, q_lora_rank=1024, kv_lora_rank=1024,
                       qk_nope_head_dim=192, qk_rope_head_dim=64,
                       v_head_dim=128, rope_theta=5e4),
    sliding_window=513,
    index_n_heads=64,
    index_head_dim=128,
    index_topk=2048,
    mla_lora_rescale=True,
    attn_head_gate=True,
)


def tiny_config(vocab_size: int = 128) -> TransformerConfig:
    """Tiny widths that keep the published model's shape: a dense full
    layer, a sparse full one and three sliding ones; a full layer 4 heads
    (key 16 + 8, value 16) over a 32-wide latent with an indexer of 2
    heads x 16 keeping 8; a sliding layer 2 heads (key 24 + 8, value 16)
    over a 48-wide latent, a base of its own and a window of 5; 16 experts
    top-2, of which 4 are held (ids 4-7) where a test asks for a share."""
    return TransformerConfig(
        vocab_size=vocab_size, hidden_size=64, num_layers=5, num_heads=4,
        num_kv_heads=4, head_dim=24, intermediate_size=160, rope_theta=8e7,
        rms_eps=1e-5, moe=True, num_experts=16, num_experts_per_tok=2,
        moe_intermediate_size=32, shared_expert_size=32,
        shared_expert_gate=False, first_k_dense_replace=1,
        router_scoring="sigmoid", router_bias=True,
        routed_scaling_factor=1.0, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        layer_kinds=("full_attention",) + _PERIOD,
        sliding=LatentKind(num_heads=2, q_lora_rank=24, kv_lora_rank=48,
                           qk_nope_head_dim=24, qk_rope_head_dim=8,
                           v_head_dim=16, rope_theta=5e4),
        sliding_window=5, index_n_heads=2, index_head_dim=16, index_topk=8,
        mla_lora_rescale=True, attn_head_gate=True,
    )


def tiny_factory():
    """model_factory for tests/dry-runs: random-weight tiny LM."""
    cfg = tiny_config()
    return init_params(jax.random.PRNGKey(0), cfg, jnp.float32), cfg, None


def real_factory(model_dir: str, dtype="bfloat16", **kw):
    """Arch-registry front door.  No loader maps the checkpoint's tensor
    names yet (module docstring): said here rather than served wrongly."""
    raise NotImplementedError(
        "Dots3NoteForCausalLM: no checkpoint loader yet; serve seeded "
        "weights through a model_factory (vllm_omni_tpu.models."
        "dots3_note:tiny_factory, or the benchmark's "
        "benchmark/archs/dots3_note.py)")
