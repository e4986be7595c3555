"""granite-4.0-h-small (``model_type`` ``granitemoehybrid``, "Granite 4.0-H
Small", 32B parameters, about 9B active): Mamba-2 layers as the sequence
mixer of nine layers in ten, grouped-query attention WITHOUT positional
encoding on the tenth, 72 experts routed top-10 plus an ungated shared
SwiGLU in every layer, and the family's four multipliers.

Reference: the model's public ``config.json``
(``ibm-granite/granite-4.0-h-small``).  ``x_0 = 12 E[ids]``; a block is
``x += 0.22 mixer(rms(x)); x += 0.22 (moe(rms(x)) + shared(rms(x)))``, plain
norm weights, eps 1e-5; after the last layer a norm, then the head, which
is the embedding transposed, divided by 16.  The attention layers' softmax
scale is ``attention_multiplier`` 1/128, not ``head_dim ** -0.5``.

The TPU build runs it on the shared functional transformer
(models/common/transformer.py): the layer kinds are data (``layer_kinds``:
``"mamba"`` beside ``"full_attention"``), the fourth sequence mixer is
``state_layers._mamba_mixer`` over ``ops/ssd.py``, whose state (the
convolution's last three inputs, ``[3, 8448]``, and the rule's ``[128, 64,
128]`` float32, 4 MB a sequence a layer) takes a slot of the engine's state cache as Qwen3-
Next's and LFM2's do, and is updated where it lies (docs/kv_cache.md, "Four
kinds of state"); ``use_rope=False`` builds no table and rotates no key;
the sparse MLP is the thinker's softmax router renormalised over its picks,
GLM's ungated shared expert and Qwen3-Next's expert share.

Not served, and refused or absent by name:

- what the engine refuses for any model with state slots (offload,
  transfer, speculation, an int8 cache, tensor parallelism) and a prefix
  hit, one by one (docs/kv_cache.md);
- (more than one group of B and C IS served since PR 46:
  ``TransformerConfig.mamba_n_groups``, models/nemotron_h; this model has
  one, and with one the traced program is the one it was);
- a loader for the checkpoint's tensor names: there is nothing here to
  test one on.  The weight tree keeps the checkpoint's names without their
  suffixes (``state_layers._mamba_init``), the taps transposed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common.transformer import TransformerConfig, init_params
from vllm_omni_tpu.models.qwen3_next import expert_share  # noqa: F401

# Published granite-4.0-h-small geometry (config.json): hidden 4096, 40
# layers of which 5, 15, 25 and 35 are attention (32 q / 8 kv heads x 128,
# no positions, scale 1/128) and the rest Mamba-2 (128 heads x 64, one group
# of B and C at 128, 4 taps with a bias, chunk 256); every layer 72 experts
# x 768 top-10 (softmax over the kept logits) plus a shared SwiGLU of 1536;
# embedding x 12, branches x 0.22, logits / 16; vocabulary 100352, tied;
# eps 1e-5.
GRANITE_LAYER_TYPES = tuple(
    "full_attention" if i % 10 == 5 else "mamba" for i in range(40))

GRANITE_4_0_H_SMALL = TransformerConfig(
    vocab_size=100352,
    hidden_size=4096,
    num_layers=40,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    intermediate_size=768,
    rope_theta=10000.0,
    rms_eps=1e-5,
    tie_word_embeddings=True,
    moe=True,
    num_experts=72,
    num_experts_per_tok=10,
    moe_intermediate_size=768,
    moe_renormalize=True,
    shared_expert_size=1536,
    shared_expert_gate=False,
    layer_kinds=GRANITE_LAYER_TYPES,
    mamba_num_heads=128,
    mamba_head_dim=64,
    mamba_state_dim=128,
    mamba_conv_kernel=4,
    mamba_chunk_size=256,
    use_rope=False,
    attention_multiplier=0.0078125,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
)


def tiny_config(vocab_size: int = 128) -> TransformerConfig:
    """Tiny widths that keep what the published model forces: mamba mamba
    attention mamba, 4 heads of 8 on a state of 128 (the kernel's lane
    width) with a chunk of 8 tokens so that a prompt crosses chunks, 4 q
    on 2 kv heads of 16 without positions at a scale that is not the
    head's, 8 experts top-3 plus an ungated shared one, the four
    multipliers, tied embeddings."""
    return TransformerConfig(
        vocab_size=vocab_size, hidden_size=64, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=16, intermediate_size=32, rms_eps=1e-5,
        tie_word_embeddings=True, moe=True, num_experts=8,
        num_experts_per_tok=3, moe_intermediate_size=32,
        shared_expert_size=48, shared_expert_gate=False,
        layer_kinds=("mamba", "mamba", "full_attention", "mamba"),
        mamba_num_heads=4, mamba_head_dim=8, mamba_state_dim=128,
        mamba_conv_kernel=4, mamba_chunk_size=8, use_rope=False,
        attention_multiplier=1.0 / 16, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0,
    )


def tiny_factory():
    """model_factory for tests/dry-runs: random-weight tiny Granite-H."""
    cfg = tiny_config()
    return init_params(jax.random.PRNGKey(0), cfg, jnp.float32), cfg, None


def real_factory(model_dir: str, dtype="bfloat16", **kw):
    """Arch-registry front door.  No loader maps the checkpoint's tensor
    names yet (module docstring): said here rather than served wrongly."""
    raise NotImplementedError(
        "GraniteMoeHybridForCausalLM: no checkpoint loader yet; serve "
        "seeded weights through a model_factory (vllm_omni_tpu.models."
        "granite_moe_hybrid:tiny_factory, or the benchmark's "
        "benchmark/archs/granite_moe_hybrid.py)")
