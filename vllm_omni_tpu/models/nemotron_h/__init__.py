"""NVIDIA-Nemotron-3-Super-120B-A12B (``model_type`` ``nemotron_h``, 120B
parameters, about 12B active): 88 layers that are each ONE sublayer, a
Mamba-2 mixer (40), grouped-query attention without positional encoding
(8) or a layer of latent experts (40); the experts are ungated
(``down(relu(up(x))^2)``), work in a 1024-wide latent a quarter of the
model's width, and are picked 22 of 512 by a sigmoid router with a
correction bias and a scale of 5; Mamba-2 has eight groups of B and C.

Reference: the model's public ``config.json``
(``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``).  ``x_0 = E[ids]``; for
each letter of ``hybrid_override_pattern`` ``x += f(rms(x))``, one norm and
one branch a layer, plain norm weights, eps 1e-5; after the last layer a
norm, then the head (untied).

- ``M``: ``[z | xBC | dt] = h W_in`` (8192 | 10 240 | 128); ``xBC =
  silu(conv_4(xBC) + bias)``; ``u`` [128 heads, 64], ``B`` and ``C`` [8
  groups, 128]; head ``h`` reads group ``h // 16``; the gate ``silu(z)``,
  then an RMSNorm over each group's 1024 channels; ``W_out``.
- ``*``: 32 query heads on 2 key-value heads of 128, nothing rotated
  (``rope_theta`` and ``partial_rotary_factor`` are in the file and read by
  nothing in the family's code), scale ``128 ** -0.5``.
- ``E``: ``s = sigmoid(h W_g)`` over 512 in float32; the 22 largest of ``s
  + e_score_correction_bias`` are picked, weighted ``5 s_i / sum``; ``l = h
  W_1`` (4096 -> 1024); ``r = sum_i w_i D_i relu(U_i l)^2``; ``out = r W_2
  + D_s relu(U_s h)^2`` with the shared expert (5376 wide) on ``h`` itself.

The TPU build runs it on the shared functional transformer
(models/common/transformer.py).  A layer of one sublayer is a layer whose
weights hold one half of the block (``input_norm`` and a mixer, or
``post_norm`` and an MLP; kind ``"feed_forward"`` for the latter), and
such a layer holds no pages, no state slot and no stand-in for what it
lacks.  The Mamba-2 layers are ``state_layers._mamba_mixer`` over
``ops/ssd.py`` with a group axis on B and C, their state in the engine's
slots as granite-4.0-h-small's (docs/kv_cache.md, "Four kinds of state");
the experts are ``ops/moe.py``'s dispatch told ``act`` (ungated) and
``route_on`` (the router reads the model's row, the experts its latent),
between ``fc1_latent_proj`` and ``fc2_latent_proj`` under the scope
``omni.moe_latent``.

Not served, and refused or absent by name:

- what the engine refuses for any model with state slots (offload,
  transfer, speculation, an int8 cache, tensor parallelism) and a prefix
  hit, one by one (docs/kv_cache.md);
- the multi-token-prediction module (``num_nextn_predict_layers`` 1,
  pattern ``*E``): a step that yields more than one token is not built
  for a model with state slots;
- a limit on the time step (the config has ``time_step_min`` /
  ``time_step_max`` / ``time_step_floor``, which the family's code reads
  for the initialiser; no clamp is applied in the forward here), a dense
  MLP layer (``-`` in the pattern), biases other than the convolution's;
- expert parallelism across chips: ungated or latent experts ride one
  chip, whole or as an expert share (``expert_share``);
- a loader for the checkpoint's tensor names: there is nothing here to
  test one on.  The weight tree keeps the checkpoint's names without their
  suffixes; a block's one ``norm`` is ``input_norm`` before a mixer and
  ``post_norm`` before the experts, its ``mixer`` the kind's own key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common.transformer import (
    FEED_FORWARD,
    TransformerConfig,
    init_params,
)
from vllm_omni_tpu.models.qwen3_next import expert_share  # noqa: F401

#: the letters of ``hybrid_override_pattern`` -> the program's kinds
PATTERN_KINDS = {"M": "mamba", "*": "full_attention", "E": FEED_FORWARD}

#: the published pattern: 40 Mamba-2, 8 attention, 40 expert layers
NEMOTRON_3_SUPER_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def layer_kinds(pattern: str) -> tuple[str, ...]:
    """``hybrid_override_pattern`` -> ``TransformerConfig.layer_kinds``."""
    unknown = sorted(set(pattern) - set(PATTERN_KINDS))
    if unknown:
        raise ValueError(f"hybrid_override_pattern letters {unknown} are "
                         f"not built (only {sorted(PATTERN_KINDS)})")
    return tuple(PATTERN_KINDS[c] for c in pattern)


# Published geometry (config.json): hidden 4096; attention 32 q / 2 kv heads
# x 128, no positions; Mamba-2 128 heads x 64 on a state of 128, 8 groups, 4
# taps with a bias, chunk 128; 512 experts x 2688 in a latent of 1024,
# top-22 by sigmoid score plus correction bias, renormalised, x 5, and one
# shared expert of 5376, all ungated relu^2; vocabulary 131072, untied;
# eps 1e-5.
NEMOTRON_3_SUPER_120B_A12B = TransformerConfig(
    vocab_size=131072,
    hidden_size=4096,
    num_layers=88,
    num_heads=32,
    num_kv_heads=2,
    head_dim=128,
    intermediate_size=2688,
    rope_theta=10000.0,
    rms_eps=1e-5,
    tie_word_embeddings=False,
    moe=True,
    num_experts=512,
    num_experts_per_tok=22,
    moe_intermediate_size=2688,
    moe_renormalize=True,
    shared_expert_size=5376,
    shared_expert_gate=False,
    router_scoring="sigmoid",
    router_bias=True,
    routed_scaling_factor=5.0,
    mlp_gated=False,
    mlp_activation="relu2",
    moe_latent_size=1024,
    layer_kinds=layer_kinds(NEMOTRON_3_SUPER_PATTERN),
    mamba_num_heads=128,
    mamba_head_dim=64,
    mamba_state_dim=128,
    mamba_conv_kernel=4,
    mamba_chunk_size=128,
    mamba_n_groups=8,
    use_rope=False,
)


def tiny_config(vocab_size: int = 128) -> TransformerConfig:
    """Tiny widths that keep what the published model forces: the pattern
    ``M E * E M E`` (every layer one sublayer), 8 Mamba-2 heads of 8 in 4
    groups of B and C on a state of 128 (the kernel's lane width; two heads
    a group, the gated norm 16 channels at a time) with a chunk of 8 tokens
    so that a prompt crosses chunks, 4 q on 1 kv head of 16 without
    positions, 8 ungated relu^2 experts of 24 in a latent of 32 (half the
    model's 64) top-3 by sigmoid score plus bias times 2.5, an ungated
    shared expert of 48, an untied head."""
    return TransformerConfig(
        vocab_size=vocab_size, hidden_size=64, num_layers=6, num_heads=4,
        num_kv_heads=1, head_dim=16, intermediate_size=24, rms_eps=1e-5,
        tie_word_embeddings=False, moe=True, num_experts=8,
        num_experts_per_tok=3, moe_intermediate_size=24,
        shared_expert_size=48, shared_expert_gate=False,
        router_scoring="sigmoid", router_bias=True,
        routed_scaling_factor=2.5, mlp_gated=False, mlp_activation="relu2",
        moe_latent_size=32, layer_kinds=layer_kinds("ME*EME"),
        mamba_num_heads=8, mamba_head_dim=8, mamba_state_dim=128,
        mamba_conv_kernel=4, mamba_chunk_size=8, mamba_n_groups=4,
        use_rope=False,
    )


def tiny_factory():
    """model_factory for tests/dry-runs: random-weight tiny Nemotron-H."""
    cfg = tiny_config()
    return init_params(jax.random.PRNGKey(0), cfg, jnp.float32), cfg, None


def real_factory(model_dir: str, dtype="bfloat16", **kw):
    """Arch-registry front door.  No loader maps the checkpoint's tensor
    names yet (module docstring): said here rather than served wrongly."""
    raise NotImplementedError(
        "NemotronHForCausalLM: no checkpoint loader yet; serve seeded "
        "weights through a model_factory (vllm_omni_tpu.models."
        "nemotron_h:tiny_factory, or the benchmark's "
        "benchmark/archs/nemotron_h.py)")
