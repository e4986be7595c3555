"""Qwen3-Omni-MoE thinker: the understanding LM (stage 0).

Reference: vllm_omni/model_executor/models/qwen3_omni/
qwen3_omni_moe_thinker.py (MoE backbone qwen3_moe.py; AuT audio encoder and
vision tower are modality front-ends feeding the same LM).  The TPU build
runs the MoE text backbone on the shared functional transformer
(models/common/transformer.py) with qk_norm (Qwen3 style); audio/vision
encoders land as separate encoder modules that prepend embeddings via the
prompt_embeds path.

The thinker's engine runs with ``collect_hidden=True`` so every generated
token's final hidden state ships to the talker stage (reference:
hidden-state slicing into pooler_output, gpu_ar_model_runner.py:525-568).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common.transformer import TransformerConfig, init_params

# Published Qwen3-Omni-30B-A3B-Instruct thinker geometry
# (thinker_config.text_config of the HF config.json): hidden 2048, 48
# layers, 32 heads / 4 kv, head_dim 128, 128 experts top-8,
# moe_intermediate 768, vocabulary 152064.
QWEN3_OMNI_THINKER_30B = TransformerConfig(
    vocab_size=152064,
    hidden_size=2048,
    num_layers=48,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    intermediate_size=768,
    qk_norm=True,
    moe=True,
    num_experts=128,
    num_experts_per_tok=8,
    moe_intermediate_size=768,
    # multimodal 3D-RoPE splits of head_dim//2 = 64 (t/h/w), the Qwen-Omni
    # mrope_section from the HF config (reference: mrope.py:25 usage)
    mrope_sections=(24, 20, 20),
)


def tiny_config(vocab_size: int = 128) -> TransformerConfig:
    import dataclasses

    # head_dim 16 -> half 8 -> (4, 2, 2) mrope splits
    return dataclasses.replace(
        TransformerConfig.tiny_moe(vocab_size), mrope_sections=(4, 2, 2)
    )


def tiny_factory():
    """model_factory for tests/dry-runs: random-weight tiny MoE thinker."""
    cfg = tiny_config()
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return params, cfg, None


def real_factory(model_dir: str, dtype="bfloat16", **kw):
    """Arch-registry front door: load the REAL thinker LM from a
    Qwen3-Omni checkpoint directory (the same loader the family's stage
    YAML names, stage_configs/qwen3_omni_moe.yaml:11-16)."""
    from vllm_omni_tpu.model_loader.hf_qwen import load_qwen_lm

    return load_qwen_lm(
        model_dir, dtype=dtype,
        hf_config_name="thinker_config.text_config",
        submodel="thinker", **kw)
