"""Qwen3-Omni-MoE talker: AR codec-token LM (stage 1).

Reference: vllm_omni/model_executor/models/qwen3_omni/
qwen3_omni_moe_talker.py — a smaller MoE LM that consumes the thinker's
hidden states (projected into its own width) and autoregressively emits
speech-codec tokens; the MTP code predictor
(qwen3_omni_moe_code_predictor_mtp.py) is a later spec-decode extension.

The thinker→talker handoff rides the engine's prompt_embeds path: the
stage input processor packs thinker hidden states as prompt_embeds, and
the transformer's optional ``embed_proj`` adapts thinker width → talker
width (models/common/transformer.py _embed_input).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_omni_tpu.models.common import nn
from vllm_omni_tpu.models.common.transformer import TransformerConfig, init_params

# codec vocabulary (speech tokens); real talker: 32 layers hidden 1024.
QWEN3_OMNI_TALKER_30B = TransformerConfig(
    vocab_size=4096 + 8,  # codec codes + specials
    hidden_size=1024,
    num_layers=20,
    num_heads=16,
    num_kv_heads=4,
    head_dim=128,
    intermediate_size=3072,
    qk_norm=True,
    moe=True,
    num_experts=64,
    num_experts_per_tok=6,
    moe_intermediate_size=384,
)

CODEC_EOS = 4097  # end-of-speech codec token (tiny preset convention)


def tiny_config(codec_vocab: int = 64) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=codec_vocab,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=128,
        moe=True,
        num_experts=4,
        num_experts_per_tok=2,
        moe_intermediate_size=64,
    )


def init_talker_params(key, cfg: TransformerConfig, thinker_hidden: int,
                       dtype=jnp.float32):
    """Talker params = MoE LM + projection from thinker hidden width."""
    params = init_params(key, cfg, dtype)
    params["embed_proj"] = nn.linear_init(
        jax.random.fold_in(key, 99), thinker_hidden, cfg.hidden_size,
        bias=False, dtype=dtype,
    )
    return params


def tiny_factory():
    """model_factory: tiny talker consuming 64-wide thinker states."""
    cfg = tiny_config()
    params = init_talker_params(jax.random.PRNGKey(1), cfg,
                                thinker_hidden=64)
    return params, cfg, None


# ------------------------------------------------------- checkpoint load
def load_talker(model_dir: str, dtype=jnp.bfloat16):
    """Load the ``talker.*`` weights of a Qwen3-Omni checkpoint.

    The talker LM is a Qwen3-MoE with a shared expert
    (norm_topk_prob=False) whose token table is ``codec_embedding`` and
    whose output head is ``codec_head`` — both handled by the shared
    Qwen loader.  On top of it ride two ResizeMLP projections from
    thinker width (transformers Qwen3OmniMoeTalkerResizeMLP):
    ``hidden_projection`` feeds the prompt-embeds path (wired as
    ``embed_proj`` so ``_embed_input`` applies it), ``text_projection``
    is kept for the thinker-text conditioning stream.

    Returns (params, cfg, eos) — the model_factory contract; eos is the
    talker's codec EOS id.
    """
    import json
    import os

    import numpy as np

    from vllm_omni_tpu.model_loader.hf_qwen import (
        config_from_hf,
        load_qwen_lm,
    )
    from vllm_omni_tpu.model_loader.safetensors_loader import (
        load_checkpoint_tree,
        np_param_dtype,
    )

    cfg = config_from_hf(model_dir, "talker_config.text_config")
    params, _, _ = load_qwen_lm(model_dir, cfg=cfg, dtype=dtype,
                                submodel="talker")

    with open(os.path.join(model_dir, "config.json")) as f:
        talker_cfg = json.load(f).get("talker_config", {})
    eos = talker_cfg.get("codec_eos_token_id")
    thinker_hidden = talker_cfg.get("thinker_hidden_size", cfg.hidden_size)

    # second pass: the two thinker-width ResizeMLP projections
    # (decoded selectively — the name_filter skips the rest of the
    # composite checkpoint at the shard-key level)
    want = {}
    for hf_key, ours in (("hidden_projection", "embed_proj"),
                         ("text_projection", "text_proj")):
        for fc in ("fc1", "fc2"):
            for leaf, suffix in (("w", "weight"), ("b", "bias")):
                want[f"talker.{hf_key}.linear_{fc}.{suffix}"] = \
                    (ours, fc, leaf)
    np_dtype = np_param_dtype(dtype)
    inter = cfg.intermediate_size
    proj = {
        key: {"fc1": {"w": np.zeros((thinker_hidden, inter), np_dtype),
                      "b": np.zeros((inter,), np_dtype)},
              "fc2": {"w": np.zeros((inter, cfg.hidden_size), np_dtype),
                      "b": np.zeros((cfg.hidden_size,), np_dtype)}}
        for key in ("embed_proj", "text_proj")
    }
    n, _ = load_checkpoint_tree(
        model_dir, want.get, proj, dtype=np_dtype,
        name_filter=lambda name: name in want,
    )
    if n != len(want):
        raise ValueError(
            f"{model_dir}: talker projections covered {n}/{len(want)} "
            "tensors")
    for key in ("embed_proj", "text_proj"):
        params[key] = jax.tree_util.tree_map(jnp.asarray, proj[key])
    return params, cfg, eos


def project_thinker_text(params, text_embeds):
    """Apply the talker's ``text_projection`` ResizeMLP to thinker text
    embeddings (the conditioning stream the reference sums with the
    projected hidden states, qwen3_omni_moe_talker.py)."""
    p = params["text_proj"]
    return jax.numpy.asarray(
        nn.linear(p["fc2"], jax.nn.silu(nn.linear(p["fc1"], text_embeds))))
