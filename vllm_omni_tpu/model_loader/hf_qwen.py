"""HF-format Qwen2/Qwen3(-MoE) LM checkpoint loading.

Maps HuggingFace transformer weight names onto the functional param tree of
models/common/transformer.py (reference loads these models through vLLM's
loader; omni-side registration at vllm_omni/engine/arg_utils.py:33-43).

Layout conversions:
- HF linears are [out, in]; ours are [in, out] (transpose).
- HF gate_proj/up_proj pairs fuse into our ``gate_up`` [in, 2*inter]
  (silu_mul splits [gate; up] halves, ops/activation.py:13).
- HF per-expert MLPs stack onto the leading E axis of ``experts.gate_up`` /
  ``experts.down`` (the EP shard axis).

Streaming: shards load one at a time into preallocated numpy buffers, so
peak host memory is params + one shard.
"""

from __future__ import annotations

import json
import os
import re
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from vllm_omni_tpu.logger import init_logger
from vllm_omni_tpu.model_loader.safetensors_loader import (
    iter_safetensors,
    np_param_dtype,
)
from vllm_omni_tpu.models.common import transformer as tfm

logger = init_logger(__name__)


def config_from_hf(model_dir: str,
                   hf_config_name: Optional[str] = None) -> tfm.TransformerConfig:
    """Translate an HF config.json into a TransformerConfig.

    ``hf_config_name`` selects a sub-config inside multi-part checkpoints
    (reference: OmniModelConfig.hf_config_name, config/model.py:46-60 —
    e.g. "thinker_config.text_config" for Qwen3-Omni).
    """
    with open(os.path.join(model_dir, "config.json")) as f:
        hf = json.load(f)
    for part in (hf_config_name or "").split("."):
        if part:
            hf = hf[part]
    num_heads = hf["num_attention_heads"]
    moe = "num_experts" in hf or "num_routed_experts" in hf
    model_type = hf.get("model_type", "").lower()
    # Qwen2 family uses q/k/v biases implicitly (no config field); Qwen3
    # exposes attention_bias explicitly (default False)
    attention_bias = hf.get("attention_bias", model_type.startswith("qwen2"))
    return tfm.TransformerConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=num_heads,
        num_kv_heads=hf.get("num_key_value_heads", num_heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // num_heads,
        intermediate_size=hf["intermediate_size"],
        rope_theta=hf.get("rope_theta", 1e6),
        rms_eps=hf.get("rms_norm_eps", 1e-6),
        qk_norm="qwen3" in model_type,
        attention_bias=attention_bias,
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        moe=moe,
        num_experts=hf.get("num_experts", hf.get("num_routed_experts", 8)),
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        moe_intermediate_size=hf.get("moe_intermediate_size", 0),
        moe_renormalize=hf.get("norm_topk_prob", True),
        shared_expert_size=hf.get("shared_expert_intermediate_size", 0)
        if moe else 0,
        # multimodal 3-D RoPE sections (thinker/talker text configs carry
        # rope_scaling.mrope_section; positions then come in [B, 3, S])
        mrope_sections=tuple(
            (hf.get("rope_scaling") or {}).get("mrope_section"))
        if (hf.get("rope_scaling") or {}).get("mrope_section") else None,
        **_hybrid_keys(hf, model_type),
    )


def _hybrid_keys(hf: dict, model_type: str) -> dict:
    """The Qwen3-Next family's extra switches: the layer pattern, the
    Gated DeltaNet head geometry, and what its attention layers and norms
    do differently (models/qwen3_next)."""
    if model_type != "qwen3_next":
        return {}
    return dict(
        full_attention_interval=hf.get("full_attention_interval", 4),
        linear_num_key_heads=hf["linear_num_key_heads"],
        linear_num_value_heads=hf["linear_num_value_heads"],
        linear_key_head_dim=hf["linear_key_head_dim"],
        linear_value_head_dim=hf["linear_value_head_dim"],
        linear_conv_kernel_dim=hf.get("linear_conv_kernel_dim", 4),
        attn_output_gate=True,
        partial_rotary_factor=hf.get("partial_rotary_factor", 0.25),
        norm_zero_centered=True,
        residual_in_fp32=True,
    )


def _alloc_tree(cfg: tfm.TransformerConfig, dtype) -> dict:
    """Numpy buffers shaped like init_params output, without computing
    random values (jax.eval_shape traces the init)."""
    shapes = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    )
    return jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, dtype), shapes
    )


_LAYER_RE = re.compile(
    r"^(?:(?:model|language_model|thinker\.model|talker\.model)\.)?"
    r"layers\.(\d+)\.(.+?)\.(weight|bias)$"
)
# prefix optional: bare backbone checkpoints (e.g. a Qwen3Model saved as
# a diffusion text_encoder) name tensors layers.N... with no model. root
_PREFIX_RE = re.compile(
    r"^(?:model|language_model|thinker\.model|talker\.model)\."
)

_DIRECT = {
    "input_layernorm": ("input_norm", "w", False),
    "post_attention_layernorm": ("post_norm", "w", False),
    "self_attn.q_proj": ("q_proj", "w", True),
    "self_attn.k_proj": ("k_proj", "w", True),
    "self_attn.v_proj": ("v_proj", "w", True),
    "self_attn.o_proj": ("o_proj", "w", True),
    "self_attn.q_norm": ("q_norm", "w", False),
    "self_attn.k_norm": ("k_norm", "w", False),
    "mlp.down_proj": ("down", "w", True),
}

_EXPERT_RE = re.compile(r"^mlp\.experts\.(\d+)\.(gate_proj|up_proj|down_proj)$")
# Gated DeltaNet tensors (Qwen3-Next); A_log and dt_bias carry no
# .weight suffix, so they get a pattern of their own
_GDN_RE = re.compile(
    r"^(?:(?:model|language_model)\.)?layers\.(\d+)\.linear_attn\.(.+)$")


def _load_gdn(la: dict, cfg: tfm.TransformerConfig, sub: str, arr) -> bool:
    """One ``linear_attn.*`` tensor into a layer's ``linear_attn`` tree.

    The checkpoint fuses the input projections and interleaves them by
    KEY head: ``in_proj_qkvz`` holds, sixteen times, the rows
    [q Dk | k Dk | v r*Dv | z r*Dv] of one key head and the r = Hv / Hk
    value heads it serves; ``in_proj_ba`` holds [b r | a r] likewise.
    Ours are apart and flat, in the order the convolution sees them
    ([q | k | v], z; [b | a]), so the forward needs no per-token
    reshuffle."""
    n_k, n_v, d_k, d_v = cfg.gdn_heads
    r = n_v // n_k
    if sub == "in_proj_qkvz.weight":
        w = arr.reshape(n_k, 2 * d_k + 2 * r * d_v, -1)  # [Hk, rows, hidden]
        q, k, v, z = np.split(w, [d_k, 2 * d_k, 2 * d_k + r * d_v], axis=1)
        flat = [a.reshape(-1, a.shape[-1]) for a in (q, k, v, z)]
        la["in_proj_qkv"]["w"][...] = np.concatenate(flat[:3], axis=0).T
        la["in_proj_z"]["w"][...] = flat[3].T
    elif sub == "in_proj_ba.weight":
        w = arr.reshape(n_k, 2 * r, -1)
        la["in_proj_ba"]["w"][...] = np.concatenate(
            [w[:, :r].reshape(n_v, -1), w[:, r:].reshape(n_v, -1)],
            axis=0).T
    elif sub == "conv1d.weight":  # [C, 1, K] -> [K, C]
        la["conv"]["w"][...] = arr[:, 0, :].T
    elif sub in ("A_log", "dt_bias"):
        la[sub][...] = arr
    elif sub == "norm.weight":
        la["norm"]["w"][...] = arr
    elif sub == "out_proj.weight":
        la["out_proj"]["w"][...] = arr.T
    else:
        return False
    return True


def load_qwen_lm(
    model_dir: str,
    cfg: Optional[tfm.TransformerConfig] = None,
    dtype=jnp.bfloat16,
    hf_config_name: Optional[str] = None,
    submodel: Optional[str] = None,
):
    """Load an HF Qwen2/Qwen3(-MoE) checkpoint.

    ``submodel`` restricts loading to one component of a composite
    checkpoint ("thinker" / "talker"): only tensors under that prefix
    are consumed — without it, a full Qwen3-Omni checkpoint would write
    both thinker.model.* and talker.model.* into the same tree.

    Returns (params, cfg, eos_token_id) — the model_factory contract.
    """
    from vllm_omni_tpu.model_loader.hub import resolve_model_path

    model_dir = resolve_model_path(model_dir, submodel=submodel)
    if cfg is None:
        cfg = config_from_hf(model_dir, hf_config_name)
    if isinstance(dtype, str):  # YAML model_factory_args pass strings
        from vllm_omni_tpu.config.model import resolve_dtype

        dtype = resolve_dtype(dtype)
    np_dtype = np_param_dtype(dtype)
    params = _alloc_tree(cfg, np_dtype)
    inter = cfg.moe_intermediate_size or cfg.intermediate_size

    # sibling components of a composite checkpoint that OTHER loaders
    # own — skipped at the shard-key level (never decoded, never counted
    # unmapped) so a correct load stays warning-free
    sibling = (f"{submodel}.code_predictor.", f"{submodel}.audio_tower.",
               f"{submodel}.visual.", f"{submodel}.text_projection.",
               f"{submodel}.hidden_projection.") if submodel else ()

    def keep(name):
        if submodel is None:
            return True
        return name.startswith(f"{submodel}.") \
            and not any(name.startswith(p) for p in sibling)

    loaded, unmapped = 0, []
    for name, arr in iter_safetensors(model_dir, keep):
        if name.startswith("mtp."):
            continue  # the multi-token-prediction module is not served
        g = _GDN_RE.match(name)
        if g:
            li = int(g.group(1))
            la = (params["layers"][li].get("linear_attn")
                  if li < cfg.num_layers else None)
            if la is not None and _load_gdn(la, cfg, g.group(2), arr):
                loaded += 1
            else:
                unmapped.append(name)
            continue
        m = _LAYER_RE.match(name)
        if m:
            li, sub, kind = int(m.group(1)), m.group(2), m.group(3)
            if li >= cfg.num_layers:
                unmapped.append(name)
                continue
            layer = params["layers"][li]
            if kind == "bias":
                key = _DIRECT.get(sub, (None,))[0]
                if key is not None and key in layer and "b" in layer[key]:
                    layer[key]["b"][...] = arr
                    loaded += 1
                else:
                    unmapped.append(name)
                continue
            if sub in _DIRECT:
                key, leaf, transpose = _DIRECT[sub]
                if key not in layer:
                    unmapped.append(name)
                    continue
                layer[key][leaf][...] = arr.T if transpose else arr
                loaded += 1
                continue
            if sub == "mlp.gate_proj":
                layer["gate_up"]["w"][:, : cfg.intermediate_size] = arr.T
                loaded += 1
                continue
            if sub == "mlp.up_proj":
                layer["gate_up"]["w"][:, cfg.intermediate_size:] = arr.T
                loaded += 1
                continue
            if sub == "mlp.gate":  # MoE router [E, hidden]
                layer["router"]["w"][...] = arr.T
                loaded += 1
                continue
            if sub.startswith("mlp.shared_expert") and cfg.moe \
                    and "shared_expert" in layer:
                sse = cfg.shared_expert_size
                if sub == "mlp.shared_expert.gate_proj":
                    layer["shared_expert"]["gate_up"]["w"][:, :sse] = arr.T
                elif sub == "mlp.shared_expert.up_proj":
                    layer["shared_expert"]["gate_up"]["w"][:, sse:] = arr.T
                elif sub == "mlp.shared_expert.down_proj":
                    layer["shared_expert"]["down"]["w"][...] = arr.T
                elif sub == "mlp.shared_expert_gate":
                    layer["shared_gate"]["w"][...] = arr.T
                else:
                    unmapped.append(name)
                    continue
                loaded += 1
                continue
            em = _EXPERT_RE.match(sub)
            if em and cfg.moe:
                e, which = int(em.group(1)), em.group(2)
                if cfg.router_experts:
                    # an expert share holds ids offset .. offset + E only
                    e -= cfg.expert_offset
                    if not 0 <= e < cfg.num_experts:
                        continue
                if which == "gate_proj":
                    layer["experts"]["gate_up"][e, :, :inter] = arr.T
                elif which == "up_proj":
                    layer["experts"]["gate_up"][e, :, inter:] = arr.T
                else:
                    layer["experts"]["down"][e] = arr.T
                loaded += 1
                continue
            unmapped.append(name)
            continue
        stripped = _PREFIX_RE.sub("", name)
        if stripped in ("embed_tokens.weight", "codec_embedding.weight"):
            # codec_embedding: the talker's code-token table
            # (Qwen3OmniMoeTalkerModel)
            params["embed"]["w"][...] = arr  # embeddings stay [vocab, hidden]
            loaded += 1
        elif stripped == "norm.weight":
            params["final_norm"]["w"][...] = arr
            loaded += 1
        elif name in ("lm_head.weight", "thinker.lm_head.weight",
                      "talker.lm_head.weight", "talker.codec_head.weight",
                      "codec_head.weight"):
            if cfg.tie_word_embeddings:
                unmapped.append(name)
            else:
                params["lm_head"]["w"][...] = arr.T
                loaded += 1
        else:
            unmapped.append(name)
    if unmapped:
        logger.warning("unmapped checkpoint tensors (%d): %s%s",
                       len(unmapped), unmapped[:8],
                       "..." if len(unmapped) > 8 else "")
    logger.info("loaded %d tensors from %s", loaded, model_dir)
    params = jax.tree_util.tree_map(jnp.asarray, params)
    eos = _eos_token_id(model_dir)
    return params, cfg, eos


def _eos_token_id(model_dir: str):
    """Primary eos id or the full list (multi-eos checkpoints like Qwen2.5
    stop on any of them — Request.check_stop accepts both forms)."""
    for fn in ("generation_config.json", "config.json"):
        p = os.path.join(model_dir, fn)
        if os.path.isfile(p):
            with open(p) as f:
                eos = json.load(f).get("eos_token_id")
            if isinstance(eos, list):
                return [int(e) for e in eos] if eos else None
            if eos is not None:
                return int(eos)
    return None


# load_qwen_lm already satisfies the stage model_factory contract directly:
#   engine_args:
#     model_factory: "vllm_omni_tpu.model_loader.hf_qwen:load_qwen_lm"
#     model_factory_args: {model_dir: /path/to/checkpoint}
