"""Model registries.

``DiffusionModelRegistry`` mirrors the reference's lazy arch->pipeline map
(vllm_omni/diffusion/registry.py:16-102, 17 pipelines); ``OmniModelRegistry``
mirrors the AR model registry (model_executor/models/registry.py:65).
Builders are lazy import paths so importing the registry stays light.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Any, Callable, Optional

from vllm_omni_tpu.logger import init_logger

logger = init_logger(__name__)


@dataclass
class _Entry:
    module: str
    attr: str

    def load(self):
        return getattr(importlib.import_module(self.module), self.attr)


# arch name (as appears in model_index.json `_class_name` for diffusers
# checkpoints) -> pipeline class
_DIFFUSION_MODELS: dict[str, _Entry] = {
    "QwenImagePipeline": _Entry(
        "vllm_omni_tpu.models.qwen_image.pipeline", "QwenImagePipeline"
    ),
    # image editing: input image VAE-encoded and appended to the token
    # sequence (reference: pipeline_qwen_image_edit.py:218 /
    # pipeline_qwen_image_edit_plus.py)
    "QwenImageEditPipeline": _Entry(
        "vllm_omni_tpu.models.qwen_image.edit_pipeline",
        "QwenImageEditPipeline"
    ),
    "QwenImageEditPlusPipeline": _Entry(
        "vllm_omni_tpu.models.qwen_image.edit_pipeline",
        "QwenImageEditPlusPipeline"
    ),
    # composite + N layers denoised jointly on the rope frame axis
    # (reference: pipeline_qwen_image_layered.py)
    "QwenImageLayeredPipeline": _Entry(
        "vllm_omni_tpu.models.qwen_image.layered_pipeline",
        "QwenImageLayeredPipeline"
    ),
    # video (reference: Wan2.2 T2V family, diffusion/registry.py:16-102)
    "WanPipeline": _Entry(
        "vllm_omni_tpu.models.wan.pipeline", "WanT2VPipeline"
    ),
    "WanT2VPipeline": _Entry(
        "vllm_omni_tpu.models.wan.pipeline", "WanT2VPipeline"
    ),
    # image(+text)-to-video: first frame anchored via VAE-latent + mask
    # conditioning channels (reference: WanImageToVideoPipeline /
    # Wan2.2 TI2V, diffusion/registry.py:16-102)
    "WanImageToVideoPipeline": _Entry(
        "vllm_omni_tpu.models.wan.pipeline", "WanI2VPipeline"
    ),
    "WanI2VPipeline": _Entry(
        "vllm_omni_tpu.models.wan.pipeline", "WanI2VPipeline"
    ),
    "WanTI2VPipeline": _Entry(
        "vllm_omni_tpu.models.wan.pipeline", "WanI2VPipeline"
    ),
    # joint-attention MMDiT siblings (reference: FluxPipeline / SD3,
    # diffusion/registry.py:16-102) — one shared MMDiT block implementation
    "FluxPipeline": _Entry(
        "vllm_omni_tpu.models.flux.pipeline", "FluxPipeline"
    ),
    "StableDiffusion3Pipeline": _Entry(
        "vllm_omni_tpu.models.sd3.pipeline", "SD3Pipeline"
    ),
    "SD3Pipeline": _Entry(
        "vllm_omni_tpu.models.sd3.pipeline", "SD3Pipeline"
    ),
    # audio (reference: StableAudio family)
    "StableAudioPipeline": _Entry(
        "vllm_omni_tpu.models.stable_audio.pipeline", "StableAudioPipeline"
    ),
    # unified-sequence single-stream DiT (reference: z_image/
    # pipeline_z_image.py)
    "ZImagePipeline": _Entry(
        "vllm_omni_tpu.models.z_image.pipeline", "ZImagePipeline"
    ),
    # Flux-geometry MMDiT with true CFG + renorm (reference:
    # longcat_image/pipeline_longcat_image.py:202)
    "LongCatImagePipeline": _Entry(
        "vllm_omni_tpu.models.longcat_image.pipeline",
        "LongCatImagePipeline"
    ),
    "LongCatImageEditPipeline": _Entry(
        "vllm_omni_tpu.models.longcat_image.pipeline",
        "LongCatImageEditPipeline"
    ),
    # AR+diffusion hybrid: the MoT LLM runs the flow itself (reference:
    # bagel/pipeline_bagel.py:153)
    "BagelPipeline": _Entry(
        "vllm_omni_tpu.models.bagel.pipeline", "BagelPipeline"
    ),
    # the published repo declares this arch in config.json (reference:
    # omni_diffusion.py:79 routes it to BagelPipeline)
    "BagelForConditionalGeneration": _Entry(
        "vllm_omni_tpu.models.bagel.pipeline", "BagelPipeline"
    ),
    # unified causal MM generator, shared single stack (reference:
    # hunyuan_image_3/pipeline_hunyuan_image_3.py:65)
    "HunyuanImage3ForCausalMM": _Entry(
        "vllm_omni_tpu.models.hunyuan_image_3.pipeline",
        "HunyuanImage3Pipeline"
    ),
    # AR-prior + DiT two-model generation (reference:
    # glm_image/pipeline_glm_image.py:247-255)
    "GlmImagePipeline": _Entry(
        "vllm_omni_tpu.models.glm_image.pipeline", "GlmImagePipeline"
    ),
    # Flux-architecture variants over the shared MMDiT (reference:
    # ovis_image/, flux2_klein/)
    "OvisImagePipeline": _Entry(
        "vllm_omni_tpu.models.ovis_image.pipeline", "OvisImagePipeline"
    ),
    "Flux2KleinPipeline": _Entry(
        "vllm_omni_tpu.models.flux2_klein.pipeline", "Flux2KleinPipeline"
    ),
}

# AR architectures -> the family's entry-stage (thinker/LM) REAL
# checkpoint factory.  Stage YAMLs address stages by explicit
# `model_factory` strings; this registry is the arch-name front door
# (reference: model_executor/models/registry.py:65 — e.g.
# Qwen3OmniMoeForConditionalGeneration): resolve(arch) returns a
# callable (model_dir, **kw) -> (params, TransformerConfig,
# eos_token_id) that LOADS the checkpoint — never a random-init toy
# (tiny factories stay reachable only via their explicit module paths,
# e.g. "...thinker:tiny_factory").  Downstream stages
# (talker/code2wav/...) stay per-stage factories in the family's stage
# YAML.
_AR_MODELS: dict[str, _Entry] = {
    "Qwen3OmniMoeForConditionalGeneration": _Entry(
        "vllm_omni_tpu.models.qwen3_omni.thinker", "real_factory"
    ),
    "Qwen2_5OmniForConditionalGeneration": _Entry(
        "vllm_omni_tpu.models.qwen2_5_omni.thinker", "real_factory"
    ),
    "Qwen2_5OmniModel": _Entry(
        "vllm_omni_tpu.models.qwen2_5_omni.thinker", "real_factory"
    ),
    "Qwen3TTSForConditionalGeneration": _Entry(
        "vllm_omni_tpu.models.qwen3_tts.tts_lm", "real_factory"
    ),
    # plain Qwen LMs serve through the same engine (single-stage llm)
    "Qwen2ForCausalLM": _Entry(
        "vllm_omni_tpu.model_loader.hf_qwen", "load_qwen_lm"
    ),
    "Qwen3ForCausalLM": _Entry(
        "vllm_omni_tpu.model_loader.hf_qwen", "load_qwen_lm"
    ),
    "Qwen3MoeForCausalLM": _Entry(
        "vllm_omni_tpu.model_loader.hf_qwen", "load_qwen_lm"
    ),
    # hybrid Gated-DeltaNet / gated-attention stack with a state cache
    # beside the paged KV cache (docs/kv_cache.md)
    "Qwen3NextForCausalLM": _Entry(
        "vllm_omni_tpu.models.qwen3_next", "real_factory"
    ),
    # multi-head latent attention over a latent paged cache, a leading
    # dense layer, sigmoid-routed experts (GLM-4.7-Flash; docs/kv_cache.md)
    "Glm4MoeLiteForCausalLM": _Entry(
        "vllm_omni_tpu.models.glm4_moe_lite", "real_factory"
    ),
    # latent attention of two kinds (a learned selection on the full
    # layers, a window on the others) over two kinds of page
    # (dots3-note-prev; docs/kv_cache.md)
    "Dots3NoteForCausalLM": _Entry(
        "vllm_omni_tpu.models.dots3_note", "real_factory"
    ),
    # gated short convolutions (a state slot of the taps alone) beside
    # grouped-query attention of 64-wide heads, two to a row of the pool
    # (LFM2-8B-A1B; docs/kv_cache.md)
    "Lfm2MoeForCausalLM": _Entry(
        "vllm_omni_tpu.models.lfm2_moe", "real_factory"
    ),
    # Mamba-2 layers whose state is updated in its slot, attention without
    # positions on one layer in ten, four multipliers
    # (granite-4.0-h-small; docs/kv_cache.md)
    "GraniteMoeHybridForCausalLM": _Entry(
        "vllm_omni_tpu.models.granite_moe_hybrid", "real_factory"
    ),
    # layers of ONE sublayer (a Mamba-2 mixer with eight groups of B and C,
    # attention without positions, or ungated latent experts alone)
    # (NVIDIA-Nemotron-3-Super-120B-A12B; docs/kv_cache.md)
    "NemotronHForCausalLM": _Entry(
        "vllm_omni_tpu.models.nemotron_h", "real_factory"
    ),
    # a parallel block under one LayerNorm, a window over per-head (K, V)
    # pages on three layers in four, rotary a kind of layer, four shared
    # experts averaged (command-a-plus-05-2026; docs/kv_cache.md)
    "Cohere2MoeForCausalLM": _Entry(
        "vllm_omni_tpu.models.cohere2_moe", "real_factory"
    ),
    # five 128-key window layers with a learned sink in their softmax to
    # one full layer, keys of 192 beside values of 128, kv heads and a
    # rotary base a kind of layer (MiMo-V2.5; docs/kv_cache.md)
    "MiMoV2ForCausalLM": _Entry(
        "vllm_omni_tpu.models.mimo_v2", "real_factory"
    ),
}


class DiffusionModelRegistry:
    @staticmethod
    def register(arch: str, module: str, attr: str) -> None:
        _DIFFUSION_MODELS[arch] = _Entry(module, attr)

    @staticmethod
    def resolve(arch: str):
        if arch not in _DIFFUSION_MODELS:
            raise KeyError(
                f"unknown diffusion architecture {arch!r}; known: "
                f"{sorted(_DIFFUSION_MODELS)}"
            )
        return _DIFFUSION_MODELS[arch].load()

    @staticmethod
    def supported() -> list[str]:
        return sorted(_DIFFUSION_MODELS)


class OmniModelRegistry:
    @staticmethod
    def register(arch: str, module: str, attr: str) -> None:
        _AR_MODELS[arch] = _Entry(module, attr)

    @staticmethod
    def resolve(arch: str):
        if arch not in _AR_MODELS:
            raise KeyError(
                f"unknown AR architecture {arch!r}; known: {sorted(_AR_MODELS)}"
            )
        return _AR_MODELS[arch].load()

    @staticmethod
    def supported() -> list[str]:
        return sorted(_AR_MODELS)
