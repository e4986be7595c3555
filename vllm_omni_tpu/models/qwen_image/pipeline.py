"""Qwen-Image text->image pipeline (TPU-native).

Role of the reference's ``QwenImagePipeline``
(vllm_omni/diffusion/models/qwen_image/pipeline_qwen_image.py:241,539-722):
encode_prompt (text-encoder hidden states) -> prepare latents/timesteps
(FlowMatch) -> denoise loop (CFG + MMDiT) -> VAE decode.

TPU-first: the whole denoise loop is ONE jitted computation
(lax.fori_loop over steps — no per-step Python dispatch, no CUDA-graph
machinery); CFG runs as a doubled batch (or, distributed, over the ``cfg``
mesh axis); shapes are static per (H, W) geometry — the step count is a
dynamic loop bound over a padded schedule, so XLA caches one executable
per resolution regardless of num_inference_steps.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from vllm_omni_tpu.diffusion import cache as step_cache
from vllm_omni_tpu.diffusion import scheduler as fm
from vllm_omni_tpu.diffusion.request import (
    DiffusionOutput,
    InvalidRequestError,
    OmniDiffusionRequest,
)
from vllm_omni_tpu.logger import init_logger
from vllm_omni_tpu.models.common import causal_vae as vae_mod
from vllm_omni_tpu.models.common.causal_vae import CausalVAEConfig
from vllm_omni_tpu.models.common.transformer import (
    TransformerConfig,
    forward_hidden,
    init_params as init_text_params,
)
from vllm_omni_tpu.models.qwen_image import transformer as dit
from vllm_omni_tpu.models.qwen_image.transformer import QwenImageDiTConfig
from vllm_omni_tpu.utils.tokenizer import ByteTokenizer

logger = init_logger(__name__)


@dataclass(frozen=True)
class QwenImagePipelineConfig:
    dit: QwenImageDiTConfig = field(default_factory=QwenImageDiTConfig)
    vae: CausalVAEConfig = field(
        default_factory=CausalVAEConfig.qwen_image)
    text: TransformerConfig = field(default_factory=TransformerConfig)
    max_text_len: int = 128
    shift: float = 1.0
    use_dynamic_shifting: bool = True
    # "euler" | "unipc" (order-2 multistep, diffusion/scheduler.py)
    scheduler: str = "euler"
    # Schedule arrays are padded to this bucket so the step count is a
    # *dynamic* fori_loop bound: XLA compiles one executable per (H, W)
    # geometry, not per step count, and a 1-step warmup warms the same
    # executable that 50-step requests hit.
    steps_bucket: int = 64

    @staticmethod
    def tiny() -> "QwenImagePipelineConfig":
        return QwenImagePipelineConfig(
            dit=QwenImageDiTConfig.tiny(),
            vae=CausalVAEConfig.tiny(),
            text=TransformerConfig.tiny(vocab_size=512),
            max_text_len=32,
        )

    @staticmethod
    def bench() -> "QwenImagePipelineConfig":
        """Single-chip bench scale (fits one v5e with bf16 weights)."""
        return QwenImagePipelineConfig(
            dit=QwenImageDiTConfig(
                num_layers=16, num_heads=16, head_dim=128, joint_dim=1024
            ),
            vae=CausalVAEConfig(base_dim=64),
            text=TransformerConfig(
                vocab_size=512,
                hidden_size=1024,
                num_layers=8,
                num_heads=8,
                num_kv_heads=4,
                head_dim=128,
                intermediate_size=2816,
            ),
        )

    @staticmethod
    def resident() -> "QwenImagePipelineConfig":
        """Real Qwen-Image BLOCK geometry (joint 3584 / 24 heads / the
        MXU shapes that set the perf ceiling) with the layer count
        auto-sized to what fits the attached chip's HBM resident in
        bf16 — 60 (the full model) on large-HBM parts, ~18 on a 16 GB
        v5e.  The honest single-chip bench preset: per-layer timing is
        identical to the full model, only the layer count is reduced
        (and reported).  The text encoder keeps the real 3584 width
        (joint-attention parity) at a reduced depth — text encode is a
        one-shot cost outside the denoise loop."""
        import dataclasses

        one_layer = jax.eval_shape(lambda: dit.init_params(
            jax.random.PRNGKey(0),
            dataclasses.replace(QwenImageDiTConfig(), num_layers=1),
            jnp.bfloat16))
        per_block_bytes = 2 * sum(
            int(np.prod(leaf.shape))
            for leaf in jax.tree.leaves(one_layer["blocks"]))
        try:
            from vllm_omni_tpu.platforms import current_platform

            hbm = current_platform().hbm_bytes() or 16e9
        except Exception:
            hbm = 16e9
        # reserve for activations @1024px, VAE (fp32), the text stack,
        # and compiled-executable scratch
        budget = max(hbm - 5e9, per_block_bytes * 2)
        layers = int(min(60, max(2, budget // per_block_bytes)))
        return QwenImagePipelineConfig(
            dit=QwenImageDiTConfig(num_layers=layers),
            vae=CausalVAEConfig.qwen_image(),
            text=TransformerConfig(
                vocab_size=512,
                hidden_size=3584,
                num_layers=4,
                num_heads=28,
                num_kv_heads=4,
                head_dim=128,
                intermediate_size=18944,
            ),
        )

    @staticmethod
    def real_q() -> "QwenImagePipelineConfig":
        """Real Qwen-Image DiT geometry (full 60 layers / 24 heads /
        3584 — the 20.4B-param transformer that sets the headline
        number) with the ``resident()`` lite text stack (real 3584
        width at reduced depth; text encode is a one-shot cost outside
        the denoise loop).  Built with ``quantize_init='int4'`` the DiT
        packs to 10.3 GB and the FULL depth sits resident in one 16 GB
        chip's HBM — the honest single-chip route to a measured (not
        extrapolated) 60-layer number when host->HBM bandwidth can't
        sustain layerwise streaming."""
        return QwenImagePipelineConfig(
            dit=QwenImageDiTConfig(),
            vae=CausalVAEConfig.qwen_image(),
            text=TransformerConfig(
                vocab_size=512,
                hidden_size=3584,
                num_layers=4,
                num_heads=28,
                num_kv_heads=4,
                head_dim=128,
                intermediate_size=18944,
            ),
        )

    @staticmethod
    def real() -> "QwenImagePipelineConfig":
        """The REAL Qwen-Image geometry (reference:
        transformer config.json — 60 layers / 24 heads / joint 3584;
        Qwen2.5-VL-7B text encoder; 8x causal VAE).  20.4B-param DiT:
        doesn't fit one v5e chip resident — run with TP over a mesh or
        layerwise weight streaming (``ops/offload.py``)."""
        return QwenImagePipelineConfig(
            dit=QwenImageDiTConfig(),
            vae=CausalVAEConfig.qwen_image(),
            text=TransformerConfig(
                vocab_size=152064,
                hidden_size=3584,
                num_layers=28,
                num_heads=28,
                num_kv_heads=4,
                head_dim=128,
                intermediate_size=18944,
            ),
            max_text_len=512,
            use_dynamic_shifting=True,
        )


# Text-encoder chat template + drop index for Qwen-Image (reference:
# pipeline_qwen_image.py:293-294 — the first 34 tokens are the fixed
# system/user preamble and are dropped from the embeddings).
PROMPT_TEMPLATE = (
    "<|im_start|>system\nDescribe the image by detailing the color, shape, "
    "size, texture, quantity, text, spatial relationships of the objects "
    "and background:<|im_end|>\n<|im_start|>user\n{}<|im_end|>\n"
    "<|im_start|>assistant\n"
)
PROMPT_TEMPLATE_DROP_IDX = 34


@jax.jit
def _rel_drift(lat, prev):
    """TeaCache drift gate for the streamed (host-loop) denoise: one
    fused scalar => ONE host sync per step (module-level jit so the
    executable compiles once per process, not once per image)."""
    diff = jnp.mean(jnp.abs(lat.astype(jnp.float32) - prev))
    base = jnp.mean(jnp.abs(prev))
    return diff / jnp.maximum(base, 1e-8)


class QwenImagePipeline:
    """Text -> image.  Weights are random-initialized from the config, or
    loaded from a diffusers-format checkpoint via ``from_pretrained``."""

    output_type = "image"
    # Edit pipelines condition on VAE-encoded input images, so their VAE
    # keeps the encoder half.
    needs_vae_encoder = False

    def __init__(
        self,
        config: QwenImagePipelineConfig,
        dtype=jnp.bfloat16,
        seed: int = 0,
        mesh=None,
        cache_config=None,  # StepCacheConfig | None (step-skip acceleration)
        init_weights: bool = True,
        offload: str = "",  # "" | "layerwise" (weights stream from host)
        quantize_init: str = "",  # "" | "int8" | "fp8" | "int4"
        step_loop: str = "device",  # "device" (fori_loop) | "host"
        step_chunk: int = 1,  # denoise steps per device call (host loop)
    ):
        from vllm_omni_tpu.parallel.pipeline_mesh import MeshWiring

        self.cfg = config
        self.dtype = dtype
        self.mesh = mesh
        self.wiring = MeshWiring(mesh, type(self).__name__).validate(
            {"dp", "cfg", "ring", "ulysses", "tp", "pp"})
        if self.wiring.size("pp") > 1 and len(self.wiring.active) > 1:
            raise ValueError(
                "pp composes with no other axis yet — rebuild the mesh "
                f"with pp alone (active: {sorted(self.wiring.active)})")
        if (self.wiring.size("pp") > 1 and cache_config is not None
                and getattr(cache_config, "backend", "") == "dbcache"):
            raise ValueError(
                "dbcache is not wired into the pp denoise path yet — "
                "use teacache or pp without a step cache")
        self.cache_config = cache_config
        self.offload = offload
        if offload not in ("", "layerwise"):
            raise ValueError(f"unknown offload mode {offload!r}")
        self.step_loop = step_loop
        self.step_chunk = int(step_chunk)
        if step_loop not in ("device", "host"):
            raise ValueError(f"unknown step_loop mode {step_loop!r}")
        if self.step_chunk < 1:
            raise ValueError(f"step_chunk must be >=1, got {step_chunk}")
        if step_loop == "host":
            # A CHUNK of jitted denoise steps per device call instead of
            # the whole loop in one call (a 60-layer 50-step execution
            # runs minutes): the host regains control every step_chunk
            # steps.  Off by default; nothing on a locally attached chip
            # is known to need it (CHANGES.md PR 21).
            # Same executable for every chunk size — num_steps is a
            # traced scalar, the schedule is rolled to the chunk start.
            if mesh is not None:
                raise ValueError("step_loop='host' is single-device")
            if offload == "layerwise":
                raise ValueError(
                    "layerwise offload already drives a host loop")
            # step caches DO work here: the cache carry (skip state,
            # Taylor anchors, drift accumulator) threads through each
            # chunked device call explicitly (cache.run_denoise_loop
            # carry_in/return_carry), with cache decisions indexed by
            # the GLOBAL step — identical skips to one uninterrupted
            # device loop.
            if config.scheduler != "euler":
                raise ValueError(
                    "step_loop='host' supports the euler solver only "
                    "(multistep solvers carry state across the calls)")
        if offload == "layerwise":
            # Streaming drives a Python block loop on ONE device; the
            # multi-chip answer to big models is TP over a mesh instead.
            if mesh is not None:
                raise ValueError("layerwise offload is single-device; "
                                 "use mesh TP for multi-chip")
            if cache_config is not None and cache_config.backend not in (
                    "", "teacache"):
                # teacache's whole-model skip maps cleanly onto the host
                # block-walk (a skipped step saves the full weight
                # transfer); dbcache's split eval does not
                raise ValueError("layerwise offload supports the "
                                 "teacache step cache only")
            if config.scheduler != "euler":
                raise ValueError("layerwise offload supports the euler "
                                 "solver only")
        if config.text.hidden_size != config.dit.joint_dim:
            raise ValueError(
                "text hidden_size must equal dit joint_dim "
                f"({config.text.hidden_size} != {config.dit.joint_dim})"
            )
        self.tokenizer = ByteTokenizer(config.text.vocab_size)
        key = jax.random.PRNGKey(seed)
        k1, k2, k3 = jax.random.split(key, 3)
        # Decoder-only VAE for text->image (edit pipelines add the
        # encoder); fp32 regardless of model dtype — the 127M-param VAE
        # is not the memory story and bf16 visibly banding-artifacts the
        # decoded image.  DiT/text skip init when a checkpoint will
        # overwrite them (init_weights=False avoids materializing +
        # placing tens of GB of randoms only to discard them).
        self.vae_params = self._place(vae_mod.init_params(
            k3, config.vae, jnp.float32, encoder=self.needs_vae_encoder))
        if init_weights and offload == "layerwise":
            from vllm_omni_tpu.diffusion import offload as ol

            logger.info("Host-init for layerwise streaming (dtype=%s)",
                        dtype)
            # repeated blocks alias a few distinct host buffers: the
            # streamed transfer volume is identical, and materializing
            # 50+ GB of distinct randoms first-touch-faults for minutes
            # on sandboxed hosts (real checkpoints take the loader path)
            self.text_params = ol.host_tiled_init_aliased(
                jax.eval_shape(
                    lambda: init_text_params(k1, config.text, dtype)),
                dtype, block_key="layers", seed=seed + 1)
            self.dit_params = ol.host_tiled_init_aliased(
                jax.eval_shape(
                    lambda: dit.init_params(k2, config.dit, dtype)),
                dtype, block_key="blocks", seed=seed + 2)
        elif init_weights and quantize_init:
            # Quantize each DiT block as it is initialized: peak device
            # memory is the quantized tree plus ONE transient bf16 block,
            # so a model whose float tree exceeds HBM (real Qwen-Image:
            # 41 GB bf16 vs 16 GB v5e) still builds quantized-resident
            # (int4 -> 10.3 GB).  Mesh placement would need sharded
            # per-block quantization — single-device only for now.
            if mesh is not None:
                raise ValueError(
                    "quantize_init is single-device; quantize after "
                    "sharded init (engine post-hoc path) instead")
            logger.info(
                "Initializing QwenImagePipeline params (dtype=%s, "
                "blockwise %s quantization)", dtype, quantize_init)
            self.text_params = self._place(
                init_text_params(k1, config.text, dtype))
            self.dit_params = self._init_dit_quantized(
                k2, quantize_init)
        elif init_weights:
            logger.info(
                "Initializing QwenImagePipeline params (dtype=%s)", dtype)
            self.text_params = self._place(
                init_text_params(k1, config.text, dtype))
            self.dit_params = self._place(
                dit.init_params(k2, config.dit, dtype), tp=True)
        else:
            self.text_params = self.dit_params = None
        self._denoise_cache: dict = {}
        # HF text-encode mode (from_pretrained): chat template + drop_idx
        self.hf_tokenizer = None

    def _place(self, params, tp: bool = False):
        """Put a param tree on the mesh: TP layout for the DiT, replicated
        otherwise (reference: SP plan application at model init,
        diffusion/registry.py:122-294).  Without a mesh, commit to the
        default device once — leaving loader numpy trees uncommitted would
        re-transfer the weights on every jit call.

        Under pipeline parallelism the DiT blocks restack onto a leading
        layer axis sharded over ``pp`` (each rank holds L/pp blocks —
        parallel/pp.py)."""
        if self.mesh is None:
            return jax.device_put(params)
        if tp and self.wiring.size("pp") > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from vllm_omni_tpu.parallel import pp as pp_mod

            n_blocks = len(params["blocks"])
            pp = self.wiring.size("pp")
            if n_blocks % pp:
                raise ValueError(
                    f"num_layers={n_blocks} must divide pp={pp}")
            stacked = pp_mod.stack_blocks(params["blocks"])
            top = {k: v for k, v in params.items() if k != "blocks"}
            rep = NamedSharding(self.mesh, P())
            return {
                **jax.device_put(top, rep),
                "blocks_stacked": jax.tree.map(
                    lambda x: jax.device_put(
                        x, NamedSharding(self.mesh, P("pp"))),
                    stacked),
            }
        from vllm_omni_tpu.parallel.sharding import (
            replicated,
            shard_dit_params,
        )

        if tp:
            return shard_dit_params(params, self.mesh)
        return jax.device_put(params, replicated(self.mesh))

    def _init_dit_quantized(self, key, mode: str):
        """Init + quantize the DiT one block at a time on device,
        emitting blocks STACKED on a leading layer axis (the lax.scan
        layout ``dit.forward`` walks).

        Uses ``init_params``' exact key schedule (split L+8; top from
        keys[:6], block i from keys[i+8]) so the result is a
        QUANTIZATION OF THE SAME random model a dense build produces —
        dense-vs-quantized closeness tests stay meaningful.  The init is
        a scan whose body is (init one bf16 block -> quantize): the bf16
        weights exist only as a ~0.7 GB transient inside one scan
        iteration, and the scan's stacked output buffer is allocated
        once at the quantized size.  This is how the real 60-layer
        geometry (41 GB bf16) builds on a 16 GB chip."""
        from vllm_omni_tpu.diffusion.quantization import quantize_params

        cfg_d = self.cfg.dit
        dtype = self.dtype

        @jax.jit
        def q_top(ks):
            return quantize_params(dit.init_top(ks, cfg_d, dtype=dtype),
                                   mode=mode)

        @jax.jit
        def q_blocks(ks):
            def body(carry, k):
                blk = dit.init_block(k, cfg_d, dtype=dtype)
                return carry, quantize_params(blk, mode=mode)

            _, stacked = jax.lax.scan(body, None, ks)
            return stacked

        keys = jax.random.split(key, cfg_d.num_layers + 8)
        out = q_top(keys[:8])
        out["blocks_stacked"] = q_blocks(keys[8:])
        return out

    @classmethod
    def from_pretrained(
        cls,
        model_dir: str,
        dtype=jnp.bfloat16,
        seed: int = 0,
        mesh=None,
        cache_config=None,
        max_text_len: int = 512,
        offload: str = "",
    ) -> "QwenImagePipeline":
        """Build from a diffusers-format checkpoint directory (reference:
        DiffusersPipelineLoader, diffusion/model_loader/diffusers_loader.py
        + pipeline component resolution, omni_diffusion.py:34-109).

        Loads the DiT, the Qwen2.5-VL-style text encoder, and the causal
        VAE with real weights, plus the HF tokenizer and the FlowMatch
        scheduler shift config.
        """
        import os

        from vllm_omni_tpu.model_loader import diffusers_loader as dl

        dl.load_model_index(model_dir)  # validates layout
        dit_params, dit_cfg = dl.load_qwen_image_dit(
            os.path.join(model_dir, "transformer"), dtype=dtype
        )
        te_dir = os.path.join(model_dir, "text_encoder")
        text_params, text_cfg = dl.load_text_encoder(te_dir, dtype=dtype)
        vae_params, vae_cfg = dl.load_causal_vae(
            os.path.join(model_dir, "vae"), dtype=jnp.float32,
            encoder=cls.needs_vae_encoder,
        )
        sched = dl.scheduler_config(model_dir)
        config = QwenImagePipelineConfig(
            dit=dit_cfg,
            vae=vae_cfg,
            text=text_cfg,
            max_text_len=max_text_len,
            # defaults mirror diffusers FlowMatchEulerDiscreteScheduler
            # (and scheduler_config()'s own) so present-but-sparse and
            # absent scheduler configs behave identically
            shift=sched.get("shift", 1.0),
            use_dynamic_shifting=sched.get("use_dynamic_shifting", False),
        )
        pipe = cls(config, dtype=dtype, seed=seed, mesh=mesh,
                   cache_config=cache_config, init_weights=False,
                   offload=offload)
        if offload == "layerwise":
            # keep the loader's host numpy trees — blocks stream per use
            pipe.dit_params = dit_params
            pipe.text_params = text_params
        else:
            pipe.dit_params = pipe._place(dit_params, tp=True)
            pipe.text_params = pipe._place(text_params)
        pipe.vae_params = pipe._place(vae_params)
        tok_dir = os.path.join(model_dir, "tokenizer")
        if os.path.isdir(tok_dir):
            from transformers import AutoTokenizer

            pipe.hf_tokenizer = AutoTokenizer.from_pretrained(tok_dir)
            # the drop-34 preamble removal in _encode_prompt_hf is only
            # correct under right padding; some checkpoints ship
            # padding_side='left' in tokenizer_config.json
            pipe.hf_tokenizer.padding_side = "right"
        else:
            logger.warning("no tokenizer/ under %s; byte fallback",
                           model_dir)
        return pipe

    # ------------------------------------------------------------- encode
    def encode_prompt(self, prompts: list[str]):
        """Returns (hidden [B, S, joint_dim], mask [B, S])."""
        if self.hf_tokenizer is not None:
            return self._encode_prompt_hf(prompts)
        ids, lens = self.tokenizer.batch_encode(prompts, self.cfg.max_text_len)
        hidden = self._encode_jit(self.text_params, jnp.asarray(ids))
        mask = (
            np.arange(self.cfg.max_text_len)[None, :] < lens[:, None]
        ).astype(np.int32)
        return hidden, jnp.asarray(mask)

    def _encode_prompt_hf(self, prompts: list[str]):
        """Real-checkpoint text encoding: chat-template the prompt, take
        the final hidden states, and drop the fixed 34-token preamble
        (reference: _get_qwen_prompt_embeds, pipeline_qwen_image.py:366-399
        — with right padding, dropping the first `drop_idx` positions
        equals dropping the first drop_idx real tokens; we keep a static
        [B, max_text_len] shape and carry validity in the mask)."""
        drop = PROMPT_TEMPLATE_DROP_IDX
        txts = [PROMPT_TEMPLATE.format(p) for p in prompts]
        enc = self.hf_tokenizer(
            txts,
            max_length=self.cfg.max_text_len + drop,
            padding="max_length",
            truncation=True,
            return_tensors="np",
        )
        ids = np.asarray(enc["input_ids"], np.int32)
        mask = np.asarray(enc["attention_mask"], np.int32)
        hidden = self._encode_jit(self.text_params, jnp.asarray(ids))
        return (
            hidden[:, drop:].astype(self.dtype),
            jnp.asarray(mask[:, drop:]),
        )

    @functools.cached_property
    def _encode_jit(self):
        if self.offload == "layerwise":
            return lambda p, ids: self._stream_encode_hidden(ids)
        # params are an explicit jit ARGUMENT: closure capture would bake
        # them into the executable as constants, so sleep() couldn't free
        # the buffers and weight swaps would silently not apply
        return jax.jit(
            lambda p, ids: forward_hidden(p, self.cfg.text, ids)
        )

    # ---------------------------------------------- layerwise streaming
    @functools.cached_property
    def _text_stream(self):
        from vllm_omni_tpu.diffusion import offload as ol

        top, layers = ol.split_host_blocks(self.text_params, "layers")
        return jax.device_put(top), layers

    @functools.cached_property
    def _dit_stream(self):
        from vllm_omni_tpu.diffusion import offload as ol

        top, blocks = ol.split_host_blocks(self.dit_params, "blocks")
        return jax.device_put(top), blocks

    @functools.cached_property
    def _dit_streamer(self):
        """Persistent streamer with as many blocks pinned resident in HBM
        as fit beyond activations + double buffer — pinned blocks are
        transferred once per pipeline, not once per step, cutting the
        transfer-bound step time proportionally."""
        from vllm_omni_tpu.diffusion.offload import BlockStreamer

        _, blocks = self._dit_stream
        return BlockStreamer(blocks,
                             pinned=BlockStreamer.auto_pin(blocks))

    @functools.cached_property
    def _stream_text_jits(self):
        from vllm_omni_tpu.models.common import nn as cnn
        from vllm_omni_tpu.models.common import transformer as tfm
        from vllm_omni_tpu.ops import flash_attention, rms_norm

        tcfg = self.cfg.text

        @jax.jit
        def prefix(top, ids):
            b, s = ids.shape
            x = cnn.embedding(top["embed"], ids)
            # text-only positions: 1-D, or equal-stream [B, 3, S] when
            # the encoder config carries mrope sections (Qwen2.5-VL
            # checkpoints do — equal streams are numerically 1-D rope)
            shape = (b, s) if tcfg.mrope_sections is None else (b, 3, s)
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], shape)
            cos, sin = tfm._rope_tables(tcfg, positions)
            return x, cos, sin

        @jax.jit
        def layer(lp, x, cos, sin):
            b, s, _ = x.shape

            def attend(q, k, v):
                return flash_attention(
                    q.reshape(b, s, tcfg.num_heads, tcfg.head_dim),
                    k.reshape(b, s, tcfg.num_kv_heads, tcfg.head_dim),
                    v.reshape(b, s, tcfg.num_kv_heads, tcfg.head_dim),
                    causal=True,
                )

            return tfm._layer_step(lp, tcfg, x, cos, sin, attend)

        @jax.jit
        def suffix(top, x):
            return rms_norm(x, top["final_norm"]["w"], tcfg.rms_eps)

        return prefix, layer, suffix

    def _stream_encode_hidden(self, ids: jax.Array) -> jax.Array:
        """Text-encoder forward with layer weights streamed from host —
        the 7B encoder's 15 GB of bf16 weights never need to be resident
        at once."""
        import time as _time

        from vllm_omni_tpu.diffusion.offload import BlockStreamer

        t0 = _time.perf_counter()
        prefix, layer, suffix = self._stream_text_jits
        top, layers = self._text_stream
        x, cos, sin = prefix(top, jnp.asarray(ids))
        x = BlockStreamer(layers).run(
            lambda lp, c: layer(lp, c, cos, sin), x)
        out = suffix(top, x)
        jax.block_until_ready(out)
        logger.info("streamed text encode: %.1fs (%d layers)",
                    _time.perf_counter() - t0, len(layers))
        return out

    @functools.cached_property
    def _stream_dit_jits(self):
        from vllm_omni_tpu.models.common import nn as cnn
        from vllm_omni_tpu.ops import rms_norm

        cfg = self.cfg

        @functools.partial(jax.jit, static_argnames=("grid_h", "grid_w"))
        def prefix(top, latents, txt_states, txt_mask, t, grid_h, grid_w):
            img = cnn.linear(top["img_in"], latents)
            txt = rms_norm(txt_states, top["txt_norm"]["w"])
            txt = cnn.linear(top["txt_in"], txt)
            temb = cnn.timestep_embedding(t, 256)
            temb = cnn.linear(
                top["time_in2"],
                jax.nn.silu(cnn.linear(top["time_in1"],
                                       temb.astype(img.dtype))))
            temb_act = jax.nn.silu(temb)
            img_freqs, txt_freqs = dit.rope_freqs(
                cfg.dit, grid_h, grid_w, txt_states.shape[1])
            kv_mask = jnp.concatenate(
                [txt_mask.astype(jnp.int32),
                 jnp.ones((img.shape[0], img.shape[1]), jnp.int32)],
                axis=1,
            )
            return img, txt, temb_act, img_freqs, txt_freqs, kv_mask

        @jax.jit
        def block(blk, img, txt, temb_act, img_freqs, txt_freqs, kv_mask):
            return dit.block_forward(
                blk, cfg.dit, img, txt, temb_act, img_freqs, txt_freqs,
                None, kv_mask)

        @jax.jit
        def suffix(top, img, temb_act):
            mod = cnn.linear(top["norm_out_mod"], temb_act)
            scale, shift = jnp.split(mod, 2, axis=-1)
            img = (cnn.layernorm({}, img) * (1.0 + scale[:, None, :])
                   + shift[:, None, :])
            return cnn.linear(top["proj_out"], img)

        @functools.partial(jax.jit, static_argnames=("do_cfg",))
        def sched_step(latents, v, sigmas, i, gscale, do_cfg):
            if do_cfg:
                v_pos, v_neg = jnp.split(v, 2, axis=0)
                v = v_neg + gscale * (v_pos - v_neg)
            dt = sigmas[i + 1] - sigmas[i]
            return (latents.astype(jnp.float32)
                    + dt * v.astype(jnp.float32)).astype(latents.dtype)

        return prefix, block, suffix, sched_step

    def _stream_denoise(self, latents, txt_all, mask_all, sigmas,
                        timesteps, gscale, num_steps, grid_h, grid_w,
                        do_cfg):
        """Python-driven denoise loop with DiT block weights streamed
        from host per step (one jitted executable per piece; the 60-block
        walk transfers 41 GB/step for the real geometry, overlapped with
        compute by the BlockStreamer lookahead; blocks that fit HBM stay
        pinned resident across steps).

        TeaCache rides the host loop: the lax.cond gate of the jitted
        path (diffusion/cache.py:cached_eval) becomes a Python branch —
        a skipped step here saves not just the DiT FLOPs but the whole
        per-step weight transfer, which is what the streamed walk is
        bound by."""
        import time as _time

        prefix, block, suffix, sched_step = self._stream_dit_jits
        top, _ = self._dit_stream
        streamer = self._dit_streamer
        sigmas = jnp.asarray(sigmas)
        gscale = jnp.float32(gscale)
        t_start = _time.perf_counter()
        cc = self.cache_config
        use_cache = cc is not None and cc.enabled
        prev_v = prev_lat = None
        accum = float("inf")
        n = int(num_steps)
        self.last_skipped_steps = 0
        scm = cc.scm_steps_mask if use_cache else None
        for i in range(n):
            if use_cache and prev_lat is not None:
                accum += float(_rel_drift(latents, prev_lat))
                in_window = (i >= cc.warmup_steps
                             and i < n - cc.tail_steps)
                # deterministic steps-cache-mask overrides the drift
                # gate when configured (same precedence as the jitted
                # path, diffusion/cache.py:cached_eval); steps beyond
                # the mask compute, matching _scm_mask_array's padding
                if scm is not None:
                    want_skip = i < len(scm) and not bool(scm[i])
                else:
                    want_skip = accum < cc.rel_l1_threshold
                if in_window and want_skip:
                    self.last_skipped_steps += 1
                    latents = sched_step(latents, prev_v, sigmas,
                                         jnp.int32(i), gscale,
                                         do_cfg=do_cfg)
                    continue
            lat_in = (jnp.concatenate([latents, latents], axis=0)
                      if do_cfg else latents)
            t = jnp.broadcast_to(timesteps[i], (lat_in.shape[0],))
            img, txt_i, temb_act, img_f, txt_f, kv_mask = prefix(
                top, lat_in, txt_all, mask_all, t,
                grid_h=grid_h, grid_w=grid_w)
            img, txt_i = streamer.run(
                lambda blk, c: block(blk, c[0], c[1], temb_act, img_f,
                                     txt_f, kv_mask),
                (img, txt_i))
            v = suffix(top, img, temb_act)
            if use_cache:
                prev_v = v
                prev_lat = latents.astype(jnp.float32)
                accum = 0.0
            latents = sched_step(latents, v, sigmas, jnp.int32(i), gscale,
                                 do_cfg=do_cfg)
            if i == 0:
                jax.block_until_ready(latents)
                logger.info("streamed denoise: first step %.1fs "
                            "(includes per-piece compiles)",
                            _time.perf_counter() - t_start)
        jax.block_until_ready(latents)
        n_run = n - self.last_skipped_steps
        self.last_stream_denoise_s = _time.perf_counter() - t_start
        logger.info(
            "streamed denoise: %d steps (%d run, %d cache-skipped) in "
            "%.1fs", n, n_run, self.last_skipped_steps,
            self.last_stream_denoise_s)
        return latents

    # ------------------------------------------------------------ denoise
    def _sp_attn_fn(self, n_heads: int, seq_len: int, batch2: int):
        """shard_map-wrapped joint USP attention for the DiT blocks, or
        None when the mesh/shape constraints don't allow the explicit SP
        path (GSPMD still partitions the dense fallback correctly).
        Shared wiring: parallel/pipeline_mesh.py."""
        return self.wiring.joint_attn_fn(n_heads, seq_len, batch2)

    def _denoise_fn(self, grid_h: int, grid_w: int, sched_len: int,
                    batch2: int = 0,
                    cond_grids: tuple[tuple[int, int], ...] = (),
                    frames: int = 1):
        # batch2 affects only the shard_map attn dispatch decision — keep
        # it out of the key on meshless pipelines (jit handles shapes).
        key = (grid_h, grid_w, sched_len, cond_grids, frames) + (
            (batch2,) if self.mesh is not None else ())
        if key in self._denoise_cache:
            return self._denoise_cache[key]

        cfg = self.cfg
        n_cond = sum(ch * cw for ch, cw in cond_grids)
        if self.wiring.size("pp") > 1:
            run = self._pp_denoise_fn(grid_h, grid_w, sched_len,
                                      cond_grids, frames)
            self._denoise_cache[key] = run
            return run
        attn_fn = self._sp_attn_fn(
            cfg.dit.num_heads, frames * grid_h * grid_w + n_cond, batch2)
        mesh = self.mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            lat2_sharding = NamedSharding(
                mesh, P(("cfg", "dp"), ("ring", "ulysses"), None))
            txt2_sharding = NamedSharding(mesh, P(("cfg", "dp"), None, None))

        @jax.jit
        def run(
            dit_params, latents, txt, txt_mask, neg_txt, neg_mask,
            sigmas, timesteps, gscale, num_steps, cond=None,
            step_offset=None, total_steps=None, cache_carry=None,
        ):
            # latents: [B, S_img, C_in]; txt/neg_txt: [B, S_txt, joint];
            # sigmas/timesteps padded to sched_len(+1); num_steps is a
            # traced scalar — the loop bound is dynamic, the shapes static.
            schedule = fm.FlowMatchSchedule(sigmas=sigmas, timesteps=timesteps)
            do_cfg = neg_txt is not None
            txt_all = (
                jnp.concatenate([txt, neg_txt], axis=0) if do_cfg else txt
            )
            mask_all = (
                jnp.concatenate([txt_mask, neg_mask], axis=0)
                if do_cfg
                else txt_mask
            )
            if mesh is not None:
                # CFG parallel: the [positive; negative] halves of the
                # doubled batch ride the cfg axis (cfg outermost in the
                # batch spec), image sequence over the SP axes — GSPMD
                # inserts the cfg combine at the guidance step below.
                txt_all = jax.lax.with_sharding_constraint(
                    txt_all, txt2_sharding)

            def embed(lat, i):
                t = jnp.broadcast_to(timesteps[i], (lat.shape[0],))
                s_gen = lat.shape[1]
                # image edit: VAE-encoded condition tokens extend the
                # sequence; velocity is read off the generated tokens
                lat_model = (lat if cond is None
                             else jnp.concatenate([lat, cond], axis=1))
                lat_in = (jnp.concatenate([lat_model, lat_model], 0)
                          if do_cfg else lat_model)
                t_in = jnp.concatenate([t, t], 0) if do_cfg else t
                if mesh is not None:
                    lat_in = jax.lax.with_sharding_constraint(
                        lat_in, lat2_sharding)
                return s_gen, lat_in, t_in

            def finish(img, temb_act, s_gen):
                v = dit.forward_suffix(dit_params, img,
                                       temb_act)[:, :s_gen]
                if do_cfg:
                    v_pos, v_neg = jnp.split(v, 2, axis=0)
                    v = v_neg + gscale * (v_pos - v_neg)
                return v

            def prefix_state(lat, i):
                s_gen, lat_in, t_in = embed(lat, i)
                return s_gen, dit.forward_prefix(
                    dit_params, cfg.dit, lat_in, txt_all, t_in,
                    (grid_h, grid_w), txt_mask=mask_all,
                    cond_grids=cond_grids, frames=frames)

            def run_blocks(state, blocks):
                # list -> unrolled loop, stacked dict -> lax.scan
                # (dit.walk_blocks — one block's HLO in the program)
                img, txt_i, temb_act, img_f, txt_f, kv_mask = state
                img, txt_i = dit.walk_blocks(
                    blocks, cfg.dit, img, txt_i, temb_act, img_f,
                    txt_f, attn_fn, kv_mask)
                return (img, txt_i, temb_act, img_f, txt_f, kv_mask)

            def slice_blocks(lo, hi):
                if "blocks_stacked" in dit_params:
                    return jax.tree.map(
                        lambda x: x[lo:hi], dit_params["blocks_stacked"])
                return dit_params["blocks"][lo:hi]

            # ONE block-stack implementation serves the uncached,
            # teacache, and dbcache paths (dbcache splits it at
            # fn_compute_blocks — the always-computed anchor)
            fn_blocks = (self.cache_config.fn_compute_blocks
                         if self.cache_config is not None else 0)
            n_blocks = cfg.dit.num_layers

            def eval_velocity(lat, i):
                s_gen, state = prefix_state(lat, i)
                state = run_blocks(state, slice_blocks(0, n_blocks))
                return finish(state[0], state[2], s_gen)

            def eval_first(lat, i):
                s_gen, state = prefix_state(lat, i)
                state = run_blocks(state, slice_blocks(0, fn_blocks))
                return state, finish(state[0], state[2], s_gen)

            def eval_rest(state):
                state = run_blocks(state,
                                   slice_blocks(fn_blocks, n_blocks))
                return finish(state[0], state[2],
                              int(latents.shape[1]))

            return step_cache.run_denoise_loop(
                self.cache_config, schedule, eval_velocity, latents,
                num_steps, solver=self.cfg.scheduler,
                eval_split=(eval_first, eval_rest),
                step_offset=step_offset, total_steps=total_steps,
                carry_in=cache_carry,
                # chunked callers (step_offset set) always get the
                # 3-tuple — (latents, 0, None) when uncached — so the
                # host loop has ONE call shape; plain callers keep the
                # 2-tuple
                return_carry=(cache_carry is not None
                              or step_offset is not None),
            )

        self._denoise_cache[key] = run
        return run

    # ----------------------------------------------------------- generate
    def forward(self, req: OmniDiffusionRequest) -> list[DiffusionOutput]:
        sp = req.sampling_params
        cfg = self.cfg
        ratio = cfg.vae.spatial_ratio
        patch = cfg.dit.patch_size
        mult = ratio * patch
        if sp.height % mult or sp.width % mult:
            raise InvalidRequestError(
                f"height/width must be multiples of {mult} "
                f"(vae ratio {ratio} x patch {patch}); got "
                f"{sp.height}x{sp.width}"
            )
        if sp.num_inference_steps < 1:
            raise InvalidRequestError("num_inference_steps must be >= 1")
        lat_h, lat_w = sp.height // ratio, sp.width // ratio
        grid_h, grid_w = lat_h // patch, lat_w // patch
        frames = self._latent_frames(req)
        seq_len = frames * grid_h * grid_w
        n_per = max(1, sp.num_images_per_prompt)
        prompts = [p for p in req.prompt for _ in range(n_per)]
        b = len(prompts)

        # Encode each unique prompt once, then repeat embeddings per image
        # (reference repeats post-encode too, pipeline_qwen_image.py).
        if req.prompt_embeds is not None:
            txt = jnp.asarray(req.prompt_embeds, self.dtype)
            txt_mask = jnp.ones(txt.shape[:2], jnp.int32)
        else:
            txt, txt_mask = self.encode_prompt(req.prompt)
        if n_per > 1:
            txt = jnp.repeat(txt, n_per, axis=0)
            txt_mask = jnp.repeat(txt_mask, n_per, axis=0)
        do_cfg = sp.guidance_scale > 1.0
        neg_txt = neg_mask = None
        if do_cfg:
            if req.negative_prompt_embeds is not None:
                neg_txt = jnp.asarray(req.negative_prompt_embeds, self.dtype)
                neg_mask = jnp.ones(neg_txt.shape[:2], jnp.int32)
            else:
                neg_txt, neg_mask = self.encode_prompt(
                    [sp.negative_prompt] * len(req.prompt)
                )
            if n_per > 1:
                neg_txt = jnp.repeat(neg_txt, n_per, axis=0)
                neg_mask = jnp.repeat(neg_mask, n_per, axis=0)

        # Unseeded requests sample a fresh seed (reference semantics: a
        # torch Generator is only seeded when the user provides one).
        seed = (
            sp.seed
            if sp.seed is not None
            else int(np.random.randint(0, 2**31 - 1))
        )
        noise = jax.random.normal(
            jax.random.PRNGKey(seed),
            (b, seq_len, cfg.dit.in_channels),
            jnp.float32,
        ).astype(self.dtype)

        mu = fm.compute_dynamic_shift_mu(seq_len)
        num_steps = sp.num_inference_steps
        schedule = fm.make_schedule(
            num_steps,
            shift=cfg.shift,
            use_dynamic_shifting=cfg.use_dynamic_shifting,
            mu=mu,
        )
        sched_len = max(num_steps, cfg.steps_bucket)
        sigmas = jnp.zeros((sched_len + 1,)).at[: num_steps + 1].set(
            schedule.sigmas
        )
        timesteps = jnp.zeros((sched_len,)).at[:num_steps].set(
            schedule.timesteps
        )
        cond_tokens, cond_grids = self._edit_cond(req, b)
        if self.offload == "layerwise":
            if cond_tokens is not None:
                raise InvalidRequestError(
                    "image-edit conditioning is not supported with "
                    "layerwise offload yet")
            if frames != 1:
                raise InvalidRequestError(
                    "layered generation (frames > 1) is not supported "
                    "with layerwise offload yet")
            txt_all = (jnp.concatenate([txt, neg_txt], axis=0)
                       if do_cfg else txt)
            mask_all = (jnp.concatenate([txt_mask, neg_mask], axis=0)
                        if do_cfg else txt_mask)
            latents = self._stream_denoise(
                noise, txt_all, mask_all, sigmas, timesteps,
                sp.guidance_scale, num_steps, grid_h, grid_w, do_cfg)
        else:
            run = self._denoise_fn(
                grid_h, grid_w, sched_len, batch2=(2 * b if do_cfg else b),
                cond_grids=cond_grids, frames=frames)
            gscale = jnp.float32(sp.guidance_scale)
            if self.step_loop == "host":
                # step_chunk steps per device call (see __init__): the
                # SAME compiled executable runs with num_steps=k over
                # the schedule rolled so index 0 is the chunk start.
                # With a step cache, its carry threads through the
                # chunks (device-resident; no host transfer) and skip
                # decisions use the GLOBAL step index — identical to
                # one uninterrupted device loop.
                import time as _time

                t_start = _time.perf_counter()
                use_cc = (self.cache_config is not None
                          and self.cache_config.enabled)
                carry = step_cache.init_cache_carry(
                    self.cache_config, noise)
                latents = noise
                skipped = jnp.int32(0)
                for i in range(0, num_steps, self.step_chunk):
                    k = min(self.step_chunk, num_steps - i)
                    latents, sk, carry = run(
                        self.dit_params, latents, txt, txt_mask,
                        neg_txt, neg_mask,
                        jnp.roll(sigmas, -i), jnp.roll(timesteps, -i),
                        gscale, jnp.int32(k), cond=cond_tokens,
                        step_offset=jnp.int32(i),
                        total_steps=jnp.int32(num_steps),
                        cache_carry=carry,
                    )
                    skipped = skipped + sk
                jax.block_until_ready(latents)
                self.last_skipped_steps = (
                    int(jax.device_get(skipped)) if use_cc else 0)
                self.last_stream_denoise_s = (
                    _time.perf_counter() - t_start)
            else:
                latents, skipped_steps = run(
                    self.dit_params,
                    noise,
                    txt,
                    txt_mask,
                    neg_txt,
                    neg_mask,
                    sigmas,
                    timesteps,
                    gscale,
                    jnp.int32(num_steps),
                    cond=cond_tokens,
                )
                self.last_skipped_steps = int(skipped_steps)

        images = self._decode_latents(latents, grid_h, grid_w,
                                      frames=frames)
        images = np.asarray(images)
        outs = []
        for i, prompt in enumerate(prompts):
            rid = req.request_ids[i // n_per]
            if n_per > 1:
                rid = f"{rid}-{i % n_per}"
            outs.append(
                DiffusionOutput(
                    request_id=rid,
                    prompt=prompt,
                    data=images[i],
                    output_type="image",
                )
            )
        return outs

    def _pp_denoise_fn(self, grid_h: int, grid_w: int, sched_len: int,
                       cond_grids: tuple = (), frames: int = 1):
        """Denoise with the block stack pipelined over the ``pp`` axis
        (GPipe microbatches, parallel/pp.py): per-rank weight memory
        drops to L/pp blocks; the CFG-doubled batch supplies the
        microbatches."""
        from jax.sharding import PartitionSpec as P

        from vllm_omni_tpu.parallel import pp as pp_mod

        cfg = self.cfg
        mesh = self.mesh
        pp = self.wiring.size("pp")

        from jax import shard_map

        @jax.jit
        def run(dit_params, latents, txt, txt_mask, neg_txt, neg_mask,
                sigmas, timesteps, gscale, num_steps, cond=None):
            schedule = fm.FlowMatchSchedule(sigmas=sigmas,
                                            timesteps=timesteps)
            do_cfg = neg_txt is not None
            txt_all = (jnp.concatenate([txt, neg_txt], axis=0)
                       if do_cfg else txt)
            mask_all = (jnp.concatenate([txt_mask, neg_mask], axis=0)
                        if do_cfg else txt_mask)
            blocks = dit_params["blocks_stacked"]

            def eval_velocity(lat, i):
                t = jnp.broadcast_to(timesteps[i], (lat.shape[0],))
                s_gen = lat.shape[1]
                lat_model = (lat if cond is None
                             else jnp.concatenate([lat, cond], axis=1))
                lat_in = (jnp.concatenate([lat_model, lat_model], 0)
                          if do_cfg else lat_model)
                t_in = jnp.concatenate([t, t], 0) if do_cfg else t
                img, txt_i, temb_act, img_f, txt_f, kv_mask = \
                    dit.forward_prefix(
                        dit_params, cfg.dit, lat_in, txt_all, t_in,
                        (grid_h, grid_w), txt_mask=mask_all,
                        cond_grids=cond_grids, frames=frames)
                b2 = img.shape[0]
                if b2 % pp:
                    raise ValueError(
                        f"(cfg-doubled) batch {b2} must divide pp={pp}")

                # freqs are batch-free trace constants; only batched
                # activations ride the microbatch carry
                def scan_blocks(local_blocks, carry):
                    im, tx, temb_c, kvm = carry

                    def body(c, blk):
                        i_, t_ = c
                        i_, t_ = dit.block_forward(
                            blk, cfg.dit, i_, t_, temb_c, img_f, txt_f,
                            None, kvm)
                        return (i_, t_), None

                    (im, tx), _ = jax.lax.scan(body, (im, tx),
                                               local_blocks)
                    return (im, tx, temb_c, kvm)

                sm = shard_map(
                    functools.partial(pp_mod.pipeline_apply,
                                      scan_fn=scan_blocks),
                    mesh=mesh,
                    in_specs=(pp_mod.pp_block_specs(blocks), P()),
                    out_specs=P(),
                    check_vma=False,
                )
                mb = pp_mod.microbatch(
                    (img, txt_i, temb_act, kv_mask), pp)
                img = pp_mod.unmicrobatch(sm(blocks, mb))[0]
                v = dit.forward_suffix(dit_params, img, temb_act)[:, :s_gen]
                if do_cfg:
                    v_pos, v_neg = jnp.split(v, 2, axis=0)
                    v = v_neg + gscale * (v_pos - v_neg)
                return v

            return step_cache.run_denoise_loop(
                self.cache_config, schedule, eval_velocity, latents,
                num_steps, solver=self.cfg.scheduler,
            )

        return run

    def _edit_cond(self, req, batch: int):
        """(cond_tokens [B, S_cond, in_channels] | None, cond_grids) —
        edit pipelines override to VAE-encode input images."""
        return None, ()

    @functools.cached_property
    def _decode_jit(self):
        @functools.partial(jax.jit, static_argnames=("grid_h", "grid_w"))
        def dec(vae_params, latents, grid_h, grid_w):
            cfg = self.cfg
            patch = cfg.dit.patch_size
            b = latents.shape[0]
            # unpack [B, gh*gw, p*p*C] -> [B, gh*p, gw*p, C]
            c = cfg.vae.z_channels
            x = latents.reshape(b, grid_h, grid_w, patch, patch, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(
                b, grid_h * patch, grid_w * patch, c
            )
            img = vae_mod.decode_image(
                vae_params, cfg.vae, x.astype(jnp.float32))
            img = jnp.clip((img + 1.0) * 127.5, 0, 255)
            return img.astype(jnp.uint8)

        return dec

    def _latent_frames(self, req) -> int:
        """Simultaneously-generated image planes (rope frame axis);
        layered pipelines override (reference:
        pipeline_qwen_image_layered.py:457-553)."""
        return 1

    def _decode_latents(self, latents, grid_h, grid_w, frames: int = 1):
        # DiT out_channels == vae latent channels; proj_out emits
        # patch^2 * C which equals in_channels when packing matches.
        if frames == 1:
            return self._decode_jit(self.vae_params, latents, grid_h,
                                    grid_w)
        b = latents.shape[0]
        per = latents.reshape(b * frames, grid_h * grid_w,
                              latents.shape[-1])
        imgs = self._decode_jit(self.vae_params, per, grid_h, grid_w)
        return imgs.reshape(b, frames, *imgs.shape[1:])

    def _encode_image_latents(self, images: jax.Array) -> jax.Array:
        """[B, H, W, 3] in [-1, 1] -> packed [B, gh*gw, p*p*z] latents
        (inverse of the decode unpack) — used by edit pipelines."""
        cfg = self.cfg
        patch = cfg.dit.patch_size
        lat = vae_mod.encode_image(
            self.vae_params, cfg.vae, images.astype(jnp.float32))
        b, h, w, c = lat.shape
        gh, gw = h // patch, w // patch
        x = lat.reshape(b, gh, patch, gw, patch, c)
        return x.transpose(0, 1, 3, 2, 4, 5).reshape(
            b, gh * gw, patch * patch * c).astype(self.dtype)
