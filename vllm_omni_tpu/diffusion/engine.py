"""DiffusionEngine — engine facade for DiT pipelines.

Role of the reference's ``DiffusionEngine`` (diffusion/diffusion_engine.py:
45,69,183,345): resolve the pipeline class from the registry, build it from
``OmniDiffusionConfig``, warm up the jit cache with a dummy generation, and
serve ``step(OmniDiffusionRequest) -> [DiffusionOutput]``.

Where the reference spawns a multiproc executor with one WorkerProc per
GPU + shm MessageQueue broadcast (executor/multiproc_executor.py:47), the
TPU-native engine is single-controller: one process drives the whole mesh
through pjit — the intra-stage fan-out machinery collapses into XLA
(SURVEY.md §7 design stance #1).
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp

from vllm_omni_tpu.config.diffusion import OmniDiffusionConfig
from vllm_omni_tpu.config.model import resolve_dtype
from vllm_omni_tpu.diffusion.request import (
    DiffusionOutput,
    OmniDiffusionRequest,
    OmniDiffusionSamplingParams,
)
from vllm_omni_tpu.introspection.phase_clock import startup
from vllm_omni_tpu.logger import init_logger
from vllm_omni_tpu.metrics.stats import Histogram
from vllm_omni_tpu.models.registry import DiffusionModelRegistry

logger = init_logger(__name__)

# diffusion batch wall times run seconds-to-minutes, not milliseconds
_GEN_SECONDS_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                        60.0, 120.0, 300.0)


_UNSET = object()


def resolve_arch(config: OmniDiffusionConfig, declared=_UNSET) -> str:
    """Pipeline class from explicit config or the checkpoint's
    model_index.json ``_class_name`` (reference: omni_diffusion.py:34-109);
    single-repo HF checkpoints (HunyuanImage-3) resolve via config.json
    ``architectures`` instead.  ``declared`` lets a caller that already
    parsed config.json pass its result in (one parse, one view)."""
    if config.model_arch:
        return config.model_arch
    idx = os.path.join(config.model, "model_index.json")
    if os.path.isfile(idx):
        with open(idx) as f:
            name = json.load(f).get("_class_name", "")
        if name:
            return name
    if declared is _UNSET:
        declared = _declared_arch(config.model) if config.model else None
    if declared:
        return declared
    # default flagship
    return "QwenImagePipeline"


def _declared_arch(model: str):
    """Registry architecture declared by a local dir's config.json
    (single-repo HF layout, no model_index.json), or None.  Mirrors the
    reference routing (omni_diffusion.py:78-83): any listed
    architecture the registry knows, plus model_type == "bagel"."""
    p = os.path.join(model, "config.json")
    if not os.path.isfile(p):
        return None
    try:
        with open(p) as f:
            cfg = json.load(f)
    except Exception:
        return None
    supported = DiffusionModelRegistry.supported()
    for arch in cfg.get("architectures") or []:
        if arch in supported:
            return arch
    if cfg.get("model_type") == "bagel":
        return "BagelPipeline"
    return None


def _arch_checkpoint(model: str) -> bool:
    return _declared_arch(model) is not None


class DiffusionEngine:
    def __init__(self, od_config: OmniDiffusionConfig, warmup: bool = True):
        self.od_config = od_config
        declared = (_declared_arch(od_config.model)
                    if od_config.model else None)
        arch = resolve_arch(od_config, declared)
        pipeline_cls = DiffusionModelRegistry.resolve(arch)
        dtype = resolve_dtype(od_config.dtype)
        size = od_config.extra.get("size", "")
        pipe_cfg = self._pipeline_config(pipeline_cls, size)
        solver = od_config.extra.get("scheduler")
        if solver:
            if solver not in ("euler", "unipc"):
                raise ValueError(
                    f"unknown scheduler {solver!r} (euler | unipc)")
            if not hasattr(pipe_cfg, "scheduler"):
                raise ValueError(
                    f"{arch} does not support a scheduler override")
            import dataclasses

            pipe_cfg = dataclasses.replace(pipe_cfg, scheduler=solver)
        logger.info("Building %s (size=%s dtype=%s)", arch, size or "default", dtype)
        cache_config = None
        if od_config.cache_backend:
            if od_config.cache_backend not in ("teacache", "dbcache",
                                              "taylorseer"):
                raise ValueError(
                    f"unsupported cache_backend {od_config.cache_backend!r} "
                    "(TPU path supports 'teacache', 'dbcache', "
                    "'taylorseer')"
                )
            from vllm_omni_tpu.diffusion.cache import StepCacheConfig

            cache_config = StepCacheConfig.from_dict(
                od_config.cache_backend, od_config.cache_config
            )
        mesh = None
        if od_config.parallel.world_size > 1:
            # Stage mesh from the configured parallel degrees (reference:
            # initialize_model_parallel, parallel_state.py:624); the
            # pipeline shards weights/activations over it.
            from vllm_omni_tpu.parallel.mesh import build_mesh

            mesh = build_mesh(
                od_config.parallel,
                jax.devices()[: od_config.parallel.world_size],
            )
        self.mesh = mesh
        extra_kwargs = {}

        def require_ctor_param(name, value):
            # optional pipeline features are opted into per-arch by
            # declaring the kwarg; anything else fails loudly here
            # rather than as a TypeError deep in the constructor
            import inspect

            if name not in inspect.signature(
                    pipeline_cls.__init__).parameters:
                raise ValueError(
                    f"{arch} does not support {name}={value!r}")
            extra_kwargs[name] = value

        if od_config.offload:
            require_ctor_param("offload", od_config.offload)
        step_loop = od_config.extra.get("step_loop")
        if step_loop:
            require_ctor_param("step_loop", step_loop)
        step_chunk = od_config.extra.get("step_chunk")
        if step_chunk is not None:  # 0 must reach pipeline validation
            require_ctor_param("step_chunk", int(step_chunk))
        from_ckpt = (
            od_config.model
            and (os.path.isfile(os.path.join(od_config.model,
                                             "model_index.json"))
                 # single-repo HF checkpoints (HunyuanImage-3) carry a
                 # registry architecture in config.json instead
                 or declared is not None)
            and hasattr(pipeline_cls, "from_pretrained")
        )
        quant_at_init = False
        if od_config.quantization in ("int8", "fp8", "int4") \
                and not from_ckpt and not od_config.offload \
                and mesh is None:  # sharded builds quantize post-hoc
            import inspect

            # pipelines exposing quantize_init quantize each DiT block as
            # it is initialized — the only way a model whose bf16 tree
            # exceeds HBM (real Qwen-Image: 41 GB vs 16 GB) can be built
            # quantized-resident; post-hoc quantization would have to
            # materialize the float tree first
            if "quantize_init" in inspect.signature(
                    pipeline_cls.__init__).parameters:
                extra_kwargs["quantize_init"] = od_config.quantization
                quant_at_init = True
        if from_ckpt:
            # diffusers-format checkpoint directory: real weights
            with startup.span("omni.setup.weights"):
                self.pipeline = pipeline_cls.from_pretrained(
                    od_config.model, dtype=dtype, seed=od_config.seed,
                    cache_config=cache_config, mesh=mesh, **extra_kwargs,
                )
            if solver and hasattr(self.pipeline.cfg, "scheduler"):
                # from_pretrained builds its own config; re-apply the
                # override (it was validated above) before any denoise
                # executable is traced
                import dataclasses

                self.pipeline.cfg = dataclasses.replace(
                    self.pipeline.cfg, scheduler=solver)
        else:
            if od_config.model and os.path.isdir(od_config.model):
                # a real directory without model_index.json is a broken
                # checkpoint path, not a preset name — don't silently
                # serve random weights
                raise ValueError(
                    f"model dir {od_config.model!r} has no "
                    "model_index.json (not a diffusers-format checkpoint)"
                )
            if od_config.model:
                logger.warning(
                    "model %r is not a local checkpoint directory; "
                    "building %s with random-init weights",
                    od_config.model, arch,
                )
            with startup.span("omni.setup.weights"):
                self.pipeline = pipeline_cls(
                    pipe_cfg, dtype=dtype, seed=od_config.seed,
                    cache_config=cache_config, mesh=mesh, **extra_kwargs,
                )
        if quant_at_init:
            pass  # already quantized block-by-block during init
        elif od_config.quantization in ("int8", "fp8", "int4"):
            from vllm_omni_tpu.diffusion.quantization import (
                quantize_params,
                quantize_params_host,
            )

            # layerwise-streamed trees live in HOST memory: quantize
            # there (halves the per-step host->HBM transfer the walk is
            # bound by); the jnp path would round-trip every block
            # through the device
            quantize = (
                quantize_params_host
                if getattr(self.pipeline, "offload", "") == "layerwise"
                else quantize_params)
            self.pipeline.dit_params = quantize(
                self.pipeline.dit_params, mode=od_config.quantization
            )
        elif od_config.quantization:
            raise ValueError(
                f"unsupported quantization {od_config.quantization!r} "
                "(TPU path supports 'int8'/'fp8'/'int4' weight-only)"
            )
        from vllm_omni_tpu.diffusion.lora import LoRAManager

        self.lora_manager = LoRAManager()
        # observability: step counters + batch-time histogram surfaced
        # through /metrics; stage_id stamped by OmniStage
        self.stage_id = 0
        self._num_requests = 0
        self._num_batches = 0
        self._gen_seconds = Histogram(buckets=_GEN_SECONDS_BUCKETS)
        if warmup:
            with startup.span("omni.setup.warmup"):
                self._warmup()

    def metrics_snapshot(self) -> dict:
        return {"startup": startup.snapshot(), "diffusion": {
            "requests_total": self._num_requests,
            "batches_total": self._num_batches,
            "gen_seconds": self._gen_seconds.snapshot(),
        }}

    @staticmethod
    def _pipeline_config(pipeline_cls, size: str):
        # Pipelines expose tiny()/bench() presets on their config
        # dataclass; subclasses that reuse a parent's __init__ but carry
        # their own config declare it via ``config_cls``.
        import inspect

        cfg_type = getattr(pipeline_cls, "config_cls", None)
        if cfg_type is None:
            sig = inspect.signature(pipeline_cls.__init__)
            cfg_type = sig.parameters["config"].annotation
            if isinstance(cfg_type, str):
                # postponed annotation: resolve from the module DEFINING
                # the __init__ (an inheriting pipeline's own module may
                # not import the parent's config name)
                import importlib

                mod = importlib.import_module(
                    pipeline_cls.__init__.__module__)
                cfg_type = getattr(mod, cfg_type)
        if size and hasattr(cfg_type, size):
            return getattr(cfg_type, size)()
        return cfg_type()

    def _warmup(self):
        """Compile-warm the denoise loop with a 1-step generation at the
        serving geometry (reference _dummy_run, diffusion_engine.py:316-343).
        The step count is a dynamic loop bound (pipeline steps_bucket), so
        the 1-step warmup compiles the same executable real requests use."""
        t0 = time.perf_counter()
        modality = getattr(self.pipeline, "output_type", "image")
        if modality == "audio":
            sp = OmniDiffusionSamplingParams(
                num_inference_steps=1, guidance_scale=1.0, seed=0,
                extra={"seconds_total": 0.25},
            )
        else:
            mult = getattr(self.pipeline, "geometry_multiple", None)
            if mult is None:
                mult = (
                    self.pipeline.cfg.vae.spatial_ratio
                    * self.pipeline.cfg.dit.patch_size
                )
            h0, w0 = self.od_config.default_height, self.od_config.default_width
            if modality == "video":
                # Video warmup must not reuse the image default geometry:
                # frames * (H/mult) * (W/mult) latent tokens at 1024² with
                # CFG-doubled batch tried to allocate ~1.1 TiB (ADVICE
                # high, round 1). Warm the compile cache at a small spatial
                # size; serving geometries compile on first use like any
                # other shape bucket.
                h0 = min(h0, self.od_config.warmup_video_size)
                w0 = min(w0, self.od_config.warmup_video_size)
            height = max(mult, h0 // mult * mult)
            width = max(mult, w0 // mult * mult)
            sp = OmniDiffusionSamplingParams(
                height=height, width=width, num_inference_steps=1,
                guidance_scale=4.0, seed=0,
                num_frames=2 if modality == "video" else 1,
            )
            if getattr(self.pipeline, "needs_image_cond", False):
                # I2V / image-edit pipelines require a conditioning image
                import numpy as np

                sp.image = np.zeros((height, width, 3), np.uint8)
        self.pipeline.forward(OmniDiffusionRequest(
            prompt=["warmup"], sampling_params=sp))
        logger.info("Warmup done in %.1fs", time.perf_counter() - t0)

    # ------------------------------------------------------- sleep / wake
    # default weight-tree attributes; pipelines with extra trees (e.g.
    # GLM-Image's AR prior) declare their own ``param_attrs`` so sleep()
    # frees EVERYTHING
    _PARAM_ATTRS = ("dit_params", "text_params", "vae_params",
                    "vae_encoder_params", "decoder_params")

    def _param_attrs(self):
        return getattr(self.pipeline, "param_attrs", self._PARAM_ATTRS)

    @property
    def is_asleep(self) -> bool:
        return getattr(self, "_asleep", False)

    def sleep(self) -> None:
        """Offload every pipeline weight tree to host RAM, freeing HBM for
        sibling stages sharing the chip (reference: CuMemAllocator
        sleep/wake, diffusion/worker/diffusion_worker.py:204-271; the TPU
        host-offload row of SURVEY §2.10).  ``step`` refuses while asleep;
        ``wake`` restores the original device placement."""
        if self.is_asleep:
            return
        import numpy as np

        self._host_stash = {}
        for attr in self._param_attrs():
            tree = getattr(self.pipeline, attr, None)
            if tree is None:
                continue
            # device_get copies to host; dropping the pipeline reference
            # releases the HBM buffers
            self._host_stash[attr] = jax.tree_util.tree_map(
                lambda x: np.asarray(jax.device_get(x)), tree
            )
            setattr(self.pipeline, attr, None)
        # pipelines with DERIVED trees (e.g. Hunyuan's aliased shared
        # stack) drop them here so no stale device references survive
        hook = getattr(self.pipeline, "post_sleep", None)
        if hook is not None:
            hook()
        # fused LoRA trees + the base ref hold full DiT-sized device
        # buffers; drop them or the eviction is theater
        self.lora_manager.drop_device_state()
        self._asleep = True
        logger.info("engine asleep: %d weight trees offloaded to host",
                    len(self._host_stash))

    def wake(self) -> None:
        if not self.is_asleep:
            return
        place = getattr(self.pipeline, "_place", None)
        for attr, tree in self._host_stash.items():
            if place is not None:
                tree = place(tree, tp=(attr == "dit_params"))
            else:
                tree = jax.device_put(tree)
            setattr(self.pipeline, attr, tree)
        self._host_stash = {}
        self._asleep = False
        hook = getattr(self.pipeline, "post_wake", None)
        if hook is not None:
            hook()
        logger.info("engine awake: weights restored to device")

    def load_lora(self, path: str, name: Optional[str] = None) -> str:
        """Register a LoRA adapter (reference: DiffusionLoRAManager load,
        lora/manager.py:33)."""
        if self.od_config.quantization:
            raise ValueError(
                "LoRA fusion targets float weights; it cannot combine with "
                f"quantization={self.od_config.quantization!r}"
            )
        return self.lora_manager.load(path, name)

    def step(self, req: OmniDiffusionRequest) -> list[DiffusionOutput]:
        if self.is_asleep:
            raise RuntimeError(
                "engine is asleep (weights offloaded to host); call wake()"
            )
        t0 = time.perf_counter()
        # per-request LoRA activation via sampling extras (reference:
        # lora_manager.set_active_adapter, diffusion_worker.py:178-184)
        lora = req.sampling_params.extra.get("lora")
        base = self.pipeline.dit_params
        if lora and self.od_config.quantization:
            raise ValueError(
                "per-request LoRA cannot combine with quantized weights"
            )
        if lora:
            if isinstance(lora, str):
                name, scale, path = lora, 1.0, None
            else:
                name = lora.get("name")
                scale = lora.get("scale", 1.0)
                path = lora.get("path")
            from vllm_omni_tpu.diffusion.request import (
                InvalidRequestError,
            )

            if name is None:
                raise InvalidRequestError("lora request needs a 'name'")
            # serving-layer convenience (reference: per-request lora
            # {name, path, scale} through the Images API,
            # tests/e2e/online_serving/test_images_generations_lora.py):
            # unseen adapters load on first use from their path; the
            # SAME name with a DIFFERENT path reloads (serving the old
            # weights silently would be a trap)
            if path and (name not in self.lora_manager.adapter_names
                         or self.lora_manager.source_path(name) != path):
                self.load_lora(path, name)
            if name not in self.lora_manager.adapter_names:
                # a client naming typo is a 400, not a stage crash
                raise InvalidRequestError(
                    f"unknown lora adapter {name!r} (loaded: "
                    f"{self.lora_manager.adapter_names}); pass a 'path' "
                    "to load it")
            self.pipeline.dit_params = self.lora_manager.activate(
                base, name, scale
            )
        try:
            outs = self.pipeline.forward(req)
        finally:
            self.pipeline.dit_params = base
        dt = time.perf_counter() - t0
        self._num_batches += 1
        self._num_requests += len(outs)
        self._gen_seconds.observe(dt)
        for o in outs:
            o.metrics["gen_s"] = dt
        return outs

    @classmethod
    def make_engine(cls, od_config: OmniDiffusionConfig) -> "DiffusionEngine":
        return cls(od_config)
