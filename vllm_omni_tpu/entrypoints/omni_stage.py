"""OmniStage: one pipeline stage wrapping an engine.

Behavioral port of the reference's OmniStage (reference:
entrypoints/omni_stage.py:236 — config parse, worker spawn, submit/
try_collect, process_engine_inputs deriving next-stage inputs).  Where the
reference always spawns a worker process per stage, the TPU-native default
is **in-proc**: a stage is an engine object stepped by the orchestrator's
polling loop (one Python controller per host; pjit does the fan-out).
Process isolation across TPU slices arrives with the TCP connector — the
stage surface (submit / poll / collect) is transport-agnostic.

Engine selection mirrors stage_type (llm | diffusion) from the stage YAML
(reference: omni_stage.py:248-344).
"""

from __future__ import annotations

import importlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from vllm_omni_tpu.config.stage import StageConfig
from vllm_omni_tpu.introspection.phase_clock import startup
from vllm_omni_tpu.logger import init_logger
from vllm_omni_tpu.metrics.stats import StageRequestStats
from vllm_omni_tpu.outputs import OmniRequestOutput
from vllm_omni_tpu.sampling_params import SamplingParams
from vllm_omni_tpu.utils.tokenizer import stream_decoder

logger = init_logger(__name__)


@dataclass
class StageRequest:
    """Transport-level request entering a stage (the analogue of the
    reference's per-stage task dicts in _stage_worker)."""

    request_id: str
    # AR stages: token ids; diffusion stages: text prompt
    prompt_token_ids: Optional[list[int]] = None
    prompt: Optional[str] = None
    sampling_params: dict[str, Any] = field(default_factory=dict)
    prompt_embeds: Optional[Any] = None
    additional_information: dict[str, Any] = field(default_factory=dict)
    # raw media for multimodal thinker stages: {"image": [...], "audio":
    # [...]} — run through the stage's mm_processor at submit (reference:
    # multimodal chat messages -> OmniInputProcessor)
    multi_modal_data: Optional[dict[str, Any]] = None
    # per-request trace context ({"trace_id", "request_id"}), created at
    # Omni/AsyncOmni arrival and re-stamped on every stage handoff by the
    # orchestrator — a plain dict so it survives the stage_proc sockets
    # and connector edges through OmniSerializer (tracing/trace.py)
    trace: Optional[dict[str, Any]] = None
    # REMAINING end-to-end time budget in seconds, decremented by the
    # orchestrator on every stage handoff (resilience/deadline.py) — a
    # plain float for the same serialization reasons as ``trace``.
    # Receiving stages convert it to their own monotonic expiry and
    # enforce it at admission + every step; <= 0 means already expired.
    deadline_s: Optional[float] = None
    # the client asked for the reply as it is made: a stage that is the
    # pipeline's text output then yields, beside its final output, a
    # partial output a step (``OmniStage.poll``).  Any other stage, and a
    # stage in a process of its own, takes no notice of it.
    stream: bool = False


def _import_obj(path: str):
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


def _auto_llm_factory(model, stage_id: int):
    """Resolve an llm stage's model factory from a bare ``model`` path:
    a .gguf file takes the GGUF intake, a checkpoint directory resolves
    its architecture through OmniModelRegistry (the arch front door —
    reference: model resolution, model_executor/models/registry.py:65 +
    arg_utils.py:96-97 gguf load_format).  Returns (factory, args)."""
    import os

    if isinstance(model, str) and model.endswith(".gguf") \
            and os.path.isfile(model):
        from vllm_omni_tpu.model_loader.gguf_loader import load_gguf_lm

        return load_gguf_lm, {"model_dir": model}
    if isinstance(model, str) and os.path.isdir(model):
        from vllm_omni_tpu.config.stage import _arch_of
        from vllm_omni_tpu.models.registry import OmniModelRegistry

        arch = _arch_of(model)
        if arch and arch in OmniModelRegistry.supported():
            return (OmniModelRegistry.resolve(arch),
                    {"model_dir": model})
        raise ValueError(
            f"stage {stage_id}: architecture {arch!r} not in the AR "
            f"registry ({OmniModelRegistry.supported()}); set "
            "engine_args.model_factory explicitly")
    raise ValueError(
        f"stage {stage_id}: llm stages need engine_args.model_factory "
        "('pkg.mod:fn' -> (params, cfg, eos_id)) or a checkpoint "
        "path/.gguf in engine_args.model")


def _sp_equal(a: dict, b: dict) -> bool:
    """Value equality for merged sampling-param dicts, tolerating array
    values (conditioning tensors in ``extra``) that make plain dict ==
    raise on ambiguous truthiness."""
    if a.keys() != b.keys():
        return False
    for k in a:
        va, vb = a[k], b[k]
        if va is vb:
            continue
        if isinstance(va, dict) and isinstance(vb, dict):
            if not _sp_equal(va, vb):
                return False
            continue
        try:
            if bool(va == vb):
                continue
            return False
        except (ValueError, TypeError):
            import numpy as np

            if not (np.shape(va) == np.shape(vb)
                    and bool(np.all(np.asarray(va) == np.asarray(vb)))):
                return False
    return True


class OmniStage:
    #: whether the last ``poll()`` advanced the stage, finished outputs or
    #: none: its engine ran a step that dispatched or retired device work
    #: (a zero-scheduled tick did not), or a diffusion batch ran.  The
    #: orchestrator's loop waits only after a turn in which no stage did
    #: (AsyncOmni._loop_once).  The proxy of a stage in another process
    #: (ProcStage) polls an inbox, advances nothing here and leaves it so.
    advanced = False

    def __init__(self, config: StageConfig):
        self.config = config
        self.stage_id = config.stage_id
        self.tokenizer = None  # set for llm stages in _build_engine
        self.mm_processor = None  # multimodal front end (set in _build_engine)
        self.engine = self._build_engine()
        self._pending: list[StageRequest] = []
        self._done: list[OmniRequestOutput] = []
        self._input_processor = config.resolve_input_processor()
        self._submit_ts: dict[str, float] = {}
        self._trace_ctx: dict[str, dict] = {}
        self.request_stats: list[StageRequestStats] = []
        # request_id -> the incremental decoder of a streaming request's
        # reply (utils/tokenizer.py stream_decoder): the ids not yet
        # turned into text, until the request's final output
        self._stream_decoders: dict[str, Any] = {}
        # spans/metrics from the engine must carry the pipeline position
        if hasattr(self.engine, "stage_id"):
            self.engine.stage_id = self.stage_id
        from vllm_omni_tpu.metrics.profiler import StageProfiler

        self.profiler = StageProfiler(self.stage_id)

    # ----------------------------------------------------------- profiling
    def start_profile(self, trace_dir: str) -> None:
        """Begin a jax.profiler trace for this stage (reference:
        PROFILER_START task, omni_stage.py:740-777)."""
        self.profiler.start(trace_dir)

    def stop_profile(self) -> Optional[str]:
        """End the trace; returns the directory it landed in."""
        return self.profiler.stop()

    # -------------------------------------------------------- engine build
    def _build_engine(self):
        args = dict(self.config.engine_args)
        if self.config.stage_type == "llm":
            factory = args.pop("model_factory", None)
            if factory is None:
                # arch front door: a bare ``model`` path resolves its
                # loader from the checkpoint itself — a .gguf file via
                # the GGUF intake (reference: arg_utils.py:96-97), a
                # safetensors dir via config.json architectures
                # (OmniModelRegistry)
                factory, auto_args = _auto_llm_factory(
                    args.get("model"), self.stage_id)
                fa = args.get("model_factory_args") or {}
                fa.update(auto_args)
                args["model_factory_args"] = fa
            if isinstance(factory, str):
                factory = _import_obj(factory)
            factory_args = args.pop("model_factory_args", {}) or {}
            if factory_args.get("model_dir") == "required":
                # a SEPARATE checkpoint the user must supply (e.g. a
                # speech tokenizer) — fail with guidance instead of a
                # weight-coverage error against the wrong directory
                raise ValueError(
                    f"stage {self.stage_id} needs its own checkpoint "
                    "(separate from the model path) — set it with "
                    f"--stage-override '{self.stage_id}."
                    'model_factory_args={"model_dir": "/path"}\'')
            with startup.span("omni.setup.weights", stage=self.stage_id):
                params, model_cfg, eos = factory(**factory_args)
            # voice registry: engine_args.voices maps name -> conditioning
            # assets (speaker_embedding / reference_mel); the serving
            # layer advertises the names (/v1/audio/voices) and vocoder
            # models resolve them per request (batch_conditioning)
            voices = args.get("voices")
            if isinstance(voices, dict) and hasattr(model_cfg, "voices"):
                model_cfg.voices = {
                    name: entry for name, entry in voices.items()
                    if isinstance(entry, dict)
                }
            # multimodal front end (thinker stages): factory builds the
            # encoder+placeholder processor around the model's embed table
            # (reference: Qwen3OmniMoeThinkerMultiModalProcessor)
            mm_factory = args.pop("mm_processor", None)
            if mm_factory is not None:
                if isinstance(mm_factory, str):
                    mm_factory = _import_obj(mm_factory)
                self.mm_processor = mm_factory(
                    params, model_cfg,
                    **(args.pop("mm_processor_args", {}) or {}),
                )
            from vllm_omni_tpu.engine import EngineConfig, LLMEngine

            known = EngineConfig.__dataclass_fields__
            eng_kwargs = {k: v for k, v in args.items() if k in known}
            if isinstance(eng_kwargs.get("kv_transfer"), dict):
                from vllm_omni_tpu.core.scheduler import KVTransferConfig

                eng_kwargs["kv_transfer"] = KVTransferConfig(
                    **eng_kwargs["kv_transfer"]
                )
            # Tokenizer only where text crosses the boundary: entry stages
            # encode string prompts, text-final stages decode outputs.
            # Intermediate codec stages (talker) must NOT decode their token
            # ids into byte-garbage "text".
            is_text_stage = (
                -1 in self.config.engine_input_source
                or (self.config.final_output
                    and self.config.final_output_type == "text")
            )
            if is_text_stage and getattr(model_cfg, "vocab_size", None):
                from vllm_omni_tpu.utils.tokenizer import load_tokenizer

                self.tokenizer = load_tokenizer(
                    args.get("model"), model_cfg.vocab_size
                )
            # MTP draft head for spec decode (talker stages): factory
            # builds draft_fn around the backbone params
            draft_fn = None
            draft_factory = args.pop("draft_factory", None)
            if (draft_factory is not None
                    and eng_kwargs.get("num_speculative_tokens", 0) > 0):
                if isinstance(draft_factory, str):
                    draft_factory = _import_obj(draft_factory)
                with startup.span("omni.setup.draft", stage=self.stage_id):
                    draft_fn = draft_factory(
                        params, model_cfg,
                        eng_kwargs["num_speculative_tokens"],
                    )
            # EngineConfig(warmup=...) in the stage YAML precompiles the
            # bucketed executables inside LLMEngine.__init__ — before
            # the stage reports ready, so traffic never hits a compile
            # (its spans ``omni.setup.cache`` and ``.warmup`` open inside)
            with startup.span("omni.setup.engine", stage=self.stage_id):
                engine = LLMEngine(
                    params, model_cfg, EngineConfig(**eng_kwargs),
                    eos_token_id=eos, draft_fn=draft_fn)
            if engine.config.kv_transfer is not None:
                # extracted KV rides the stage output (D2H2D v1); the
                # consuming stage's input processor forwards it into
                # additional_information["kv_payload"] for injection
                from vllm_omni_tpu.distributed.kv_transfer import (
                    make_output_kv_sink,
                )

                engine.kv_transfer_sink = make_output_kv_sink()
            return engine
        elif self.config.stage_type == "diffusion":
            from vllm_omni_tpu.config.diffusion import OmniDiffusionConfig
            from vllm_omni_tpu.diffusion.engine import DiffusionEngine

            od = OmniDiffusionConfig.from_kwargs(**args)
            with startup.span("omni.setup.engine", stage=self.stage_id):
                return DiffusionEngine.make_engine(od)
        raise ValueError(f"unknown stage_type {self.config.stage_type!r}")

    # ------------------------------------------------------------- intake
    def submit(self, reqs: list[StageRequest]) -> None:
        now = time.perf_counter()
        for r in reqs:
            self._submit_ts[r.request_id] = now
            if r.trace:
                self._trace_ctx[r.request_id] = r.trace
        if self.config.stage_type == "llm":
            defaults = dict(self.config.default_sampling_params)
            for r in reqs:
                if (r.prompt_token_ids is None and r.prompt is not None
                        and self.tokenizer is not None):
                    r.prompt_token_ids = self.tokenizer.encode(r.prompt)
                sp_kwargs = {**defaults, **r.sampling_params}
                known = SamplingParams.__dataclass_fields__
                sp = SamplingParams(
                    **{k: v for k, v in sp_kwargs.items() if k in known}
                )
                mm_kwargs = {}
                if r.multi_modal_data and self.mm_processor is None:
                    # silently treating placeholders as ordinary text would
                    # produce wrong output — reject loudly instead
                    self.engine.add_errored_request(
                        r.request_id,
                        "request has multi_modal_data but this stage has "
                        "no mm_processor configured",
                    )
                    continue
                if r.multi_modal_data and self.mm_processor is not None:
                    try:
                        processed = self.mm_processor(
                            list(r.prompt_token_ids or []),
                            r.multi_modal_data,
                        )
                    except (ValueError, TypeError, KeyError) as e:
                        # one bad image/audio must not break batch-mates:
                        # surface as a per-request error output (same
                        # contract as scheduler intake rejection)
                        self.engine.add_errored_request(
                            r.request_id,
                            f"multimodal processing failed: {e}",
                        )
                        continue
                    r.prompt_token_ids = processed.prompt_token_ids
                    r.prompt_embeds = processed.prompt_embeds
                    mm_kwargs = dict(
                        mrope_positions=processed.mrope_positions,
                        mrope_delta=processed.mrope_delta,
                    )
                    ds = getattr(processed, "deepstack_embeds", None)
                    if ds is not None:
                        mm_kwargs["deepstack_embeds"] = ds
                info = dict(r.additional_information)
                if r.trace:
                    # engine-level spans (queue_wait/prefill/decode/
                    # sampling) key off the request's trace context
                    info["trace"] = dict(r.trace)
                # upstream-extracted KV prefix lands in this engine's cache
                # (receive half of the transfer manager)
                injected_kv = info.pop("kv_payload", None)
                from vllm_omni_tpu.resilience.deadline import expiry_ts

                self.engine.add_request(
                    list(r.prompt_token_ids or []), sp,
                    request_id=r.request_id,
                    prompt_embeds=r.prompt_embeds,
                    additional_information=info,
                    injected_kv=injected_kv,
                    # remaining budget -> this process's monotonic clock
                    deadline_ts=expiry_ts(r.deadline_s),
                    # partial outputs only where their text has a reader:
                    # the stage whose text is the pipeline's output
                    stream=r.stream and self._streams_text,
                    **mm_kwargs,
                )
        else:
            from vllm_omni_tpu.resilience.deadline import expiry_ts

            for r in reqs:
                # diffusion engines have no scheduler admission: the
                # batch assembly in _run_diffusion_batch enforces this
                r._deadline_ts = expiry_ts(r.deadline_s)
            self._pending.extend(reqs)

    @property
    def _streams_text(self) -> bool:
        return (self.tokenizer is not None and self.config.final_output
                and self.config.final_output_type == "text")

    def abort(self, request_id: str) -> None:
        """Drop a request whose reader has gone (a client that closed its
        stream, ``AsyncOmni.abort``): the engine frees its pages and no
        output of it follows.  On the thread that polls the stage."""
        if self.config.stage_type == "llm":
            self.engine.abort_request(request_id)
        else:
            self._pending = [r for r in self._pending
                             if r.request_id != request_id]
        self._stream_decoders.pop(request_id, None)
        self._submit_ts.pop(request_id, None)
        self._trace_ctx.pop(request_id, None)

    # -------------------------------------------------------------- drive
    def poll(self) -> list[OmniRequestOutput]:
        """Advance the stage's engine and return its new outputs: those
        of requests that finished (the in-proc analogue of try_collect,
        omni_stage.py:572) and, for a request that streams, a partial
        output a step whose text is what the step's tokens add to the
        reply.  The partials' texts joined are a prefix of the final's
        text (an incomplete trailing character is held back until it is
        whole), and the final's text is the whole reply as ever."""
        outs: list[OmniRequestOutput] = []
        self.advanced = False
        if self.config.stage_type == "llm":
            if self.engine.has_unfinished_requests:
                before = self.engine.work_steps
                outs = self.engine.step()
                self.advanced = self.engine.work_steps != before
        else:
            self.advanced = bool(self._pending)
            outs = self._run_diffusion_batch()
        decode_text = (self.tokenizer is not None
                       and self.config.final_output_type == "text")
        for o in outs:
            o.stage_id = self.stage_id
            if not o.finished:
                # a partial: the new ids' text, and nothing recorded
                dec = self._stream_decoders.get(o.request_id)
                if dec is None:
                    dec = self._stream_decoders[o.request_id] = \
                        stream_decoder(self.tokenizer)
                for c in o.outputs:
                    c.text = dec.feed(c.token_ids)
                continue
            if self._stream_decoders:
                self._stream_decoders.pop(o.request_id, None)
            if decode_text:
                for c in o.outputs:
                    if c.text is None:
                        c.text = self.tokenizer.decode(c.token_ids)
            self._record(o)
        return outs

    @property
    def has_unfinished(self) -> bool:
        if self.config.stage_type == "llm":
            return self.engine.has_unfinished_requests
        return bool(self._pending)

    def _merged_sp_kwargs(self, r: StageRequest) -> dict[str, Any]:
        # memoized per request: requests can sit in _pending across many
        # polls and the merge/compare runs in the hot polling loop
        cached = getattr(r, "_merged_sp", None)
        if cached is not None:
            return cached
        from vllm_omni_tpu.diffusion.request import OmniDiffusionSamplingParams

        defaults = dict(self.config.default_sampling_params)
        merged = {**defaults, **r.sampling_params}
        known = OmniDiffusionSamplingParams.__dataclass_fields__
        merged = {k: v for k, v in merged.items() if k in known}
        r._merged_sp = merged
        return merged

    def _run_diffusion_batch(self) -> list[OmniRequestOutput]:
        if not self._pending:
            return []
        from vllm_omni_tpu.resilience.deadline import (
            deadline_output,
            expired,
        )

        # deadline enforcement at batch assembly (the diffusion analogue
        # of scheduler admission): a queued request whose budget ran out
        # terminates as deadline_exceeded instead of burning a full
        # denoising run
        live, dead = [], []
        for r in self._pending:
            (dead if expired(getattr(r, "_deadline_ts", None))
             else live).append(r)
        if dead:
            # poll() records these like any other batch outcome
            self._pending = live
            return [deadline_output(r.request_id, self.stage_id,
                                    "expired in diffusion queue")
                    for r in dead]
        from vllm_omni_tpu.diffusion.request import (
            OmniDiffusionRequest,
            OmniDiffusionSamplingParams,
        )

        # Batch only requests whose effective sampling params match the
        # head request's — a diffusion batch shares one geometry/steps/seed
        # (reference batches under the identical-sampling-params constraint,
        # omni_stage.py:797-843; ADVICE r1 medium: batching mixed params
        # silently applied the first request's params to all). Plain dict
        # equality, not a repr key: repr truncates large arrays and is
        # insertion-order sensitive.
        merged = [self._merged_sp_kwargs(r) for r in self._pending]
        head = merged[0]
        batch: list[StageRequest] = []
        rest: list[StageRequest] = []
        limit = max(1, self.config.runtime.max_batch_size)
        for r, m in zip(self._pending, merged):
            if len(batch) < limit and _sp_equal(m, head):
                batch.append(r)
            else:
                rest.append(r)
        self._pending = rest
        sp = OmniDiffusionSamplingParams(**head)
        req = OmniDiffusionRequest(
            prompt=[r.prompt or "" for r in batch],
            sampling_params=sp,
            request_ids=[r.request_id for r in batch],
        )
        t0, w0 = time.perf_counter(), time.time()
        try:
            diff_outs = self.engine.step(req)
        except Exception as e:
            # Scope the failure to this batch's requests (ADVICE r1 low:
            # a poll exception must not take down unrelated streams).
            logger.exception(
                "stage %d: diffusion batch failed (%d reqs)",
                self.stage_id, len(batch),
            )
            from vllm_omni_tpu.diffusion.request import InvalidRequestError

            kind = ("invalid_request" if isinstance(e, InvalidRequestError)
                    else "internal")
            return [
                OmniRequestOutput.from_error(
                    r.request_id, f"{type(e).__name__}: {e}",
                    stage_id=self.stage_id, kind=kind,
                )
                for r in batch
            ]
        dur = time.perf_counter() - t0
        from vllm_omni_tpu.tracing import get_recorder

        rec = get_recorder()
        for r in batch:
            rec.record(r.trace, "diffusion_generate", w0, dur,
                       stage_id=self.stage_id,
                       args={"batch": len(batch),
                             "steps": sp.num_inference_steps})
        return [
            OmniRequestOutput.from_diffusion(
                o.request_id, [o.data], final_output_type=o.output_type
            )
            for o in diff_outs
        ]

    # --------------------------------------------- next-stage input derive
    def process_engine_inputs(
        self, upstream_outputs: list[OmniRequestOutput]
    ) -> list[StageRequest]:
        """Derive this stage's inputs from upstream outputs (reference:
        omni_stage.py:585-634; default: prev output token ids become the
        next prompt, custom fn hook for model-specific wiring)."""
        if self._input_processor is not None:
            return self._input_processor(self.config, upstream_outputs)
        reqs = []
        for out in upstream_outputs:
            token_ids = out.outputs[0].token_ids if out.outputs else []
            text = out.outputs[0].text if out.outputs else None
            reqs.append(StageRequest(
                request_id=out.request_id,
                prompt_token_ids=list(token_ids),
                prompt=text,
            ))
        return reqs

    # ------------------------------------------------------------- metrics
    def engine_metrics_snapshot(self) -> dict:
        """Step-level engine metrics for /metrics; {} when the engine
        exposes none (ProcStage overrides with the worker's last shipped
        snapshot)."""
        fn = getattr(self.engine, "metrics_snapshot", None)
        return fn() if fn is not None else {}

    def _record(self, out: OmniRequestOutput) -> None:
        t0 = self._submit_ts.pop(out.request_id, None)
        gen_ms = (time.perf_counter() - t0) * 1e3 if t0 else 0.0
        ctx = self._trace_ctx.pop(out.request_id, None)
        if ctx is not None:
            # stage-granularity span: submit to output (covers queue +
            # compute; for proc stages it additionally covers transport)
            from vllm_omni_tpu.tracing import get_recorder

            get_recorder().record(
                ctx, "stage", time.time() - gen_ms / 1e3, gen_ms / 1e3,
                stage_id=self.stage_id, cat="stage",
                args={"tokens_out": sum(len(c.token_ids)
                                        for c in out.outputs)},
            )
        self.request_stats.append(StageRequestStats(
            request_id=out.request_id,
            stage_id=self.stage_id,
            tokens_in=len(out.prompt_token_ids),
            tokens_out=sum(len(c.token_ids) for c in out.outputs),
            gen_ms=gen_ms,
        ))
