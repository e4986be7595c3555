"""OpenAI-compatible API server.

Behavioral port of the reference's FastAPI server (reference:
entrypoints/openai/api_server.py:107 — /v1/chat/completions:729,
/v1/images/generations:935, /health:860, /v1/models:896, audio speech:805)
on the standard library's threading HTTP server: the runtime ships zero
web-framework dependencies, matching the native-runtime stance (handler
threads submit into AsyncOmni's event loop and stream SSE chunks back).

Run: ``python -m vllm_omni_tpu.entrypoints.cli serve <model> [--port]``.
"""

from __future__ import annotations

import asyncio
import base64
import io
import json
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional

import numpy as np

from vllm_omni_tpu.entrypoints.async_omni import AsyncOmni
from vllm_omni_tpu.entrypoints.openai.chat_stream import (
    ChatStream,
    FlushQueue,
)
from vllm_omni_tpu.introspection.phase_clock import startup
from vllm_omni_tpu.logger import init_logger
from vllm_omni_tpu.version import __version__

logger = init_logger(__name__)


class ServerState:
    """Owns the AsyncOmni engine + the asyncio loop it streams on."""

    def __init__(self, omni: AsyncOmni, model_name: str):
        self.omni = omni
        self.model_name = model_name
        self.loop = asyncio.new_event_loop()
        self._loop_thread = threading.Thread(
            target=self.loop.run_forever, daemon=True, name="omni-asyncio"
        )
        self._loop_thread.start()
        # the streamed replies with a delta to write (chat_stream.py):
        # that loop's thread writes them in one pass
        self.flushes = FlushQueue()

    def shutdown(self):
        stream = self.omni.metrics.stream
        if stream.requests_total:
            first = stream.first_event_ms.snapshot()
            logger.info(
                "streams: %d requests, %d events, %d tokens; first token "
                "to first event p50 %.2f ms, p99 %.2f ms",
                stream.requests_total, stream.events_total,
                stream.tokens_total, first["p50"], first["p99"])
        self.omni.shutdown()
        self.loop.call_soon_threadsafe(self.loop.stop)

    def entry_tokenizer(self):
        """Entry stage's tokenizer (chat-template source), if any.
        Process-disaggregated entry stages keep their tokenizer in the
        worker — chat falls back to the plain transcript there (warned
        once so the divergence from in-proc deployments is visible)."""
        for stage in self.omni._omni.stages:
            if -1 in stage.config.engine_input_source:
                if (stage.tokenizer is None
                        and stage.config.runtime.process
                        and not getattr(self, "_warned_proc_tok", False)):
                    self._warned_proc_tok = True
                    logger.warning(
                        "entry stage runs in a worker process; chat "
                        "templates are not applied (plain transcript)"
                    )
                return stage.tokenizer
        return None

    # ---------------------------------------------------------- bridging
    def collect(self, prompt, sampling_params, request_id=None) -> list:
        """Run one request to completion, returning all final outputs."""

        async def _run():
            outs = []
            async for o in self.omni.generate(prompt, sampling_params,
                                              request_id):
                outs.append(o)
            return outs

        return asyncio.run_coroutine_threadsafe(_run(), self.loop).result()

    def stream(self, prompt, sampling_params, request_id, sink) -> None:
        """Run one request that streams (``AsyncOmni.generate_to``):
        every output (a partial output a step, then the finals; an
        exception that ends it) goes to ``sink.feed`` and then
        ``sink.close()`` is called, both on this loop's thread, which so
        is the one writer of every stream
        (entrypoints/openai/chat_stream.py).  Returns at once."""

        async def _run():
            try:
                await self.omni.generate_to(sink, prompt, sampling_params,
                                            request_id, stream=True)
            except Exception as e:  # never admitted: the sink's refusal
                sink.feed(e)
                sink.close()

        asyncio.run_coroutine_threadsafe(_run(), self.loop)

    def collect_many(self, jobs: list[tuple]) -> list[list]:
        """Run several (prompt, sampling_params, request_id) jobs
        concurrently so batching stages can batch them."""

        async def _run_all():
            async def one(prompt, sp, rid):
                outs = []
                async for o in self.omni.generate(prompt, sp, rid):
                    outs.append(o)
                return outs

            return await asyncio.gather(*(one(*j) for j in jobs))

        return asyncio.run_coroutine_threadsafe(_run_all(),
                                                self.loop).result()


def _decode_image_part(part: dict) -> np.ndarray:
    """OpenAI image_url content part -> [H, W, 3] uint8 (data: URLs with
    base64 PNG, or raw base64)."""
    url = part.get("image_url", {})
    if isinstance(url, dict):
        url = url.get("url", "")
    if url.startswith("data:"):
        b64 = url.partition(",")[2]
    else:
        b64 = url
    raw = base64.b64decode(b64)
    from PIL import Image

    img = Image.open(io.BytesIO(raw)).convert("RGB")
    return np.asarray(img)


def _decode_audio_part(part: dict) -> np.ndarray:
    """OpenAI input_audio content part -> 1-D float32 waveform.
    Formats: "wav" (stdlib wave, 16-bit PCM) or "f32le" (raw floats)."""
    spec = part.get("input_audio", {})
    raw = base64.b64decode(spec.get("data", ""))
    fmt = spec.get("format", "wav")
    if fmt == "f32le":
        return np.frombuffer(raw, np.float32).copy()
    if fmt == "wav":
        import wave

        with wave.open(io.BytesIO(raw)) as w:
            frames = w.readframes(w.getnframes())
            width = w.getsampwidth()
        if width == 2:
            return (np.frombuffer(frames, np.int16)
                    .astype(np.float32) / 32768.0)
        raise ValueError(f"unsupported wav sample width {width}")
    raise ValueError(f"unsupported audio format {fmt!r}")


def _chat_prompt_from_messages(
    messages: list[dict], tokenizer=None
) -> tuple[str, dict]:
    """Chat templating + multimodal content extraction.

    Returns (prompt_text, multi_modal_data).  Image/audio content parts
    (OpenAI ``image_url`` / ``input_audio``) are decoded into arrays and a
    textual placeholder marks their position; the stage's mm processor
    expands markers into encoder tokens (reference: _preprocess_chat with
    mm data, serving_chat.py:335).  An HF tokenizer with a chat template
    formats the transcript; the byte-tokenizer path uses a plain
    role-tagged transcript."""
    mm: dict[str, list] = {}
    norm_messages = []
    for m in messages:
        content = m.get("content", "")
        if isinstance(content, list):  # multimodal content parts
            text_parts = []
            for c in content:
                t = c.get("type")
                if t == "text":
                    text_parts.append(c.get("text", ""))
                elif t == "image_url":
                    mm.setdefault("image", []).append(_decode_image_part(c))
                elif t == "input_audio":
                    mm.setdefault("audio", []).append(_decode_audio_part(c))
            content = " ".join(text_parts)
        norm_messages.append({"role": m.get("role", "user"),
                              "content": content})
    if tokenizer is not None and hasattr(tokenizer, "apply_chat_template") \
            and getattr(tokenizer, "chat_template", None):
        prompt = tokenizer.apply_chat_template(
            norm_messages, tokenize=False, add_generation_prompt=True)
    else:
        parts = [f"{m['role']}: {m['content']}" for m in norm_messages]
        parts.append("assistant:")
        prompt = "\n".join(parts)
    return prompt, mm


def _sampling_from_body(body: dict) -> dict:
    sp = {}
    # explicit nulls mean "unset" per OpenAI semantics
    max_toks = body.get("max_completion_tokens")
    if max_toks is None:
        max_toks = body.get("max_tokens")
    if max_toks is not None:
        sp["max_tokens"] = max_toks
    for k in ("temperature", "top_p", "top_k", "seed"):
        if body.get(k) is not None:
            sp[k] = body[k]
    # extension (vLLM ships the same one): benchmark clients pin the
    # output length so token accounting is exact
    if body.get("ignore_eos") is not None:
        sp["ignore_eos"] = bool(body["ignore_eos"])
    # OpenAI logprobs: chat sends a boolean + optional top_logprobs
    # count; legacy /v1/completions sends an integer count directly
    lp = body.get("logprobs")
    if lp is not None and lp is not False:
        # chat: logprobs=true + top_logprobs=k; legacy completions:
        # logprobs=k directly (0 is valid — chosen-token logprob only)
        k = int(lp) if not isinstance(lp, bool) \
            else int(body.get("top_logprobs") or 0)
        if not 0 <= k <= 20:
            raise ValueError("top_logprobs must be within [0, 20]")
        sp["logprobs"] = k
    return sp


# SSE audio delta granularity (samples per chunk; 12000 ≈ 0.5s @ 24kHz)
_AUDIO_CHUNK_SAMPLES = 12000


def _b64_png(img: np.ndarray) -> str:
    """uint8 [H, W, 3] -> base64 PNG (PIL if present, raw fallback)."""
    try:
        from PIL import Image

        buf = io.BytesIO()
        Image.fromarray(img).save(buf, format="PNG")
        return base64.b64encode(buf.getvalue()).decode()
    except ImportError:
        return base64.b64encode(img.tobytes()).decode()


class OmniRequestHandler(BaseHTTPRequestHandler):
    state: ServerState  # injected via server class attribute
    protocol_version = "HTTP/1.1"

    # --------------------------------------------------------------- utils
    def log_message(self, fmt, *args):
        logger.debug("%s %s", self.address_string(), fmt % args)

    def _json(self, code: int, obj: dict, default=None):
        # ``default``: encoder fallback for the /debug family, whose
        # duck-typed snapshots may carry numpy scalars etc.
        data = json.dumps(obj, default=default).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def _error(self, code: int, message: str, etype: str = "invalid_request_error"):
        self._json(code, {"error": {"message": message, "type": etype}})

    # error kind -> (HTTP status, OpenAI error type).  The taxonomy
    # (docs/serving.md): 400 = the client's fault; 429 "shed" =
    # admission control refused a HEALTHY server at capacity (back off,
    # then retry — the load harness maps the knee of the serving curve
    # off this status); 503 "retryable" = infra broke before any output
    # (idempotent resubmit ok); 504 = the time budget was spent
    # (response abandoned); anything else is a 500.
    _ERROR_KIND_HTTP = {
        "invalid_request": (400, "invalid_request_error"),
        "shed": (429, "overloaded"),
        "deadline_exceeded": (504, "deadline_exceeded"),
        "retryable": (503, "retryable_error"),
    }

    def _surface_error(self, outs) -> bool:
        """If any pipeline output is errored, reply with an OpenAI-style
        error (instead of HTTP 200 with an empty/garbage payload) and
        return True.  Validation failures (ValueError) map to 400."""
        err = next((o for o in outs if o.is_error), None)
        if err is None:
            return False
        msg = err.error_message or "request failed"
        code, etype = self._ERROR_KIND_HTTP.get(
            err.error_kind, (500, "internal_error"))
        self._error(code, msg, etype)
        return True

    def _tenant_info(self) -> dict:
        """Per-tenant metrics attribution + WFQ weight: the
        ``x-omni-tenant`` header rides request metadata
        (additional_information["tenant"]) into the engine, labeling
        the SLO/goodput/queue-depth series on /metrics so fleet
        dashboards can split the serving curve per tenant
        (docs/load_testing.md); ``x-omni-priority`` rides alongside it
        into ``Request.priority`` — the deficit-round-robin weight of
        the WFQ overload scheduler (docs/control_plane.md).  Both are
        CLIENT input: sanitized/clamped at the Request property, never
        trusted here."""
        info = {"accept_mono": self._accept_mono}
        # bound the RAW bytes at ingress (OL10 first-harvest): the
        # values ride request metadata — and every stage-handoff
        # serialization — until the Request properties sanitize at
        # use, so a megabyte header must not be carried that far.
        # Semantic sanitization stays where it was: sanitize_tenant
        # caps the label at 64 chars, sanitize_priority clamps [1, 8]
        tenant = self.headers.get("x-omni-tenant")
        if tenant:
            info["tenant"] = tenant[:256]
        priority = self.headers.get("x-omni-priority")
        if priority:
            info["priority"] = priority[:64]
        # external trace join (tracing/journey.py): a W3C traceparent
        # or x-omni-trace-id header continues the CALLER's trace id
        # through this request's journey spans — validated/bounded
        # client input; the orchestrator mints the context at arrival
        from vllm_omni_tpu.tracing import inbound_trace_id

        tid = inbound_trace_id(self.headers)
        if tid:
            info["trace_id"] = tid
        return info

    def _body(self) -> dict:
        length = int(self.headers.get("Content-Length", 0))
        if length == 0:
            return {}
        return json.loads(self.rfile.read(length))

    def _sse_start(self):
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

    def _sse_end(self):
        self.wfile.write(b"0\r\n\r\n")

    # --------------------------------------------------------------- GET
    def do_GET(self):
        from urllib.parse import parse_qs, urlsplit

        parts = urlsplit(self.path)
        if parts.path == "/metrics":
            return self._metrics(parse_qs(parts.query))
        if parts.path.startswith("/debug/"):
            return self._debugz(parts.path, parse_qs(parts.query))
        if self.path == "/health":
            self._health()
        elif self.path == "/v1/models":
            self._json(200, {
                "object": "list",
                "data": [{
                    "id": self.state.model_name,
                    "object": "model",
                    "owned_by": "vllm-omni-tpu",
                    "max_model_len": None,
                }],
            })
        elif self.path == "/v1/audio/voices":
            # voices declared by the stage config (reference:
            # /v1/audio/voices, api_server.py:833)
            voices = []
            for stage in self.state.omni._omni.stages:
                voices.extend(stage.config.engine_args.get("voices", ()))
            self._json(200, {"voices": voices or ["default"]})
        elif self.path == "/version":
            self._json(200, {"version": __version__})
        else:
            self._error(404, f"unknown path {self.path}")

    def _health(self):
        """Honest /health (docs/debugging.md): last-step age + engine
        liveness, 503 once the stall watchdog has tripped or the engine
        loop died — so a load balancer ejects a wedged replica instead
        of the static "ok" feeding it traffic forever."""
        from vllm_omni_tpu.introspection.debugz import health_snapshot

        omni = getattr(self.state.omni, "_omni", self.state.omni)
        alive = getattr(self.state.omni, "engine_thread_alive", None)
        code, body = health_snapshot(omni, engine_thread_alive=alive)
        self._json(code, body)

    def _debugz(self, path: str, query: dict):
        """``/debug/z`` introspection family (docs/debugging.md): live
        JSON views of engines, requests, KV occupancy, the flight-
        recorder ring, thread stacks, and the watchdog.  Read-only."""
        from vllm_omni_tpu.introspection import debugz

        omni = getattr(self.state.omni, "_omni", self.state.omni)
        if path == "/debug/z":
            return self._json(200, debugz.debug_index(), default=str)
        if path == "/debug/engine":
            return self._json(200, debugz.debug_engine(omni),
                              default=str)
        if path == "/debug/requests":
            return self._json(200, debugz.debug_requests(omni),
                              default=str)
        if path == "/debug/kv":
            return self._json(200, debugz.debug_kv(omni), default=str)
        if path == "/debug/flightrecorder":
            try:
                tail = int(query.get("n", [0])[0]) or None
            except (TypeError, ValueError):
                return self._error(400, "n must be an integer")
            return self._json(
                200, debugz.debug_flightrecorder(omni, tail=tail),
                default=str)
        if path == "/debug/stacks":
            return self._json(200, debugz.debug_stacks(), default=str)
        if path == "/debug/watchdog":
            return self._json(200, debugz.debug_watchdog(omni),
                              default=str)
        if path == "/debug/disagg":
            # disagg-router view (docs/disaggregation.md): replica
            # health/drain state, in-flight request phases, failover
            # ledger; {"enabled": false} on non-disagg deployments
            return self._json(200, debugz.debug_disagg(omni),
                              default=str)
        if path == "/debug/controlplane":
            # control-plane view (docs/control_plane.md): sensors,
            # in-flight re-role/scale operation, action ring;
            # {"enabled": false} on uncontrolled deployments
            return self._json(200, debugz.debug_controlplane(omni),
                              default=str)
        if path == "/debug/alerts":
            # omnipulse rule states + transition ring + dump-cooldown
            # self-view ({"enabled": false} without an alert engine)
            return self._json(200, debugz.debug_alerts(omni),
                              default=str)
        if path == "/debug/tenants":
            # per-stage heavy-hitter attribution boards (top-k per
            # consumption meter, with error bounds)
            return self._json(200, debugz.debug_tenants(omni),
                              default=str)
        if path == "/debug/cache":
            # fleet cache-economics board (docs/disaggregation.md):
            # replica digests, duplicated prefixes, regret ledger;
            # {"enabled": false} on non-disagg deployments
            return self._json(200, debugz.debug_cache(omni),
                              default=str)
        if path == "/debug/trace":
            # trace-layer self-view (docs/observability.md): recorder
            # occupancy, spans_dropped, writer paths, last export
            return self._json(200, debugz.debug_trace(omni),
                              default=str)
        return self._error(404, f"unknown debug path {path}; "
                           f"see /debug/z")

    def _metrics(self, query: dict):
        """``GET /metrics``: Prometheus text exposition (the scrape
        surface); ``/metrics?format=json`` keeps the JSON summary."""
        omni = getattr(self.state.omni, "_omni", self.state.omni)
        # device memory snapshot (per-process accounting analogue,
        # reference: worker/gpu_memory_utils.py NVML probes) — only when
        # this process runs a stage itself: the parent of an all-process
        # pipeline must never touch JAX, or it would take the chip its
        # workers hold (one process per chip)
        device = None
        if any(not s.config.runtime.process for s in omni.stages):
            from vllm_omni_tpu.platforms import current_platform

            p = current_platform()
            device = {
                "platform": p.name,
                "kind": p.device_kind(),
                "hbm_bytes": p.hbm_bytes(),
            }
        if query.get("format", ["prometheus"])[0] == "json":
            summary = (omni.stats_summary()
                       if hasattr(omni, "stats_summary")
                       else omni.metrics.summary())
            summary["device"] = device
            return self._json(200, summary)
        from vllm_omni_tpu.metrics.prometheus import render_from_omni

        data = render_from_omni(omni, device=device).encode()
        self.send_response(200)
        self.send_header("Content-Type",
                         "text/plain; version=0.0.4; charset=utf-8")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    # --------------------------------------------------------------- POST
    def do_POST(self):
        # stamped before the body is read: ``intake_ms`` (engine arrival
        # minus this, docs/observability.md) then covers the handler too
        self._accept_mono = time.monotonic()
        try:
            body = self._body()
        except (json.JSONDecodeError, ValueError) as e:
            return self._error(400, f"bad JSON: {e}")
        try:
            if self.path in ("/start_profile", "/stop_profile"):
                # gated + server-chosen directory (the reference gates the
                # torch-profiler endpoint behind VLLM_TORCH_PROFILER_DIR
                # the same way): a client must never control filesystem
                # paths or toggle tracing on an ungated server
                from vllm_omni_tpu import envs

                trace_dir = envs.OMNI_TPU_PROFILER_DIR
                if not trace_dir:
                    return self._error(
                        403,
                        "profiling disabled: set OMNI_TPU_PROFILER_DIR "
                        "on the server to enable",
                    )
                if self.path == "/start_profile":
                    self.state.omni.start_profile(trace_dir)
                    self._json(200, {"status": "profiling",
                                     "trace_dir": trace_dir})
                else:
                    landed = self.state.omni.stop_profile()
                    self._json(200, {"status": "stopped",
                                     "trace_dir": landed or trace_dir})
            elif self.path == "/v1/chat/completions":
                self._chat_completions(body)
            elif self.path == "/v1/completions":
                self._completions(body)
            elif self.path == "/v1/images/edits":
                self._images_edits(body)
            elif self.path == "/v1/images/generations":
                self._images_generations(body)
            elif self.path == "/v1/audio/speech":
                self._audio_speech(body)
            elif self.path == "/v1/videos":
                self._videos(body)
            else:
                self._error(404, f"unknown path {self.path}")
        except BrokenPipeError:
            pass
        except Exception as e:
            logger.exception("request failed")
            try:
                self._error(500, str(e), "internal_error")
            except Exception:
                pass

    # ------------------------------------------------------ chat/completions
    def _chat_completions(self, body: dict):
        messages = body.get("messages")
        if not messages:
            return self._error(400, "messages required")
        try:
            prompt_text, mm = _chat_prompt_from_messages(
                messages, tokenizer=self.state.entry_tokenizer())
        except Exception as e:
            # any failure decoding client-supplied content (corrupt wav ->
            # wave.Error, non-string url -> AttributeError, bad base64,
            # ...) is the client's fault, never a 500
            return self._error(400, f"bad multimodal content: {e}")
        info = self._tenant_info()
        if mm or info:
            prompt: Any = {"prompt": prompt_text}
            if mm:
                prompt["multi_modal_data"] = mm
            if info:
                prompt["additional_information"] = info
        else:
            prompt = prompt_text
        try:
            sp = _sampling_from_body(body)
        except ValueError as e:
            return self._error(400, str(e))
        rid = f"chatcmpl-{uuid.uuid4().hex[:16]}"
        created = int(time.time())
        try:
            n = int(body.get("n") or 1)
        except (TypeError, ValueError):
            return self._error(400, "n must be an integer")
        if not 1 <= n <= 16:
            return self._error(400, "n must be within [1, 16]")
        if body.get("stream"):
            if n > 1:
                return self._error(400, "streaming with n > 1 is not "
                                   "supported")
            # the reply leaves as it is made: the loop's thread writes an
            # event a step while this thread sleeps, and this thread
            # writes the rest (chat_stream.py).  The FIRST item decides
            # the response: an error before any output (shed at
            # admission, expired deadline, invalid prompt) still gets its
            # REAL HTTP status
            opts = body.get("stream_options")
            reply = ChatStream(
                self, rid, created,
                include_usage=bool(isinstance(opts, dict)
                                   and opts.get("include_usage")))
            self.state.stream(prompt, sp, rid, reply)
            reply.done.wait()
            reply.finish()
            return
        # n choices fan out as independent requests with distinct seeds
        # (vLLM n>1 semantics; batching stages batch them) — n == 1 is
        # the one-job case of the same loop
        base_seed = sp.get("seed")
        jobs = []
        for i in range(n):
            spi = dict(sp)
            if base_seed is not None and n > 1:
                spi["seed"] = int(base_seed) + i
            jobs.append((prompt, spi, rid if n == 1 else f"{rid}-{i}"))
        all_outs = self.state.collect_many(jobs)
        choices = []
        n_prompt = n_out = 0
        for i, outs in enumerate(all_outs):
            if self._surface_error(outs):
                return
            text_out = next(
                (o for o in outs if o.final_output_type == "text"),
                outs[0] if outs else None)
            if text_out is None:
                return self._error(500, "pipeline produced no output",
                                   "internal_error")
            message: dict[str, Any] = {
                "role": "assistant",
                "content": (text_out.outputs[0].text
                            if text_out.outputs else None),
            }
            # multimodal finals ride OpenAI-style audio/images extensions
            # (reference: audio/image choices, serving_chat.py:1617,1683)
            for o in outs:
                if o.final_output_type == "audio" \
                        and "audio" in o.multimodal_output:
                    wav = np.asarray(o.multimodal_output["audio"],
                                     np.float32)
                    message["audio"] = {
                        "id": f"audio-{rid}-{i}",
                        "data": base64.b64encode(wav.tobytes()).decode(),
                        "format": "f32le",
                    }
                elif o.final_output_type == "image" and o.images:
                    message["images"] = [
                        {"b64_json": _b64_png(np.asarray(img))}
                        for img in o.images
                    ]
            n_prompt = len(text_out.prompt_token_ids)
            n_out += sum(len(c.token_ids) for c in text_out.outputs)
            choice = {
                "index": i,
                "message": message,
                "finish_reason": (text_out.outputs[0].finish_reason
                                  if text_out.outputs else None),
            }
            lp = (text_out.outputs[0].logprobs
                  if text_out.outputs else None)
            if lp is not None:
                choice["logprobs"] = {"content": self._logprob_content(
                    text_out.outputs[0].token_ids, lp)}
            choices.append(choice)
        self._json(200, {
            "id": rid,
            "object": "chat.completion",
            "created": created,
            "model": body.get("model", self.state.model_name),
            "choices": choices,
            "usage": {
                "prompt_tokens": n_prompt,
                "completion_tokens": n_out,
                "total_tokens": n_prompt + n_out,
            },
        })

    def _logprob_content(self, token_ids, entries) -> list:
        """Runner logprob entries -> OpenAI response shape, tokens
        decoded through the entry tokenizer when available."""
        tok = self.state.entry_tokenizer()

        def decode(tid):
            if tok is None:
                return str(tid)
            try:
                # convert_ids_to_tokens keeps partial-UTF8 BPE pieces
                # faithful (decode() would emit U+FFFD for them)
                if hasattr(tok, "convert_ids_to_tokens"):
                    return tok.convert_ids_to_tokens([int(tid)])[0]
                return tok.decode([int(tid)])
            except Exception:
                return str(tid)

        content = []
        for tid, e in zip(token_ids, entries):
            content.append({
                "token": decode(tid),
                "logprob": e["logprob"],
                "top_logprobs": [
                    {"token": decode(i), "logprob": v}
                    for i, v in zip(e["top_ids"], e["top_logprobs"])
                ],
            })
        return content

    def _chat_chunks(self, out, rid: str, created: int):
        """The events of a final output that is no text (a text output's
        are ``ChatStream``'s)."""
        base = {
            "id": rid,
            "object": "chat.completion.chunk",
            "created": created,
            "model": self.state.model_name,
        }
        if out.final_output_type == "audio" and "audio" in out.multimodal_output:
            # stream the waveform in bounded chunks so playback can start
            # before the full clip is serialized (reference: chunked audio
            # deltas, serving_chat.py:539 + chunk adapter)
            wav = np.asarray(out.multimodal_output["audio"], np.float32)
            chunk = max(1, _AUDIO_CHUNK_SAMPLES)
            for lo in range(0, len(wav), chunk):
                yield {**base, "choices": [{
                    "index": 0,
                    "delta": {"audio": {
                        "data": base64.b64encode(
                            wav[lo: lo + chunk].tobytes()).decode(),
                        "format": "f32le",
                    }},
                    "finish_reason": None,
                }]}

    # ---------------------------------------------------------- completions
    def _completions(self, body: dict):
        prompt = body.get("prompt")
        if prompt is None:
            return self._error(400, "prompt required")
        # OpenAI prompt forms: str | [str, ...] | [int, ...] (token ids)
        if isinstance(prompt, str):
            prompts = [prompt]
        elif isinstance(prompt, list) and prompt and all(
                isinstance(p, str) for p in prompt):
            prompts = prompt
        elif isinstance(prompt, list) and all(
                isinstance(p, int) for p in prompt):
            prompts = [prompt]
        else:
            return self._error(400, "prompt must be a string, list of "
                               "strings, or list of token ids")
        try:
            sp = _sampling_from_body(body)
        except ValueError as e:
            return self._error(400, str(e))
        rid = f"cmpl-{uuid.uuid4().hex[:16]}"
        info = self._tenant_info()

        def _wrap(p):
            # tenant attribution rides the dict prompt form; a fresh
            # info dict per job (mutable metadata must not be shared)
            if not info:
                return p
            if isinstance(p, str):
                return {"prompt": p, "additional_information": dict(info)}
            return {"prompt_token_ids": list(p),
                    "additional_information": dict(info)}

        jobs = [(_wrap(p), sp, f"{rid}-{i}")
                for i, p in enumerate(prompts)]
        all_outs = self.state.collect_many(jobs)
        choices = []
        for i, outs in enumerate(all_outs):
            if self._surface_error(outs):
                return
            text_out = next(
                (o for o in outs if o.final_output_type == "text"), None)
            if text_out is None:
                return self._error(500, "no text output", "internal_error")
            choice = {
                "index": i,
                "text": text_out.outputs[0].text,
                "finish_reason": text_out.outputs[0].finish_reason,
            }
            entries = text_out.outputs[0].logprobs
            if entries is not None:
                content = self._logprob_content(
                    text_out.outputs[0].token_ids, entries)
                choice["logprobs"] = {  # legacy completions shape
                    "tokens": [c["token"] for c in content],
                    "token_logprobs": [c["logprob"] for c in content],
                    "top_logprobs": [
                        {t["token"]: t["logprob"]
                         for t in c["top_logprobs"]}
                        for c in content],
                    "text_offset": [],
                }
            choices.append(choice)
        self._json(200, {
            "id": rid,
            "object": "text_completion",
            "created": int(time.time()),
            "model": body.get("model", self.state.model_name),
            "choices": choices,
        })

    def _apply_lora_field(self, body: dict, sp: dict):
        """Per-request LoRA through the Images API (reference payload
        {"lora": {"name", "path", "scale"}},
        tests/e2e/online_serving/test_images_generations_lora.py).
        Returns an error string after responding, or None."""
        lora = body.get("lora")
        if lora is None:
            return None
        if isinstance(lora, str):
            lora = {"name": lora}
        if not isinstance(lora, dict) or not (
                lora.get("name") or lora.get("path")):
            self._error(400, "lora must be {'name'|'path'[, 'scale']}")
            return "bad lora"
        lora = dict(lora)
        lora.setdefault("name", lora.get("path"))
        sp.setdefault("extra", {})["lora"] = lora
        return None

    # ------------------------------------------------- images/generations
    def _images_generations(self, body: dict):
        prompt = body.get("prompt")
        if not prompt:
            return self._error(400, "prompt required")
        sp: dict[str, Any] = {}
        if body.get("size"):
            try:
                w, h = body["size"].lower().split("x")
                sp["width"], sp["height"] = int(w), int(h)
            except ValueError:
                return self._error(400, f"bad size {body['size']!r}")
        for k in ("num_inference_steps", "guidance_scale", "seed"):
            if body.get(k) is not None:
                sp[k] = body[k]
        err = self._apply_lora_field(body, sp)
        if err:
            return
        n = int(body.get("n", 1))
        rid = f"img-{uuid.uuid4().hex[:16]}"
        # submit all n at once so the diffusion stage can batch them
        jobs = [(prompt, sp, f"{rid}-{i}") for i in range(n)]
        data = []
        for outs in self.state.collect_many(jobs):
            if self._surface_error(outs):
                return
            for o in outs:
                if o.final_output_type == "image":
                    data.extend(
                        {"b64_json": _b64_png(np.asarray(img))}
                        for img in o.images
                    )
        self._json(200, {"created": int(time.time()), "data": data})

    def _images_edits(self, body: dict):
        """Image editing / image-conditioned generation (reference:
        /v1/images/edits, api_server.py:1051): a base64 input image rides
        ``sampling_params.image`` into an image-conditioned pipeline
        (image-edit or I2V-style conditioning)."""
        prompt = body.get("prompt")
        if not prompt:
            return self._error(400, "prompt required")
        image_b64 = body.get("image")
        if not image_b64:
            return self._error(400, "image required (base64 PNG)")
        try:
            img = _decode_image_part(
                {"image_url": {"url": image_b64}})
        except Exception as e:
            return self._error(400, f"bad image: {e}")
        sp: dict[str, Any] = {}
        if body.get("size"):
            try:
                w, h = body["size"].lower().split("x")
                sp["width"], sp["height"] = int(w), int(h)
            except ValueError:
                return self._error(400, f"bad size {body['size']!r}")
        for k in ("num_inference_steps", "guidance_scale", "seed"):
            if body.get(k) is not None:
                sp[k] = body[k]
        err = self._apply_lora_field(body, sp)
        if err:
            return
        sp["image"] = img
        rid = f"imgedit-{uuid.uuid4().hex[:16]}"
        outs = self.state.collect(prompt, sp, rid)
        if self._surface_error(outs):
            return
        data = []
        for o in outs:
            if o.final_output_type == "image" and o.images:
                data.extend({"b64_json": _b64_png(np.asarray(im))}
                            for im in o.images)
            elif o.final_output_type == "video" and o.images:
                # image-conditioned video pipelines return frames; ship
                # frame 0 as the edited still
                vid = np.asarray(o.images[0])
                if vid.ndim == 4:
                    data.append({"b64_json": _b64_png(vid[0])})
        self._json(200, {"created": int(time.time()), "data": data})

    # ------------------------------------------------------------ videos
    def _videos(self, body: dict):
        """Video generation (reference: /v1/videos, api_server.py:1528).
        Returns frames as base64 raw RGB plus geometry metadata."""
        prompt = body.get("prompt")
        if not prompt:
            return self._error(400, "prompt required")
        sp: dict[str, Any] = {}
        if body.get("size"):
            try:
                w, h = body["size"].lower().split("x")
                sp["width"], sp["height"] = int(w), int(h)
            except ValueError:
                return self._error(400, f"bad size {body['size']!r}")
        for k in ("num_inference_steps", "guidance_scale", "seed",
                  "num_frames", "fps"):
            if body.get(k) is not None:
                sp[k] = body[k]
        rid = f"video-{uuid.uuid4().hex[:16]}"
        outs = self.state.collect(prompt, sp, rid)
        if self._surface_error(outs):
            return
        video = next(
            (o.multimodal_output.get("video",
                                     o.images[0] if o.images else None)
             for o in outs if o.final_output_type == "video"),
            None,
        )
        if video is None:
            return self._error(500, "pipeline produced no video",
                               "internal_error")
        arr = np.asarray(video)
        self._json(200, {
            "created": int(time.time()),
            "data": [{
                "b64_rgb": base64.b64encode(arr.tobytes()).decode(),
                "shape": list(arr.shape),  # [F, H, W, 3]
                "dtype": str(arr.dtype),
            }],
        })

    # ------------------------------------------------------- audio/speech
    def _audio_speech(self, body: dict):
        text = body.get("input")
        if not text:
            return self._error(400, "input required")
        rid = f"speech-{uuid.uuid4().hex[:16]}"
        # a named voice rides additional_information to the vocoder
        # stage, which resolves it through its voice registry
        # (reference: speech request voice -> speaker assets)
        voice = body.get("voice")
        prompt = ({"prompt": text,
                   "additional_information": {"voice": voice}}
                  if voice else text)
        outs = self.state.collect(prompt, {}, rid)
        if self._surface_error(outs):
            return
        audio = next(
            (o.multimodal_output["audio"] for o in outs
             if o.final_output_type == "audio"
             and "audio" in o.multimodal_output),
            None,
        )
        if audio is None:
            return self._error(500, "pipeline produced no audio",
                               "internal_error")
        raw = np.asarray(audio, np.float32).tobytes()
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Content-Length", str(len(raw)))
        self.end_headers()
        self.wfile.write(raw)


def build_server(
    model: Optional[str] = None,
    stage_configs=None,
    host: str = "0.0.0.0",
    port: int = 8000,
    **overrides,
) -> tuple[ThreadingHTTPServer, ServerState]:
    omni = AsyncOmni(model=model, stage_configs=stage_configs, **overrides)
    state = ServerState(omni, model or "omni")
    handler = type("BoundHandler", (OmniRequestHandler,), {"state": state})
    server = ThreadingHTTPServer((host, port), handler)
    # the socket listens and the orchestrator's loop turns
    startup.stamp("serving")
    return server, state


def run_server(model=None, stage_configs=None, host="0.0.0.0", port=8000,
               **overrides):
    server, state = build_server(model, stage_configs, host, port, **overrides)
    logger.info("vllm-omni-tpu API server on %s:%d", host, port)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        state.shutdown()
        server.server_close()
