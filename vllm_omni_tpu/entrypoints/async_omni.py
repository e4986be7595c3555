"""AsyncOmni: online multi-stage orchestrator with per-request streaming.

Behavioral port of the reference's AsyncOmni (reference:
entrypoints/async_omni.py:60 — per-request asyncio streaming over the same
stage pipeline, output-handler task, abort).  The in-proc TPU build steps
the stages on a dedicated engine thread (the analogue of the reference's
stage worker processes) and bridges results into per-request asyncio queues
via ``loop.call_soon_threadsafe`` — request intake and SSE streaming stay
non-blocking on the server's event loop.
"""

from __future__ import annotations

import asyncio
import itertools
import queue
import threading
import time
from typing import Any, AsyncIterator, Optional, Union

from vllm_omni_tpu.analysis.runtime import traced
from vllm_omni_tpu.config.stage import StageConfig
from vllm_omni_tpu.entrypoints.omni import Omni
from vllm_omni_tpu.entrypoints.omni_stage import StageRequest
from vllm_omni_tpu.introspection import PhaseClock
from vllm_omni_tpu.logger import init_logger
from vllm_omni_tpu.outputs import OmniRequestOutput

logger = init_logger(__name__)

_SENTINEL = object()


def _deliver(items: list) -> None:
    """``items``: a delivery, its item, the next delivery, its item, ...
    (flat, so that a step's hand-over allocates one list and not a tuple
    a row)."""
    it = iter(items)
    for put in it:
        put(next(it))


class AsyncOmni:
    def __init__(
        self,
        model: Optional[str] = None,
        stage_configs: Optional[Union[str, list[StageConfig]]] = None,
        **overrides: Any,
    ):
        # reuse the sync orchestrator's stage construction + dataflow
        self._omni = Omni(model=model, stage_configs=stage_configs,
                          **overrides)
        self._n_finals = sum(
            1 for s in self._omni.stages if s.config.final_output
        )
        self._intake: queue.Queue = queue.Queue()
        # request_id -> (event loop, the stream's delivery: a callable
        # for that loop's thread, ``_admit``)
        self._streams: dict[str, tuple[asyncio.AbstractEventLoop,
                                       Any]] = {}
        self._finals_seen: dict[str, int] = {}
        # what the engine thread has for the streams and has not handed
        # over yet: event loop -> [delivery, item, delivery, item, ...], one
        # hand-over a loop (``_flush``), however many rows of a step stream
        self._outbox: dict[asyncio.AbstractEventLoop, list] = {}
        # ids whose reader has gone (``abort``), for the engine thread to
        # take out of the stages
        self._aborted: queue.SimpleQueue = queue.SimpleQueue()
        self._req_counter = itertools.count()
        self._running = True
        # pause gate (reference: pause_generation/resume_generation,
        # async_omni.py:739-782): a threading.Event so requests arriving
        # on ANY event loop and the engine thread agree on the state.
        # _pause_lock closes the gate-check -> stream-registration race:
        # a generate() that saw the gate open registers its stream
        # INSIDE the lock, so a pause clearing the event (also inside
        # the lock) is guaranteed to see it in _streams
        self._resume_event = threading.Event()
        self._resume_event.set()
        self._pause_lock = traced(threading.Lock(),
                                  "AsyncOmni._pause_lock")
        # engine-level stats heartbeat period (seconds); tests shrink it
        self._stats_interval = 10.0
        self._thread = threading.Thread(target=self._engine_loop,
                                        daemon=True, name="omni-engine")
        self._thread.start()

    # ----------------------------------------------------------- lifecycle
    def shutdown(self) -> None:
        self._running = False
        self._thread.join(timeout=10)
        self._omni.watchdog.stop()
        self._omni.alerts.stop()
        # final drain + the one Chrome-document export (the heartbeat
        # only streams JSONL)
        self._omni.flush_traces()

    @property
    def stage_configs(self):
        return self._omni.stage_configs

    @property
    def metrics(self):
        return self._omni.metrics

    @property
    def watchdog(self):
        """The orchestrator's stall watchdog (introspection)."""
        return self._omni.watchdog

    @property
    def alerts(self):
        """The orchestrator's alert engine (metrics/alerts.py)."""
        return self._omni.alerts

    @property
    def engine_thread_alive(self) -> bool:
        """Liveness of the engine loop thread — the /health answer to
        "is anything still stepping the stages"."""
        return self._thread.is_alive()

    def start_profile(self, trace_dir: str) -> None:
        """Fan a jax.profiler trace out to every stage (reference:
        profile RPC chain, omni.py:398-497)."""
        self._omni.start_profile(trace_dir)

    def stop_profile(self) -> Optional[str]:
        """End the trace; returns the directory it landed in."""
        return self._omni.stop_profile()

    # ------------------------------------------------------- pause/resume
    async def pause_generation(
        self,
        *,
        wait_for_inflight_requests: bool = False,
        clear_cache: bool = True,
    ) -> None:
        """Pause generation for a weight update (reference:
        AsyncOmni.pause_generation, async_omni.py:739-773).  New
        requests block in ``generate`` until ``resume_generation``.

        ``wait_for_inflight_requests``: True drains in-flight requests
        first; False (default) aborts them immediately.
        ``clear_cache``: drop every stage engine's unreferenced APC page
        (cached KV is stale once weights change)."""
        with self._pause_lock:
            if not self._resume_event.is_set():
                return
            self._resume_event.clear()
        if wait_for_inflight_requests:
            while self._streams or not self._intake.empty():
                await asyncio.sleep(0.005)
        else:
            for rid in list(self._streams):
                self.abort(rid)
        if clear_cache:
            # even in abort mode the STAGES keep draining aborted work
            # (stream abort is best-effort); a reset before it finishes
            # would let freed pages re-register pre-swap KV into the
            # cache — wait for every stage (its _pending queue AND its
            # engine, stage.has_unfinished) to go idle first
            while (not self._intake.empty()
                   or any(getattr(s, "has_unfinished", False)
                          for s in self._omni.stages)):
                await asyncio.sleep(0.005)
            released = 0
            for stage in self._omni.stages:
                eng = getattr(stage, "engine", None)
                fn = getattr(eng, "reset_prefix_cache", None)
                if fn is not None:
                    released += fn()
            logger.info("paused: %d prefix-cache pages released",
                        released)

    async def resume_generation(self) -> None:
        """Unblock requests waiting behind ``pause_generation``."""
        self._resume_event.set()

    async def is_paused(self) -> bool:
        return not self._resume_event.is_set()

    # -------------------------------------------------------------- intake
    async def generate(
        self,
        prompt: Union[str, list[int], dict],
        sampling_params: Optional[dict] = None,
        request_id: Optional[str] = None,
        deadline_s: Optional[float] = None,
        stream: bool = False,
    ) -> AsyncIterator[OmniRequestOutput]:
        """Submit one request; yields one OmniRequestOutput per final stage
        (reference: AsyncOmni.generate, async_omni.py:235).
        ``deadline_s`` bounds the request end-to-end; expiry surfaces as
        a ``deadline_exceeded`` error output (HTTP 504 at the server).
        ``stream``: also yield, before the text stage's final output, a
        partial output (``finished`` false) for every step that added
        tokens to the reply: it holds the step's token ids alone and, as
        ``text``, what they add to the reply; the final output is the
        whole reply as without it (docs/serving.md, "Streaming")."""
        request_id = self._request_id(request_id)
        out_q: asyncio.Queue = asyncio.Queue()
        await self._admit(request_id, prompt, sampling_params, deadline_s,
                          stream, out_q.put_nowait)
        try:
            while True:
                item = await out_q.get()
                if item is _SENTINEL:
                    return
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            self._forget(request_id)

    async def generate_to(
        self,
        sink,
        prompt: Union[str, list[int], dict],
        sampling_params: Optional[dict] = None,
        request_id: Optional[str] = None,
        deadline_s: Optional[float] = None,
        stream: bool = False,
    ) -> str:
        """``generate`` for a caller that takes the outputs as calls and
        not as an iteration: ``sink.feed(item)`` for every output (and
        for an exception that ends the request), then ``sink.close()``,
        each on the calling event loop's thread and straight from the
        engine thread's hand-over, with no queue and no task between
        them: at a token an event and hundreds of streams that is most
        of what an event costs the loop.  Returns the request's id once
        it is admitted; what ``generate`` would raise before that, it
        raises."""
        request_id = self._request_id(request_id)

        def put(item) -> None:
            if item is _SENTINEL:
                self._forget(request_id)
                sink.close()
            else:
                sink.feed(item)

        await self._admit(request_id, prompt, sampling_params, deadline_s,
                          stream, put)
        return request_id

    def _forget(self, request_id: str) -> None:
        self._streams.pop(request_id, None)
        self._finals_seen.pop(request_id, None)

    def _request_id(self, request_id: Optional[str]) -> str:
        if request_id is None:
            return f"async-{next(self._req_counter)}"
        if request_id in self._streams:
            raise ValueError(f"request_id {request_id!r} already in flight")
        return request_id

    async def _admit(self, request_id: str, prompt, sampling_params,
                     deadline_s: Optional[float], stream: bool,
                     put) -> None:
        """Build the request, pass the pause gate, register ``put`` as
        the stream's delivery (called on this event loop's thread with
        every output, with an exception, and last with the sentinel) and
        hand the request to the engine thread."""
        # pause gate: block intake until resume_generation (reference:
        # "New generation/encoding requests are blocked until resume").
        # The gate check and the stream registration below share
        # _pause_lock so a concurrent pause either sees this request in
        # _streams or blocks it here — never neither.
        sp = dict(sampling_params or {})
        if isinstance(prompt, dict):
            req = StageRequest(request_id=request_id, sampling_params=sp,
                               **prompt)
        elif isinstance(prompt, str):
            req = StageRequest(request_id=request_id, prompt=prompt,
                               sampling_params=sp)
        else:
            req = StageRequest(request_id=request_id,
                               prompt_token_ids=list(prompt),
                               sampling_params=sp)
        req.stream = bool(stream)
        # trace context + deadline BEFORE enqueue: the engine thread may
        # drain the intake the instant the put lands.  A caller-supplied
        # trace_id (the server's traceparent / x-omni-trace-id join)
        # rides additional_information and is consumed here — the
        # journey continues the external trace instead of a fresh id
        req.trace = self._omni.trace_begin(
            request_id,
            trace_id=req.additional_information.pop("trace_id", None))
        req.deadline_s = self._omni.deadline_begin(
            request_id,
            req.deadline_s if req.deadline_s is not None else deadline_s)
        loop = asyncio.get_running_loop()
        while True:
            with self._pause_lock:
                if self._resume_event.is_set():
                    # re-check the duplicate guard HERE: two same-id
                    # calls parked behind a pause both passed the early
                    # check; the second must fail, not silently steal
                    # the first's stream
                    if request_id in self._streams:
                        raise ValueError(
                            f"request_id {request_id!r} already in "
                            "flight")
                    self._streams[request_id] = (loop, put)
                    self._finals_seen[request_id] = 0
                    # enqueue INSIDE the lock: a put after release could
                    # slip past a concurrent pause's intake-empty check
                    # and run mid-weight-swap.  The queue is unbounded,
                    # so the put never actually blocks:
                    # omnilint: disable=OL9 - unbounded queue put;
                    # in-lock enqueue is the pause-gate invariant
                    self._intake.put(req)
                    break
            if not self._running:
                raise RuntimeError(
                    "AsyncOmni is shut down; request rejected while "
                    "paused")
            await asyncio.sleep(0.01)
        self._omni.metrics.record_arrival(request_id)

    def abort(self, request_id: str) -> None:
        """Drop the stream, and have the engine thread take the request
        out of every stage on its next turn: its pages are freed and no
        output of it follows (a stage that is mid-way through a batch
        that holds it, as a diffusion stage is, finishes the batch; what
        it delivers then finds no stream and is discarded)."""
        entry = self._streams.pop(request_id, None)
        if entry is not None:
            loop, put = entry
            loop.call_soon_threadsafe(put, _SENTINEL)
            self._aborted.put(request_id)
        self._omni.trace_finish(request_id)

    # --------------------------------------------------------- engine loop
    def _emit(self, request_id: str, item,
              outbox: Optional[dict] = None) -> None:
        entry = self._streams.get(request_id)
        if entry is None:
            return
        loop, put = entry
        if outbox is None:
            outbox = self._outbox
        items = outbox.get(loop)
        if items is None:
            items = outbox[loop] = []
        items.append(put)
        items.append(item)

    def _flush(self) -> None:
        """Hand over what ``_emit`` gathered."""
        if self._outbox:
            outbox, self._outbox = self._outbox, {}
            self._hand_over(outbox)

    @staticmethod
    def _hand_over(outbox: dict) -> None:
        """One wake-up of each event loop, whose thread then makes the
        streams' deliveries in order."""
        for loop, items in outbox.items():
            try:
                loop.call_soon_threadsafe(_deliver, items)
            except RuntimeError:
                # the loop was closed with items still on their way to it
                # (its requests were aborted, or their caller is gone):
                # nobody is left to read them
                pass

    def _emit_outputs(self, stage, outs: list) -> None:
        """A poll's outputs to their streams, in the order the stage
        gave them (a request's partials stand before its final), and the
        finals on to the stages that consume them.  A partial output (a
        streaming request's new tokens, ``finished`` false) goes to its
        stream and nowhere else: it is no final, is not forwarded and
        ends nothing.  A batch with no partial in it is handed over at
        once (``_flush``), as every batch was before requests streamed;
        one that holds partials, so a step's worth of events for the
        writer, waits for the moment the engine thread next waits for the
        device (``_engine_loop``), or for the return of the next poll
        that had no such moment, but for a request's FIRST partial, which
        leaves at once: a user waits for the first token, it is the first
        item its stream is given, and a step brings one or two of them
        where it brings hundreds of later ones."""
        omni = self._omni
        final_type = (stage.config.final_output_type
                      if stage.config.final_output else None)
        finals = []
        partials = False
        firsts: dict = {}
        for o in outs:
            rid = o.request_id
            if not o.finished:
                partials = True
                if final_type is not None:
                    o.final_output_type = final_type
                    # the first one carries the first token's instant
                    # (engine/stream.py), the others nothing
                    self._emit(rid, o, firsts if o.metrics else None)
            elif o.is_error:
                # errored outputs terminate their streams and are not
                # forwarded downstream
                omni.metrics.record_finish(rid)
                omni.trace_finish(rid)
                self._emit(rid, o)
                self._emit(rid, _SENTINEL)
            else:
                finals.append(o)
                if final_type is None:
                    continue
                o.final_output_type = final_type
                self._emit(rid, o)
                if rid not in self._finals_seen:
                    continue  # aborted: its stream and its count are gone
                seen = self._finals_seen[rid] = self._finals_seen[rid] + 1
                if seen >= self._n_finals:
                    # E2E spans through the LAST final output
                    omni.metrics.record_finish(rid)
                    omni.trace_finish(rid)
                    self._emit(rid, _SENTINEL)
        if finals:
            try:
                omni._forward(stage, finals)
            except Exception as e:
                # scope the failure to the requests in this batch
                logger.exception("forward from stage %d failed",
                                 stage.stage_id)
                for o in finals:
                    # terminate the stream's trace too: the sync
                    # generate() sweeps leftover contexts at the end,
                    # the online loop has no such sweep
                    omni.trace_finish(o.request_id)
                    self._emit(o.request_id, e)
                    self._emit(o.request_id, _SENTINEL)
        if firsts:
            self._hand_over(firsts)
        if not partials:
            self._flush()

    def _engine_loop(self) -> None:
        omni = self._omni
        entry_stages = [s for s in omni.stages
                        if -1 in s.config.engine_input_source]
        entry_stage = entry_stages[0] if entry_stages else omni.stages[0]
        self._last_stats = time.monotonic()
        # the phases of this loop beside the engine's (omni.step and
        # below, docs/observability.md), in the profiler's own trace and
        # on the entry stage's engine's clock: what this thread does
        # while the device waits for it.  A stage in a process of its own
        # has no engine here; the loop then keeps a clock of its own.
        self._phases = getattr(getattr(entry_stage, "engine", None),
                               "phases", None) or PhaseClock()
        # the streams' items leave as an engine starts to wait for the
        # device: their writer, which shares the interpreter lock with
        # this thread, then works while this thread sleeps.  Handed over
        # at ``omni.emit``, a step's events were written while this
        # thread assembled the next step, and every event cost the
        # assembly what it cost the writer (PERF.md, PR 56).
        for stage in omni.stages:
            clock = getattr(getattr(stage, "engine", None), "phases", None)
            if clock is not None:
                clock.on_enter("omni.readback", self._flush)
        while self._running:
            with self._phases.phase("omni.loop"):
                self._loop_once(entry_stage)

    def _loop_once(self, entry_stage) -> None:
        omni = self._omni
        _phase = self._phases.phase
        # periodic engine-level stats heartbeat (reference: the
        # do_log_stats keep-alive task, omni_stage.py:1134-1146)
        now = time.monotonic()
        if now - self._last_stats >= self._stats_interval:
            self._last_stats = now
            # harvest stage request stats continuously (the offline
            # path collects at end-of-generate) so long-running
            # servers aggregate + stream jsonl as they go
            omni.harvest_stage_stats()
            # JSONL only: the full Chrome rewrite is shutdown-time
            # work, not something to run on the engine thread
            omni.flush_traces(export_chrome=False)
            if self._streams:
                summ = omni.stats_summary()
                logger.info(
                    "stats: %d in flight, e2e p50 %.0fms, stages %s",
                    len(self._streams), summ["e2e"]["p50_ms"],
                    {i: st["tps"]
                     for i, st in summ["stages"].items()},
                )
        # 1. drain intake
        with _phase("omni.intake"):
            pending = []
            try:
                while True:
                    pending.append(self._intake.get_nowait())
            except queue.Empty:
                pass
            if pending:
                try:
                    entry_stage.submit(pending)
                except Exception as e:  # bad request payloads
                    for r in pending:
                        omni.trace_finish(r.request_id)
                        self._emit(r.request_id, e)
                        self._emit(r.request_id, _SENTINEL)
                    self._flush()
            # after the intake: a request is registered and queued before
            # anyone can abort it, so its abort finds it in a stage
            while not self._aborted.empty():
                rid = self._aborted.get_nowait()
                for stage in omni.stages:
                    stage.abort(rid)
        # 2. step stages + forward
        progressed = False
        for stage in omni.stages:
            try:
                outs = stage.poll()
            except Exception as e:
                # last resort: a poll failure can't be attributed to one
                # request (engine-level starvation is error-finished per
                # request inside LLMEngine.step and arrives as outputs)
                logger.exception("stage %d poll failed", stage.stage_id)
                for rid in list(self._streams):
                    omni.trace_finish(rid)
                    self._emit(rid, e)
                    self._emit(rid, _SENTINEL)
                self._flush()
                continue
            # a stage that delivered outputs advanced, and so did one
            # whose poll ran a step that did device work or a diffusion
            # batch, whether or not anything finished (OmniStage.advanced)
            progressed |= bool(outs) or getattr(stage, "advanced", False)
            # partials that met no wait for the device in this poll (an
            # engine gone idle, a step with nothing to retire) leave now
            self._flush()
            if not outs:
                continue
            with _phase("omni.emit"):
                self._emit_outputs(stage, outs)
        if not progressed and not pending:
            # nothing advanced this turn (no stage ran a step that did
            # device work, none delivered an output, the intake was
            # empty): wait, so as not to spin on the GIL.  With work
            # unfinished it is a busy wait, and what is left to wait for
            # is out of this thread's hands: a stage in a process of its
            # own, or an engine whose step scheduled nothing (a streaming
            # request idling for its next chunk, pages pinned by a
            # transfer awaiting its ACK).  There the wait is also what
            # spaces the ticks the engine counts before it declares a
            # waiting request starved (LLMEngine._run_scheduled)
            busy = any(getattr(s, "has_unfinished", False)
                       for s in omni.stages)
            with _phase("omni.idle_wait") as p:
                threading.Event().wait(0.002)
            if busy:
                self._phases.note_busy_wait(p.ns)
