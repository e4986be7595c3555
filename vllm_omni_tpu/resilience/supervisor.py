"""Stage supervision: keep a crashed/hung stage worker from being fatal.

``StageSupervisor`` wraps a ``ProcStage`` (entrypoints/stage_proc.py)
behind the same stage surface the orchestrators poll (submit / poll /
has_unfinished / process_engine_inputs / profiling / shutdown), adding:

- **liveness heartbeats** — a background thread sends ``ping`` frames
  on the existing command channel; the worker's ``pong`` reports which
  requests are mid-execution.  Missed pongs beyond the budget declare
  the worker hung (catching wedges ``is_alive`` can't see, e.g. a
  deadlocked remote worker whose process the orchestrator can't
  observe at all).
- **crash detection on both transports** — the wrapped stage's fatal
  latch covers proc death (``is_alive``), channel EOF (the only signal
  a remote worker gives), and failed sends.
- **bounded automatic restart** — exponential backoff + deterministic
  jitter, at most ``max_restarts`` respawns per stage; remote workers
  are never respawned (their lifecycle belongs to their host).
- **redelivery, exactly once** — queued-but-unstarted requests are
  resubmitted to the fresh worker (the worker-side request-id dedup
  makes duplicate delivery harmless); requests the dead worker had
  STARTED fail fast with a structured *retryable* error instead of the
  old permanent ``_fatal`` mass-failure, and a request that outlives a
  second crash fails rather than looping forever.  Started-ness is as
  fresh as the last pong, so a request that entered the running batch
  just before the crash may be redelivered instead of failed — that
  re-executes it, but never duplicates client-visible output: the
  stage channel carries outputs at finished-request granularity only,
  and the dead worker's outputs died with it.

All events count through the resilience metrics registry
(``stage_restarts_total``, ``stage_heartbeat_misses_total``,
``requests_redelivered_total``, ``requests_failed_retryable_total``)
and the heartbeat defaults are deliberately generous — a mid-traffic
XLA compile stalls pongs for tens of seconds and must not read as a
hang.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Callable, Optional

from vllm_omni_tpu.analysis.runtime import traced
from vllm_omni_tpu.config.stage import StageConfig
from vllm_omni_tpu.entrypoints.omni_stage import StageRequest
from vllm_omni_tpu.logger import init_logger
from vllm_omni_tpu.outputs import OmniRequestOutput
from vllm_omni_tpu.resilience.deadline import RETRYABLE
from vllm_omni_tpu.resilience.metrics import resilience_metrics
from vllm_omni_tpu.resilience.retry import RetryPolicy

logger = init_logger(__name__)


class StageSupervisor:
    """Supervised face of a process-disaggregated stage.

    ``stage_factory`` is injectable so the unit tests drive the whole
    failure state machine against a fake stage with a fake clock —
    no spawned processes, no sleeps."""

    def __init__(
        self,
        config: StageConfig,
        device_env: Optional[dict] = None,
        *,
        ready_timeout: float = 300.0,
        restart_policy: Optional[RetryPolicy] = None,
        heartbeat_interval_s: Optional[float] = None,
        heartbeat_misses: Optional[int] = None,
        stage_factory: Optional[Callable] = None,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
    ):
        rt = config.runtime
        if stage_factory is None:
            from vllm_omni_tpu.entrypoints.stage_proc import ProcStage

            stage_factory = ProcStage
        self._stage = stage_factory(config, device_env=device_env,
                                    ready_timeout=ready_timeout,
                                    supervised=True)
        self.config = config
        self.stage_id = config.stage_id
        self.engine = None  # orchestrator-side: never a local engine
        self._restart_policy = restart_policy or RetryPolicy(
            max_attempts=getattr(rt, "max_restarts", 3),
            base_delay_s=0.5, multiplier=2.0, max_delay_s=15.0,
            jitter=0.2,
        )
        self._hb_interval = (heartbeat_interval_s
                             if heartbeat_interval_s is not None
                             else getattr(rt, "heartbeat_interval_s", 5.0))
        self._hb_misses = (heartbeat_misses
                           if heartbeat_misses is not None
                           else getattr(rt, "heartbeat_misses", 12))
        self._clock = clock
        self._sleep = sleep
        self._rng = random.Random(f"supervisor/{config.stage_id}")
        self._lock = traced(threading.RLock(),
                            "StageSupervisor._lock")
        # request_id -> original StageRequest (the redelivery payload)
        self._tracked: dict[str, StageRequest] = {}
        self._redelivered: set[str] = set()
        self._failed_outs: list[OmniRequestOutput] = []
        self._restarts = 0
        self._restarting = False
        self._dead = False  # restart budget exhausted / not restartable
        self._closed = False
        if self._hb_interval > 0:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop, daemon=True,
                name=f"supervise-stage{config.stage_id}")
            self._hb_thread.start()

    # ------------------------------------------------------ stage surface
    @property
    def request_stats(self):
        return self._stage.request_stats

    @property
    def has_unfinished(self) -> bool:
        with self._lock:
            return bool(self._stage.has_unfinished or self._tracked
                        or self._failed_outs)

    def process_engine_inputs(self, upstream_outputs):
        return self._stage.process_engine_inputs(upstream_outputs)

    def engine_metrics_snapshot(self) -> dict:
        return self._stage.engine_metrics_snapshot()

    def resilience_snapshot(self) -> dict:
        fn = getattr(self._stage, "resilience_snapshot", None)
        return fn() if fn is not None else {}

    def start_profile(self, trace_dir: str) -> None:
        self._stage.start_profile(trace_dir)

    def stop_profile(self, timeout: float = 60.0, wait: bool = True) -> None:
        self._stage.stop_profile(timeout=timeout, wait=wait)

    def wait_profile_ack(self, timeout: float = 60.0) -> None:
        self._stage.wait_profile_ack(timeout)

    def submit(self, reqs: list[StageRequest]) -> None:
        with self._lock:
            for r in reqs:
                self._tracked[r.request_id] = r
            if self._dead:
                # no worker will ever serve these — fail now, same
                # shape as any other stage error output
                for r in reqs:
                    self._fail_locked(
                        r.request_id,
                        "stage worker unavailable (restart budget "
                        "exhausted)")
                return
            self._stage.submit(reqs)

    def abort(self, request_id: str) -> None:
        """``OmniStage.abort``: a request nobody reads any more is not
        redelivered either."""
        with self._lock:
            self._tracked.pop(request_id, None)
            self._redelivered.discard(request_id)
            self._stage.abort(request_id)

    def poll(self) -> list[OmniRequestOutput]:
        outs = self._stage.poll()
        with self._lock:
            for o in outs:
                if o.finished:
                    self._tracked.pop(o.request_id, None)
                    self._redelivered.discard(o.request_id)
            # failure handling BEFORE the drain so fail-fast outputs
            # surface in this very poll, not the next one
            if (self._stage._fatal is not None and not self._restarting
                    and not self._dead and not self._closed):
                self._on_failure(self._stage._fatal)
            if self._failed_outs:
                outs = outs + self._failed_outs
                self._failed_outs = []
        return outs

    def shutdown(self, timeout: float = 10.0) -> None:
        with self._lock:
            self._closed = True
        self._stage.shutdown(timeout)

    # --------------------------------------------------------- heartbeats
    def _heartbeat_loop(self) -> None:
        while True:
            self._sleep(self._hb_interval)
            with self._lock:
                if self._closed:
                    return
                if self._restarting or self._dead:
                    continue
                if self._stage._fatal is not None:
                    self._on_failure(self._stage._fatal)
                    continue
                self._stage.ping()
                age = self._clock() - self._stage.last_pong
                if age > self._hb_interval * 2:
                    # a miss needs a full unanswered ping cycle: one
                    # interval of age is NORMAL (sleep overshoot plus
                    # pong round trip), and counting it would make the
                    # miss series climb on perfectly healthy stages
                    resilience_metrics.inc(
                        "stage_heartbeat_misses_total",
                        stage=self.stage_id)
                if age > self._hb_interval * self._hb_misses:
                    logger.error(
                        "stage %d: no heartbeat for %.1fs (budget "
                        "%.1fs) — declaring the worker hung",
                        self.stage_id, age,
                        self._hb_interval * self._hb_misses)
                    self._stage.mark_hung(
                        f"worker hung: no heartbeat for {age:.1f}s")
                    self._on_failure(self._stage._fatal)

    # ----------------------------------------------------- failure policy
    def _fail_locked(self, request_id: str, detail: str,
                     kind: str = RETRYABLE) -> None:
        self._tracked.pop(request_id, None)
        self._stage._inflight.discard(request_id)
        o = OmniRequestOutput.from_error(
            request_id, detail, stage_id=self.stage_id, kind=kind)
        self._stage._record(o)
        self._failed_outs.append(o)
        resilience_metrics.inc("requests_failed_retryable_total",
                               stage=self.stage_id)

    def _on_failure(self, reason: str) -> None:
        """Split the in-flight set (lock held): mid-execution requests
        fail fast as retryable; queued-but-unstarted ones await
        redelivery to the restarted worker — unless they already got
        their one redelivery, or restarting is off the table."""
        reason = reason or "worker lost"
        started = self._stage.started_request_ids & set(self._tracked)
        for rid in sorted(started):
            self._fail_locked(
                rid, f"stage worker died mid-execution: {reason}")
        for rid in sorted(set(self._tracked)):
            if rid in self._redelivered:
                self._fail_locked(
                    rid,
                    f"stage worker died again after redelivery: "
                    f"{reason}")
        can_restart = (self._stage.restartable
                       and self._restarts
                       < self._restart_policy.max_attempts)
        if not can_restart:
            logger.error(
                "stage %d: worker lost (%s) and %s — failing %d "
                "in-flight request(s)", self.stage_id, reason,
                ("not restartable" if not self._stage.restartable
                 else "restart budget exhausted"), len(self._tracked))
            for rid in sorted(set(self._tracked)):
                self._fail_locked(
                    rid, f"stage worker died: {reason}")
            self._dead = True
            return
        self._restarting = True
        threading.Thread(target=self._do_restart, args=(reason,),
                         daemon=True,
                         name=f"restart-stage{self.stage_id}").start()

    def _do_restart(self, reason: str) -> None:
        while True:
            with self._lock:
                if self._closed:
                    return
                self._restarts += 1
                attempt = self._restarts
            delay = self._restart_policy.delay_s(attempt, self._rng)
            logger.warning(
                "stage %d: worker lost (%s); restart %d/%d in %.2fs",
                self.stage_id, reason, attempt,
                self._restart_policy.max_attempts, delay)
            self._sleep(delay)
            with self._lock:
                if self._closed:
                    return
            try:
                self._stage.restart()
                break
            except Exception as e:
                logger.error("stage %d: restart attempt %d failed: %s",
                             self.stage_id, attempt, e)
                with self._lock:
                    if self._restarts >= self._restart_policy.max_attempts:
                        for rid in sorted(set(self._tracked)):
                            self._fail_locked(
                                rid,
                                f"stage worker unrecoverable after "
                                f"{attempt} restart attempt(s): {e}")
                        self._dead = True
                        self._restarting = False
                        return
        with self._lock:
            resilience_metrics.inc("stage_restarts_total",
                                   stage=self.stage_id)
            redeliver = [self._tracked[rid]
                         for rid in sorted(self._tracked)]
            self._redelivered.update(r.request_id for r in redeliver)
            self._restarting = False
        if redeliver:
            logger.warning(
                "stage %d: restarted; redelivering %d unstarted "
                "request(s)", self.stage_id, len(redeliver))
            resilience_metrics.inc("requests_redelivered_total",
                                   n=len(redeliver), stage=self.stage_id)
            self._stage.submit(redeliver)
