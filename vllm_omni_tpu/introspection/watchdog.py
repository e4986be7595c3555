"""Stall watchdog: trip when step progress stalls past a deadline.

The supervisor's heartbeats (resilience/supervisor.py) catch a *worker
process* that stopped answering; nothing before this module caught the
in-process failure mode — an engine thread alive but wedged (deadlocked
lock, runaway device wait, scheduler livelock) while requests age out.
The watchdog is a monitor thread that polls registered *sources* and
declares a stall when a source is busy (has unfinished work) but its
progress counter has not advanced within the deadline.

Two kinds of "no progress" look identical from outside and must not be
conflated (docs/debugging.md):

- **XLA compile stalls** — a shape-cache miss mid-traffic blocks every
  in-flight request for a full compile (tens of seconds).  The
  runner's PR 5 compile telemetry distinguishes them: a
  fresh compile in flight (``compile_stats["in_flight"]``) or the
  ``jit_compiles_total`` counter advancing since the stall began means
  the device is compiling, not hung.  Those windows EXTEND the deadline
  (counted in ``compile_stalls`` so a pathological compile loop is
  still visible) instead of tripping.
- **true hangs** — busy, no steps, no compile activity.  On trip the
  watchdog captures the full incident context: all-thread stacks
  (``sys._current_frames``), every registered engine's in-flight
  request table (age, phase, token accounting, deadline remaining,
  tenant), the flight-recorder tails, and the per-source stall ages —
  and writes one dump document (``dump_to_file``) before notifying
  ``on_trip`` callbacks.  ``/health`` turns 503 once tripped so a load
  balancer ejects the wedged replica.

Cross-process stages feed the same machinery: the supervisor's
heartbeat state (last-pong age) registers as a source, so a trip dump
covers remote workers the in-proc probes cannot see.

Clock and sleep are injectable (same stance as StageSupervisor) so the
unit tests drive the whole state machine with a fake clock — no real
threads, no sleeps.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from vllm_omni_tpu.analysis.runtime import traced
from vllm_omni_tpu.introspection.flight_recorder import (
    build_dump,
    dump_to_file,
)
from vllm_omni_tpu.logger import init_logger

logger = init_logger(__name__)

# a probe returns this shape; every field optional but "busy"
#   busy:              the source has unfinished work
#   progress:          any monotone int that advances when work advances
#   compiles:          cumulative fresh-compile count (jit_compiles_total)
#   compile_in_flight: a fresh XLA compile is running right now
#   detail:            JSON-ready context included in trip dumps
Probe = Callable[[], dict]


@dataclass
class _SourceState:
    name: str
    probe: Probe
    last_progress: Optional[int] = None
    last_compiles: int = 0
    # when the current no-progress window began (None = progressing)
    stalled_since: Optional[float] = None
    compile_stalls: int = 0
    # whether the previous poll already saw this compile in flight —
    # compile_stalls counts compile EVENTS, not poll intervals
    was_compiling: bool = False
    detail: dict = field(default_factory=dict)


class StallWatchdog:
    """Monitor for in-process engine liveness.

    ``check_once()`` is the whole state machine (the thread just calls
    it on an interval), so tests — and operators poking a live process
    — can drive it synchronously.
    """

    def __init__(
        self,
        deadline_s: float = 60.0,
        *,
        poll_interval_s: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
        on_trip: Optional[Callable[[dict], None]] = None,
        dump_path: Optional[str] = None,
    ):
        self.deadline_s = float(deadline_s)
        self._poll = (poll_interval_s if poll_interval_s is not None
                      else max(self.deadline_s / 4.0, 0.05))
        self._clock = clock
        self._sleep = sleep
        self._dump_path = dump_path
        self._on_trip: list[Callable[[dict], None]] = (
            [on_trip] if on_trip else [])
        self._lock = traced(threading.Lock(), "StallWatchdog._lock")
        self._sources: dict[str, _SourceState] = {}
        # weak handles to engines for the trip dump's request tables +
        # flight-recorder tails (the introspection registry owns the
        # weakrefs; the watchdog just asks at dump time)
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        # trip latch: /health flips 503 off this (one trip is enough to
        # eject the replica; un-tripping is a restart's job)
        self.tripped: Optional[dict] = None
        self.trips = 0

    # ------------------------------------------------------------- sources
    def add_source(self, name: str, probe: Probe) -> None:
        with self._lock:
            self._sources[name] = _SourceState(name=name, probe=probe)

    def add_engine(self, name: str, engine) -> None:
        """Register an LLMEngine-shaped object (anything exposing
        ``introspect_progress``)."""
        self.add_source(name, engine.introspect_progress)

    def add_supervisor(self, name: str, supervisor) -> None:
        """Register a StageSupervisor: progress is the worker's last
        pong stamp, so a remote worker that stops answering heartbeats
        stalls this source and lands in the trip dump alongside the
        in-proc engines (the supervisor still owns restart policy)."""

        def probe() -> dict:
            stage = getattr(supervisor, "_stage", None)
            last_pong = float(getattr(stage, "last_pong", 0.0) or 0.0)
            return {
                "busy": bool(getattr(supervisor, "has_unfinished", False)),
                # ms resolution keeps the counter integral and monotone
                "progress": int(last_pong * 1e3),
                "detail": {
                    "kind": "supervised_stage",
                    "restarts": getattr(supervisor, "_restarts", 0),
                    "dead": getattr(supervisor, "_dead", False),
                },
            }

        self.add_source(name, probe)

    def on_trip(self, fn: Callable[[dict], None]) -> None:
        self._on_trip.append(fn)

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "StallWatchdog":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="stall-watchdog")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._closed = True

    def _loop(self) -> None:
        while not self._closed:
            self._sleep(self._poll)
            if self._closed:
                return
            try:
                self.check_once()
            except Exception:  # the monitor must never kill the process
                logger.exception("watchdog check failed")

    # ---------------------------------------------------------- the check
    def check_once(self) -> Optional[dict]:
        """Poll every source once; returns the trip document if this
        check tripped, else None.  Idempotent after a trip (the latch
        stays set; further stalls don't re-dump)."""
        now = self._clock()
        with self._lock:
            sources = list(self._sources.values())
        stalled: list[tuple[_SourceState, float]] = []
        for st in sources:
            try:
                p = st.probe() or {}
            except Exception as e:
                # a probe that raises is itself a liveness signal worth
                # surfacing, but never a reason to trip
                st.detail = {"probe_error": repr(e)}
                continue
            st.detail = dict(p.get("detail") or {})
            progress = p.get("progress")
            compiles = int(p.get("compiles") or 0)
            in_flight = bool(p.get("compile_in_flight"))
            if not p.get("busy"):
                st.stalled_since = None
                st.last_progress = progress
                st.last_compiles = compiles
                st.was_compiling = False
                continue
            if st.last_progress is None or progress != st.last_progress:
                # progress observed NOW: the next stall window is
                # measured from this observation, so one poll interval
                # of queueing never inflates the stall age.
                # was_compiling resets too: compile-event accounting
                # belongs to stall windows only
                st.last_progress = progress
                st.last_compiles = compiles
                st.stalled_since = now
                st.was_compiling = False
                continue
            # busy + no progress: the stall window is open
            if st.stalled_since is None:
                st.stalled_since = now
            if in_flight or compiles != st.last_compiles:
                # the device is compiling, not hung: restart the window.
                # compile_stalls counts compile EVENTS — a completion
                # (counter advanced) or a NEW in-flight compile — not
                # every poll that re-observes the same long compile
                if compiles != st.last_compiles or not st.was_compiling:
                    st.compile_stalls += 1
                st.last_compiles = compiles
                st.was_compiling = in_flight
                st.stalled_since = now
                continue
            st.was_compiling = False
            stalled_for = now - st.stalled_since
            if stalled_for >= self.deadline_s:
                stalled.append((st, stalled_for))
        if not stalled or self.tripped is not None:
            return None
        return self._trip(stalled)

    # -------------------------------------------------------------- tripping
    def _trip(self, stalled: list[tuple[_SourceState, float]]) -> dict:
        from vllm_omni_tpu import introspection

        worst = max(s for _, s in stalled)
        names = [st.name for st, _ in stalled]
        logger.error(
            "stall watchdog TRIPPED: %s made no progress for %.1fs "
            "(deadline %.1fs)", ", ".join(names), worst, self.deadline_s)
        engines = introspection.iter_engines()
        # registry read under the lock: add_source from another thread
        # mid-trip must not race the dump's source inventory (OL7)
        with self._lock:
            registered = sorted(self._sources)
        extra: dict[str, Any] = {
            "watchdog": {
                "deadline_s": self.deadline_s,
                "stalled_sources": [
                    {"name": st.name, "stalled_s": round(s, 3),
                     "compile_stalls": st.compile_stalls,
                     "detail": st.detail}
                    for st, s in stalled
                ],
                "sources": registered,
            },
            "requests": [
                {"engine": getattr(e, "stage_id", i),
                 "table": introspection.request_table(e)}
                for i, e in enumerate(engines)
            ],
        }
        doc = build_dump(
            "watchdog_trip",
            recorders=[e.flight for e in engines
                       if getattr(e, "flight", None) is not None],
            extra=extra)
        dump_to_file(doc, self._dump_path)
        self.trips += 1
        self.tripped = {
            "reason": "stall",
            "sources": names,
            "stalled_s": round(worst, 3),
            "ts": doc["ts"],
        }
        for fn in list(self._on_trip):
            try:
                fn(doc)
            except Exception:
                logger.exception("watchdog on_trip callback failed")
        return doc

    # ------------------------------------------------------------- reading
    def state(self) -> dict:
        """JSON-ready view for /debug + /health: per-source stall ages
        and the trip latch."""
        now = self._clock()
        with self._lock:
            sources = list(self._sources.values())
        return {
            "deadline_s": self.deadline_s,
            "running": self._thread is not None and not self._closed,
            "tripped": self.tripped,
            "trips": self.trips,
            "sources": {
                st.name: {
                    "stalled_s": (round(now - st.stalled_since, 3)
                                  if st.stalled_since is not None else 0.0),
                    "compile_stalls": st.compile_stalls,
                    "last_progress": st.last_progress,
                }
                for st in sources
            },
        }
