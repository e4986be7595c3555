"""Per-request distributed tracing across pipeline stages.

The repo had jax.profiler fan-out and aggregate stats jsonl but no
request-trace propagation — once stages run
in separate processes nobody can answer "where did request X spend its
900 ms".  This module is the span layer underneath:

- a ``trace context`` is a plain dict ``{"trace_id", "request_id"}``
  created at ``Omni``/``AsyncOmni`` arrival.  Plain dicts (not a class)
  so the context survives every transport the pipeline already has —
  ``StageRequest.trace`` rides the stage_proc command sockets and the
  connector edges through OmniSerializer unchanged.
- each process owns one global ``TraceRecorder``; engines and stages
  record finished spans into it (recording is a no-op for requests
  without a context, so an untraced server pays one dict lookup).
- cross-process stage workers drain their recorder into the ``outputs``
  message (entrypoints/stage_proc.py); the orchestrator merges the
  shipped spans, so one request's trace id carries spans from every
  stage regardless of process placement.
- ``TraceWriter`` streams spans as JSONL next to the ``*.stats.jsonl``
  files and exports the whole trace as Chrome trace-event JSON
  (Perfetto / chrome://tracing loadable).

Span timestamps are wall-clock (``time.time``) so spans recorded in
different processes land on one timeline; durations come from the
caller's monotonic clock.
"""

from __future__ import annotations

import json
import threading
import uuid
from collections import deque
from typing import Any, Optional

from vllm_omni_tpu.analysis.runtime import traced


def new_trace_context(request_id: str) -> dict:
    """Fresh per-request trace context (created once, at arrival)."""
    return {"trace_id": uuid.uuid4().hex, "request_id": request_id}


class TraceRecorder:
    """Process-global span sink.  Bounded: a recorder nobody drains (a
    stage worker between output batches, a server without tracing
    enabled) must not grow memory forever.

    Eviction is COUNTED, never silent: ``spans_dropped`` is the
    lifetime number of spans the ring pushed out before anyone drained
    them, surfaced as ``trace_spans_dropped_total`` on /metrics — a
    growing counter means the drain cadence (or the capacity) is wrong
    and the traces being analyzed have holes."""

    def __init__(self, capacity: int = 65536):
        self._capacity = capacity
        self._spans: deque = deque(maxlen=capacity)
        self._lock = traced(threading.Lock(), "TraceRecorder._lock")
        self._dropped = 0

    def record(
        self,
        ctx: Optional[dict],
        name: str,
        start_ts: float,
        dur_s: float,
        *,
        stage_id: int = -1,
        cat: str = "engine",
        args: Optional[dict] = None,
        replica_id: Optional[str] = None,
        role: Optional[str] = None,
    ) -> None:
        """Record one finished span.  ``ctx`` None means the request is
        untraced — the call is a no-op (this is the enablement switch:
        no trace context, no spans).

        ``replica_id``/``role``: fleet identity (docs/observability.md
        journey traces).  Spans carrying a replica id render on their
        own Perfetto process track — N same-process engine replicas
        stepped by one router must not collide on one pid row the way
        same-process pipeline stages deliberately do."""
        if not ctx:
            return
        span = {
            "trace_id": ctx.get("trace_id", ""),
            "request_id": ctx.get("request_id", ""),
            "name": name,
            "cat": cat,
            "stage_id": stage_id,
            "ts_us": start_ts * 1e6,
            "dur_us": max(dur_s, 0.0) * 1e6,
        }
        if replica_id is not None:
            span["replica_id"] = replica_id
        if role is not None:
            span["role"] = role
        if args:
            span["args"] = args
        with self._lock:
            if len(self._spans) == self._capacity:
                self._dropped += 1
            self._spans.append(span)

    def extend(self, spans: list[dict]) -> None:
        """Merge spans recorded by another process (shipped over the
        stage worker's outputs message)."""
        with self._lock:
            overflow = (len(self._spans) + len(spans)) - self._capacity
            if overflow > 0:
                # a batch larger than the whole ring also drops its own
                # head, not just the resident spans it pushes out
                self._dropped += overflow
            self._spans.extend(spans)

    def drain(self) -> list[dict]:
        with self._lock:
            spans = list(self._spans)
            self._spans.clear()
        return spans

    def tail(self, n: int) -> list[dict]:
        """The ``n`` most recent undrained spans, NON-destructively —
        the journey slice an alert evidence bundle captures
        (metrics/alerts.py) must never steal spans from the writer's
        next drain."""
        with self._lock:
            spans = list(self._spans)
        return spans[-n:] if n > 0 else []

    @property
    def spans_dropped(self) -> int:
        """Lifetime spans evicted undrained (trace_spans_dropped_total)."""
        with self._lock:
            return self._dropped

    @property
    def capacity(self) -> int:
        return self._capacity

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


_global_recorder = TraceRecorder()


def get_recorder() -> TraceRecorder:
    """The process-global recorder (one per process; stage workers own
    their own and ship spans back over the command channel)."""
    return _global_recorder


# ------------------------------------------------------------- exporters
#: pid base for per-replica process tracks — far above any plausible
#: stage_id+1 pid so the two namespaces can never collide
_REPLICA_PID_BASE = 1000


def iter_chrome_events(spans):
    """Spans -> Chrome trace events, one at a time (the streaming core
    shared by ``to_chrome_trace`` and ``TraceWriter.export_chrome`` —
    the writer must never materialize a second full copy of the span
    buffer just to serialize it).

    Track layout (docs/observability.md journey-trace tour):

    - spans WITHOUT a replica id: pid = stage_id + 1 (pid 0 is the
      orchestrator, whose spans carry stage_id -1) — the classic
      pipeline-stage layout;
    - spans WITH a replica id (fleet spans: engine replicas behind a
      DisaggRouter, the router itself, control-plane operations): one
      pid per distinct replica id, allocated in first-seen order from
      ``_REPLICA_PID_BASE`` — N same-process replicas get N tracks
      instead of colliding on one stage row;
    - tid = one lane per (pid, request_id) so concurrent requests don't
      overlap in the track view.  Metadata events name every process
      and thread, emitted after the X events."""
    tids: dict[tuple, int] = {}
    stage_pids: set[int] = set()
    replica_pids: dict[str, int] = {}
    replica_roles: dict[str, str] = {}
    for s in spans:
        rid = s.get("replica_id")
        if rid is not None:
            pid = replica_pids.setdefault(
                rid, _REPLICA_PID_BASE + len(replica_pids))
            if s.get("role"):
                replica_roles[rid] = s["role"]  # last role wins
        else:
            pid = int(s.get("stage_id", -1)) + 1
            stage_pids.add(pid)
        key = (pid, s.get("request_id", ""))
        tid = tids.setdefault(key, len(tids) + 1)
        args = {"trace_id": s.get("trace_id", ""),
                "request_id": s.get("request_id", "")}
        if rid is not None:
            args["replica_id"] = rid
            if s.get("role"):
                args["role"] = s["role"]
        args.update(s.get("args") or {})
        yield {
            "name": s.get("name", ""),
            "cat": s.get("cat", ""),
            "ph": "X",
            "ts": s.get("ts_us", 0.0),
            "dur": s.get("dur_us", 0.0),
            "pid": pid,
            "tid": tid,
            "args": args,
        }
    for pid in sorted(stage_pids):
        yield {
            "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": ("orchestrator" if pid == 0
                              else f"stage_{pid - 1}")},
        }
    for rid, pid in replica_pids.items():
        role = replica_roles.get(rid)
        yield {
            "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": (f"replica:{rid} ({role})" if role
                              else f"replica:{rid}")},
        }
    for (pid, req_id), tid in tids.items():
        yield {
            "ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
            "args": {"name": req_id or "-"},
        }


def to_chrome_trace(spans: list[dict]) -> dict:
    """Spans -> a complete Chrome trace-event document (Perfetto
    loadable).  Convenience face of ``iter_chrome_events`` for bounded
    span lists; long-running writers stream instead
    (``TraceWriter.export_chrome``)."""
    return {"traceEvents": list(iter_chrome_events(spans)),
            "displayTimeUnit": "ms"}


class TraceWriter:
    """Sink for drained spans: streams ``{prefix}.trace.jsonl`` (one
    span per line, append-only — same convention as the stats jsonl
    files) and rewrites ``{prefix}.trace.json`` as a complete Chrome
    trace on every ``export_chrome``.  The in-memory accumulation for the
    Chrome export is bounded so a long-running server doesn't hold a
    lifetime of spans (the JSONL keeps the full history), spans the cap
    pushed out are COUNTED (``chrome_spans_dropped``), and the export
    declares its own truncation in ``otherData`` instead of silently
    presenting a tail as the whole story.  The export itself streams
    event-by-event — serializing 200k spans must not build a second
    full copy of the buffer in memory."""

    def __init__(self, path_prefix: str, chrome_capacity: int = 200_000):
        self._prefix = path_prefix
        self._spans: deque = deque(maxlen=chrome_capacity)
        self._lock = traced(threading.Lock(), "TraceWriter._lock")
        # spans the bounded chrome buffer evicted before any export
        # (lifetime) — the truncation note in the export metadata; the
        # JSONL still has them
        self._chrome_dropped = 0
        self._last_export_ts: Optional[float] = None
        # serializes whole exports (heartbeat vs shutdown flush) so two
        # concurrent export_chrome calls never interleave on the same
        # file; distinct from _lock so recording threads don't convoy
        # behind export IO
        self._export_lock = traced(threading.Lock(),
                                   "TraceWriter._export_lock")

    @property
    def jsonl_path(self) -> str:
        return f"{self._prefix}.trace.jsonl"

    @property
    def chrome_path(self) -> str:
        return f"{self._prefix}.trace.json"

    def write(self, spans: list[dict]) -> None:
        if not spans:
            return
        with self._lock:
            cap = self._spans.maxlen or 0
            overflow = (len(self._spans) + len(spans)) - cap
            if cap and overflow > 0:
                self._chrome_dropped += overflow
            self._spans.extend(spans)
            # omnilint: disable=OL9 - the jsonl append must stay
            # ordered with the chrome buffer extend above; writers are
            # rare (drain cadence) and the file is local append-only
            with open(self.jsonl_path, "a") as f:
                for s in spans:
                    f.write(json.dumps(s) + "\n")

    def export_chrome(self) -> str:
        import os
        import time as _time

        with self._export_lock:
            with self._lock:
                spans = list(self._spans)
                dropped = self._chrome_dropped
            # serialize OUTSIDE the span lock (recording threads must
            # not convoy behind file IO), streaming one event at a
            # time into a temp file swapped in atomically — a reader
            # (or a crashed export) never sees a half-written document
            tmp = f"{self.chrome_path}.tmp"
            # omnilint: disable=OL9 - file IO under the EXPORT lock is
            # the point: it serializes rare whole-document exports
            # against each other; span recording rides _lock only and
            # never waits here
            with open(tmp, "w") as f:
                f.write('{"traceEvents":[')
                first = True
                for ev in iter_chrome_events(spans):
                    if not first:
                        f.write(",")
                    first = False
                    f.write(json.dumps(ev))
                meta = {
                    "spans": len(spans),
                    "spans_dropped": dropped,
                    "truncated": dropped > 0,
                    "note": ("chrome buffer capped; the .trace.jsonl "
                             "keeps the full span history"
                             if dropped > 0 else "complete"),
                }
                f.write('],"displayTimeUnit":"ms","otherData":'
                        + json.dumps(meta) + "}")
            os.replace(tmp, self.chrome_path)
            with self._lock:
                self._last_export_ts = _time.time()
        return self.chrome_path

    @property
    def chrome_spans_dropped(self) -> int:
        with self._lock:
            return self._chrome_dropped

    def debug_snapshot(self) -> dict:
        """/debug/trace: writer paths + chrome-buffer bookkeeping."""
        with self._lock:
            return {
                "jsonl_path": self.jsonl_path,
                "chrome_path": self.chrome_path,
                "buffered_spans": len(self._spans),
                "chrome_capacity": self._spans.maxlen,
                "chrome_spans_dropped": self._chrome_dropped,
                "last_export_ts": self._last_export_ts,
            }
