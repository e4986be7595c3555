"""/debug/z builders: JSON-ready views of a live serving process.

Everything /metrics can't answer during an incident — *which* request
is stuck, what the pipeline slot holds, which radix nodes pin which
pages — renders here.  Pure read-side introspection over duck-typed
engine/stage objects (``getattr`` throughout): AR engines report
everything, diffusion/generation engines and process-disaggregated
stages degrade to whatever they expose, and a half-built pipeline
mid-crash still produces a document instead of a second traceback.

Served by the OpenAI server (entrypoints/openai/api_server.py):

- ``/debug/z``              — index of the family
- ``/debug/engine``         — per-stage engine state (pipeline slot,
                              last step record, warmup/bucket state,
                              compile + fallback telemetry)
- ``/debug/requests``       — in-flight request table
- ``/debug/kv``             — pages/pins/radix/tier occupancy
- ``/debug/flightrecorder`` — the step-record ring (?n= tail size)
- ``/debug/stacks``         — all-thread stacks
- ``/debug/watchdog``       — stall-watchdog state

None of these mutate anything, and none sync the device.
"""

from __future__ import annotations

import time
from typing import Any, Optional

from vllm_omni_tpu.introspection.flight_recorder import capture_stacks

ENDPOINTS = ("/debug/engine", "/debug/requests", "/debug/kv",
             "/debug/flightrecorder", "/debug/stacks", "/debug/watchdog",
             "/debug/disagg", "/debug/controlplane", "/debug/trace",
             "/debug/alerts", "/debug/tenants", "/debug/cache")


# -------------------------------------------------------- request table
def request_table(engine) -> list[dict]:
    """In-flight request table for one engine: the incident-response
    answer to "which request is stuck".  Age/deadline are monotonic
    durations; absent fields degrade to None."""
    from vllm_omni_tpu.resilience.deadline import remaining_s

    sched = getattr(engine, "scheduler", None)
    if sched is None:
        return []
    now = time.monotonic()
    rows: list[dict] = []
    for phase, queue in (("waiting", getattr(sched, "waiting", ())),
                         ("running", getattr(sched, "running", ()))):
        for req in list(queue):
            info = getattr(req, "additional_information", {}) or {}
            remaining = remaining_s(getattr(req, "deadline_ts", None))
            arrival = getattr(req, "arrival_mono", 0.0)
            rows.append({
                "request_id": getattr(req, "request_id", "?"),
                "phase": phase,
                "status": getattr(getattr(req, "status", None),
                                  "name", str(getattr(req, "status", ""))),
                "tenant": getattr(req, "tenant", "default"),
                "age_s": round(now - arrival, 3) if arrival else None,
                "prompt_tokens": getattr(req, "num_prompt_tokens", None),
                "output_tokens": len(getattr(req, "output_token_ids", ())),
                "computed_tokens": getattr(req, "num_computed_tokens",
                                           None),
                "inflight_tokens": getattr(req, "num_inflight_tokens", 0),
                "deadline_remaining_s": (round(remaining, 3)
                                         if remaining is not None
                                         else None),
                "awaiting_chunks": bool(getattr(req, "awaiting_chunks",
                                                False)),
                "parked": bool(info.get("_parked_len")),
            })
    return rows


# --------------------------------------------------------- engine views
def _pipeline_slot(engine) -> dict:
    inflight = getattr(engine, "_inflight", None)
    if inflight is None:
        return {"occupied": False}
    sched_out = getattr(inflight, "sched_out", None)
    handle = getattr(inflight, "handle", None)
    rows = getattr(handle, "rows", None)
    return {
        "occupied": True,
        "prefills": len(getattr(sched_out, "prefills", ())),
        "decodes": len(getattr(sched_out, "decodes", ())),
        "rows": sorted(rows) if isinstance(rows, dict) else None,
    }


def engine_debug(engine) -> dict:
    """Pipeline slot + last step record + warmup/bucket/compile state
    for one engine (AR; other engine kinds report what they have)."""
    runner = getattr(engine, "runner", None)
    flight = getattr(engine, "flight", None)
    cfg = getattr(engine, "config", None)
    last = flight.tail(1) if flight is not None else []
    doc: dict[str, Any] = {
        "engine_type": type(engine).__name__,
        "stage_id": getattr(engine, "stage_id", None),
        "has_unfinished": bool(getattr(engine, "has_unfinished_requests",
                                       False)),
        "pipeline_slot": _pipeline_slot(engine),
        "last_step": last[0] if last else None,
        "last_step_age_s": (flight.last_step_age_s()
                            if flight is not None else None),
        "async_fallback": dict(getattr(engine, "async_fallback", {}) or {}),
    }
    if cfg is not None:
        doc["config"] = {
            "worker_type": getattr(cfg, "worker_type", None),
            "async_scheduling": getattr(cfg, "async_scheduling", None),
            "unified_batching": getattr(cfg, "unified_batching", None),
            "kv_offload": getattr(cfg, "kv_offload", None),
            "max_num_seqs": getattr(cfg, "max_num_seqs", None),
            "max_num_batched_tokens": getattr(cfg,
                                              "max_num_batched_tokens",
                                              None),
        }
    if runner is not None:
        doc["warmup"] = {
            "batch_buckets": list(getattr(runner, "_batch_buckets", ())),
            "seq_buckets": list(getattr(runner, "_seq_buckets", ())),
            "token_buckets": list(getattr(runner, "_token_buckets", ())),
            "shapes_seen": len(getattr(runner, "_jit_seen", ()) or ()),
        }
        # the executables by name; ``current`` names the one a warm-up
        # or a step is building right now
        stats = getattr(runner, "compile_stats", None)
        doc["compile"] = stats.snapshot() if stats is not None else {}
    ledger = getattr(engine, "memory", None)
    if ledger is not None:
        doc["device_memory"] = ledger.snapshot()
    roofline = getattr(engine, "roofline", None)
    if roofline is not None:
        # rolling MFU/MBU window (metrics/roofline.py): the live
        # roofline view — window means + the last ~32 per-step readings
        doc["roofline"] = roofline.snapshot()
    return doc


def kv_debug(engine) -> dict:
    """Radix/page/pin/tier occupancy for one engine's KV manager."""
    sched = getattr(engine, "scheduler", None)
    kv = getattr(sched, "kv", None)
    if kv is None:
        return {}
    fn = getattr(kv, "debug_snapshot", None)
    doc = fn() if fn is not None else {
        "pages_total": getattr(kv, "num_pages", None),
        "pages_free": getattr(kv, "num_free_pages", None),
    }
    tiers = getattr(engine, "kv_tiers", None)
    if tiers is not None:
        doc["tiers"] = tiers.debug_snapshot()
    return doc


# ----------------------------------------------------- pipeline rollups
def _stage_engines(omni):
    """[(stage_id, engine-or-None, stage)] over the pipeline; proc
    stages carry engine None (their engine lives in the worker)."""
    out = []
    for stage in getattr(omni, "stages", ()):
        out.append((getattr(stage, "stage_id", None),
                    getattr(stage, "engine", None), stage))
    return out


def _per_stage(omni, fn, empty) -> dict:
    doc = {}
    for sid, engine, stage in _stage_engines(omni):
        if engine is None:
            doc[str(sid)] = {
                "process_stage": True,
                "note": "engine runs in a worker process; see the "
                        "worker's own dump / engine_metrics_snapshot",
                "metrics_snapshot": _safe_snapshot(stage),
            }
        else:
            try:
                doc[str(sid)] = fn(engine)
            except Exception as e:
                # the builders read live engine state without locks;
                # a torn read mid-mutation degrades to an error marker
                # instead of 500ing the one request an operator is
                # using to debug the engine — retry, don't crash
                doc[str(sid)] = {"error": repr(e), "retry": True}
    return doc if doc else empty


def _safe_snapshot(stage) -> dict:
    fn = getattr(stage, "engine_metrics_snapshot", None)
    try:
        return fn() if fn is not None else {}
    except Exception:
        return {}


def debug_engine(omni) -> dict:
    return {"stages": _per_stage(omni, engine_debug, {})}


def debug_requests(omni) -> dict:
    return {"stages": _per_stage(omni, request_table, {})}


def debug_kv(omni) -> dict:
    return {"stages": _per_stage(omni, kv_debug, {})}


def debug_flightrecorder(omni, tail: Optional[int] = None) -> dict:
    def one(engine):
        flight = getattr(engine, "flight", None)
        return (flight.snapshot(tail=tail) if flight is not None
                else {})

    return {"stages": _per_stage(omni, one, {})}


def debug_stacks() -> dict:
    return {"stacks": capture_stacks()}


def debug_watchdog(omni) -> dict:
    wd = getattr(omni, "watchdog", None)
    return wd.state() if wd is not None else {"enabled": False}


def debug_disagg(omni) -> dict:
    """Disagg-router state (docs/disaggregation.md): replica table
    (role/dead/ejected/drained/queue depth), in-flight request phases,
    and the failover/handoff ledgers.  ``{"enabled": False}`` on
    deployments without a router — the endpoint always answers."""
    router = getattr(omni, "router", None)
    if router is None:
        return {"enabled": False}
    try:
        return router.debug_snapshot()
    except Exception as e:
        # same stance as _per_stage: a torn concurrent read degrades
        # to a retry marker, never a 500 on the debugging request
        return {"enabled": True, "error": repr(e), "retry": True}


def debug_cache(omni) -> dict:
    """Fleet cache-economics board (docs/disaggregation.md): per-
    replica radix digest summaries, top cross-replica duplicated
    prefixes, the dispatch regret ledger, and the fleet hit-rate
    counters.  ``{"enabled": False}`` on deployments without a disagg
    router — the endpoint always answers; a torn concurrent read
    degrades to the retry marker, never a 500."""
    cache = getattr(getattr(omni, "router", None), "cache", None)
    if cache is None:
        return {"enabled": False}
    try:
        return cache.board()
    except Exception as e:
        return {"enabled": True, "error": repr(e), "retry": True}


def debug_controlplane(omni) -> dict:
    """Control-plane state (docs/control_plane.md): the sensor
    snapshot, the in-flight operation's stage, warming replicas, and
    the structured-action ring.  ``{"enabled": False}`` on deployments
    without a controller — the endpoint always answers."""
    cp = getattr(omni, "controlplane", None)
    if cp is None:
        return {"enabled": False}
    try:
        return cp.debug_snapshot()
    except Exception as e:
        # same stance as _per_stage: a torn concurrent read degrades
        # to a retry marker, never a 500 on the debugging request
        return {"enabled": True, "error": repr(e), "retry": True}


def debug_trace(omni) -> dict:
    """Trace-layer self-view (docs/observability.md): recorder
    occupancy + drop accounting, and — when a writer is configured —
    its file paths, chrome-buffer bookkeeping, and the last-export
    timestamp.  The one subsystem that had no /debug view of itself:
    "why does my trace have holes" is answered here, not by reading
    the jsonl backwards."""
    from vllm_omni_tpu.tracing import get_recorder

    rec = get_recorder()
    writer = getattr(omni, "_trace_writer", None)
    doc = {
        "enabled": writer is not None,
        "recorder": {
            "buffered_spans": len(rec),
            "capacity": rec.capacity,
            "spans_dropped": rec.spans_dropped,
        },
    }
    if writer is not None:
        try:
            doc["writer"] = writer.debug_snapshot()
        except Exception as e:
            # same stance as _per_stage: torn read -> retry marker
            doc["writer"] = {"error": repr(e), "retry": True}
    return doc


def debug_alerts(omni) -> dict:
    """Alert-engine state (docs/observability.md): every rule's
    declaration + lifecycle state, window values at the last
    evaluation, the transition-ring tail, and the dump-cooldown
    self-view evidence capture rides.  ``{"enabled": False}`` on
    deployments without an alert engine — the endpoint always
    answers."""
    alerts = getattr(omni, "alerts", None)
    if alerts is None:
        return {"enabled": False}
    try:
        return alerts.snapshot()
    except Exception as e:
        # same stance as _per_stage: a torn concurrent read degrades
        # to a retry marker, never a 500 on the debugging request
        return {"enabled": True, "error": repr(e), "retry": True}


def debug_tenants(omni) -> dict:
    """Per-stage tenant attribution boards (metrics/attribution.py):
    top-k heavy hitters per consumption meter with their proven error
    bounds — the incident answer to "which tenant is eating the
    fleet"."""

    def one(engine):
        attr = getattr(engine, "attribution", None)
        # claim_slots=False: a debugging poll must not burn lifetime
        # /metrics label slots on tenants the exposition never renders
        return (attr.snapshot(claim_slots=False)
                if attr is not None else {})

    return {"stages": _per_stage(omni, one, {})}


def debug_index() -> dict:
    return {"endpoints": list(ENDPOINTS),
            "hint": "see docs/debugging.md for the tour"}


# ---------------------------------------------------------------- health
def health_snapshot(omni, engine_thread_alive: Optional[bool] = None
                    ) -> tuple[int, dict]:
    """The honest /health: (status_code, body).  503 once the watchdog
    has tripped or the engine loop died — a load balancer must eject a
    wedged replica instead of feeding it traffic the static "ok" used
    to invite."""
    wd = getattr(omni, "watchdog", None)
    ages = []
    for _, engine, _ in _stage_engines(omni):
        flight = getattr(engine, "flight", None)
        if flight is not None:
            age = flight.last_step_age_s()
            if age is not None:
                ages.append(age)
    body: dict[str, Any] = {
        "status": "ok",
        # youngest engine step across stages; None before any step ran
        # (an idle engine's age GROWS — pair it with the busy flag)
        "last_step_age_s": (round(min(ages), 3) if ages else None),
        "busy": any(
            bool(getattr(e, "has_unfinished_requests", False))
            for _, e, _ in _stage_engines(omni) if e is not None),
        "watchdog": (wd.state() if wd is not None
                     else {"enabled": False}),
    }
    # read-only alert visibility: the count of firing alerts rides the
    # payload WITHOUT joining the 503 decision — ejection stays the
    # watchdog/engine-liveness contract (an overload alert means "shed
    # and scale", not "take the replica out back")
    alerts = getattr(omni, "alerts", None)
    if alerts is not None:
        try:
            body["alerts_firing"] = len(alerts.firing())
        except Exception:
            body["alerts_firing"] = None
    if engine_thread_alive is not None:
        body["engine_alive"] = bool(engine_thread_alive)
    code = 200
    if wd is not None and wd.tripped is not None:
        body["status"] = "stalled"
        code = 503
    if engine_thread_alive is False:
        body["status"] = "dead"
        code = 503
    return code, body
