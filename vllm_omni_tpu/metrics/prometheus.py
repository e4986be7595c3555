"""Prometheus text exposition for the pipeline + engine metrics.

The serving layer's ``GET /metrics`` renders through here (JSON summary
stays available at ``/metrics?format=json``).  Dependency-free on
purpose — the runtime ships no prometheus_client, matching the
native-runtime stance of the stdlib HTTP server.

``METRIC_SPECS`` is the single source of truth for the exported metric
surface: name (without the ``vllm_omni_tpu_`` prefix), type, help, and
the labels every sample must carry.  ``validate_exposition`` parses a
rendered exposition back against it — ``scripts/check_metrics_names.py``
and the metrics tests both run that check so the surface can't silently
drift.
"""

from __future__ import annotations

import re
from typing import Optional

METRIC_PREFIX = "vllm_omni_tpu_"

# metric name must match this (prefix + lowercase/underscore only — no
# digits, which is why the E2E latency series is "request_latency_ms")
NAME_RE = re.compile(r"vllm_omni_tpu_[a-z_]+")

# name -> (type, help, required label names)
METRIC_SPECS: dict[str, tuple[str, str, tuple[str, ...]]] = {
    "requests_finished_total": (
        "counter", "Requests that completed the full pipeline", ()),
    "request_latency_ms": (
        "gauge", "End-to-end request latency percentiles (recent window)",
        ("quantile",)),
    "stage_requests_total": (
        "counter", "Requests processed per stage", ("stage",)),
    "stage_tokens_in_total": (
        "counter", "Input tokens per stage", ("stage",)),
    "stage_tokens_out_total": (
        "counter", "Output tokens per stage", ("stage",)),
    "stage_tokens_per_second": (
        "gauge", "Generation throughput per stage", ("stage",)),
    "transfer_count_total": (
        "counter", "Inter-stage transfers per edge",
        ("from_stage", "to_stage")),
    "transfer_bytes_total": (
        "counter", "Inter-stage transfer bytes per edge",
        ("from_stage", "to_stage")),
    "transfer_ms_total": (
        "counter", "Inter-stage transfer milliseconds per edge",
        ("from_stage", "to_stage")),
    "scheduler_waiting": (
        "gauge", "Requests in the waiting queue", ("stage",)),
    "scheduler_running": (
        "gauge", "Requests in the running batch", ("stage",)),
    "preemptions_total": (
        "counter", "Requests preempted (recompute policy)", ("stage",)),
    "rejections_total": (
        "counter", "Requests rejected at intake or error-finished",
        ("stage",)),
    "kv_pages_total": (
        "gauge", "KV cache pages in the pool", ("stage",)),
    "kv_pages_used": (
        "gauge", "KV cache pages allocated to live requests", ("stage",)),
    "kv_page_utilization": (
        "gauge", "Fraction of KV cache pages in use", ("stage",)),
    # the JSON snapshot's ``kv_pages_used`` histogram (the gauge above
    # has that name here)
    "kv_pages_used_per_step": (
        "histogram", "KV cache pages held, observed once a step",
        ("stage",)),
    "prefix_cache_hits_total": (
        "counter", "Automatic-prefix-cache hits", ("stage",)),
    "prefix_cache_hit_tokens_total": (
        "counter", "Prompt tokens served from the prefix cache",
        ("stage",)),
    # ---- the window kind of page of models with sliding-window layers,
    # and the selection of models with learned sparse attention
    # (docs/kv_cache.md, "Two kinds of page")
    "kv_window_pages_used": (
        "histogram",
        "Pages of the window kind held, observed once a step", ("stage",)),
    # the pools a KIND of attention layer, where a model has two kinds or
    # keeps an index key (the snapshot's ``kv.kinds``)
    "kv_kind_heads": (
        "gauge", "Rows a cached token has in a pool of this kind of layer "
        "(its kv heads)", ("stage", "kind")),
    "kv_kind_row_width": (
        "gauge", "Lanes a K row (a latent layer's one row) takes in this "
        "kind's pool, padding included", ("stage", "kind")),
    "kv_kind_row_used": (
        "gauge", "Of those lanes, the ones that hold numbers",
        ("stage", "kind")),
    "kv_kind_pages_total": (
        "gauge", "Pages of this kind's pool", ("stage", "kind")),
    "sparse_keep_pct": (
        "histogram",
        "Share of the visible keys a step's rows attend on sparse layers",
        ("stage",)),
    "window_keep_pct": (
        "histogram",
        "Share of the visible keys a step's rows attend on sliding layers",
        ("stage",)),
    "window_prefix_hits_refused_total": (
        "counter",
        "Prefix-cache hits not handed out because freed window pages "
        "no longer hold the boundary's rows", ("stage",)),
    "window_pages_freed_total": (
        "counter", "Window pages given back after sliding out of reach",
        ("stage",)),
    # ---- the state cache of models with recurrent layers
    # (docs/kv_cache.md): one slot a sequence beside its pages
    "state_slots": (
        "histogram", "State slots in use, observed once a step",
        ("stage",)),
    "ssd_chunk_fill": (
        "histogram",
        "Tokens over chunk slots x 100 of a ragged step's state-space "
        "chunks", ("stage",)),
    "state_slot_allocs_total": (
        "counter", "State slots handed to a sequence with its first page",
        ("stage",)),
    "state_resets_total": (
        "counter",
        "Preemptions that threw away a sequence's recurrent state",
        ("stage",)),
    "prefix_hits_refused_total": (
        "counter",
        "Prefix-cache hits not handed out because pages hold no "
        "recurrent state", ("stage",)),
    # ---- kvcache subsystem: radix prefix index + tiered offload
    # (docs/kv_cache.md)
    "kv_prefix_hit_tokens_total": (
        "counter",
        "Prompt tokens adopted from the radix prefix index (all tiers)",
        ("stage",)),
    "kv_tier_hbm_pages": (
        "gauge", "KV pages holding live data on the device", ("stage",)),
    "kv_tier_host_pages": (
        "gauge", "KV payloads parked in the host-RAM tier", ("stage",)),
    "kv_tier_remote_pages": (
        "gauge", "KV payloads parked in the remote tier", ("stage",)),
    "kv_offload_bytes_total": (
        "counter",
        "KV bytes moved per tier and direction (out = away from HBM, "
        "in = restored toward it)", ("stage", "tier", "dir")),
    "kv_restore_seconds": (
        "histogram",
        "Tier-restore latency per request run (fetch + inject)",
        ("stage",)),
    "kv_restored_tokens_total": (
        "counter",
        "Recompute tokens avoided by tier restores (cold prefix "
        "adoptions + park restores)", ("stage",)),
    "kv_parked_tokens_total": (
        "counter", "Tokens parked to the tiers at preemption",
        ("stage",)),
    "engine_steps_total": (
        "counter", "Engine step() executions", ("stage",)),
    "tokens_generated_total": (
        "counter", "Output tokens sampled", ("stage",)),
    "prefill_tokens_total": (
        "counter", "Prompt tokens prefilled", ("stage",)),
    "ttft_ms": (
        "histogram", "Time to first token", ("stage",)),
    "tpot_ms": (
        "histogram", "Time per output token (finished requests)",
        ("stage",)),
    "itl_ms": (
        "histogram", "Inter-token latency", ("stage",)),
    "engine_step_ms": (
        "histogram", "Engine step wall time", ("stage",)),
    # step-phase breakdown (async pipelined engine, docs/async_engine.md)
    "engine_step_host_ms": (
        "histogram",
        "Host-side work per engine step: the step less its wait on the "
        "device (schedule, assemble, unpack, update, bookkeeping)",
        ("stage",)),
    "engine_step_device_ms": (
        "histogram",
        "Time the engine thread waited on the device per engine step "
        "(the jitted call's launch and the readback's device_get)",
        ("stage",)),
    "engine_step_overlap_ratio": (
        "gauge",
        "Fraction of host step work overlapped with in-flight device "
        "compute", ("stage",)),
    # lifetime counter pairing with engine_step_host_ms_sum: rate()
    # over any window recovers a WINDOWED overlap ratio, which the
    # cumulative gauge above hides after long uptime
    "engine_step_overlapped_host_ms_total": (
        "counter",
        "Host step work milliseconds performed while a dispatched "
        "device step was in flight", ("stage",)),
    # ---- the host's phases on the host's clock, every step
    # (introspection/phase_clock.py, docs/observability.md)
    "engine_host_phase_ms_total": (
        "counter",
        "Exclusive milliseconds the engine thread spent in a host phase "
        "(omni.*: the phases of one turn of the loop sum to the turn)",
        ("stage", "phase")),
    "engine_stalls_total": (
        "counter",
        "Turns of the engine loop of a second or more and four times "
        "the recent 99th percentile, with no compile in them",
        ("stage",)),
    "engine_stall_ms_total": (
        "counter",
        "Milliseconds of stalled turns above the recent 99th percentile",
        ("stage",)),
    # ---- unified ragged batching (docs/ragged_batching.md)
    "engine_step_padding_efficiency": (
        "gauge",
        "Useful tokens / padded device rows across dispatches "
        "(1.0 = zero padding)", ("stage",)),
    "engine_step_batched_tokens": (
        "histogram", "Real tokens computed per engine step", ("stage",)),
    "engine_step_useful_tokens_total": (
        "counter", "Real tokens computed across device dispatches",
        ("stage",)),
    "engine_step_padded_tokens_total": (
        "counter", "Padded device rows across dispatches", ("stage",)),
    # ---- live roofline attribution (metrics/roofline.py,
    # docs/performance.md): achieved FLOPs / HBM bytes per step from
    # static model geometry × the token mix, over the platform peaks —
    # rolling-window means, wall-clock denominator (host stalls count
    # against utilization, exactly as they count against goodput)
    "engine_step_mfu": (
        "gauge",
        "Achieved model FLOPs over the platform bf16 peak, rolling "
        "window over recent steps (wall-clock denominator)", ("stage",)),
    "engine_step_mbu": (
        "gauge",
        "Achieved HBM bytes (weights + KV traffic) over the platform "
        "bandwidth peak per phase (prefill | decode | mixed — a "
        "token-packed step carrying both row kinds reports honestly "
        "as mixed), rolling window",
        ("stage", "phase")),
    # jit shape-cache telemetry: the unified path shrinks the cache
    # from a (batch, seq) grid to a token-bucket line — measurable here
    "jit_compiles_total": (
        "counter", "Fresh XLA executable compiles in the model runner",
        ("stage",)),
    "jit_cache_hits_total": (
        "counter", "Runner dispatches served by the jit shape cache",
        ("stage",)),
    "jit_compile_seconds_total": (
        "counter",
        "Cumulative seconds spent blocked on fresh compiles "
        "(first call per shape, to completion)", ("stage",)),
    # ---- the set-up on the program's own clock
    # (introspection/phase_clock.py, docs/observability.md "Set-up")
    "engine_compile_seconds_total": (
        "counter",
        "Seconds of the fresh executables' first calls by part: JAX's "
        "own tracing, lowering, and compiling or loading from the "
        "persistent cache (backend), and the rest (run): they sum to "
        "jit_compile_seconds_total", ("stage", "part")),
    "engine_setup_seconds": (
        "gauge",
        "Seconds of the process's start-up record by span (omni.setup "
        "and its children, summed over stages): a span's whole "
        "duration, children included", ("stage", "phase")),
    # async pipeline drain granularity (docs/async_engine.md): sync
    # steps per reason while async scheduling is on.  Since PR 11 only
    # host-state reasons exist (kv_transfer | kv_offload | streaming |
    # kv_pressure | reshaped) — the shape-based fallback matrix (spec /
    # logprobs / collect_hidden / embeds / prefill) is deleted with the
    # split executor and those label values can no longer be emitted
    "async_fallback_total": (
        "counter",
        "Async pipeline steps that fell back to the synchronous path",
        ("stage", "reason")),
    # how often the two-slot pipeline engages: rate() of this over
    # rate(engine_steps_total) is the share of steps dispatched ahead of
    # their predecessor's retire; each drain is a step after which the
    # next one overlaps nothing
    "pipelined_steps_total": (
        "counter",
        "Engine steps dispatched through the two-slot pipeline",
        ("stage",)),
    "pipeline_drains_total": (
        "counter",
        "In-flight steps retired with nothing dispatched behind them "
        "(bubble, synchronous fallback, or an empty queue)", ("stage",)),
    # ---- serving-curve observability (docs/load_testing.md): SLO
    # attainment + goodput per tenant, admission-control shedding,
    # queueing, and per-phase saturation — the engine-side face of the
    # open-loop load harness (vllm_omni_tpu/loadgen/)
    "slo_attainment_ratio": (
        "gauge",
        "Finished requests meeting every configured SLO target "
        "(TTFT/TPOT) over all finished, per tenant", ("stage", "tenant")),
    "slo_requests_total": (
        "counter", "Finished requests judged against the SLO targets",
        ("stage", "tenant")),
    # lifetime counter pair for slo_attainment_ratio: rate() over any
    # window recovers a WINDOWED attainment the cumulative gauge hides
    "slo_requests_met_total": (
        "counter", "Finished requests inside every SLO target",
        ("stage", "tenant")),
    "goodput_tokens_total": (
        "counter",
        "Output tokens from requests that met their SLO targets "
        "(tokens_generated_total counts all — the gap is wasted work)",
        ("stage", "tenant")),
    "shed_requests_total": (
        "counter",
        "Arrivals refused by admission control (HTTP 429), per reason "
        "— distinct from 503 retryable / 504 deadline_exceeded",
        ("stage", "reason", "tenant")),
    "request_queue_depth": (
        "gauge", "Waiting-queue depth per tenant", ("stage", "tenant")),
    "queue_wait_ms": (
        "histogram", "Arrival to first scheduled, per request",
        ("stage",)),
    "intake_ms": (
        "histogram",
        "HTTP accept to engine arrival, per request the front end stamped",
        ("stage",)),
    # ---- the streamed replies of the serving layer (docs/serving.md,
    # "Streaming"): process-wide, written by the streams' one writer
    "stream_requests_total": (
        "counter", "Chat requests answered as a stream of events", ()),
    "stream_events_total": (
        "counter", "Content events written to streams", ()),
    "stream_tokens_total": (
        "counter",
        "Tokens those events carried (over events: tokens an event)", ()),
    "stream_first_event_ms": (
        "histogram",
        "Engine's first token to the write of the first content event",
        ()),
    "phase_saturation_ratio": (
        "gauge",
        "Fraction of the capacity ceiling used per phase (prefill/"
        "decode token budget, running seats) at the last schedule",
        ("stage", "phase")),
    # ---- introspection (docs/debugging.md): device-memory ledger,
    # span-loss accounting, stall-watchdog state
    "device_memory_bytes": (
        "gauge",
        "Live device memory per component (weights, kv_pages, "
        "spec_buffers, workspace); components sum to the device total",
        ("stage", "component")),
    "device_memory_peak_bytes": (
        "gauge",
        "Peak watermark of device memory per component (monotone)",
        ("stage", "component")),
    "trace_spans_dropped_total": (
        "counter",
        "Trace spans evicted from the recorder ring before any drain "
        "(a growing count means the trace files have holes)", ()),
    "watchdog_trips_total": (
        "counter", "Stall-watchdog trips (true hangs, compile stalls "
        "exempted)", ()),
    "watchdog_tripped": (
        "gauge",
        "Whether the stall watchdog has tripped (1 = /health serves "
        "503)", ()),
    "diffusion_requests_total": (
        "counter", "Diffusion requests generated", ("stage",)),
    "diffusion_batches_total": (
        "counter", "Diffusion batches executed", ("stage",)),
    "diffusion_gen_seconds": (
        "histogram", "Diffusion batch generation time", ("stage",)),
    "hbm_bytes": (
        "gauge", "Device HBM capacity", ()),
    # ---- resilience subsystem (vllm_omni_tpu/resilience/metrics.py) —
    # orchestrator-side restart/retry/breaker/deadline/fault counters
    "stage_restarts_total": (
        "counter", "Supervised stage worker restarts", ("stage",)),
    "stage_heartbeat_misses_total": (
        "counter", "Heartbeat intervals without a worker pong",
        ("stage",)),
    "requests_redelivered_total": (
        "counter",
        "Queued-but-unstarted requests redelivered after a restart",
        ("stage",)),
    "requests_failed_retryable_total": (
        "counter",
        "Requests failed fast with a retryable error (worker lost)",
        ("stage",)),
    "connector_retries_total": (
        "counter", "Connector RPC attempts that failed and were retried",
        ("site",)),
    "circuit_breaker_trips_total": (
        "counter", "Circuit breaker transitions to OPEN", ("site",)),
    "circuit_breaker_open": (
        "gauge", "Whether the edge's circuit breaker is open",
        ("site",)),
    "deadline_exceeded_total": (
        "counter", "Requests terminated by their end-to-end deadline",
        ("stage",)),
    "faults_injected_total": (
        "counter", "Fault-plan injections fired (testing only)",
        ("site",)),
    # ---- disaggregated prefill/decode serving (vllm_omni_tpu/disagg/,
    # docs/disaggregation.md) — handoff volume/latency, failover ledger,
    # router tier health, degradation state
    "kv_handoff_bytes_total": (
        "counter",
        "Prefill->decode KV handoff bytes per direction (out = shipped "
        "by the prefill tier, in = received by the decode tier)",
        ("dir",)),
    "kv_handoff_seconds": (
        "histogram",
        "Prefill->decode KV handoff latency per request (ship + "
        "receive + integrity verification)", ()),
    "failover_total": (
        "counter",
        "Requests re-routed by the disagg router, per reason (replica "
        "death, handoff failure, adoption failure, tier loss)",
        ("reason",)),
    "router_healthy_replicas": (
        "gauge",
        "Replicas in the dispatch rotation per tier (healthy, not "
        "drained)", ("role",)),
    "degraded_mode": (
        "gauge",
        "Whether the router is serving colocated because a tier has "
        "zero healthy replicas (1 = degraded)", ()),
    # ---- control plane (vllm_omni_tpu/controlplane/,
    # docs/control_plane.md) — re-role/autoscale actuation ledger,
    # fleet shape, and the WFQ scheduler's deferral accounting
    "controlplane_reroles_total": (
        "counter",
        "Completed live role flips (drain -> quiesce -> flip -> "
        "re-admit) per direction", ("from_role", "to_role")),
    "controlplane_replicas": (
        "gauge", "Non-dead replicas per role, as the controller sees "
        "the fleet", ("role",)),
    "controlplane_actions_total": (
        "counter",
        "Control-plane actions applied on the router thread (drain, "
        "undrain, rerole, scale_up, remove_replica)", ("action",)),
    "wfq_deferred_requests_total": (
        "counter",
        "Deficit-round-robin rounds that held a tenant's head-of-line "
        "request back while placing other work (weighted-fair overload "
        "scheduling)", ("stage", "tenant")),
    # ---- omnipulse (metrics/alerts.py + metrics/attribution.py,
    # docs/observability.md): alert lifecycle + per-tenant heavy-hitter
    # attribution.  Attribution values are space-saving sketch
    # ESTIMATES (est >= true >= est - total/capacity); only the top-k
    # tenants per meter render, inside the tenant cardinality cap
    "alerts_firing": (
        "gauge", "Whether the named alert rule is firing (1 = its "
        "condition held past for-duration)", ("alert",)),
    "alert_transitions_total": (
        "counter",
        "Alert lifecycle transitions per rule and destination state "
        "(pending | firing | resolved | inactive)", ("alert", "to")),
    "tenant_tokens_total": (
        "counter",
        "Per-tenant token consumption by kind (prefill | decode), "
        "space-saving estimate over the top-k heavy hitters",
        ("stage", "tenant", "kind")),
    "tenant_kv_page_seconds_total": (
        "counter",
        "Per-tenant KV page-seconds of residency per tier (hbm = live "
        "device pages, host = parked payloads), sketch estimate",
        ("stage", "tenant", "tier")),
    "tenant_handoff_bytes_total": (
        "counter",
        "Per-tenant prefill->decode KV handoff bytes, sketch estimate",
        ("stage", "tenant")),
    "tenant_queue_wait_ms_total": (
        "counter",
        "Per-tenant cumulative arrival-to-first-scheduled wait, "
        "sketch estimate", ("stage", "tenant")),
    "tenant_sheds_total": (
        "counter",
        "Per-tenant admission-control sheds, sketch estimate — unlike "
        "shed_requests_total this sees past the cardinality cap",
        ("stage", "tenant")),
    "attribution_tracked_tenants": (
        "gauge",
        "Distinct tenants currently tracked by the attribution sketch "
        "per meter (bounded by the sketch capacity)",
        ("stage", "meter")),
    # ---- omniscope (metrics/cache_economics.py,
    # docs/observability.md): fleet KV-cache economics — router-
    # aggregated radix digests scoring every dispatch for wasted
    # re-prefill (the regret signal prefix-affinity routing minimizes)
    "fleet_prefix_hit_tokens_total": (
        "counter",
        "Prompt tokens served from ANY replica's prefix cache, "
        "fleet-wide (delta-accumulated across replica replacement)",
        ()),
    "fleet_prefill_tokens_total": (
        "counter",
        "Prompt tokens prefilled fleet-wide — the hit-rate "
        "denominator's other half", ()),
    "fleet_prefix_hit_rate": (
        "gauge",
        "Fleet prefix hit rate: hit tokens / (hit + prefilled) over "
        "the fleet's lifetime counters", ()),
    "fleet_duplicate_prefill_tokens_total": (
        "counter",
        "Wasted re-prefill: tokens the chosen replica prefilled that "
        "another in-rotation replica (peer_replica) or a parked cold "
        "copy (peer_cold_tier) already held", ("reason",)),
    "fleet_duplicate_prefix_tokens": (
        "gauge",
        "Tokens of prefix content currently duplicated across replica "
        "caches (k replicas holding a page count k-1 redundant "
        "copies), from the bounded digests", ()),
    "cache_digest_nodes": (
        "gauge",
        "Radix digest entries exported by the replica on the last "
        "stride refresh (hard-capped — the digest cost bound)",
        ("replica",)),
    "tenant_duplicate_prefill_tokens_total": (
        "counter",
        "Per-tenant wasted re-prefill tokens, sketch estimate — which "
        "tenants' traffic the cache-blind router scatters",
        ("stage", "tenant")),
    # ---- omniaffinity (disagg/router.py, docs/disaggregation.md):
    # prefix-affinity dispatch + the cluster KV fabric
    "router_affinity_dispatch_total": (
        "counter",
        "Affinity-scored placements by outcome: hit (a warm owner "
        "won), miss (cold prefix — load + tenant-hash owner), "
        "load_override (a warm hit existed but load won the score)",
        ("outcome",)),
    "kv_prefix_pull_bytes_total": (
        "counter",
        "Bytes of shared-prefix KV pulled from the cluster fabric "
        "instead of re-prefilled; src=peer when a live replica still "
        "advertises the prefix HBM-resident, cold otherwise",
        ("src",)),
    "kv_prefix_pull_seconds": (
        "histogram",
        "Fabric prefix-pull latency: fetch + integrity verify + "
        "re-publish, as seen by the router thread", ()),
}

#: attribution meter -> (/metrics series, fixed extra labels); meters
#: without a row stay /debug/tenants-only
_ATTRIBUTION_SERIES: dict[str, tuple[str, dict]] = {
    "prefill_tokens": ("tenant_tokens_total", {"kind": "prefill"}),
    "decode_tokens": ("tenant_tokens_total", {"kind": "decode"}),
    "kv_page_seconds_hbm": ("tenant_kv_page_seconds_total",
                            {"tier": "hbm"}),
    "kv_page_seconds_host": ("tenant_kv_page_seconds_total",
                             {"tier": "host"}),
    "handoff_bytes": ("tenant_handoff_bytes_total", {}),
    "queue_wait_ms": ("tenant_queue_wait_ms_total", {}),
    "sheds": ("tenant_sheds_total", {}),
    "duplicate_prefill_tokens": (
        "tenant_duplicate_prefill_tokens_total", {}),
}

_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def _fmt_value(v) -> str:
    if v is None:
        return "0"
    f = float(v)
    if f == float("inf"):
        return "+Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _escape_label_value(v) -> str:
    """Prometheus text-format label escaping (backslash, quote,
    newline).  Label values can carry CLIENT input (the tenant label
    comes from the x-omni-tenant header), so unescaped rendering would
    let one request corrupt the whole exposition."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{_escape_label_value(v)}"' for k, v in labels.items()
    )
    return "{" + inner + "}"


class _Exposition:
    """Buffers samples per metric family: the text format requires every
    line of a family to form ONE group (HELP/TYPE then all samples) —
    interleaving per-stage loop output would break strict OpenMetrics
    parsers even though the Prometheus server tolerates it."""

    def __init__(self):
        # family name -> sample lines, in first-use order
        self._families: dict[str, list[str]] = {}

    def sample(self, name: str, labels: dict, value,
               suffix: str = "") -> None:
        full = METRIC_PREFIX + name
        self._families.setdefault(name, []).append(
            f"{full}{suffix}{_fmt_labels(labels)} {_fmt_value(value)}")

    def histogram(self, name: str, labels: dict, snap: dict) -> None:
        """Render a stats.Histogram snapshot (cumulative buckets)."""
        for le, cum in snap.get("buckets", ()):
            self.sample(name, {**labels, "le": _fmt_value(le)}, cum,
                        suffix="_bucket")
        self.sample(name, labels, snap.get("sum", 0.0), suffix="_sum")
        self.sample(name, labels, snap.get("count", 0), suffix="_count")

    def render(self) -> str:
        lines: list[str] = []
        for name, samples in self._families.items():
            spec = METRIC_SPECS[name]
            full = METRIC_PREFIX + name
            lines.append(f"# HELP {full} {spec[1]}")
            lines.append(f"# TYPE {full} {spec[0]}")
            lines.extend(samples)
        return "\n".join(lines) + "\n"


def render_exposition(summary: dict, engine_snaps: dict,
                      device: Optional[dict] = None,
                      resilience: Optional[dict] = None,
                      process_stats: Optional[dict] = None,
                      disagg: Optional[dict] = None) -> str:
    """``summary``: OrchestratorAggregator.summary(); ``engine_snaps``:
    {stage_id: LLMEngine/DiffusionEngine.metrics_snapshot() or {}};
    ``resilience``: resilience_metrics.snapshot() (restart/retry/
    breaker/deadline counters, labels already attached);
    ``process_stats``: process-level introspection counters
    ({spans_dropped, watchdog_trips, watchdog_tripped});
    ``disagg``: DisaggRouter.disagg_snapshot() (the handoff-latency
    histogram — the disagg counters/gauges ride ``resilience``)."""
    exp = _Exposition()
    e2e = summary.get("e2e", {})
    exp.sample("requests_finished_total", {}, e2e.get("num_finished", 0))
    for q, key in (("0.5", "p50_ms"), ("0.9", "p90_ms"),
                   ("0.99", "p99_ms")):
        exp.sample("request_latency_ms", {"quantile": q}, e2e.get(key, 0.0))
    for sid, st in sorted(summary.get("stages", {}).items()):
        labels = {"stage": sid}
        exp.sample("stage_requests_total", labels, st.get("num_requests", 0))
        exp.sample("stage_tokens_in_total", labels, st.get("tokens_in", 0))
        exp.sample("stage_tokens_out_total", labels, st.get("tokens_out", 0))
        exp.sample("stage_tokens_per_second", labels, st.get("tps", 0.0))
    for edge, e in sorted(summary.get("edges", {}).items()):
        frm, _, to = str(edge).partition("->")
        labels = {"from_stage": frm, "to_stage": to}
        exp.sample("transfer_count_total", labels, e.get("transfers", 0))
        exp.sample("transfer_bytes_total", labels, e.get("bytes", 0))
        exp.sample("transfer_ms_total", labels, e.get("ms", 0.0))
    stream = summary.get("stream")
    if stream:
        for key in ("requests_total", "events_total", "tokens_total"):
            exp.sample(f"stream_{key}", {}, stream.get(key, 0))
        if stream.get("first_event_ms", {}).get("count"):
            exp.histogram("stream_first_event_ms", {},
                          stream["first_event_ms"])
    for sid, snap in sorted(engine_snaps.items()):
        if not snap:
            continue
        labels = {"stage": sid}
        sched = snap.get("scheduler")
        if sched:
            exp.sample("scheduler_waiting", labels, sched.get("waiting", 0))
            exp.sample("scheduler_running", labels, sched.get("running", 0))
            exp.sample("preemptions_total", labels,
                       sched.get("preemptions", 0))
            exp.sample("rejections_total", labels,
                       sched.get("rejections", 0))
        kv = snap.get("kv")
        if kv:
            exp.sample("kv_pages_total", labels, kv.get("pages_total", 0))
            exp.sample("kv_pages_used", labels, kv.get("pages_used", 0))
            exp.sample("kv_page_utilization", labels,
                       kv.get("utilization", 0.0))
            for kind, rec in sorted((kv.get("kinds") or {}).items()):
                for field in ("heads", "row_width", "row_used",
                              "pages_total"):
                    exp.sample(f"kv_kind_{field}", {**labels, "kind": kind},
                               rec.get(field, 0))
        if "kv_pages_used" in snap:
            exp.histogram("kv_pages_used_per_step", labels,
                          snap["kv_pages_used"])
        pc = snap.get("prefix_cache")
        if pc and pc.get("enabled"):
            exp.sample("prefix_cache_hits_total", labels, pc.get("hits", 0))
            exp.sample("prefix_cache_hit_tokens_total", labels,
                       pc.get("hit_tokens", 0))
        state = snap.get("state")
        if state:
            exp.histogram("state_slots", labels, snap["state_slots"])
            for name in ("state_slot_allocs_total", "state_resets_total",
                         "prefix_hits_refused_total"):
                exp.sample(name, labels, state.get(name, 0))
        window = snap.get("window")
        if window:
            exp.histogram("kv_window_pages_used", labels,
                          snap["kv_window_pages_used"])
            exp.histogram("window_keep_pct", labels,
                          snap["window_keep_pct"])
            exp.sample("window_prefix_hits_refused_total", labels,
                       window.get("prefix_hits_refused_total", 0))
            exp.sample("window_pages_freed_total", labels,
                       window.get("pages_freed_total", 0))
        if snap.get("sparse_keep_pct", {}).get("count"):
            exp.histogram("sparse_keep_pct", labels,
                          snap["sparse_keep_pct"])
        if snap.get("ssd_chunk_fill", {}).get("count"):
            exp.histogram("ssd_chunk_fill", labels, snap["ssd_chunk_fill"])
        tiers = snap.get("kv_tiers")
        if tiers:
            exp.sample("kv_prefix_hit_tokens_total", labels,
                       tiers.get("prefix_hit_tokens", 0))
            exp.sample("kv_tier_hbm_pages", labels,
                       tiers.get("hbm_pages", 0))
            exp.sample("kv_tier_host_pages", labels,
                       tiers.get("host_pages", 0))
            exp.sample("kv_tier_remote_pages", labels,
                       tiers.get("remote_pages", 0))
            for edge, n in sorted(
                    (tiers.get("bytes_moved") or {}).items()):
                tier, _, direction = str(edge).partition("/")
                exp.sample("kv_offload_bytes_total",
                           {**labels, "tier": tier, "dir": direction}, n)
            exp.sample("kv_restored_tokens_total", labels,
                       tiers.get("restored_tokens", 0))
            exp.sample("kv_parked_tokens_total", labels,
                       tiers.get("parked_tokens", 0))
        if snap.get("kv_restore_seconds", {}).get("count"):
            exp.histogram("kv_restore_seconds", labels,
                          snap["kv_restore_seconds"])
        counters = snap.get("counters")
        if counters:
            exp.sample("engine_steps_total", labels,
                       counters.get("num_steps", 0))
            exp.sample("tokens_generated_total", labels,
                       counters.get("tokens_generated", 0))
            exp.sample("prefill_tokens_total", labels,
                       counters.get("prefill_tokens", 0))
        gauges = snap.get("gauges")
        if gauges and not sched:
            # engines without a scheduler snapshot still expose depth
            exp.sample("scheduler_waiting", labels,
                       gauges.get("num_waiting", 0))
            exp.sample("scheduler_running", labels,
                       gauges.get("num_running", 0))
        for hist_name in ("ttft_ms", "tpot_ms", "itl_ms"):
            h = snap.get(hist_name)
            if h:
                exp.histogram(hist_name, labels, h)
        if snap.get("step_ms"):
            exp.histogram("engine_step_ms", labels, snap["step_ms"])
        if snap.get("host_ms"):
            exp.histogram("engine_step_host_ms", labels, snap["host_ms"])
        if snap.get("device_ms"):
            exp.histogram("engine_step_device_ms", labels,
                          snap["device_ms"])
        overlap = snap.get("overlap")
        if overlap:
            exp.sample("engine_step_overlap_ratio", labels,
                       overlap.get("ratio", 0.0))
            exp.sample("engine_step_overlapped_host_ms_total", labels,
                       overlap.get("overlapped_host_ms_total", 0.0))
        phases = snap.get("phases")
        if phases:
            for phase, v in sorted((phases.get("by_name") or {}).items()):
                exp.sample("engine_host_phase_ms_total",
                           {**labels, "phase": phase}, v.get("ms", 0.0))
        stalls = snap.get("stalls")
        if stalls:
            exp.sample("engine_stalls_total", labels,
                       stalls.get("total", 0))
            exp.sample("engine_stall_ms_total", labels,
                       stalls.get("ms_total", 0.0))
        if snap.get("batched_tokens"):
            exp.histogram("engine_step_batched_tokens", labels,
                          snap["batched_tokens"])
        padding = snap.get("padding")
        if padding:
            exp.sample("engine_step_padding_efficiency", labels,
                       padding.get("efficiency", 0.0))
            exp.sample("engine_step_useful_tokens_total", labels,
                       padding.get("useful_tokens_total", 0))
            exp.sample("engine_step_padded_tokens_total", labels,
                       padding.get("padded_tokens_total", 0))
        roofline = snap.get("roofline")
        if roofline:
            exp.sample("engine_step_mfu", labels,
                       roofline.get("mfu", 0.0))
            for phase, v in sorted((roofline.get("mbu") or {}).items()):
                exp.sample("engine_step_mbu",
                           {**labels, "phase": phase}, v)
        compile_stats = snap.get("compile")
        if compile_stats:
            exp.sample("jit_compiles_total", labels,
                       compile_stats.get("compiles", 0))
            exp.sample("jit_cache_hits_total", labels,
                       compile_stats.get("cache_hits", 0))
            exp.sample("jit_compile_seconds_total", labels,
                       compile_stats.get("compile_s", 0.0))
            parts = {part: compile_stats.get(f"{part}_s", 0.0)
                     for part in ("trace", "lower", "backend")}
            parts["run"] = max(compile_stats.get("compile_s", 0.0)
                               - sum(parts.values()), 0.0)
            for part, secs in parts.items():
                exp.sample("engine_compile_seconds_total",
                           {**labels, "part": part}, secs)
        setup_s: dict = {}
        for span in (snap.get("startup") or {}).get("spans") or ():
            # the process's record: every engine shows it, each under
            # its own stage the spans that belong to no other
            if span.get("ms") is not None and str(span.get("stage")) in (
                    "None", str(sid)):
                setup_s[span["name"]] = (setup_s.get(span["name"], 0.0)
                                         + span["ms"] / 1e3)
        for phase, secs in sorted(setup_s.items()):
            exp.sample("engine_setup_seconds",
                       {**labels, "phase": phase}, secs)
        for reason, count in sorted(
                (snap.get("async_fallback") or {}).items()):
            exp.sample("async_fallback_total",
                       {**labels, "reason": reason}, count)
        pipeline = snap.get("pipeline")
        if pipeline:
            exp.sample("pipelined_steps_total", labels,
                       pipeline.get("pipelined_steps", 0))
            exp.sample("pipeline_drains_total", labels,
                       pipeline.get("drains", 0))
        # serving-curve observability: queue depth + shed ledger + SLO
        # attainment/goodput per tenant + queue-wait + saturation
        queue = snap.get("queue")
        if queue:
            for tenant, depth in sorted(
                    (queue.get("depth_by_tenant") or {}).items()):
                exp.sample("request_queue_depth",
                           {**labels, "tenant": tenant}, depth)
        for key, n in sorted((snap.get("shed") or {}).items()):
            reason, _, tenant = str(key).partition("/")
            exp.sample("shed_requests_total",
                       {**labels, "reason": reason,
                        "tenant": tenant or "default"}, n)
        # WFQ deferral ledger (docs/control_plane.md): rounds a
        # tenant's head-of-line request waited behind other tenants
        wfq = snap.get("wfq")
        if wfq:
            for tenant, n in sorted(
                    (wfq.get("deferred_by_tenant") or {}).items()):
                exp.sample("wfq_deferred_requests_total",
                           {**labels, "tenant": tenant}, n)
        slo = snap.get("slo")
        if slo:
            for tenant, st in sorted((slo.get("tenants") or {}).items()):
                tl = {**labels, "tenant": tenant}
                exp.sample("slo_attainment_ratio", tl,
                           st.get("attainment", 0.0))
                exp.sample("slo_requests_total", tl,
                           st.get("finished", 0))
                exp.sample("slo_requests_met_total", tl, st.get("met", 0))
                exp.sample("goodput_tokens_total", tl,
                           st.get("goodput_tokens", 0))
        # per-tenant heavy-hitter attribution: top-k sketch estimates
        # per meter (docs/observability.md); only meters with traffic
        # render, and every value is declared approximate in HELP.
        # Rows without the lifetime ``export`` slot are skipped: top-k
        # bounds each scrape, but under adversarial churn its
        # membership over time is unbounded, and every label value
        # lives forever in the scrape database — the sketch layer
        # budgets MAX_TENANT_SERIES distinct tenants per engine for
        # its whole life (attribution.py), and per-key estimates never
        # decrease, so the counter-typed series stay monotone.
        # /debug/tenants keeps the full uncapped boards
        attr = snap.get("attribution")
        if attr:
            for meter, doc in sorted((attr.get("meters") or {}).items()):
                series = _ATTRIBUTION_SERIES.get(meter)
                if series is None:
                    continue
                name, extra = series
                for row in doc.get("top") or ():
                    if not row.get("export", True):
                        continue
                    exp.sample(name, {**labels, "tenant": row["tenant"],
                                      **extra}, row["est"])
                if doc.get("tenants_tracked"):
                    exp.sample("attribution_tracked_tenants",
                               {**labels, "meter": meter},
                               doc["tenants_tracked"])
        if snap.get("queue_wait_ms"):
            exp.histogram("queue_wait_ms", labels, snap["queue_wait_ms"])
        if snap.get("intake_ms"):
            exp.histogram("intake_ms", labels, snap["intake_ms"])
        for phase, v in sorted((snap.get("saturation") or {}).items()):
            exp.sample("phase_saturation_ratio",
                       {**labels, "phase": phase}, v)
        # device-memory ledger: per-component live/peak bytes
        # (components sum to total; docs/debugging.md)
        dm = snap.get("device_memory")
        if dm:
            for comp, v in sorted((dm.get("components") or {}).items()):
                cl = {**labels, "component": comp}
                exp.sample("device_memory_bytes", cl,
                           v.get("bytes", 0))
                exp.sample("device_memory_peak_bytes", cl,
                           v.get("peak_bytes", 0))
        diff = snap.get("diffusion")
        if diff:
            exp.sample("diffusion_requests_total", labels,
                       diff.get("requests_total", 0))
            exp.sample("diffusion_batches_total", labels,
                       diff.get("batches_total", 0))
            if diff.get("gen_seconds"):
                exp.histogram("diffusion_gen_seconds", labels,
                              diff["gen_seconds"])
    if device and device.get("hbm_bytes"):
        exp.sample("hbm_bytes", {}, device["hbm_bytes"])
    if process_stats:
        exp.sample("trace_spans_dropped_total", {},
                   process_stats.get("spans_dropped", 0))
        exp.sample("watchdog_trips_total", {},
                   process_stats.get("watchdog_trips", 0))
        exp.sample("watchdog_tripped", {},
                   1 if process_stats.get("watchdog_tripped") else 0)
    if disagg and disagg.get("handoff_seconds", {}).get("count"):
        exp.histogram("kv_handoff_seconds", {},
                      disagg["handoff_seconds"])
    if disagg and disagg.get("prefix_pull_seconds", {}).get("count"):
        exp.histogram("kv_prefix_pull_seconds", {},
                      disagg["prefix_pull_seconds"])
    cache = (disagg or {}).get("cache")
    if cache:
        # fleet cache economics (metrics/cache_economics.py): the
        # router's aggregated digest board
        exp.sample("fleet_prefix_hit_tokens_total", {},
                   cache.get("fleet_hit_tokens", 0))
        exp.sample("fleet_prefill_tokens_total", {},
                   cache.get("fleet_prefill_tokens", 0))
        exp.sample("fleet_prefix_hit_rate", {},
                   cache.get("hit_rate", 0.0))
        for reason, v in sorted(
                (cache.get("duplicate_by_reason") or {}).items()):
            exp.sample("fleet_duplicate_prefill_tokens_total",
                       {"reason": reason}, v)
        exp.sample("fleet_duplicate_prefix_tokens", {},
                   cache.get("duplicate_prefix_tokens", 0))
        for rid, n in sorted(
                (cache.get("digest_nodes") or {}).items()):
            exp.sample("cache_digest_nodes", {"replica": str(rid)}, n)
    for name, samples in (resilience or {}).items():
        if name not in METRIC_SPECS:
            continue  # unknown names never leak past the drift guard
        for labels, value in samples:
            exp.sample(name, labels, value)
    return exp.render()


def render_from_omni(omni, device: Optional[dict] = None) -> str:
    """Render the exposition for a (sync) ``Omni`` orchestrator: the
    aggregator summary plus every stage's engine snapshot (proc stages
    report the last snapshot shipped over their command channel) plus
    the resilience counters — this process's own, merged with the
    snapshots stage WORKERS ship on their outputs frames (deadline
    kills happen at the worker's scheduler; without the merge /metrics
    would report 0 for process-disaggregated stages)."""
    from vllm_omni_tpu.resilience.metrics import (
        merge_snapshots,
        resilience_metrics,
    )
    from vllm_omni_tpu.tracing import get_recorder

    summary = omni.metrics.summary()
    snaps = {}
    worker_res = []
    for stage in getattr(omni, "stages", ()):
        fn = getattr(stage, "engine_metrics_snapshot", None)
        snaps[stage.stage_id] = fn() if fn is not None else {}
        rfn = getattr(stage, "resilience_snapshot", None)
        if rfn is not None:
            worker_res.append(rfn())
    wd = getattr(omni, "watchdog", None)
    process_stats = {
        # THIS process's recorder (stage workers drain theirs over the
        # outputs frames before their rings can evict)
        "spans_dropped": get_recorder().spans_dropped,
        "watchdog_trips": getattr(wd, "trips", 0),
        "watchdog_tripped": getattr(wd, "tripped", None) is not None,
    }
    # a disagg-routed deployment hangs its router off the orchestrator;
    # its handoff histogram joins the exposition (counters/gauges
    # already ride the resilience registry)
    router = getattr(omni, "router", None)
    return render_exposition(
        summary, snaps, device=device,
        resilience=merge_snapshots(resilience_metrics.snapshot(),
                                   *worker_res),
        process_stats=process_stats,
        disagg=(router.disagg_snapshot() if router is not None
                else None))


# ------------------------------------------------------------ validation
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})?\s+(\S+)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _base_name(sample_name: str) -> str:
    """Strip histogram sample suffixes back to the declared metric name."""
    stripped = sample_name[len(METRIC_PREFIX):]
    for suffix in _HIST_SUFFIXES:
        if stripped.endswith(suffix):
            base = stripped[: -len(suffix)]
            if base in METRIC_SPECS and METRIC_SPECS[base][0] == "histogram":
                return base
    return stripped


def validate_exposition(text: str) -> list[str]:
    """Check a rendered exposition against METRIC_SPECS; returns a list
    of violations (empty = clean).  Rules: every sample name matches
    ``vllm_omni_tpu_[a-z_]+`` (histogram ``_bucket/_sum/_count`` samples
    validate against their base name), is declared in METRIC_SPECS, and
    carries every label its spec requires (``stage`` where applicable)."""
    errors = []
    for lineno, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            errors.append(f"line {lineno}: unparseable sample {line!r}")
            continue
        sample_name, _, labels_str, _ = m.groups()
        if not sample_name.startswith(METRIC_PREFIX):
            errors.append(
                f"line {lineno}: {sample_name} lacks the "
                f"{METRIC_PREFIX} prefix")
            continue
        base = _base_name(sample_name)
        spec = METRIC_SPECS.get(base)
        if spec is None:
            errors.append(
                f"line {lineno}: {sample_name} not declared in "
                "METRIC_SPECS")
            continue
        if not NAME_RE.fullmatch(METRIC_PREFIX + base):
            errors.append(
                f"line {lineno}: {METRIC_PREFIX + base} violates the "
                "naming rule vllm_omni_tpu_[a-z_]+")
        labels = dict(_LABEL_RE.findall(labels_str or ""))
        for required in spec[2]:
            if required not in labels:
                errors.append(
                    f"line {lineno}: {sample_name} missing required "
                    f"label {required!r}")
    return errors


def validate_specs() -> list[str]:
    """Static check of the registry itself (names must be regex-clean
    even before anything renders)."""
    errors = []
    for name, (mtype, help_text, labels) in METRIC_SPECS.items():
        if not NAME_RE.fullmatch(METRIC_PREFIX + name):
            errors.append(
                f"{METRIC_PREFIX + name} violates vllm_omni_tpu_[a-z_]+")
        if mtype not in ("counter", "gauge", "histogram"):
            errors.append(f"{name}: unknown type {mtype!r}")
        if not help_text:
            errors.append(f"{name}: empty help text")
    return errors
