"""OL6 — metric-drift: the Prometheus metric surface can't silently move.

The omnilint absorption of ``scripts/check_metrics_names.py`` (that
script is now a thin shim over this module so existing CI invocations
keep working).  Two layers:

- static (pure AST, runs anywhere): every key literal in the
  ``METRIC_SPECS`` dict must match ``vllm_omni_tpu_[a-z_]+`` after the
  prefix — lowercase/underscore only, no digits (which is why the E2E
  latency series is ``request_latency_ms``)
- dynamic (imports ``metrics/prometheus.py`` — dependency-free by
  design, so safe in any lane): render a synthetic exposition covering
  every stage/edge/engine series and parse it back against the specs
  (``validate_specs`` + ``validate_exposition``)
"""

from __future__ import annotations

import ast
import re
from typing import Iterable

from vllm_omni_tpu.analysis.engine import FileContext, Finding, Rule
from vllm_omni_tpu.analysis.manifest import METRIC_MODULES, in_scope

_NAME_RE = re.compile(r"vllm_omni_tpu_[a-z_]+")
_PREFIX = "vllm_omni_tpu_"


def synthetic_summary() -> dict:
    """An aggregator summary exercising every stage/edge series."""
    return {
        "stages": {
            0: {"num_requests": 3, "tokens_in": 30, "tokens_out": 12,
                "tps": 41.5},
            1: {"num_requests": 3, "tokens_in": 12, "tokens_out": 12,
                "tps": 9.0},
        },
        "edges": {"0->1": {"transfers": 3, "bytes": 4096, "ms": 1.25}},
        "e2e": {"num_finished": 3, "window": 3, "p50_ms": 101.0,
                "p90_ms": 250.0, "p99_ms": 251.0},
    }


def synthetic_engine_snapshot() -> dict:
    """An engine snapshot exercising every engine series (LLM histograms
    + scheduler/KV gauges + diffusion counters)."""
    hist = {"buckets": [[10.0, 1], [100.0, 2], [float("inf"), 3]],
            "sum": 123.0, "count": 3, "p50": 40.0, "p90": 100.0,
            "p99": 110.0}
    return {
        "gauges": {"num_waiting": 1, "num_running": 2},
        "counters": {"num_steps": 7, "tokens_generated": 12,
                     "prefill_tokens": 30},
        "ttft_ms": hist, "tpot_ms": hist, "itl_ms": hist,
        "step_ms": hist, "host_ms": hist, "device_ms": hist,
        "overlap": {"ratio": 0.75, "host_ms_total": 40.0,
                    "overlapped_host_ms_total": 30.0},
        # the host's phases, stalls and collections
        # (introspection/phase_clock.py)
        "phases": {"clock_ms": 5000.0,
                   "by_name": {"omni.step": {"ms": 12.5, "n": 7},
                               "omni.assemble.pack": {"ms": 3.25, "n": 7}},
                   "busy_waits": 4, "busy_wait_ms": 8.5},
        "stalls": {"total": 1, "ms_total": 2100.0, "records": []},
        "gc": {"ms_total": 3.5, "collections": {"0": 9, "1": 1, "2": 0}},
        "batched_tokens": hist,
        "padding": {"useful_tokens_total": 42, "padded_tokens_total": 64,
                    "efficiency": 0.6563},
        "compile": {"compiles": 9, "cache_hits": 120,
                    "compile_s": 33.5, "trace_s": 11.0, "lower_s": 15.5,
                    "backend_s": 5.75},
        # the process's start-up record (engine_setup_seconds{phase})
        "startup": {"now_ms": 90000.0, "stamps": {"serving": 61000.0},
                    "spans": [
                        {"name": "omni.setup", "stage": None,
                         "t0_ms": 14000.0, "ms": 47000.0, "self_ms": 310.0,
                         "parent": None},
                        {"name": "omni.setup.warmup", "stage": 0,
                         "t0_ms": 28000.0, "ms": 32500.0,
                         "self_ms": 32500.0,
                         "parent": "omni.setup.engine"}]},
        # live roofline attribution (metrics/roofline.py):
        # engine_step_mfu + the phase-labeled engine_step_mbu
        "roofline": {"mfu": 0.31, "mbu": {"prefill": 0.12,
                                          "decode": 0.55,
                                          "mixed": 0.4},
                     "window_steps": 128},
        "async_fallback": {"prefill": 4, "kv_transfer": 1},
        "pipeline": {"pipelined_steps": 120, "sync_steps": 5, "drains": 3},
        "scheduler": {"waiting": 1, "running": 2, "preemptions": 1,
                      "rejections": 0},
        "kv": {"pages_total": 64, "pages_used": 8, "utilization": 0.125,
               "kinds": {"sliding_attention": {
                   "heads": 8, "row_width": 256, "row_used": 192,
                   "pages_total": 33}}},
        "prefix_cache": {"enabled": True, "hits": 2, "hit_tokens": 16},
        "kv_tiers": {
            "hbm_pages": 8, "host_pages": 3, "remote_pages": 1,
            "host_bytes": 12288,
            "bytes_moved": {"host/out": 16384, "host/in": 8192,
                            "remote/out": 4096, "remote/in": 4096},
            "prefix_hit_tokens": 16, "restored_tokens": 24,
            "parked_tokens": 32, "offload_evictions": 2,
        },
        "kv_restore_seconds": hist,
        # the state cache of a model with recurrent layers
        "state_slots": hist,
        "kv_pages_used": hist,
        "kv_window_pages_used": hist,
        "sparse_keep_pct": hist,
        "window_keep_pct": hist,
        "ssd_chunk_fill": hist,
        "window": {"prefix_hits_refused_total": 2, "pages_freed_total": 9},
        "state": {"state_slot_allocs_total": 5, "state_resets_total": 1,
                  "prefix_hits_refused_total": 4},
        # serving-curve observability (docs/load_testing.md): tenant-
        # labeled SLO/goodput ledger, queue depth + wait, shed ledger,
        # per-phase saturation — the drift guard must cover every
        # series the loadgen harness reads mid-flight
        "queue_wait_ms": hist,
        "intake_ms": hist,
        "queue": {"depth_by_tenant": {"default": 1, "acme": 2}},
        "shed": {"queue_depth/acme": 3, "deadline_headroom/default": 1},
        # weighted-fair overload scheduling (docs/control_plane.md)
        "wfq": {"deferred_by_tenant": {"default": 2, "acme": 1}},
        "slo": {
            "targets": {"ttft_ms": 500.0, "tpot_ms": 50.0},
            "tenants": {
                "default": {"finished": 4, "met": 3, "tokens": 128,
                            "goodput_tokens": 96, "attainment": 0.75},
                "acme": {"finished": 2, "met": 2, "tokens": 64,
                         "goodput_tokens": 64, "attainment": 1.0},
            },
        },
        "saturation": {"prefill": 0.5, "decode": 0.25, "seats": 0.75},
        # per-tenant heavy-hitter attribution (metrics/attribution.py):
        # every meter that maps to a /metrics series must render —
        # tenant_tokens_total{kind}, tenant_kv_page_seconds_total{tier},
        # handoff/queue-wait/shed meters, and the tracked-tenants gauge
        "attribution": {
            "capacity": 256,
            "meters": {
                "prefill_tokens": {
                    "total": 1200.0, "tenants_tracked": 2,
                    "max_overestimate": 4.7,
                    "top": [{"tenant": "acme", "est": 900.0,
                             "err": 0.0},
                            {"tenant": "default", "est": 300.0,
                             "err": 4.0}]},
                "decode_tokens": {
                    "total": 640.0, "tenants_tracked": 2,
                    "max_overestimate": 2.5,
                    "top": [{"tenant": "acme", "est": 512.0,
                             "err": 0.0}]},
                "kv_page_seconds_hbm": {
                    "total": 42.5, "tenants_tracked": 1,
                    "max_overestimate": 0.2,
                    "top": [{"tenant": "acme", "est": 42.5,
                             "err": 0.0}]},
                "kv_page_seconds_host": {
                    "total": 7.25, "tenants_tracked": 1,
                    "max_overestimate": 0.1,
                    "top": [{"tenant": "default", "est": 7.25,
                             "err": 0.0}]},
                "handoff_bytes": {
                    "total": 16384.0, "tenants_tracked": 1,
                    "max_overestimate": 64.0,
                    "top": [{"tenant": "acme", "est": 16384.0,
                             "err": 0.0}]},
                "queue_wait_ms": {
                    "total": 850.0, "tenants_tracked": 2,
                    "max_overestimate": 3.4,
                    "top": [{"tenant": "default", "est": 600.0,
                             "err": 1.0}]},
                "sheds": {
                    "total": 4.0, "tenants_tracked": 1,
                    "max_overestimate": 0.1,
                    "top": [{"tenant": "acme", "est": 4.0,
                             "err": 0.0}]},
                # omniscope per-tenant redundancy (metrics/
                # cache_economics.py): wasted re-prefill tokens the
                # router meters at dispatch time
                "duplicate_prefill_tokens": {
                    "total": 96.0, "tenants_tracked": 1,
                    "max_overestimate": 0.4,
                    "top": [{"tenant": "acme", "est": 96.0,
                             "err": 0.0}]},
            },
        },
        # device-memory ledger (introspection/memory_ledger.py):
        # components sum to total; every new component label value
        # renders through the same two series
        "device_memory": {
            "source": "fallback",
            "total_bytes": 3145728,
            "peak_total_bytes": 3145728,
            "components": {
                "weights": {"bytes": 2097152, "peak_bytes": 2097152},
                "kv_pages": {"bytes": 1048576, "peak_bytes": 1048576},
                "workspace": {"bytes": 0, "peak_bytes": 0},
            },
        },
        "diffusion": {"requests_total": 3, "batches_total": 2,
                      "gen_seconds": hist},
    }


def run_check() -> list[str]:
    """Spec + rendered-exposition round-trip; returns violation strings
    (the contract scripts/check_metrics_names.py and
    tests/metrics/test_prometheus.py have always consumed)."""
    from vllm_omni_tpu.metrics.prometheus import (
        render_exposition,
        validate_exposition,
        validate_specs,
    )

    hist = {"buckets": [[0.005, 1], [0.1, 2], [float("inf"), 3]],
            "sum": 0.2, "count": 3}
    errors = validate_specs()
    text = render_exposition(
        synthetic_summary(),
        {0: synthetic_engine_snapshot(), 1: synthetic_engine_snapshot()},
        device={"hbm_bytes": 16 * 2**30},
        # process-level introspection counters (span loss + watchdog)
        process_stats={"spans_dropped": 5, "watchdog_trips": 1,
                       "watchdog_tripped": True},
        # disaggregated serving (docs/disaggregation.md): the handoff
        # histogram plus the router's registry-riding counters/gauges —
        # every series the failover e2e asserts on must render here —
        # and the omniscope fleet cache board (metrics/
        # cache_economics.py exposition shape)
        disagg={"handoff_seconds": hist,
                "prefix_pull_seconds": hist,
                "cache": {
                    "fleet_hit_tokens": 320,
                    "fleet_prefill_tokens": 480,
                    "hit_rate": 0.4,
                    "duplicate_by_reason": {"peer_replica": 96,
                                            "peer_cold_tier": 32},
                    "duplicate_prefix_tokens": 64,
                    "digest_nodes": {"prefill0": 12, "decode0": 3},
                }},
        resilience={
            "kv_handoff_bytes_total": [({"dir": "out"}, 8192),
                                       ({"dir": "in"}, 8192)],
            "failover_total": [({"reason": "prefill_replica_died"}, 1),
                               ({"reason": "handoff_failed"}, 2)],
            "router_healthy_replicas": [({"role": "prefill"}, 2),
                                        ({"role": "decode"}, 1)],
            "degraded_mode": [({}, 0)],
            # omniaffinity (disagg/router.py): affinity dispatch
            # outcomes + cluster-KV-fabric pull bytes
            "router_affinity_dispatch_total": [
                ({"outcome": "hit"}, 5), ({"outcome": "miss"}, 3),
                ({"outcome": "load_override"}, 1)],
            "kv_prefix_pull_bytes_total": [({"src": "peer"}, 8192),
                                           ({"src": "cold"}, 4096)],
            # control plane (docs/control_plane.md): the controller's
            # registry-riding fleet gauges and actuation counters —
            # every series the closed-loop bench asserts on
            "controlplane_reroles_total": [
                ({"from_role": "decode", "to_role": "prefill"}, 1),
                ({"from_role": "prefill", "to_role": "decode"}, 1)],
            "controlplane_replicas": [({"role": "prefill"}, 2),
                                      ({"role": "decode"}, 2)],
            "controlplane_actions_total": [
                ({"action": "drain"}, 2), ({"action": "rerole"}, 1),
                ({"action": "scale_up"}, 1)],
            # omnipulse alert lifecycle (metrics/alerts.py): the
            # firing gauge + per-destination transition counters the
            # loadgen overload e2e asserts on mid-flight
            "alerts_firing": [({"alert": "slo_fast_burn"}, 1),
                              ({"alert": "engine_stalled"}, 0)],
            "alert_transitions_total": [
                ({"alert": "slo_fast_burn", "to": "pending"}, 2),
                ({"alert": "slo_fast_burn", "to": "firing"}, 1),
                ({"alert": "slo_fast_burn", "to": "resolved"}, 1)],
        },
    )
    errors += validate_exposition(text)
    return errors


class MetricDriftRule(Rule):
    id = "OL6"
    name = "metric-drift"
    node_types = (ast.Assign, ast.AnnAssign)

    def __init__(self):
        self._specs_node = None

    def applies(self, ctx: FileContext) -> bool:
        return in_scope(ctx.path, METRIC_MODULES)

    def visit(self, node, ctx: FileContext) -> Iterable[Finding]:
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        is_specs = any(
            isinstance(t, ast.Name) and t.id == "METRIC_SPECS"
            for t in targets)
        if not is_specs or node.value is None:
            return
        self._specs_node = node
        if isinstance(node.value, ast.Dict):
            yield from self._check_keys(node.value, ctx)

    def _check_keys(self, d: ast.Dict, ctx) -> Iterable[Finding]:
        for k in d.keys:
            if not (isinstance(k, ast.Constant)
                    and isinstance(k.value, str)):
                continue
            full = _PREFIX + k.value
            if not _NAME_RE.fullmatch(full) or re.search(r"\d", k.value):
                yield ctx.finding(
                    self.id, k,
                    f"metric name '{k.value}' breaks the naming rule "
                    f"({_NAME_RE.pattern}, no digits)")

    def finish(self, ctx: FileContext) -> Iterable[Finding]:
        anchor = self._specs_node or ctx.tree
        try:
            errors = run_check()
        except Exception as e:  # import/render blew up: that IS drift
            yield ctx.finding(
                self.id, anchor,
                f"metric surface check failed to run: "
                f"{type(e).__name__}: {e}")
            return
        for err in errors:
            yield ctx.finding(self.id, anchor, f"metric drift: {err}")
