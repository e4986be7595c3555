"""Path manifests scoping the TPU-specific rules.

HOT_PATHS: modules on the serving hot path, where an accidental
host↔device sync (rule OL2) stalls every in-flight request — the
scheduler/runner/engine step loop and the kernels under it.  Cold
surfaces (entrypoints, config, model loaders) legitimately sync and are
not listed.

PROTOCOL_MODULES: files implementing a cross-process frame protocol
(rule OL5 checks every sent frame type has a receiver handler and that
span payloads are re-stamped on the other side).

BENCH_PATHS: measurement code where wall-clock timing without
``block_until_ready`` measures dispatch (enqueue) instead of execution
(rule OL4).

METRIC_MODULES: the Prometheus registry files rule OL6 (the absorbed
scripts/check_metrics_names.py drift guard) validates.
"""

from __future__ import annotations

HOT_PATHS: tuple[str, ...] = (
    "vllm_omni_tpu/core/",
    # kvcache tier moves run between schedule() and execute() on the
    # engine thread — a stray per-page host sync in the offload path
    # multiplies by every payload parked that step (the batched
    # extract/inject discipline of docs/kv_cache.md)
    "vllm_omni_tpu/kvcache/",
    "vllm_omni_tpu/ops/",
    # the ragged unified kernel is covered by the ops/ prefix above;
    # listed explicitly because a stray host sync inside the ONE
    # dispatch serving a whole mixed step stalls every request at once
    "vllm_omni_tpu/ops/ragged_paged_attention.py",
    # the shared KV quantizer is covered by the kvcache/ prefix above;
    # listed explicitly because its helpers run inside the KV-write
    # path of EVERY forward and inside the tier drain — a host sync in
    # quantize/dequantize would serialize each step on each payload
    "vllm_omni_tpu/kvcache/quant.py",
    "vllm_omni_tpu/sample/",
    "vllm_omni_tpu/worker/",
    "vllm_omni_tpu/engine/",
    # the open-loop load harness: no jax today, but a stray host sync
    # creeping into a driver would serialize the very concurrency the
    # harness exists to measure — linted from day one
    "vllm_omni_tpu/loadgen/",
    # introspection: the flight recorder appends INSIDE the engine
    # step loop and the watchdog/debugz probes read live engine state
    # from other threads — a stray device sync in either would stall
    # serving exactly while an operator is debugging it
    "vllm_omni_tpu/introspection/",
    # disaggregated serving: the router steps every replica engine on
    # ONE thread — a stray device sync in the routing/handoff logic
    # would stall all tiers at once (payloads are host numpy by the
    # time they reach this layer; keep it that way)
    "vllm_omni_tpu/disagg/",
    # control plane: actuation runs BETWEEN router steps on the engine
    # thread, and the sensor tick reads live engine state from the
    # controller thread — a stray device sync in either stalls all
    # replicas at once (or serializes serving behind a poll)
    "vllm_omni_tpu/controlplane/",
    # journey tracing + live roofline: both record INSIDE the router/
    # engine step loops (spans per dispatch/handoff/retire, MFU/MBU per
    # step) — the whole design is host-ints-only, and a stray device
    # sync here would stall serving exactly in proportion to how
    # observable it is
    "vllm_omni_tpu/tracing/",
    "vllm_omni_tpu/metrics/roofline.py",
    # omnipulse: the attribution sketch is fed from the engine step
    # loop (token/page·second/shed meters per request event) and the
    # alert probes read live engine state from the evaluation thread —
    # host dict/heap arithmetic only; a device sync in either stalls
    # serving in proportion to how observable it is
    "vllm_omni_tpu/metrics/attribution.py",
    "vllm_omni_tpu/metrics/alerts.py",
    # omniscope: dispatch-regret scoring runs inside the router's
    # dispatch path and digest folding inside its step loop — pure
    # dict/set arithmetic over already-exported digests; a device sync
    # here would stall every tier at once
    "vllm_omni_tpu/metrics/cache_economics.py",
)

PROTOCOL_MODULES: tuple[str, ...] = (
    # speaks submit/abort/outputs/fatal/profile_*/shutdown/bye plus the
    # resilience PR's ping/pong heartbeat frames (sender AND handler
    # both live here, so OL5 can check the pairing statically)
    "vllm_omni_tpu/entrypoints/stage_proc.py",
    # drives restarts/redelivery over those frames; constructs no frame
    # literals itself today — listed so any future frame it grows is
    # linted from day one
    "vllm_omni_tpu/resilience/supervisor.py",
    # the disagg handoff protocol (meta + per-shard layer streams) and
    # the router consuming replica health answers — no frame literals
    # today (payloads ride connector keys), listed so any future wire
    # frames are linted from day one
    "vllm_omni_tpu/disagg/roles.py",
    "vllm_omni_tpu/disagg/router.py",
)

BENCH_PATHS: tuple[str, ...] = (
    "vllm_omni_tpu/benchmarks/",
    "vllm_omni_tpu/metrics/",
    "tests/benchmarks/",
    # async pipelined step: the engine's dispatch/retire halves and the
    # runner's dispatch_decode/retire_decode time host vs. device phases
    # for the overlap metrics — OL4 watches that any wall-clock pair
    # around a jax dispatch in them syncs (or says why it must not).
    # model_runner.py also carries the unified ragged dispatch
    # (_run_unified/dispatch_unified) and the compile-telemetry timing
    # in _run_jit, whose fresh-compile branch (phase_clock.py
    # CompileStats.first_call) must block_until_ready before stopping
    # the clock
    "vllm_omni_tpu/engine/llm_engine.py",
    "vllm_omni_tpu/worker/model_runner.py",
    # the open-loop runner times around async dispatch (arrival ->
    # first output -> completion across asyncio tasks / HTTP threads);
    # OL4 watches that any wall-clock pair it grows around a jax
    # dispatch syncs first — today its durations are client-observed
    # network/queue round trips, which is the product being measured
    "vllm_omni_tpu/loadgen/",
)

METRIC_MODULES: tuple[str, ...] = (
    "vllm_omni_tpu/metrics/prometheus.py",
    # alert gauges/transition counters and attribution series render
    # through METRIC_SPECS like everything else; listed so any future
    # spec table grown in these modules rides the OL6 drift guard
    "vllm_omni_tpu/metrics/alerts.py",
    "vllm_omni_tpu/metrics/attribution.py",
    # omniscope fleet cache series (fleet_prefix_hit_tokens_total &
    # co.) render from the router's exposition block through the same
    # spec table
    "vllm_omni_tpu/metrics/cache_economics.py",
)

# --------------------------------------------------------------- omnirace
# THREADED_PATHS: modules with real cross-thread locking that are NOT on
# the serving hot path — rule OL9 (blocking-under-lock) covers
# HOT_PATHS ∪ THREADED_PATHS.  A blocking call under a lock here won't
# stall a device step directly, but it convoys every thread that needs
# the lock (heartbeats, /metrics, intake) behind one slow operation.
THREADED_PATHS: tuple[str, ...] = (
    # supervisor heartbeat/restart threads + fault injector
    "vllm_omni_tpu/resilience/",
    # connector cv-protected stores, the TCP KV server's per-connection
    # threads, and the client's one-socket mutex
    "vllm_omni_tpu/distributed/",
    # histograms observed by the engine thread, snapshotted by /metrics
    "vllm_omni_tpu/metrics/",
    # span ring shared by every stage thread + the drain/export path
    "vllm_omni_tpu/tracing/",
    # native shm ring op lock
    "vllm_omni_tpu/native/",
    # the async orchestrator's pause gate + engine loop
    "vllm_omni_tpu/entrypoints/async_omni.py",
    # the stage channel's send mutex (submit thread vs profile RPC)
    "vllm_omni_tpu/entrypoints/stage_proc.py",
    "vllm_omni_tpu/entrypoints/openai/api_server.py",
    # closed-loop bench workers share a result lock
    "vllm_omni_tpu/benchmarks/",
    # the lock tracer itself: its meta-lock must stay leaf-only
    "vllm_omni_tpu/analysis/runtime.py",
    # controller thread emits intents; the router thread actuates —
    # the intent/ring lock convoys both if anything blocks under it
    "vllm_omni_tpu/controlplane/",
)

# LOCK_GUARDS: the concurrency manifest rule OL7 (lock-discipline)
# enforces.  Per class (keyed "path::ClassName"), which attributes are
# guarded by which lock attribute: every read/write of a guarded
# attribute must happen under `with self.<lock>` — directly, or in a
# private helper whose every same-class call site holds the lock
# (__init__/__del__ are exempt: construction and teardown are
# single-threaded by contract).  Lock attribute names must follow the
# *lock/*cv/*cond naming convention (rules/_lockinfo.py) so the `with`
# scopes are recognizable.
#
# Declare the invariant that is TRUE and must stay true — the manifest
# is documentation the linter enforces, not aspiration.  Deliberately
# unguarded attributes (GIL-atomic monitoring reads) are simply not
# listed, or the access carries a reasoned OL7 suppression.
LOCK_GUARDS: dict[str, dict[str, tuple[str, ...]]] = {
    # engine thread observes while the /metrics HTTP thread snapshots
    "vllm_omni_tpu/metrics/stats.py::Histogram": {
        "_lock": ("_counts", "_sum", "_count", "_window"),
    },
    # every subsystem counts events here from its own thread
    "vllm_omni_tpu/resilience/metrics.py::ResilienceMetrics": {
        "_lock": ("_counters", "_gauges"),
    },
    # orchestrator thread (submit/poll) vs heartbeat + restart threads
    "vllm_omni_tpu/resilience/supervisor.py::StageSupervisor": {
        "_lock": ("_tracked", "_redelivered", "_failed_outs",
                  "_restarts", "_restarting", "_dead", "_closed"),
    },
    # chaos sites fire from every replica/stage thread
    "vllm_omni_tpu/resilience/faults.py::FaultInjector": {
        "_lock": ("_steps", "_rngs"),
    },
    # engine step appends; /debug + crash hooks snapshot from anywhere
    "vllm_omni_tpu/introspection/flight_recorder.py::FlightRecorder": {
        "_lock": ("_ring", "_seq", "_dropped", "_last_mono",
                  "_last_wall"),
    },
    "vllm_omni_tpu/introspection/memory_ledger.py::DeviceMemoryLedger": {
        "_lock": ("_peaks", "_peak_total", "_last"),
    },
    # monitor thread mutates source states; /debug reads them
    "vllm_omni_tpu/introspection/watchdog.py::StallWatchdog": {
        "_lock": ("_sources",),
    },
    # per-connection server threads share the one object table
    "vllm_omni_tpu/distributed/tcp.py::KVStoreServer": {
        "_cv": ("_store",),
    },
    # one persistent socket, many caller threads
    "vllm_omni_tpu/distributed/tcp.py::TCPConnector": {
        "_lock": ("_sock",),
    },
    # per-namespace store shared by every same-namespace instance
    "vllm_omni_tpu/distributed/connectors.py::InProcConnector": {
        "_cv": ("_store",),
    },
    # every stage thread records; the writer drains
    "vllm_omni_tpu/tracing/trace.py::TraceRecorder": {
        "_lock": ("_spans", "_dropped"),
    },
    "vllm_omni_tpu/tracing/trace.py::TraceWriter": {
        "_lock": ("_spans", "_chrome_dropped", "_last_export_ts"),
    },
    # engine thread accounts steps; /metrics + /debug threads snapshot
    "vllm_omni_tpu/metrics/roofline.py::RooflineTracker": {
        "_lock": ("_window", "_flops_total", "_bytes_total"),
    },
    # controller thread emits intents + reads the ring; the router
    # thread drains intents, records outcomes, and bumps the applied-
    # action counters.  The state-machine fields (_op, _warming,
    # hysteresis) are deliberately NOT listed: they are controller-
    # thread-private by contract (actuate() only touches the guarded
    # attributes below)
    "vllm_omni_tpu/controlplane/controller.py::ControlPlane": {
        "_lock": ("_pending", "_done", "_ring", "_seq", "actions"),
    },
    # evaluation thread and force_firing (the watchdog thread) both
    # step the per-rule lifecycle — every state WRITE happens under
    # the lock (serialized check+set, so the two can't double-land a
    # firing edge); /debug/alerts, /health, and the control plane's
    # advisory READ the per-rule scalars lock-free in the watchdog's
    # GIL-atomic monitoring-read stance, so they're not listed
    "vllm_omni_tpu/metrics/alerts.py::AlertEngine": {
        "_lock": ("_rules", "_transitions"),
    },
    # (TenantAttribution's _meters dict is immutable post-__init__ —
    # the lock guards the SKETCH CONTENTS, which OL7's attribute
    # granularity can't express; its mutation sites all hold _lock)
    # any thread may dump (crash hooks, alert evidence, SIGUSR2)
    "vllm_omni_tpu/introspection/flight_recorder.py::DumpCooldown": {
        "_lock": ("_last", "_suppressed"),
    },
    # the router thread folds digests + scores dispatches while
    # /metrics and /debug/cache snapshot from HTTP threads and the
    # alert probe reads from the evaluation thread
    "vllm_omni_tpu/metrics/cache_economics.py::CacheEconomics": {
        "_lock": ("_digests", "_cover", "_last", "_fleet_hit_tokens",
                  "_fleet_prefill_tokens", "_dup_by_reason",
                  "_pending", "_ledger", "_dispatches"),
    },
}


# --------------------------------------------------------------- omniflow
# The OL10 hostile-input-taint manifest: which expressions produce
# attacker-controlled values (TAINT_SOURCES), which calls launder them
# into safe values (SANITIZERS), and which calls/operations must never
# see them raw (TAINT_SINKS).  The rule (rules/taint_flow.py) flags
# every source->sink dataflow that crosses no sanitizer — the bug class
# of the PR 7 unsanitized tenant label (unbounded /metrics cardinality
# + label injection) and the PR 12 float("inf") priority crash.

TAINT_SOURCES: dict[str, tuple[str, ...]] = {
    # hostile HTTP headers read off the OpenAI server's request object:
    # `headers.get("x-omni-tenant")` / `headers["x-omni-tenant"]`
    "headers": ("x-omni-tenant", "x-omni-priority", "traceparent",
                "x-omni-trace-id"),
    # raw (pre-sanitizer) client metadata: EVERY read of these
    # attributes is hostile until a sanitizer touches it — the
    # Request.tenant/priority properties exist precisely to be the one
    # blessed crossing
    "attrs": ("additional_information",),
    # cross-host payload metadata off a connector edge: a torn or
    # hostile remote store controls every field of the `{key}/meta`
    # header (num_layers/shape/dtype/crc32)
    "meta_suffixes": ("/meta",),
    # key-prefix carve-out: `additional_information` doubles as the
    # engine's internal scratch namespace, and internal keys are
    # underscore-prefixed by convention ("_parked_len",
    # "_hidden_chunks") — reads of those are engine-written state, not
    # client input
    "internal_key_prefixes": ("_",),
}

# terminal function name -> defining file (the drift guard checks the
# def still exists there; matching in the rule is by terminal name so
# fixture files exercise the same manifest)
SANITIZERS: dict[str, str] = {
    "sanitize_tenant": "vllm_omni_tpu/metrics/stats.py",
    "sanitize_priority": "vllm_omni_tpu/metrics/stats.py",
    "inbound_trace_id": "vllm_omni_tpu/tracing/journey.py",
    "parse_traceparent": "vllm_omni_tpu/tracing/journey.py",
    "_escape_label_value": "vllm_omni_tpu/metrics/prometheus.py",
}

TAINT_SINKS: dict[str, tuple[str, ...]] = {
    # metric label dicts: a raw tenant here is unbounded series
    # cardinality + Prometheus exposition injection
    "metric_labels": ("_fmt_labels", "cap_tenant"),
    # log calls: raw client bytes in a log line are log injection (and
    # an f-string renders them before any later escaping could help)
    "log_receivers": ("logger", "logging", "log"),
    # filesystem paths: a client-controlled path component is traversal
    "fs_calls": ("open", "os.replace", "os.rename", "os.remove",
                 "os.unlink", "os.makedirs", "os.path.join"),
    # scheduler arithmetic (WFQ quantum weights): an unclamped client
    # number in admission math is the float("inf") crash class — scoped
    # to the scheduler so ordinary string plumbing stays quiet
    "sched_arith_paths": ("vllm_omni_tpu/core/scheduler.py",),
}

# --------------------------------------------------------------- recompile
# The OL11 recompile-hazard manifest: every `_run_jit(kind, shape_key,
# thunk)` dispatch must build its shape key from BUCKETED values or
# static config — a per-request int in the key (or in a jitted dummy
# array's shape) compiles one executable per distinct value, which is
# the mid-traffic 20-40 s XLA stall warmup exists to prevent.  The
# rule (rules/recompile_hazard.py) also checks every conditional
# argument variant at the dispatch site is observable in the key (the
# PR 11 `n_deep` bug class) and every dispatched `kind` is reachable
# from the warmup walker.
RECOMPILE: dict[str, tuple[str, ...]] = {
    # the jit telemetry choke points — every dispatch goes through one
    "dispatch_fns": ("_run_jit",),
    # calls that BUCKET a raw count (their result is shape-safe even
    # when fed per-request ints)
    "bucket_fns": ("_bucket", "_make_buckets",
                   "_bucketed_prefill_shapes", "auto_blocks",
                   "auto_ragged_blocks"),
    # attributes holding precomputed bucket tables / static tile picks
    # (and the resident-KV layout flag: one of exactly two executable
    # families per kind — int8-quantized caches are a different pytree,
    # so the flag MUST ride every dispatch key, threaded through the
    # warmup walker so both layouts compile before traffic)
    "bucket_attrs": ("_token_buckets", "_batch_buckets", "_seq_buckets",
                     "_token_block", "q_block", "_pages_per_block",
                     "_dma_slots", "_kv_quant"),
    # attribute reads that ARE per-request counts
    "per_request_attrs": ("num_new_tokens", "num_tokens",
                          "num_computed_tokens", "num_inflight_tokens",
                          "num_prompt_tokens"),
    # the warmup bucket walkers: kinds dispatched outside these must be
    # warmed inside them
    "warmup_funcs": ("precompile",),
    # jax array constructors whose literal shape tuples the rule scans
    "array_ctors": ("zeros", "ones", "full", "empty"),
}


# ---------------------------------------------------------------- omnileak
# The OL12 resource-lifecycle manifest: acquire->release pairs whose
# obligation the exception-edge CFG checks path-by-path.  Every entry is
# a protocol this repo's review passes have already paid for once: the
# PR 15 harvest found a failed dump write consuming the DumpCooldown
# window and an un-closed host-tier park interval; PR 12's found an
# aborted re-role stranding a drained donor; PR 9's found failover
# ledger entries surviving revive.
#
# Spec shape (schema in docs/static_analysis.md):
#   carrier  "path::Class" owning the protocol — the carrier's own
#            methods ARE the implementation and are never judged;
#   acquire/release/transfer
#            call specs, "recv.method" or bare "method".  The receiver
#            part substring-matches the call receiver's terminal name
#            ("kv.allocate" matches self.kv.allocate and
#            self.scheduler.kv.allocate, NOT recorder.allocate) —
#            transfer marks ownership moving into a tracked container;
#   on       which witness-path kinds to report:
#            "escape"  — an exception leaves the function with the
#                        obligation live (caller-owned resources: only
#                        the acquiring frame can release);
#            "swallow" — an exception is caught and the function exits
#                        normally with no release reachable from the
#                        handler (the stranded-state shape — valid for
#                        registry-owned resources too, where a later
#                        keyed cleanup covers ordinary escapes);
#            "normal"  — a normal path drops the obligation (strictest;
#                        no in-tree protocol needs it, tests use it).
RESOURCE_PROTOCOLS: tuple[dict, ...] = (
    {
        # paged KV page-table entries: registry-owned (the manager
        # tracks pages per request; abort/finish free by request id),
        # so only a swallowed failure that reports success leaks
        "name": "kv-page-table",
        "carrier": "vllm_omni_tpu/core/kv_cache_manager.py"
                   "::KVCacheManager",
        "acquire": ("kv.allocate", "kv.adopt_streamed"),
        "release": ("kv.free", "kv.restore_truncated"),
        "on": ("swallow",),
    },
    {
        # cross-tier transfer pins: pinned pages survive free() until
        # acked, so a swallowed transfer failure pins HBM forever
        "name": "kv-transfer-pin",
        "carrier": "vllm_omni_tpu/core/kv_cache_manager.py"
                   "::KVCacheManager",
        "acquire": ("kv.pin_for_transfer",),
        "release": ("kv.ack_transfer",),
        "on": ("swallow",),
    },
    {
        # host-tier park intervals (the PR 15 un-closed interval bug):
        # every parked request must be restored or dropped
        "name": "kv-park-interval",
        "carrier": "vllm_omni_tpu/core/kv_cache_manager.py"
                   "::KVCacheManager",
        "acquire": ("kv.park_request",),
        "release": ("kv.restore_parked", "kv.drop_park"),
        "on": ("swallow",),
    },
    {
        # the flight-recorder dump window: caller-owned — ready()
        # atomically reserves the cooldown window and ONLY the
        # acquiring frame can roll it back, so an escaping exception
        # after a successful ready() suppresses evidence capture for
        # the whole cooldown period (the PR 15 consumed-window bug)
        "name": "dump-cooldown-window",
        "carrier": "vllm_omni_tpu/introspection/flight_recorder.py"
                   "::DumpCooldown",
        "acquire": ("cooldown.ready",),
        "release": ("cooldown.release",),
        "on": ("swallow", "escape"),
    },
    {
        # router drain: a drained replica serves nothing until
        # undrained or removed — the PR 12 stranded-donor resource
        "name": "router-drain",
        "carrier": "vllm_omni_tpu/disagg/router.py::DisaggRouter",
        "acquire": ("router.drain",),
        "release": ("router.undrain", "router.remove_replica"),
        "on": ("swallow", "escape"),
    },
    {
        # exactly-once failover submission ledger: an entry nothing
        # clears replays or suppresses a request forever (PR 9)
        "name": "failover-submission-ledger",
        "carrier": "vllm_omni_tpu/disagg/router.py::EngineReplica",
        "acquire": ("_submitted.add",),
        "release": ("_submitted.discard", "_submitted.clear"),
        "on": ("swallow",),
    },
)

# The OL13 typestate manifest: declared state machines whose mutation
# sites the CFG checks against the transition graph, plus the
# generalized PR 12 abort check — a non-terminal state write followed
# by a swallowed exception path from which no recovery transition is
# reachable strands the object.
#
# Spec shape:
#   class       "path::Class" carrying the state field (the class's own
#               methods are exempt — they ARE the machine);
#   field       the attribute holding the state;
#   states/transitions/terminal
#               the graph; ``aliases`` maps writer-vocabulary names to
#               canonical states ("resolved" -> "inactive");
#   values      for boolean flag machines: {True: name, False: name};
#   transition_fn
#               mutations also happen through calls to this method
#               (target = positional arg ``target_arg``), and ITS body
#               is exempt (it is the one blessed mutation site);
#   recover     call vocabulary that re-admits/rolls back — reaching
#               one from a swallowed handler discharges the abort
#               check;
#   match       "class" (default: the file must define/import the
#               class or its module) or "field" (any assignment of the
#               field counts — for distinctive field names whose
#               carrier instances travel between modules).
STATE_MACHINES: tuple[dict, ...] = (
    {
        # the control-plane operation ladder (rerole/scale_down), with
        # the bounded actuation-refused retry edges back to draining
        "name": "controlplane-op",
        "class": "vllm_omni_tpu/controlplane/controller.py::_Op",
        "field": "stage",
        "states": ("draining", "flipping", "readmitting", "removing"),
        "transitions": {
            "draining": ("flipping", "removing"),
            "flipping": ("readmitting", "draining"),
            "removing": ("draining",),
            "readmitting": (),
        },
        "terminal": (),
        "recover": ("_abort_op", "_finish_op"),
    },
    {
        # the alert lifecycle ring; "resolved" is writer vocabulary
        # for the inactive state (the transition doc keeps the word)
        "name": "alert-lifecycle",
        "class": "vllm_omni_tpu/metrics/alerts.py::_RuleState",
        "field": "state",
        "states": ("inactive", "pending", "firing"),
        "aliases": {"resolved": "inactive"},
        "transitions": {
            "inactive": ("pending", "firing"),
            "pending": ("firing", "inactive"),
            "firing": ("inactive",),
        },
        "terminal": ("inactive",),
        "transition_fn": "_transition",
        "target_arg": 1,
        "recover": (),
    },
    {
        # replica rotation membership as a two-state machine: drained
        # is the non-terminal "someone must re-admit or remove me"
        # state (the PR 12 stranded-donor bug, generalized)
        "name": "replica-rotation",
        "class": "vllm_omni_tpu/disagg/router.py::EngineReplica",
        "field": "drained",
        "values": {True: "drained", False: "in-rotation"},
        "states": ("drained", "in-rotation"),
        "transitions": {
            "drained": ("in-rotation",),
            "in-rotation": ("drained",),
        },
        "terminal": ("in-rotation",),
        "recover": ("undrain", "remove_replica", "revive",
                    "_abort_op"),
        "match": "field",
    },
)


class ManifestError(RuntimeError):
    """A manifest entry no longer resolves to real code — a renamed
    module/class must fail the lint run loudly, not silently un-lint
    whatever the entry used to cover."""


def validate_manifest(root: "str | None" = None) -> None:
    """Check every path-shaped manifest entry resolves to an existing
    file/dir and every ``path::Class`` / sanitizer entry to a real
    class/def.  Called once per CLI run (``__main__``) and by
    ``tests/analysis``; raises :class:`ManifestError` listing every
    broken entry."""
    import os

    if root is None:
        from vllm_omni_tpu.analysis.engine import REPO_ROOT
        root = REPO_ROOT
    problems: list[str] = []

    def check_path(entry: str, table: str) -> "str | None":
        """Absolute path for an existing entry, else records a problem."""
        p = os.path.join(root, entry.rstrip("/"))
        if entry.endswith("/"):
            if not os.path.isdir(p):
                problems.append(f"{table}: no such directory: {entry}")
                return None
        elif not os.path.isfile(p):
            problems.append(f"{table}: no such file: {entry}")
            return None
        return p

    for table, entries in (("HOT_PATHS", HOT_PATHS),
                           ("THREADED_PATHS", THREADED_PATHS),
                           ("BENCH_PATHS", BENCH_PATHS),
                           ("PROTOCOL_MODULES", PROTOCOL_MODULES),
                           ("METRIC_MODULES", METRIC_MODULES),
                           ("sched_arith_paths",
                            TAINT_SINKS["sched_arith_paths"])):
        for entry in entries:
            check_path(entry, table)
    for key, guards in LOCK_GUARDS.items():
        path, _, cls = key.partition("::")
        p = check_path(path, "LOCK_GUARDS")
        if p is None:
            continue
        with open(p, encoding="utf-8") as fh:
            src = fh.read()
        import re as _re
        if not _re.search(rf"^\s*class\s+{_re.escape(cls)}\b", src,
                          _re.MULTILINE):
            problems.append(f"LOCK_GUARDS: no class '{cls}' in {path}")
        del guards
    for fn, path in SANITIZERS.items():
        p = check_path(path, "SANITIZERS")
        if p is None:
            continue
        with open(p, encoding="utf-8") as fh:
            src = fh.read()
        if f"def {fn}(" not in src:
            problems.append(f"SANITIZERS: no def '{fn}' in {path}")

    # ---- omnileak (OL12/OL13): every acquire/release/transfer spec,
    # state, transition endpoint and recover name must resolve to real
    # code — a renamed method must fail the run, not silently un-lint
    # the protocol it used to guard
    import re as _re

    def read_class_src(key: str, table: str) -> "str | None":
        path, _, cls = key.partition("::")
        p = check_path(path, table)
        if p is None:
            return None
        with open(p, encoding="utf-8") as fh:
            src = fh.read()
        if not _re.search(rf"^\s*class\s+{_re.escape(cls)}\b", src,
                          _re.MULTILINE):
            problems.append(f"{table}: no class '{cls}' in {path}")
            return None
        return src

    def def_somewhere(name: str) -> bool:
        """``def name(`` anywhere under the package tree — recover
        vocabularies cross modules (the controller re-admits what the
        router drained)."""
        pkg = os.path.join(root, "vllm_omni_tpu")
        for dirpath, dirnames, filenames in os.walk(pkg):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in filenames:
                if not f.endswith(".py"):
                    continue
                with open(os.path.join(dirpath, f),
                          encoding="utf-8") as fh:
                    if f"def {name}(" in fh.read():
                        return True
        return False

    for proto in RESOURCE_PROTOCOLS:
        tag = f"RESOURCE_PROTOCOLS[{proto.get('name', '?')}]"
        src = read_class_src(proto["carrier"], tag)
        for kind in proto.get("on", ()):
            if kind not in ("escape", "swallow", "normal"):
                problems.append(f"{tag}: unknown path kind {kind!r}")
        if src is None:
            continue
        for spec in (proto.get("acquire", ()) + proto.get("release", ())
                     + proto.get("transfer", ())):
            recv, _, meth = spec.rpartition(".")
            if f"def {meth}(" in src:
                continue
            # container protocols (``_submitted.add``): the method is
            # a builtin, the receiver must be a carrier attribute
            if recv and f"self.{recv}" in src:
                continue
            problems.append(
                f"{tag}: spec '{spec}' resolves to neither a def nor "
                f"a carrier attribute in {proto['carrier']}")
    for mach in STATE_MACHINES:
        tag = f"STATE_MACHINES[{mach.get('name', '?')}]"
        src = read_class_src(mach["class"], tag)
        if src is None:
            continue
        field = mach["field"]
        if not _re.search(rf"\b{_re.escape(field)}\b\s*[:=]", src):
            problems.append(
                f"{tag}: field '{field}' never assigned/declared in "
                f"{mach['class'].partition('::')[0]}")
        states = tuple(mach.get("states", ()))
        if not mach.get("values"):
            for st in states:
                if f'"{st}"' not in src and f"'{st}'" not in src:
                    problems.append(
                        f"{tag}: state {st!r} never appears in "
                        f"{mach['class'].partition('::')[0]}")
        for src_st, dsts in mach.get("transitions", {}).items():
            for st in (src_st,) + tuple(dsts):
                if st not in states:
                    problems.append(
                        f"{tag}: transition endpoint {st!r} not in "
                        f"states")
        for st in tuple(mach.get("terminal", ())) + tuple(
                mach.get("aliases", {}).values()):
            if st not in states:
                problems.append(f"{tag}: state {st!r} not in states")
        fn = mach.get("transition_fn")
        if fn and f"def {fn}(" not in src:
            problems.append(
                f"{tag}: no def '{fn}' in "
                f"{mach['class'].partition('::')[0]}")
        for name in mach.get("recover", ()):
            if not def_somewhere(name):
                problems.append(
                    f"{tag}: recover '{name}' is not a def anywhere "
                    f"under vllm_omni_tpu/")
    if problems:
        raise ManifestError(
            "manifest entries no longer resolve (a rename must update "
            "analysis/manifest.py, not silently un-lint):\n  "
            + "\n  ".join(problems))


def in_scope(path: str, prefixes: tuple[str, ...]) -> bool:
    """True when repo-relative ``path`` matches a manifest entry (a
    directory prefix ending in "/", an exact file, or a bare filename)."""
    for p in prefixes:
        if p.endswith("/"):
            if path.startswith(p):
                return True
        elif path == p or path.endswith("/" + p):
            return True
    return False
