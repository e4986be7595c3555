"""Pipeline metrics: per-stage / per-edge / end-to-end aggregation.

Behavioral port of the reference's metrics layer (reference:
vllm_omni/metrics/stats.py — StageRequestStats:28, StageStats:18,
TransferEdgeStats:59, RequestE2EStats:75, OrchestratorAggregator:115 with
per-stage TPS + E2E latency aggregation and optional ``*.stats.jsonl``
output wired in entrypoints/omni.py:692-697,759-791).
"""

from __future__ import annotations

import bisect
import json
import re
import threading
import time
from collections import deque
from dataclasses import asdict, dataclass, field
from typing import Optional

from vllm_omni_tpu.analysis.runtime import traced


@dataclass
class StageRequestStats:
    request_id: str
    stage_id: int
    tokens_in: int = 0
    tokens_out: int = 0
    gen_ms: float = 0.0
    rx_bytes: int = 0
    rx_decode_ms: float = 0.0
    in_flight_ms: float = 0.0


@dataclass
class StageStats:
    stage_id: int
    num_requests: int = 0
    tokens_in: int = 0
    tokens_out: int = 0
    gen_ms_total: float = 0.0

    @property
    def tps(self) -> float:
        return (self.tokens_out / (self.gen_ms_total / 1e3)
                if self.gen_ms_total else 0.0)


@dataclass
class TransferEdgeStats:
    from_stage: int
    to_stage: int
    num_transfers: int = 0
    bytes_total: int = 0
    transfer_ms_total: float = 0.0


@dataclass
class RequestE2EStats:
    request_id: str
    # wall-clock arrival, kept for LOGS only (jsonl records, dashboards
    # correlating against external timestamps) — never for durations
    arrival_ts: float
    finish_ts: float = 0.0
    # duration clock: monotonic stamps.  An NTP step mid-request would
    # corrupt a wall-clock difference (negative or wildly inflated
    # latencies poisoning the histograms); time.monotonic() is immune.
    arrival_mono: float = 0.0
    finish_mono: float = 0.0

    @property
    def e2e_ms(self) -> float:
        return max(0.0, (self.finish_mono - self.arrival_mono) * 1e3)


# Prometheus-style latency buckets (ms).  Wide on purpose: one set serves
# TTFT (tens of ms on-chip, seconds under load) and ITL (single-digit ms)
# — per-metric tuning would make cross-deployment dashboards incomparable.
LATENCY_BUCKETS_MS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                      500.0, 1000.0, 2500.0, 5000.0, 10000.0, 30000.0,
                      60000.0)

# Token-count buckets (engine_step_batched_tokens): powers of two up to
# the largest plausible per-step token budget.
TOKEN_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
                 512.0, 1024.0, 2048.0, 4096.0, 8192.0)

# KV pages held while a step ran (live tables and cached prefixes), up to
# the pool's size
KV_PAGE_BUCKETS = (64.0, 256.0, 1024.0, 2048.0, 4096.0, 8192.0, 12288.0,
                   16384.0, 24576.0, 32768.0, 65536.0, 131072.0)

# state slots in use (a count of sequences, up to max_num_seqs)
PERCENT_BUCKETS = (1.0, 2.0, 5.0, 10.0, 20.0, 30.0, 40.0, 50.0, 60.0,
                   70.0, 80.0, 90.0, 100.0)
STATE_SLOT_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)

# KV tier-restore latency buckets, in SECONDS: sub-ms for small
# host-tier hits up to tens of seconds for big runs over a slow remote
# tier (docs/kv_cache.md).
KV_RESTORE_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                        0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


class Histogram:
    """Fixed-bucket histogram with a recent-value window for percentiles.

    Buckets follow Prometheus semantics (``snapshot()`` returns CUMULATIVE
    counts per upper bound, plus sum/count) so the exposition layer
    (metrics/prometheus.py) can render ``_bucket``/``_sum``/``_count``
    series directly.  Percentiles come from a bounded recent window (the
    same recency stance as OrchestratorAggregator — a lifetime of
    latencies would both grow memory and bury regressions under history).

    Thread-safe: the engine thread observes while the /metrics HTTP
    thread snapshots.
    """

    def __init__(self, buckets=LATENCY_BUCKETS_MS, window: int = 4096):
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self._counts = [0] * (len(self.buckets) + 1)  # last = +Inf
        self._sum = 0.0
        self._count = 0
        self._window: deque = deque(maxlen=window)
        self._lock = traced(threading.Lock(), "Histogram._lock")

    def observe(self, value: float, n: int = 1) -> None:
        """Record ``value`` ``n`` times (n>1 amortizes per-token metrics
        a multi-step decode window emits in one host round trip)."""
        if n <= 0:
            return
        i = bisect.bisect_left(self.buckets, value)
        with self._lock:
            self._counts[i] += n
            self._sum += value * n
            self._count += n
            # the window weights repeated observations once per call —
            # enough for percentile math without O(n) appends
            self._window.append(value)

    def percentile(self, p: float) -> float:
        with self._lock:
            xs = list(self._window)
        return nearest_rank_pct(xs, p)

    def snapshot(self) -> dict:
        with self._lock:
            counts = list(self._counts)
            s, c = self._sum, self._count
            xs = list(self._window)
        cum = 0
        cumulative = []
        for le, n in zip(self.buckets + (float("inf"),), counts):
            cum += n
            cumulative.append([le, cum])
        return {
            "buckets": cumulative,
            "sum": round(s, 3),
            "count": c,
            "p50": round(nearest_rank_pct(xs, 0.50), 3),
            "p90": round(nearest_rank_pct(xs, 0.90), 3),
            "p99": round(nearest_rank_pct(xs, 0.99), 3),
        }


# label value for requests that carry no tenant metadata (the OpenAI
# server stamps ``x-omni-tenant`` into additional_information["tenant"])
DEFAULT_TENANT = "default"
# tenant values past the cardinality cap collapse into this bucket —
# the tenant label is CLIENT input, and a client inventing a fresh
# tenant per request must not grow engine memory or /metrics series
# without bound
OVERFLOW_TENANT = "other"
MAX_TENANT_SERIES = 32

_TENANT_BAD_CHARS = re.compile(r"[^A-Za-z0-9_.:\-]")


def sanitize_tenant(raw) -> str:
    """Client tenant -> safe, bounded label value: charset restricted
    to [A-Za-z0-9_.:-] (anything else becomes "_"), capped at 64
    chars, empty/missing -> DEFAULT_TENANT.  Exposition-side escaping
    exists too; sanitizing at the source keeps ledger keys, JSON
    snapshots, and log lines clean as well."""
    if not raw:
        return DEFAULT_TENANT
    s = _TENANT_BAD_CHARS.sub("_", str(raw))[:64]
    return s or DEFAULT_TENANT


def cap_tenant(tenant: str, known: "set[str] | dict") -> str:
    """Collapse a NEW tenant into OVERFLOW_TENANT once ``known``
    already tracks MAX_TENANT_SERIES distinct tenants."""
    if tenant in known or len(known) < MAX_TENANT_SERIES:
        return tenant
    return OVERFLOW_TENANT


# weighted-fair-queueing priority (docs/control_plane.md): an integer
# weight in [MIN_PRIORITY, MAX_PRIORITY] — a priority-8 tenant gets 8x
# a priority-1 tenant's share of the admission quantum under overload.
# DEFAULT_PRIORITY is the neutral weight every request without explicit
# metadata gets, so deployments that never send x-omni-priority keep
# exact FCFS-equivalent behavior (equal weights degenerate DRR to
# round-robin over tenants).
MIN_PRIORITY = 1
MAX_PRIORITY = 8
DEFAULT_PRIORITY = 4


def sanitize_priority(raw) -> int:
    """Client priority -> bounded int weight: parsed leniently (ints,
    numeric strings, floats truncate), clamped to
    [MIN_PRIORITY, MAX_PRIORITY]; anything unparseable or missing ->
    DEFAULT_PRIORITY.  CLIENT input (the x-omni-priority header) — it
    must never raise and never exceed the clamp, exactly the
    hostile-input stance of ``sanitize_tenant``."""
    if raw is None:
        return DEFAULT_PRIORITY
    try:
        f = float(str(raw).strip())
    except (TypeError, ValueError):
        return DEFAULT_PRIORITY
    if f != f:  # NaN parses as a float but orders with nothing
        return DEFAULT_PRIORITY
    # clamp in FLOAT space before truncating: "inf"/"1e400" parse fine
    # and int() on an infinity raises OverflowError — an out-of-range
    # value must clamp, never raise (one hostile header would
    # otherwise crash schedule() for every tenant)
    return int(max(float(MIN_PRIORITY), min(float(MAX_PRIORITY), f)))


@dataclass
class TenantSLOStats:
    """Per-tenant SLO attainment + goodput accounting over finished
    requests.  "Met" means every CONFIGURED target held: TTFT <= target
    and TPOT <= target (a missing target always passes; a <=1-token
    request has no TPOT and passes that leg).  Exactly-at-target counts
    as met — the SLO is an upper bound, not a strict one."""

    finished: int = 0        # successfully finished requests
    met: int = 0             # finished requests inside every SLO target
    tokens: int = 0          # output tokens over all finished requests
    goodput_tokens: int = 0  # output tokens of SLO-met requests only

    @property
    def attainment(self) -> float:
        """met / finished; 0.0 with zero completions (an idle tenant
        reports no attainment rather than a fake-perfect 1.0)."""
        if self.finished <= 0:
            return 0.0
        return self.met / self.finished


class DeltaRing:
    """Bounded ring of (monotonic_t, cumulative-sample) pairs for
    windowed counter deltas — the substrate burn-rate alerting
    (metrics/alerts.py) computes real windows from.

    Lifetime-cumulative ratios hide incidents: after a week of uptime,
    a minute of 100% errors moves ``slo_attainment_ratio`` by noise.
    Sampling the cumulative counters on a cadence and differencing
    against the sample closest to ``now - window_s`` recovers the
    WINDOWED rate.  All stamps are ``time.monotonic()`` — the same
    NTP-immunity stance as the PR 7 duration clocks (an NTP step
    mid-window must never fabricate or swallow a burn).

    Samples are plain dicts of floats; ``window_delta`` returns both
    the delta and the actual span covered (early in a process's life a
    1h window is backed by whatever history exists — the caller
    normalizes rates by the REAL span, never the nominal one).
    Not thread-safe: one owner samples and reads (the alert engine's
    evaluation thread).
    """

    def __init__(self, horizon_s: float, max_samples: int = 720,
                 clock=time.monotonic):
        self.horizon_s = float(horizon_s)
        self.max_samples = int(max_samples)
        self._clock = clock
        self._samples: deque = deque()

    def sample(self, values: dict) -> None:
        now = self._clock()
        self._samples.append((now, dict(values)))
        # keep ONE sample at-or-beyond the horizon so a full-window
        # delta always has a baseline to difference against
        while (len(self._samples) > 2
               and (now - self._samples[1][0] >= self.horizon_s
                    or len(self._samples) > self.max_samples)):
            self._samples.popleft()

    def window_delta(self, window_s: float, key: str
                     ) -> tuple[float, float]:
        """(delta, span_s) of ``key`` over the trailing ``window_s``:
        newest sample minus the newest sample at least ``window_s``
        old (falling back to the oldest available).  (0, 0) before two
        samples exist."""
        if len(self._samples) < 2:
            return 0.0, 0.0
        t_new, new = self._samples[-1]
        base_t, base = self._samples[0]
        for t, s in self._samples:
            if t_new - t >= window_s:
                base_t, base = t, s
            else:
                break
        return (float(new.get(key, 0.0)) - float(base.get(key, 0.0)),
                t_new - base_t)


def burn_rate(d_bad: float, d_total: float, budget: float) -> float:
    """Error-budget burn rate over one window: the window's bad
    fraction divided by the allowed bad fraction (``budget`` =
    1 - SLO objective).  1.0 = exactly on budget; 14.4 = burning a
    30-day budget in ~2 days (the classic fast-page threshold).  An
    empty window burns nothing — no traffic is not an SLO violation."""
    if d_total <= 0:
        return 0.0
    return (max(d_bad, 0.0) / d_total) / max(budget, 1e-9)


class EngineStepMetrics:
    """Step-level engine gauges/counters/histograms, sampled from
    ``LLMEngine.step()`` (the vLLM-core Stats/StatLogger analogue):
    scheduler depth gauges, token counters, and the request-latency
    histograms the serving SLOs are written against — TTFT (arrival to
    first output token), TPOT (per-output-token time over a finished
    request, excluding the first token), ITL (inter-token latency
    between consecutive host-visible emissions).
    """

    def __init__(self):
        self.ttft_ms = Histogram()
        self.tpot_ms = Histogram()
        self.itl_ms = Histogram()
        self.step_ms = Histogram()
        # step-phase breakdown: host-side work vs. device-bound wait per
        # step (device_ms is what the engine thread waited on the
        # device by its phase clock, host_ms the rest of the step:
        # introspection/phase_clock.py), plus how much of the host work
        # ran while a dispatched step was still computing (the async
        # pipeline's win — see docs/async_engine.md; sync steps overlap
        # nothing)
        self.host_ms = Histogram()
        self.device_ms = Histogram()
        # tokens per step (REAL tokens computed, before padding) — with
        # the useful/padded counters below this makes the unified
        # ragged path's padding win measurable (docs/ragged_batching.md)
        self.batched_tokens = Histogram(buckets=TOKEN_BUCKETS)
        # per-request KV tier restore latency (fetch + inject), seconds
        # — the cold path must earn its transfers (docs/kv_cache.md)
        self.kv_restore_s = Histogram(buckets=KV_RESTORE_BUCKETS_S)
        # arrival -> FIRST time scheduled, per request (the queueing
        # component the serving curve bends on)
        self.queue_wait_ms = Histogram()
        # HTTP accept -> LLMEngine.add_request, per request that the
        # front end stamped (``accept_mono`` in additional_information):
        # what the handler, the orchestrator's intake queue and the
        # stage's tokenizer add in front of the engine
        self.intake_ms = Histogram()
        # state slots in use, observed once a step: the occupancy of the
        # second kind of cache a model with recurrent layers holds
        # (core/kv_cache_manager.py; never observed for other models)
        self.state_slots = Histogram(buckets=STATE_SLOT_BUCKETS)
        # KV pages held, observed once a step that scheduled rows, for
        # every model: how full the traffic keeps the pool (a latent
        # cache's pages and per-head K/V pages count alike)
        self.kv_pages_used = Histogram(buckets=KV_PAGE_BUCKETS)
        # pages of the window kind held, once such a step, by a model
        # with sliding-window layers (never observed for other models)
        self.kv_window_pages_used = Histogram(buckets=KV_PAGE_BUCKETS)
        # of the keys a step's rows see on the full layers of a model with
        # learned sparse attention, the share they attend:
        # 100 x sum min(ctx, top_k) / sum ctx over the step's new tokens
        self.sparse_keep_pct = Histogram(buckets=PERCENT_BUCKETS)
        # of the keys a step's rows see on the sliding layers of a model
        # with window layers, the share the window leaves them:
        # 100 x sum min(ctx, window) / sum ctx over the step's new tokens
        # (at 100 nothing a step ran was cut by the window)
        self.window_keep_pct = Histogram(buckets=PERCENT_BUCKETS)
        # how full the chunks of a Mamba-2 model's chunked form ran, once
        # a ragged step that had any: 100 x tokens in chunks / chunk
        # slots (ops/ssd.py chunk_slots; the one-token rows take the
        # recurrent form and are in neither count)
        self.ssd_chunk_fill = Histogram(buckets=PERCENT_BUCKETS)
        # SLO targets (None = unconfigured leg always passes) + the
        # per-tenant attainment/goodput ledger they gate
        self.slo_ttft_ms: Optional[float] = None
        self.slo_tpot_ms: Optional[float] = None
        self.tenants: dict[str, TenantSLOStats] = {
            DEFAULT_TENANT: TenantSLOStats()}
        # per-phase saturation (last schedule's fractions): how close
        # each capacity axis ran to its ceiling — the knee of the
        # serving curve shows up here before latency explodes
        self.saturation: dict[str, float] = {
            "prefill": 0.0, "decode": 0.0, "seats": 0.0}
        # gauges (last sampled values)
        self.num_waiting = 0
        self.num_running = 0
        # counters
        self.num_steps = 0
        self.tokens_generated = 0
        self.prefill_tokens = 0
        self.host_ms_total = 0.0
        self.overlapped_host_ms_total = 0.0
        # padding efficiency: real tokens vs. padded device rows across
        # every dispatch (bucketed split path vs. token-packed unified)
        self.useful_tokens_total = 0
        self.padded_tokens_total = 0
        # new tokens the ragged kernel attended as whole q-block tiles
        # (LLMEngine._record_step; ops/ragged_paged_attention.py)
        self.wide_q_tokens_total = 0
        # tokens prefill chunks gave up to fit the bucket their step
        # already needed, and steps whose decode rows alone opened a
        # bucket above the token budget's (core/scheduler.py
        # PackingContract.step_cap)
        self.chunk_tokens_trimmed_total = 0
        self.bucket_raised_steps_total = 0
        # steps whose sampler applied a top-k / top-p filter, by where
        # its thresholds came from: the bounded set of candidates, or a
        # sort of the whole vocabulary (sample/sampler.py)
        self.sampler_bounded_steps_total = 0
        self.sampler_full_sort_steps_total = 0
        # steps whose executable runs the experts' grouped matmul as the
        # few-rows kernel (ops/grouped_matmul.py): decided as the step's
        # program is traced, added from its bucket, no device read
        self.moe_grouped_kernel_steps_total = 0

    def on_schedule(self, waiting: int, running: int) -> None:
        self.num_waiting = waiting
        self.num_running = running

    def on_saturation(self, prefill: float, decode: float,
                      seats: float) -> None:
        """Fractions of this step's capacity ceilings actually used:
        prefill/decode tokens over the step token budget, running seats
        over max_num_seqs (sampled per schedule; last value wins)."""
        self.saturation["prefill"] = round(min(max(prefill, 0.0), 1.0), 4)
        self.saturation["decode"] = round(min(max(decode, 0.0), 1.0), 4)
        self.saturation["seats"] = round(min(max(seats, 0.0), 1.0), 4)

    def on_request_slo(self, tenant: Optional[str], ttft_ms: float,
                       tpot_ms: Optional[float], n_tokens: int) -> None:
        """Account one successfully finished request against the SLO
        targets.  ``tpot_ms`` is None for <=1-token requests (no
        per-output-token time exists); that leg passes.  Exactly at a
        target counts as met (<=)."""
        t = cap_tenant(sanitize_tenant(tenant), self.tenants)
        st = self.tenants.setdefault(t, TenantSLOStats())
        met = True
        if self.slo_ttft_ms is not None and ttft_ms > self.slo_ttft_ms:
            met = False
        if (met and self.slo_tpot_ms is not None and tpot_ms is not None
                and tpot_ms > self.slo_tpot_ms):
            met = False
        st.finished += 1
        st.tokens += n_tokens
        if met:
            st.met += 1
            st.goodput_tokens += n_tokens

    def on_step(self, step_ms: float, new_tokens: int,
                prefill_tokens: int, host_ms: Optional[float] = None,
                device_ms: Optional[float] = None,
                overlapped_host_ms: float = 0.0) -> None:
        self.num_steps += 1
        self.tokens_generated += new_tokens
        self.prefill_tokens += prefill_tokens
        self.step_ms.observe(step_ms)
        if host_ms is not None:
            self.host_ms.observe(host_ms)
            self.host_ms_total += host_ms
            self.overlapped_host_ms_total += min(overlapped_host_ms,
                                                 host_ms)
        if device_ms is not None:
            self.device_ms.observe(device_ms)

    def on_padding(self, useful: int, padded: int) -> None:
        """Per-step device-row accounting: ``useful`` real tokens rode
        ``padded`` padded rows (engine samples the runner's counters
        around each dispatch/execute)."""
        if padded <= 0:
            return
        self.useful_tokens_total += useful
        self.padded_tokens_total += padded
        self.batched_tokens.observe(float(useful))

    def slo_totals(self) -> dict:
        """Cumulative SLO counters summed over tenants — the shape the
        alert engine's :class:`DeltaRing` samples so burn rates come
        from real windows instead of the lifetime attainment ratio
        (which a week of uptime renders incident-blind)."""
        finished = met = tokens = goodput = 0
        for st in self.tenants.values():
            finished += st.finished
            met += st.met
            tokens += st.tokens
            goodput += st.goodput_tokens
        return {
            "finished": finished,
            "met": met,
            "bad": finished - met,
            "tokens": tokens,
            "goodput_tokens": goodput,
        }

    @property
    def padding_efficiency(self) -> float:
        """useful / padded over all dispatches (1.0 = zero padding)."""
        if self.padded_tokens_total <= 0:
            return 0.0
        return self.useful_tokens_total / self.padded_tokens_total

    @property
    def overlap_ratio(self) -> float:
        """Fraction of host-side step work performed while a dispatched
        device step was in flight (0 for purely synchronous serving)."""
        if self.host_ms_total <= 0.0:
            return 0.0
        return self.overlapped_host_ms_total / self.host_ms_total

    def snapshot(self) -> dict:
        return {
            "gauges": {
                "num_waiting": self.num_waiting,
                "num_running": self.num_running,
            },
            "counters": {
                "num_steps": self.num_steps,
                "tokens_generated": self.tokens_generated,
                "prefill_tokens": self.prefill_tokens,
            },
            "ttft_ms": self.ttft_ms.snapshot(),
            "tpot_ms": self.tpot_ms.snapshot(),
            "itl_ms": self.itl_ms.snapshot(),
            "step_ms": self.step_ms.snapshot(),
            "host_ms": self.host_ms.snapshot(),
            "device_ms": self.device_ms.snapshot(),
            "batched_tokens": self.batched_tokens.snapshot(),
            "kv_restore_seconds": self.kv_restore_s.snapshot(),
            "queue_wait_ms": self.queue_wait_ms.snapshot(),
            "intake_ms": self.intake_ms.snapshot(),
            "state_slots": self.state_slots.snapshot(),
            "kv_pages_used": self.kv_pages_used.snapshot(),
            "kv_window_pages_used": self.kv_window_pages_used.snapshot(),
            "sparse_keep_pct": self.sparse_keep_pct.snapshot(),
            "window_keep_pct": self.window_keep_pct.snapshot(),
            "ssd_chunk_fill": self.ssd_chunk_fill.snapshot(),
            "saturation": dict(self.saturation),
            "slo": {
                "targets": {"ttft_ms": self.slo_ttft_ms,
                            "tpot_ms": self.slo_tpot_ms},
                "tenants": {
                    t: {"finished": st.finished, "met": st.met,
                        "tokens": st.tokens,
                        "goodput_tokens": st.goodput_tokens,
                        "attainment": round(st.attainment, 4)}
                    for t, st in sorted(self.tenants.items())
                },
            },
            "padding": {
                "useful_tokens_total": self.useful_tokens_total,
                "padded_tokens_total": self.padded_tokens_total,
                "wide_q_tokens_total": self.wide_q_tokens_total,
                "chunk_tokens_trimmed_total":
                    self.chunk_tokens_trimmed_total,
                "bucket_raised_steps_total":
                    self.bucket_raised_steps_total,
                "efficiency": round(self.padding_efficiency, 4),
            },
            "sampler": {
                "bounded_steps_total": self.sampler_bounded_steps_total,
                "full_sort_steps_total":
                    self.sampler_full_sort_steps_total,
            },
            "moe": {
                "grouped_kernel_steps_total":
                    self.moe_grouped_kernel_steps_total,
            },
            "overlap": {
                "ratio": round(self.overlap_ratio, 4),
                "host_ms_total": round(self.host_ms_total, 3),
                "overlapped_host_ms_total": round(
                    self.overlapped_host_ms_total, 3),
            },
        }


def nearest_rank_pct(xs: list, p: float) -> float:
    """Nearest-rank percentile over a sequence: index ceil(p*n)-1.
    (int(p*n) would bias toward the max — p50 of [10, 20] must be 10.)
    The int(p*100*n) form sidesteps float error in p itself (0.99*n)."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    idx = max(0, -(-int(p * 100 * len(xs)) // 100) - 1)
    return xs[min(len(xs) - 1, idx)]


class StreamStats:
    """What the serving layer streamed (``/v1/chat/completions`` with
    ``"stream": true``): requests, content events and the tokens they
    carried (tokens over events is what an event held: 1 while the
    writer keeps up with the steps, above 1 where deltas that waited
    went out as one), and from the engine's first-token instant to the
    write of the first content event, the part of a client's time to
    first token that is the hand-over's own.  Plain counts with no lock:
    the event loop's thread writes the streams' events and so nearly
    all of these; what a handler's thread writes at a reply's end (a
    text final behind an audio one) can lose an increment to it, and
    nothing reads them to a count's precision.  Read by ``/metrics``."""

    def __init__(self):
        self.requests_total = 0
        self.events_total = 0
        self.tokens_total = 0
        self.first_event_ms = Histogram()

    def snapshot(self) -> dict:
        return {
            "requests_total": self.requests_total,
            "events_total": self.events_total,
            "tokens_total": self.tokens_total,
            "first_event_ms": self.first_event_ms.snapshot(),
        }


class OrchestratorAggregator:
    """``stats_path`` is a path *prefix*: per-stage request records stream
    to ``{prefix}.stage{N}.stats.jsonl`` and E2E records to
    ``{prefix}.e2e.stats.jsonl`` (reference: the per-stage ``*.stats.jsonl``
    files of metrics/stats.py:115, wired at omni.py:692-697)."""

    def __init__(self, num_stages: int, stats_path: Optional[str] = None,
                 window: int = 4096):
        self.stages = {i: StageStats(stage_id=i) for i in range(num_stages)}
        self.edges: dict[tuple[int, int], TransferEdgeStats] = {}
        # in-flight only: finished entries are EVICTED (a long-running
        # server harvests stats every heartbeat — unbounded history would
        # grow memory forever and make summary() sort a lifetime of
        # latencies on the engine thread)
        self.requests: dict[str, RequestE2EStats] = {}
        self.per_request: deque = deque(maxlen=window)
        self._recent_e2e_ms: deque = deque(maxlen=window)
        self.num_finished = 0
        self._stats_path = stats_path
        self.stream = StreamStats()

    def _append_jsonl(self, suffix: str, record: dict) -> None:
        with open(f"{self._stats_path}.{suffix}.stats.jsonl", "a") as f:
            f.write(json.dumps(record) + "\n")

    # ------------------------------------------------------------ recording
    def record_arrival(self, request_id: str) -> None:
        self.requests[request_id] = RequestE2EStats(
            request_id=request_id, arrival_ts=time.time(),
            arrival_mono=time.monotonic(),
        )

    def record_finish(self, request_id: str) -> None:
        r = self.requests.pop(request_id, None)
        if r is None:
            return
        r.finish_ts = time.time()
        r.finish_mono = time.monotonic()
        self.num_finished += 1
        self._recent_e2e_ms.append(r.e2e_ms)
        if self._stats_path:
            self._append_jsonl("e2e", {
                "request_id": r.request_id,
                "arrival_ts": r.arrival_ts,
                "finish_ts": r.finish_ts,
                "e2e_ms": round(r.e2e_ms, 3),
            })

    def record_stage_request(self, s: StageRequestStats) -> None:
        self.per_request.append(s)
        st = self.stages.setdefault(s.stage_id, StageStats(stage_id=s.stage_id))
        st.num_requests += 1
        st.tokens_in += s.tokens_in
        st.tokens_out += s.tokens_out
        st.gen_ms_total += s.gen_ms
        if self._stats_path:
            self._append_jsonl(f"stage{s.stage_id}", asdict(s))

    def record_transfer(self, from_stage: int, to_stage: int,
                        nbytes: int, ms: float) -> None:
        key = (from_stage, to_stage)
        edge = self.edges.setdefault(
            key, TransferEdgeStats(from_stage=from_stage, to_stage=to_stage)
        )
        edge.num_transfers += 1
        edge.bytes_total += nbytes
        edge.transfer_ms_total += ms

    # ------------------------------------------------------------- summary
    def summary(self) -> dict:
        e2e = list(self._recent_e2e_ms)

        def pct(p):
            return nearest_rank_pct(e2e, p)

        return {
            "stages": {
                i: {
                    "num_requests": st.num_requests,
                    "tokens_in": st.tokens_in,
                    "tokens_out": st.tokens_out,
                    "tps": round(st.tps, 2),
                }
                for i, st in self.stages.items()
            },
            "edges": {
                f"{k[0]}->{k[1]}": {
                    "transfers": e.num_transfers,
                    "bytes": e.bytes_total,
                    "ms": round(e.transfer_ms_total, 2),
                }
                for k, e in self.edges.items()
            },
            "e2e": {
                "num_finished": self.num_finished,
                # percentiles over the recent window, not lifetime
                "window": len(e2e),
                "p50_ms": round(pct(0.50), 2),
                "p90_ms": round(pct(0.90), 2),
                "p99_ms": round(pct(0.99), 2),
            },
            "stream": self.stream.snapshot(),
        }
