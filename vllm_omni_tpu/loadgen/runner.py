"""Open-loop runner: drive a workload, fold records into curve points.

Three drivers share one record shape and one summarizer:

- ``run_http``    — the OpenAI HTTP server (one thread per arrival,
  fired at its scheduled offset regardless of completions).
- ``run_inproc``  — an ``AsyncOmni`` in this process (asyncio tasks;
  arrivals are ``sleep``-scheduled, never awaited-on-completion).
- ``simulate``    — a virtual-time FCFS queue: no clock, no server,
  bit-deterministic records.  The goodput math's oracle (tests) and
  the CI smoke curve's backend (scripts/loadgen.sh) — a real engine's
  scheduling noise must not gate a merge.

The OPEN-LOOP invariant everywhere: offered load is fixed by the
arrival schedule.  A saturated server sees requests keep arriving and
must shed (429) or queue — which is exactly what the serving curve is
supposed to show; a closed-loop client would self-throttle and hide it.

Timing note (omnilint OL4): every duration here is wall-clock around a
NETWORK or queue round trip on purpose — client-observed latency is
the product being measured, and no jax dispatch happens in this
module.  Durations come from ``time.monotonic``.
"""

from __future__ import annotations

import heapq
import threading
import time
from dataclasses import dataclass
from typing import Optional, Sequence

from vllm_omni_tpu.loadgen.workload import LoadRequest
from vllm_omni_tpu.metrics.stats import nearest_rank_pct


@dataclass
class SLOTargets:
    """Per-request SLO upper bounds (ms).  ``None`` legs always pass;
    a leg the driver could not MEASURE (e.g. TTFT on a non-streaming
    HTTP request) also passes — absence of evidence must not zero the
    goodput of an otherwise healthy run."""

    ttft_ms: Optional[float] = None
    tpot_ms: Optional[float] = None
    e2e_ms: Optional[float] = None

    def as_dict(self) -> dict:
        return {"ttft_ms": self.ttft_ms, "tpot_ms": self.tpot_ms,
                "e2e_ms": self.e2e_ms}


@dataclass
class RequestRecord:
    """One request's observed lifecycle.  All times are SECONDS offset
    from the run's t0 (monotonic deltas — never wall-clock pairs)."""

    request_id: str
    tenant: str = "default"
    scenario: str = "chat"
    arrival_s: float = 0.0           # scheduled offset
    fired_s: float = 0.0             # when the driver actually submitted
    first_s: Optional[float] = None  # first output observed
    end_s: Optional[float] = None
    tokens_out: int = 0
    # "ok" | "shed" (429 / error_kind shed) | "expired" (504 /
    # deadline_exceeded) | "error" (everything else)
    status: str = "error"

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.first_s is None:
            return None
        return max(self.first_s - self.fired_s, 0.0) * 1e3

    @property
    def e2e_ms(self) -> Optional[float]:
        if self.end_s is None:
            return None
        return max(self.end_s - self.fired_s, 0.0) * 1e3

    @property
    def tpot_ms(self) -> Optional[float]:
        """Per-output-token time excluding the first token; None when
        fewer than 2 tokens exist (no per-token time to report)."""
        if (self.first_s is None or self.end_s is None
                or self.tokens_out <= 1):
            return None
        return (max(self.end_s - self.first_s, 0.0) * 1e3
                / (self.tokens_out - 1))


def slo_met(rec: RequestRecord, slo: SLOTargets) -> bool:
    """True when the request completed AND every configured+measured
    SLO leg held (<= — exactly at the target counts as met)."""
    if rec.status != "ok":
        return False
    for target, value in ((slo.ttft_ms, rec.ttft_ms),
                         (slo.tpot_ms, rec.tpot_ms),
                         (slo.e2e_ms, rec.e2e_ms)):
        if target is not None and value is not None and value > target:
            return False
    return True


def _pcts(xs: list) -> dict:
    return {"p50": round(nearest_rank_pct(xs, 0.50), 3),
            "p90": round(nearest_rank_pct(xs, 0.90), 3),
            "p99": round(nearest_rank_pct(xs, 0.99), 3)}


def summarize(records: Sequence[RequestRecord], offered_rps: float,
              slo: Optional[SLOTargets] = None,
              duration_s: Optional[float] = None) -> dict:
    """Fold one rate point's records into a ``serving_curve`` entry.

    Throughput counts every completed request; GOODPUT counts only the
    SLO-met ones (sheds/expiries/errors are attainment misses by
    definition — refusing a request is not serving it).  ``duration_s``
    defaults to the observed makespan (first fire to last event)."""
    slo = slo or SLOTargets()
    if duration_s is None:
        lo = min((r.fired_s for r in records), default=0.0)
        hi = max((r.end_s if r.end_s is not None else r.fired_s
                  for r in records), default=0.0)
        duration_s = max(hi - lo, 1e-9)
    ok = [r for r in records if r.status == "ok"]
    met = [r for r in ok if slo_met(r, slo)]
    tokens_ok = sum(r.tokens_out for r in ok)
    tokens_good = sum(r.tokens_out for r in met)
    n = len(records)
    point = {
        "offered_rps": round(float(offered_rps), 4),
        "duration_s": round(duration_s, 3),
        "num_requests": n,
        "completed": len(ok),
        "shed": sum(1 for r in records if r.status == "shed"),
        "expired": sum(1 for r in records if r.status == "expired"),
        "errors": sum(1 for r in records if r.status == "error"),
        "attained_req_per_s": round(len(ok) / duration_s, 4),
        "attained_tok_per_s": round(tokens_ok / duration_s, 4),
        "goodput_req_per_s": round(len(met) / duration_s, 4),
        "goodput_tok_per_s": round(tokens_good / duration_s, 4),
        # SLO-met over OFFERED (not over completed): the non-increasing
        # quantity the curve's knee is read from
        "slo_attainment": round(len(met) / n, 4) if n else 0.0,
        "slo": slo.as_dict(),
        "ttft_ms": _pcts([r.ttft_ms for r in ok
                          if r.ttft_ms is not None]),
        "tpot_ms": _pcts([r.tpot_ms for r in ok
                          if r.tpot_ms is not None]),
        "e2e_ms": _pcts([r.e2e_ms for r in ok
                         if r.e2e_ms is not None]),
    }
    return point


#: required keys of a serving_curve point (tests and the loadgen.sh
#: gate validate what ``summarize`` returns against this)
CURVE_POINT_KEYS = (
    "offered_rps", "duration_s", "num_requests", "completed", "shed",
    "expired", "errors", "attained_req_per_s", "attained_tok_per_s",
    "goodput_req_per_s", "goodput_tok_per_s", "slo_attainment", "slo",
    "ttft_ms", "tpot_ms", "e2e_ms",
)


def validate_curve_point(point: dict) -> list[str]:
    """Schema check for one serving_curve entry; returns violations
    (empty = valid)."""
    errors = [f"missing key {k!r}" for k in CURVE_POINT_KEYS
              if k not in point]
    for k in ("ttft_ms", "tpot_ms", "e2e_ms"):
        sub = point.get(k)
        if isinstance(sub, dict):
            errors += [f"{k} missing {p!r}" for p in ("p50", "p90", "p99")
                       if p not in sub]
    counted = sum(point.get(k, 0) or 0 for k in
                  ("completed", "shed", "expired", "errors"))
    if point.get("num_requests") is not None \
            and counted != point["num_requests"]:
        errors.append(
            f"counts don't partition num_requests: {counted} != "
            f"{point['num_requests']}")
    return errors


# ------------------------------------------------------------ simulator
def simulate(workload: Sequence[LoadRequest], prefill_s: float,
             per_token_s: float, servers: int = 1,
             queue_limit: Optional[int] = None) -> list[RequestRecord]:
    """Virtual-time FCFS queue: ``servers`` identical seats, service
    time = prefill_s + max_tokens * per_token_s, first token after the
    prefill + one token time.  An arrival finding ``queue_limit``
    requests already waiting is SHED (mirroring the scheduler's
    queue-depth admission control).  Pure math — deterministic records
    with zero wall-clock, which is what makes it a CI gate."""
    free = [0.0] * max(int(servers), 1)
    heapq.heapify(free)
    starts: list[float] = []  # admitted requests' start times, in order
    records = []
    for lr in sorted(workload, key=lambda r: r.at_s):
        rec = RequestRecord(
            request_id=lr.request_id, tenant=lr.tenant,
            scenario=lr.scenario, arrival_s=lr.at_s, fired_s=lr.at_s)
        waiting = sum(1 for s in starts if s > lr.at_s)
        if queue_limit is not None and waiting >= queue_limit:
            rec.status = "shed"
            rec.end_s = lr.at_s
            records.append(rec)
            continue
        start = max(lr.at_s, heapq.heappop(free))
        service = prefill_s + lr.max_tokens * per_token_s
        end = start + service
        heapq.heappush(free, end)
        starts.append(start)
        rec.first_s = start + prefill_s + per_token_s
        rec.end_s = end
        rec.tokens_out = lr.max_tokens
        rec.status = "ok"
        records.append(rec)
    return records


# ---------------------------------------------------------- in-process
_ERROR_STATUS = {"shed": "shed", "deadline_exceeded": "expired"}


def run_inproc(omni, workload: Sequence[LoadRequest],
               deadline_s: Optional[float] = None,
               temperature: float = 0.0,
               timeout_s: float = 600.0) -> list[RequestRecord]:
    """Drive an ``AsyncOmni`` open-loop: one asyncio task per arrival,
    created at its scheduled offset — task creation never waits on any
    completion.  Runs a private event loop to completion and returns
    the records."""
    import asyncio

    records: list[RequestRecord] = []

    async def one(lr: LoadRequest, t0: float) -> None:
        rec = RequestRecord(
            request_id=lr.request_id, tenant=lr.tenant,
            scenario=lr.scenario, arrival_s=lr.at_s,
            fired_s=time.monotonic() - t0)
        info = {"tenant": lr.tenant}
        if lr.priority is not None:
            info["priority"] = lr.priority
        prompt = {"prompt_token_ids": list(lr.prompt_token_ids),
                  "additional_information": info}
        sp = {"max_tokens": lr.max_tokens, "temperature": temperature,
              "ignore_eos": True}
        failed = None
        try:
            async for o in omni.generate(prompt, sp, lr.request_id,
                                         deadline_s=deadline_s):
                now = time.monotonic() - t0
                if o.is_error:
                    failed = _ERROR_STATUS.get(o.error_kind, "error")
                    rec.end_s = now
                    break
                if rec.first_s is None:
                    rec.first_s = now
                rec.end_s = now
                rec.tokens_out += sum(len(c.token_ids)
                                      for c in o.outputs)
        except Exception:
            failed = "error"
            rec.end_s = time.monotonic() - t0
        rec.status = failed if failed else (
            "ok" if rec.end_s is not None else "error")
        records.append(rec)

    async def drive() -> None:
        t0 = time.monotonic()
        tasks: list = []
        for lr in workload:
            delay = lr.at_s - (time.monotonic() - t0)
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append((asyncio.ensure_future(one(lr, t0)), lr))
        if not tasks:
            return
        _, pending = await asyncio.wait([t for t, _ in tasks],
                                        timeout=timeout_s)
        if pending:
            # requests still in flight at the timeout are RECORDED as
            # errors, never silently dropped — dropping them would
            # shrink the offered population and flatter the knee of
            # the curve in exactly the overload regime the harness
            # exists to measure
            for t in pending:
                t.cancel()
            await asyncio.gather(*pending, return_exceptions=True)
            now = time.monotonic() - t0
            seen = {r.request_id for r in records}
            for t, lr in tasks:
                if t in pending and lr.request_id not in seen:
                    records.append(RequestRecord(
                        request_id=lr.request_id, tenant=lr.tenant,
                        scenario=lr.scenario, arrival_s=lr.at_s,
                        fired_s=lr.at_s, end_s=now, status="error"))

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(drive())
    finally:
        loop.close()
    return records


# ---------------------------------------------------------------- HTTP
def _classify_http(code: int) -> str:
    if code == 429:
        return "shed"
    if code == 504:
        return "expired"
    return "error"


def _http_one(base_url: str, lr: LoadRequest, t0: float,
              records: list, lock: threading.Lock,
              timeout_s: float) -> None:
    """Fire one chat completion immediately (the dispatcher already
    slept to its offset) and record the client-observed lifecycle
    (TTFT from the first SSE data event when streaming).  The wire
    work lives in the shared ``chat_http_request`` driver."""
    from vllm_omni_tpu.benchmarks.serving import chat_http_request

    rec = RequestRecord(
        request_id=lr.request_id, tenant=lr.tenant, scenario=lr.scenario,
        arrival_s=lr.at_s, fired_s=time.monotonic() - t0)
    headers = {"x-omni-tenant": lr.tenant}
    if lr.priority is not None:
        headers["x-omni-priority"] = str(lr.priority)
    res = chat_http_request(base_url, {
        "model": "loadgen",
        "messages": [{"role": "user", "content": lr.prompt}],
        "max_tokens": lr.max_tokens,
        "temperature": 0,
        # pin the output length (server extension): SSE carries no
        # usage block, so exact goodput/TPOT accounting needs the
        # token count to BE max_tokens
        "ignore_eos": True,
        "stream": bool(lr.stream),
    }, headers=headers, timeout_s=timeout_s)
    rec.end_s = res["end_mono"] - t0
    if res["first_event_mono"] is not None:
        rec.first_s = res["first_event_mono"] - t0
    if res["ok"]:
        rec.tokens_out = (res["usage_completion_tokens"]
                          if res["usage_completion_tokens"] is not None
                          else lr.max_tokens)
        rec.status = "ok"
    elif res["http_status"] is not None:
        rec.status = _classify_http(res["http_status"])
    elif res["error"] is not None:
        # mid-stream SSE error event: the taxonomy rides its would-be
        # HTTP code (429 shed / 504 expired / ...)
        code = res["error"].get("code") \
            if isinstance(res["error"], dict) else None
        rec.status = (_classify_http(code) if isinstance(code, int)
                      else "error")
    else:
        rec.status = "error"
    with lock:
        records.append(rec)


def run_http(base_url: str, workload: Sequence[LoadRequest],
             timeout_s: float = 600.0) -> list[RequestRecord]:
    """Drive the OpenAI server open-loop: the dispatcher (this thread)
    sleeps to each arrival's offset and spawns that request's thread
    AT FIRE TIME — live threads scale with the in-flight count, not
    the workload size (pre-spawning a 10-minute trace would hold
    thousands of sleeping stacks on the measurement host).  A thread
    per in-flight request is deliberate: a bounded pool would gate
    arrivals on completions and close the loop."""
    records: list[RequestRecord] = []
    lock = threading.Lock()
    t0 = time.monotonic()
    threads = []
    for lr in sorted(workload, key=lambda r: r.at_s):
        delay = lr.at_s - (time.monotonic() - t0)
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=_http_one,
                             args=(base_url, lr, t0, records, lock,
                                   timeout_s))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    return records
