"""Continuous-batching schedulers.

Behavioral port of the reference's two scheduler subclasses onto a
device-agnostic, host-side core:

- ``ARScheduler``   ≈ OmniARScheduler (reference:
  core/sched/omni_ar_scheduler.py:40) — waiting/running queues, chunked
  prefill under a token budget, preemption by recompute, plus the
  cross-stage KV-transfer lifecycle: trigger criteria (prefill_finished /
  special_token, :84-136), block snapshot (:553-594), delayed free until
  extraction ACK (:444-546).
- ``GenerationScheduler`` ≈ OmniGenerationScheduler (reference:
  core/sched/omni_generation_scheduler.py:25) — one-shot generators
  (code2wav / DiT-as-stage): the whole prompt is scheduled at once and the
  request finishes in a single step.

The scheduler never touches jax; its output is plain ints/lists which the
model runner buckets and pads into device arrays.
"""

from __future__ import annotations

import sys
import time
from dataclasses import dataclass, field
from typing import Optional

from vllm_omni_tpu.core.kv_cache_manager import KVCacheManager
from vllm_omni_tpu.request import KVTransferState, Request, RequestStatus
from vllm_omni_tpu.resilience.deadline import DEADLINE_EXCEEDED

#: error_kind of a load-shed rejection (HTTP 429 at the serving layer).
#: Distinct from the PR 3 taxonomy on purpose: 503 ("retryable") means
#: infrastructure broke mid-request, 504 ("deadline_exceeded") means the
#: time budget was spent — 429 means the server is HEALTHY but at
#: capacity, and backing off (not just resubmitting) is the right
#: client response.  The open-loop load harness maps the knee of the
#: serving curve off this status instead of timing out.
SHED = "shed"


@dataclass
class KVTransferConfig:
    """When to trigger cross-stage KV extraction for a request
    (reference: omni_ar_scheduler.py:84-136)."""

    trigger: str = "prefill_finished"  # or "special_token"
    special_token_id: Optional[int] = None


def packed_rows_needed(max_num_batched_tokens: int, decode_rows: int,
                       token_block: int) -> int:
    """Packed rows a budgeted step must have room for: the token budget,
    or the step's decode rows and one token block beside them where
    those alone pass it.  The one rule behind the scheduler's chunk cap
    (``PackingContract.step_cap``) and the runner's warm-up reach."""
    return max(max_num_batched_tokens, decode_rows + token_block)


@dataclass(frozen=True)
class PackingContract:
    """How the runner packs a step onto the token axis: every sequence
    takes a segment of whole token blocks, and the step runs in the
    first bucket of the line that holds its segments.  The engine hands
    the runner's to the scheduler, which then sizes a prefill chunk in
    packed rows; a scheduler without one sizes it by the token budget
    alone."""

    token_block: int
    token_buckets: tuple[int, ...]

    def rows(self, n_new: int) -> int:
        """Packed rows of a segment of ``n_new`` tokens."""
        return -(-n_new // self.token_block) * self.token_block

    def bucket(self, rows: int) -> int:
        for b in self.token_buckets:
            if rows <= b:
                return b
        return self.token_buckets[-1]

    def step_cap(self, max_num_batched_tokens: int, decode_rows: int) -> int:
        """The bucket a step runs in anyway: the token budget's, or the
        one its decode rows open with one token block left for a chunk.
        Prefill chunks fill it and never open the next."""
        return self.bucket(packed_rows_needed(
            max_num_batched_tokens, decode_rows, self.token_block))


@dataclass
class SchedulerConfig:
    """``max_num_batched_tokens`` is a step's budget of NEW tokens: a
    decode row spends one (a verify row its candidates), prefill chunks
    the rest.  Under an engine a chunk is additionally held to the
    packed-row bucket the step already needs (``PackingContract``): the
    runner gives every sequence a whole token block, so a chunk sized by
    tokens alone would push a full step one row past its bucket and
    double the rows every dense layer computes."""

    max_num_seqs: int = 8
    max_num_batched_tokens: int = 2048
    max_model_len: int = 4096
    # Chunked prefill: prompts longer than the token budget are split into
    # chunks; later chunks attend the cached KV of earlier ones
    # (forward_unified: the ragged kernel reads the pages already written).
    enable_chunked_prefill: bool = False
    # speculative decoding: max draft tokens verified per decode step
    # (drafts come from the runner's MTP head via req.spec_draft_tokens)
    num_speculative_tokens: int = 0
    kv_transfer: Optional[KVTransferConfig] = None
    # unified ragged batching: emit ONE token-budgeted mixed batch per
    # step — decodes claim the budget first, prefill chunks fill the
    # remainder (chunked prefill becomes the mechanism, not an opt-in
    # special case) — which the runner packs into a single ragged
    # device dispatch (docs/ragged_batching.md)
    unified_batching: bool = False
    # tiered KV offload (docs/kv_cache.md): preemption PARKS the
    # victim's computed KV in the host/remote tiers instead of
    # discarding it, and re-admission restores the run — recompute
    # becomes a transfer whenever the bytes beat the flops
    # (kvcache/policy.py decides per run)
    kv_offload: bool = False
    # admission control (load shedding, docs/load_testing.md): cap on
    # the waiting queue — an arrival that would push past it is SHED
    # (error_kind "shed", HTTP 429) instead of queued into a wait it
    # can only lose.  None = unbounded (classic behavior); 0 sheds
    # every new request (drain mode)
    max_queue_depth: Optional[int] = None
    # shed arrivals whose remaining deadline budget is below this floor
    # — a request that cannot plausibly finish in time is refused at
    # the door (429) rather than admitted to expire mid-queue (504).
    # 0.0 disables the check
    admission_deadline_headroom_s: float = 0.0
    # weighted-fair overload scheduling (docs/control_plane.md): order
    # the waiting queue by per-tenant deficit round robin — each
    # tenant's admission share is proportional to its requests'
    # priority weight (Request.priority, the sanitized x-omni-priority
    # metadata) — and make the max_queue_depth shed priority-ordered:
    # a full queue sheds its lowest-priority fresh entry to admit a
    # higher-priority arrival, instead of FCFS-shedding the arrival.
    # Off (default) keeps strict arrival order; with it on but no
    # client sending priorities, every tenant carries the neutral
    # weight and DRR degenerates to per-tenant round robin
    wfq_scheduling: bool = False
    # DRR quantum added per unit of priority weight each round, in
    # prompt tokens — the granularity of interleaving between tenants
    # (bigger = longer per-tenant runs, smaller = finer interleave)
    wfq_quantum_tokens: int = 256

    @property
    def chunking_enabled(self) -> bool:
        """Chunked prefill is ON whenever unified batching is: splitting
        prompts under the token budget is how the unified batch packs."""
        return self.enable_chunked_prefill or self.unified_batching


@dataclass
class ScheduledRequest:
    request: Request
    num_new_tokens: int
    slot_mapping: list[int]
    block_table: list[int]
    # position of the first new token (== num_computed_tokens at schedule)
    start_pos: int
    # the sequence's state slot in a model with recurrent layers
    # (KVCacheManager.state_slot); -1 for every other model
    state_slot: int = -1
    # the sequence's table over the window kind of page and the step's
    # slots there, in a model with sliding-window layers
    # (KVCacheManager.window_table); None for every other model
    window_table: Optional[list[int]] = None
    window_slots: Optional[list[int]] = None

    @property
    def is_prefill(self) -> bool:
        return self.start_pos < self.request.num_prompt_tokens

    @property
    def samples_final(self) -> bool:
        """This chunk reaches the sequence's last token, so the step
        SAMPLES from its final row.  The ONE definition of the
        final-chunk predicate — the scheduler's async accounting
        (note_async_dispatch) and the runner's sampling-row selection
        (_unified_sampling) must agree exactly, or a lagged retire
        consumes a token the runner never sampled.
        Evaluate BEFORE the step's token is appended (num_tokens moves)."""
        req = self.request
        return (self.start_pos + self.num_new_tokens >= req.num_tokens
                and not req.awaiting_chunks)


@dataclass
class SchedulerOutput:
    prefills: list[ScheduledRequest] = field(default_factory=list)
    decodes: list[ScheduledRequest] = field(default_factory=list)
    preempted: list[Request] = field(default_factory=list)
    # requests whose KV must be extracted+shipped this step
    # (reference: OmniSchedulerOutput.finished_requests_needing_kv_transfer)
    kv_transfer_requests: list[tuple[Request, list[int], int]] = field(
        default_factory=list
    )
    # unified ragged batching: the runner may pack prefills + decodes
    # into ONE token-packed dispatch (it still applies its own fallback
    # matrix — spec decode, logprobs, collect_hidden, embeds)
    unified: bool = False
    # async pipelining: request_id -> Request.async_generation at
    # dispatch, for every row the in-flight step SAMPLES (decodes and
    # sequence-final prefill chunks; mid-prefill chunks are absent).
    # The lagged retire consumes a token only when the generation still
    # matches — a preempt-and-readmit while the step was in flight
    # bumps the generation, discarding the stale token.
    async_sampled: dict[str, int] = field(default_factory=dict)
    # tokens this step's chunks gave up to fit the step's bucket, and
    # whether its decode rows alone opened a bucket above the token
    # budget's (PackingContract.step_cap; docs/observability.md)
    chunk_tokens_trimmed: int = 0
    bucket_raised: bool = False

    @property
    def num_scheduled(self) -> int:
        return len(self.prefills) + len(self.decodes)


class ARScheduler:
    def __init__(self, config: SchedulerConfig, kv_manager: KVCacheManager):
        self.config = config
        self.kv = kv_manager
        self.waiting: list[Request] = []
        self.running: list[Request] = []
        self._finished_ids: set[str] = set()
        # transfers triggered in update_from_output, delivered to the runner
        # via the *next* schedule() output (the reference's runner handles
        # them at the start of execute_model, gpu_ar_model_runner.py:100-106)
        self._pending_kv_transfers: list[tuple[Request, list[int], int]] = []
        # requests rejected at intake; drained by the engine into outputs
        self._errored: list[Request] = []
        # transfers awaiting extraction ACK, keyed by request_id
        self._active_transfer_reqs: dict[str, Request] = {}
        # lifetime counters for step-level metrics (/metrics gauges)
        self.num_preemptions = 0
        self.num_rejections = 0
        # preemptions that threw away a sequence's recurrent state
        self.num_state_resets = 0
        # load-shed counters, keyed (reason, tenant) — rendered as
        # shed_requests_total{reason, tenant} on /metrics
        self.shed_counts: dict[tuple[str, str], int] = {}
        # optional heavy-hitter attribution sink installed by the
        # engine (metrics/attribution.py ``TenantAttribution.add``):
        # unlike the capped shed ledger above, the sketch sees past
        # the cardinality cap — which tenant is driving the 429s
        self.attribution_sink = None
        # WFQ deferral ledger: rounds a tenant's head-of-line fresh
        # request was held back by its deficit while the DRR pass
        # placed other work — rendered as
        # wfq_deferred_requests_total{tenant} on /metrics
        self.wfq_deferred: dict[str, int] = {}
        # DRR rotation pointer: the tenant the next ordering pass
        # visits first (rotates every pass so quantum ties don't
        # permanently favor the first-arrived tenant)
        self._wfq_rotation = 0
        # set once any admitted request carries a deadline, so the
        # per-step expiry sweep stays free for deadline-less serving
        self._deadlines_possible = False
        # the runner's packing contract, handed over by the engine; None
        # (a scheduler built without a runner) sizes chunks by the token
        # budget alone
        self.packing: Optional[PackingContract] = None

    # ------------------------------------------------------------- intake
    def add_request(self, request: Request, injected_len: int = 0) -> None:
        """``injected_len``: prompt-prefix tokens whose KV the engine will
        inject from an upstream stage — only the remainder must fit the
        per-step token budget."""
        n = request.num_prompt_tokens
        # reject anything that could never be scheduled — otherwise the
        # request would pin the waiting queue and starve the engine
        reason = None
        if n > self.config.max_model_len:
            reason = "prompt exceeds max_model_len"
        elif (not self.config.chunking_enabled
              and n - injected_len > self.config.max_num_batched_tokens):
            reason = "prompt exceeds max_num_batched_tokens (chunked prefill off)"
        elif self.kv.pages_needed(n) > self.kv.num_pages:
            reason = "prompt needs more KV pages than the whole pool"
        if reason is not None:
            self.reject(request, reason)
            return
        if (request.deadline_ts is not None
                and time.monotonic() >= request.deadline_ts):
            # deadline enforcement at admission: the budget was spent
            # upstream (earlier stages / queues / transfers) — reject
            # with the distinct terminal status instead of burning
            # compute on an answer nobody is waiting for
            self.reject(request, "deadline exceeded before admission",
                        kind=DEADLINE_EXCEEDED)
            return
        # admission control AFTER the validity + expiry checks: a
        # malformed request is a 400 and a spent budget a 504 even when
        # the server is also overloaded — shed (429) only claims
        # requests that WOULD have been served on an idle server.  The
        # shed path returns before the request ever enters the waiting
        # queue: no pages, no scheduling work, no engine admission.
        if (self.config.max_queue_depth is not None
                and len(self.waiting) >= self.config.max_queue_depth):
            # priority-ordered shed (WFQ): a full queue prefers to
            # displace its lowest-priority FRESH entry over refusing a
            # strictly higher-priority arrival — under overload the
            # low-priority work is what defers, not whoever arrived
            # last.  Equal priority keeps the FCFS shed (no churn).
            if not (self.config.wfq_scheduling
                    and self._shed_lower_priority(request)):
                self.shed(request, "queue_depth",
                          f"waiting queue at capacity "
                          f"({self.config.max_queue_depth}); retry with "
                          "backoff")
                return
        if (self.config.admission_deadline_headroom_s > 0.0
                and request.deadline_ts is not None
                and request.deadline_ts - time.monotonic()
                < self.config.admission_deadline_headroom_s):
            self.shed(request, "deadline_headroom",
                      "remaining deadline below the admission floor "
                      f"({self.config.admission_deadline_headroom_s}s); "
                      "request would expire mid-queue")
            return
        if request.deadline_ts is not None:
            self._deadlines_possible = True
        request.status = RequestStatus.WAITING
        # per-request opt-out: a disagg router placing a request
        # COLOCATED on a prefill-role engine (degraded mode) suppresses
        # the transfer — the whole-prompt extraction would produce a
        # payload nobody consumes, exactly in the capacity-constrained
        # state the degradation ladder exists for
        if (self.config.kv_transfer is not None
                and not request.additional_information.get(
                    "disable_kv_transfer")):
            request.kv_transfer = KVTransferState.PENDING
        self.waiting.append(request)

    def shed(self, request: Request, reason: str, message: str) -> None:
        """Load-shed an arrival (admission control): count it per
        (reason, tenant) and error-finish it with the distinct ``shed``
        kind (HTTP 429) — the request never enters the waiting queue.
        Tenant values past the cardinality cap collapse into "other"
        (a client inventing tenants must not grow the ledger forever)."""
        from vllm_omni_tpu.metrics.stats import cap_tenant

        tenant = cap_tenant(request.tenant,
                            {t for _, t in self.shed_counts})
        key = (reason, tenant)
        self.shed_counts[key] = self.shed_counts.get(key, 0) + 1
        if self.attribution_sink is not None:
            # UNcapped tenant on purpose: the sketch bounds its own
            # memory, and attribution past the cap is its whole point
            self.attribution_sink(request.tenant, "sheds", 1.0)
        self.reject(request, message, kind=SHED)

    def _shed_lower_priority(self, arrival: Request) -> bool:
        """Displace the lowest-priority fresh waiting request (newest
        among ties) when the ``arrival`` strictly outranks it; returns
        True when room was made.  Only FRESH entries are candidates —
        anything with computed progress (preemption victims, prefix-
        cache adoptions, parked restores) or streaming chunk intake
        holds state worth strictly more than an empty slot."""
        victim = None
        for req in self.waiting:
            if (req.num_computed_tokens > 0 or req.awaiting_chunks
                    or req.status is RequestStatus.PREEMPTED
                    or req.output_token_ids
                    or req.additional_information.get("_parked_len")):
                # progress, streamed output, or a preemption victim
                # (whose num_computed_tokens was RESET to 0): all hold
                # state a client already saw — never displaceable
                continue
            if victim is None or req.priority <= victim.priority:
                victim = req  # <=: newest of the lowest class loses
        if victim is None or victim.priority >= arrival.priority:
            return False
        self.waiting.remove(victim)
        self.shed(victim, "queue_depth",
                  "displaced by a higher-priority arrival with the "
                  f"waiting queue at capacity "
                  f"({self.config.max_queue_depth}); retry with backoff")
        return True

    def _wfq_order(self) -> None:
        """Deficit-round-robin ordering of the waiting queue
        (docs/control_plane.md).  Entries with computed progress —
        preemption victims, parked restores — keep the queue head in
        their existing order (their pages/progress must not rot behind
        fresh arrivals); fresh arrivals are grouped per tenant (FIFO
        within a tenant) and interleaved by DRR: each round a tenant's
        deficit grows by ``wfq_quantum_tokens x priority`` and its
        head requests are placed while the deficit covers their token
        cost.  Every tenant's deficit grows every round, so every
        admitted tenant makes progress — starvation-free by
        construction.  A round that holds a tenant's head back while
        placing other work counts one deferral for that tenant."""
        from vllm_omni_tpu.metrics.stats import cap_tenant

        resuming: list[Request] = []
        groups: dict[str, list[Request]] = {}
        for req in self.waiting:
            if (req.num_computed_tokens > 0
                    or req.status is RequestStatus.PREEMPTED
                    or req.additional_information.get("_parked_len")):
                resuming.append(req)
            else:
                groups.setdefault(req.tenant, []).append(req)
        if len(groups) <= 1:
            return  # zero or one tenant: FIFO is already fair
        tenants = list(groups)
        start = self._wfq_rotation % len(tenants)
        tenants = tenants[start:] + tenants[:start]
        self._wfq_rotation += 1
        quantum = max(self.config.wfq_quantum_tokens, 1)
        deficit = {t: 0.0 for t in tenants}
        idx = {t: 0 for t in tenants}
        order: list[Request] = []
        remaining = sum(len(q) for q in groups.values())
        while remaining > 0:
            placed_this_round = 0
            held: list[str] = []
            for t in tenants:
                q = groups[t]
                i = idx[t]
                if i >= len(q):
                    continue
                deficit[t] += quantum * q[i].priority
                while i < len(q) and deficit[t] >= max(
                        q[i].num_tokens, 1):
                    deficit[t] -= max(q[i].num_tokens, 1)
                    order.append(q[i])
                    i += 1
                    remaining -= 1
                    placed_this_round += 1
                idx[t] = i
                if i < len(q):
                    held.append(t)
                else:
                    # classic DRR: an emptied queue forfeits its
                    # leftover deficit (no banking credit while idle)
                    deficit[t] = 0.0
            if placed_this_round:
                for t in held:
                    key = cap_tenant(t, self.wfq_deferred)
                    self.wfq_deferred[key] = \
                        self.wfq_deferred.get(key, 0) + 1
            # a round that placed nothing still grew every deficit, so
            # the loop always terminates (costs are finite)
        self.waiting = resuming + order

    def queue_depth_by_tenant(self) -> dict[str, int]:
        """Waiting-queue depth split per tenant (request_queue_depth
        gauge).  Always contains "default" so the series exists from
        the first scrape, idle or not; tenants past the cardinality
        cap report under "other"."""
        from vllm_omni_tpu.metrics.stats import cap_tenant

        depths: dict[str, int] = {"default": 0}
        for req in self.waiting:
            t = cap_tenant(req.tenant, depths)
            depths[t] = depths.get(t, 0) + 1
        return depths

    def reject(self, request: Request, reason: str,
               kind: str = "invalid_request") -> None:
        """Error-finish a request at intake: it surfaces as a FINISHED_ERROR
        output on the next step() instead of raising into the caller
        (one bad request must not break its batch-mates)."""
        request.status = RequestStatus.FINISHED_ERROR
        request.additional_information.setdefault("error", reason)
        # invalid_request -> HTTP 400; internal -> 500
        request.additional_information.setdefault("error_kind", kind)
        self._finished_ids.add(request.request_id)
        self._errored.append(request)
        self.num_rejections += 1
        # a parked payload for a dead request is unreachable garbage
        self.kv.drop_park(request)

    def find_request(self, request_id: str):
        """(queue, request) for an in-flight id, else (None, None)."""
        for q in (self.waiting, self.running):
            for req in q:
                if req.request_id == request_id:
                    return q, req
        return None, None

    def fail_request(self, request_id: str, reason: str,
                     kind: str = "invalid_request") -> bool:
        """Error-finish an IN-FLIGHT request (e.g. a streamed prompt chunk
        overflowed the limits): frees its pages and surfaces a
        FINISHED_ERROR output on the next step."""
        q, req = self.find_request(request_id)
        if req is None:
            return False
        q.remove(req)
        self.kv.free(req)
        self.reject(req, reason, kind)
        return True

    def expire_deadlines(self) -> list[Request]:
        """Error-finish every waiting/running request whose deadline
        passed (engine calls this each step; the requests surface as
        ``deadline_exceeded`` outputs through the normal errored
        drain).  Returns the expired requests so the engine can count
        them per stage."""
        if not self._deadlines_possible:
            return []
        now = time.monotonic()
        out: list[Request] = []
        for q in (self.waiting, self.running):
            for req in [r for r in q
                        if r.deadline_ts is not None
                        and now >= r.deadline_ts]:
                q.remove(req)
                self.kv.free(req)
                self.reject(req, "deadline exceeded",
                            kind=DEADLINE_EXCEEDED)
                out.append(req)
        return out

    def abort_request(self, request_id: str) -> None:
        q, req = self.find_request(request_id)
        if req is None:
            return
        req.status = RequestStatus.FINISHED_ABORTED
        q.remove(req)
        self._free_request(req)

    @property
    def has_unfinished(self) -> bool:
        return bool(self.waiting or self.running)

    @property
    def has_pending_errored(self) -> bool:
        """Intake-rejected requests waiting to be drained into outputs.
        Engines must keep stepping while these exist — a lone rejected
        request would otherwise never surface (ADVICE r1 medium)."""
        return bool(self._errored)

    # ----------------------------------------------------------- schedule
    def schedule(self) -> SchedulerOutput:
        out = SchedulerOutput()
        out.unified = self.config.unified_batching
        out.kv_transfer_requests = self.drain_pending_kv_transfers()
        budget = self.config.max_num_batched_tokens
        if self.config.wfq_scheduling and len(self.waiting) > 1:
            # weighted-fair admission order: the loop below still pops
            # waiting[0]; DRR just decides who stands there
            self._wfq_order()

        # 1. running requests decode first (one token each) — prioritize
        #    latency of in-flight sequences, preempting the newest on OOM
        #    (recompute policy, matching vLLM's default the reference extends).
        still_running: list[Request] = []
        snapshot = list(self.running)
        if self.config.unified_batching:
            # unified admission order: DECODES claim the token budget
            # first, prefill chunks fill the remainder (stable sort —
            # relative arrival order preserved within each class).  This
            # also points the preemption policy (_preempt_for walks the
            # snapshot tail) at chunking requests before decoding ones.
            snapshot.sort(key=self._needs_chunk)
        # packed rows left for prefill chunks in the bucket the step
        # runs in anyway; without a runner's contract a segment counts
        # no rows and the token budget alone sizes a chunk
        pack = self.packing
        room, rows = sys.maxsize, (lambda n_new: 0)
        if pack is not None:
            decode_rows = self._decode_rows_ahead(snapshot)
            cap = pack.step_cap(self.config.max_num_batched_tokens,
                                decode_rows)
            room, rows = cap - decode_rows, pack.rows
        for i, req in enumerate(snapshot):
            if req.status is not RequestStatus.RUNNING:
                continue  # preempted earlier in this very loop
            if budget <= 0:
                still_running.append(req)
                continue
            if req.num_inflight_tokens > 1:
                # a spec VERIFY dispatch is in flight: how many of its
                # k+1 candidates were accepted — and therefore where
                # this request's next KV position is — is unknown until
                # the lagged retire.  Hold the request one step; plain
                # decode rows (exactly one in-flight token) keep
                # pipelining ahead.
                still_running.append(req)
                continue
            # async pipelining schedules AHEAD of token knowledge: a
            # dispatched-but-unretired decode will append exactly one
            # token, so its in-flight count stands in for the token the
            # host hasn't seen yet (num_computed_tokens was already
            # advanced at dispatch; sync mode always has inflight 0)
            remaining = (req.num_tokens + req.num_inflight_tokens
                         - req.num_computed_tokens)
            if remaining <= 0:
                # streaming request fully caught up with the chunks that
                # have arrived: idle until the next append
                still_running.append(req)
                continue
            # awaiting_chunks: the would-be sampling position may still
            # be mid-prompt (more chunks coming) — compute arrived tokens
            # as prefill chunks, never as a sampling decode.
            # mid-prompt embeds: the decode path embeds from the token
            # table, so any still-in-prompt position of an embeds-based
            # request MUST run as a prefill chunk (its input is the
            # upstream hidden row, not token id 0) — this also covers a
            # chunked-prefill resume whose last chunk is a single token
            mid_prompt_embeds = (
                req.prompt_embeds is not None
                and req.num_computed_tokens < req.num_prompt_tokens
            )
            if remaining > 1 or req.awaiting_chunks or mid_prompt_embeds:
                # mid-prefill, or a preempted request recomputing prompt +
                # generated tokens (num_tokens, not num_prompt_tokens — a
                # resumed request chunks through its generated suffix too
                # instead of crawling it one decode step at a time):
                # schedule the next chunk rather than a decode token
                if room <= 0:
                    # an earlier chunk filled the bucket: next step
                    still_running.append(req)
                    continue
                want = min(remaining, budget)
                chunk = min(want, room)
                if not self.kv.can_allocate(req, chunk):
                    out.preempted.extend(
                        self._preempt_for(req, snapshot[i + 1:], chunk)
                    )
                if not self.kv.can_allocate(req, chunk):
                    self._preempt(req)
                    out.preempted.append(req)
                    continue
                table = self.kv.allocate(req, chunk)
                if table is None:
                    self._preempt(req)
                    out.preempted.append(req)
                    continue
                slots = self.kv.slot_mapping(req, chunk)
                out.prefills.append(ScheduledRequest(
                    request=req, num_new_tokens=chunk, slot_mapping=slots,
                    block_table=table, start_pos=req.num_computed_tokens,
                ))
                budget -= chunk
                room -= rows(chunk)
                out.chunk_tokens_trimmed += want - chunk
                still_running.append(req)
                continue
            if not self.kv.can_allocate(req, 1):
                # victims come only from *unscheduled* requests (later in
                # the priority order) — preempting one already in
                # out.decodes would free pages its scheduled KV write
                # still targets
                out.preempted.extend(self._preempt_for(req, snapshot[i + 1:]))
                if not self.kv.can_allocate(req, 1):
                    # still not enough: preempt this request itself
                    self._preempt(req)
                    out.preempted.append(req)
                    continue
            # speculative decode: verify up to k drafted tokens in this
            # step's forward (1 regular + n_spec draft positions); degrade
            # to a plain decode under budget/page pressure
            n_new = 1
            k = self.config.num_speculative_tokens
            if k and req.spec_draft_tokens and budget > 1:
                # drafts beyond the request's remaining max_tokens are
                # guaranteed-discarded work — don't schedule them
                remaining_out = (req.sampling_params.max_tokens
                                 - len(req.output_token_ids))
                # max_model_len leg counts the IN-FLIGHT token too: an
                # async-scheduled verify whose input token is still in
                # flight has num_tokens lagging by one, and without the
                # correction the last candidate position would land one
                # slot past the cap (allocating a page the block-table
                # truncation then cannot address)
                n_spec = min(
                    len(req.spec_draft_tokens), k, budget - 1,
                    self.config.max_model_len
                    - (req.num_tokens + req.num_inflight_tokens),
                    max(remaining_out - 1, 0),
                )
                if n_spec > 0 and self.kv.can_allocate(req, 1 + n_spec):
                    n_new = 1 + n_spec
            table = self.kv.allocate(req, n_new)
            if table is None:
                self._preempt(req)
                out.preempted.append(req)
                continue
            slots = self.kv.slot_mapping(req, n_new)
            out.decodes.append(ScheduledRequest(
                request=req, num_new_tokens=n_new, slot_mapping=slots,
                block_table=table, start_pos=req.num_computed_tokens,
            ))
            budget -= n_new
            still_running.append(req)
        self.running = still_running

        # 2. admit waiting requests (chunked prefill under the budget).
        # num_tokens (not num_prompt_tokens): a preempted request resumes by
        # recomputing KV for its prompt *and* its already-generated tokens.
        while (self.waiting and budget > 0 and room > 0
               and len(self.running) < self.config.max_num_seqs):
            req = self.waiting[0]
            if (self.config.kv_offload
                    and req.num_computed_tokens == 0
                    and not req.awaiting_chunks
                    and req.additional_information.get("_parked_len")):
                # parked preemption victim: restore its KV run from the
                # tier store instead of recomputing it.  Extraction
                # still in flight (queued this very step) -> wait one
                # step; payload gone -> fall through to full recompute
                if self.kv.park_in_flight(req):
                    break
                if self.kv.parked_available(req):
                    if not self.kv.restore_parked(req):
                        break  # page pressure: retry next step
                else:
                    # payload lost (host tier shed it with no remote
                    # edge): full recompute — which, with chunking off,
                    # may no longer fit one step.  _preempt skipped its
                    # starvation reject trusting the park; re-check
                    # here or the head request wedges the queue forever
                    # while other traffic keeps the engine busy.
                    # drop_park (not a bare _parked_len pop) also
                    # closes the host-tier page·second interval —
                    # residency attribution must stop at the shed, not
                    # run on through the whole recompute
                    self.kv.drop_park(req)
                    if (not self.config.chunking_enabled
                            and req.num_tokens
                            > self.config.max_num_batched_tokens):
                        self.waiting.pop(0)
                        # reject() alone doesn't free: release any pages
                        # a prior restore attempt left behind
                        self.kv.free(req)
                        self.reject(
                            req,
                            "parked KV payload lost and the recompute "
                            f"footprint ({req.num_tokens} tokens) "
                            "exceeds the step budget "
                            f"({self.config.max_num_batched_tokens}) "
                            "with chunked prefill off",
                            kind="internal",
                        )
                        continue
            if req.num_computed_tokens == 0 and not req.awaiting_chunks:
                # automatic prefix caching: adopt cached pages covering
                # the longest full-page prompt prefix; the request then
                # prefills from mid-prompt through the runner's
                # chunked-continuation path (vLLM-core APC semantics)
                self.kv.match_prefix(req)
            remaining = req.num_tokens - req.num_computed_tokens
            if remaining <= 0 and req.awaiting_chunks:
                # streaming request admitted before its first chunk has
                # content to compute: park it in running (idle) so it
                # doesn't pin the waiting queue
                self.waiting.pop(0)
                req.status = RequestStatus.RUNNING
                self.running.append(req)
                continue
            if (remaining == 1 and req.output_token_ids
                    and not req.awaiting_chunks):
                # resume-as-decode: a restored preemption victim whose
                # only outstanding position is the sampling one re-enters
                # through the decode executable — the one the
                # uninterrupted stream would have run — not a 1-token
                # prefill chunk.  The two executables agree only to the
                # last ULP, and on near-flat logits that flips greedy
                # argmaxes, breaking the offload bit-equality contract
                if not self.kv.can_allocate(req, 1):
                    break
                table = self.kv.allocate(req, 1)
                if table is None:
                    break
                slots = self.kv.slot_mapping(req, 1)
                out.decodes.append(ScheduledRequest(
                    request=req, num_new_tokens=1, slot_mapping=slots,
                    block_table=table, start_pos=req.num_computed_tokens,
                ))
                budget -= 1
                room -= rows(1)
                self.waiting.pop(0)
                req.status = RequestStatus.RUNNING
                self.running.append(req)
                continue
            if self.config.chunking_enabled:
                want = min(remaining, budget)
                chunk = min(want, room)
            elif remaining > budget or rows(remaining) > room:
                # the whole prompt must fit this step's budget, and its
                # segment the step's bucket
                break
            else:
                want = chunk = remaining
            if chunk <= 0 or not self.kv.can_allocate(req, chunk):
                break
            table = self.kv.allocate(req, chunk)
            if table is None:
                break
            slots = self.kv.slot_mapping(req, chunk)
            out.prefills.append(ScheduledRequest(
                request=req, num_new_tokens=chunk, slot_mapping=slots,
                block_table=table, start_pos=req.num_computed_tokens,
            ))
            budget -= chunk
            room -= rows(chunk)
            out.chunk_tokens_trimmed += want - chunk
            self.waiting.pop(0)
            req.status = RequestStatus.RUNNING
            self.running.append(req)
        if pack is not None and out.prefills:
            out.bucket_raised = cap > pack.bucket(
                self.config.max_num_batched_tokens)
        if self.kv.num_state_slots:
            # the slot column: taken with the sequence's first page, so
            # every scheduled row of a recurrent model has one by now
            for sc in out.prefills + out.decodes:
                sc.state_slot = self.kv.state_slot(sc.request.request_id)
        if getattr(self.kv, "window", 0):
            # the second table and its slots, for the sliding-window
            # layers' kind of page (grown with the first in allocate())
            for sc in out.prefills + out.decodes:
                rid = sc.request.request_id
                sc.window_table = self.kv.window_table(rid)
                sc.window_slots = self.kv.window_slot_mapping(
                    rid, sc.start_pos, sc.num_new_tokens)
        return out

    @staticmethod
    def _needs_chunk(req: Request) -> bool:
        """The request's next row is a prefill chunk, not a decode row
        (the running loop's own test, before any is scheduled)."""
        remaining = (req.num_tokens + req.num_inflight_tokens
                     - req.num_computed_tokens)
        return (remaining > 1 or req.awaiting_chunks
                or (req.prompt_embeds is not None
                    and req.num_computed_tokens < req.num_prompt_tokens))

    def _decode_rows_ahead(self, snapshot: list[Request]) -> int:
        """Packed rows this step's decode rows will hold, wherever they
        stand in the running order: a row costs a token block (a verify
        row its candidates' blocks).  Counted before the loop, so that a
        chunk scheduled ahead of a decode row leaves its segment free;
        a row that then degrades or is preempted was counted too, which
        costs its chunk a few tokens and never a bucket."""
        k = self.config.num_speculative_tokens
        rows = 0
        for req in snapshot:
            if (req.num_inflight_tokens > 1 or self._needs_chunk(req)
                    or req.num_tokens + req.num_inflight_tokens
                    <= req.num_computed_tokens):
                continue  # held a step, a chunk, or idle
            drafts = min(len(req.spec_draft_tokens or ()), k)
            rows += self.packing.rows(1 + drafts)
        return rows

    def running_rows_fit(self) -> bool:
        """Whether the next ``schedule()`` can give every running row its
        pages without preempting: each row's demand as the running loop
        would ask it (a decode row's token, a verify row's candidates, a
        chunk up to the whole budget), summed against what is free of each
        kind of page.  The pipelined step asks before it schedules ahead
        of a retire: a victim's in-flight token is dropped with its
        progress, and a request that has outgrown the pool would recompute
        for ever, each cycle's one new token being the one in flight."""
        budget = self.config.max_num_batched_tokens
        k = self.config.num_speculative_tokens
        need = wneed = 0
        for req in self.running:
            remaining = (req.num_tokens + req.num_inflight_tokens
                         - req.num_computed_tokens)
            if req.num_inflight_tokens > 1 or remaining <= 0:
                continue  # held a step, or idle
            n_new = (min(remaining, budget) if self._needs_chunk(req)
                     else 1 + min(len(req.spec_draft_tokens or ()), k))
            grow, wgrow = self.kv.pages_to_grow(req, n_new)
            need += grow
            wneed += wgrow
        return (need <= self.kv.num_free_pages
                and wneed <= self.kv.window_pages_free)

    def _preempt(self, req: Request) -> None:
        """Preemption: free pages, reset progress, back to waiting.
        With kv_offload on, the victim's computed KV run is PARKED in
        the tier store first (extraction drains before this step's
        forward can overwrite the freed pages) — re-admission restores
        the run instead of recomputing it.  Recompute remains the
        fallback: in-flight async tokens, a policy veto, or a lost
        payload all degrade to the classic path bit-identically."""
        self.num_preemptions += 1
        if self.kv.state_slot(req.request_id) >= 0:
            # the victim's recurrent state goes with its slot: it
            # recomputes from position 0, where a row reads zeros
            self.num_state_resets += 1
        if self.config.kv_offload:
            # the manager parks only the COMMITTED prefix (in-flight
            # async slots excluded — their tokens are discarded below
            # and may re-sample differently on recompute)
            self.kv.park_request(req)
        self.kv.free(req)
        req.num_computed_tokens = 0
        # an in-flight async token is discarded with the progress — the
        # recompute re-derives it (bit-identical for greedy; the retire
        # skips requests whose in-flight count was reset)
        req.num_inflight_tokens = 0
        # collected hidden states are recomputed from scratch on resume —
        # stale chunks would duplicate the prefix
        req.additional_information.pop("_hidden_chunks", None)
        req.status = RequestStatus.PREEMPTED
        # invalidate any in-flight async token for this request (see
        # Request.async_generation)
        req.async_generation += 1
        if req in self.running:
            self.running.remove(req)
        parked = req.additional_information.get("_parked_len", 0)
        if (not self.config.chunking_enabled
                and req.num_tokens - parked
                > self.config.max_num_batched_tokens):
            # the recompute footprint (prompt + generated, or a formerly
            # injected prefix) no longer fits one step and chunking is off:
            # requeueing would pin the waiting head forever while other
            # requests keep the engine busy (the starvation guard never
            # fires when something else schedules).  A parked run
            # shrinks the footprint to its un-parked remainder — but if
            # the payload is later lost the starvation guard still
            # error-finishes the request rather than wedging the queue
            self.kv.drop_park(req)
            self.reject(
                req,
                "preempted request cannot resume: recompute footprint "
                f"({req.num_tokens} tokens) exceeds the step budget "
                f"({self.config.max_num_batched_tokens}) with chunked "
                "prefill off",
                kind="internal",
            )
            return
        self.waiting.insert(0, req)

    def _preempt_for(
        self, req: Request, candidates: list[Request], num_tokens: int = 1
    ) -> list[Request]:
        """Preempt newest-first from ``candidates`` until ``req`` fits;
        returns the victims (possibly insufficient — caller rechecks)."""
        preempted = []
        for victim in reversed(candidates):
            if victim is req or victim.status is not RequestStatus.RUNNING:
                continue
            self._preempt(victim)
            preempted.append(victim)
            if self.kv.can_allocate(req, num_tokens):
                break
        return preempted

    # ------------------------------------------------------ update (post-run)
    def update_from_output(
        self,
        scheduler_output: SchedulerOutput,
        sampled: dict[str, "int | list[int]"],
        kv_extracted_req_ids: Optional[set[str]] = None,
    ) -> list[Request]:
        """Advance request state after the runner executed a step.

        ``sampled`` maps request_id -> new token (int), or — for a
        speculative-decode verify step — the list of accepted tokens (the
        regular sample plus every draft that matched; its length is the
        number of positions whose KV is now verified-valid).
        ``kv_extracted_req_ids`` ACKs completed KV extractions so pinned
        pages can be freed (reference: omni_ar_scheduler.py:444-471).
        Returns the list of requests that finished this step.
        """
        finished: list[Request] = []
        for sched in scheduler_output.prefills + scheduler_output.decodes:
            req = sched.request
            token = sampled.get(req.request_id)
            if token is None:
                req.num_computed_tokens += sched.num_new_tokens
                continue  # mid-prefill chunk: nothing sampled yet
            if isinstance(token, list):
                # spec decode: only accepted positions advance — rejected
                # draft slots are re-written when real tokens reach those
                # positions (slots are position-keyed, stale KV beyond
                # the context is never attended).  Advance is per-token
                # inside the loop so a special_token KV-transfer trigger
                # sees exactly the coverage plain decoding would
                # (KV through the token BEFORE the one just appended).
                tokens = token
                per_token_advance = True
            else:
                req.num_computed_tokens += sched.num_new_tokens
                tokens = [token]
                per_token_advance = False
            stopped = False
            for t in tokens:
                if per_token_advance:
                    req.num_computed_tokens += 1
                stopped = self._append_and_check_stop(req, t)
                if stopped:
                    break
            if stopped:
                finished.append(req)
                self._finish_running(req)
        if kv_extracted_req_ids:
            for rid in kv_extracted_req_ids:
                self._ack_kv_transfer(rid)
        return finished

    # ------------------------------------------------- async pipelined step
    def note_async_dispatch(self, scheduler_output: SchedulerOutput) -> None:
        """Account a pipelined dispatch BEFORE its tokens are host-
        visible: every scheduled chunk advances num_computed_tokens (its
        KV slots are being written by the in-flight step), and each row
        the step SAMPLES — single-token decodes and sequence-final
        prefill chunks — marks one in-flight token, so the next
        schedule() can emit the following decode without waiting for
        the readback.  Mid-prefill chunks sample nothing; the next
        chunk pipelines right behind them."""
        for sched in scheduler_output.decodes:
            req = sched.request
            req.num_computed_tokens += sched.num_new_tokens
            req.num_inflight_tokens += sched.num_new_tokens
            scheduler_output.async_sampled[req.request_id] = \
                req.async_generation
        for sched in scheduler_output.prefills:
            req = sched.request
            final = sched.samples_final
            req.num_computed_tokens += sched.num_new_tokens
            if final:
                req.num_inflight_tokens += 1
                scheduler_output.async_sampled[req.request_id] = \
                    req.async_generation

    def update_from_async_retire(
        self,
        scheduler_output: SchedulerOutput,
        sampled: dict[str, "int | list[int]"],
    ) -> list[Request]:
        """The one-step-lagged counterpart of ``update_from_output`` for
        a pipelined dispatch: num_computed_tokens already advanced at
        dispatch, so only the token append + stop checks happen here.
        Requests that finished, aborted, expired, or were preempted
        while their step was in flight have their token DISCARDED (the
        overshoot contract — greedy recompute re-derives a preempted
        request's token bit-identically); a preempt-and-readmit is
        caught by the async_generation stamp, not just the in-flight
        counter.

        A spec VERIFY row retires a LIST of accepted tokens: its
        dispatch advanced ``num_computed_tokens`` by the full candidate
        width (1 + drafts), so the rewind here keeps exactly the
        accepted prefix — rejected candidate slots are position-keyed
        garbage re-written when real tokens reach those positions, the
        same contract as the synchronous update."""
        finished: list[Request] = []
        # in-flight contribution per row (mirrors note_async_dispatch):
        # a final prefill chunk marked ONE in-flight token however wide
        # the chunk; a decode/verify row marked its full candidate width
        rows = ([(s, 1) for s in scheduler_output.prefills]
                + [(s, s.num_new_tokens) for s in scheduler_output.decodes])
        for sched, contrib in rows:
            req = sched.request
            gen = scheduler_output.async_sampled.get(req.request_id)
            consumed = (gen is not None
                        and gen == req.async_generation
                        and req.num_inflight_tokens > 0)
            if consumed:
                req.num_inflight_tokens = max(
                    req.num_inflight_tokens - contrib, 0)
            if req.is_finished:
                # overshoot: the request stopped one step earlier
                # (EOS/stop/abort/deadline) while this dispatch was in
                # flight — discard the token and rewind the speculative
                # advance so KV accounting matches what sync mode would
                # have recorded (the overshoot slot's write is garbage
                # in the request's own freed pages, never attended)
                if consumed:
                    req.num_computed_tokens -= sched.num_new_tokens
                continue
            if not consumed:
                # mid-prefill chunk (nothing sampled), or preempted /
                # re-admitted while in flight (token discarded with the
                # progress reset)
                continue
            token = sampled.get(req.request_id)
            if token is None:
                continue
            if isinstance(token, list):
                # verify row: keep the accepted prefix, rewind the rest
                # (per-token advance mirrors the sync spec update so a
                # stop inside the run leaves computed == appended)
                req.num_computed_tokens -= sched.num_new_tokens
                stopped = False
                for t in token:
                    req.num_computed_tokens += 1
                    stopped = self._append_and_check_stop(req, t)
                    if stopped:
                        break
                if stopped:
                    finished.append(req)
                    self._finish_running(req)
                continue
            if self._append_and_check_stop(req, token):
                finished.append(req)
                self._finish_running(req)
        return finished

    def _append_and_check_stop(self, req: Request, token: int) -> bool:
        """The ONE append/stop sequence shared by the sync update and
        the async lagged retire — a finish criterion or transfer
        trigger added here applies to both, preserving the sync/async
        bit-identity contract."""
        req.append_output_token(int(token))
        self._maybe_trigger_kv_transfer(req)
        stopped = req.check_stop()
        if not stopped and req.num_tokens >= self.config.max_model_len:
            req.status = RequestStatus.FINISHED_LENGTH
            stopped = True
        return stopped

    def _finish_running(self, req: Request) -> None:
        if req in self.running:
            self.running.remove(req)
        self._free_request(req)

    # ----------------------------------------------------- kv transfer hooks
    def drain_errored(self) -> list[Request]:
        errored, self._errored = self._errored, []
        return errored

    def drain_pending_kv_transfers(self) -> list[tuple[Request, list[int], int]]:
        pending, self._pending_kv_transfers = self._pending_kv_transfers, []
        return pending

    def _maybe_trigger_kv_transfer(self, req: Request) -> None:
        cfg = self.config.kv_transfer
        if cfg is None or req.kv_transfer is not KVTransferState.PENDING:
            return
        trigger = False
        if cfg.trigger == "prefill_finished":
            trigger = req.num_computed_tokens >= req.num_prompt_tokens
        elif cfg.trigger == "special_token":
            trigger = (cfg.special_token_id is not None
                       and req.output_token_ids
                       and req.output_token_ids[-1] == cfg.special_token_id)
        if not trigger:
            return
        # Only tokens whose KV is actually in the cache: the token sampled
        # this step is written at the *next* step's decode.
        seq_len = req.num_computed_tokens
        block_ids = self.kv.pin_for_transfer(req, seq_len)
        req.kv_transfer = KVTransferState.ACTIVE
        req.kv_transfer_block_ids = block_ids
        req.kv_transfer_seq_len = seq_len
        self._pending_kv_transfers.append((req, block_ids, seq_len))
        self._active_transfer_reqs[req.request_id] = req

    def _ack_kv_transfer(self, request_id: str) -> None:
        self.kv.ack_transfer(request_id)
        # direct map, not a queue scan: the request may already have
        # finished and left running/waiting by the time the ACK lands
        req = self._active_transfer_reqs.pop(request_id, None)
        if req is not None:
            req.kv_transfer = KVTransferState.DONE

    def _free_request(self, req: Request) -> None:
        """Free pages unless a transfer is still ACTIVE (delayed free,
        reference: omni_ar_scheduler.py:473-546 — pinned pages survive)."""
        self._finished_ids.add(req.request_id)
        self.kv.free(req)
        self.kv.drop_park(req)

    def restore_failed(self, request_id: str, failed_entries: list,
                       keep_tokens: int) -> set[str]:
        """A queued tier restore came up short at engine drain time
        (payload vanished between match and fetch): unwind the
        never-injected entries (their nodes sit on garbage pages),
        keep the contiguous ``keep_tokens`` that are valid, and rewind
        the rest — the scheduler recomputes it as ordinary chunks next
        step.  The engine drops this step's now-misaligned
        ScheduledRequest before executing.  Returns the ids of OTHER
        requests that co-adopted a failed node in the same pass and
        were truncated along with it — their scheds must drop too."""
        _, req = self.find_request(request_id)
        if req is None:
            return set()
        co = self.kv.restore_failed_entries(req, failed_entries,
                                            keep_tokens)
        unwound: set[str] = set()
        for rid, keep in co.items():
            _, co_req = self.find_request(rid)
            if co_req is None:
                continue
            self.kv.restore_truncated(co_req, keep)
            unwound.add(rid)
        return unwound


class GenerationScheduler(ARScheduler):
    """One-shot generation fast path (reference:
    omni_generation_scheduler.py:33-261): the entire prompt is allocated and
    scheduled in one step; there is no decode phase — the model's forward
    produces the final (multimodal) output and the request finishes
    (:362-377)."""

    def schedule(self) -> SchedulerOutput:
        out = SchedulerOutput()
        while self.waiting and len(self.running) < self.config.max_num_seqs:
            req = self.waiting[0]
            n = req.num_prompt_tokens
            if not self.kv.can_allocate(req, n):
                break
            table = self.kv.allocate(req, n)
            if table is None:
                break
            slots = self.kv.slot_mapping(req, n)
            out.prefills.append(ScheduledRequest(
                request=req, num_new_tokens=n, slot_mapping=slots,
                block_table=table, start_pos=0,
            ))
            self.waiting.pop(0)
            req.status = RequestStatus.RUNNING
            self.running.append(req)
        return out

    def update_from_output(
        self,
        scheduler_output: SchedulerOutput,
        sampled: dict[str, int],
        kv_extracted_req_ids: Optional[set[str]] = None,
    ) -> list[Request]:
        finished: list[Request] = []
        for sched in scheduler_output.prefills:
            req = sched.request
            req.num_computed_tokens += sched.num_new_tokens
            req.status = RequestStatus.FINISHED_STOPPED
            finished.append(req)
            self.running.remove(req)
            self._free_request(req)
        return finished
