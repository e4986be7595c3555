"""Engine request type + lifecycle status.

Mirrors the behavioral surface of the reference's ``OmniRequest``
(reference: vllm_omni/request.py:14 — adds prompt_embeds,
additional_information, external_req_id on top of vLLM's Request) and the
``RequestStatus`` extension with WAITING_FOR_CHUNK
(reference: vllm_omni/patch.py:21-41).

Host-side bookkeeping only — nothing here touches jax.  Device-side state
(KV pages, sampler state) is owned by the KV-cache manager and model runner.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from vllm_omni_tpu.sampling_params import SamplingParams


class RequestStatus(enum.IntEnum):
    # mirrors the reference's extended enum; WAITING_FOR_CHUNK is the
    # async-chunk streaming state added by patch.py:26-41
    WAITING_FOR_CHUNK = -1
    WAITING = 0
    RUNNING = 1
    PREEMPTED = 2
    FINISHED_STOPPED = 3
    FINISHED_LENGTH = 4
    FINISHED_ABORTED = 5
    FINISHED_ERROR = 6

    @property
    def is_finished(self) -> bool:
        return self >= RequestStatus.FINISHED_STOPPED


FINISH_REASON = {
    RequestStatus.FINISHED_STOPPED: "stop",
    RequestStatus.FINISHED_LENGTH: "length",
    RequestStatus.FINISHED_ABORTED: "abort",
    RequestStatus.FINISHED_ERROR: "error",
}


class KVTransferState(enum.Enum):
    """Cross-stage KV-transfer lifecycle of one request (reference:
    core/sched/omni_ar_scheduler.py:84-136 trigger + :444-546 delayed free)."""

    NONE = "none"          # no transfer configured
    PENDING = "pending"    # trigger criteria not yet met
    ACTIVE = "active"      # triggered; blocks pinned until extraction ACK
    DONE = "done"          # runner ACKed extraction; blocks may be freed


@dataclass
class Request:
    request_id: str
    prompt_token_ids: list[int]
    sampling_params: SamplingParams = field(default_factory=SamplingParams)
    # single id or a list (multi-eos checkpoints stop on any)
    eos_token_id: Optional[int | list[int]] = None
    arrival_time: float = 0.0
    # duration clock twin of arrival_time: TTFT/queue-wait spans are
    # computed monotonic-to-monotonic (an NTP step mid-request must not
    # corrupt latency histograms); the wall-clock stamp above stays for
    # logs and trace timestamps only
    arrival_mono: float = 0.0
    # omni extensions (reference: request.py:14)
    prompt_embeds: Optional[np.ndarray] = None      # [S, hidden]
    additional_information: dict[str, Any] = field(default_factory=dict)
    external_req_id: Optional[str] = None
    # multimodal 3D-RoPE positions for the prompt ([3, S_prompt]) and the
    # generated-token delta (position of token p = p + delta); computed by
    # models/common/mrope.compute_mrope_positions (reference: mrope.py:25)
    mrope_positions: Optional[np.ndarray] = None
    mrope_delta: int = 0
    # deepstack multiscale visual features as sparse spans:
    # [(offset, [n_deep, T_item, hidden])] covering each visual item's
    # prompt positions; level i is added to the hidden states after
    # decoder layer i (reference: Qwen3-Omni thinker deepstack,
    # qwen3_omni_moe_thinker.py:177-178)
    deepstack_embeds: Optional[list] = None

    # end-to-end deadline as a monotonic expiry on THIS process's clock
    # (resilience/deadline.py: the orchestrator ships REMAINING budget
    # across process boundaries; each engine converts it back to its own
    # clock).  None = no deadline.  Enforced at scheduler admission and
    # on every engine step.
    deadline_ts: Optional[float] = None
    # the request asked to be streamed: every step that appends tokens to
    # it hands them over as a partial output (engine/stream.py), where a
    # request that did not ask yields its one final output and nothing
    # before it
    stream: bool = False

    # ----- mutable engine state -----
    status: RequestStatus = RequestStatus.WAITING
    output_token_ids: list[int] = field(default_factory=list)
    # async pipelined engine: tokens sampled by a dispatched-but-not-yet-
    # retired step (device-resident, not in output_token_ids yet).  The
    # scheduler counts them when computing the decode remainder so it can
    # schedule the NEXT step before the token value reaches the host;
    # retire decrements, preemption/abort resets (the in-flight token is
    # discarded and greedily re-derived on recompute).
    num_inflight_tokens: int = 0
    # bumped on every preemption: a lagged async retire consumes its
    # token only when the generation recorded at dispatch still matches,
    # so a preempt-and-readmit while a step was in flight (possible
    # under unified batching, where waiting requests join pipelined
    # steps) can never resurrect the discarded token
    async_generation: int = 0
    # per-output-token logprob entries when sampling_params.logprobs is
    # set: {"logprob": float, "top_ids": [...], "top_logprobs": [...]}
    # (spec-decode multi-accept steps skip entries — the verify path
    # has no per-token sampling distribution to report)
    output_logprobs: list = field(default_factory=list)
    # of the two lists above, the entries a partial output has already
    # handed over (engine/stream.py); a preemption keeps both lists, so
    # neither count ever moves back
    num_streamed: int = 0
    num_streamed_logprobs: int = 0
    num_computed_tokens: int = 0
    kv_transfer: KVTransferState = KVTransferState.NONE
    # block-id snapshot taken at transfer trigger, truncated to seq len
    # (reference: omni_ar_scheduler.py:553-594)
    kv_transfer_block_ids: Optional[list[int]] = None
    kv_transfer_seq_len: int = 0
    multimodal_output: dict[str, Any] = field(default_factory=dict)
    # speculative-decode draft tokens proposed by the MTP head after the
    # last verified step (reference: talker MTP code predictor,
    # models/qwen3_omni/qwen3_omni_moe_code_predictor_mtp.py); consumed by
    # the next decode step's verify forward
    spec_draft_tokens: list[int] = field(default_factory=list)
    # streaming (async_chunk) intake: the prompt may still GROW via
    # engine.append_prompt_chunk — prefill chunks run as they arrive and
    # sampling is held until the final chunk lands (reference:
    # WAITING_FOR_CHUNK + OmniChunkTransferAdapter,
    # transfer_adapter/chunk_transfer_adapter.py:19)
    awaiting_chunks: bool = False
    # hidden states destined for the next stage (pooler_output payloads,
    # reference: gpu_ar_model_runner.py:525-568)
    pooled_hidden: Optional[np.ndarray] = None

    @property
    def tenant(self) -> str:
        """Multi-tenant metrics label, plumbed from request metadata
        (OpenAI header ``x-omni-tenant`` ->
        additional_information["tenant"]); "default" when absent.
        CLIENT input: sanitized to a bounded safe charset before it
        can reach a metrics label or ledger key."""
        from vllm_omni_tpu.metrics.stats import sanitize_tenant

        return sanitize_tenant(self.additional_information.get("tenant"))

    # lazily cached sanitized priority (the WFQ scheduler reads it in
    # per-schedule loops; re-parsing the raw header per access would be
    # avoidable hot-path work)
    _priority_cache: Optional[int] = field(default=None, repr=False)

    @property
    def priority(self) -> int:
        """Weighted-fair-queueing weight, plumbed from request metadata
        (OpenAI header ``x-omni-priority`` ->
        additional_information["priority"]); the neutral weight when
        absent.  CLIENT input: clamped to the bounded priority range
        exactly like the tenant label is sanitized.  Cached on first
        read — metadata is fixed by the time scheduling reads it."""
        if self._priority_cache is None:
            from vllm_omni_tpu.metrics.stats import sanitize_priority

            self._priority_cache = sanitize_priority(
                self.additional_information.get("priority"))
        return self._priority_cache

    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_token_ids)

    @property
    def num_tokens(self) -> int:
        return len(self.prompt_token_ids) + len(self.output_token_ids)

    @property
    def all_token_ids(self) -> list[int]:
        return list(self.prompt_token_ids) + list(self.output_token_ids)

    @property
    def is_finished(self) -> bool:
        return self.status.is_finished

    @property
    def finish_reason(self) -> Optional[str]:
        return FINISH_REASON.get(self.status)

    def append_output_token(self, token_id: int) -> None:
        self.output_token_ids.append(token_id)

    def check_stop(self) -> bool:
        """Apply finish criteria after a new token; returns True if the
        request just finished (reference finish logic lives in vLLM's
        scheduler update_from_output, extended at omni_ar_scheduler.py:193)."""
        sp = self.sampling_params
        n_out = len(self.output_token_ids)
        if n_out == 0:
            return False
        last = self.output_token_ids[-1]
        if n_out >= sp.min_tokens:
            eos = self.eos_token_id
            eos_hit = (last in eos if isinstance(eos, (list, tuple))
                       else last == eos) if eos is not None else False
            if not sp.ignore_eos and eos_hit:
                self.status = RequestStatus.FINISHED_STOPPED
                return True
            if last in sp.stop_token_ids:
                self.status = RequestStatus.FINISHED_STOPPED
                return True
        if n_out >= sp.max_tokens:
            self.status = RequestStatus.FINISHED_LENGTH
            return True
        return False
