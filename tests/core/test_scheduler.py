"""Scheduler behavioral contract (ports the reference's OmniARScheduler /
OmniGenerationScheduler semantics, core/sched/*.py)."""

from vllm_omni_tpu.core.kv_cache_manager import KVCacheManager
from vllm_omni_tpu.core.scheduler import (
    ARScheduler,
    GenerationScheduler,
    KVTransferConfig,
    SchedulerConfig,
)
from vllm_omni_tpu.request import KVTransferState, Request, RequestStatus
from vllm_omni_tpu.sampling_params import SamplingParams


def _mk(cfg=None, pages=64, page_size=4, cls=ARScheduler):
    cfg = cfg or SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64,
                                 max_model_len=64)
    return cls(cfg, KVCacheManager(pages, page_size))


def _req(rid, n=8, max_tokens=4, **kw):
    return Request(request_id=rid, prompt_token_ids=list(range(n)),
                   sampling_params=SamplingParams(max_tokens=max_tokens), **kw)


def test_prefill_then_decode_lifecycle():
    s = _mk()
    s.add_request(_req("a", n=8, max_tokens=2))
    out = s.schedule()
    assert len(out.prefills) == 1 and not out.decodes
    assert out.prefills[0].num_new_tokens == 8
    finished = s.update_from_output(out, {"a": 42})
    assert not finished
    req = s.running[0]
    assert req.output_token_ids == [42]
    assert req.num_computed_tokens == 8

    out2 = s.schedule()
    assert len(out2.decodes) == 1 and not out2.prefills
    d = out2.decodes[0]
    assert d.start_pos == 8 and d.num_new_tokens == 1
    finished = s.update_from_output(out2, {"a": 7})
    assert len(finished) == 1  # max_tokens=2 reached
    assert finished[0].status == RequestStatus.FINISHED_LENGTH
    assert not s.has_unfinished


def test_eos_stops():
    s = _mk()
    req = _req("a", n=4, max_tokens=10)
    req.eos_token_id = 99
    s.add_request(req)
    out = s.schedule()
    finished = s.update_from_output(out, {"a": 99})
    assert finished and finished[0].status == RequestStatus.FINISHED_STOPPED
    assert finished[0].finish_reason == "stop"


def test_token_budget_defers_waiting():
    cfg = SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=10,
                          max_model_len=64)
    s = _mk(cfg)
    s.add_request(_req("a", n=8))
    s.add_request(_req("b", n=8))  # doesn't fit in the same step
    out = s.schedule()
    assert len(out.prefills) == 1
    s.update_from_output(out, {"a": 1})
    out2 = s.schedule()
    # b prefills now, a decodes
    assert {sc.request.request_id for sc in out2.prefills} == {"b"}
    assert {sc.request.request_id for sc in out2.decodes} == {"a"}


def test_max_num_seqs_limit():
    cfg = SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=1024,
                          max_model_len=64)
    s = _mk(cfg)
    for rid in "abc":
        s.add_request(_req(rid, n=4))
    out = s.schedule()
    assert len(out.prefills) == 2
    assert len(s.waiting) == 1


def test_preemption_recompute_on_page_exhaustion():
    # 4 pages of 4 slots = 16 tokens total; two 8-token requests fill it,
    # the first decode token forces a preemption
    cfg = SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64,
                          max_model_len=64)
    s = _mk(cfg, pages=4, page_size=4)
    s.add_request(_req("a", n=8, max_tokens=8))
    s.add_request(_req("b", n=8, max_tokens=8))
    out = s.schedule()
    assert len(out.prefills) == 2
    s.update_from_output(out, {"a": 1, "b": 1})
    out2 = s.schedule()
    assert out2.preempted, "one request must be preempted on page exhaustion"
    victim = out2.preempted[0]
    assert victim.status == RequestStatus.PREEMPTED
    assert victim in s.waiting
    # the survivor still decoded
    assert len(out2.decodes) == 1
    # recompute policy, radix-tempered: the victim's in-flight progress
    # is discarded, but the radix index evicts DEEPEST-first, so the
    # victim's first prompt page survives the survivor's allocation and
    # is re-adopted at re-admission (the flat chained-hash map evicted
    # the chain head and restarted from 0 — docs/kv_cache.md)
    assert victim.num_computed_tokens == 4
    assert len(s.kv.block_table(victim.request_id)) == 1


def test_kv_transfer_trigger_on_prefill_finished():
    cfg = SchedulerConfig(
        max_num_seqs=4, max_num_batched_tokens=64, max_model_len=64,
        kv_transfer=KVTransferConfig(trigger="prefill_finished"),
    )
    s = _mk(cfg)
    s.add_request(_req("a", n=8, max_tokens=4))
    out = s.schedule()
    s.update_from_output(out, {"a": 5})
    req = s.running[0]
    assert req.kv_transfer == KVTransferState.ACTIVE
    # the transfer rides the *next* schedule() so the runner extracts at
    # the start of its step (reference: gpu_ar_model_runner.py:100-106)
    out2 = s.schedule()
    assert out2.kv_transfer_requests
    _, block_ids, seq_len = out2.kv_transfer_requests[0]
    # only computed tokens are in the cache (the sampled token's KV is
    # written next step)
    assert seq_len == 8
    assert len(block_ids) == 2  # ceil(8/4)
    # ACK frees the pin
    s.update_from_output(out2, {"a": 6}, kv_extracted_req_ids={"a"})
    assert req.kv_transfer == KVTransferState.DONE


def test_kv_transfer_special_token_trigger():
    cfg = SchedulerConfig(
        max_num_seqs=4, max_num_batched_tokens=64, max_model_len=64,
        kv_transfer=KVTransferConfig(trigger="special_token",
                                     special_token_id=77),
    )
    s = _mk(cfg)
    s.add_request(_req("a", n=4, max_tokens=8))
    out = s.schedule()
    s.update_from_output(out, {"a": 5})
    assert s.running[0].kv_transfer == KVTransferState.PENDING
    out2 = s.schedule()
    s.update_from_output(out2, {"a": 77})
    assert s.running[0].kv_transfer == KVTransferState.ACTIVE


def test_generation_scheduler_one_shot():
    s = _mk(cls=GenerationScheduler)
    s.add_request(_req("a", n=12))
    s.add_request(_req("b", n=6))
    out = s.schedule()
    assert len(out.prefills) == 2
    assert all(sc.num_new_tokens == sc.request.num_prompt_tokens
               for sc in out.prefills)
    finished = s.update_from_output(out, {})
    assert len(finished) == 2
    assert not s.has_unfinished
    # all pages returned
    assert s.kv.num_free_pages == 64


def test_unschedulable_prompt_rejected_at_intake():
    # budget 10 < prompt 12 with chunked prefill off -> intake error, not
    # an engine-starving waiting-queue pin
    cfg = SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=10,
                          max_model_len=64)
    s = _mk(cfg)
    s.add_request(_req("a", n=12))
    assert not s.has_unfinished
    errored = s.drain_errored()
    assert len(errored) == 1
    assert errored[0].status == RequestStatus.FINISHED_ERROR


def test_prompt_larger_than_kv_pool_rejected():
    cfg = SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64,
                          max_model_len=64)
    s = _mk(cfg, pages=2, page_size=4)  # pool holds 8 tokens
    s.add_request(_req("a", n=12))
    assert s.drain_errored()


def test_ack_after_finish_marks_done():
    cfg = SchedulerConfig(
        max_num_seqs=4, max_num_batched_tokens=64, max_model_len=64,
        kv_transfer=KVTransferConfig(trigger="prefill_finished"),
    )
    s = _mk(cfg)
    req = _req("a", n=4, max_tokens=1)
    s.add_request(req)
    out = s.schedule()
    finished = s.update_from_output(out, {"a": 5})  # finishes (max_tokens=1)
    assert finished and not s.has_unfinished
    assert req.kv_transfer == KVTransferState.ACTIVE
    # ACK lands after the request left running/waiting
    from vllm_omni_tpu.core.scheduler import SchedulerOutput
    s.update_from_output(SchedulerOutput(), {}, {"a"})
    assert req.kv_transfer == KVTransferState.DONE


def test_chunked_prefill_flag_accepted():
    # chunked prefill is implemented (tests/core/test_chunked_prefill.py);
    # the flag constructs a working scheduler
    cfg = SchedulerConfig(enable_chunked_prefill=True)
    s = _mk(cfg)
    assert s.config.enable_chunked_prefill


def test_abort():
    s = _mk()
    s.add_request(_req("a", n=4))
    s.abort_request("a")
    assert not s.has_unfinished


def test_spec_verify_in_flight_holds_request():
    """Async spec pipelining: while a k+1-candidate verify dispatch is
    in flight (num_inflight_tokens > 1) the request's next KV position
    is unknown — schedule() must HOLD it (no row emitted) until the
    lagged retire lands; plain decode rows (one in-flight token) keep
    pipelining ahead."""
    cfg = SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64,
                          max_model_len=64, num_speculative_tokens=3)
    s = _mk(cfg)
    s.add_request(_req("a", n=4, max_tokens=16))
    out = s.schedule()
    s.note_async_dispatch(out)
    retired = s.update_from_async_retire(out, {"a": 1})
    assert not retired
    req = s.running[0]
    req.spec_draft_tokens = [5, 6, 7]

    out2 = s.schedule()          # verify row: 1 + 3 candidates
    assert out2.decodes and out2.decodes[0].num_new_tokens == 4
    s.note_async_dispatch(out2)
    assert req.num_inflight_tokens == 4

    held = s.schedule()          # verify in flight -> held, not rescheduled
    assert held.num_scheduled == 0
    assert req in s.running

    # lagged retire: 2 of 4 candidates accepted -> rewind keeps exactly
    # the accepted prefix and the request schedules again
    s.update_from_async_retire(out2, {"a": [2, 3]})
    assert req.num_inflight_tokens == 0
    assert req.output_token_ids == [1, 2, 3]
    assert req.num_computed_tokens == req.num_tokens - 1
    req.spec_draft_tokens = []
    out3 = s.schedule()
    assert out3.decodes and out3.decodes[0].num_new_tokens == 1
    assert out3.decodes[0].start_pos == req.num_computed_tokens


def test_preemption_and_rejection_counters():
    """Lifetime counters surfaced by /metrics (observability PR)."""
    s = _mk()
    assert s.num_preemptions == 0 and s.num_rejections == 0
    s.add_request(_req("too-long", n=100))  # > max_model_len -> reject
    assert s.num_rejections == 1
    s._preempt(_req("victim", n=4))
    assert s.num_preemptions == 1


def test_restored_park_resumes_as_decode():
    """Resume-as-decode: a restored preemption victim whose only
    outstanding position is the sampling one re-enters through the
    DECODE path — the executable the uninterrupted stream would have
    run — not a 1-token prefill chunk (the two agree only to the last
    ULP, which flips greedy argmaxes on near-flat logits;
    docs/kv_cache.md)."""
    import numpy as np

    from vllm_omni_tpu.kvcache.policy import OffloadPolicy
    from vllm_omni_tpu.kvcache.tiers import TieredKVStore

    cfg = SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64,
                          max_model_len=64, kv_offload=True)
    kv = KVCacheManager(4, 4, enable_prefix_caching=False,
                        tiers=TieredKVStore(),
                        policy=OffloadPolicy(mode="always"))
    s = ARScheduler(cfg, kv)
    s.add_request(_req("a", n=8, max_tokens=2))
    s.add_request(_req("b", n=8, max_tokens=8))
    out = s.schedule()          # both prefill: 2 pages each, pool full
    assert len(out.prefills) == 2
    s.update_from_output(out, {"a": 1, "b": 1})

    out2 = s.schedule()         # a's decode page preempts b -> parked
    assert out2.preempted and out2.preempted[0].request_id == "b"
    victim = out2.preempted[0]
    assert victim.additional_information.get("_parked_len") == 8
    # simulate the engine's same-step extraction drain: the payload
    # lands in the host tier and the in-flight marker clears
    offloads, _ = kv.take_pending_moves()
    parks = [o for o in offloads if o.key.endswith(victim.request_id)]
    assert parks, "preemption with kv_offload must queue a park"
    for o in parks:
        kv.tiers.put(o.key, [(np.zeros(2, np.float32),
                              np.zeros(2, np.float32))])
        kv.note_park_extracted(o.key)
    s.update_from_output(out2, {"a": 2})  # a finishes -> pages free

    out3 = s.schedule()         # b restores; 1 token outstanding
    assert not out3.prefills, \
        "restored victim must not re-enter through the prefill path"
    assert [d.request.request_id for d in out3.decodes] == ["b"]
    d = out3.decodes[0]
    assert d.num_new_tokens == 1 and len(d.slot_mapping) == 1
    assert d.start_pos == victim.num_computed_tokens == 8
    assert kv.restored_tokens == 8
    # the resumed row continues its stream like any running decode
    s.update_from_output(out3, {"b": 2})
    assert victim.output_token_ids == [1, 2]
    assert victim.status == RequestStatus.RUNNING


def test_parked_payload_lost_closes_park_interval():
    """Payload-lost recompute goes through drop_park: the host-tier
    page·second interval (per-tenant attribution, metrics/
    attribution.py) stops at the shed instead of accruing phantom
    residency through the request's whole recompute+decode life."""
    from vllm_omni_tpu.kvcache.policy import OffloadPolicy
    from vllm_omni_tpu.kvcache.tiers import TieredKVStore

    cfg = SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64,
                          max_model_len=64, kv_offload=True)
    kv = KVCacheManager(4, 4, enable_prefix_caching=False,
                        tiers=TieredKVStore(),
                        policy=OffloadPolicy(mode="always"))
    s = ARScheduler(cfg, kv)
    s.add_request(_req("a", n=8, max_tokens=2))
    s.add_request(_req("b", n=8, max_tokens=8))
    out = s.schedule()
    s.update_from_output(out, {"a": 1, "b": 1})
    out2 = s.schedule()         # a's decode page preempts b -> parked
    victim = out2.preempted[0]
    assert victim.request_id == "b"
    # extraction drains, but the payload never lands in the host tier
    # (shed before the restore): parked_available stays False
    offloads, _ = kv.take_pending_moves()
    for o in offloads:
        if o.key.endswith(victim.request_id):
            kv.note_park_extracted(o.key)
    s.update_from_output(out2, {"a": 2})  # a finishes -> pages free
    assert victim.request_id in kv._park_time
    out3 = s.schedule()         # payload lost -> full recompute
    assert [p.request.request_id for p in out3.prefills] == ["b"]
    # the park interval is CLOSED and the park marker is gone
    assert victim.request_id not in kv._park_time
    assert "_parked_len" not in victim.additional_information
