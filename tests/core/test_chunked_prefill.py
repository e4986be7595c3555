"""Chunked prefill: scheduler continuation chunks + token-identical engine
output vs unchunked (VERDICT r1 next-step #6; reference behavior inherited
from vLLM's scheduler by OmniARScheduler, core/sched/omni_ar_scheduler.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.helpers import unified_step
from vllm_omni_tpu.core.kv_cache_manager import KVCacheManager
from vllm_omni_tpu.core.scheduler import ARScheduler, SchedulerConfig
from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.ops.attention import attention_ref, flash_attention
from vllm_omni_tpu.request import Request, RequestStatus
from vllm_omni_tpu.sampling_params import SamplingParams


def _mk_req(rid, n, max_tokens=4):
    return Request(
        request_id=rid,
        prompt_token_ids=list(range(1, n + 1)),
        sampling_params=SamplingParams(temperature=0.0, max_tokens=max_tokens),
        eos_token_id=None,
    )


# ---------------------------------------------------------------- op level
def test_flash_attention_q_offsets_matches_ref():
    key = jax.random.PRNGKey(0)
    b, sq, skv, h, hkv, d = 3, 8, 32, 4, 2, 16
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, skv, hkv, d), jnp.float32)
    v = jax.random.normal(kv_, (b, skv, hkv, d), jnp.float32)
    offsets = jnp.asarray([0, 5, 17], jnp.int32)
    ctx = offsets + sq
    kv_mask = (jnp.arange(skv)[None, :] < ctx[:, None]).astype(jnp.int32)

    got = flash_attention(q, k, v, causal=True, kv_mask=kv_mask,
                          q_offsets=offsets)
    want = attention_ref(q, k, v, causal=True, kv_mask=kv_mask,
                         q_offsets=offsets)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_q_offsets_pallas_kernel():
    # exercise the Pallas kernel path explicitly (interpret mode on CPU)
    key = jax.random.PRNGKey(1)
    b, sq, skv, h, hkv, d = 2, 16, 64, 4, 2, 32
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, skv, hkv, d), jnp.float32)
    v = jax.random.normal(kv_, (b, skv, hkv, d), jnp.float32)
    offsets = jnp.asarray([3, 40], jnp.int32)
    ctx = offsets + sq
    kv_mask = (jnp.arange(skv)[None, :] < ctx[:, None]).astype(jnp.int32)
    got = flash_attention(q, k, v, causal=True, kv_mask=kv_mask,
                          q_offsets=offsets, use_pallas=True,
                          block_q=8, block_k=16)
    want = attention_ref(q, k, v, causal=True, kv_mask=kv_mask,
                         q_offsets=offsets)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------- model level
def test_chunked_forward_matches_full_prefill():
    cfg = tfm.TransformerConfig.tiny()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    from vllm_omni_tpu.ops.paged_attention import init_kv_cache

    page = 4
    prompt = list(np.random.default_rng(0).integers(1, 100, size=13))

    # full prefill oracle
    caches_a = init_kv_cache(cfg.num_layers, 16, page, cfg.num_kv_heads,
                             cfg.head_dim, jnp.float32)
    pages = [0, 1, 2, 3]  # cover 16 slots
    (full_hidden,), caches_a = unified_step(
        params, cfg, caches_a, [(prompt, 0, pages)], page)

    # chunked: 6 + 7, the second chunk attends the first's cached KV
    caches_b = init_kv_cache(cfg.num_layers, 16, page, cfg.num_kv_heads,
                             cfg.head_dim, jnp.float32)
    c1 = 6
    _, caches_b = unified_step(
        params, cfg, caches_b, [(prompt[:c1], 0, pages)], page)
    (h2,), caches_b = unified_step(
        params, cfg, caches_b, [(prompt[c1:], c1, pages)], page)

    np.testing.assert_allclose(
        np.asarray(h2), np.asarray(full_hidden[c1:]),
        atol=1e-4, rtol=1e-4)
    # caches identical too
    for (ka, va), (kb, vb) in zip(caches_a, caches_b):
        np.testing.assert_allclose(np.asarray(ka), np.asarray(kb), atol=1e-5)
        np.testing.assert_allclose(np.asarray(va), np.asarray(vb), atol=1e-5)


# --------------------------------------------------------------- scheduler
def test_scheduler_chunks_long_prompt():
    kv = KVCacheManager(num_pages=64, page_size=4)
    sched = ARScheduler(SchedulerConfig(
        max_num_seqs=4, max_num_batched_tokens=16, max_model_len=256,
        enable_chunked_prefill=True), kv)
    req = _mk_req("r0", 40)
    sched.add_request(req)

    out1 = sched.schedule()
    assert len(out1.prefills) == 1
    assert out1.prefills[0].num_new_tokens == 16
    assert out1.prefills[0].start_pos == 0
    finished = sched.update_from_output(out1, {})
    assert not finished and req.num_computed_tokens == 16

    out2 = sched.schedule()
    assert out2.prefills[0].start_pos == 16
    assert out2.prefills[0].num_new_tokens == 16
    sched.update_from_output(out2, {})

    out3 = sched.schedule()
    assert out3.prefills[0].start_pos == 32
    assert out3.prefills[0].num_new_tokens == 8
    # final chunk: the runner samples; simulate it
    finished = sched.update_from_output(out3, {"r0": 7})
    assert req.num_computed_tokens == 40
    assert req.output_token_ids == [7]


def test_scheduler_mid_prefill_preemption_recomputes():
    kv = KVCacheManager(num_pages=8, page_size=4)  # 32 slots total
    sched = ARScheduler(SchedulerConfig(
        max_num_seqs=4, max_num_batched_tokens=8, max_model_len=64,
        enable_chunked_prefill=True), kv)
    a = _mk_req("a", 24)
    sched.add_request(a)
    out = sched.schedule()
    assert out.prefills[0].num_new_tokens == 8
    sched.update_from_output(out, {})
    # burn the pool so the continuation cannot fit: add a second request
    # that grabs the remaining pages
    b = _mk_req("b", 8)
    sched.add_request(b)
    out = sched.schedule()
    # a continues (8 more), b admitted if pages remain
    sched.update_from_output(out, {})
    # force page exhaustion for a's final chunk by shrinking free pool
    while kv.num_free_pages:
        kv._free.pop()
    out = sched.schedule()
    # a (head of running) cannot fit its chunk: preempts b first, else self
    assert a.num_computed_tokens in (0, 16, 24) or a.status is \
        RequestStatus.PREEMPTED


# ------------------------------------------------------------- engine e2e
@pytest.mark.parametrize("budget", [8, 16])
def test_engine_chunked_token_identical(budget):
    cfg = tfm.TransformerConfig.tiny()
    params = tfm.init_params(jax.random.PRNGKey(2), cfg, jnp.float32)
    prompt = list(np.random.default_rng(3).integers(1, 100, size=37))
    sp = SamplingParams(temperature=0.0, max_tokens=8)

    def run(chunked, btok):
        eng = LLMEngine(params, cfg, EngineConfig(
            num_pages=64, page_size=4, max_model_len=128, max_num_seqs=4,
            max_num_batched_tokens=btok, dtype=jnp.float32, seed=0,
            enable_chunked_prefill=chunked,
        ))
        outs = eng.generate([prompt], sp)
        assert outs[0].finished and not outs[0].is_error, \
            outs[0].error_message
        return outs[0].outputs[0].token_ids

    want = run(False, 2048)
    got = run(True, budget)
    assert got == want


def test_engine_chunked_multi_request_parity():
    cfg = tfm.TransformerConfig.tiny()
    params = tfm.init_params(jax.random.PRNGKey(4), cfg, jnp.float32)
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(1, 100, size=n)) for n in (30, 5, 21)]
    sp = SamplingParams(temperature=0.0, max_tokens=6)

    def run(chunked, btok):
        eng = LLMEngine(params, cfg, EngineConfig(
            num_pages=64, page_size=4, max_model_len=128, max_num_seqs=4,
            max_num_batched_tokens=btok, dtype=jnp.float32, seed=0,
            enable_chunked_prefill=chunked,
        ))
        outs = eng.generate(prompts, sp)
        return [o.outputs[0].token_ids for o in outs]

    assert run(True, 16) == run(False, 2048)


def _resume_after_preempt(prefix_caching: bool):
    kv = KVCacheManager(num_pages=64, page_size=4,
                        enable_prefix_caching=prefix_caching)
    sched = ARScheduler(SchedulerConfig(
        max_num_seqs=4, max_num_batched_tokens=8, max_model_len=256,
        enable_chunked_prefill=True), kv)
    req = _mk_req("r", 10, max_tokens=32)
    sched.add_request(req)
    # prefill in chunks of 8, then decode a few tokens
    out = sched.schedule(); sched.update_from_output(out, {})
    out = sched.schedule(); sched.update_from_output(out, {"r": 1})
    for t in (2, 3, 4, 5, 6):
        out = sched.schedule()
        assert len(out.decodes) == 1
        sched.update_from_output(out, {"r": t})
    assert req.num_tokens == 16
    # preempt: pages free (registering full prompt pages when caching)
    sched._preempt(req)
    assert req.num_computed_tokens == 0
    return sched, req


def test_resumed_request_chunks_generated_suffix():
    """A preempted request recomputes prompt + generated tokens in chunks,
    not one decode step at a time (code-review finding: the continuation
    branch must gate on num_tokens, not num_prompt_tokens)."""
    sched, req = _resume_after_preempt(prefix_caching=False)
    # resume: admission chunk of 8, then the *running* branch must chunk
    # the remaining 8 (which includes generated tokens) in ONE prefill
    out = sched.schedule()
    assert len(out.prefills) == 1 and out.prefills[0].num_new_tokens == 8
    sched.update_from_output(out, {})
    out = sched.schedule()
    assert len(out.prefills) == 1 and len(out.decodes) == 0
    assert out.prefills[0].start_pos == 8
    # chunk covers through num_tokens-1... the final recompute chunk ends
    # at num_tokens (16), whose last row resamples the next token
    assert out.prefills[0].num_new_tokens == 8


def test_resumed_request_reuses_cached_prefix():
    """With automatic prefix caching, preemption registers the full
    prompt pages; resume adopts them and recomputes ONLY the
    tail (prompt remainder + generated tokens) in one chunk."""
    sched, req = _resume_after_preempt(prefix_caching=True)
    out = sched.schedule()
    # 8 prompt tokens rode the cache: only tokens 8..15 recompute
    assert req.num_computed_tokens == 8
    assert len(out.prefills) == 1
    assert out.prefills[0].start_pos == 8
    assert out.prefills[0].num_new_tokens == 8
    assert sched.kv.prefix_hit_tokens == 8
    sched.update_from_output(out, {"r": 7})
    assert req.num_computed_tokens == 16


def test_intake_accepts_long_prompt_when_chunked():
    kv = KVCacheManager(num_pages=64, page_size=4)
    sched = ARScheduler(SchedulerConfig(
        max_num_seqs=4, max_num_batched_tokens=16, max_model_len=256,
        enable_chunked_prefill=True), kv)
    req = _mk_req("long", 100)
    sched.add_request(req)
    assert req.status is RequestStatus.WAITING


# ------------------------------------------- chunks sized in packed rows
def _contract(max_model_len, token_block=8):
    """The packing contract an engine would hand over: the runner's
    token block and its bucket line (powers of two up to the cap)."""
    from vllm_omni_tpu.core.scheduler import PackingContract
    from vllm_omni_tpu.worker.model_runner import _make_buckets

    return PackingContract(token_block, _make_buckets(16, max_model_len))


def _packed_sched(n_decodes, *, max_num_seqs, budget, max_model_len,
                  k=0, chunked=True):
    """A scheduler under a packing contract with ``n_decodes`` requests
    already decoding (verify rows of 1 + k where ``k``)."""
    kv = KVCacheManager(num_pages=4096, page_size=16)
    sched = ARScheduler(SchedulerConfig(
        max_num_seqs=max_num_seqs, max_num_batched_tokens=budget,
        max_model_len=max_model_len, enable_chunked_prefill=chunked,
        num_speculative_tokens=k), kv)
    sched.packing = _contract(max_model_len)
    decoders = [_mk_req(f"d{i}", 3, max_tokens=10_000)
                for i in range(n_decodes)]
    for r in decoders:
        sched.add_request(r)
    while any(not r.output_token_ids for r in decoders):
        out = sched.schedule()
        sched.update_from_output(out, {
            s.request.request_id: 1 for s in out.decodes + out.prefills
            if not s.is_prefill or s.samples_final})
    for r in decoders:
        r.spec_draft_tokens = [2] * k
    return sched


def _step_rows(sched, out):
    """Packed rows of a step, as ARModelRunner._assemble_unified counts."""
    return sum(sched.packing.rows(s.num_new_tokens)
               for s in out.decodes + out.prefills)


@pytest.mark.parametrize("n", [0, 1, 9, 24, 31, 62, 63])
def test_chunk_fills_the_bucket_the_step_needs(n):
    """The benchmark's limits (64 seats, 2048 tokens a step): a long
    prompt beside ``n`` decode rows never opens the 4096-row bucket, and
    its chunks cover it once, in order."""
    sched = _packed_sched(n, max_num_seqs=64, budget=2048,
                          max_model_len=8448)
    doc = _mk_req("doc", 5000)
    sched.add_request(doc)
    covered, trimmed, by_tokens = [], 0, 0
    while doc.num_computed_tokens < 5000:
        out = sched.schedule()
        assert len(out.decodes) == n, "a decode row lost its seat"
        assert len(out.prefills) == 1
        chunk = out.prefills[0]
        assert _step_rows(sched, out) <= 2048
        assert not out.bucket_raised
        assert chunk.start_pos == (covered[-1][1] if covered else 0)
        covered.append((chunk.start_pos,
                        chunk.start_pos + chunk.num_new_tokens))
        trimmed += out.chunk_tokens_trimmed
        # what the token budget alone would have given the chunk
        by_tokens += min(5000 - chunk.start_pos, 2048 - n)
        if covered[-1][1] < 5000:
            # a whole chunk takes every row the decode rows leave
            assert chunk.num_new_tokens == 2048 - 8 * n
        sampled = {s.request.request_id: 1 for s in out.decodes}
        if covered[-1][1] == 5000:
            sampled["doc"] = 1
        sched.update_from_output(out, sampled)
    assert covered[-1][1] == 5000
    # 7 tokens a decode row and whole chunk: what fitting the bucket costs
    assert trimmed == by_tokens - 5000
    assert trimmed >= 7 * n * (len(covered) - 2)


@pytest.mark.parametrize("n", [9, 12, 15])
def test_decode_rows_past_the_budget_keep_their_seats(n):
    """8 x decodes > max_num_batched_tokens: every decode row is still
    scheduled, the chunk gets at least a token block, and the step runs
    in the bucket the decode rows alone set."""
    sched = _packed_sched(n, max_num_seqs=16, budget=64, max_model_len=512)
    sched.add_request(_mk_req("doc", 300))
    out = sched.schedule()
    assert len(out.decodes) == n and len(out.prefills) == 1
    assert out.prefills[0].num_new_tokens >= 8
    rows = _step_rows(sched, out)
    assert sched.packing.bucket(rows) == sched.packing.bucket(8 * n + 8)
    assert sched.packing.bucket(8 * n + 8) > sched.packing.bucket(64)
    assert out.bucket_raised


def test_verify_row_counts_at_its_aligned_size():
    """A verify row of 1 + k = 9 tokens holds two token blocks: three of
    them leave a 64-row step 16 rows, though its budget has 37 tokens."""
    sched = _packed_sched(3, max_num_seqs=8, budget=64, max_model_len=512,
                          k=8)
    sched.add_request(_mk_req("doc", 300))
    out = sched.schedule()
    assert [s.num_new_tokens for s in out.decodes] == [9, 9, 9]
    assert out.prefills[0].num_new_tokens == 16
    assert out.chunk_tokens_trimmed == 37 - 16
    assert _step_rows(sched, out) == 64


def test_chunk_ahead_of_a_decode_row_leaves_its_segment_free():
    """Classic order (no decodes-first sort): a chunking request that
    stands before the decode rows in the running list still leaves them
    their segments, and their tokens of the budget."""
    sched = _packed_sched(0, max_num_seqs=8, budget=64, max_model_len=512)
    doc = _mk_req("doc", 300)
    sched.add_request(doc)
    out = sched.schedule()
    sched.update_from_output(out, {})
    late = [_mk_req(f"l{i}", 3, max_tokens=100) for i in range(2)]
    for r in late:
        sched.add_request(r)
    # the doc's second chunk takes the budget: the late prompts wait
    out = sched.schedule()
    sched.update_from_output(out, {})
    assert [s.request.request_id for s in out.prefills] == ["doc"]
    # give them a step of their own by hand, behind the doc in running
    for r in late:
        sched.waiting.remove(r)
        r.status = RequestStatus.RUNNING
        sched.kv.allocate(r, 3)
        r.num_computed_tokens = 3
        r.output_token_ids.append(1)
        sched.running.append(r)
    assert sched.running[0] is doc
    out = sched.schedule()
    assert len(out.decodes) == 2
    assert out.prefills[0].num_new_tokens == 64 - 2 * 8
    assert _step_rows(sched, out) == 64


def test_whole_prompt_waits_for_a_segment_that_fits():
    """Chunked prefill off: a prompt is admitted whole only if its
    segment fits the step's bucket beside the decode rows, else it waits
    a step as it does when the token budget is short."""
    sched = _packed_sched(2, max_num_seqs=8, budget=64, max_model_len=512,
                          chunked=False)
    # 2 decode rows hold 16 rows: 48 left.  41 tokens fit the token
    # budget (62) and the bucket (48 rows)
    sched.add_request(_mk_req("fits", 41))
    sched.add_request(_mk_req("waits", 9))  # 16 rows: past the bucket
    out = sched.schedule()
    assert [s.request.request_id for s in out.prefills] == ["fits"]
    assert _step_rows(sched, out) == 64
    assert out.chunk_tokens_trimmed == 0  # deferred, not trimmed
    sched.update_from_output(
        out, {s.request.request_id: 1
              for s in out.decodes + out.prefills})
    out = sched.schedule()
    assert [s.request.request_id for s in out.prefills] == ["waits"]


def test_scheduler_without_a_runner_sizes_by_tokens():
    """No contract (every scheduler these tests build bare): a chunk is
    the token budget the decode rows leave, as before."""
    sched = _packed_sched(9, max_num_seqs=16, budget=64, max_model_len=512)
    sched.packing = None
    sched.add_request(_mk_req("doc", 300))
    out = sched.schedule()
    assert out.prefills[0].num_new_tokens == 64 - 9
    assert (out.chunk_tokens_trimmed, out.bucket_raised) == (0, False)


def test_runner_reach_and_scheduler_cap_are_one_rule():
    """The runner's warm-up reach is the scheduler's cap at a full house
    (core/scheduler.py packed_rows_needed): the benchmark's limits reach
    2048 rows and no further; seats past budget / 8 raise both."""
    from vllm_omni_tpu.core.scheduler import packed_rows_needed

    assert packed_rows_needed(2048, 63 * 8, 8) == 2048
    assert packed_rows_needed(2048, 31 * 8, 8) == 2048
    assert packed_rows_needed(2048, 300 * 8, 8) == 2408
    c = _contract(8448)
    assert c.step_cap(2048, 63 * 8) == 2048
    assert c.step_cap(2048, 300 * 8) == 4096
    assert c.step_cap(64, 0) == 64
