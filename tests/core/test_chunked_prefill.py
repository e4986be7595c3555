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
