"""Async pipelined engine step: dispatch step N, do step N-1's host work
while the device computes (device-resident sampled tokens feed the next
dispatch).  Numerics contract: greedy async output is IDENTICAL to sync
(same forward, same argmax — only the host readback lags one step).
The EOS/stop hazard of scheduling ahead of token knowledge is the
one-step overshoot: its dispatch is discarded and the speculative
KV-accounting advance rewound (core/scheduler.py update_from_async_retire).
"""

import time

import jax
import jax.numpy as jnp
import pytest

from vllm_omni_tpu.engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.sampling_params import SamplingParams


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tfm.TransformerConfig.tiny(vocab_size=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return params, cfg


def _engine(params, cfg, **kw):
    # the synchronous step unless a test asks for the pipelined one: the
    # sync side of every oracle here is built by this default
    defaults = dict(num_pages=64, page_size=4, max_model_len=128,
                    max_num_seqs=4, dtype=jnp.float32,
                    async_scheduling=False)
    defaults.update(kw)
    return LLMEngine(params, cfg, EngineConfig(**defaults))


PROMPTS = [[1, 5, 9, 2, 7], [3, 3, 8], [11, 4, 6, 1, 2, 9, 5]]
GREEDY = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)


def _spy_dispatch(eng):
    """Count pipelined dispatches without changing behavior."""
    calls = []
    orig = eng.runner.dispatch_decode

    def spy(scheds, prev=None):
        calls.append(len(scheds))
        return orig(scheds, prev)

    eng.runner.dispatch_decode = spy
    return calls


# --------------------------------------------------------- equality oracle
def test_async_greedy_matches_sync(tiny_model):
    params, cfg = tiny_model
    base = _engine(params, cfg).generate(PROMPTS, GREEDY)
    eng = _engine(params, cfg, async_scheduling=True)
    calls = _spy_dispatch(eng)
    outs = eng.generate(PROMPTS, GREEDY)
    for b, m in zip(base, outs):
        assert m.outputs[0].token_ids == b.outputs[0].token_ids
        assert len(m.outputs[0].token_ids) == 12
    assert calls, "async engine never took the pipelined path"


@pytest.mark.slow  # fast siblings: test_async_greedy_matches_sync pins
#                    async/sync parity, oracle fixture [staggered_mixed]
#                    pins staggered-wave streams bit-exactly
def test_async_greedy_matches_sync_mixed_waves(tiny_model):
    """Staggered arrivals force repeated prefill (sync fallback) /
    decode (pipelined) transitions — the pipeline must drain and refill
    without corrupting any stream."""
    params, cfg = tiny_model

    def run(async_mode):
        eng = _engine(params, cfg, async_scheduling=async_mode)
        sp = SamplingParams(temperature=0.0, max_tokens=10,
                            ignore_eos=True)
        outs = {}
        eng.add_request(PROMPTS[0], sp, request_id="r0")
        eng.add_request(PROMPTS[1], sp, request_id="r1")
        steps = 0
        added = False
        while eng.has_unfinished_requests:
            for o in eng.step():
                outs[o.request_id] = o.outputs[0].token_ids
            steps += 1
            if steps == 3 and not added:
                # a mid-stream arrival while decodes are in flight
                eng.add_request(PROMPTS[2], sp, request_id="r2")
                added = True
        return outs

    sync, asy = run(False), run(True)
    assert set(sync) == set(asy) == {"r0", "r1", "r2"}
    for rid in sync:
        assert asy[rid] == sync[rid], rid


def test_async_sampled_seeded_reproducible(tiny_model):
    """Seeded temperature sampling through the on-device sampler is
    reproducible run-to-run (the stream may differ from sync mode — the
    step counter advances differently — but must be self-consistent)."""
    params, cfg = tiny_model
    sp = SamplingParams(temperature=0.9, seed=7, max_tokens=8,
                        ignore_eos=True)
    a = _engine(params, cfg, async_scheduling=True).generate(PROMPTS, sp)
    b = _engine(params, cfg, async_scheduling=True).generate(PROMPTS, sp)
    for x, y in zip(a, b):
        assert x.outputs[0].token_ids == y.outputs[0].token_ids


# --------------------------------------------------- stop one-step lag
def test_async_eos_one_step_lag_rollback(tiny_model):
    """A stop token detected one step late: the overshoot dispatch is
    discarded (no ghost token in the output) and the KV accounting is
    rewound — the pool ends fully free, exactly like sync mode."""
    params, cfg = tiny_model
    probe = _engine(params, cfg).generate([PROMPTS[0]], GREEDY)
    toks = probe[0].outputs[0].token_ids
    stop = toks[5]
    first_hit = toks.index(stop)
    sp_stop = SamplingParams(temperature=0.0, max_tokens=12,
                             stop_token_ids=[stop])

    eng = _engine(params, cfg, async_scheduling=True,
                  enable_prefix_caching=False)
    finished_reqs = []
    orig = eng.scheduler.update_from_async_retire

    def spy(sched_out, sampled):
        done = orig(sched_out, sampled)
        finished_reqs.extend(done)
        return done

    eng.scheduler.update_from_async_retire = spy
    out = eng.generate([PROMPTS[0]], sp_stop)
    got = out[0].outputs[0].token_ids
    assert got == toks[: first_hit + 1], "ghost token past the stop"
    assert out[0].outputs[0].finish_reason == "stop"
    kv = eng.scheduler.kv
    assert kv.num_free_pages == kv.num_pages, "KV pages leaked"
    # the speculative advance of the discarded overshoot was rewound:
    # computed positions match sync semantics (all tokens but the last)
    assert finished_reqs, "stop never surfaced through the async retire"
    req = finished_reqs[-1]
    # the final overshoot drains as soon as the scheduler empties — no
    # dangling in-flight slot, and the speculative advance was rewound
    assert eng._inflight is None
    assert req.num_inflight_tokens == 0
    assert req.num_computed_tokens == req.num_tokens - 1


@pytest.mark.slow  # fast siblings: test_async_eos_one_step_lag_rollback
# pins the lagged retire never overshoots; sync max_tokens exactness
# lives in test_llm_engine.py
def test_async_max_tokens_exact(tiny_model):
    """max_tokens is enforced at the lagged retire — never overshot in
    the emitted stream."""
    params, cfg = tiny_model
    for n in (1, 2, 7):
        sp = SamplingParams(temperature=0.0, max_tokens=n,
                            ignore_eos=True)
        outs = _engine(params, cfg, async_scheduling=True).generate(
            PROMPTS, sp)
        assert all(len(o.outputs[0].token_ids) == n for o in outs)


def test_async_max_model_len_boundary(tiny_model):
    """A page-aligned max_model_len, reached via FINISHED_LENGTH: the
    last schedulable position is max_model_len-1 (the retire that pushes
    num_tokens to the limit lands in the same call that dispatched it,
    finishing the request before any further schedule) — lengths, finish
    reasons, and tokens identical to sync, pool fully restored."""
    params, cfg = tiny_model
    sp = SamplingParams(temperature=0.0, max_tokens=1000, ignore_eos=True)
    kw = dict(num_pages=16, page_size=4, max_model_len=16,
              enable_prefix_caching=False)
    base = _engine(params, cfg, **kw).generate(PROMPTS[:2], sp)
    eng = _engine(params, cfg, async_scheduling=True, **kw)
    outs = eng.generate(PROMPTS[:2], sp)
    for b, m in zip(base, outs):
        assert m.outputs[0].token_ids == b.outputs[0].token_ids
        assert m.outputs[0].finish_reason == "length"
    kv = eng.scheduler.kv
    assert kv.num_free_pages == kv.num_pages
    assert eng._inflight is None


# ------------------------------------------------ disruption while in flight
def test_async_preemption_with_step_in_flight(tiny_model):
    """A page pool too small for the whole batch forces recompute
    preemption mid-decode; the preempted request's in-flight token is
    discarded and greedily re-derived — final streams stay identical to
    an ample-pool sync run."""
    params, cfg = tiny_model
    base = _engine(params, cfg).generate(PROMPTS, GREEDY)
    eng = _engine(params, cfg, async_scheduling=True, num_pages=10,
                  enable_prefix_caching=False)
    outs = eng.generate(PROMPTS, GREEDY)
    assert eng.scheduler.num_preemptions > 0, \
        "pool sized too generously — preemption never exercised"
    for b, m in zip(base, outs):
        assert m.outputs[0].token_ids == b.outputs[0].token_ids


def test_async_deadline_expiry_with_step_in_flight(tiny_model):
    """A deadline expiring between dispatch and retire error-finishes
    the request (its in-flight token is discarded) without disturbing
    batch-mates."""
    params, cfg = tiny_model
    eng = _engine(params, cfg, async_scheduling=True)
    sp = SamplingParams(temperature=0.0, max_tokens=20, ignore_eos=True)
    eng.add_request(PROMPTS[0], sp, request_id="victim",
                    deadline_ts=time.monotonic() + 3600)
    eng.add_request(PROMPTS[1], sp, request_id="survivor")
    results = {}
    steps = 0
    while eng.has_unfinished_requests:
        steps += 1
        if steps == 5:
            # expire mid-pipeline, with a dispatched step in flight
            _, req = eng.scheduler.find_request("victim")
            if req is not None:
                req.deadline_ts = time.monotonic() - 1.0
        for o in eng.step():
            results[o.request_id] = o
    assert results["victim"].finished
    assert results["victim"].outputs[0].finish_reason == "error"
    assert (results["victim"].multimodal_output.get("error_kind")
            == "deadline_exceeded")
    assert len(results["survivor"].outputs[0].token_ids) == 20
    kv = eng.scheduler.kv
    assert kv.num_free_pages == kv.num_pages


def test_async_abort_with_step_in_flight(tiny_model):
    params, cfg = tiny_model
    eng = _engine(params, cfg, async_scheduling=True)
    sp = SamplingParams(temperature=0.0, max_tokens=20, ignore_eos=True)
    eng.add_request(PROMPTS[0], sp, request_id="gone")
    eng.add_request(PROMPTS[1], sp, request_id="stays")
    results = {}
    steps = 0
    while eng.has_unfinished_requests:
        steps += 1
        if steps == 4:
            eng.abort_request("gone")
        for o in eng.step():
            results[o.request_id] = o
    assert "gone" not in results
    assert len(results["stays"].outputs[0].token_ids) == 20


# ------------------------------------------- retired fallback matrix
# The PR 11 contract: spec decode, logprobs, collect_hidden, and embeds
# batches RIDE the pipeline (the unified dispatch carries their
# verify/logprob/hidden work on device) — the per-reason drain counters
# for them are structurally impossible to increment.

FORBIDDEN_FALLBACKS = ("spec", "logprobs", "collect_hidden", "embeds",
                       "prefill")


def _assert_no_forbidden_fallbacks(eng):
    for reason in FORBIDDEN_FALLBACKS:
        assert reason not in eng.async_fallback, eng.async_fallback


def test_async_logprobs_pipelines(tiny_model):
    """logprobs ride the handle: the chosen/top-k values compute in the
    dispatched step and surface at the lagged retire — the batch
    pipelines, the entries stay 1:1 aligned with tokens, and the values
    match a sync engine's."""
    params, cfg = tiny_model
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True,
                        logprobs=3)
    base = _engine(params, cfg).generate([PROMPTS[0]], sp)
    eng = _engine(params, cfg, async_scheduling=True)
    calls = _spy_dispatch(eng)
    out = eng.generate([PROMPTS[0]], sp)
    c = out[0].outputs[0]
    b = base[0].outputs[0]
    assert c.token_ids == b.token_ids
    assert len(c.logprobs) == len(b.logprobs)
    for got, want in zip(c.logprobs, b.logprobs):
        assert got["top_ids"] == want["top_ids"]
        assert abs(got["logprob"] - want["logprob"]) < 1e-4
    assert calls, "logprobs decode batch must take the pipelined path"
    _assert_no_forbidden_fallbacks(eng)


def test_async_spec_decode_pipelines(tiny_model):
    """An installed draft head no longer drains the pipeline: verify
    rows are k+1-token ragged rows of the unified dispatch, outputs
    match a sync spec-decode engine exactly, and the 'spec' fallback
    reason never fires."""
    params, cfg = tiny_model

    def draft_fn(hidden, tokens, positions):
        return jnp.tile(tokens[:, None], (1, 2))

    def run(async_mode):
        eng = LLMEngine(params, cfg, EngineConfig(
            num_pages=64, page_size=4, max_model_len=128, max_num_seqs=4,
            dtype=jnp.float32, num_speculative_tokens=2,
            async_scheduling=async_mode), draft_fn=draft_fn)
        dispatched = []
        orig = eng.runner.dispatch_unified
        eng.runner.dispatch_unified = lambda so, prev=None: (
            dispatched.append(
                sum(s.num_new_tokens > 1 for s in so.decodes))
            or orig(so, prev))
        return eng.generate(PROMPTS, GREEDY), dispatched, eng

    sync_out, _, _ = run(False)
    async_out, dispatched, eng = run(True)
    for b, m in zip(sync_out, async_out):
        assert m.outputs[0].token_ids == b.outputs[0].token_ids
    assert any(n > 0 for n in dispatched), \
        "verify rows never rode the unified dispatch"
    _assert_no_forbidden_fallbacks(eng)
    assert eng.runner.spec_stats["accepted"] > 0


def test_async_collect_hidden_pipelines(tiny_model):
    """collect_hidden rides the handle: the packed hidden state ships
    with the one lagged retire transfer, payloads match sync, and the
    batch pipelines."""
    params, cfg = tiny_model
    sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
    base = _engine(params, cfg, collect_hidden=True).generate(
        [PROMPTS[0]], sp)
    eng = _engine(params, cfg, async_scheduling=True, collect_hidden=True)
    outs = eng.generate([PROMPTS[0]], sp)
    import numpy as np

    want = base[0].multimodal_output["hidden_states"]
    got = outs[0].multimodal_output["hidden_states"]
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-5)
    _assert_no_forbidden_fallbacks(eng)


def test_async_embeds_pipelines(tiny_model):
    """Embeds-as-input prefills scatter into the packed token buffer
    and pipeline; the stream matches the token-id path exactly."""
    import numpy as np

    params, cfg = tiny_model
    prompt = [3, 7, 11, 2]
    embeds = np.asarray(params["embed"]["w"])[prompt]
    sp = SamplingParams(temperature=0.0, max_tokens=5, ignore_eos=True)
    want = _engine(params, cfg).generate([prompt], sp)
    eng = _engine(params, cfg, async_scheduling=True)
    eng.add_request([0] * len(prompt), sp, request_id="e",
                    prompt_embeds=embeds)
    results = []
    while eng.has_unfinished_requests:
        results.extend(eng.step())
    assert (results[0].outputs[0].token_ids
            == want[0].outputs[0].token_ids)
    _assert_no_forbidden_fallbacks(eng)


def test_async_generation_worker_ignores_knob(tiny_model):
    """async_scheduling only applies to AR engines; a generation stage
    silently runs synchronously instead of breaking."""
    params, cfg = tiny_model
    eng = _engine(params, cfg, async_scheduling=True,
                  worker_type="generation")
    assert eng.config.async_scheduling is False


def test_async_metrics_count_each_token_once(tiny_model):
    """Overshoot retires must not re-count a finished request's stream:
    tokens_generated, TTFT observations, and the latency table match
    sync mode exactly (a resurrected _req_lat entry would also leak per
    finished request in a long-running server)."""
    params, cfg = tiny_model
    sync = _engine(params, cfg)
    sync.generate(PROMPTS, GREEDY)
    eng = _engine(params, cfg, async_scheduling=True)
    eng.generate(PROMPTS, GREEDY)
    assert eng._inflight is None, "final overshoot left dangling"
    expected = len(PROMPTS) * GREEDY.max_tokens
    assert sync.step_metrics.tokens_generated == expected
    assert eng.step_metrics.tokens_generated == expected
    assert eng.step_metrics.ttft_ms._count == len(PROMPTS)
    assert not eng._req_lat, "latency entries leaked past finish"


# -------------------------------------------------------- overlap metric
def test_async_overlap_ratio_reported(tiny_model):
    """The CPU-backend microbench of the acceptance criteria: host work
    for step N-1 completes while step N's dispatch is in flight, so the
    overlap ratio is > 0 and surfaces through metrics_snapshot()."""
    params, cfg = tiny_model
    eng = _engine(params, cfg, async_scheduling=True)
    eng.generate(PROMPTS, GREEDY)
    assert eng.step_metrics.overlap_ratio > 0.0
    snap = eng.metrics_snapshot()
    assert snap["overlap"]["ratio"] > 0.0
    assert snap["host_ms"]["count"] > 0
    assert snap["device_ms"]["count"] > 0
    # sync engines report the breakdown too, with zero overlap
    sync = _engine(params, cfg)
    sync.generate(PROMPTS, GREEDY)
    assert sync.step_metrics.overlap_ratio == 0.0
    assert sync.metrics_snapshot()["host_ms"]["count"] > 0


def test_async_dispatch_retire_spans_recorded(tiny_model):
    """The pipelined step records separate dispatch/retire spans (the
    sync path's decode/sampling spans can't represent a lagged retire)."""
    params, cfg = tiny_model
    from vllm_omni_tpu.tracing import get_recorder, new_trace_context

    get_recorder().drain()
    eng = _engine(params, cfg, async_scheduling=True)
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    rid = eng.add_request(PROMPTS[0], sp)
    _, req = eng.scheduler.find_request(rid)
    req.additional_information["trace"] = new_trace_context(rid)
    while eng.has_unfinished_requests:
        eng.step()
    names = {s["name"] for s in get_recorder().drain()
             if s["request_id"] == rid}
    assert "dispatch" in names and "retire" in names, names


@pytest.mark.slow  # fast siblings: test_warmup_precompiles_all_traffic_
# shapes warms the same token-bucket executables and
# test_async_greedy_matches_sync pins pipelined correctness; only the
# dispatch-fn cache-stability assertion is unique here
def test_async_warmup_precompiles_dispatch_path(tiny_model):
    """warmup() with async_scheduling warms the dispatch executable so
    serving traffic hits no new compile on the pipelined path."""
    params, cfg = tiny_model
    eng = _engine(params, cfg, async_scheduling=True)
    n = eng.warmup(prefill_shapes=[
        (len(PROMPTS), max(len(p) for p in PROMPTS))])
    assert n > 0
    fn = eng.runner._decode_sample_fn
    size = fn._cache_size()
    outs = eng.generate(PROMPTS, GREEDY)
    assert all(len(o.outputs[0].token_ids) == 12 for o in outs)
    assert fn._cache_size() == size, \
        "pipelined traffic compiled a shape warmup missed"
