"""E2E engine tests on a tiny random-weight transformer — the analogue of
the reference's random-weight model CI strategy (SURVEY.md §4, e.g.
riverclouds/qwen_image_random).  The paged-decode path is checked against a
full-forward greedy oracle: continuous batching must not change numerics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.sampling_params import SamplingParams


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tfm.TransformerConfig.tiny(vocab_size=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return params, cfg


def _greedy_oracle(params, cfg, prompt, n_tokens):
    """Greedy decode via repeated full forward (no KV cache)."""
    toks = list(prompt)
    for _ in range(n_tokens):
        hidden = tfm.forward_hidden(params, cfg, jnp.asarray([toks]))
        logits = tfm.logits_from_hidden(params, cfg, hidden[0, -1])
        toks.append(int(jnp.argmax(logits)))
    return toks[len(prompt):]


def _engine(params, cfg, **kw):
    defaults = dict(num_pages=64, page_size=4, max_model_len=128,
                    max_num_seqs=4, dtype=jnp.float32)
    defaults.update(kw)
    return LLMEngine(params, cfg, EngineConfig(**defaults))


def test_greedy_matches_full_forward_oracle(tiny_model):
    params, cfg = tiny_model
    eng = _engine(params, cfg)
    prompt = [1, 5, 9, 2, 7]
    n = 6
    outs = eng.generate([prompt], SamplingParams(temperature=0.0, max_tokens=n))
    got = outs[0].outputs[0].token_ids
    want = _greedy_oracle(params, cfg, prompt, n)
    assert got == want


def test_batch_mixed_lengths_matches_oracle(tiny_model):
    params, cfg = tiny_model
    eng = _engine(params, cfg)
    prompts = [[3, 1, 4], [1, 5, 9, 2, 6, 5], [10], [8, 8, 8, 8]]
    n = 5
    outs = eng.generate(
        [list(p) for p in prompts],
        SamplingParams(temperature=0.0, max_tokens=n),
    )
    assert len(outs) == len(prompts)
    for p, o in zip(prompts, outs):
        assert o.outputs[0].token_ids == _greedy_oracle(params, cfg, p, n)
        assert o.outputs[0].finish_reason == "length"


def test_continuous_batching_join_midstream(tiny_model):
    """A request added while another decodes must not perturb either."""
    params, cfg = tiny_model
    eng = _engine(params, cfg)
    eng.add_request([2, 4, 6], SamplingParams(temperature=0.0, max_tokens=8),
                    request_id="first")
    for _ in range(3):
        eng.step()
    eng.add_request([9, 7], SamplingParams(temperature=0.0, max_tokens=4),
                    request_id="second")
    results = {}
    while eng.has_unfinished_requests:
        for out in eng.step():
            results[out.request_id] = out
    assert results["first"].outputs[0].token_ids == _greedy_oracle(
        params, cfg, [2, 4, 6], 8)
    assert results["second"].outputs[0].token_ids == _greedy_oracle(
        params, cfg, [9, 7], 4)


def test_eos_stop(tiny_model):
    params, cfg = tiny_model
    eng = _engine(params, cfg)
    # discover what greedy emits first, then declare it the eos token
    first = _greedy_oracle(params, cfg, [1, 2, 3], 1)[0]
    eng.eos_token_id = first
    outs = eng.generate([[1, 2, 3]], SamplingParams(temperature=0.0,
                                                    max_tokens=10))
    assert outs[0].outputs[0].token_ids == [first]
    assert outs[0].outputs[0].finish_reason == "stop"


def test_kv_transfer_sink_receives_payload(tiny_model):
    from vllm_omni_tpu.core.scheduler import KVTransferConfig

    params, cfg = tiny_model
    eng = _engine(params, cfg,
                  kv_transfer=KVTransferConfig(trigger="prefill_finished"))
    received = []
    eng.kv_transfer_sink = lambda req, payload: received.append((req, payload))
    eng.generate([[1, 2, 3, 4, 5]], SamplingParams(temperature=0.0,
                                                   max_tokens=2))
    assert len(received) == 1
    req, payload = received[0]
    assert len(payload) == cfg.num_layers
    k, v = payload[0]
    # [Hkv, seq_len, D]; seq_len = 5 computed prompt tokens
    assert k.shape == (cfg.num_kv_heads, 5, cfg.head_dim)


def test_sampled_generation_stays_in_vocab(tiny_model):
    params, cfg = tiny_model
    eng = _engine(params, cfg)
    outs = eng.generate(
        [[1, 2, 3]],
        SamplingParams(temperature=1.0, top_k=10, seed=0, max_tokens=5),
    )
    toks = outs[0].outputs[0].token_ids
    assert len(toks) == 5
    assert all(0 <= t < cfg.vocab_size for t in toks)


def test_preemption_resume_matches_oracle(tiny_model):
    """A KV pool too small for both requests forces recompute-preemption
    mid-generation; resumed requests must still match the oracle exactly."""
    params, cfg = tiny_model
    # 6 pages of 4 slots = 24 tokens: two requests at 8-token prompts + 8
    # outputs (16 tokens each) cannot coexist
    eng = _engine(params, cfg, num_pages=6)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8], [9, 10, 11, 12, 13, 14, 15, 16]]
    outs = eng.generate([list(p) for p in prompts],
                        SamplingParams(temperature=0.0, max_tokens=8))
    for p, o in zip(prompts, outs):
        assert o.outputs[0].token_ids == _greedy_oracle(params, cfg, p, 8)


def test_too_long_prompt_returns_error_output(tiny_model):
    params, cfg = tiny_model
    eng = _engine(params, cfg, max_model_len=16)
    outs = eng.generate([[1] * 20, [1, 2, 3]],
                        SamplingParams(temperature=0.0, max_tokens=2))
    assert len(outs) == 2
    by_id = {o.request_id: o for o in outs}
    errored = [o for o in outs if o.outputs[0].finish_reason == "error"]
    assert len(errored) == 1 and not errored[0].outputs[0].token_ids


def test_max_model_len_caps_generation(tiny_model):
    params, cfg = tiny_model
    eng = _engine(params, cfg, max_model_len=8)
    outs = eng.generate([[1, 2, 3, 4, 5]],
                        SamplingParams(temperature=0.0, max_tokens=100))
    o = outs[0].outputs[0]
    assert len(o.token_ids) == 3  # 5 prompt + 3 = 8 = max_model_len
    assert o.finish_reason == "length"


def test_unseeded_requests_decorrelated(tiny_model):
    """Two identical unseeded prompts at high temperature should not emit
    identical completions (per-request salt mixes in)."""
    params, cfg = tiny_model
    eng = _engine(params, cfg)
    outs = eng.generate([[1, 2, 3]] * 4,
                        SamplingParams(temperature=3.0, max_tokens=8))
    seqs = {tuple(o.outputs[0].token_ids) for o in outs}
    assert len(seqs) > 1


def test_prompt_embeds_prefill_matches_token_path(tiny_model):
    """Feeding prompt_embeds equal to the embedding rows of a prompt must
    reproduce the token-id path exactly (embeds-as-input correctness)."""
    params, cfg = tiny_model
    prompt = [3, 7, 11, 2]
    embeds = np.asarray(params["embed"]["w"])[prompt]
    eng = _engine(params, cfg)
    want = eng.generate([prompt], SamplingParams(temperature=0.0,
                                                max_tokens=5))[0]
    eng2 = _engine(params, cfg)
    eng2.add_request([0] * len(prompt),
                     SamplingParams(temperature=0.0, max_tokens=5),
                     request_id="e", prompt_embeds=embeds)
    results = []
    while eng2.has_unfinished_requests:
        results.extend(eng2.step())
    assert results[0].outputs[0].token_ids == want.outputs[0].token_ids


def test_prompt_embeds_with_width_projection():
    """Upstream embeds in a different width ride embed_proj (thinker 32 →
    talker 64)."""
    from vllm_omni_tpu.models.qwen3_omni import talker

    cfg = talker.tiny_config()
    params = talker.init_talker_params(jax.random.PRNGKey(5), cfg,
                                       thinker_hidden=32)
    eng = _engine(params, cfg)
    embeds = np.random.RandomState(0).randn(6, 32).astype(np.float32)
    eng.add_request([0] * 6, SamplingParams(temperature=0.0, max_tokens=4),
                    request_id="w", prompt_embeds=embeds)
    results = []
    while eng.has_unfinished_requests:
        results.extend(eng.step())
    toks = results[0].outputs[0].token_ids
    assert len(toks) == 4 and all(0 <= t < cfg.vocab_size for t in toks)


def test_prompt_embeds_survives_preemption():
    """A preempted embeds request resumes by recomputing prompt (embeds) +
    generated tokens (table lookups) — no crash, correct output length."""
    params_cfg = tfm.TransformerConfig.tiny(vocab_size=64)
    params = tfm.init_params(jax.random.PRNGKey(0), params_cfg, jnp.float32)
    # pool too small for two requests at full length
    eng = _engine(params, params_cfg, num_pages=6)
    embeds = np.asarray(params["embed"]["w"])[[1, 2, 3, 4, 5, 6, 7, 8]]
    eng.add_request([0] * 8, SamplingParams(temperature=0.0, max_tokens=8),
                    request_id="a", prompt_embeds=embeds)
    eng.add_request(list(range(9, 17)),
                    SamplingParams(temperature=0.0, max_tokens=8),
                    request_id="b")
    results = {}
    while eng.has_unfinished_requests:
        for o in eng.step():
            results[o.request_id] = o
    assert len(results["a"].outputs[0].token_ids) == 8
    assert len(results["b"].outputs[0].token_ids) == 8
    # the embeds request's output must equal its unpreempted run
    eng2 = _engine(params, params_cfg, num_pages=64)
    eng2.add_request([0] * 8, SamplingParams(temperature=0.0, max_tokens=8),
                     request_id="a2", prompt_embeds=embeds)
    solo = []
    while eng2.has_unfinished_requests:
        solo.extend(eng2.step())
    assert results["a"].outputs[0].token_ids == solo[0].outputs[0].token_ids


def test_starved_request_error_finishes_not_crashes(tiny_model):
    """A request whose recompute footprint outgrows the KV pool is
    error-finished; the engine stays serviceable for later requests."""
    params, cfg = tiny_model
    eng = _engine(params, cfg, num_pages=2)  # pool: 8 tokens
    eng.add_request([1, 2, 3, 4, 5, 6],
                    SamplingParams(temperature=0.0, max_tokens=10),
                    request_id="grow")
    results = {}
    while eng.has_unfinished_requests:
        for o in eng.step():
            results[o.request_id] = o
    assert results["grow"].outputs[0].finish_reason == "error"
    # engine still works afterwards
    outs = eng.generate([[1, 2]], SamplingParams(temperature=0.0,
                                                 max_tokens=2))
    assert outs[0].outputs[0].finish_reason == "length"


def test_collect_hidden_correct_after_preemption(tiny_model):
    """Preemption must not duplicate collected hidden rows: the final
    hidden_states length equals prompt + outputs - 1 regardless of
    recompute."""
    params, cfg = tiny_model
    eng = _engine(params, cfg, num_pages=6, collect_hidden=True)
    eng.add_request([1, 2, 3, 4, 5, 6, 7, 8],
                    SamplingParams(temperature=0.0, max_tokens=8),
                    request_id="a")
    eng.add_request([9, 10, 11, 12, 13, 14, 15, 16],
                    SamplingParams(temperature=0.0, max_tokens=8),
                    request_id="b")
    results = {}
    while eng.has_unfinished_requests:
        for o in eng.step():
            results[o.request_id] = o
    for o in results.values():
        hs = o.multimodal_output["hidden_states"]
        assert hs.shape == (8 + 8 - 1, cfg.hidden_size)


def test_generation_scheduler_engine(tiny_model):
    params, cfg = tiny_model
    eng = _engine(params, cfg, worker_type="generation", collect_hidden=True)
    outs = eng.generate([[1, 2, 3, 4]], SamplingParams(max_tokens=1))
    assert len(outs) == 1 and outs[0].finished


def test_generation_runner_precompile():
    """One-shot generation runner warmup: the padded-batch forward
    compiles at declared shapes, and traffic at the same buckets hits a
    warm executable (same contract as ARModelRunner.precompile)."""
    import numpy as np

    from vllm_omni_tpu.core.scheduler import ScheduledRequest, SchedulerOutput
    from vllm_omni_tpu.request import Request
    from vllm_omni_tpu.worker.generation_runner import GenerationModelRunner

    class Toy:
        def forward(self, params, token_ids, lengths):
            return {"y": token_ids.astype(jnp.float32) * params["w"]}

        def slice_output(self, outputs, row, in_len):
            return {"y": np.asarray(outputs["y"][row, :in_len])}

    runner = GenerationModelRunner({"w": jnp.float32(2.0)}, Toy(),
                                   max_num_seqs=4, max_model_len=64)
    assert runner.precompile(prefill_shapes=[(2, 10)]) == 2  # b in {1, 2}
    size = runner._forward._cache_size()
    req = Request(request_id="r", prompt_token_ids=list(range(1, 9)))
    sched = ScheduledRequest(request=req, num_new_tokens=8,
                             slot_mapping=[], block_table=[], start_pos=0)
    runner.execute(SchedulerOutput(prefills=[sched]))
    np.testing.assert_allclose(
        req.multimodal_output["y"], np.arange(1, 9, dtype=np.float32) * 2)
    assert runner._forward._cache_size() == size


def test_step_metrics_and_snapshot(tiny_model):
    """Step-level observability (the /metrics source): TTFT/TPOT/ITL
    histograms populate from real steps, token counters add up, and the
    snapshot reports KV utilization + scheduler counters."""
    params, cfg = tiny_model
    # the synchronous step's count of steps (the pipelined step runs one
    # more, the overshoot; its counters: tests/engine/test_async_step.py)
    eng = _engine(params, cfg, async_scheduling=False)
    outs = eng.generate(
        [[1, 2, 3], [4, 5, 6, 7]],
        SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True),
    )
    assert all(o.outputs[0].finish_reason == "length" for o in outs)
    snap = eng.metrics_snapshot()
    assert snap["ttft_ms"]["count"] == 2      # one first token each
    assert snap["tpot_ms"]["count"] == 2      # one per finished request
    assert snap["itl_ms"]["count"] == 6       # 3 post-first tokens each
    assert snap["counters"]["tokens_generated"] == 8
    assert snap["counters"]["prefill_tokens"] == 7
    # the prefill step samples the first token: 1 prefill + 3 decodes
    assert snap["counters"]["num_steps"] == 4
    assert snap["step_ms"]["count"] == snap["counters"]["num_steps"]
    # all requests finished: pool drained, queues empty
    assert snap["kv"]["pages_used"] == 0
    assert snap["kv"]["pages_total"] == 64
    assert snap["scheduler"] == {"waiting": 0, "running": 0,
                                 "preemptions": 0, "rejections": 0}
    # per-request latency state must not leak
    assert not eng._req_lat and not eng._trace_started


def test_engine_records_spans_for_traced_requests(tiny_model):
    """Requests carrying a trace context get queue_wait/prefill/decode/
    sampling spans; untraced requests record nothing."""
    from vllm_omni_tpu.tracing import get_recorder, new_trace_context

    params, cfg = tiny_model
    get_recorder().drain()
    # the synchronous step's span names
    eng = _engine(params, cfg, async_scheduling=False)
    ctx = new_trace_context("traced")
    eng.add_request([1, 2, 3],
                    SamplingParams(temperature=0.0, max_tokens=2,
                                   ignore_eos=True),
                    request_id="traced",
                    additional_information={"trace": ctx})
    eng.add_request([4, 5], SamplingParams(temperature=0.0, max_tokens=2,
                                           ignore_eos=True),
                    request_id="untraced")
    while eng.has_unfinished_requests:
        eng.step()
    spans = get_recorder().drain()
    assert spans and all(s["request_id"] == "traced" for s in spans)
    assert all(s["trace_id"] == ctx["trace_id"] for s in spans)
    names = {s["name"] for s in spans}
    assert {"queue_wait", "prefill", "decode", "sampling"} <= names


PROMPTS = [[1, 5, 9, 2, 7], [3, 3, 8], [11, 4, 6, 1, 2, 9, 5]]


def test_warmup_precompiles_all_traffic_shapes(tiny_model):
    """engine.warmup() => serving traffic hits zero new executables on
    the unified/decode paths (a mid-traffic XLA compile stalls every
    in-flight request for its whole duration).  The warmup surface is
    the 1-D token-bucket line plus the decode buckets × {plain,
    logprobs} — the (batch, seq) grid of the deleted split executor is
    gone."""
    params, cfg = tiny_model
    eng = _engine(params, cfg)
    n = eng.warmup(prefill_shapes=[
        (len(PROMPTS), max(len(p) for p in PROMPTS))])
    assert n > 0
    compiles = eng.runner.compile_stats["compiles"]
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    outs = eng.generate(PROMPTS, sp)
    assert all(len(o.outputs[0].token_ids) == 12 for o in outs)
    # identical prompts again: APC prefix hits resume mid-prompt
    # through the unified continuation — same token buckets, still warm
    outs2 = eng.generate(PROMPTS, sp)
    assert eng.runner.compile_stats["compiles"] == compiles, \
        "traffic compiled shapes warmup missed"
    for a, b in zip(outs, outs2):
        assert a.outputs[0].token_ids == b.outputs[0].token_ids
    # warmup's dropped-slot writes must not have corrupted generation:
    # a fresh un-warmed engine produces identical greedy tokens
    base = _engine(params, cfg).generate(PROMPTS, sp)
    for b, m in zip(base, outs):
        assert m.outputs[0].token_ids == b.outputs[0].token_ids
