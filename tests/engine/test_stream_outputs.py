"""Partial outputs: a request that streams gets, from every step that
appended tokens to it, an output that is not finished and holds the new
token ids alone (engine/stream.py); a request that does not stream gets
its one final output as ever.  Both step paths: the two-slot pipeline
(``_retire_step``) and the synchronous step (``_run_scheduled``)."""

import jax
import jax.numpy as jnp
import pytest

from vllm_omni_tpu.engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.sampling_params import SamplingParams

PROMPTS = [[1, 5, 9, 2, 7], [3, 3, 8], [11, 4, 6, 1, 2, 9, 5]]
PATHS = pytest.mark.parametrize("pipelined", [True, False],
                                ids=["pipelined", "synchronous"])


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tfm.TransformerConfig.tiny(vocab_size=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return params, cfg


def _engine(tiny_model, pipelined, **kw):
    params, cfg = tiny_model
    defaults = dict(num_pages=64, page_size=4, max_model_len=128,
                    max_num_seqs=4, dtype=jnp.float32,
                    async_scheduling=pipelined)
    defaults.update(kw)
    return LLMEngine(params, cfg, EngineConfig(**defaults))


def _sp(**kw):
    return SamplingParams(**{"temperature": 0.0, "max_tokens": 12,
                             "ignore_eos": True, **kw})


def _run(eng, after_step=None):
    """Step the engine dry; ({id: [partials]}, {id: final})."""
    partials, finals = {}, {}
    step = 0
    while eng.has_unfinished_requests:
        for o in eng.step():
            if o.finished:
                assert o.request_id not in finals
                finals[o.request_id] = o
            else:
                assert o.request_id not in finals, \
                    "a partial after its request's final"
                partials.setdefault(o.request_id, []).append(o)
        step += 1
        if after_step is not None:
            after_step(step, partials)
    return partials, finals


def _joined(partials):
    return [t for p in partials for t in p.outputs[0].token_ids]


@PATHS
def test_a_streaming_request_yields_its_tokens_a_step(tiny_model, pipelined):
    eng = _engine(tiny_model, pipelined)
    rids = [eng.add_request(p, _sp(), stream=True) for p in PROMPTS]
    partials, finals = _run(eng)
    assert eng.steps_by_path["pipelined" if pipelined else "sync"] > 0
    for rid in rids:
        whole = finals[rid].outputs[0].token_ids
        assert len(whole) == 12
        # the last step's tokens come with the final, whole; every
        # earlier step's came as a partial: joined they are its prefix
        got = _joined(partials[rid])
        assert got == whole[:len(got)] and len(got) >= 10
        for p in partials[rid]:
            assert not p.finished and not p.is_error
            assert p.prompt_token_ids == []
            assert len(p.outputs) == 1
            assert 1 <= len(p.outputs[0].token_ids) <= 2
            assert p.outputs[0].finish_reason is None
            assert p.outputs[0].text is None
        # a step, a partial: no step's tokens were kept for a later one
        assert len(partials[rid]) >= 10


@PATHS
def test_the_final_is_what_a_request_that_does_not_stream_gets(
        tiny_model, pipelined):
    base = _engine(tiny_model, pipelined).generate(PROMPTS, _sp())
    eng = _engine(tiny_model, pipelined)
    rids = [eng.add_request(p, _sp(), stream=True) for p in PROMPTS]
    _, finals = _run(eng)
    for b, rid in zip(base, rids):
        f = finals[rid]
        assert f.outputs[0].token_ids == b.outputs[0].token_ids
        assert f.prompt_token_ids == b.prompt_token_ids
        assert f.outputs[0].finish_reason == b.outputs[0].finish_reason
        assert f.finished


@PATHS
def test_a_request_that_does_not_stream_yields_no_partial(
        tiny_model, pipelined):
    eng = _engine(tiny_model, pipelined)
    quiet = eng.add_request(PROMPTS[0], _sp())
    loud = eng.add_request(PROMPTS[1], _sp(), stream=True)
    partials, finals = _run(eng)
    assert quiet not in partials and partials[loud]
    assert len(finals[quiet].outputs[0].token_ids) == 12
    assert len(finals[loud].outputs[0].token_ids) == 12


@PATHS
def test_partials_carry_their_tokens_logprobs(tiny_model, pipelined):
    eng = _engine(tiny_model, pipelined)
    rid = eng.add_request(PROMPTS[2], _sp(logprobs=2), stream=True)
    plain = eng.add_request(PROMPTS[1], _sp(), stream=True)
    partials, finals = _run(eng)
    whole = finals[rid].outputs[0]
    assert len(whole.logprobs) == len(whole.token_ids) == 12
    got = [e for p in partials[rid] for e in p.outputs[0].logprobs]
    assert got == whole.logprobs[:len(got)] and len(got) >= 10
    for p in partials[rid]:
        assert len(p.outputs[0].logprobs) == len(p.outputs[0].token_ids)
    assert all(p.outputs[0].logprobs is None for p in partials[plain])


@PATHS
def test_the_first_partial_alone_says_when_the_first_token_was(
        tiny_model, pipelined):
    import time

    eng = _engine(tiny_model, pipelined)
    t0 = time.monotonic()
    rid = eng.add_request(PROMPTS[0], _sp(), stream=True)
    partials, _ = _run(eng)
    first, *rest = partials[rid]
    assert t0 <= first.metrics["first_token_mono"] <= time.monotonic()
    assert all(p.metrics == {} for p in rest)


@PATHS
def test_partials_go_on_across_a_preemption(tiny_model, pipelined):
    """A pool too small for the batch preempts mid-decode: the victim
    recomputes, and what it streams neither repeats nor skips a token."""
    base = _engine(tiny_model, False).generate(PROMPTS, _sp())
    eng = _engine(tiny_model, pipelined, num_pages=10,
                  enable_prefix_caching=False)
    rids = [eng.add_request(p, _sp(), stream=True) for p in PROMPTS]
    partials, finals = _run(eng)
    assert eng.scheduler.num_preemptions > 0, \
        "pool sized too generously: no preemption"
    for b, rid in zip(base, rids):
        whole = finals[rid].outputs[0].token_ids
        assert whole == b.outputs[0].token_ids
        got = _joined(partials[rid])
        assert got == whole[:len(got)] and len(got) >= 10


@PATHS
def test_an_aborted_request_streams_no_further(tiny_model, pipelined):
    eng = _engine(tiny_model, pipelined)
    gone = eng.add_request(PROMPTS[0], _sp(), stream=True)
    stays = eng.add_request(PROMPTS[1], _sp(), stream=True)
    at_abort = {}

    def abort(step, partials):
        if step == 5:
            eng.abort_request(gone)
            at_abort["n"] = len(_joined(partials.get(gone, [])))

    partials, finals = _run(eng, after_step=abort)
    assert gone not in finals
    assert 0 < at_abort["n"] < 12
    assert len(_joined(partials[gone])) == at_abort["n"]
    assert len(finals[stays].outputs[0].token_ids) == 12
    kv = eng.scheduler.kv
    assert kv.num_free_pages == kv.num_pages


def test_a_reply_of_one_step_is_its_final_alone(tiny_model):
    eng = _engine(tiny_model, True)
    rid = eng.add_request(PROMPTS[0], _sp(max_tokens=1), stream=True)
    partials, finals = _run(eng)
    assert rid not in partials
    assert len(finals[rid].outputs[0].token_ids) == 1
