"""The multi-step decode window is RETIRED (PR 11): the async pipelined
step is the host-round-trip amortization, and it serves the batches the
lax.scan window never could (mixed, sampled, spec, logprobs).  The knob
survives as an accepted no-op so existing configs keep constructing —
these tests pin the deprecation contract and the warmup coverage that
replaced the (batch, seq) executable grid."""

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
    defaults = dict(num_pages=64, page_size=4, max_model_len=128,
                    max_num_seqs=4, dtype=jnp.float32)
    defaults.update(kw)
    return LLMEngine(params, cfg, EngineConfig(**defaults))


PROMPTS = [[1, 5, 9, 2, 7], [3, 3, 8], [11, 4, 6, 1, 2, 9, 5]]


def test_multi_step_knob_is_accepted_noop(tiny_model):
    """A config carrying the retired knob still constructs and serves;
    the scheduler only ever emits window-1 rows, and the stream is
    identical to an engine without the knob."""
    params, cfg = tiny_model
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    base = _engine(params, cfg).generate(PROMPTS, sp)
    eng = _engine(params, cfg, multi_step_decode=4)
    seen = set()
    orig = eng.runner.execute

    def spy(sched_out, extract_kv=True):
        for s in sched_out.decodes:
            seen.add(s.window)
        return orig(sched_out, extract_kv)

    eng.runner.execute = spy
    multi = eng.generate(PROMPTS, sp)
    for b, m in zip(base, multi):
        assert m.outputs[0].token_ids == b.outputs[0].token_ids
        assert len(m.outputs[0].token_ids) == 12
    assert seen == {1}, f"retired window machinery scheduled: {seen}"


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


def test_logprobs_with_retired_knob(tiny_model):
    """logprobs requests serve normally with the knob present (they
    ride the decode logprobs executable, not a fallback)."""
    params, cfg = tiny_model
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True,
                        logprobs=3)
    out = _engine(params, cfg, multi_step_decode=4).generate(
        [PROMPTS[0]], sp)
    c = out[0].outputs[0]
    assert len(c.token_ids) == 6
    assert len(c.logprobs) >= 6
