"""Eight serving scenarios on the normal path (LLMEngine -> ARScheduler ->
KVCacheManager -> ARModelRunner), each at ``TransformerConfig.tiny`` and
``tiny_moe``, held to a plain float32 reference that shares no forward with
the served path: ``tests/benchmark/dense_arch.py`` for the dense model,
``benchmark/archs/qwen3_moe.py`` for the one with experts (dense attention
over the whole sequence, every expert computed, no cache).

What a scenario is held to.

- Every request is served greedily with ``logprobs`` on, and the served
  logprob of every generated token lies within ``TOL`` of the reference's at
  that prefix (teacher-forced: one reference call over prompt + generated
  tokens).  Logprobs are compared, never tokens against a file: with random
  weights the largest logit changes on rounding.  Every mode below returns
  logprobs for its rows (speculative verify rows included), so none leans
  on a plain twin for this.
- Where the program promises equality between two live engines, the streams
  are compared token for token between those engines: speculative greedy =
  plain greedy; speculative under preemption = the same requests with pages
  to spare (and ``num_preemptions > 0``); embeds as input = token ids;
  async + unified = the sync default.
- ``logprobs=3``: the served top-3 ids are the reference's, their values
  within ``TOL``.  ``collect_hidden``: the payload holds one final-normed
  row a prompt and generated token but the last; neither reference exposes
  hidden states, so each row is held through the head: the log-softmax of
  ``row @ lm_head`` within ``TOL`` of the reference's at that position,
  over the whole vocabulary.

The tolerance.  Engine and reference are both float32 on the CPU (full
precision matmuls) and differ in the order of summation: paged attention
runs the flash recurrence page by page, the experts run as grouped matmuls,
the q/k norm and rotary tables are computed once a step.  Through 2 layers
on logits of order 1 that is a few float32 roundings.  Readings (the
largest over a scenario's tokens, this file on the CPU): 2.4e-7 to 9.5e-7
in all sixteen cases; through the head from the collected hidden rows
9.1e-7 (``tiny``) and 8.6e-7 (``tiny_moe``).  The limit is
``test_qwen3_next.py``'s 2e-5, twenty times the worst reading.  The control
keeps the paged KV pool in bfloat16 (8 bits of mantissa) and reads 1.4e-3
(3.7e-3 at ``tiny_moe``), seventy times the limit: the limit can see a
cache that is not the precision the engine states.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import qwen3_moe as moe_arch
from tests.benchmark import dense_arch
from vllm_omni_tpu.engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.models.qwen3_omni import mtp
from vllm_omni_tpu.sampling_params import SamplingParams

TOL = 2e-5
VOCAB = 64

PROMPTS = [[1, 5, 9, 2, 7], [3, 3, 8], [11, 4, 6, 1, 2, 9, 5],
           [9, 9, 1, 2], [7, 1], [2, 4, 8, 16, 32, 1]]

#: program configuration and the module whose reference it is held to; the
#: q/k norm is switched on for the model with experts, as in the published
#: Qwen3-MoE that ``qwen3_moe.py`` was written for (its reference applies
#: the norm where a layer holds one)
MODELS = {
    "tiny": (tfm.TransformerConfig.tiny(VOCAB), dense_arch),
    "tiny_moe": (dataclasses.replace(tfm.TransformerConfig.tiny_moe(VOCAB),
                                     qk_norm=True), moe_arch),
}


@dataclasses.dataclass(frozen=True)
class Model:
    params: dict
    cfg: tfm.TransformerConfig
    arch: object

    @property
    def config(self) -> dict:
        """The published keys a reference reads from a configuration file."""
        return {"rms_norm_eps": self.cfg.rms_eps,
                "head_dim": self.cfg.head_dim,
                "rope_theta": self.cfg.rope_theta,
                "num_experts_per_tok": self.cfg.num_experts_per_tok,
                "norm_topk_prob": self.cfg.moe_renormalize}

    def reference(self, token_ids: list[int], last_n: int) -> np.ndarray:
        """[last_n, vocab] float32 log-probabilities after the last
        ``last_n`` positions of ``token_ids``."""
        return np.asarray(self.arch.reference_logprobs(
            self.params, self.config, token_ids, last_n))


def _model(name: str) -> Model:
    cfg, arch = MODELS[name]
    return Model(tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32),
                 cfg, arch)


@pytest.fixture(scope="module", params=sorted(MODELS))
def model(request) -> Model:
    return _model(request.param)


ENGINE = dict(num_pages=64, page_size=4, max_model_len=128, max_num_seqs=8,
              max_num_batched_tokens=32, dtype=jnp.float32, seed=0)


def _engine(model: Model, draft_fn=None, **kw) -> LLMEngine:
    return LLMEngine(model.params, model.cfg,
                     EngineConfig(**{**ENGINE, **kw}), draft_fn=draft_fn)


def _greedy(max_tokens: int, logprobs: int = 0) -> SamplingParams:
    return SamplingParams(temperature=0.0, max_tokens=max_tokens,
                          ignore_eos=True, logprobs=logprobs)


def _drain(eng: LLMEngine, late: dict = None, sp=None) -> dict:
    """Step the engine dry; ``late`` maps a step count to the prompts
    (indices of PROMPTS) that arrive after that step."""
    late, outs, steps = dict(late or {}), {}, 0
    while eng.has_unfinished_requests:
        for o in eng.step():
            assert not o.is_error, o.error_message
            outs[o.request_id] = o
        steps += 1
        for idx in late.pop(steps, ()):
            eng.add_request(PROMPTS[idx], sp, request_id=f"r{idx}")
    return outs


def _generate(eng: LLMEngine, prompts: list, sp, tag: str = "g") -> dict:
    """request id -> (prompt, output), in the order of ``prompts``."""
    outs = eng.generate(prompts, sp)
    for o in outs:
        assert not o.is_error, o.error_message
    return {f"{tag}{i}": (p, o) for i, (p, o) in enumerate(zip(prompts, outs))}


def _ids(output) -> list[int]:
    return [int(t) for t in output.outputs[0].token_ids]


def _streams(served: dict) -> dict:
    return {rid: _ids(o) for rid, (_, o) in sorted(served.items())}


def _staggered(eng: LLMEngine) -> dict:
    """Two arrival waves land while earlier requests decode."""
    sp = _greedy(10)
    eng.add_request(PROMPTS[0], sp, request_id="r0")
    eng.add_request(PROMPTS[1], sp, request_id="r1")
    outs = _drain(eng, late={2: (2, 3), 4: (4,)}, sp=sp)
    return {rid: (PROMPTS[int(rid[1:])], o) for rid, o in outs.items()}


def _worst(model: Model, served: dict) -> float:
    """Largest distance of a served logprob from the reference's, over
    every generated token of every request."""
    worst = 0.0
    for prompt, o in served.values():
        ids = _ids(o)
        got = [float(e["logprob"]) for e in o.outputs[0].logprobs]
        assert len(got) == len(ids) > 0
        ref = model.reference(list(prompt) + ids[:-1], len(ids))
        worst = max(worst, max(abs(g - float(ref[i, t]))
                               for i, (g, t) in enumerate(zip(got, ids))))
    return worst


# ------------------------------------------------------------- scenarios
# Each returns request id -> (prompt, output) of everything it served,
# after holding whatever the mode promises beyond the reference.
def scenario_greedy_batches(model):
    """Plain greedy generate at several batch shapes (1 / 3 / 6)."""
    served = {}
    for n in (1, 3, 6):
        served.update(_generate(_engine(model), PROMPTS[:n], _greedy(10),
                                tag=f"batch{n}/"))
    return served


def scenario_staggered_mixed(model):
    """Mixed prefill+decode steps throughout."""
    return _staggered(_engine(model))


def scenario_spec_greedy(model):
    """Spec decode (tiny MTP head, k=3): greedy output equals plain
    decoding."""
    draft_fn = mtp.tiny_factory(model.params, model.cfg, 3)
    served = _generate(_engine(model, draft_fn, num_speculative_tokens=3),
                       PROMPTS[:3], _greedy(10))
    plain = _generate(_engine(model), PROMPTS[:3], _greedy(10))
    assert _streams(served) == _streams(plain)
    return served


def scenario_spec_preempted(model):
    """Spec decode under page pressure: preemption lands while drafts are
    in flight; recompute re-derives the stream the same requests give with
    pages to spare."""
    def run(num_pages):
        eng = _engine(model, mtp.tiny_factory(model.params, model.cfg, 3),
                      num_pages=num_pages, max_num_seqs=4,
                      num_speculative_tokens=3, enable_prefix_caching=False)
        prompts = {"p0": PROMPTS[0], "p1": PROMPTS[2]}
        for rid, p in prompts.items():
            eng.add_request(p, _greedy(8), request_id=rid)
        outs = _drain(eng)
        return ({rid: (prompts[rid], o) for rid, o in outs.items()},
                eng.scheduler.num_preemptions)

    served, preemptions = run(6)
    spare, none = run(64)
    assert preemptions > 0 and none == 0, "pressure never exercised"
    assert _streams(served) == _streams(spare)
    return served


def scenario_logprobs(model):
    """Greedy + logprobs=3: the served top-3 are the reference's."""
    served = _generate(_engine(model), PROMPTS[:2], _greedy(6, logprobs=3))
    for prompt, o in served.values():
        ids = _ids(o)
        ref = model.reference(list(prompt) + ids[:-1], len(ids))
        for i, e in enumerate(o.outputs[0].logprobs):
            want = np.argsort(-ref[i], kind="stable")[:3]
            assert list(e["top_ids"]) == want.tolist()
            np.testing.assert_allclose(e["top_logprobs"], ref[i][want],
                                       atol=TOL, rtol=0)
    return served


def scenario_collect_hidden(model):
    """collect_hidden: the payload's rows, through the head, are the
    reference's distributions at their positions."""
    served = _generate(
        _engine(model, collect_hidden=True, enable_prefix_caching=False),
        PROMPTS[:2], _greedy(5))
    head = np.asarray(model.params["lm_head"]["w"], np.float64)
    for prompt, o in served.values():
        ids = _ids(o)
        hs = np.asarray(o.multimodal_output["hidden_states"], np.float64)
        rows = len(prompt) + len(ids) - 1  # the last token is never fed
        assert hs.shape == (rows, model.cfg.hidden_size)
        logits = hs @ head
        implied = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        ref = model.reference(list(prompt) + ids[:-1], rows)
        assert np.abs(implied - ref).max() < TOL
    return served


def scenario_embeds(model):
    """Embeds-as-input prefill (upstream hidden rows) reproduces the
    token-id path."""
    prompt = [3, 7, 11, 2]
    eng = _engine(model)
    eng.add_request([0] * len(prompt), _greedy(5), request_id="e",
                    prompt_embeds=np.asarray(model.params["embed"]["w"])[prompt])
    served = {"e": (prompt, _drain(eng)["e"])}
    by_ids = _generate(_engine(model), [prompt], _greedy(5), tag="e")
    assert _streams(served)["e"] == _streams(by_ids)["e0"]
    return served


def scenario_async_unified(model):
    """async + unified together, staggered mixed waves: the sync
    default's streams (EngineConfig.async_scheduling: "Greedy token
    streams are bit-identical to sync mode")."""
    served = _staggered(_engine(model, unified_batching=True,
                                async_scheduling=True))
    assert _streams(served) == _streams(_staggered(_engine(model)))
    return served


SCENARIOS = {
    "greedy_batches": scenario_greedy_batches,
    "staggered_mixed": scenario_staggered_mixed,
    "spec_greedy": scenario_spec_greedy,
    "spec_preempted": scenario_spec_preempted,
    "logprobs": scenario_logprobs,
    "collect_hidden": scenario_collect_hidden,
    "embeds": scenario_embeds,
    "async_unified": scenario_async_unified,
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_served_logprobs_agree_with_the_reference(model, name):
    served = SCENARIOS[name](model)
    assert served
    assert _worst(model, served) < TOL


def test_a_bfloat16_kv_pool_fails_the_tolerance():
    """The control: the same engine with its paged KV pool kept in
    bfloat16 is far outside the tolerance, so the tolerance can see a
    cache that is not the float32 the engine states."""
    model = _model("tiny")
    eng = _engine(model)
    eng.runner.kv_caches = [(k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))
                            for k, v in eng.runner.kv_caches]
    served = _generate(eng, PROMPTS[:3], _greedy(10))
    assert _worst(model, served) > 10 * TOL
