"""Qwen3-Next on the normal path (LLMEngine -> scheduler -> KVCacheManager
-> ARModelRunner) at tiny widths that keep every ratio of the published
model, against the plain float32 reference of benchmark/archs/qwen3_next.py
(the delta rule token by token, dense attention, every expert computed).

Logprobs are compared, never sampled tokens: with random weights the
largest logit changes on rounding.

The tolerance.  Engine and reference are both float32 (the CPU's matmuls
are full precision) and differ by the order of summation: the engine's
chunked delta rule sums 64 tokens of a chunk at a time where the reference
steps token by token, attention runs the flash recurrence, the experts
run as grouped matmuls.  That is a few float32 roundings (6e-8 relative)
through 4 layers on logits of order 1: readings are 1e-6 to 4e-6.  The
limit is 2e-5; a recurrent state kept in bfloat16 (8 bits) reads 1e-3 to
1e-2 and fails it, which ``test_a_bfloat16_state_fails_the_tolerance``
holds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import qwen3_next as arch
from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.models.qwen3_next import tiny_config
from vllm_omni_tpu.sampling_params import SamplingParams

TOL = 2e-5
VOCAB = 256
CFG = tiny_config(VOCAB)
#: the reference reads the keys of a configuration file
CONFIG = {
    "rms_norm_eps": CFG.rms_eps, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "head_dim": CFG.head_dim,
    "partial_rotary_factor": CFG.partial_rotary_factor,
    "rope_theta": CFG.rope_theta, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 32,
}
ENGINE = dict(num_pages=64, page_size=16, max_model_len=512, max_num_seqs=4,
              max_num_batched_tokens=64, enable_chunked_prefill=True,
              dtype=jnp.float32)


def _prompt(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(3, VOCAB, n).tolist()


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(1), CFG, jnp.float32)


@pytest.fixture(scope="module")
def eng(params):
    return LLMEngine(params, CFG, EngineConfig(**ENGINE))


def _serve(engine, prompts: dict, n_new: int = 5, after_step=None) -> dict:
    """request id -> (generated ids, their served logprobs), greedy."""
    sp = SamplingParams(max_tokens=n_new, temperature=0.0, ignore_eos=True,
                        logprobs=0)
    for rid, prompt in prompts.items():
        engine.add_request(prompt, sp, request_id=rid)
    got, steps = {}, 0
    while engine.has_unfinished_requests:
        for o in engine.step():
            got[o.request_id] = (
                [int(t) for t in o.outputs[0].token_ids],
                [float(e["logprob"]) for e in o.outputs[0].logprobs])
        steps += 1
        if after_step is not None:
            after_step(steps)
    return got


def _reference(params, prompt, ids):
    ref = arch.reference_logprobs(params, CONFIG, prompt + ids[:-1], len(ids))
    return [float(ref[i, t]) for i, t in enumerate(ids)]


@pytest.mark.parametrize("n_prompt", [150, 40])
def test_engine_agrees_with_the_reference(eng, params, n_prompt):
    """Prefill, then decode through both caches.  150 tokens are longer
    than max_num_batched_tokens (64), so the state crosses two chunk
    boundaries, and no multiple of 64, so the rule's last chunk is ragged;
    40 fit one step."""
    prompt = _prompt(n_prompt, n_prompt)
    ids, served = _serve(eng, {f"ref{n_prompt}": prompt}, n_new=6)[
        f"ref{n_prompt}"]
    want = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, want)) < TOL


def test_a_bfloat16_state_fails_the_tolerance(params):
    """The control: the same engine with the recurrent state kept in
    bfloat16 between steps is outside the tolerance, so the tolerance can
    see the precision the configuration states."""
    engine = LLMEngine(params, CFG, EngineConfig(**ENGINE))
    engine.runner.state_caches = [
        (conv, rec.astype(jnp.bfloat16))
        for conv, rec in engine.runner.state_caches]
    prompt = _prompt(150, 150)
    ids, served = _serve(engine, {"bf16": prompt}, n_new=6)["bf16"]
    want = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, want)) > 10 * TOL


def test_two_sequences_in_one_unified_step_equal_each_alone(eng):
    """Unequal lengths packed into the same steps (chunks of both in one
    ragged batch, then decode rows beside a chunk) against each alone."""
    prompts = {"pair_a": _prompt(1, 90), "pair_b": _prompt(2, 37)}
    together = _serve(eng, prompts)
    shared = [r for r in eng.flight.tail()
              if {"pair_a", "pair_b"} <= set(r["requests"])]
    assert shared and all(r["state_rows"] == 2 for r in shared)
    for rid, prompt in prompts.items():
        alone = _serve(eng, {rid + "_alone": prompt})[rid + "_alone"]
        assert alone[0] == together[rid][0]
        np.testing.assert_allclose(alone[1], together[rid][1], atol=TOL)


def test_a_reused_slot_gives_what_a_fresh_one_gives(eng, params):
    """The second sequence of a slot starts from zeros, whatever the first
    left there."""
    kv = eng.scheduler.kv
    seen = {}
    _serve(eng, {"first": _prompt(3, 70)},
           after_step=lambda n: seen.setdefault("first",
                                                kv.state_slot("first")))
    slot = seen["first"]
    assert slot >= 0 and kv.state_slot("first") == -1  # given back
    rec = eng.runner.state_caches[0][1]
    assert float(jnp.abs(rec[slot]).max()) > 0  # and left dirty
    prompt = _prompt(4, 50)
    ids, served = _serve(
        eng, {"second": prompt},
        after_step=lambda n: seen.setdefault("second",
                                             kv.state_slot("second")))[
        "second"]
    assert seen["second"] == slot
    want = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, want)) < TOL


def test_a_preempted_sequence_equals_an_unpreempted_one(eng, params):
    """Preemption recomputes from a zeroed slot: the tokens generated
    before it are re-prefilled with the prompt, and what follows equals
    the unpreempted run (and the reference)."""
    prompt = _prompt(5, 75)
    want_ids, want_lp = _serve(eng, {"calm": prompt}, n_new=7)["calm"]
    resets = eng.scheduler.num_state_resets

    def preempt(step):
        _, req = eng.scheduler.find_request("hit")
        if req is not None and len(req.output_token_ids) == 3 \
                and eng.scheduler.num_state_resets == resets:
            eng.scheduler._preempt(req)

    ids, served = _serve(eng, {"hit": prompt}, n_new=7,
                         after_step=preempt)["hit"]
    assert eng.scheduler.num_state_resets == resets + 1
    assert eng.metrics_snapshot()["state"]["state_resets_total"] == resets + 1
    assert ids == want_ids
    np.testing.assert_allclose(served, want_lp, atol=TOL)
    ref = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, ref)) < TOL


def test_a_shared_first_page_yields_no_prefix_hit(eng, params):
    """Two prompts share their first two pages (a chat template's): the
    index finds them, and the hit is refused and counted, because pages
    hold no recurrent state.  The second answer still agrees with the
    reference, which a served hit would break."""
    head = _prompt(6, 32)
    before = eng.metrics_snapshot()
    _serve(eng, {"tmpl_a": head + _prompt(7, 20)})
    prompt = head + _prompt(8, 25)
    ids, served = _serve(eng, {"tmpl_b": prompt})["tmpl_b"]
    after = eng.metrics_snapshot()
    assert after["prefix_cache"]["enabled"]
    assert after["prefix_cache"]["hits"] == before["prefix_cache"]["hits"] == 0
    assert (after["state"]["prefix_hits_refused_total"]
            == before["state"]["prefix_hits_refused_total"] + 1)
    want = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, want)) < TOL


def test_the_state_cache_is_counted(eng):
    """Occupancy and the slots' life in /metrics?format=json and the
    flight record (docs/observability.md)."""
    _serve(eng, {"count_a": _prompt(9, 20), "count_b": _prompt(10, 20)})
    snap = eng.metrics_snapshot()
    assert snap["state"]["slots_total"] == 4
    assert snap["state"]["slots_in_use"] == 0
    assert snap["state"]["state_slot_allocs_total"] >= 2
    hist = snap["state_slots"]
    assert hist["count"] > 0 and 1 <= hist["sum"] / hist["count"] <= 4
    assert all(r["state_rows"] == r["prefills"] + r["decodes"]
               for r in eng.flight.tail())
    assert snap["device_memory"]["components"]["state_slots"]["bytes"] > 0


@pytest.mark.parametrize("kwargs, names", [
    (dict(kv_offload=True, kv_offload_policy="always"), "kv_offload"),
    (dict(engine_role="prefill"), "kv_transfer"),
    (dict(engine_role="decode"), "kv_transfer"),
    (dict(num_speculative_tokens=2), "speculative decoding"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype int8"),
    (dict(tensor_parallel_size=2), "tensor_parallel_size"),
])
def test_what_cannot_carry_state_is_refused_at_construction(
        params, kwargs, names):
    """Parking / tiered offload, KV transfer, speculative verify rows and
    an int8 state fail when the engine is built, with the mechanism's
    name: none of them moves or rolls back a state slot yet."""
    with pytest.raises(ValueError, match=names):
        LLMEngine(params, CFG, EngineConfig(**ENGINE, **kwargs))


def test_kv_transfer_trigger_is_refused_too(params):
    from vllm_omni_tpu.core.scheduler import KVTransferConfig

    with pytest.raises(ValueError, match="inject_kv / extract_kv"):
        LLMEngine(params, CFG, EngineConfig(
            **ENGINE, kv_transfer=KVTransferConfig()))
