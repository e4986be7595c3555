"""granite-4.0-h-small on the normal path (LLMEngine -> scheduler ->
KVCacheManager -> ARModelRunner) at tiny widths that keep what the
published model forces (Mamba-2 layers whose state is updated in its slot,
an attention layer without positions at a scale that is not the head's,
the four multipliers, an ungated shared expert beside a softmax router
renormalised over its picks, tied embeddings), against the plain float32
reference of benchmark/archs/granite_moe_hybrid.py (the recurrence as a
scan over tokens, dense attention, every held expert computed).

Logprobs are compared, never sampled tokens: with random weights the
largest logit changes on rounding.

The tolerance.  Engine and reference are both float32 (the CPU's matmuls
are full precision) and differ by the order of summation: the served rule
runs chunks of 8 tokens as matrix products (exp(x) exp(y) against exp(x +
y), sums over a chunk in another order) and one-token rows through the
recurrence, the convolution adds the carried rows' taps, attention runs
the flash recurrence over pages, the experts run as grouped matmuls.  That
is a few float32 roundings (6e-8 relative) through 4 layers on logits of
order 1: readings are 1e-6 to 5e-6.  The limit is 2e-5.  Each of the four
altered forms (a carried state zeroed, a key rotated, a multiplier left at
1, a scale of head_dim ** -0.5) reads 1e-3 or more and a state kept in
bfloat16 2e-4: all fail it.  The experts here are unrelated draws
(``tfm.init_params``, not the benchmark's related ones), so a single wrong
pick shows at full size; the embedding's and the final norm's gains are
the benchmark's (``arch.EMBED_SHRINK``, ``arch.HEAD_GAIN``), without which
the logits are too flat for any of this to show.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import granite_moe_hybrid as arch
from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.models.granite_moe_hybrid import (
    GRANITE_4_0_H_SMALL,
    expert_share,
    tiny_config,
)
from vllm_omni_tpu.sampling_params import SamplingParams

TOL = 2e-5
VOCAB = 256
CFG = tiny_config(VOCAB)
#: the reference reads the keys of a configuration file
CONFIG = {"rms_norm_eps": CFG.rms_eps, "hidden_size": CFG.hidden_size,
          "num_attention_heads": CFG.num_heads,
          "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 128,
          "num_experts_per_tok": 3, "attention_multiplier": 1.0 / 16,
          "embedding_multiplier": 12, "residual_multiplier": 0.22,
          "logits_scaling": 16}
ENGINE = dict(num_pages=64, page_size=16, max_model_len=512, max_num_seqs=4,
              max_num_batched_tokens=64, enable_chunked_prefill=True,
              dtype=jnp.float32)


def _prompt(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(3, VOCAB, n).tolist()


def _draw(cfg):
    """The program's draw with the benchmark's two gains on the embedding
    and the final norm (the routers and q/k as drawn)."""
    params = tfm.init_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    params["embed"]["w"] = params["embed"]["w"] * arch.EMBED_SHRINK
    params["final_norm"]["w"] = params["final_norm"]["w"] * arch.HEAD_GAIN
    return params


@pytest.fixture(scope="module")
def params():
    return _draw(CFG)


@pytest.fixture(scope="module")
def eng(params):
    return LLMEngine(params, CFG, EngineConfig(**ENGINE))


def _serve(engine, prompts: dict, n_new=5, after_step=None) -> dict:
    """request id -> (generated ids, their served logprobs), greedy."""
    for rid, prompt in prompts.items():
        n = n_new[rid] if isinstance(n_new, dict) else n_new
        engine.add_request(prompt, SamplingParams(
            max_tokens=n, temperature=0.0, ignore_eos=True, logprobs=0),
            request_id=rid)
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


def _reference(params, prompt, ids, config=CONFIG):
    ref = arch.reference_logprobs(params, config, prompt + ids[:-1], len(ids))
    return [float(ref[i, t]) for i, t in enumerate(ids)]


def _worst(engine, params, rid, prompt, n_new=6, config=CONFIG):
    ids, served = _serve(engine, {rid: prompt}, n_new=n_new)[rid]
    want = _reference(params, prompt, ids, config)
    return max(abs(a - b) for a, b in zip(served, want))


# ------------------------------------------------------- the weight tree
def test_the_weight_tree_is_the_models(params):
    kinds = ["mamba" if "mamba" in layer else "attention"
             for layer in params["layers"]]
    assert kinds == ["mamba", "mamba", "attention", "mamba"]
    assert all("experts" in layer and "shared_expert" in layer
               and "shared_gate" not in layer for layer in params["layers"])
    assert "lm_head" not in params
    attn = params["layers"][2]
    assert "q_norm" not in attn and attn["q_proj"]["w"].shape == (64, 64)
    mb = params["layers"][0]["mamba"]
    # [z | xBC | dt]: 32 | 32 + 2 x 128 | 4
    assert mb["in_proj"]["w"].shape == (64, 32 + 288 + 4)
    assert mb["conv"]["w"].shape == (4, 288) and mb["conv"]["b"].shape == (288,)
    assert float(jnp.abs(mb["conv"]["b"]).max()) > 0
    assert mb["A_log"].shape == mb["dt_bias"].shape == mb["D"].shape == (4,)
    assert mb["norm"]["w"].shape == (32,)
    assert CFG.num_state_layers == 3 and CFG.num_kv_layers == 1
    assert CFG.state_shapes("mamba") == ((3, 288), (4, 8, 128))
    assert CFG.attn_kwargs == {"scale": 1.0 / 16}


def test_the_published_constant_is_the_catalogs():
    m = GRANITE_4_0_H_SMALL
    assert m.layer_types.count("mamba") == 36
    assert [i for i, k in enumerate(m.layer_types)
            if k == "full_attention"] == [5, 15, 25, 35]
    assert m.state_shapes("mamba") == ((3, 8448), (128, 64, 128))
    # 4.19 MB of state and 0.10 MB of convolution inputs a sequence a layer
    assert 4 * 128 * 64 * 128 == 4_194_304 and 4 * 3 * 8448 == 101_376
    layout = m.kv_layout
    assert (layout.heads, layout.width, layout.pools) == (8, 128, 2)
    assert 2 * layout.pools * layout.heads * layout.width == 4096
    assert m.attn_kwargs == {"scale": 1.0 / 128} and not m.use_rope
    assert (m.embedding_multiplier, m.residual_multiplier,
            m.logits_scaling) == (12.0, 0.22, 16.0)


# ------------------------------------------------- against the reference
def test_the_whole_forward_agrees_with_the_reference(params):
    """``forward_hidden`` (no cache, no pages: two sequences side by side)
    against the reference, every position's logprobs."""
    a, b = _prompt(20, 45), _prompt(21, 45)
    hidden = tfm.forward_hidden(params, CFG, jnp.asarray([a, b]))
    got = jax.nn.log_softmax(
        tfm.logits_from_hidden(params, CFG, hidden), -1)
    for row, prompt in zip(got, (a, b)):
        want = arch.reference_logprobs(params, CONFIG, prompt, len(prompt))
        assert float(jnp.abs(row - want).max()) < TOL


@pytest.mark.parametrize("n_prompt", [150, 40])
def test_engine_agrees_with_the_reference(eng, params, n_prompt):
    """Prefill, then decode through pages and slots.  150 tokens are
    longer than max_num_batched_tokens (64), so the state crosses two step
    boundaries and eighteen chunk boundaries of the rule; 40 fit one
    step."""
    assert _worst(eng, params, f"ref{n_prompt}",
                  _prompt(n_prompt, n_prompt)) < TOL


def test_a_bfloat16_state_fails_the_tolerance(params):
    """The control: the same engine with the rule's state and the
    convolution's rows kept in bfloat16 between steps is outside the
    tolerance, so the tolerance can see the precision the file states."""
    engine = LLMEngine(params, CFG, EngineConfig(**ENGINE))
    engine.runner.state_caches = [
        tuple(a.astype(jnp.bfloat16) for a in state)
        for state in engine.runner.state_caches]
    # reads 1.9e-4: the state's 8 bits arrive through a norm over all heads
    assert _worst(engine, params, "bf16", _prompt(150, 150)) > 5 * TOL


# ------------------------------------------ the four altered forms fail
def test_a_zeroed_carried_state_fails(params):
    """A state that is not carried across a step's boundary: the slots
    zeroed between the prompt's second chunk and its third."""
    engine = LLMEngine(params, CFG, EngineConfig(**ENGINE))

    def zero(step):
        if step == 2:
            engine.runner.state_caches = [
                tuple(jnp.zeros_like(a) for a in state)
                for state in engine.runner.state_caches]

    prompt = _prompt(150, 150)
    ids, served = _serve(engine, {"zeroed": prompt}, n_new=6,
                         after_step=zero)["zeroed"]
    want = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, want)) > 50 * TOL


@pytest.mark.parametrize("altered", [
    dict(use_rope=True),                 # a rotated key
    dict(embedding_multiplier=1.0),
    dict(residual_multiplier=1.0),
    dict(logits_scaling=1.0),
    dict(attention_multiplier=None),     # head_dim ** -0.5
], ids=["rotated", "embedding", "residual", "logits", "scale"])
def test_an_altered_form_fails_the_tolerance(params, altered):
    """The program with one of the model's departures undone, served
    against the model's reference: each reads far over the limit (the
    rotated key, in one layer of four at a flat softmax, 6e-4: the least)."""
    cfg = dataclasses.replace(CFG, **altered)
    engine = LLMEngine(params, cfg, EngineConfig(**ENGINE))
    assert _worst(engine, params, "altered", _prompt(40, 40)) > 20 * TOL


# ------------------------------------------------------ slots and pages
def test_two_sequences_in_one_unified_step_equal_each_alone(eng):
    """Unequal lengths packed into the same steps (chunks of both in one
    ragged batch, then one-token rows beside a chunk: the recurrent form
    and the chunked form in one dispatch) against each alone."""
    prompts = {"pair_a": _prompt(1, 90), "pair_b": _prompt(2, 37)}
    together = _serve(eng, prompts)
    shared = [r for r in eng.flight.tail()
              if {"pair_a", "pair_b"} <= set(r["requests"])]
    assert shared and all(r["state_rows"] == 2 for r in shared)
    assert any(r["prefills"] and r["decodes"] for r in shared)
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
    conv, state = eng.runner.state_caches[0]
    assert conv.shape == (4, 3, 288) and state.shape == (4, 4, 8, 128)
    assert float(jnp.abs(state[slot]).max()) > 0  # and left dirty
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
    assert ids == want_ids
    np.testing.assert_allclose(served, want_lp, atol=TOL)
    ref = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, ref)) < TOL


def test_a_shared_first_page_yields_no_prefix_hit(eng, params):
    """Two prompts share their first two pages: the hit is refused and
    counted, because pages hold no state."""
    head = _prompt(6, 32)
    before = eng.metrics_snapshot()
    _serve(eng, {"tmpl_a": head + _prompt(7, 20)})
    prompt = head + _prompt(8, 25)
    ids, served = _serve(eng, {"tmpl_b": prompt})["tmpl_b"]
    after = eng.metrics_snapshot()
    assert after["prefix_cache"]["hits"] == before["prefix_cache"]["hits"] == 0
    assert (after["state"]["prefix_hits_refused_total"]
            == before["state"]["prefix_hits_refused_total"] + 1)
    want = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, want)) < TOL


def test_sequences_joining_and_leaving_equal_each_alone(params):
    """24 requests of unequal prompts and lengths through an engine of 8
    seats: they wait, join full decode batches beside others' chunks and
    leave at different steps, slots and pages handed on.  Every one gives
    the tokens and logprobs it gives alone."""
    engine = LLMEngine(params, CFG, EngineConfig(
        **{**ENGINE, "max_num_seqs": 8, "num_pages": 128,
           "max_num_batched_tokens": 128}))
    rng = np.random.default_rng(64)
    prompts = {f"s{i}": _prompt(100 + i, int(rng.integers(5, 60)))
               for i in range(24)}
    n_new = {rid: int(rng.integers(4, 16)) for rid in prompts}
    held = []
    together = _serve(
        engine, prompts, n_new=n_new,
        after_step=lambda n: held.append(
            engine.scheduler.kv.state_slots_in_use))
    assert max(held) >= 6 and held[-1] == 0
    for rid, prompt in prompts.items():
        alone = _serve(engine, {rid + "_alone": prompt},
                       n_new=n_new[rid])[rid + "_alone"]
        assert alone[0] == together[rid][0], rid
        np.testing.assert_allclose(alone[1], together[rid][1], atol=TOL)


# --------------------------------------------------- the share and the MLP
def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """One sparse layer at the router's whole width against its four
    shares of two experts each: the routed parts add up and the shared
    MLP, which every chip computes alike, is counted once."""
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(7), (24, CFG.hidden_size))
    whole = tfm._mlp(layer, CFG, x)
    shared = tfm._mlp({k: v for k, v in layer.items()
                       if k != "experts" and k != "router"}
                      | {"gate_up": layer["shared_expert"]["gate_up"],
                         "down": layer["shared_expert"]["down"]}, CFG, x)
    parts = []
    for offset in range(0, 8, 2):
        cfg = expert_share(CFG, held=2, offset=offset)
        cut = dict(layer, experts={k: v[offset:offset + 2]
                                   for k, v in layer["experts"].items()})
        parts.append(tfm._mlp(cut, cfg, x) - shared)
        with jax.default_matmul_precision("highest"):
            want = arch._mlp(cut, x, 3, offset)
        assert float(jnp.abs(tfm._mlp(cut, cfg, x) - want).max()) < TOL
    assert float(jnp.abs(sum(parts) + shared - whole).max()) < TOL
    # a share is no rescaled whole: it is smaller by what the others hold
    assert float(jnp.abs(parts[0]).max()) > 1e-3
    assert float(jnp.abs(parts[0] + shared - whole).max()) > 1e-3


def test_the_router_weighs_by_a_softmax_over_the_kept_logits(params):
    """The ten (here three) kept logits' softmax, not the softmax over all
    experts cut to the kept ones: the same numbers, which the reference's
    form and the program's form must both give."""
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(8), (16, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = arch._mlp(layer, x, 3, 0)
    assert float(jnp.abs(tfm._mlp(layer, CFG, x) - want).max()) < TOL
    unnormalised = dataclasses.replace(CFG, moe_renormalize=False)
    assert float(jnp.abs(tfm._mlp(layer, unnormalised, x) - want).max()) \
        > 1e-3


# --------------------------------------------------------------- counted
def test_the_state_cache_is_counted(eng):
    """Occupancy, the slots' bytes and the chunks' fill in
    /metrics?format=json and the flight record (docs/observability.md)."""
    before = eng.metrics_snapshot()["ssd_chunk_fill"]
    _serve(eng, {"count_a": _prompt(9, 20), "count_b": _prompt(10, 20)})
    snap = eng.metrics_snapshot()
    assert snap["state"]["slots_total"] == 4
    assert snap["state"]["slots_in_use"] == 0
    # a sequence: three mamba layers x ([3, 288] + [4, 8, 128]) float32
    per_layer = 4 * (3 * 288 + 4 * 8 * 128)
    assert snap["state"]["bytes_per_slot_layer"] == per_layer
    assert snap["state"]["bytes_per_slot"] == 3 * per_layer
    assert snap["device_memory"]["components"]["state_slots"]["bytes"] \
        == 4 * 3 * per_layer
    hist = snap["state_slots"]
    assert hist["count"] > 0 and 1 <= hist["sum"] / hist["count"] <= 4
    # one ragged step held both prompts: 40 tokens in 2 x 3 chunks of 8
    fill = snap["ssd_chunk_fill"]
    assert fill["count"] == before["count"] + 1
    assert fill["sum"] - before["sum"] == pytest.approx(100.0 * 40 / 48,
                                                       abs=1e-3)
    assert all(r["state_rows"] == r["prefills"] + r["decodes"]
               for r in eng.flight.tail())
    kv = snap["kv"]
    assert (kv["layout"], kv["row_width"]) == ("kv", 16)


@pytest.mark.parametrize("kwargs, names", [
    (dict(kv_offload=True, kv_offload_policy="always"), "kv_offload"),
    (dict(engine_role="prefill"), "kv_transfer"),
    (dict(num_speculative_tokens=2), "speculative decoding"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype int8"),
    (dict(tensor_parallel_size=2), "tensor_parallel_size"),
])
def test_what_cannot_carry_state_is_refused_at_construction(
        params, kwargs, names):
    """Whatever moves, copies or rolls back pages alone is refused for a
    model whose sequences hold a slot of state, as for Qwen3-Next."""
    with pytest.raises(ValueError, match=names):
        LLMEngine(params, CFG, EngineConfig(**ENGINE, **kwargs))
