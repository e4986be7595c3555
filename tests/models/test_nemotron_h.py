"""NVIDIA-Nemotron-3-Super on the normal path (LLMEngine -> scheduler ->
KVCacheManager -> ARModelRunner) at tiny widths that keep what the
published model forces (a layer of ONE sublayer: a Mamba-2 mixer with four
groups of B and C and a gated norm a group at a time, an attention mixer
without positions, or ungated relu^2 experts in a latent half the model's
width picked by a sigmoid router with a correction bias and a scale, with
an ungated shared expert; an untied head), against the plain float32
reference of benchmark/archs/nemotron_h.py (the recurrence as a scan over
tokens, dense attention, every held expert computed).

Logprobs are compared, never sampled tokens: with random weights the
largest logit changes on rounding.

The tolerance.  Engine and reference are both float32 (the CPU's matmuls
are full precision) and differ by the order of summation: the served rule
runs chunks of 8 tokens as matrix products a group at a time and one-token
rows through the recurrence, the convolution adds the carried rows' taps,
attention runs the flash recurrence over pages, the experts run as grouped
matmuls in the latent.  That is a few float32 roundings (6e-8 relative)
through 6 sublayers on logits of order 1: readings are 5e-7 to 3e-6.  The
limit is 2e-5.  The controls read 1e-4 and more: a state kept in bfloat16,
one group where there are four, a rotated key, a scale left at 1, another
activation (the router's logits rounded to bfloat16 are the control of
tests/ops/test_moe_latent.py, over more tokens than a prompt here has).
The experts here are unrelated draws (``tfm.init_params``, not the
benchmark's related ones), so a single wrong pick shows at full size.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import nemotron_h as arch
from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models import registry
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.models.nemotron_h import (
    NEMOTRON_3_SUPER_120B_A12B,
    expert_share,
    layer_kinds,
    real_factory,
    tiny_config,
)
from vllm_omni_tpu.sampling_params import SamplingParams

TOL = 2e-5
VOCAB = 256
CFG = tiny_config(VOCAB)
#: the reference reads the keys of a configuration file
CONFIG = {"layer_norm_epsilon": CFG.rms_eps, "head_dim": CFG.head_dim,
          "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 128,
          "n_groups": 4, "num_experts_per_tok": 3,
          "routed_scaling_factor": 2.5}
ENGINE = dict(num_pages=64, page_size=16, max_model_len=512, max_num_seqs=4,
              max_num_batched_tokens=64, enable_chunked_prefill=True,
              dtype=jnp.float32)


def _prompt(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(3, VOCAB, n).tolist()


def _draw(cfg):
    """The program's draw, the embedding's rows a unit wide (the plain
    0.02 leaves the first sublayer's input a hundredth of what the others
    add) and the routers' correction bias four times the plain draw's, so
    that it decides picks."""
    params = tfm.init_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    params["embed"]["w"] = params["embed"]["w"] * 50.0
    for layer in params["layers"]:
        if "router" in layer:
            layer["router"]["e_bias"] = layer["router"]["e_bias"] * 4.0
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
        engine.add_request(prompt, SamplingParams(
            max_tokens=n_new, temperature=0.0, ignore_eos=True, logprobs=0),
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
def test_a_layer_is_one_sublayer_in_the_weight_tree(params):
    """``M E * E M E``: a mixer layer holds ``input_norm`` and its mixer
    and NO feed-forward part; an expert layer ``post_norm`` and its experts
    and NO mixer: nothing stands in for the half a layer lacks."""
    mixers = {"mamba": {"input_norm", "mamba"},
              "full_attention": {"input_norm", "q_proj", "k_proj", "v_proj",
                                 "o_proj"},
              tfm.FEED_FORWARD: {"post_norm", "router", "experts",
                                 "shared_expert", "fc1_latent_proj",
                                 "fc2_latent_proj"}}
    assert CFG.layer_types == layer_kinds("ME*EME")
    for layer, kind in zip(params["layers"], CFG.layer_types):
        assert set(layer) == mixers[kind], kind
    assert CFG.one_sublayer and "lm_head" in params
    mb = params["layers"][0]["mamba"]
    # [z | xBC | dt]: 64 | 64 + 2 x 4 x 128 | 8
    assert CFG.mamba_conv_dim == 64 + 1024
    assert mb["in_proj"]["w"].shape == (64, 64 + 1088 + 8)
    assert mb["conv"]["w"].shape == (4, 1088) and mb["norm"]["w"].shape == (64,)
    ex = params["layers"][1]
    assert ex["experts"]["up"].shape == (8, 32, 24)      # in the latent
    assert ex["experts"]["down"].shape == (8, 24, 32)
    assert ex["fc1_latent_proj"]["w"].shape == (64, 32)
    assert ex["fc2_latent_proj"]["w"].shape == (32, 64)
    assert ex["router"]["w"].shape == (64, 8)            # on the model's row
    assert ex["shared_expert"]["up"]["w"].shape == (64, 48)
    assert ex["router"]["e_bias"].dtype == jnp.float32
    # a stack of two halves a layer keeps both in every layer
    both = tfm.init_params(jax.random.PRNGKey(0),
                           tfm.TransformerConfig.tiny_moe(), jnp.float32)
    assert all({"input_norm", "post_norm"} <= set(layer)
               for layer in both["layers"])


def test_a_layer_without_a_mixer_holds_no_cache(eng):
    """Six layers: one holds pages, two hold state slots, and the three
    expert layers neither."""
    assert (CFG.num_layers, CFG.num_kv_layers, CFG.num_state_layers) == (
        6, 1, 2)
    assert len(eng.runner.kv_caches.kv) == 1
    assert len(eng.runner.state_caches) == 2
    conv, state = eng.runner.state_caches[0]
    assert conv.shape == (4, 3, 1088) and state.shape == (4, 8, 8, 128)
    snap = eng.scheduler.kv
    assert snap.num_state_slots == 4
    assert eng._state_bytes_per_slot == 2 * 4 * (3 * 1088 + 8 * 8 * 128)


def test_the_published_constant_is_the_catalogs():
    m = NEMOTRON_3_SUPER_120B_A12B
    kinds = m.layer_types
    assert len(kinds) == 88
    assert (kinds.count("mamba"), kinds.count("full_attention"),
            kinds.count(tfm.FEED_FORWARD)) == (40, 8, 40)
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [
        7, 16, 25, 36, 47, 58, 69, 78]
    assert (m.num_kv_layers, m.num_state_layers) == (8, 40)
    # layers 25-35, the period the benchmark serves
    assert kinds[25:36] == layer_kinds("*EMEMEMEMEM")
    assert m.mamba_conv_dim == 8192 + 2 * 8 * 128 == 10240
    assert m.state_shapes("mamba") == ((3, 10240), (128, 64, 128))
    assert 4 * (128 * 64 * 128 + 3 * 10240) == 4_317_184   # 4.32 MB
    layout = m.kv_layout
    assert (layout.heads, layout.width, layout.pools) == (2, 128, 2)
    assert 2 * layout.pools * layout.heads * layout.width == 1024  # 1 KB
    assert m.attn_kwargs == {} and not m.use_rope       # 128 ** -0.5
    assert (m.num_experts, m.num_experts_per_tok, m.moe_latent_size,
            m.moe_intermediate_size, m.shared_expert_size) == (
        512, 22, 1024, 2688, 5376)
    assert (m.router_scoring, m.router_bias, m.routed_scaling_factor,
            m.mlp_gated, m.mlp_activation) == ("sigmoid", True, 5.0, False,
                                               "relu2")
    share = expert_share(m, held=128, offset=0)
    assert (share.num_experts, share.router_experts) == (128, 512)
    with pytest.raises(ValueError, match="not built"):
        layer_kinds("M-*E")                 # a dense MLP layer
    assert registry._AR_MODELS["NemotronHForCausalLM"].module.endswith(
        "models.nemotron_h")
    with pytest.raises(NotImplementedError, match="no checkpoint loader"):
        real_factory("/nowhere")


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
    """Prefill in chunks, then decode through pages and slots.  150 tokens
    are longer than max_num_batched_tokens (64), so the state crosses two
    step boundaries and eighteen chunk boundaries of the rule; 40 fit one
    step."""
    assert _worst(eng, params, f"ref{n_prompt}",
                  _prompt(n_prompt, n_prompt)) < TOL


def test_an_expert_share_agrees_with_the_references_share(params):
    """Experts 4-7 of the 8-wide router held here, through the engine: the
    reference is given the same share (``expert_offset``) and what the
    absent experts would add is left out in both."""
    cfg = expert_share(CFG, held=4, offset=4)
    share = dict(params, layers=[
        dict(layer, experts={k: v[4:] for k, v in layer["experts"].items()})
        if "experts" in layer else layer for layer in params["layers"]])
    engine = LLMEngine(share, cfg, EngineConfig(**ENGINE))
    config = {**CONFIG, "expert_offset": 4}
    assert _worst(engine, share, "share", _prompt(7, 90), config=config) < TOL
    # and the share is not the whole: against the uncut reference it reads
    # what the absent experts add
    assert _worst(engine, params, "whole", _prompt(7, 90)) > 50 * TOL


# ------------------------------------------------------------ the controls
def test_a_bfloat16_state_fails_the_tolerance(params):
    """The same engine with the rule's state and the convolution's rows
    kept in bfloat16 between steps is outside the tolerance, so the
    tolerance can see the precision the file states."""
    engine = LLMEngine(params, CFG, EngineConfig(**ENGINE))
    engine.runner.state_caches = [
        tuple(a.astype(jnp.bfloat16) for a in state)
        for state in engine.runner.state_caches]
    assert _worst(engine, params, "bf16", _prompt(150, 150)) > 5 * TOL


@pytest.mark.parametrize("altered", [
    dict(mamba_n_groups=1),        # one norm over all channels, B and C...
    dict(use_rope=True),           # a rotated key
    dict(routed_scaling_factor=1.0),
    dict(mlp_activation="silu"),   # ungated silu, not relu^2
], ids=["groups", "rotated", "scale", "activation"])
def test_an_altered_form_fails_the_tolerance(params, altered):
    """The program with one of the model's departures undone, served
    against the model's reference: each reads far over the limit.  With
    one group the in projection's columns no longer hold eight B and C, so
    the weights are drawn for it and the reference keeps the four groups'
    reading of a prefix of them: what differs is the model."""
    cfg = dataclasses.replace(CFG, **altered)
    served = params if "mamba_n_groups" not in altered else _draw(cfg)
    engine = LLMEngine(served, cfg, EngineConfig(**ENGINE))
    ids, got = _serve(engine, {"altered": _prompt(40, 40)}, n_new=6)[
        "altered"]
    want = _reference(params, _prompt(40, 40), ids)
    assert max(abs(a - b) for a, b in zip(got, want)) > 20 * TOL


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


def test_what_any_model_with_state_slots_is_refused(params):
    """Speculation, an int8 cache and tensor parallelism are refused at
    construction, as for every model with state slots (the MTP module is
    not served for that reason)."""
    for asked in (dict(num_speculative_tokens=2),
                  dict(kv_cache_dtype="int8"),
                  dict(tensor_parallel_size=2)):
        with pytest.raises(ValueError, match="recurrent"):
            LLMEngine(params, CFG, EngineConfig(**ENGINE, **asked))
