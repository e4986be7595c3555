"""MiMo-V2.5's language model on the normal path (LLMEngine -> scheduler ->
KVCacheManager -> ARModelRunner) at tiny COUNTS and the published head
widths, which are what the model forces (a full layer with a dense MLP,
three sliding layers and a full one; 4 q heads of 192, 64 of them rotated,
on 2 kv heads in the sliding layers and 1 in the full ones; values of 128
times 0.707; a window of 21 keys, which is not a multiple of the 16-token
page, with a learned sink a head in the sliding layers' softmax; a rotary
base a kind; 8 experts top-2 by renormalised sigmoid score with a
correction bias), against the plain float32 reference of
benchmark/archs/mimo_v2.py (a dense masked softmax with the sink in its
denominator, every held expert computed).

Logprobs are compared, never sampled tokens: with random weights the
largest logit changes on rounding.

The tolerance.  Engine and reference are both float32 (the CPU's matmuls
are full precision) and differ by the order of summation: attention runs
the flash recurrence over pages of two kinds whose K rows are 256 lanes
wide (64 of them zeros) and starts it at the sink, the experts run as
grouped matmuls.  That is a few float32 roundings (6e-8 relative) through
5 layers on logits of order 1: readings are 3e-6 to 7e-6.  The limit is
3e-5.  Every control reads 1e-3 and more.  The experts here are unrelated
draws (``tfm.init_params``, not the benchmark's related ones), so a single
wrong pick shows at full size.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import mimo_v2 as arch
from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
from vllm_omni_tpu.kvcache import layouts
from vllm_omni_tpu.models import registry
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.models.mimo_v2 import (
    MIMO_V2_5,
    expert_share,
    real_factory,
    tiny_config,
)
from vllm_omni_tpu.sampling_params import SamplingParams

# the modules, not the functions of the same names that ``ops`` exports
pa = importlib.import_module("vllm_omni_tpu.ops.paged_attention")
rpa = importlib.import_module("vllm_omni_tpu.ops.ragged_paged_attention")

TOL = 3e-5
VOCAB = 256
CFG = tiny_config(VOCAB)
WINDOW = CFG.sliding_window
#: the reference reads the keys of a configuration file
CONFIG = {"layernorm_epsilon": CFG.rms_eps, "head_dim": 192,
          "v_head_dim": 128, "partial_rotary_factor": 0.334,
          "attention_value_scale": 0.707, "num_key_value_heads": 1,
          "swa_num_key_value_heads": 2, "rope_theta": 1e7,
          "swa_rope_theta": 1e4, "sliding_window": WINDOW,
          "num_experts_per_tok": 2, "hybrid_layer_pattern": [0, 1, 1, 1, 0]}
ENGINE = dict(num_pages=64, page_size=16, max_model_len=512, max_num_seqs=4,
              max_num_batched_tokens=64, enable_chunked_prefill=True,
              dtype=jnp.float32)


def _prompt(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(3, VOCAB, n).tolist()


def _draw(cfg):
    """The program's draw as the benchmark changes it: the embedding's rows
    eight times as wide (the plain 0.02 leaves the first layer's input a
    tenth of what the branches add), q and k four times (a score then
    deviates by some 5, so that WHICH keys a row attends decides its
    output), and the sinks moved up by 3 so that one holds a fair part of a
    row's mass (the initialiser's +-1 beside 21 sharp keys holds little)."""
    params = tfm.init_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    params["embed"]["w"] = params["embed"]["w"] * 8.0
    for layer in params["layers"]:
        for name in ("q_proj", "k_proj"):
            layer[name]["w"] = layer[name]["w"] * 4.0
        if "sinks" in layer:
            layer["sinks"] = layer["sinks"] + 3.0
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
def test_a_kind_of_layer_has_its_own_heads_and_the_sliding_one_a_sink(
        params):
    """q of 4 x 192, k of Hkv x 192, v of Hkv x 128, o from 4 x 128; Hkv 1
    on a full layer and 2 on a sliding one, which also holds one float32
    sink a query head; layer 0's MLP is dense, the others route over 8
    experts with a correction bias; the head is untied."""
    for layer, kind in zip(params["layers"], CFG.layer_types):
        kv = 2 if kind == "sliding_attention" else 1
        assert layer["q_proj"]["w"].shape == (64, 4 * 192)
        assert layer["k_proj"]["w"].shape == (64, kv * 192)
        assert layer["v_proj"]["w"].shape == (64, kv * 128)
        assert layer["o_proj"]["w"].shape == (4 * 128, 64)
        assert ("sinks" in layer) == (kind == "sliding_attention")
        assert {"input_norm", "post_norm"} <= set(layer)
    assert params["layers"][1]["sinks"].shape == (4,)
    assert params["layers"][1]["sinks"].dtype == jnp.float32
    assert "experts" not in params["layers"][0]
    assert params["layers"][0]["gate_up"]["w"].shape == (64, 2 * 96)
    for layer in params["layers"][1:]:
        assert layer["router"]["w"].shape == (64, 8)
        assert layer["router"]["e_bias"].shape == (8,)
        assert "shared_expert" not in layer
    assert "lm_head" in params
    # a model whose kinds differ in the window alone keeps ONE record
    plain = tfm.TransformerConfig.tiny_moe()
    assert plain.head_kind("sliding_attention") == plain.head_kind() \
        == tfm.HeadKind(2, plain.rope_theta, False)
    assert plain.value_dim == plain.head_dim


def test_the_two_kinds_of_page_differ_in_shape(eng):
    """Five layers hold pages: two of the full kind (1 kv head), three of
    the window kind (2 kv heads); a K row of 256 lanes (192 used) beside a
    V row of 128, accounted a kind; the kernels are told the window and
    handed the sinks, and nothing else (the scale is the head's)."""
    assert (CFG.num_kv_layers, CFG.num_window_layers,
            CFG.num_state_layers) == (5, 3, 0)
    window_pages = -(-4 * (WINDOW + 64) // 16) + 4 + 1
    shapes = [[a.shape for a in cache] for cache in eng.runner.kv_caches]
    full = [(1, 64, 16, 256), (1, 64, 16, 128)]
    wide = [(2, window_pages, 16, 256), (2, window_pages, 16, 128)]
    assert shapes == [full, wide, wide, wide, full]
    kinds = eng.metrics_snapshot()["kv"]["kinds"]
    for kind, heads, pages in (("full_attention", 1, 64),
                               ("sliding_attention", 2, window_pages)):
        rec = kinds[kind]
        assert (rec["heads"], rec["row_width"], rec["row_used"],
                rec["value_width"], rec["pages_total"]) == (
            heads, 256, 192, 128, pages)
        # float32 here: heads x (256 + 128) lanes x 4 B held
        assert rec["bytes_per_token_layer"] == heads * 384 * 4
        assert rec["lanes_padding_pct"] == pytest.approx(100 * 64 / 384,
                                                         abs=0.01)
    assert eng._kv_bytes_per_token == (2 * 1 + 3 * 2) * 384 * 4
    assert CFG.attn_kwargs_of("sliding_attention") == {"window": WINDOW}
    assert CFG.attn_kwargs_of("full_attention") == {}
    from vllm_omni_tpu.metrics.prometheus import render_exposition

    text = render_exposition({}, {0: eng.metrics_snapshot()})
    assert 'kv_kind_heads{kind="sliding_attention",stage="0"} 2' in text \
        or 'kv_kind_heads{stage="0",kind="sliding_attention"} 2' in text
    assert "kv_kind_row_used" in text and "kv_kind_pages_total" in text


def test_the_published_constant_is_the_catalogs():
    m = MIMO_V2_5
    kinds = m.layer_types
    assert len(kinds) == 48 and kinds.count("sliding_attention") == 39
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [
        0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert (m.hidden_size, m.num_heads, m.head_dim, m.v_head_dim,
            m.rotary_dim) == (4096, 64, 192, 128, 64)
    assert m.head_kind("full_attention") == tfm.HeadKind(4, 1e7, False)
    assert m.head_kind("sliding_attention") == tfm.HeadKind(8, 1e4, True)
    for kind, heads in (("full_attention", 4), ("sliding_attention", 8)):
        layout = m.kv_layout_of(kind)
        assert (layout.kind, layout.heads, layout.width, layout.used,
                layout.v_width, layout.pools) == ("kv", heads, 256, 192,
                                                  128, 2)
        # bytes a token a layer in bfloat16: held and used
        assert 2 * heads * layout.lanes == {4: 3072, 8: 6144}[heads]
        assert 2 * heads * layout.lanes_used == {4: 2560, 8: 5120}[heads]
    assert m.attn_kwargs_of("sliding_attention") == {"window": 128}
    assert m.attn_kwargs_of("full_attention") == {}
    assert (m.attention_value_scale, m.sliding_window, m.rms_eps,
            m.vocab_size, m.tie_word_embeddings) == (
        0.707, 128, 1e-5, 152576, False)
    assert (m.num_experts, m.num_experts_per_tok, m.moe_intermediate_size,
            m.intermediate_size, m.first_k_dense_replace,
            m.shared_expert_size) == (256, 8, 2048, 16384, 1, 0)
    assert (m.router_scoring, m.router_bias, m.routed_scaling_factor,
            m.moe_renormalize) == ("sigmoid", True, 1.0, True)
    # the issue's count: 308.8 B, 15.4 B active
    sliding = 4096 * (64 * 192 + 8 * 320) + 64 * 128 * 4096
    full = 4096 * (64 * 192 + 4 * 320) + 64 * 128 * 4096
    expert, router = 3 * 4096 * 2048, 4096 * 256
    assert (sliding, full) == (94_371_840, 89_128_960)
    rest = 3 * 4096 * 16384 + 2 * 152576 * 4096
    total = 39 * sliding + 9 * full + 47 * (router + 256 * expert) + rest
    active = 39 * sliding + 9 * full + 47 * (router + 8 * expert) + rest
    assert 308.5e9 < total < 309.1e9 and 15.2e9 < active < 15.6e9
    share = expert_share(m, held=16, offset=0)
    assert (share.num_experts, share.router_experts) == (16, 256)
    assert registry._AR_MODELS["MiMoV2ForCausalLM"].module.endswith(
        "models.mimo_v2")
    with pytest.raises(NotImplementedError, match="no checkpoint loader"):
        real_factory("/nowhere")


# ------------------------------------------------- against the reference
@pytest.mark.parametrize("n_prompt", [150, 40, 21, 20])
def test_engine_agrees_with_the_reference(eng, params, n_prompt):
    """Prefill in chunks, then decode through the two kinds of page.  150
    tokens are longer than max_num_batched_tokens (64) and seven times the
    window: chunk boundaries at 64 and 128 lie past it, and by the compared
    tokens the sliding layers have given eight of their ten pages back; 40
    fit one step and cross the window INSIDE the chunk; 21 and 20 sit at
    it and under it."""
    before = eng.scheduler.kv.window_pages_freed
    assert _worst(eng, params, f"ref{n_prompt}",
                  _prompt(n_prompt, n_prompt)) < TOL
    freed = eng.scheduler.kv.window_pages_freed - before
    # what slid out of reach before the last token: whole pages below
    # position ``n_prompt + 5 - window``
    assert freed == max(n_prompt + 5 - WINDOW, 0) // 16


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
    assert _worst(engine, params, "whole", _prompt(7, 90)) > 30 * TOL


# ------------------------------------------------------------ the controls
def _swap_bases(cfg):
    return dataclasses.replace(
        cfg, rope_theta=cfg.sliding_heads.rope_theta,
        sliding_heads=dataclasses.replace(cfg.sliding_heads,
                                          rope_theta=cfg.rope_theta))


ALTERED = {
    # the window off: every key attended
    "window_off": lambda cfg: dataclasses.replace(cfg,
                                                  sliding_window=10 ** 6),
    "value_scale_1": lambda cfg: dataclasses.replace(
        cfg, attention_value_scale=1.0),
    "rotary_bases_swapped": _swap_bases,
    "all_192_dims_rotated": lambda cfg: dataclasses.replace(
        cfg, partial_rotary_factor=1.0),
}


@pytest.mark.parametrize("name", sorted(ALTERED))
def test_an_altered_configuration_fails_the_tolerance(params, name):
    """The program with one of the model's departures undone, served
    against the model's reference: each reads far over the limit."""
    engine = LLMEngine(params, ALTERED[name](CFG), EngineConfig(**ENGINE))
    assert _worst(engine, params, "altered", _prompt(90, 90)) > 30 * TOL


def _float8_write(k_cache, v_cache, k, v, slots, _write=tfm.write_kv_cache):
    def mantissa3(x):
        # float32 rounded to float8 e4m3's three bits of mantissa
        bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                            jnp.uint32)
        bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    return _write(k_cache, v_cache, mantissa3(k), mantissa3(v), slots)


def _sink_as_a_key(s, mask, sink, _softmax=pa.sink_softmax):
    """The sink with a VALUE (the first key the row admits lends it its
    own): the mass the sink took is handed to that key, and a row's
    weights sum to one."""
    p = _softmax(s, mask, sink)
    first = jnp.argmax(mask, axis=-1, keepdims=True)
    at_first = jnp.arange(s.shape[-1]) == first
    return p + jnp.where(at_first & mask,
                         1.0 - jnp.sum(p, -1, keepdims=True), 0.0)


def _no_sinks(op):
    return lambda *a, **kw: op(
        *a, **{k: v for k, v in kw.items() if k != "sinks"})


@pytest.mark.parametrize("name", ["no_sink", "sink_as_a_key",
                                  "float8_pages"])
def test_an_altered_served_path_fails_the_tolerance(params, name,
                                                    monkeypatch):
    """What no configuration field switches, taken out of the served path
    by hand (as ``scripts/sparse_window_control.py`` does on the chip): the
    kernels not handed the sinks; the sink given a value, so that a row's
    weights sum to one; the rows rounded to float8's mantissa on their way
    into the pages."""
    if name == "no_sink":
        for op in ("paged_attention", "ragged_paged_attention"):
            monkeypatch.setattr(tfm, op, _no_sinks(getattr(tfm, op)))
    elif name == "sink_as_a_key":
        monkeypatch.setattr(pa, "sink_softmax", _sink_as_a_key)
        monkeypatch.setattr(rpa, "sink_softmax", _sink_as_a_key)
    else:
        monkeypatch.setattr(tfm, "write_kv_cache", _float8_write)
    # the ops are jitted functions of their own: what a sound test traced
    # must not be found again, here or (the altered trace) after
    jax.clear_caches()
    try:
        engine = LLMEngine(params, CFG, EngineConfig(**ENGINE))
        assert _worst(engine, params, name, _prompt(90, 90)) > 30 * TOL
    finally:
        jax.clear_caches()


def test_the_kv_head_counts_cannot_be_swapped_over_these_weights(params):
    """The two kinds' kv head counts swapped: the pools would hold 2 rows a
    token under the full table and 1 under the window table, and the
    weights' k and v rows (1 and 2 heads) do not fit them.  The shapes do
    not allow the alteration: it stops at the first write."""
    cfg = dataclasses.replace(
        CFG, num_kv_heads=2,
        sliding_heads=dataclasses.replace(CFG.sliding_heads, num_kv_heads=1))
    engine = LLMEngine(params, cfg, EngineConfig(**ENGINE))
    with pytest.raises(Exception):
        _serve(engine, {"swapped": _prompt(3, 30)}, n_new=2)


# ----------------------------------------------- the layer, piece by piece
def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One sparse layer at a router of 16 with its correction bias, cut
    into 16 shares of one expert: the routed parts of all shares add up to
    what the uncut reference gives for the whole layer's MLP, through the
    program's share path and through the reference's; a share alone is not
    the layer."""
    cfg = dataclasses.replace(CFG, num_experts=16, num_layers=2,
                              layer_kinds=CFG.layer_types[:2])
    layer = tfm.init_params(jax.random.PRNGKey(9), cfg,
                            jnp.float32)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (53, 64), jnp.float32)
    whole = arch._moe_mlp(layer, h, 2, 0)
    ref_parts, served_parts = [], []
    for offset in range(16):
        cut = dict(layer, experts={k: v[offset:offset + 1]
                                   for k, v in layer["experts"].items()})
        ref_parts.append(arch._moe_mlp(cut, h, 2, offset))
        served_parts.append(tfm._moe_mlp(
            cut, expert_share(cfg, held=1, offset=offset), h))
    np.testing.assert_allclose(sum(ref_parts), whole, atol=2e-6)
    np.testing.assert_allclose(sum(served_parts), whole, atol=2e-6)
    np.testing.assert_allclose(tfm._moe_mlp(layer, cfg, h), whole,
                               atol=2e-6)
    assert float(jnp.abs(ref_parts[0] - whole).max()) > 1e-3
    # the selection is on score + bias, the weight the score alone
    flat = dict(layer, router=dict(
        layer["router"], e_bias=jnp.zeros_like(layer["router"]["e_bias"])))
    assert float(jnp.abs(arch._moe_mlp(flat, h, 2, 0) - whole).max()) > 1e-3


def test_the_architecture_module_refuses_a_drifted_constant(monkeypatch):
    """``program_config`` builds the program's configuration from the
    file's published keys and holds the program's own constant to it: a
    width that drifted in the program, or a switch the file leaves another
    way than the model, is a ``SystemExit``."""
    from benchmark import spec
    from vllm_omni_tpu.models import mimo_v2

    config = spec.load_json(
        spec.ROOT / "benchmark/configs/mimov25-l7-ep16.json")
    cfg = arch.program_config(config)
    assert cfg.layer_types == ("full_attention",) + (
        "sliding_attention",) * 5 + ("full_attention",)
    assert (cfg.num_experts, cfg.router_experts, cfg.expert_offset,
            cfg.first_k_dense_replace) == (16, 256, 0, 1)
    for drift in (dict(attention_value_scale=1.0), dict(v_head_dim=192),
                  dict(sliding_window=256), dict(partial_rotary_factor=0.5),
                  dict(sliding_heads=tfm.HeadKind(8, 1e4, False))):
        monkeypatch.setattr(mimo_v2, "MIMO_V2_5",
                            dataclasses.replace(MIMO_V2_5, **drift))
        with pytest.raises(SystemExit, match="differs from the published"):
            arch.program_config(config)
    monkeypatch.setattr(mimo_v2, "MIMO_V2_5", MIMO_V2_5)
    for key, value in (("add_swa_attention_sink_bias", False),
                       ("add_full_attention_sink_bias", True),
                       ("n_shared_experts", 1), ("swa_v_head_dim", 192),
                       ("moe_layer_freq", [0, 1, 0, 1, 1, 1, 1])):
        with pytest.raises(SystemExit):
            arch.program_config({**config, key: value})


# ------------------------------------------------------ pages and metrics
def test_two_sequences_in_one_unified_step_equal_each_alone(eng):
    """Unequal lengths packed into the same steps (chunks of both in one
    ragged batch, then one-token rows beside a chunk) against each alone;
    the flight record holds the window's pairs."""
    prompts = {"pair_a": _prompt(1, 90), "pair_b": _prompt(2, 37)}
    together = _serve(eng, prompts)
    shared = [r for r in eng.flight.tail()
              if {"pair_a", "pair_b"} <= set(r["requests"])]
    assert shared and any(r["prefills"] and r["decodes"] for r in shared)
    for r in shared:
        assert 0 < r["prefill_ctx_window"] + r["decode_ctx_window"] <= \
            r["prefill_ctx"] + r["decode_ctx"]
        assert r["decode_ctx_window"] <= WINDOW * r["decodes"]
    for rid, prompt in prompts.items():
        alone = _serve(eng, {rid + "_alone": prompt})[rid + "_alone"]
        assert alone[0] == together[rid][0]
        np.testing.assert_allclose(alone[1], together[rid][1], atol=TOL)


def test_a_prefix_hit_is_refused_and_counted(eng):
    """A prompt asked again finds its pages in the index, but the sliding
    layers' rows at the boundary were given back: the hit is refused,
    counted once, and the answer recomputed (and the same)."""
    prompt = _prompt(6, 100)
    ids_a, lp_a = _serve(eng, {"ask_a": prompt}, n_new=4)["ask_a"]
    before = eng.metrics_snapshot()
    ids_b, lp_b = _serve(eng, {"ask_b": prompt}, n_new=4)["ask_b"]
    after = eng.metrics_snapshot()
    assert after["window"]["prefix_hits_refused_total"] == \
        before["window"]["prefix_hits_refused_total"] + 1
    assert after["prefix_cache"]["hits"] == before["prefix_cache"]["hits"]
    assert ids_b == ids_a
    np.testing.assert_allclose(lp_b, lp_a, atol=TOL)


def test_a_preempted_sequence_resumes_through_both_kinds_of_page(params):
    """A sequence preempted in decode (its pages of both kinds freed) and
    resumed re-prefills through the window and still agrees with the
    reference."""
    engine = LLMEngine(params, CFG, EngineConfig(**ENGINE))
    free = engine.scheduler.kv.num_free_pages
    kicked = []

    def kick(step):
        if step == 5 and not kicked:
            req = next(iter(engine.scheduler.running))
            engine.scheduler._preempt(req)
            kicked.append(req.request_id)

    prompt = _prompt(11, 100)
    ids, served = _serve(engine, {"pre": prompt}, n_new=6,
                         after_step=kick)["pre"]
    assert kicked == ["pre"]
    want = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, want)) < TOL
    kv = engine.scheduler.kv
    assert kv.window_pages_in_use == 0 and kv.num_free_pages == free


def test_what_the_window_pages_cannot_carry(params):
    """Speculation, an int8 cache, offload, transfer and tensor
    parallelism are refused at construction, each naming the window
    pages, as for every model with sliding-window layers: per-kind head
    counts and a key wider than its value ride none of them either."""
    both = ("window pages", "pools of two shapes")
    assert layouts.layouts_of(CFG) == layouts.layouts_of(MIMO_V2_5) == both
    reasons = layouts.cannot_carry(MIMO_V2_5)
    assert all(reasons[m] for m in layouts.MECHANISMS)
    assert "ONE head count and ONE width" in reasons[layouts.TRANSFER]
    # a model whose kinds differ in the window alone has no such layout
    from vllm_omni_tpu.models.cohere2_moe import COMMAND_A_PLUS_05_2026

    assert layouts.layouts_of(COMMAND_A_PLUS_05_2026) == ("window pages",)
    for asked in (dict(num_speculative_tokens=2),
                  dict(kv_cache_dtype="int8"),
                  dict(tensor_parallel_size=2),
                  dict(kv_offload=True, kv_offload_policy="always"),
                  dict(engine_role="prefill")):
        with pytest.raises(ValueError, match="two kinds of page"):
            LLMEngine(params, CFG, EngineConfig(**ENGINE, **asked))
