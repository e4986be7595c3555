"""command-a-plus-05-2026 on the normal path (LLMEngine -> scheduler ->
KVCacheManager -> ARModelRunner) at tiny widths that keep what the
published model forces (one period: three sliding layers and a full one;
a parallel block under ONE LayerNorm that subtracts the mean; 4 q on 2 kv
heads over two kinds of per-head page; a window of 21 keys, which is not a
multiple of the 16-token page, with rotary on the sliding layers alone and
neighbouring dims paired; 8 experts top-2 by renormalised sigmoid score;
four shared experts averaged; tied embeddings), against the plain float32
reference of benchmark/archs/cohere2_moe.py (the published rotary formula,
a dense masked softmax, every held expert computed, the four shared
experts apart).

Logprobs are compared, never sampled tokens: with random weights the
largest logit changes on rounding.

The tolerance.  Engine and reference are both float32 (the CPU's matmuls
are full precision) and differ by the order of summation: attention runs
the flash recurrence over pages of two kinds, the rotation is half-split
over permuted columns where the reference pairs neighbours, the experts run
as grouped matmuls, the four shared experts as one of four times the width.
That is a few float32 roundings (6e-8 relative) through 4 layers on logits
of order 1: readings are 2e-6 to 7e-6.  The limit is 3e-5.  The controls
read 1e-3 and more: the window off, rotary on the full layer, a sequential
block, an RMSNorm, the shared experts summed.  The experts here are
unrelated draws (``tfm.init_params``, not the benchmark's related ones), so
a single wrong pick shows at full size.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import cohere2_moe as arch
from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
from vllm_omni_tpu.kvcache import layouts
from vllm_omni_tpu.models import registry
from vllm_omni_tpu.models.cohere2_moe import (
    COMMAND_A_PLUS_05_2026,
    expert_share,
    real_factory,
    tiny_config,
)
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.ops.rope import pairs_to_halves
from vllm_omni_tpu.sampling_params import SamplingParams

TOL = 3e-5
VOCAB = 256
CFG = tiny_config(VOCAB)
WINDOW = CFG.sliding_window
#: the reference reads the keys of a configuration file
CONFIG = {"layer_norm_eps": CFG.rms_eps, "head_dim": CFG.head_dim,
          "rope_theta": CFG.rope_theta, "sliding_window": WINDOW,
          "num_experts_per_tok": 2, "num_shared_experts": 4,
          "logit_scale": 1, "layer_types": list(CFG.layer_types)}
ENGINE = dict(num_pages=64, page_size=16, max_model_len=512, max_num_seqs=4,
              max_num_batched_tokens=64, enable_chunked_prefill=True,
              dtype=jnp.float32)


def _prompt(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(3, VOCAB, n).tolist()


def _draw(cfg, permute=True):
    """The program's draw as the benchmark serves it: the embedding's rows
    eight times as wide (the plain 0.02 leaves the first layer's input a
    tenth of what the branches add; more, and the tied head sees the input
    token alone), q and k four times (a score then deviates by some 2, so
    that WHICH keys a row attends decides its output), and the sliding
    layers' q and k columns in the served order."""
    params = tfm.init_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    params["embed"]["w"] = params["embed"]["w"] * 8.0
    for layer, kind in zip(params["layers"], cfg.layer_types):
        for name in ("q_proj", "k_proj"):
            w = layer[name]["w"] * 4.0
            if permute and kind == "sliding_attention":
                w = pairs_to_halves(w, cfg.head_dim)
            layer[name]["w"] = w
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
def test_a_layer_holds_one_norm_and_both_branches(params):
    """A parallel block's weights: ``input_norm``, the mixer, the router,
    the experts and ONE shared expert of four times the width, and no
    ``post_norm``; the head is the embedding."""
    want = {"input_norm", "q_proj", "k_proj", "v_proj", "o_proj", "router",
            "experts", "shared_expert"}
    for layer in params["layers"]:
        assert set(layer) == want
    assert "lm_head" not in params
    first = params["layers"][0]
    assert first["q_proj"]["w"].shape == (64, 4 * 16)
    assert first["k_proj"]["w"].shape == (64, 2 * 16)
    assert first["router"]["w"].shape == (64, 8)
    assert "e_bias" not in first["router"]
    assert first["experts"]["gate_up"].shape == (8, 64, 64)
    assert first["shared_expert"]["gate_up"]["w"].shape == (64, 2 * 96)
    assert first["shared_expert"]["down"]["w"].shape == (96, 64)
    assert "shared_gate" not in first
    # a stack of two halves a layer keeps both norms in every layer
    both = tfm.init_params(jax.random.PRNGKey(0),
                           tfm.TransformerConfig.tiny_moe(), jnp.float32)
    assert all({"input_norm", "post_norm"} <= set(layer)
               for layer in both["layers"])


def test_the_two_kinds_of_page_are_per_head(eng):
    """Four layers hold pages: three of the window kind, one of the full
    kind, both (K, V) rows of 2 kv heads x 16, accounted a kind."""
    assert (CFG.num_kv_layers, CFG.num_window_layers,
            CFG.num_state_layers) == (4, 3, 0)
    window_pages = -(-4 * (WINDOW + 64) // 16) + 4 + 1
    shapes = [[a.shape for a in cache] for cache in eng.runner.kv_caches]
    assert shapes == [[(2, window_pages, 16, 16)] * 2] * 3 + [
        [(2, 64, 16, 16)] * 2]
    kinds = eng._kv_kinds
    assert kinds["sliding_attention"]["layers"] == 3
    assert kinds["sliding_attention"]["pages_total"] == window_pages
    assert kinds["full_attention"]["pages_total"] == 64
    for kind in kinds.values():
        # K and V of 2 heads x 16 float32 numbers
        assert kind["bytes_per_token_layer"] == 2 * 2 * 16 * 4
        assert (kind["row_width"], kind["index_width"]) == (16, 0)
    assert eng._kv_bytes_per_token == 4 * 256
    assert CFG.attn_kwargs_of("sliding_attention") == {"window": WINDOW}
    assert CFG.attn_kwargs_of("full_attention") == {}


def test_the_published_constant_is_the_catalogs():
    m = COMMAND_A_PLUS_05_2026
    kinds = m.layer_types
    assert len(kinds) == 32 and kinds.count("sliding_attention") == 24
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == list(
        range(3, 32, 4))
    assert (m.num_kv_layers, m.num_window_layers) == (32, 24)
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim) == (
        4096, 128, 8, 128)
    for kind in ("full_attention", "sliding_attention"):
        layout = m.kv_layout_of(kind)
        assert (layout.kind, layout.heads, layout.width, layout.pools) == (
            "kv", 8, 128, 2)
        # 4 096 B a token a layer in bfloat16
        assert 2 * layout.pools * layout.heads * layout.width == 4096
    assert m.attn_kwargs_of("sliding_attention") == {"window": 4096}
    assert m.attn_kwargs_of("full_attention") == {}
    assert (m.rope_theta, m.rope_kinds, m.rotary_dim) == (
        50000.0, ("sliding_attention",), 128)
    assert (m.num_experts, m.num_experts_per_tok, m.moe_intermediate_size,
            m.shared_expert_size, m.shared_expert_scale,
            m.shared_expert_gate) == (128, 8, 4096, 16384, 0.25, False)
    assert (m.router_scoring, m.router_bias, m.routed_scaling_factor,
            m.moe_renormalize) == ("sigmoid", False, 1.0, True)
    assert (m.parallel_block, m.norm_subtract_mean, m.tie_word_embeddings,
            m.rms_eps, m.vocab_size, m.first_k_dense_replace) == (
        True, True, True, 1e-5, 262144, 0)
    # the issue's count: 6.79 B a layer, 218 B with the embedding
    attn = 2 * 4096 * 16384 + 2 * 4096 * 1024
    layer = attn + 4 * 3 * 4096 * 4096 + 4096 * 128 + 128 * 3 * 4096 * 4096
    assert attn == 142_606_336 and 6.78e9 < layer < 6.80e9
    assert 217.9e9 < 32 * layer + 262144 * 4096 < 218.6e9
    share = expert_share(m, held=16, offset=0)
    assert (share.num_experts, share.router_experts) == (16, 128)
    assert registry._AR_MODELS["Cohere2MoeForCausalLM"].module.endswith(
        "models.cohere2_moe")
    with pytest.raises(NotImplementedError, match="no checkpoint loader"):
        real_factory("/nowhere")


# ------------------------------------------------- against the reference
@pytest.mark.parametrize("n_prompt", [150, 40, 21, 20])
def test_engine_agrees_with_the_reference(eng, params, n_prompt):
    """Prefill in chunks, then decode through the two kinds of page.  150
    tokens are longer than max_num_batched_tokens (64) and seven times the
    window: by the compared tokens the sliding layers have given eight of
    their ten pages back; 40 fit one step and cross the window; 21 and 20
    sit at it and under it."""
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
        for layer in params["layers"]])
    engine = LLMEngine(share, cfg, EngineConfig(**ENGINE))
    config = {**CONFIG, "expert_offset": 4}
    assert _worst(engine, share, "share", _prompt(7, 90), config=config) < TOL
    # and the share is not the whole: against the uncut reference it reads
    # what the absent experts add
    assert _worst(engine, params, "whole", _prompt(7, 90)) > 30 * TOL


# ------------------------------------------------------------ the controls
@pytest.mark.parametrize("altered", [
    dict(sliding_window=10 ** 6),      # the window off: every key attended
    dict(rope_kinds=None),             # rotary on the full layer too
    dict(rope_kinds=()),               # no rotary on the sliding layers
    dict(parallel_block=False),        # attention, THEN the experts
    dict(norm_subtract_mean=False),    # an RMSNorm
    dict(shared_expert_scale=1.0),     # the shared experts summed
], ids=["window_off", "rotary_full", "no_rotary", "sequential", "rmsnorm",
        "shared_sum"])
def test_an_altered_form_fails_the_tolerance(params, altered):
    """The program with one of the model's departures undone, served
    against the model's reference: each reads far over the limit.  A
    sequential block needs a second norm a layer: it gets the initialiser's
    ones, so that what differs is the order alone."""
    cfg = dataclasses.replace(CFG, **altered)
    served = params
    if "parallel_block" in altered:
        served = dict(params, layers=[
            dict(layer, post_norm={"w": jnp.ones((64,), jnp.float32)})
            for layer in params["layers"]])
    engine = LLMEngine(served, cfg, EngineConfig(**ENGINE))
    ids, got = _serve(engine, {"altered": _prompt(90, 90)}, n_new=6)[
        "altered"]
    want = _reference(params, _prompt(90, 90), ids)
    assert max(abs(a - b) for a, b in zip(got, want)) > 30 * TOL


def test_unpermuted_columns_fail_the_tolerance(params):
    """The checkpoint's column order served as it is (the same draw
    without ``pairs_to_halves``) rotates other pairs of dims than the
    published formula does on that checkpoint: outside the tolerance, so
    the tolerance sees the permutation."""
    plain = _draw(CFG, permute=False)
    engine = LLMEngine(plain, CFG, EngineConfig(**ENGINE))
    assert _worst(engine, params, "plain", _prompt(40, 40)) > 30 * TOL


# ----------------------------------------------- the layer, piece by piece
def test_the_mean_of_four_shared_experts_is_the_fused_form(params):
    """The reference's four shared experts, each computed apart and
    averaged, against the program's one shared SwiGLU of four times the
    width at ``shared_expert_scale`` 1/4."""
    layer = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(3), (37, 64), jnp.float32)
    want = arch._shared_mean(layer["shared_expert"], h, 4)
    got = tfm._dense_mlp(layer["shared_expert"], CFG, h) * 0.25
    np.testing.assert_allclose(got, want, atol=1e-6)
    apart = [arch._shared_mean(
        {"gate_up": {"w": jnp.concatenate(
            [layer["shared_expert"]["gate_up"]["w"][:, s * 24:(s + 1) * 24],
             layer["shared_expert"]["gate_up"]["w"][
                 :, 96 + s * 24:96 + (s + 1) * 24]], axis=1)},
         "down": {"w": layer["shared_expert"]["down"]["w"][
             s * 24:(s + 1) * 24]}}, h, 1) for s in range(4)]
    np.testing.assert_allclose(sum(apart) / 4, want, atol=1e-6)
    # and through the program's sparse MLP: routed sum + the mean
    routed_only = dataclasses.replace(CFG, shared_expert_size=0)
    bare = {k: v for k, v in layer.items() if k != "shared_expert"}
    np.testing.assert_allclose(
        tfm._moe_mlp(layer, CFG, h) - tfm._moe_mlp(bare, routed_only, h),
        want, atol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer(params):
    """One layer at a router of 8, cut into 8 shares of one expert: the
    routed parts of all shares, with the shared experts' mean (which every
    chip computes alike) counted ONCE, add up to what the uncut reference
    gives for the whole layer's MLP; through the program's share path and
    through the reference's."""
    layer = params["layers"][2]
    h = jax.random.normal(jax.random.PRNGKey(5), (53, 64), jnp.float32)
    whole = arch._mlp(layer, h, 2, 0, 4)
    shared = arch._shared_mean(layer["shared_expert"], h, 4)
    ref_parts, served_parts = [], []
    for offset in range(8):
        cut = dict(layer, experts={k: v[offset:offset + 1]
                                   for k, v in layer["experts"].items()})
        ref_parts.append(arch._mlp(cut, h, 2, offset, 4) - shared)
        cfg = expert_share(CFG, held=1, offset=offset)
        served_parts.append(tfm._moe_mlp(cut, cfg, h) - shared)
    np.testing.assert_allclose(sum(ref_parts) + shared, whole, atol=2e-6)
    np.testing.assert_allclose(sum(served_parts) + shared, whole, atol=2e-6)
    # a share alone is not the layer
    assert float(jnp.abs(ref_parts[0] + shared - whole).max()) > 1e-3


def test_the_block_joins_both_branches_in_one_sum(params):
    """``_layer_step`` on a parallel layer is ``x + mixer(n(x)) +
    mlp(n(x))`` with ONE norm: the mixer's and the MLP's branch computed
    apart from the same normed rows, then added."""
    cfg = dataclasses.replace(CFG, layer_kinds=("full_attention",),
                              num_layers=1)
    layer = tfm.init_params(jax.random.PRNGKey(2), cfg,
                            jnp.float32)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 9, 64), jnp.float32)

    def attend(q, k, v):
        return tfm.flash_attention(
            q.reshape(1, 9, -1, 16), k.reshape(1, 9, -1, 16),
            v.reshape(1, 9, -1, 16), causal=True)

    got = tfm._layer_step(layer, cfg, x, None, None, attend)
    xf = x - x.mean(-1, keepdims=True)
    h = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + 1e-5) \
        * layer["input_norm"]["w"]
    want = (x + tfm._mixer(layer, cfg, h, None, None, attend,
                           "full_attention")
            + tfm._mlp(layer, cfg, h))
    np.testing.assert_allclose(got, want, atol=1e-6)
    # the cache-less forward has no window: refused by name
    with pytest.raises(NotImplementedError, match="sliding-window"):
        tfm.forward_hidden(params, CFG, jnp.zeros((1, 8), jnp.int32))


# ------------------------------------------------------ pages and metrics
def test_two_sequences_in_one_unified_step_equal_each_alone(eng):
    """Unequal lengths packed into the same steps (chunks of both in one
    ragged batch, then one-token rows beside a chunk) against each alone;
    the flight record holds the window's pairs and the histogram what the
    window left of the keys."""
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


def test_weights_redrawn_in_a_running_engine_are_the_ones_served(params):
    """What ``scripts/sparse_window_control.py`` does for every seed after
    its first: the old tree deleted, another bound to the runner.  The
    next request is served by the new weights (against the reference over
    THEM, inside the tolerance; against the old ones, far outside) through
    the executables already compiled, and nothing of the old request's
    pages is read."""
    engine = LLMEngine(jax.tree.map(jnp.copy, params), CFG,
                       EngineConfig(**ENGINE))
    assert _worst(engine, params, "first", _prompt(41, 70)) < TOL
    other = jax.tree.map(lambda w: w * 1.25, params)
    old, engine.runner.params = engine.runner.params, None
    for leaf in jax.tree.leaves(old):
        leaf.delete()
    engine.runner.params = jax.tree.map(jnp.copy, other)
    ids, served = _serve(engine, {"second": _prompt(41, 70)}, n_new=6)[
        "second"]
    new = _reference(other, _prompt(41, 70), ids)
    was = _reference(params, _prompt(41, 70), ids)
    assert max(abs(a - b) for a, b in zip(served, new)) < TOL
    assert max(abs(a - b) for a, b in zip(served, was)) > 30 * TOL


def test_window_keep_pct_is_observed_and_exported(params):
    """``step_metrics.window_keep_pct``: once a step that ran tokens, 100 x
    the window's pairs over the visible pairs; in the JSON snapshot and on
    ``/metrics``, and absent for a model without window layers."""
    from vllm_omni_tpu.metrics.prometheus import render_exposition

    engine = LLMEngine(params, CFG, EngineConfig(**ENGINE))
    _serve(engine, {"keep": _prompt(9, 100)}, n_new=4)
    snap = engine.metrics_snapshot()
    keep = snap["window_keep_pct"]
    steps = [r for r in engine.flight.tail()
             if r["prefill_tokens"] + r["decodes"] > 0]
    assert keep["count"] == len(steps) > 3
    want = [100.0 * (r["prefill_ctx_window"] + r["decode_ctx_window"])
            / (r["prefill_ctx"] + r["decode_ctx"]) for r in steps]
    assert keep["sum"] == pytest.approx(sum(want), abs=1e-2)  # rounded
    # the first chunk's 64 tokens see 1..64 keys and keep at most 21 of
    # them; a decode row past 100 keeps 21 of its context
    assert want[0] == pytest.approx(
        100.0 * sum(min(i, WINDOW) for i in range(1, 65))
        / sum(range(1, 65)))
    assert steps[-1]["decodes"] == 1 and steps[-1]["decode_ctx"] > 100
    assert want[-1] == pytest.approx(
        100.0 * WINDOW / steps[-1]["decode_ctx"])
    assert "window_keep_pct_bucket" in render_exposition({}, {0: snap})
    plain = LLMEngine(
        tfm.init_params(jax.random.PRNGKey(0),
                        tfm.TransformerConfig.tiny_moe(VOCAB), jnp.float32),
        tfm.TransformerConfig.tiny_moe(VOCAB), EngineConfig(**ENGINE))
    _serve(plain, {"plain": _prompt(9, 40)}, n_new=2)
    assert plain.metrics_snapshot()["window_keep_pct"]["count"] == 0
    assert "window_keep_pct" not in render_exposition(
        {}, {0: plain.metrics_snapshot()})


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
    pages, as for every model with sliding-window layers."""
    assert layouts.layouts_of(CFG) == ("window pages",)
    assert layouts.layouts_of(COMMAND_A_PLUS_05_2026) == ("window pages",)
    reasons = layouts.cannot_carry(COMMAND_A_PLUS_05_2026)
    assert all(reasons[m] for m in layouts.MECHANISMS)
    said = layouts.refusal(COMMAND_A_PLUS_05_2026, layouts.MECHANISMS)
    assert "two kinds of page" in said
    for mechanism in layouts.MECHANISMS:
        assert mechanism in said
    assert "latent" not in said and "recurrent" not in said
    for asked in (dict(num_speculative_tokens=2),
                  dict(kv_cache_dtype="int8"),
                  dict(tensor_parallel_size=2),
                  dict(kv_offload=True, kv_offload_policy="always"),
                  dict(engine_role="prefill")):
        with pytest.raises(ValueError, match="two kinds of page"):
            LLMEngine(params, CFG, EngineConfig(**ENGINE, **asked))
