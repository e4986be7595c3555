"""dots3-note-prev on the normal path (LLMEngine -> scheduler ->
KVCacheManager -> ARModelRunner) at tiny widths that keep the published
model's shape (a dense full layer, a sparse full one with an indexer
keeping 8 keys, three sliding ones with widths, head count and rotary base
of their own and a window of 5, head-wise gates, rescaled latents, 4 of 16
experts held under a sigmoid router with its bias), against the plain
float32 reference of benchmark/archs/dots3_note.py (the EXPANDED form, the
selection by ``lax.top_k``, a dense window mask).  The engine serves the
ABSORBED form over two kinds of page, finds the selection by counting
passes and reads the window inside the paged kernel.

Logprobs are compared, never sampled tokens.  The tolerance is GLM's
(tests/engine/test_glm4_moe_lite.py): both sides are float32 and differ by
the order of summation, readings are 1e-6 to 4e-6 against a limit of 2e-5;
the selection switched off, the window switched off, or rows kept in
bfloat16 each read far over it, which the controls below hold.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import dots3_note as arch
from vllm_omni_tpu.core.kv_cache_manager import NULL_WINDOW_PAGE
from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.models.dots3_note import expert_share, tiny_config
from vllm_omni_tpu.sampling_params import SamplingParams

TOL = 2e-5
VOCAB = 256
#: 4 of the 16 experts are held here (ids 4-7), as the benchmark's cell
#: holds 32 of 256
CFG = expert_share(tiny_config(VOCAB), held=4, offset=4)
FULL, SLIDE = CFG.latent_kind(), CFG.sliding
#: the reference reads the keys of a configuration file
CONFIG = {
    "rms_norm_eps": CFG.rms_eps, "layer_types": list(CFG.layer_types),
    "num_attention_heads": FULL.num_heads, "q_lora_rank": FULL.q_lora_rank,
    "kv_lora_rank": FULL.kv_lora_rank,
    "qk_nope_head_dim": FULL.qk_nope_head_dim,
    "qk_rope_head_dim": FULL.qk_rope_head_dim, "v_head_dim": FULL.v_head_dim,
    "rope_theta": FULL.rope_theta,
    "swa_num_attention_heads": SLIDE.num_heads,
    "swa_q_lora_rank": SLIDE.q_lora_rank,
    "swa_kv_lora_rank": SLIDE.kv_lora_rank,
    "swa_qk_nope_head_dim": SLIDE.qk_nope_head_dim,
    "swa_qk_rope_head_dim": SLIDE.qk_rope_head_dim,
    "swa_v_head_dim": SLIDE.v_head_dim, "swa_rope_theta": SLIDE.rope_theta,
    "sliding_window_size": CFG.sliding_window,
    "index_topk": CFG.index_topk, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 1.0,
    "expert_offset": CFG.expert_offset,
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


def _reference(params, prompt, ids, config=CONFIG):
    ref = arch.reference_logprobs(params, config, prompt + ids[:-1], len(ids))
    return [float(ref[i, t]) for i, t in enumerate(ids)]


def _worst(served, want):
    return max(abs(a - b) for a, b in zip(served, want))


def test_the_weight_tree_is_the_models(params):
    """Two kinds of layer by their weights' shapes, an indexer on the full
    ones only, a gate a head on both, a dense first MLP, 4 held experts
    under a 16-wide router, and a cache row a kind."""
    kinds = CFG.layer_types
    assert kinds == ("full_attention",) * 2 + ("sliding_attention",) * 3
    for layer, kind in zip(params["layers"], kinds):
        lk = CFG.latent_kind(kind)
        assert layer["mla"]["kv_a"]["w"].shape == (64, lk.row)
        assert layer["attn_gate"]["w"].shape == (64, lk.num_heads)
        assert ("indexer" in layer) == (kind == "full_attention")
    assert "gate_up" in params["layers"][0]
    sparse = params["layers"][1]
    assert sparse["router"]["w"].shape == (64, 16)
    assert sparse["experts"]["gate_up"].shape[0] == 4
    assert float(jnp.abs(sparse["router"]["e_bias"]).max()) > 0
    assert CFG.kv_layout.used == 40 and CFG.kv_layout.width == 128
    wide = CFG.kv_layout_of("sliding_attention")
    assert wide.used == 56 and wide.width == 128
    assert CFG.attn_kwargs_of("sliding_attention")["window"] == 5
    assert "window" not in CFG.attn_kwargs


@pytest.mark.parametrize("n_prompt", [150, 40, 67])
def test_engine_agrees_with_the_reference(eng, params, n_prompt):
    """Chunked prefill, then decode, past the window (5) and past the
    selection (8) from the ninth token on.  150 tokens are three chunks of
    at most 64: both chunk boundaries lie inside a window and inside
    contexts that drop keys; 67 leave a 3-token chunk, a boundary 3 tokens
    from the end (inside the window of every decoded token's first)."""
    prompt = _prompt(n_prompt, n_prompt)
    ids, served = _serve(eng, {f"ref{n_prompt}": prompt}, n_new=6)[
        f"ref{n_prompt}"]
    assert _worst(served, _reference(params, prompt, ids)) < TOL


@pytest.mark.parametrize("off", ["selection", "window"])
def test_a_mechanism_switched_off_fails_the_tolerance(params, off):
    """The controls: the same weights served with the selection off (every
    key attended) or the window off (the sliding layers see everything)
    are far outside the tolerance, so the tolerance sees each mechanism."""
    cfg = dataclasses.replace(
        CFG, **({"index_topk": 10 ** 6} if off == "selection"
                else {"sliding_window": ENGINE["max_model_len"]}))
    engine = LLMEngine(params, cfg, EngineConfig(**ENGINE))
    prompt = _prompt(150, 150)
    ids, served = _serve(engine, {off: prompt}, n_new=6)[off]
    assert _worst(served, _reference(params, prompt, ids)) > 100 * TOL


@pytest.mark.parametrize("n_prompt", [150, 67])
def test_tables_within_index_topk_serve_every_key(params, n_prompt):
    """Where the page tables cannot reach past ``index_topk`` keys (a
    deployment whose ``max_model_len`` is at most that) the full layers
    skip the indexer's scores and the selection by the shapes of the call,
    and still write the index key: the served logprobs are the
    reference's with the same ``index_topk``."""
    cfg = dataclasses.replace(CFG, index_topk=10 ** 6)
    engine = LLMEngine(params, cfg, EngineConfig(**ENGINE))
    prompt = _prompt(n_prompt, n_prompt)
    ids, served = _serve(engine, {"all": prompt}, n_new=6)["all"]
    want = _reference(params, prompt, ids,
                      config={**CONFIG, "index_topk": 10 ** 6})
    assert _worst(served, want) < TOL


def test_bfloat16_rows_fail_the_tolerance(params):
    """Rows and index keys kept in bfloat16 (one step below the float32
    this test's configuration states) are outside the tolerance."""
    engine = LLMEngine(params, CFG, EngineConfig(**ENGINE))
    engine.runner.kv_caches = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), engine.runner.kv_caches)
    prompt = _prompt(150, 150)
    ids, served = _serve(engine, {"bf16": prompt}, n_new=6)["bf16"]
    assert _worst(served, _reference(params, prompt, ids)) > 10 * TOL


def test_two_sequences_in_one_unified_step_equal_each_alone(eng):
    prompts = {"pair_a": _prompt(1, 90), "pair_b": _prompt(2, 37)}
    together = _serve(eng, prompts)
    assert any({"pair_a", "pair_b"} <= set(r["requests"])
               for r in eng.flight.tail())
    for rid, prompt in prompts.items():
        alone = _serve(eng, {rid + "_alone": prompt})[rid + "_alone"]
        assert alone[0] == together[rid][0]
        np.testing.assert_allclose(alone[1], together[rid][1], atol=TOL)


def test_a_preempted_sequence_equals_an_unpreempted_one(eng, params):
    """Preemption frees both kinds of page and recomputes from position
    0; what follows equals the unpreempted run and the reference."""
    prompt = _prompt(5, 75)
    want_ids, want_lp = _serve(eng, {"calm": prompt}, n_new=7)["calm"]
    before = eng.scheduler.num_preemptions
    kv = eng.scheduler.kv
    held = {}

    def preempt(step):
        _, req = eng.scheduler.find_request("hit")
        if req is not None and len(req.output_token_ids) == 3 \
                and eng.scheduler.num_preemptions == before:
            held["before"] = kv.window_pages_in_use
            eng.scheduler._preempt(req)
            held["after"] = kv.window_pages_in_use

    ids, served = _serve(eng, {"hit": prompt}, n_new=7,
                         after_step=preempt)["hit"]
    assert eng.scheduler.num_preemptions == before + 1
    assert held["before"] > 0 and held["after"] == 0
    assert ids == want_ids
    np.testing.assert_allclose(served, want_lp, atol=TOL)
    assert _worst(served, _reference(params, prompt, ids)) < TOL


def test_window_pages_are_given_back_as_the_context_grows(params):
    """A sequence holds only the window kind's pages its next query can
    reach: the table's head points at the null page, the pool's high-water
    mark stays under seqs x (window + chunk) whatever the context, and
    everything is back when the sequence ends."""
    engine = LLMEngine(params, CFG, EngineConfig(**ENGINE))
    kv = engine.scheduler.kv
    seen = {"held": 0, "nulls": 0}

    def watch(step):
        table = kv.window_table("long")
        if table:
            seen["held"] = max(seen["held"], sum(
                p != NULL_WINDOW_PAGE for p in table))
            seen["nulls"] = max(seen["nulls"], sum(
                p == NULL_WINDOW_PAGE for p in table))
            assert len(table) == len(kv.block_table("long"))

    _serve(engine, {"long": _prompt(3, 400)}, n_new=8, after_step=watch)
    # a 64-token chunk and a 5-key window lie on at most 5 + 1 pages of 16
    assert 0 < seen["held"] <= -(-(64 + 5) // 16) + 1
    assert seen["nulls"] >= 400 // 16 - 1
    assert kv.window_pages_high_water <= -(-4 * (5 + 64) // 16) + 4
    assert kv.window_pages_in_use == 0 and kv.window_pages_freed > 20
    snap = engine.metrics_snapshot()
    assert snap["window"]["pages_freed_total"] == kv.window_pages_freed
    assert snap["window"]["pages_total"] == -(-4 * (5 + 64) // 16) + 4 + 1
    hist = snap["kv_window_pages_used"]
    assert hist["count"] > 0 and hist["sum"] / hist["count"] <= 6


def test_a_prefix_hit_is_refused_and_counted(eng, params):
    """A prompt asked again finds its pages in the index, but the sliding
    layers' rows at the boundary were given back: the hit is refused,
    counted once, and the answer recomputed (and right)."""
    prompt = _prompt(6, 100)
    ids_a, lp_a = _serve(eng, {"ask_a": prompt}, n_new=4)["ask_a"]
    before = eng.metrics_snapshot()
    ids_b, lp_b = _serve(eng, {"ask_b": prompt}, n_new=4)["ask_b"]
    after = eng.metrics_snapshot()
    assert after["window"]["prefix_hits_refused_total"] == \
        before["window"]["prefix_hits_refused_total"] + 1
    assert after["prefix_cache"]["hits"] == before["prefix_cache"]["hits"]
    assert (after["counters"]["prefill_tokens"]
            - before["counters"]["prefill_tokens"]) == 100
    assert ids_b == ids_a
    np.testing.assert_allclose(lp_b, lp_a, atol=TOL)


def test_both_kinds_of_cache_are_counted(eng):
    """``kv.kinds`` in the JSON snapshot: rows, index keys, bytes and
    pages a kind; the selection's share; the flight record's bounded
    pairs; the memory ledger's bytes from the arrays themselves."""
    _serve(eng, {"count_a": _prompt(9, 40), "count_b": _prompt(10, 20)})
    snap = eng.metrics_snapshot()
    kinds = snap["kv"]["kinds"]
    full, slide = kinds["full_attention"], kinds["sliding_attention"]
    assert (full["layers"], full["row_used"], full["index_width"]) == (
        2, 40, 16)
    assert full["bytes_per_token_layer"] == (128 + 16) * 4
    assert (slide["layers"], slide["row_used"], slide["index_width"]) == (
        3, 56, 0)
    assert slide["pages_total"] == -(-4 * (5 + 64) // 16) + 4 + 1
    assert snap["kv"]["bytes_per_token"] == 2 * 144 * 4 + 3 * 128 * 4
    keep = snap["sparse_keep_pct"]
    assert keep["count"] > 0 and 0 < keep["sum"] / keep["count"] < 100
    rec = next(r for r in reversed(eng.flight.tail())
               if r["decodes"] and r["decode_ctx"] > 8 * r["decodes"])
    assert rec["decode_ctx_kept"] == 8 * rec["decodes"]
    assert rec["decode_ctx_window"] == 5 * rec["decodes"]
    pools = (2 * 64 * 16 * (128 + 16) + 3 * slide["pages_total"] * 16 * 128
             ) * 4
    assert snap["device_memory"]["components"]["kv_pages"]["bytes"] == pools


@pytest.mark.parametrize("kwargs, names", [
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype int8"),
    (dict(engine_role="prefill"), "kv_transfer"),
    (dict(kv_offload=True, kv_offload_policy="always"), "kv_offload"),
    (dict(tensor_parallel_size=2), "tensor_parallel_size"),
    (dict(num_speculative_tokens=2), "speculative decoding"),
])
def test_what_cannot_carry_two_kinds_of_page_is_refused_at_construction(
        params, kwargs, names):
    with pytest.raises(ValueError, match="sliding-window layers.*" + names):
        LLMEngine(params, CFG, EngineConfig(**ENGINE, **kwargs))


def test_the_cacheless_forward_refuses_the_model(params):
    with pytest.raises(NotImplementedError, match="window"):
        tfm.forward_hidden(params, CFG, jnp.zeros((1, 8), jnp.int32))
