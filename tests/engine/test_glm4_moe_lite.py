"""GLM-4.7-Flash on the normal path (LLMEngine -> scheduler ->
KVCacheManager -> ARModelRunner) at tiny widths that keep the published
model's ratios (4 heads, a rotary part, a value wider than the key's
un-rotated part, a dense first layer, 8 experts top-2 with a non-zero
correction bias and an ungated shared expert), against the plain float32
reference of benchmark/archs/glm4_moe_lite.py (the EXPANDED form: per-head
keys and values from ``kv_b`` for every position, dense softmax, every
expert computed).  The engine serves the ABSORBED form over a latent paged
cache, so every agreement here is also "absorbed equals expanded".

Logprobs are compared, never sampled tokens: with random weights the
largest logit changes on rounding.

The tolerance.  Engine and reference are both float32 (the CPU's matmuls
are full precision) and differ by the order of summation: the absorbed
form contracts the query with ``W_UK`` before the keys where the reference
contracts the latent row with it, attention runs the paged online softmax,
the experts run as grouped matmuls.  That is a few float32 roundings (6e-8
relative) through 3 layers on logits of order 1: readings are 1e-6 to
3e-6.  The limit is 2e-5; latent rows kept in bfloat16 (8 bits) read 1e-3
to 1e-2 and fail it, which ``test_bfloat16_latent_rows_fail_the_tolerance``
holds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import glm4_moe_lite as arch
from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.models.glm4_moe_lite import tiny_config
from vllm_omni_tpu.sampling_params import SamplingParams

TOL = 2e-5
VOCAB = 256
CFG = tiny_config(VOCAB)
#: the reference reads the keys of a configuration file
CONFIG = {
    "rms_norm_eps": CFG.rms_eps, "rope_theta": CFG.rope_theta,
    "qk_nope_head_dim": CFG.qk_nope_head_dim,
    "qk_rope_head_dim": CFG.qk_rope_head_dim, "v_head_dim": CFG.v_head_dim,
    "kv_lora_rank": CFG.kv_lora_rank, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 1.8,
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


def test_the_weight_tree_is_the_models(params):
    """A dense first layer, sparse ones after it with the correction bias
    and no shared gate, latent attention everywhere, and a cache of one
    row a token."""
    first, second = params["layers"][0], params["layers"][1]
    assert "gate_up" in first and "experts" not in first
    assert "experts" in second and "gate_up" not in second
    assert "shared_expert" in second and "shared_gate" not in second
    assert float(jnp.abs(second["router"]["e_bias"]).max()) > 0
    assert all("mla" in layer and "q_proj" not in layer
               for layer in params["layers"])
    layout = CFG.kv_layout
    assert (layout.kind, layout.heads, layout.pools) == ("latent", 1, 1)
    assert layout.used == CFG.kv_lora_rank + CFG.qk_rope_head_dim == 72
    assert layout.width == 128  # padded to whole lane tiles


@pytest.mark.parametrize("n_prompt", [150, 40])
def test_engine_agrees_with_the_reference(eng, params, n_prompt):
    """Chunked prefill, then decode through the latent cache.  150 tokens
    are longer than max_num_batched_tokens (64): the second and third
    chunks attend latent rows the first wrote (two chunk boundaries inside
    the prompt); 40 fit one step."""
    prompt = _prompt(n_prompt, n_prompt)
    ids, served = _serve(eng, {f"ref{n_prompt}": prompt}, n_new=6)[
        f"ref{n_prompt}"]
    want = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, want)) < TOL


def test_bfloat16_latent_rows_fail_the_tolerance(params):
    """The control: the same engine with the latent pools kept in bfloat16
    (one step lower than the float32 this test's configuration states) is
    outside the tolerance, so the tolerance can see the cache's
    precision."""
    engine = LLMEngine(params, CFG, EngineConfig(**ENGINE))
    engine.runner.kv_caches = [
        (pool.astype(jnp.bfloat16), None)
        for pool, _ in engine.runner.kv_caches]
    prompt = _prompt(150, 150)
    ids, served = _serve(engine, {"bf16": prompt}, n_new=6)["bf16"]
    want = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, want)) > 10 * TOL


def test_the_cache_holds_latent_rows_and_nothing_else(eng, params):
    """What the pages hold after a prompt: for each layer one pool of
    [1, pages, page, 128] whose rows are the reference's ``[norm(c_kv) |
    rope(k_rope)]`` in lanes 0..71 and zeros after; no per-head K or V."""
    prompt = _prompt(11, 40)
    seen = {}

    def grab(step):
        pages = eng.scheduler.kv.block_table("rows40")
        if "rows" not in seen and pages:
            seen["rows"] = [np.asarray(pool[0, np.asarray(pages)])
                            for pool, _ in eng.runner.kv_caches]

    _serve(eng, {"rows40": prompt}, n_new=2, after_step=grab)
    assert len(eng.runner.kv_caches) == CFG.num_layers
    for pool, value in eng.runner.kv_caches:
        assert value is None
        assert pool.shape == (1, ENGINE["num_pages"], 16, 128)
    # layer 0's rows from the embedding alone
    x = params["embed"]["w"][jnp.asarray(prompt)]
    layer = params["layers"][0]
    h = arch._rms(x, layer["input_norm"]["w"], CFG.rms_eps)
    kv = h @ layer["mla"]["kv_a"]["w"]
    c_kv = arch._rms(kv[:, :64], layer["mla"]["kv_a_norm"]["w"], CFG.rms_eps)
    k_rope = arch._rope(kv[:, None, 64:], jnp.arange(40), CFG.rope_theta)
    got = seen["rows"][0].reshape(-1, 128)[:40]
    np.testing.assert_allclose(got[:, :64], c_kv, atol=1e-5)
    np.testing.assert_allclose(got[:, 64:72], k_rope[:, 0], atol=1e-5)
    assert not got[:, 72:].any()


def test_two_sequences_in_one_unified_step_equal_each_alone(eng):
    """Unequal lengths packed into the same steps (chunks of both in one
    ragged batch, then decode rows beside a chunk) against each alone."""
    prompts = {"pair_a": _prompt(1, 90), "pair_b": _prompt(2, 37)}
    together = _serve(eng, prompts)
    assert any({"pair_a", "pair_b"} <= set(r["requests"])
               for r in eng.flight.tail())
    for rid, prompt in prompts.items():
        alone = _serve(eng, {rid + "_alone": prompt})[rid + "_alone"]
        assert alone[0] == together[rid][0]
        np.testing.assert_allclose(alone[1], together[rid][1], atol=TOL)


def test_a_preempted_sequence_equals_an_unpreempted_one(eng, params):
    """Preemption frees the latent pages and recomputes them: what follows
    equals the unpreempted run (and the reference)."""
    prompt = _prompt(5, 75)
    want_ids, want_lp = _serve(eng, {"calm": prompt}, n_new=7)["calm"]
    before = eng.scheduler.num_preemptions

    def preempt(step):
        _, req = eng.scheduler.find_request("hit")
        if req is not None and len(req.output_token_ids) == 3 \
                and eng.scheduler.num_preemptions == before:
            eng.scheduler._preempt(req)

    ids, served = _serve(eng, {"hit": prompt}, n_new=7,
                         after_step=preempt)["hit"]
    assert eng.scheduler.num_preemptions == before + 1
    assert ids == want_ids
    np.testing.assert_allclose(served, want_lp, atol=TOL)


def test_a_second_asking_reads_its_latent_pages(eng, params):
    """Pages are pages: a prompt asked again hits the prefix cache, its
    cached latent rows are attended and not recomputed, and the answer is
    the first asking's and the reference's."""
    prompt = _prompt(6, 100)
    before = eng.metrics_snapshot()["prefix_cache"]
    ids_a, lp_a = _serve(eng, {"ask_a": prompt}, n_new=4)["ask_a"]
    prefilled = eng.metrics_snapshot()["counters"]["prefill_tokens"]
    ids_b, lp_b = _serve(eng, {"ask_b": prompt}, n_new=4)["ask_b"]
    after = eng.metrics_snapshot()
    assert after["prefix_cache"]["enabled"]
    assert after["prefix_cache"]["hits"] == before["hits"] + 1
    # six whole pages of the 100 tokens were not prefilled again
    assert after["prefix_cache"]["hit_tokens"] == before["hit_tokens"] + 96
    assert after["counters"]["prefill_tokens"] - prefilled == 100 - 96
    assert ids_b == ids_a
    np.testing.assert_allclose(lp_b, lp_a, atol=TOL)
    want = _reference(params, prompt, ids_b)
    assert max(abs(a - b) for a, b in zip(lp_b, want)) < TOL


def test_the_latent_cache_is_counted(eng):
    """The layout and its bytes in /metrics?format=json, the pages-held
    histogram every model gets, and the memory ledger's bytes from the
    arrays themselves."""
    _serve(eng, {"count_a": _prompt(9, 20), "count_b": _prompt(10, 20)})
    snap = eng.metrics_snapshot()
    kv = snap["kv"]
    assert kv["layout"] == "latent"
    assert (kv["row_width"], kv["row_used"]) == (128, 72)
    # one padded float32 row a token a layer, not 2 * Hkv * D
    assert kv["bytes_per_token_layer"] == 128 * 4
    assert kv["bytes_per_token"] == CFG.num_layers * 128 * 4
    hist = snap["kv_pages_used"]
    assert hist["count"] > 0 and hist["sum"] / hist["count"] >= 2
    pools = CFG.num_layers * ENGINE["num_pages"] * 16 * 128 * 4
    assert snap["device_memory"]["components"]["kv_pages"]["bytes"] == pools


def test_pages_used_is_observed_for_every_model():
    """The histogram is no latent-cache thing: a plain GQA model has it,
    with the (K, V) layout's bytes."""
    cfg = tfm.TransformerConfig.tiny(VOCAB)
    engine = LLMEngine(tfm.init_params(jax.random.PRNGKey(0), cfg), cfg,
                       EngineConfig(**ENGINE))
    _serve(engine, {"gqa": _prompt(12, 20)}, n_new=2)
    snap = engine.metrics_snapshot()
    assert snap["kv_pages_used"]["count"] > 0
    assert snap["kv"]["layout"] == "kv"
    assert snap["kv"]["bytes_per_token_layer"] == 2 * 2 * 16 * 4


@pytest.mark.parametrize("kwargs, names", [
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype int8"),
    (dict(engine_role="prefill"), "kv_transfer"),
    (dict(engine_role="decode"), "kv_transfer"),
    (dict(kv_offload=True, kv_offload_policy="always"), "kv_offload"),
    (dict(tensor_parallel_size=2), "tensor_parallel_size"),
    (dict(num_speculative_tokens=2), "speculative decoding"),
])
def test_what_cannot_carry_the_latent_layout_is_refused_at_construction(
        params, kwargs, names):
    """An int8 cache, KV transfer / a disaggregated role, parking and
    tiered offload, tensor parallelism and speculative verify rows fail
    when the engine is built, with the mechanism's name: none of them
    reads or writes the one-pool layout yet."""
    with pytest.raises(ValueError, match=names):
        LLMEngine(params, CFG, EngineConfig(**ENGINE, **kwargs))


def test_kv_transfer_trigger_is_refused_too(params):
    from vllm_omni_tpu.core.scheduler import KVTransferConfig

    with pytest.raises(ValueError, match="inject_kv / extract_kv"):
        LLMEngine(params, CFG, EngineConfig(
            **ENGINE, kv_transfer=KVTransferConfig()))


def test_the_runner_refuses_a_page_payload(eng):
    with pytest.raises(NotImplementedError, match="latent cache"):
        eng.runner.extract_kv([0], 4)
    with pytest.raises(ValueError, match="speculative decoding"):
        eng.runner.set_draft_fn(lambda *a, **k: None, 2)
