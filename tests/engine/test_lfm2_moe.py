"""LFM2 on the normal path (LLMEngine -> scheduler -> KVCacheManager ->
ARModelRunner) at tiny widths that keep what the published model forces
(heads of 64 two to a row of the pool, a state slot of the convolution's
taps alone, two leading dense layers, a sigmoid router with an expert
bias, tied embeddings), against the plain float32 reference of
benchmark/archs/lfm2_moe.py (the convolution as three shifted products over
the whole sequence, dense attention, every expert computed).

Logprobs are compared, never sampled tokens: with random weights the
largest logit changes on rounding.

The tolerance.  Engine and reference are both float32 (the CPU's matmuls
are full precision) and differ by the order of summation: the served
convolution adds the carried rows' taps after the step's own, attention
runs the flash recurrence over pages (and, in the pool's paired rows,
adds the other head's lanes times zero), the experts run as grouped
matmuls.  That is a few float32 roundings (6e-8 relative) through 5 layers
on logits of order 1: readings are 1e-6 to 4e-6.  The limit is 2e-5; a
convolution state kept in bfloat16 (8 bits) reads 1e-3 and the one
attention layer's pages in bfloat16 2e-4: both fail it, which the two
controls hold.  The experts here are unrelated
draws (``tfm.init_params``, not the benchmark's related ones), so a single
wrong pick shows at full size.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.archs import lfm2_moe as arch
from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.models.lfm2_moe import tiny_config
from vllm_omni_tpu.sampling_params import SamplingParams

TOL = 2e-5
VOCAB = 256
CFG = tiny_config(VOCAB)
#: the reference reads the keys of a configuration file
CONFIG = {"norm_eps": CFG.rms_eps, "rope_theta": CFG.rope_theta,
          "num_experts_per_tok": 2, "norm_topk_prob": True,
          "routed_scaling_factor": 1}
ENGINE = dict(num_pages=64, page_size=16, max_model_len=512, max_num_seqs=4,
              max_num_batched_tokens=64, enable_chunked_prefill=True,
              dtype=jnp.float32)
PAGE = 16


def _prompt(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(3, VOCAB, n).tolist()


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(1), CFG, jnp.float32)


@pytest.fixture(scope="module")
def eng(params):
    return LLMEngine(params, CFG, EngineConfig(**ENGINE))


def _serve(engine, prompts: dict, n_new=5, after_step=None) -> dict:
    """request id -> (generated ids, their served logprobs), greedy;
    ``n_new`` one count, or one a request."""
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


def _reference(params, prompt, ids):
    ref = arch.reference_logprobs(params, CONFIG, prompt + ids[:-1], len(ids))
    return [float(ref[i, t]) for i, t in enumerate(ids)]


# ------------------------------------------------------- the weight tree
def test_the_weight_tree_is_the_models(params):
    """Convolutions and one attention layer by ``layer_kinds``, dense MLPs
    first and sparse ones with the expert bias after, no head of its own,
    and a cache whose rows hold two 64-wide heads and no padding lane."""
    kinds = ["shortconv" if "shortconv" in layer else "attention"
             for layer in params["layers"]]
    assert kinds == ["shortconv", "shortconv", "attention", "shortconv",
                     "shortconv"]
    assert all("gate_up" in layer for layer in params["layers"][:2])
    assert all("experts" in layer and "shared_expert" not in layer
               for layer in params["layers"][2:])
    assert float(jnp.abs(params["layers"][2]["router"]["e_bias"]).max()) > 0
    assert "lm_head" not in params
    attn = params["layers"][2]
    assert attn["q_norm"]["w"].shape == attn["k_norm"]["w"].shape == (64,)
    sc = params["layers"][0]["shortconv"]
    assert sc["in_proj"]["w"].shape == (128, 384)
    assert sc["conv"]["w"].shape == (3, 128)
    layout = CFG.kv_layout
    assert (layout.kind, layout.heads, layout.width, layout.pools,
            layout.used) == ("kv", 1, 128, 2, 128)
    assert CFG.num_state_layers == 4 and CFG.num_kv_layers == 1
    assert CFG.state_shapes("conv") == ((2, 128),)


def test_the_published_constant_is_the_catalogs():
    from vllm_omni_tpu.models.lfm2_moe import LFM2_8B_A1B as m

    assert m.layer_types.count("conv") == 18
    assert [i for i, k in enumerate(m.layer_types)
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    layout = m.kv_layout
    # 8 kv heads of 64 as 4 rows of 128: 2 048 bytes a token a layer
    assert (layout.heads, layout.width, layout.pools) == (4, 128, 2)
    assert 2 * layout.pools * layout.heads * layout.width == 2048
    assert m.state_shapes("conv") == ((2, 2048),)


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
    longer than max_num_batched_tokens (64), so the taps cross two chunk
    boundaries and the later chunks attend paired rows the first wrote; 40
    fit one step."""
    prompt = _prompt(n_prompt, n_prompt)
    ids, served = _serve(eng, {f"ref{n_prompt}": prompt}, n_new=6)[
        f"ref{n_prompt}"]
    want = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, want)) < TOL


def test_a_bfloat16_conv_state_fails_the_tolerance(params):
    """The control: the same engine with the convolution's carried rows
    kept in bfloat16 between steps is outside the tolerance, so the
    tolerance can see the precision the configuration states."""
    engine = LLMEngine(params, CFG, EngineConfig(**ENGINE))
    engine.runner.state_caches = [
        tuple(a.astype(jnp.bfloat16) for a in state)
        for state in engine.runner.state_caches]
    prompt = _prompt(150, 150)
    ids, served = _serve(engine, {"bf16": prompt}, n_new=6)["bf16"]
    want = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, want)) > 10 * TOL


def test_bfloat16_pages_fail_the_tolerance(params):
    """The second control: the paired K and V rows kept in bfloat16 (one
    layer of five attends them: 2e-4, ten times the limit)."""
    engine = LLMEngine(params, CFG, EngineConfig(**ENGINE))
    engine.runner.kv_caches = tfm.HybridCaches(
        [(k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))
         for k, v in engine.runner.kv_caches.kv],
        engine.runner.kv_caches.state)
    prompt = _prompt(150, 150)
    ids, served = _serve(engine, {"bf16kv": prompt}, n_new=6)["bf16kv"]
    want = _reference(params, prompt, ids)
    assert max(abs(a - b) for a, b in zip(served, want)) > 5 * TOL


# ---------------------------------------------- chunks, driven by hand
def _caches(n_seqs: int):
    pages = [(jnp.zeros((1, 32, PAGE, 128), jnp.float32),) * 2]
    state = [tuple(jnp.zeros((n_seqs, *shape), jnp.float32)
                   for shape in CFG.state_shapes("conv"))
             for _ in range(CFG.num_state_layers)]
    return tfm.HybridCaches(pages, state)


def _unified_step(params, caches, chunks, n_seqs: int, t_pad: int = 64):
    """One ``forward_unified`` step over ``chunks`` = [(sequence, its
    tokens of this step, tokens it already holds)], each sequence on
    pages ``4 s .. 4 s + 3`` and slot ``s``.  Returns ({sequence: logprobs
    after its chunk's last token}, new caches)."""
    tok = np.zeros((t_pad,), np.int32)
    pos = np.zeros((t_pad,), np.int32)
    slots = np.full((t_pad,), -1, np.int32)
    tables = np.zeros((n_seqs, 4), np.int32)
    seq_lens = np.zeros((n_seqs,), np.int32)
    q_lens = np.zeros((n_seqs,), np.int32)
    cu = np.zeros((n_seqs + 1,), np.int32)
    state_slots = np.full((n_seqs,), n_seqs, np.int32)
    row, last = 0, {}
    for i, (s, new, held) in enumerate(chunks):
        n = len(new)
        cu[i] = row
        tok[row:row + n] = new
        pos[row:row + n] = held + np.arange(n)
        page_ids = 1 + 4 * s + np.arange(4)
        tables[i] = page_ids
        at = held + np.arange(n)
        slots[row:row + n] = page_ids[at // PAGE] * PAGE + at % PAGE
        seq_lens[i], q_lens[i], state_slots[i] = held + n, n, s
        last[s] = row + n - 1
        row += -(-n // 8) * 8
    cu[len(chunks):] = row
    hidden, caches = tfm.forward_unified(
        params, CFG, jnp.asarray(tok), jnp.asarray(pos), caches,
        jnp.asarray(slots), jnp.asarray(tables), jnp.asarray(seq_lens),
        jnp.asarray(cu), jnp.asarray(q_lens),
        jnp.asarray([len(chunks)], jnp.int32),
        state_slots=jnp.asarray(state_slots))
    logp = jax.nn.log_softmax(
        tfm.logits_from_hidden(params, CFG, hidden), -1)
    return {s: logp[r] for s, r in last.items()}, caches


@pytest.mark.parametrize("cuts", [
    (1, 2, 1, 5, 2, 9), (7, 1, 1, 1, 10), (2, 18), (20,)],
    ids=["1-2-1-5-2-9", "7-1-1-1-10", "2-18", "whole"])
def test_chunked_prefill_carries_the_taps_across_boundaries(params, cuts):
    """A prompt of 20 tokens fed in chunks, chunks of one and of two
    tokens among them (fewer than the two rows the taps reach back: a
    chunk's new state is then part carried rows, part its own): after
    every chunk the last token's logprobs equal the reference's at that
    position."""
    prompt = _prompt(30, 20)
    want = arch.reference_logprobs(params, CONFIG, prompt, len(prompt))
    caches, held = _caches(1), 0
    for n in cuts:
        got, caches = _unified_step(
            params, caches, [(0, prompt[held:held + n], held)], 1)
        held += n
        assert float(jnp.abs(got[0] - want[held - 1]).max()) < TOL, held


def test_a_ragged_step_keeps_its_segments_apart(params):
    """Three sequences in one step (a chunk of 9, a one-token decode row,
    a chunk of 2 that continues a sequence): nothing leaks from one
    segment's last tokens into the next one's first.  Each equals the
    reference over its own tokens."""
    a, b, c = _prompt(31, 9), _prompt(32, 14), _prompt(33, 12)
    caches = _caches(3)
    # b and c already hold 13 and 10 tokens
    _, caches = _unified_step(params, caches,
                              [(1, b[:13], 0), (2, c[:10], 0)], 3)
    got, _ = _unified_step(
        params, caches, [(0, a, 0), (1, b[13:], 13), (2, c[10:], 10)], 3)
    for s, prompt in enumerate((a, b, c)):
        want = arch.reference_logprobs(params, CONFIG, prompt, 1)[0]
        assert float(jnp.abs(got[s] - want).max()) < TOL, s


# ------------------------------------------------------ slots and pages
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
    (taps,) = eng.runner.state_caches[0]
    assert taps.shape == (4, 2, 128)
    assert float(jnp.abs(taps[slot]).max()) > 0  # and left dirty
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
    counted, because pages hold no convolution state, and the second
    answer still agrees with the reference."""
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


def test_sixty_four_sequences_joining_and_leaving_equal_each_alone(params):
    """64 requests of unequal prompts and lengths through an engine of 16
    seats: they wait, join full decode batches beside others' chunks and
    leave at different steps, slots and pages handed on.  Every one gives
    the tokens and logprobs it gives alone."""
    engine = LLMEngine(params, CFG, EngineConfig(
        **{**ENGINE, "max_num_seqs": 16, "num_pages": 128,
           "max_num_batched_tokens": 128}))
    rng = np.random.default_rng(64)
    prompts = {f"s{i}": _prompt(100 + i, int(rng.integers(5, 60)))
               for i in range(64)}
    n_new = {rid: int(rng.integers(4, 20)) for rid in prompts}
    held = []
    together = _serve(
        engine, prompts, n_new=n_new,
        after_step=lambda n: held.append(
            engine.scheduler.kv.state_slots_in_use))
    assert max(held) >= 12 and held[-1] == 0  # read after a step freed
    assert engine.metrics_snapshot()["state"]["slots_in_use"] == 0
    for rid, prompt in prompts.items():
        alone = _serve(engine, {rid + "_alone": prompt},
                       n_new=n_new[rid])[rid + "_alone"]
        assert alone[0] == together[rid][0], rid
        np.testing.assert_allclose(alone[1], together[rid][1], atol=TOL)


# ------------------------------------------------------------ the router
def test_the_router_picks_on_s_plus_b_and_weighs_with_s(params):
    """A sparse layer of the program against the reference's, under a
    bias large enough to change picks: the same sum; selecting without the
    bias, or weighing with it, gives another."""
    layer = dict(params["layers"][2])
    layer["router"] = dict(layer["router"], e_bias=jnp.asarray(
        [0.4, -0.4, 0.3, -0.3, 0.2, -0.2, 0.1, -0.1], jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(7), (24, CFG.hidden_size))
    got = tfm._mlp(layer, CFG, x)
    with jax.default_matmul_precision("highest"):
        want = arch._sparse_mlp(layer, x, 2, True, 1.0)
        s = jax.nn.sigmoid(x @ layer["router"]["w"])
    assert float(jnp.abs(got - want).max()) < TOL
    b = layer["router"]["e_bias"]
    _, on_s = jax.lax.top_k(s, 2)
    _, on_sb = jax.lax.top_k(s + b, 2)
    assert bool(jnp.any(jnp.sort(on_s, -1) != jnp.sort(on_sb, -1)))
    no_bias = dict(layer, router={"w": layer["router"]["w"]})
    assert float(jnp.abs(tfm._mlp(no_bias, CFG, x) - want).max()) > 1e-3
    # weights from s + b instead of s
    w_sb = jnp.take_along_axis(s + b, on_sb, -1)
    w_s = jnp.take_along_axis(s, on_sb, -1)
    assert float(jnp.abs(w_sb / w_sb.sum(-1, keepdims=True)
                         - w_s / w_s.sum(-1, keepdims=True)).max()) > 1e-2


# --------------------------------------------------------------- counted
def test_the_state_cache_is_counted(eng):
    """Occupancy and the slots' life in /metrics?format=json and the
    flight record, and what the 64-wide pages hold (docs/observability.md)."""
    _serve(eng, {"count_a": _prompt(9, 20), "count_b": _prompt(10, 20)})
    snap = eng.metrics_snapshot()
    assert snap["state"]["slots_total"] == 4
    assert snap["state"]["slots_in_use"] == 0
    assert snap["state"]["state_slot_allocs_total"] >= 2
    hist = snap["state_slots"]
    assert hist["count"] > 0 and 1 <= hist["sum"] / hist["count"] <= 4
    assert all(r["state_rows"] == r["prefills"] + r["decodes"]
               for r in eng.flight.tail())
    # four conv layers x 4 slots x [2, 128] float32
    assert snap["device_memory"]["components"]["state_slots"]["bytes"] \
        == 4 * 4 * 2 * 128 * 4
    kv = snap["kv"]
    assert (kv["layout"], kv["row_width"], kv["row_used"]) == ("kv", 128, 128)
    # K and V of two 64-wide heads, float32 here: no padding lane
    assert kv["bytes_per_token_layer"] == 2 * 2 * 64 * 4


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
    model whose sequences hold a slot of taps, as for Qwen3-Next."""
    with pytest.raises(ValueError, match=names):
        LLMEngine(params, CFG, EngineConfig(**ENGINE, **kwargs))
