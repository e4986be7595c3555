"""Unified ragged mixed batching: ONE token-packed device dispatch for
prefill + decode (ops/ragged_paged_attention.py through
ARModelRunner._unified_fn), greedy bit-identical to the split path, and
— with async_scheduling — mixed steps that stay pipelined instead of
draining (docs/ragged_batching.md)."""

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
                    max_num_seqs=8, max_num_batched_tokens=32,
                    dtype=jnp.float32)
    defaults.update(kw)
    return LLMEngine(params, cfg, EngineConfig(**defaults))


PROMPTS = [[1, 5, 9, 2, 7], [3, 3, 8], [11, 4, 6, 1, 2, 9, 5],
           [9, 9, 1, 2], [7, 1], [2, 4, 8, 16, 32, 1]]
GREEDY = SamplingParams(temperature=0.0, max_tokens=10, ignore_eos=True)


def _spy_execute(eng):
    """Record (prefills, decodes, device dispatches) per step, at the two
    dispatch halves that the synchronous ``execute`` and the pipelined
    step both call."""
    records = []
    runner = eng.runner

    def spy(orig, count):
        def wrapped(*args):
            d0 = runner.dispatch_count
            out = orig(*args)
            records.append(count(*args) + (runner.dispatch_count - d0,))
            return out
        return wrapped

    runner._dispatch_unified = spy(
        runner._dispatch_unified,
        lambda decodes, prefills, prev: (len(prefills), len(decodes)))
    runner.dispatch_decode = spy(
        runner.dispatch_decode,
        lambda scheds, prev=None: (0, len(scheds)))
    return records


def _run_staggered(eng, sp=GREEDY, late=((2, (2, 3)), (4, (4,)))):
    """Two arrival waves land while earlier requests decode — every
    step between waves is a MIXED prefill+decode batch."""
    late = dict(late)
    outs = {}
    eng.add_request(PROMPTS[0], sp, request_id="r0")
    eng.add_request(PROMPTS[1], sp, request_id="r1")
    steps = 0
    while eng.has_unfinished_requests:
        for o in eng.step():
            outs[o.request_id] = o.outputs[0].token_ids
        steps += 1
        for idx in late.pop(steps, ()):
            eng.add_request(PROMPTS[idx], sp, request_id=f"r{idx}")
    return outs


# ------------------------------------------------------- equality oracle
def test_unified_greedy_matches_split_batch(tiny_model):
    params, cfg = tiny_model
    base = _engine(params, cfg).generate(PROMPTS[:4], GREEDY)
    outs = _engine(params, cfg, unified_batching=True).generate(
        PROMPTS[:4], GREEDY)
    for b, u in zip(base, outs):
        assert u.outputs[0].token_ids == b.outputs[0].token_ids


@pytest.mark.slow  # fast siblings: oracle fixture [staggered_mixed]
# replays this exact stream bit-identically, and
# test_mixed_step_is_one_device_dispatch proves mixed batches form
def test_unified_greedy_matches_split_staggered_mixed(tiny_model):
    params, cfg = tiny_model
    split = _run_staggered(_engine(params, cfg))
    eng = _engine(params, cfg, unified_batching=True)
    records = _spy_execute(eng)
    uni = _run_staggered(eng)
    assert split == uni
    mixed = [r for r in records if r[0] and r[1]]
    assert mixed, "staggered waves never produced a mixed batch"


def test_mixed_step_is_one_device_dispatch(tiny_model):
    """The tentpole contract: a mixed prefill+decode step executes as
    ONE device dispatch — and since PR 11 the split executor is gone,
    so this holds with OR without the unified_batching scheduling
    policy flag (the flag only changes admission order/chunking)."""
    params, cfg = tiny_model
    for flag in (True, False):
        eng = _engine(params, cfg, unified_batching=flag)
        records = _spy_execute(eng)
        _run_staggered(eng)
        mixed = [r for r in records if r[0] and r[1]]
        assert mixed, f"no mixed steps at unified_batching={flag}"
        assert all(r[2] == 1 for r in mixed), (flag, records)


def test_split_executor_is_gone():
    """The refactor is the point: the fallback matrix and the split
    executor cannot come back silently."""
    from vllm_omni_tpu.worker.model_runner import ARModelRunner

    for name in ("_execute_split", "_unified_eligible",
                 "_run_spec_decode", "_run_prefill", "_run_decode",
                 "_run_decode_multi", "_batched_verify_probs",
                 "_rejection_accept", "_sample_and_record"):
        assert not hasattr(ARModelRunner, name), name


def test_unified_sampled_seeded_reproducible(tiny_model):
    params, cfg = tiny_model
    sp = SamplingParams(temperature=0.9, seed=11, max_tokens=8,
                        ignore_eos=True)
    a = _engine(params, cfg, unified_batching=True).generate(
        PROMPTS[:3], sp)
    b = _engine(params, cfg, unified_batching=True).generate(
        PROMPTS[:3], sp)
    for x, y in zip(a, b):
        assert x.outputs[0].token_ids == y.outputs[0].token_ids


# -------------------------------------------------- chunked prefill rides
def test_chunked_prefill_is_the_mechanism(tiny_model):
    """unified_batching implies chunking: a prompt longer than the step
    budget is accepted and chunked WITHOUT enable_chunked_prefill."""
    params, cfg = tiny_model
    long_prompt = [(i % 13) + 1 for i in range(40)]
    base = _engine(params, cfg, enable_chunked_prefill=True,
                   max_num_batched_tokens=16).generate(
        [long_prompt], GREEDY)
    eng = _engine(params, cfg, unified_batching=True,
                  max_num_batched_tokens=16)
    outs = eng.generate([long_prompt], GREEDY)
    assert outs[0].outputs[0].token_ids == base[0].outputs[0].token_ids


def test_chunk_resume_after_preemption_mid_chunk(tiny_model):
    """Page pressure preempts a request mid-prefill; its recompute
    resumes through the unified path, token-identical to split."""
    params, cfg = tiny_model
    kw = dict(num_pages=12, max_num_seqs=4, max_num_batched_tokens=16,
              enable_prefix_caching=False)
    long_a = [(i % 11) + 1 for i in range(30)]
    long_b = [(i % 7) + 2 for i in range(24)]
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)

    def run(**extra):
        eng = _engine(params, cfg, **kw, **extra)
        outs = {}
        eng.add_request(long_a, sp, request_id="a")
        steps = 0
        while eng.has_unfinished_requests:
            for o in eng.step():
                outs[o.request_id] = o.outputs[0].token_ids
            steps += 1
            if steps == 1:
                eng.add_request(long_b, sp, request_id="b")
        return eng, outs

    eng_s, split = run(enable_chunked_prefill=True)
    eng_u, uni = run(unified_batching=True)
    assert split == uni
    # the tight pool must actually have exercised preemption, and every
    # page must come home
    assert eng_u.scheduler.num_preemptions > 0
    assert eng_u.scheduler.kv.num_free_pages == 12


def test_resume_chunk_past_prompt_not_mistaken_for_verify(tiny_model):
    """Review regression (PR 11): a preempt-resume recompute chunk can
    start PAST the prompt with width > 1 — same (width, start) shape as
    a spec verify row.  Retire must classify by how the row was
    ASSEMBLED (handle.spec_rows), not by a predicate: the old check
    returned the chunk's token as a one-element accepted LIST, whose
    scheduler branch rewinds the multi-token advance to 1 and wedges
    the resume into n-tokens-of-forward-per-emitted-token."""
    params, cfg = tiny_model
    prompt = PROMPTS[3]  # 4 tokens == the chunk budget below
    kw = dict(max_num_seqs=4, max_num_batched_tokens=4,
              enable_chunked_prefill=True, enable_prefix_caching=False,
              page_size=4, max_model_len=128, dtype=jnp.float32)
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)

    def run(preempt: bool):
        # the synchronous step: after three of them three tokens are out
        eng = _engine(params, cfg, async_scheduling=False, **kw)
        outs = []
        eng.add_request(prompt, sp, request_id="a")
        steps = 0
        while eng.has_unfinished_requests:
            outs.extend(eng.step())
            steps += 1
            if preempt and steps == 3:
                # deterministic preemption with 3 generated tokens:
                # the resume recomputes [0..4) then [4..7) — a FINAL
                # chunk of width 3 starting exactly at the prompt
                # boundary, the verify-row look-alike
                _, req = eng.scheduler.find_request("a")
                assert req is not None and len(req.output_token_ids) == 3
                eng.scheduler._preempt(req)
        return eng, outs[0].outputs[0].token_ids

    _, want = run(False)
    eng, got = run(True)
    assert eng.scheduler.num_preemptions == 1
    assert got == want
    # no draft head: nothing may ever count as a verify proposal
    assert eng.runner.spec_stats["proposed"] == 0


def test_prefix_cache_hit_feeds_unified_step(tiny_model):
    """An APC prefix hit resumes mid-prompt: the remainder chunk rides
    the unified executable (start_pos > 0), token-identical to split."""
    params, cfg = tiny_model
    shared = [5, 3, 7, 1, 9, 2, 4, 6]  # two full pages at page_size=4
    prompt = shared + [8, 8]
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)

    def run(**extra):
        eng = _engine(params, cfg, max_num_batched_tokens=32, **extra)
        first = eng.generate([prompt], sp)[0].outputs[0].token_ids
        hits0 = eng.scheduler.kv.prefix_hits
        second = eng.generate([prompt], sp)[0].outputs[0].token_ids
        assert eng.scheduler.kv.prefix_hits > hits0, "no APC hit"
        return first, second

    sf, ss = run()
    uf, us = run(unified_batching=True)
    assert sf == uf and ss == us
    assert sf == ss  # cached prefix must not change the stream


# ------------------------------------------------------- async pipeline
@pytest.mark.slow  # fast siblings: oracle fixture [async_unified] pins
# the async-unified stream; test_async_greedy_matches_sync_mixed_waves
# pins async==sync over mixed arrival waves
def test_async_unified_matches_sync_and_pipelines_prefills(tiny_model):
    params, cfg = tiny_model
    split = _run_staggered(_engine(params, cfg))
    eng = _engine(params, cfg, unified_batching=True,
                  async_scheduling=True)
    dispatched = []
    orig = eng.runner.dispatch_unified
    eng.runner.dispatch_unified = lambda so, prev=None: (
        dispatched.append((len(so.prefills), len(so.decodes)))
        or orig(so, prev))
    asy = _run_staggered(eng)
    assert split == asy
    # mixed batches were DISPATCHED (pipelined), not drained
    assert any(p and d for p, d in dispatched), dispatched
    assert "prefill" not in eng.async_fallback, eng.async_fallback


@pytest.mark.slow  # fast siblings: test_async_eos_one_step_lag_rollback
#                    pins overshoot discard + KV rewind, oracle fixture
#                    [async_unified] pins async-unified stream parity
def test_async_unified_stop_token_overshoot(tiny_model):
    """A stop token lands while the next (possibly mixed) step is in
    flight: the overshoot token is discarded, streams match sync, and
    the page pool drains to empty."""
    params, cfg = tiny_model
    probe = _engine(params, cfg).generate([PROMPTS[0]], GREEDY)
    stop = probe[0].outputs[0].token_ids[4]
    sp = SamplingParams(temperature=0.0, max_tokens=10,
                        stop_token_ids=[stop])
    split = _run_staggered(_engine(params, cfg), sp=sp)
    eng = _engine(params, cfg, unified_batching=True,
                  async_scheduling=True)
    asy = _run_staggered(eng, sp=sp)
    assert split == asy
    assert eng.scheduler.kv.num_free_pages == 64


@pytest.mark.slow  # fast siblings: test_split_executor_is_gone pins the
# retirement structurally; test_async_step's per-workload pipelining
# tests (logprobs/spec/collect_hidden/embeds) each prove their reason
# never trips
def test_async_fallback_reasons_retired(tiny_model):
    """The PR 11 acceptance contract: the spec / logprobs /
    collect_hidden / embeds / prefill drain reasons are structurally
    impossible — a workload exercising logprobs + staggered prefills
    leaves all of them absent, with or without the unified scheduling
    policy flag."""
    params, cfg = tiny_model
    sp_lp = SamplingParams(temperature=0.0, max_tokens=4,
                           ignore_eos=True, logprobs=2)
    for flag in (True, False):
        eng = _engine(params, cfg, unified_batching=flag,
                      async_scheduling=True)
        eng.generate([PROMPTS[0]], sp_lp)
        _run_staggered(eng)
        for reason in ("spec", "logprobs", "collect_hidden", "embeds",
                       "prefill"):
            assert reason not in eng.async_fallback, (
                flag, eng.async_fallback)


# ------------------------------------------- retired fallback matrix
def test_logprobs_request_rides_unified(tiny_model):
    """logprobs no longer force the split path (which is gone): the
    unified/decode executables compute chosen+top-k on device and the
    entries match the pre-refactor oracle semantics."""
    params, cfg = tiny_model
    sp = SamplingParams(temperature=0.0, max_tokens=5, ignore_eos=True,
                        logprobs=2)
    base = _engine(params, cfg).generate(PROMPTS[:2], sp)
    outs = _engine(params, cfg, unified_batching=True).generate(
        PROMPTS[:2], sp)
    for b, u in zip(base, outs):
        assert u.outputs[0].token_ids == b.outputs[0].token_ids
        assert u.outputs[0].logprobs and len(u.outputs[0].logprobs) == 5
        for got, want in zip(u.outputs[0].logprobs,
                             b.outputs[0].logprobs):
            assert got["top_ids"] == want["top_ids"]
            assert abs(got["logprob"] - want["logprob"]) < 1e-4


# ------------------------------------------------------------- metrics
def test_padding_efficiency_beats_bucket_grid(tiny_model):
    """Ragged prompt lengths: the deleted split path paid (batch, seq)
    bucket padding on its prefill steps; the unified packer pays only
    token-block alignment.  Compare the measured efficiency against
    the bucket-grid cost the SAME prompts would have paid (computed
    host-side from the old bucketing rule: batch padded to a power of
    two, every row padded to the longest prompt's seq bucket)."""
    params, cfg = tiny_model
    lens = (33, 47, 18, 25)
    prompts = [[(i % 9) + 1 for i in range(n)] for n in lens]
    sp = SamplingParams(temperature=0.0, max_tokens=3, ignore_eos=True)
    kw = dict(max_num_batched_tokens=128, max_model_len=128,
              num_pages=128)
    eng = _engine(params, cfg, unified_batching=True, **kw)
    eng.generate(prompts, sp)
    eff = eng.step_metrics.padding_efficiency
    assert 0.0 < eff <= 1.0
    # the split grid's prefill step: 4 prompts -> batch bucket 4, seq
    # bucket 64 (covers 47) -> 256 padded rows for 123 useful tokens
    split_prefill_eff = sum(lens) / (4 * 64)
    assert eff > split_prefill_eff, (eff, split_prefill_eff)


def test_padding_counts_verify_tokens_as_useful(tiny_model):
    """MFU truthfulness when spec rows dominate: every candidate
    position of a verify row is scored work, so it counts USEFUL; only
    block-alignment slack pads.  A spec run must therefore report more
    useful tokens than tokens emitted (rejected candidates were still
    computed), and efficiency stays in (0, 1]."""
    params, cfg = tiny_model
    from vllm_omni_tpu.engine import LLMEngine

    def draft_fn(hidden, tokens, positions):
        return jnp.tile(tokens[:, None], (1, 3))

    from vllm_omni_tpu.engine import EngineConfig

    eng = LLMEngine(params, cfg, EngineConfig(
        num_pages=64, page_size=4, max_model_len=128, max_num_seqs=4,
        dtype=jnp.float32, num_speculative_tokens=3), draft_fn=draft_fn)
    outs = eng.generate(PROMPTS[:2], GREEDY)
    emitted = sum(len(o.outputs[0].token_ids) for o in outs)
    prompt_toks = sum(len(p) for p in PROMPTS[:2])
    stats = eng.runner.spec_stats
    assert stats["proposed"] > stats["accepted"], \
        "rejections never exercised"
    # useful = prompts + every candidate position scored (accepted OR
    # rejected) — strictly more than prompts + emitted when any draft
    # was rejected
    assert eng.runner.useful_tokens > prompt_toks + emitted
    eff = eng.step_metrics.padding_efficiency
    assert 0.0 < eff <= 1.0


def test_metrics_snapshot_and_exposition(tiny_model):
    """Padding, batched-tokens, compile, and fallback series render and
    validate against METRIC_SPECS (the OL6 drift-guard surface)."""
    from vllm_omni_tpu.metrics.prometheus import (
        render_exposition,
        validate_exposition,
    )

    params, cfg = tiny_model
    eng = _engine(params, cfg, unified_batching=True,
                  async_scheduling=True)
    _run_staggered(eng)
    snap = eng.metrics_snapshot()
    assert snap["padding"]["padded_tokens_total"] > 0
    assert 0.0 < snap["padding"]["efficiency"] <= 1.0
    assert snap["batched_tokens"]["count"] > 0
    assert snap["compile"]["compiles"] > 0
    assert snap["compile"]["cache_hits"] > 0
    text = render_exposition({}, {0: snap})
    assert validate_exposition(text) == []
    for needle in ("engine_step_padding_efficiency",
                   "engine_step_batched_tokens_count",
                   "jit_compiles_total",
                   "jit_compile_seconds_total"):
        assert needle in text, needle


# ------------------------------------- chunks sized in packed rows
def _spy_buckets(eng):
    """Record (t_pad, packed rows) of every unified step assembled."""
    seen = []
    orig = eng.runner._assemble_unified

    def spy(scheds, spec_rows):
        asm = orig(scheds, spec_rows)
        seen.append((asm.t_pad, asm.total))
        return asm

    eng.runner._assemble_unified = spy
    return seen


DOC = [(i * 7) % 61 + 1 for i in range(90)]
LONG = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)


def _doc_beside_decodes(eng, n):
    """``n`` short requests reach decode, then a 90-token prompt
    arrives: every step until it is computed carries one of its chunks
    beside ``n`` decode rows."""
    outs = {}
    for i in range(n):
        eng.add_request(PROMPTS[i], LONG, request_id=f"d{i}")
    for _ in range(2):
        eng.step()
    eng.add_request(DOC, GREEDY, request_id="doc")
    while eng.has_unfinished_requests:
        for o in eng.step():
            outs[o.request_id] = o.outputs[0].token_ids
    return outs


@pytest.mark.parametrize("n", [0, 1, 2, 3])
def test_chunked_step_packs_into_the_budget_bucket(tiny_model, n):
    """4 seats, 32 tokens a step: 3 decode rows and a chunk's block are
    32 rows, so no step with a chunk runs past the 32-row bucket, and
    the tokens are those of an engine that never chunks."""
    params, cfg = tiny_model
    kw = dict(max_num_seqs=4, max_model_len=128)
    eng = _engine(params, cfg, enable_chunked_prefill=True, **kw)
    seen = _spy_buckets(eng)
    got = _doc_beside_decodes(eng, n)
    assert seen and max(t for t, _ in seen) <= 32, seen
    # a whole chunk takes every row the decode rows' blocks leave
    assert (32, 32) in seen
    whole = _doc_beside_decodes(
        _engine(params, cfg, max_num_batched_tokens=128, **kw), n)
    assert got == whole
    pad = eng.metrics_snapshot()["padding"]
    assert pad["bucket_raised_steps_total"] == 0
    assert (pad["chunk_tokens_trimmed_total"] > 0) == (n > 0)


def test_decode_rows_alone_raise_the_bucket(tiny_model):
    """8 seats, 16 tokens a step: 5 decode rows hold 40 rows, past the
    budget's bucket.  None loses its seat, the chunk gets the rows left
    of the bucket they opened (64), and the tokens are unchanged."""
    params, cfg = tiny_model
    kw = dict(max_num_seqs=8, max_model_len=128)
    eng = _engine(params, cfg, enable_chunked_prefill=True,
                  max_num_batched_tokens=16, **kw)
    seen = _spy_buckets(eng)
    rec = _spy_execute(eng)
    got = _doc_beside_decodes(eng, 5)
    doc_steps = [r for r in rec if r[0] == 1 and r[1]]
    assert len(doc_steps) > 2 and all(r[1] == 5 for r in doc_steps), rec
    assert max(t for t, _ in seen) == 64
    whole = _doc_beside_decodes(
        _engine(params, cfg, max_num_batched_tokens=128, **kw), 5)
    assert got == whole
    pad = eng.metrics_snapshot()["padding"]
    assert pad["bucket_raised_steps_total"] > 0


def test_trimmed_chunks_are_counted_and_recorded(tiny_model):
    """A chunked run beside decode rows moves
    ``padding.chunk_tokens_trimmed_total`` (the sum of the flight
    records' ``chunk_tokens_trimmed``) and leaves
    ``padding.bucket_raised_steps_total`` at 0."""
    params, cfg = tiny_model
    eng = _engine(params, cfg, enable_chunked_prefill=True,
                  max_num_seqs=4, max_model_len=128)
    _doc_beside_decodes(eng, 3)
    recs = eng.flight.snapshot()["records"]
    assert all(isinstance(r["chunk_tokens_trimmed"], int)
               and r["bucket_raised"] is False for r in recs)
    trimmed = sum(r["chunk_tokens_trimmed"] for r in recs)
    # 32 rows less 3 decode rows' blocks is 8; the budget had left 29
    assert max(r["chunk_tokens_trimmed"] for r in recs) == 21
    pad = eng.metrics_snapshot()["padding"]
    assert pad["chunk_tokens_trimmed_total"] == trimmed > 0
    assert pad["bucket_raised_steps_total"] == 0


def _warmed_token_buckets(eng):
    return sorted(k[1] for k in eng.runner._jit_seen if k[0] == "unified")


def test_warmup_walks_what_the_scheduler_can_emit(tiny_model):
    """Warm-up compiles the token buckets a budgeted step can reach (4
    seats, 32 tokens: 16 and 32 of a line that runs to 128) and a
    chunked run beside decode rows then compiles nothing."""
    params, cfg = tiny_model
    eng = _engine(params, cfg, enable_chunked_prefill=True,
                  max_num_seqs=4, max_model_len=128, warmup=True)
    assert eng.runner._token_buckets == (16, 32, 64, 128)
    assert _warmed_token_buckets(eng) == [16, 32]
    compiles = eng.runner.compile_stats["compiles"]
    seen = _spy_buckets(eng)
    _doc_beside_decodes(eng, 3)
    assert {t for t, _ in seen} <= {16, 32}
    assert eng.runner.compile_stats["compiles"] == compiles


@pytest.mark.slow  # fast siblings: test_warmup_walks_what_the_scheduler_
# can_emit above; test_warmup_precompiles_all_traffic_shapes warms the
# same 1-D token-bucket line (the split executor's grid is gone, so the
# compiled surface no longer depends on the flag)
def test_warmup_precompiles_token_buckets(tiny_model):
    """Unified warmup walks the 1-D token-bucket line as far as a
    budgeted step reaches; traffic at any packed size then hits the
    shape cache (no mid-traffic compiles)."""
    params, cfg = tiny_model
    eng = _engine(params, cfg, unified_batching=True, warmup=True)
    compiles_after_warmup = eng.runner.compile_stats["compiles"]
    # 8 seats of one block are 64 rows, past the 32-token budget: the
    # reachable prefix of (16, 32, 64, 128) is its first three
    assert _warmed_token_buckets(eng) == [16, 32, 64]
    assert compiles_after_warmup >= 3
    _run_staggered(eng)
    _doc_beside_decodes(eng, 5)
    assert eng.runner.compile_stats["compiles"] == compiles_after_warmup


# ------------------------------------------------------------------ TP
@pytest.mark.slow
def test_unified_tp_token_identical(tiny_model):
    """Unified ragged step under tensor parallelism (shard_map wrap,
    local head shapes) matches the single-device split path."""
    params, cfg = tiny_model
    split = _run_staggered(_engine(params, cfg))
    eng = _engine(params, cfg, unified_batching=True,
                  tensor_parallel_size=2)
    uni = _run_staggered(eng)
    assert split == uni
