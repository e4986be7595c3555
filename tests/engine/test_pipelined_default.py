"""The two-slot step as the engine's own step (docs/async_engine.md).

An AR engine built by a user who sets nothing dispatches step N+1 before
it retires step N.  Three things make that safe to leave on:

- the feed of in-flight tokens compiles nothing once ``precompile`` has
  run, however many rows are fed and whichever kind of step left them;
- a request that finishes mid-batch leaves one overshoot row in flight
  whose write lands in a slot, a page, a window page or an index key the
  host has already handed to the next request: the streams stay those of
  the synchronous step, in every cache layout a cell serves;
- a schedule that may preempt runs with every token retired, so no
  request recomputes for ever on the one token it keeps losing;
- the choice is the engine's: a default AR engine's flight records say
  ``pipelined``, and an engine of another kind is told nothing about a
  value nobody set.
"""

import logging
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
from vllm_omni_tpu.models import dots3_note, glm4_moe_lite, qwen3_next
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.sampling_params import SamplingParams
from vllm_omni_tpu.worker import model_runner

VOCAB = 256
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _prompt(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(3, VOCAB, n).tolist()


def _greedy(n: int, **kw) -> SamplingParams:
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True,
                          **kw)


# ------------------------------------------------- (a) the feed compiles nothing
@pytest.mark.parametrize("tp", [1, 2], ids=["one_device", "tp2"])
def test_no_compilation_after_precompile_whatever_is_fed(tp):
    """The benchmark's own test (``benchmark/run.py``: every
    ``backend_compile_duration`` event in the window makes a run
    incorrect), on a mix that feeds 1..S rows from both kinds of handle
    into both dispatch halves.  Under a mesh too: a fed array comes back
    committed to it, and the executables are warmed on ids placed so."""
    cfg = tfm.TransformerConfig.tiny(vocab_size=VOCAB)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    # what another test of this process compiled must not stand in for
    # what precompile warms
    model_runner._take_rows.clear_cache()
    model_runner._put_rows.clear_cache()
    seats = 6  # batch buckets 1, 2, 4, 6
    eng = LLMEngine(params, cfg, EngineConfig(
        num_pages=128, page_size=4, max_model_len=128, max_num_seqs=seats,
        max_num_batched_tokens=32, enable_chunked_prefill=True,
        dtype=jnp.float32, warmup=True, tensor_parallel_size=tp))
    runner = eng.runner
    fed: set[tuple] = set()
    half = [None]

    def spy(name, orig):
        def wrapped(*args, **kw):
            half[0] = name
            return orig(*args, **kw)
        return wrapped

    runner.dispatch_decode = spy("decode", runner.dispatch_decode)
    runner._dispatch_unified = spy("unified", runner._dispatch_unified)
    feed_tokens = runner._feed_tokens

    def feed_spy(token_host, feed, prev):
        if feed:
            fed.add((prev.kind, half[0], len(feed)))
        return feed_tokens(token_host, feed, prev)

    runner._feed_tokens = feed_spy
    compiled: list[str] = []

    def listener(event, secs, **kw):
        if event == COMPILE_EVENT:
            compiled.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        # arrivals staggered so that the batch grows a row at a time (a
        # chunk beside fed decode rows: decode handle -> unified step, then
        # unified -> unified while the 70-token prompts chunk), holds at
        # every size up to the seats, and shrinks as the short ones finish
        lengths = [9, 70, 12, 70, 5, 17, 70, 8]
        for i, n in enumerate(lengths):
            eng.add_request(_prompt(i, n), _greedy(24 + 7 * (i % 3)),
                            request_id=f"r{i}")
            for _ in range(3):
                eng.step()
        while eng.has_unfinished_requests:
            eng.step()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert not compiled, f"{len(compiled)} compilations after warm-up"
    for prev_kind in ("decode", "unified"):
        for into in ("decode", "unified"):
            assert any(k == prev_kind and h == into for k, h, _ in fed), \
                (prev_kind, into, sorted(fed))
    assert {n for _, _, n in fed} >= set(range(1, seats + 1)), sorted(fed)
    assert not eng.async_fallback
    paths = {r["path"] for r in eng.flight.tail()}
    assert paths == {"pipelined"}


# ------------------------------ (b) an overshoot row into what the next request owns
def _thinker():
    return tfm.TransformerConfig.tiny(vocab_size=VOCAB)


def _qwen3_next():
    return qwen3_next.tiny_config(VOCAB)


def _glm():
    return glm4_moe_lite.tiny_config(VOCAB)


def _dots3():
    return dots3_note.expert_share(dots3_note.tiny_config(VOCAB), held=4,
                                   offset=4)


def _serve_three(engine) -> tuple[dict, dict]:
    """Two seats, three requests: ``short`` finishes first, mid-batch, and
    ``next`` takes its seat (its state slot, its pages) while ``long`` runs
    on.  Returns request -> (ids, logprobs) and what each request held."""
    engine.add_request(_prompt(1, 37), _greedy(3, logprobs=0),
                       request_id="short")
    engine.add_request(_prompt(2, 45), _greedy(14, logprobs=0),
                       request_id="long")
    engine.add_request(_prompt(3, 41), _greedy(6, logprobs=0),
                       request_id="next")
    kv = engine.scheduler.kv
    got, held = {}, {}
    while engine.has_unfinished_requests:
        for o in engine.step():
            got[o.request_id] = (
                [int(t) for t in o.outputs[0].token_ids],
                [float(e["logprob"]) for e in o.outputs[0].logprobs])
        for rid in ("short", "long", "next"):
            if kv.block_table(rid):
                h = held.setdefault(rid, {"pages": set(), "window": set(),
                                          "slot": -1})
                h["pages"] |= set(kv.block_table(rid))
                h["window"] |= set(kv.window_table(rid)) - {0}
                h["slot"] = kv.state_slot(rid)
    return got, held


@pytest.mark.parametrize("make_cfg", [_thinker, _qwen3_next, _glm, _dots3])
def test_an_overshoot_row_leaves_the_next_owner_untouched(make_cfg):
    cfg = make_cfg()
    params = tfm.init_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    # 8 pages of 16 hold two sequences of 45 + 14 tokens and no third: the
    # next request is admitted into the pages the short one gave back
    common = dict(num_pages=8, page_size=16, max_model_len=128,
                  max_num_seqs=2, max_num_batched_tokens=64,
                  enable_chunked_prefill=True, dtype=jnp.float32,
                  enable_prefix_caching=False)
    sync = LLMEngine(params, cfg, EngineConfig(async_scheduling=False,
                                               **common))
    want, _ = _serve_three(sync)
    eng = LLMEngine(params, cfg, EngineConfig(**common))
    got, held = _serve_three(eng)

    assert {r["path"] for r in eng.flight.tail()} == {"pipelined"}
    assert {r["path"] for r in sync.flight.tail()} == {"sync"}
    # the overshoot: the pipelined engine dispatched decode rows the
    # synchronous one never did, and counted none of them
    rows = lambda e: sum(r["decodes"] for r in e.flight.tail())  # noqa: E731
    assert rows(eng) > rows(sync)
    assert (eng.step_metrics.tokens_generated
            == sync.step_metrics.tokens_generated == 3 + 14 + 6)
    # and it landed in what the next request was handed
    assert held["next"]["pages"] & held["short"]["pages"]
    if cfg.num_state_layers:
        assert held["next"]["slot"] == held["short"]["slot"] >= 0
    if cfg.num_window_layers:
        assert held["next"]["window"] & held["short"]["window"]
    for rid in ("short", "long", "next"):
        assert got[rid][0] == want[rid][0], rid
        np.testing.assert_allclose(got[rid][1], want[rid][1], atol=2e-5,
                                   err_msg=rid)
    assert eng.scheduler.kv.num_free_pages == sync.scheduler.kv.num_free_pages
    assert eng._inflight is None


def test_a_schedule_that_may_preempt_runs_with_every_token_retired():
    """Twelve pages for two prompts of 30 and 24 tokens and their outputs:
    the pool runs out, and the steps whose rows may not all get their pages
    leave the pipeline (``kv_pressure``), so that a victim loses no token
    it had sampled.  The streams are the synchronous engine's, preempted
    alike."""
    cfg = tfm.TransformerConfig.tiny(vocab_size=VOCAB)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    common = dict(num_pages=12, page_size=4, max_model_len=128,
                  max_num_seqs=4, max_num_batched_tokens=16,
                  enable_chunked_prefill=True, enable_prefix_caching=False,
                  dtype=jnp.float32)

    def run(**kw):
        eng = LLMEngine(params, cfg, EngineConfig(**common, **kw))
        eng.add_request(_prompt(7, 30), _greedy(6), request_id="a")
        outs, steps = {}, 0
        while eng.has_unfinished_requests:
            for o in eng.step():
                outs[o.request_id] = o.outputs[0].token_ids
            steps += 1
            if steps == 1:
                eng.add_request(_prompt(8, 24), _greedy(6), request_id="b")
        return eng, outs

    sync, want = run(async_scheduling=False)
    eng, got = run()
    assert got == want and set(got) == {"a", "b"}
    assert sync.scheduler.num_preemptions > 0
    assert eng.scheduler.num_preemptions > 0
    assert eng.async_fallback.get("kv_pressure", 0) > 0
    assert eng.steps_by_path["pipelined"] > 0  # and back, once there is room
    assert eng.scheduler.kv.num_free_pages == 12 and eng._inflight is None


# ------------------------------------------------ (c) the choice is the engine's
@contextmanager
def _engine_log():
    """Records of the engine's logger (the package's root does not
    propagate, so pytest's own capture sees none of them)."""
    records: list[logging.LogRecord] = []
    handler = logging.Handler(level=logging.WARNING)
    handler.emit = records.append
    log = logging.getLogger("vllm_omni_tpu.engine.llm_engine")
    log.addHandler(handler)
    try:
        yield records
    finally:
        log.removeHandler(handler)


def _about_async(records) -> list[str]:
    return [r.getMessage() for r in records
            if "async_scheduling" in r.getMessage()]


def test_a_default_engine_is_pipelined_and_another_kind_hears_nothing():
    cfg = tfm.TransformerConfig.tiny(vocab_size=VOCAB)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    user = EngineConfig(num_pages=64, page_size=4, max_model_len=128,
                        max_num_seqs=4, dtype=jnp.float32)
    assert user.async_scheduling is None
    eng = LLMEngine(params, cfg, user)
    assert eng.config.async_scheduling is True
    assert user.async_scheduling is None  # the caller's object is its own
    eng.generate([_prompt(0, 9), _prompt(1, 5)], _greedy(6))
    records = eng.flight.tail()
    assert records and {r["path"] for r in records} == {"pipelined"}
    snap = eng.metrics_snapshot()
    assert snap["async_fallback"] == {}
    assert snap["pipeline"]["pipelined_steps"] == len(records)
    assert snap["pipeline"]["sync_steps"] == 0
    # one drain: the overshoot left when the queue ran dry
    assert snap["pipeline"]["drains"] == 1

    with _engine_log() as heard:
        gen = LLMEngine(params, cfg, EngineConfig(
            num_pages=64, page_size=4, max_model_len=128, max_num_seqs=4,
            dtype=jnp.float32, worker_type="generation"))
    assert gen.config.async_scheduling is False
    assert not _about_async(heard)
    # the control: a value somebody did set is answered
    with _engine_log() as heard:
        LLMEngine(params, cfg, EngineConfig(
            num_pages=64, page_size=4, max_model_len=128, max_num_seqs=4,
            dtype=jnp.float32, worker_type="generation",
            async_scheduling=True))
    assert _about_async(heard)
