"""A served step on the engine's phase clock (introspection/
phase_clock.py): its flight record's ``phases_ms`` / ``host_ms`` /
``device_ms`` / ``cpu_ms`` / ``step``, the annotation names the helper
opens, a stall made with a fault plan, the busy wait of the
orchestrator's loop, and the journey spans of traced rows only.  All on
the CPU, at a tiny width."""

from __future__ import annotations

import asyncio
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import span_readers
from vllm_omni_tpu.config.stage import StageConfig
from vllm_omni_tpu.engine import EngineConfig, LLMEngine
from vllm_omni_tpu.entrypoints.async_omni import AsyncOmni
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.resilience.faults import FaultPlan, set_fault_plan
from vllm_omni_tpu.sampling_params import SamplingParams

ASSEMBLE = ("omni.assemble.pack", "omni.assemble.sampling",
            "omni.assemble.put")
#: the host phases of section 3 of PERF.md ("Names inside the program"):
#: the step's, the runner's and the loop's annotations
PERF_MD_PHASES = {
    "omni.step", "omni.update", "omni.record",
    "omni.schedule", "omni.kv_moves",
    "omni.assemble", "omni.dispatch", "omni.readback",
    "omni.loop", "omni.intake", "omni.emit", "omni.idle_wait",
}


def _engine(**kw) -> LLMEngine:
    cfg = tfm.TransformerConfig.tiny(vocab_size=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return LLMEngine(params, cfg, EngineConfig(
        num_pages=64, page_size=4, max_model_len=128, max_num_seqs=4,
        max_num_batched_tokens=64, dtype=jnp.float32, **kw))


def _sampled(n: int = 8) -> SamplingParams:
    return SamplingParams(temperature=0.7, top_k=5, max_tokens=n,
                          ignore_eos=True)


@pytest.fixture()
def async_omni():
    omni = AsyncOmni(stage_configs=[StageConfig(
        stage_id=0, stage_type="llm",
        engine_args={"model_factory": "tests.helpers:tiny_lm_factory",
                     "num_pages": 64, "page_size": 4, "max_model_len": 128},
        engine_input_source=[-1], final_output=True,
        final_output_type="text",
        default_sampling_params={"temperature": 0.0, "max_tokens": 4})])
    yield omni
    omni.shutdown()


def _ask(omni: AsyncOmni, prompt, max_tokens: int) -> None:
    async def run():
        return [o async for o in omni.generate(
            prompt, {"max_tokens": max_tokens, "ignore_eos": True})]

    (out,) = asyncio.run(run())
    assert len(out.outputs[0].token_ids) == max_tokens


@pytest.mark.parametrize("async_scheduling", [False, True],
                         ids=["sync", "pipelined"])
def test_a_served_steps_phases_sum_to_its_host_and_device_time(
        async_scheduling):
    engine = _engine(async_scheduling=async_scheduling)
    engine.generate([[1, 2, 3, 4, 5], [6, 7, 8]], _sampled())
    records = engine.flight.tail()
    assert len(records) >= 8
    for r in records:
        step_ms = r["host_ms"] + r["device_ms"]
        assert sum(r["phases_ms"].values()) == pytest.approx(
            step_ms, rel=0.02, abs=0.01)
        # waiting on the device is part of the step, and is not all of it
        assert 0.0 < r["device_ms"] < step_ms
        assert r["device_ms"] <= (r["phases_ms"]["omni.dispatch"]
                                  + r["phases_ms"].get("omni.readback", 0.0)
                                  + 0.002)
        assert 0.0 <= r["cpu_ms"]
        assert set(r["phases_ms"]) <= set(span_readers.PHASE_LAYER) | set(
            ASSEMBLE)
    # the step's number is the one its annotation carries: consecutive
    nums = [r["step"] for r in records]
    assert nums == list(range(nums[0], nums[0] + len(nums)))
    # host_ms + device_ms == step_ms: the histograms hold the same steps
    snap = engine.metrics_snapshot()
    assert snap["step_ms"]["count"] == len(records)
    assert snap["host_ms"]["sum"] + snap["device_ms"]["sum"] == \
        pytest.approx(snap["step_ms"]["sum"], abs=0.01)  # printed to 1 us
    assert snap["step_ms"]["sum"] == pytest.approx(
        sum(r["host_ms"] + r["device_ms"] for r in records), abs=0.01)
    # the lifetime totals hold what the records hold, and the time inside
    # omni.step before the step's mark besides
    by_name = snap["phases"]["by_name"]
    for name in ("omni.dispatch", "omni.schedule") + ASSEMBLE:
        assert by_name[name]["ms"] == pytest.approx(
            sum(r["phases_ms"].get(name, 0.0) for r in records), abs=0.05)
        assert by_name[name]["n"] >= len(records)
    assert snap["stalls"]["total"] == 0
    assert set(snap["gc"]) == {"ms_total", "collections"}


@pytest.mark.parametrize("async_scheduling", [False, None],
                         ids=["sync", "default"])
def test_one_name_two_clocks_and_the_children_of_assemble(
        monkeypatch, async_scheduling):
    """What the helper hands the profiler, with or without a session: the
    names of before PR 36 and no other; and ``omni.assemble``, one
    annotation there, is its three children on the engine's clock.  The
    pipelined step (the default) drains no tier moves, so it alone never
    opens ``omni.kv_moves``."""
    opened: list = []

    class Spy:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs = name, kwargs

        def __enter__(self):
            self.t0 = time.perf_counter_ns()

        def __exit__(self, *exc):
            opened.append((self.name, self.kwargs,
                           (time.perf_counter_ns() - self.t0) / 1e6))

    engine = _engine(async_scheduling=async_scheduling)
    engine.generate([[1, 2, 3]], _sampled(2))  # compile outside
    first = engine.flight.total_steps
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Spy)
    engine.generate([[1, 2, 3, 4, 5], [6, 7, 8]], _sampled())
    records = engine.flight.tail()[first:]
    names = {name for name, _, _ in opened}
    assert names == {"omni.step", "omni.schedule", "omni.assemble",
                     "omni.dispatch", "omni.readback", "omni.update",
                     "omni.record"} | (
        {"omni.kv_moves"} if async_scheduling is False else set())
    steps = [kw["step_num"] for name, kw, _ in opened if name == "omni.step"]
    assert steps == [r["step"] for r in records]
    # the annotation's time against the clock's, summed over the steps
    annotated = sum(ms for name, _, ms in opened if name == "omni.assemble")
    clocked = sum(ms for r in records for k, ms in r["phases_ms"].items()
                  if k.startswith("omni.assemble"))
    assert clocked == pytest.approx(annotated, rel=0.05)
    # pack, sampling and put are omni.assemble: what is left of it is the
    # entering and leaving of three blocks
    children = sum(r["phases_ms"][k] for r in records for k in ASSEMBLE)
    assert all(set(ASSEMBLE) <= set(r["phases_ms"]) for r in records)
    assert children >= 0.9 * clocked


def test_the_loop_opens_its_four_names_and_counts_its_busy_waits(
        async_omni, monkeypatch):
    """Served through the orchestrator's loop, the engine's clock holds
    every name of ``span_readers.PHASE_LAYER`` (a rename fails here), the
    list of section 3 of PERF.md; the 2 ms wait counts as a busy wait
    only while the stage has unfinished work."""
    assert set(span_readers.PHASE_LAYER) == PERF_MD_PHASES
    engine = async_omni._omni.stages[0].engine
    time.sleep(0.1)  # idle turns: waits, none of them busy
    idle = engine.metrics_snapshot()["phases"]
    assert idle["by_name"]["omni.idle_wait"]["n"] >= 5
    assert (idle["busy_waits"], idle["busy_wait_ms"]) == (0, 0.0)
    _ask(async_omni, [1, 2, 3], 12)
    time.sleep(0.05)  # the reply is out before its omni.emit has closed
    busy = engine.metrics_snapshot()["phases"]
    # (all but omni.kv_moves: the pipelined step drains no tier moves)
    assert set(busy["by_name"]) == (PERF_MD_PHASES | set(ASSEMBLE)) - {
        "omni.kv_moves"}
    # a step that finished no request is followed by the wait
    assert 8 <= busy["busy_waits"] <= 13
    assert busy["busy_wait_ms"] >= 1.9 * busy["busy_waits"]
    # the turns' phases sum to the clock's time: the loop is never
    # outside a phase but between two turns
    time.sleep(0.05)
    s0 = engine.metrics_snapshot()["phases"]
    time.sleep(0.2)
    s1 = engine.metrics_snapshot()["phases"]
    spent = sum(v["ms"] for v in s1["by_name"].values()) - sum(
        v["ms"] for v in s0["by_name"].values())
    assert spent == pytest.approx(s1["clock_ms"] - s0["clock_ms"], rel=0.02)
    # idle again: the count stands still
    assert s1["busy_waits"] == busy["busy_waits"]


def test_a_single_delayed_step_is_a_stall_and_says_where():
    """A fault plan installed for one step (``delay_ms`` as the
    environment variable sets it delays every step, which raises the
    percentile and is rightly no stall)."""
    engine = _engine()
    sp = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
    engine.add_request([1, 2, 3, 4, 5], sp)
    for _ in range(12):
        engine.step()
    assert engine.metrics_snapshot()["stalls"]["total"] == 0
    stalled = engine._steps_completed
    set_fault_plan(FaultPlan.parse("step:delay_ms=1100"))
    try:
        engine.step()
    finally:
        set_fault_plan(None)
    for _ in range(3):
        engine.step()
    stalls = engine.metrics_snapshot()["stalls"]
    assert stalls["total"] == 1
    assert 1000.0 <= stalls["ms_total"] <= 2000.0
    (rec,) = stalls["records"]
    # the delay sits in omni.step, ahead of the step's mark
    assert rec["phase"] == "omni.step" and rec["step"] == stalled
    assert rec["phases_ms"]["omni.step"] >= 1100.0
    assert rec["cpu_ms"] < 0.2 * rec["turn_ms"]  # asleep, not computing
    assert (rec["running"], rec["waiting"]) == (1, 0)
    # the record of the step itself is none the wiser: its mark came after
    step = next(r for r in engine.flight.tail() if r["step"] == stalled)
    assert step["host_ms"] + step["device_ms"] < 1000.0
    from vllm_omni_tpu.metrics import prometheus

    text = prometheus.render_exposition({}, {0: engine.metrics_snapshot()})
    assert 'engine_stalls_total{stage="0"} 1' in text
    assert 'engine_host_phase_ms_total{stage="0",phase="omni.step"}' in text
    assert prometheus.validate_exposition(text) == []


def test_a_compile_in_the_turn_is_no_stall(monkeypatch):
    engine = _engine()
    sp = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True)
    engine.add_request([1, 2, 3], sp)
    for _ in range(10):
        engine.step()
    # the next dispatch is a fresh signature that takes its time
    engine.runner._jit_seen.clear()
    orig = engine.runner._decode_sample_fn

    def slow(*args, **kwargs):
        time.sleep(1.1)
        return orig(*args, **kwargs)

    monkeypatch.setattr(engine.runner, "_decode_sample_fn", slow)
    compiles = engine.runner.compile_stats["compiles"]
    engine.step()
    assert engine.runner.compile_stats["compiles"] == compiles + 1
    assert engine.metrics_snapshot()["stalls"]["total"] == 0


def test_a_compile_with_no_phase_around_it_is_no_stall():
    """A runner driven alone (precompile): ``omni.dispatch`` is then the
    outermost phase, entering it begins the turn, and the compile has to
    be noted inside it to count for that turn."""
    engine = _engine()
    engine.generate([[1, 2, 3]], _sampled(12))  # nine stepped turns
    assert len(engine.phases._turns) >= 8

    def slow():
        time.sleep(1.05)
        return jnp.zeros(())

    engine.runner._run_jit("alone", (1,), slow)
    assert engine.metrics_snapshot()["stalls"]["total"] == 0
    # the same second with nothing compiled in it is a stall
    engine.runner._run_jit("alone", (1,), slow)
    assert engine.metrics_snapshot()["stalls"]["total"] == 1


def test_journey_spans_are_recorded_for_traced_rows_only(monkeypatch):
    from vllm_omni_tpu.engine import llm_engine

    calls: list = []

    class Recorder:
        def record(self, ctx, name, start_ts, dur_s, **kw):
            calls.append((ctx, name, start_ts, dur_s, kw))

    monkeypatch.setattr(llm_engine, "get_recorder", lambda: Recorder())
    # the synchronous step's span names (the pipelined step's dispatch
    # and retire: tests/engine/test_async_step.py)
    engine = _engine(async_scheduling=False)
    sp = SamplingParams(temperature=0.0, max_tokens=3, ignore_eos=True)
    engine.generate([[1, 2, 3], [4, 5, 6, 7]], sp)
    assert calls == []  # no row carried a context: no call at all
    ctx = {"trace_id": "t" * 32, "request_id": "traced"}
    t0 = time.time()
    engine.add_request([1, 2, 3], sp, request_id="traced",
                       additional_information={"trace": ctx})
    engine.add_request([4, 5, 6], sp, request_id="plain")
    while engine.has_unfinished_requests:
        engine.step()
    assert {c[0]["request_id"] for c in calls} == {"traced"}
    names = [c[1] for c in calls]
    assert names.count("prefill") == 1 and names.count("decode") == 2
    assert names.count("sampling") == 3
    for _, name, start_ts, dur_s, kw in calls:
        if name in ("prefill", "decode", "sampling"):
            assert t0 <= start_ts <= time.time() and 0.0 < dur_s < 60.0
            assert kw["stage_id"] == 0
    prefill = next(c for c in calls if c[1] == "prefill")
    assert prefill[4]["args"] == {"tokens": 3, "start_pos": 0}
