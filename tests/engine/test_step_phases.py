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

from benchmark import phase_readers, span_readers
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


def _llm_stage(stage_id: int = 0, final: bool = True, max_tokens: int = 4,
               **engine_args) -> StageConfig:
    """The tiny model as stage ``stage_id`` of a chain: fed by the stage
    before it, the first by the intake."""
    return StageConfig(
        stage_id=stage_id, stage_type="llm",
        engine_args={"model_factory": "tests.helpers:tiny_lm_factory",
                     "num_pages": 64, "page_size": 4, "max_model_len": 128,
                     **engine_args},
        engine_input_source=[stage_id - 1], final_output=final,
        final_output_type="text",
        default_sampling_params={"temperature": 0.0,
                                 "max_tokens": max_tokens})


@pytest.fixture()
def async_omni():
    omni = AsyncOmni(stage_configs=[_llm_stage()])
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


def _idle_waits(engine) -> int:
    by_name = engine.metrics_snapshot()["phases"]["by_name"]
    return by_name.get("omni.idle_wait", {"n": 0})["n"]


def _spy_on_steps(engine, monkeypatch, read) -> list:
    """``read(engine)`` at the entry of every ``engine.step`` the loop
    makes, on the loop's own thread."""
    seen: list = []
    step = engine.step

    def spied():
        seen.append(read(engine))
        return step()

    monkeypatch.setattr(engine, "step", spied)
    return seen


def test_the_loop_opens_its_four_names_and_waits_after_idle_turns_only(
        async_omni, monkeypatch):
    """Served through the orchestrator's loop, the engine's clock holds
    every name of ``span_readers.PHASE_LAYER`` (a rename fails here), the
    list of section 3 of PERF.md; the 2 ms wait follows a turn in which
    nothing advanced, so a reply whose every step did device work takes
    none, finished request or not."""
    assert set(span_readers.PHASE_LAYER) == PERF_MD_PHASES
    engine = async_omni._omni.stages[0].engine
    time.sleep(0.1)  # idle turns: waits, none of them busy
    before = engine.metrics_snapshot()
    idle = before["phases"]
    assert idle["by_name"]["omni.idle_wait"]["n"] >= 5
    assert (idle["busy_waits"], idle["busy_wait_ms"]) == (0, 0.0)
    waits_at_step = _spy_on_steps(engine, monkeypatch, _idle_waits)
    work_before = engine.work_steps
    _ask(async_omni, [1, 2, 3], 12)
    time.sleep(0.05)  # the reply is out before its omni.emit has closed
    after = engine.metrics_snapshot()
    busy = after["phases"]
    # (all but omni.kv_moves: the pipelined step drains no tier moves)
    assert set(busy["by_name"]) == (PERF_MD_PHASES | set(ASSEMBLE)) - {
        "omni.kv_moves"}
    # eleven of the steps finished no request, every one did device work:
    # no wait between the first step and the last, and none counted busy.
    # They are there, as 0, and do not vanish (the benchmark's reader
    # gives None for a missing block, and the harness then drops the metric)
    assert len(waits_at_step) >= 12
    assert engine.work_steps - work_before >= len(waits_at_step)
    assert len(set(waits_at_step)) == 1
    assert (busy["busy_waits"], busy["busy_wait_ms"]) == (0, 0.0)
    assert phase_readers.busy_wait_ms_per_step(
        {"engine0": before, "engine1": after}) == 0.0
    # the turns' phases sum to the clock's time: the loop is never
    # outside a phase but between two turns
    time.sleep(0.05)
    s0 = engine.metrics_snapshot()["phases"]
    time.sleep(0.2)
    s1 = engine.metrics_snapshot()["phases"]
    spent = sum(v["ms"] for v in s1["by_name"].values()) - sum(
        v["ms"] for v in s0["by_name"].values())
    assert spent == pytest.approx(s1["clock_ms"] - s0["clock_ms"], rel=0.02)
    # idle again: the turns wait, and none of the waits is busy
    assert 50 <= (s1["by_name"]["omni.idle_wait"]["n"]
                  - s0["by_name"]["omni.idle_wait"]["n"]) <= 100
    assert s1["busy_waits"] == 0


class _Inbox:
    """The shape of ``ProcStage.poll``: a non-blocking read of an inbox
    that holds nothing, with work in flight somewhere else."""

    stage_id = 1
    has_unfinished = True

    def poll(self):
        return []


def _proc_stage(monkeypatch, supervised: bool):
    """A ``ProcStage`` with a request in flight and no worker behind it,
    bare or under its ``StageSupervisor``."""
    from vllm_omni_tpu.entrypoints.stage_proc import ProcStage
    from vllm_omni_tpu.resilience.supervisor import StageSupervisor

    monkeypatch.setattr(ProcStage, "_connect_worker", lambda self: None)
    cfg = StageConfig(stage_id=1, stage_type="llm", engine_input_source=[0])
    if supervised:
        stage = StageSupervisor(cfg, heartbeat_interval_s=0)
        stage._stage._inflight.add("elsewhere")
    else:
        stage = ProcStage(cfg)
        stage._inflight.add("elsewhere")
    return stage


@pytest.mark.parametrize("kind", ["inbox", "proc_stage", "supervisor"])
def test_a_stage_that_works_in_another_process_keeps_the_loops_wait(
        async_omni, monkeypatch, kind):
    """Unfinished work that this thread cannot advance (a poll that
    delivers nothing and runs nothing here) leaves the loop its wait,
    counted as busy: without it the loop would spin on the inbox."""
    stage = (_Inbox() if kind == "inbox"
             else _proc_stage(monkeypatch, kind == "supervisor"))
    assert stage.has_unfinished and not getattr(stage, "advanced", False)
    polls: list = []
    poll = stage.poll
    monkeypatch.setattr(stage, "poll",
                        lambda: polls.append(time.perf_counter()) or poll())
    engine = async_omni._omni.stages[0].engine
    async_omni._omni.stages.append(stage)
    try:
        time.sleep(0.02)
        p0, n0 = engine.metrics_snapshot()["phases"], len(polls)
        time.sleep(0.05)
        p1, n1 = engine.metrics_snapshot()["phases"], len(polls)
        # and the stage that does work here is served beside it as before
        waits_at_step = _spy_on_steps(engine, monkeypatch, _idle_waits)
        _ask(async_omni, [1, 2, 3], 6)
    finally:
        async_omni._omni.stages.remove(stage)
    turns = n1 - n0
    assert 5 <= turns <= 30  # 25 at 2 ms a turn
    assert abs((p1["busy_waits"] - p0["busy_waits"]) - turns) <= 2
    assert (p1["busy_wait_ms"] - p0["busy_wait_ms"]
            >= 1.9 * (p1["busy_waits"] - p0["busy_waits"]))
    assert len(waits_at_step) >= 6 and len(set(waits_at_step)) == 1


def test_zero_scheduled_ticks_keep_the_wait_a_streaming_request(async_omni):
    """A streaming request that idles for its next chunk makes the
    engine's steps schedule nothing: ``_steps_completed`` moves and
    ``work_steps`` does not, so every such turn waits and the loop ticks
    at 2 ms, not as fast as it can."""
    engine = async_omni._omni.stages[0].engine
    engine.add_request([1, 2, 3], _sampled(4), request_id="s",
                       awaiting_chunks=True)
    # its three tokens are prefilled (a compile, the first time): the
    # one step of work this request has until its next chunk comes
    deadline = time.monotonic() + 60.0
    while engine.work_steps == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.02)
    (req,) = engine.scheduler.running
    assert req.num_computed_tokens == 3
    p0 = engine.metrics_snapshot()["phases"]
    ticks0, work0 = engine._steps_completed, engine.work_steps
    time.sleep(0.1)
    p1 = engine.metrics_snapshot()["phases"]
    ticks = engine._steps_completed - ticks0
    assert engine.work_steps == work0
    assert 10 <= ticks <= 60  # 50 at 2 ms a tick
    assert abs((p1["busy_waits"] - p0["busy_waits"]) - ticks) <= 2
    assert engine.has_unfinished_requests and not req.is_finished


@pytest.mark.parametrize("async_scheduling", [False, None],
                         ids=["sync", "default"])
def test_zero_scheduled_ticks_keep_the_wait_a_starved_request(
        monkeypatch, async_scheduling):
    """The engine declares a waiting request starved after three
    consecutive zero-scheduled ticks, and the loop's wait is what spaces
    them (pages pinned by a transfer may come free meanwhile): every
    tick that did no device work is followed by the wait.  An engine
    that sees ``kv_transfer`` steps in the synchronous order, where all
    three ticks are such; in the two-slot order the first tick still
    retires the step in flight, which is work, and the other two wait."""
    order = ({} if async_scheduling is None
             else {"async_scheduling": async_scheduling})
    omni = AsyncOmni(stage_configs=[
        _llm_stage(max_tokens=10, num_pages=2, **order)])  # 8 tokens fit
    try:
        engine = omni._omni.stages[0].engine
        seen = _spy_on_steps(
            engine, monkeypatch,
            lambda e: (time.perf_counter(), e._starved_ticks, e.work_steps))

        async def run():
            return [o async for o in omni.generate([1, 2, 3, 4, 5, 6])]

        (out,) = asyncio.run(run())
        assert out.is_error and "starved" in out.error_message
        busy_waits = engine.metrics_snapshot()["phases"]["busy_waits"]
        work_after = engine.work_steps
    finally:
        omni.shutdown()
    # the last three steps are the ticks, each entered with one more
    # counted; the verdict is the third's output
    (t1, k1, w1), (t2, k2, w2), (t3, k3, w3) = seen[-3:]
    assert (k1, k2, k3) == (0, 1, 2)
    retired = 0 if async_scheduling is False else 1
    assert (w2 - w1, w3 - w2, work_after - w3) == (retired, 0, 0)
    if not retired:
        assert t2 - t1 >= 0.0019
    assert t3 - t2 >= 0.0019
    assert busy_waits == 2 - retired


def test_a_two_stage_pipeline_forwards_and_finishes_without_a_wait(
        monkeypatch):
    """Two engines in this process: the second serves what the first
    finished, and while either has a step to run no turn waits."""
    omni = AsyncOmni(stage_configs=[_llm_stage(0, final=False, max_tokens=5),
                                    _llm_stage(1, max_tokens=5)])
    try:
        first, second = (s.engine for s in omni._omni.stages)
        # the loop's clock is the entry stage's engine's
        waits = _spy_on_steps(first, monkeypatch, _idle_waits)
        waits_second = _spy_on_steps(second, monkeypatch,
                                     lambda _: _idle_waits(first))

        async def run():
            return [o async for o in omni.generate(
                [1, 2, 3], {"ignore_eos": True})]

        (out,) = asyncio.run(run())
        time.sleep(0.05)
        phases = first.metrics_snapshot()["phases"]
    finally:
        omni.shutdown()
    assert out.stage_id == 1 and len(out.outputs[0].token_ids) == 5
    # the second stage's prompt is the first's five tokens
    assert len(out.prompt_token_ids) == 5
    assert len(waits) >= 5 and len(waits_second) >= 5
    assert len(set(waits) | set(waits_second)) == 1
    assert phases["busy_waits"] == 0


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
