"""The set-up on the program's own clock (introspection/phase_clock.py):
the start-up record's stamps and nested spans under an injected clock, a
runner's executables by name with JAX's own events fired by hand, and
the names in the JSON snapshot, ``/debug/z`` and the Prometheus text."""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.engine import EngineConfig, LLMEngine
from vllm_omni_tpu.introspection import debugz, phase_clock
from vllm_omni_tpu.introspection.phase_clock import (
    EXECUTABLE_RING,
    CompileStats,
    PhaseClock,
    StartupRecord,
)
from vllm_omni_tpu.metrics.prometheus import render_exposition
from vllm_omni_tpu.models.common import transformer as tfm

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
MS = 1_000_000
#: what a thunk here returns: nothing of JAX's, so that its first call
#: compiles nothing and the only events are those a test fires
DONE = np.zeros(())


class Clock:
    """Nanoseconds that move only when told to."""

    def __init__(self, ns: int = 0):
        self.ns = ns

    def __call__(self) -> int:
        return self.ns

    def tick(self, ms: float) -> None:
        self.ns += int(ms * MS)


def _stats():
    """A runner's stats and its phase clock, both on one injected clock."""
    clock = Clock(10 * MS)
    return clock, CompileStats(now_ns=clock), PhaseClock(now_ns=clock)


def _fire(clock: Clock, event: str, secs: float) -> None:
    """What JAX does when a part of a compile ends: ``secs`` pass, then
    the event says so."""
    clock.tick(secs * 1e3)
    jax.monitoring.record_event_duration_secs(event, secs)


def _first_call(stats, phases, key, thunk):
    """A runner's fresh branch, as ``_run_jit`` writes it."""
    with stats.first_call(phases, key) as p:
        result = thunk()
        jax.block_until_ready(result)
        p.waited()
    return result


# ------------------------------------------------------- the start-up record
def test_nested_spans_self_times_sum_to_the_outermost():
    clock = Clock(1000 * MS)
    rec = StartupRecord(now_ns=clock, stat_path="/nonexistent")
    with rec.span("omni.setup"):
        clock.tick(3)
        with rec.span("omni.setup.weights", stage=0):
            clock.tick(40)
        with rec.span("omni.setup.engine", stage=1):
            clock.tick(2)
            with rec.span("omni.setup.cache"):
                clock.tick(5)
            with rec.span("omni.setup.warmup"):
                clock.tick(300)
            clock.tick(1)
        clock.tick(7)
    by_name = {s["name"]: s for s in rec.snapshot()["spans"]}
    assert [s["name"] for s in rec.spans] == [
        "omni.setup", "omni.setup.weights", "omni.setup.engine",
        "omni.setup.cache", "omni.setup.warmup"]
    assert by_name["omni.setup"]["ms"] == 358.0
    assert by_name["omni.setup"]["self_ms"] == 10.0
    assert by_name["omni.setup.engine"]["self_ms"] == 3.0
    assert sum(s["self_ms"] for s in rec.spans) == by_name["omni.setup"]["ms"]
    assert by_name["omni.setup"]["t0_ms"] == 1000.0
    assert by_name["omni.setup.warmup"]["t0_ms"] == 1050.0


def test_a_span_names_its_parent_and_takes_its_stage():
    rec = StartupRecord(now_ns=Clock(), stat_path="/nonexistent")
    with rec.span("omni.setup"):
        with rec.span("omni.setup.engine", stage=3):
            with rec.span("omni.setup.warmup"):
                pass
    outer, engine, warmup = rec.spans
    assert (outer["parent"], outer["stage"]) == (None, None)
    assert (engine["parent"], engine["stage"]) == ("omni.setup", 3)
    assert (warmup["parent"], warmup["stage"]) == ("omni.setup.engine", 3)


def test_an_open_span_shows_without_a_duration_and_a_raise_closes_it():
    clock = Clock()
    rec = StartupRecord(now_ns=clock, stat_path="/nonexistent")
    with pytest.raises(RuntimeError):
        with rec.span("omni.setup"):
            clock.tick(2)
            assert rec.snapshot()["spans"][0]["ms"] is None
            raise RuntimeError("no weights")
    assert rec.spans[0]["ms"] == 2.0
    with rec.span("omni.setup"):  # the stack is clean again
        pass
    assert rec.spans[1]["parent"] is None


def test_a_stamp_is_written_once():
    clock = Clock(5 * MS)
    rec = StartupRecord(now_ns=clock, stat_path="/nonexistent")
    rec.stamp("serving")
    clock.tick(9)
    rec.stamp("serving")  # a second server of the process
    stamps = rec.snapshot()["stamps"]
    assert stamps == {"process_start": None, "serving": 5.0}
    assert set(stamps) == set(phase_clock.STAMPS)


def test_process_start_is_none_without_proc(tmp_path):
    rec = StartupRecord(now_ns=Clock(), stat_path=str(tmp_path / "stat"))
    assert rec.stamps["process_start"] is None
    (tmp_path / "stat").write_text("not a stat line")
    assert StartupRecord(now_ns=Clock(), stat_path=str(
        tmp_path / "stat")).stamps["process_start"] is None


def test_process_start_is_laid_on_the_clocks_epoch(tmp_path):
    """A process 250 ticks old (a command's name may hold spaces and
    brackets), read when the boot-time clock says 12.5 s: it is 10 s old,
    whatever epoch the record's clock has."""
    import os

    tck = os.sysconf("SC_CLK_TCK")
    fields = ["S"] + ["0"] * 18 + [str(int(2.5 * tck))] + ["0"] * 10
    (tmp_path / "stat").write_text("42 (a (b) c) " + " ".join(fields))
    rec = StartupRecord(now_ns=Clock(70_000 * MS),
                        stat_path=str(tmp_path / "stat"),
                        boottime_s=lambda: 12.5)
    assert rec.stamps["process_start"] == 60_000.0


def test_the_process_record_has_its_start():
    snap = phase_clock.startup.snapshot()
    assert snap["stamps"]["process_start"] < snap["now_ms"]


def _files_above(frame) -> list[str]:
    files = []
    while frame is not None:
        files.append(frame.f_code.co_filename)
        frame = frame.f_back
    return files


def test_no_frame_of_the_record_stands_above_a_jitted_call():
    """JAX keeps the Python frames above a traced operation in its
    location, and the lowering's seconds move with them (PERF.md, Open
    question 20f): a span and a first call are ``with`` blocks, so what
    runs inside them has the stack it had without them."""
    import sys

    runner = _engine().runner
    seen = {}

    def thunk():
        seen["above"] = _files_above(sys._getframe(1))
        return DONE

    with phase_clock.startup.span("omni.setup.warmup"):
        runner._run_jit("probe", (1,), thunk)
    assert seen["above"][0].endswith("worker/model_runner.py")  # _run_jit
    assert seen["above"][1] == __file__
    assert not any(f.endswith(("phase_clock.py", "contextlib.py"))
                   for f in seen["above"])


def test_the_record_keeps_a_bounded_number_of_spans():
    rec = StartupRecord(now_ns=Clock(), stat_path="/nonexistent")
    for _ in range(phase_clock.SPAN_CAP + 10):
        with rec.span("omni.setup.warmup"):
            pass
    assert len(rec.spans) == phase_clock.SPAN_CAP


# -------------------------------------------------- every executable by name
def test_events_land_on_the_executable_being_built():
    clock, stats, phases = _stats()

    def thunk():
        _fire(clock, TRACE, 2.0)
        _fire(clock, LOWER, 3.0)
        jax.monitoring.record_event(MISS)
        _fire(clock, BACKEND, 5.0)
        clock.tick(1500)  # the first run
        return DONE

    with stats.warming_up():
        _first_call(stats, phases, ("unified", 256, 1), thunk)
    (row,) = stats.snapshot()["executables"]
    assert row == {"kind": "unified", "key": [256, 1], "t0_ms": 10.0,
                   "total_s": 11.5, "trace_s": 2.0, "lower_s": 3.0,
                   "backend_s": 5.0, "run_s": 1.5, "cache": "miss",
                   "warmup": True, "step": None}
    assert stats["compiles"] == 1 and stats["compile_s"] == 11.5
    assert (stats["trace_s"], stats["lower_s"], stats["backend_s"]) == (
        2.0, 3.0, 5.0)
    assert stats["persistent_misses"] == 1


def test_an_event_inside_another_counts_once_under_its_own_name():
    """A jitted function traced inside another's trace, and a trace inside
    a lowering: the parts stay exclusive, so ``run_s`` cannot go under 0."""
    clock, stats, phases = _stats()

    def thunk():
        clock.tick(500)
        _fire(clock, TRACE, 1.0)            # an inner function ...
        clock.tick(500)
        jax.monitoring.record_event_duration_secs(TRACE, 2.0)  # ... in this
        clock.tick(1000)
        _fire(clock, TRACE, 0.25)           # a helper traced while lowering
        clock.tick(750)
        jax.monitoring.record_event_duration_secs(LOWER, 2.0)
        return DONE

    _first_call(stats, phases, ("dispatch", 8), thunk)
    (row,) = stats.executables
    assert (row["trace_s"], row["lower_s"]) == (2.25, 1.75)
    assert row["total_s"] == 4.0 and row["run_s"] == 0.0


def test_ten_thousand_functions_traced_inside_one_still_count_once():
    """A step program traces thousands of small jitted functions before
    its own trace ends: the bound on what is remembered of them forgets
    none (PR 52's first runs on the chip forgot the oldest and read
    ``run_s`` 0.0 with parts 0.5 s over the total)."""
    clock, stats, phases = _stats()

    def thunk():
        for _ in range(10_000):
            _fire(clock, TRACE, 0.001)
        clock.tick(2000)
        jax.monitoring.record_event_duration_secs(TRACE, 12.0)
        clock.tick(500)
        return DONE

    _first_call(stats, phases, ("unified", 256, 1), thunk)
    (row,) = stats.executables
    assert row["trace_s"] == pytest.approx(12.0, abs=1e-6)
    assert row["run_s"] == pytest.approx(0.5, abs=1e-6)


def test_a_loaded_executable_is_a_hit_and_its_retrieval_lies_inside():
    clock, stats, phases = _stats()

    def thunk():
        jax.monitoring.record_event(HIT)
        jax.monitoring.record_event_duration_secs(RETRIEVAL, 0.2)
        _fire(clock, BACKEND, 0.25)
        jax.monitoring.record_event(HIT)  # its feed program
        return DONE

    _first_call(stats, phases, ("dispatch", 8), thunk)
    (row,) = stats.executables
    assert row["cache"] == "hit" and row["backend_s"] == 0.25
    assert stats["retrieval_s"] == 0.2 and stats["persistent_hits"] == 2
    assert row["warmup"] is False

    def mixed():
        jax.monitoring.record_event(MISS)
        jax.monitoring.record_event(HIT)
        return DONE

    _first_call(stats, phases, ("dispatch", 16), mixed)
    assert stats.executables[-1]["cache"] == "miss"


def test_events_with_no_runner_building_or_on_another_thread_are_outside():
    clock, stats, phases = _stats()
    before = dict(stats.snapshot()["outside"])
    # this thread's real events of the last half second (another test's
    # compiles) would read as inside the half second fired here
    phase_clock.building.stack = []
    jax.monitoring.record_event_duration_secs(BACKEND, 0.5)
    jax.monitoring.record_event(HIT)

    def thunk():
        other = threading.Thread(
            target=jax.monitoring.record_event_duration_secs,
            args=(LOWER, 0.75))
        other.start()
        other.join(timeout=10)
        assert not other.is_alive()
        return DONE

    _first_call(stats, phases, ("dispatch", 8), thunk)
    after = stats.snapshot()["outside"]
    assert after["backend_s"] - before.get("backend_s", 0.0) == (
        pytest.approx(0.5))
    assert after["lower_s"] - before.get("lower_s", 0.0) == (
        pytest.approx(0.75))
    assert after["persistent_hits"] - before.get("persistent_hits", 0) == 1
    assert stats.executables[0]["lower_s"] == 0.0
    assert stats["backend_s"] == 0.0 and stats["lower_s"] == 0.0


def _engine(**kw) -> LLMEngine:
    cfg = tfm.TransformerConfig.tiny(vocab_size=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return LLMEngine(params, cfg, EngineConfig(
        num_pages=64, page_size=4, max_model_len=128, max_num_seqs=4,
        max_num_batched_tokens=64, dtype=jnp.float32, **kw))


def test_a_compile_under_a_key_already_seen_is_outside():
    """A seen key's dispatch is the parent's: no store, no record.  A
    silent retrace there shows in ``outside`` (and in the benchmark's
    ``window_compiles``), in none of the runner's totals."""
    runner = _engine().runner
    stats = runner.compile_stats
    runner._run_jit("probe", (1,), lambda: DONE)
    assert stats["compiles"] == 1 and len(stats.executables) == 1
    hits = stats["cache_hits"]
    backend = stats["backend_s"]
    outside = stats.snapshot()["outside"].get("backend_s", 0.0)
    phase_clock.building.stack = []

    def retraced():
        assert phase_clock.building.at is None
        jax.monitoring.record_event_duration_secs(BACKEND, 0.25)
        return DONE

    runner._run_jit("probe", (1,), retraced)
    assert stats["cache_hits"] == hits + 1
    assert stats["backend_s"] == backend
    assert stats.snapshot()["outside"]["backend_s"] == pytest.approx(
        outside + 0.25)
    assert len(stats.executables) == 1
    with pytest.raises(ValueError):
        runner._run_jit("probe", (2,), lambda: (_ for _ in ()).throw(
            ValueError("the device said no")))
    assert phase_clock.building.at is None and stats["in_flight"] == 0


def test_the_ring_holds_128():
    clock, stats, phases = _stats()
    for i in range(EXECUTABLE_RING + 5):
        _first_call(stats, phases, ("unified", i), lambda: DONE)
    rows = stats.snapshot()["executables"]
    assert len(rows) == EXECUTABLE_RING == 128
    assert rows[0]["key"] == [5] and rows[-1]["key"] == [132]
    assert stats["compiles"] == EXECUTABLE_RING + 5


def test_current_is_set_during_the_thunk_and_cleared_after_a_raise():
    clock, stats, phases = _stats()
    seen = {}

    def thunk():
        clock.tick(2000)
        seen["from_another_thread"] = None
        reader = threading.Thread(target=lambda: seen.update(
            from_another_thread=stats.snapshot()["current"]))
        reader.start()
        reader.join(timeout=10)
        seen["in_flight"] = stats["in_flight"]
        raise RuntimeError("precompile unified t=2048 never returned")

    with pytest.raises(RuntimeError):
        _first_call(stats, phases, ("unified", 2048, 1), thunk)
    assert seen["from_another_thread"] == {
        "kind": "unified", "key": [2048, 1], "since_ms": 10.0, "for_s": 2.0}
    assert seen["in_flight"] == 1
    assert stats.snapshot()["current"] is None
    assert stats["in_flight"] == 0 and stats["compiles"] == 0
    assert phase_clock.building.at is None


def test_a_process_stage_ships_the_record_once_an_executable():
    """The worker's frames carry the scalars; the start-up spans and the
    executables ride the first frame and the next after a fresh one, and
    the orchestrator's side keeps what it was sent."""
    from vllm_omni_tpu.entrypoints.stage_proc import (
        _with_setup_record,
        _without_setup_record,
    )

    whole = {"num_steps": 3, "startup": {"now_ms": 1.0, "spans": ["s"]},
             "compile": {"compiles": 2, "compile_s": 1.5,
                         "executables": [{"kind": "a"}, {"kind": "b"}]}}
    light = _without_setup_record({**whole, "num_steps": 4})
    assert light == {"num_steps": 4,
                     "compile": {"compiles": 2, "compile_s": 1.5}}
    assert "executables" in whole["compile"]  # the engine's own is whole
    kept = _with_setup_record(whole, {})
    assert kept == whole
    assert _with_setup_record(light, kept) == {**whole, "num_steps": 4}
    diffusion = {"startup": {"now_ms": 2.0}, "diffusion": {"requests": 1}}
    assert _with_setup_record(_without_setup_record(diffusion),
                              diffusion) == diffusion
    assert _with_setup_record({"x": 1}, {}) == {"x": 1}


# ------------------------------------------------- the engine and the names
def test_a_warmed_engine_records_its_executables_and_its_spans():
    spans_before = len(phase_clock.startup.spans)
    engine = _engine(warmup=True)
    stats = engine.runner.compile_stats
    rows = stats.snapshot()["executables"]
    assert len(rows) == stats["compiles"] > 0
    assert all(r["warmup"] and r["step"] is None for r in rows)
    assert {r["kind"] for r in rows} >= {"unified", "dispatch"}
    assert all(r["run_s"] >= 0.0 and r["total_s"] == pytest.approx(
        r["trace_s"] + r["lower_s"] + r["backend_s"] + r["run_s"], abs=1e-5)
        for r in rows)
    assert sum(r["trace_s"] for r in rows) == pytest.approx(
        stats["trace_s"], abs=1e-4)
    assert stats["compile_s"] == pytest.approx(
        sum(r["total_s"] for r in rows), abs=1e-4)
    assert stats["trace_s"] > 0.0 and stats["backend_s"] > 0.0
    names = [s["name"] for s in phase_clock.startup.spans[spans_before:]]
    assert names == ["omni.setup.cache", "omni.setup.warmup"]
    # a compile under traffic carries the engine's step, and is no warm-up
    engine.runner._run_jit("probe", (1,), lambda: DONE)
    last = stats.executables[-1]
    assert (last["kind"], last["warmup"], last["step"]) == ("probe", False, 0)


def test_a_generation_stages_warm_up_is_recorded_under_the_same_names():
    from vllm_omni_tpu.worker.generation_runner import GenerationModelRunner

    class Vocoder:
        def forward(self, params, token_ids, lengths):
            return {"wave": token_ids.astype(jnp.float32) * params["gain"]}

    runner = GenerationModelRunner({"gain": jnp.float32(2.0)}, Vocoder(),
                                   max_num_seqs=2, max_model_len=32)
    built = runner.precompile(prefill_shapes=[(1, 16), (2, 32)])
    rows = runner.compile_stats.snapshot()["executables"]
    assert built >= 2 and [(r["kind"], r["warmup"]) for r in rows] == [
        ("generation", True)] * built
    assert [2, 32] in [r["key"] for r in rows]
    assert runner.compile_stats["compiles"] == built
    assert runner.compile_stats["compile_s"] > 0.0


def test_the_snapshot_debugz_and_prometheus_carry_the_names():
    engine = _engine(warmup=True)
    engine.add_request([1, 2, 3])
    while engine.has_unfinished_requests:
        engine.step()
    snap = engine.metrics_snapshot()
    assert set(snap["startup"]) == {"now_ms", "stamps", "spans"}
    assert set(snap["startup"]["stamps"]) == {"process_start", "serving"}
    assert {"trace_s", "lower_s", "backend_s", "retrieval_s",
            "persistent_hits", "persistent_misses",
            "executables", "current", "outside",
            "compiles", "cache_hits", "compile_s", "in_flight"} == set(
        snap["compile"])
    assert debugz.engine_debug(engine)["compile"]["current"] is None
    assert engine.introspect_progress()["detail"]["compiling"] is None
    import json

    json.dumps(snap["compile"]), json.dumps(snap["startup"])
    text = render_exposition({"stages": {}, "edges": {}, "e2e": {}},
                             {0: snap})
    for part in ("trace", "lower", "backend", "run"):
        assert (f'engine_compile_seconds_total{{stage="0",part="{part}"}}'
                in text)
    assert 'engine_setup_seconds{stage="0",phase="omni.setup.warmup"}' in text
