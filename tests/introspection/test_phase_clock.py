"""The phase clock (introspection/phase_clock.py): exclusive times of
nested phases, a step's split, and the stall check, all on a fake clock
(as the watchdog's tests drive theirs: no threads, no sleeps)."""

from __future__ import annotations

import gc

import pytest

from vllm_omni_tpu.introspection import PhaseClock, phase_clock

MS = 1_000_000


class FakeTime:
    """perf_counter_ns, thread_time_ns and time() that move only when
    told to: ``run`` spends wall and CPU time, ``wait`` wall time only."""

    def __init__(self):
        self.ns = 10 * MS
        self.cpu = 0

    def run(self, ms: float) -> None:
        self.ns += int(ms * MS)
        self.cpu += int(ms * MS)

    def wait(self, ms: float) -> None:
        self.ns += int(ms * MS)

    def clock(self) -> PhaseClock:
        return PhaseClock(now_ns=lambda: self.ns, cpu_ns=lambda: self.cpu,
                          wall=lambda: 1_700_000_000.0 + self.ns / 1e9)


def by_name(clock: PhaseClock) -> dict:
    return {k: v["ms"] for k, v in
            clock.snapshot()["phases"]["by_name"].items()}


def test_self_times_of_nested_phases_sum_to_the_outer_one():
    t = FakeTime()
    clock = t.clock()
    with clock.phase("omni.loop"):
        t.run(1)
        with clock.phase("omni.step"):
            t.run(2)
            with clock.phase("omni.assemble"):
                t.run(0.5)
                with clock.phase("omni.assemble.pack", trace=False):
                    t.run(3)
                with clock.phase("omni.assemble.put", trace=False):
                    t.run(4)
            with clock.phase("omni.readback"):
                t.wait(20)
        t.run(1.5)
    got = by_name(clock)
    assert got == {"omni.loop": 2.5, "omni.step": 2.0, "omni.assemble": 0.5,
                   "omni.assemble.pack": 3.0, "omni.assemble.put": 4.0,
                   "omni.readback": 20.0}
    # exclusive times: the phases of a turn sum to the turn
    assert sum(got.values()) == pytest.approx(32.0)
    snap = clock.snapshot()["phases"]
    assert snap["by_name"]["omni.loop"]["n"] == 1
    # the stamp the totals are complete up to: the last exit
    assert snap["clock_ms"] == pytest.approx(t.ns / 1e6)
    # a second turn adds to the lifetime totals
    with clock.phase("omni.loop"):
        t.run(1)
    assert by_name(clock)["omni.loop"] == 3.5
    assert clock.snapshot()["phases"]["by_name"]["omni.loop"]["n"] == 2


def test_what_is_called_as_a_phase_opens_runs_on_the_enclosing_phases_time():
    """``on_enter``: the orchestrator hands the streams' items over as
    ``omni.readback`` opens; what that costs is the step's own time, not
    the wait on the device."""
    t = FakeTime()
    clock = t.clock()
    calls = []

    def hand_over():
        calls.append(len(clock._stack))
        t.run(0.5)

    clock.on_enter("omni.readback", hand_over)
    with clock.phase("omni.step", trace=False):
        t.run(1.0)
        with clock.phase("omni.dispatch", trace=False):
            t.run(2.0)
        with clock.phase("omni.readback", trace=False) as p:
            t.wait(4.0)
            p.waited()
    # called once, before the phase stood on the stack
    assert calls == [1]
    assert by_name(clock) == {"omni.step": 1.5, "omni.dispatch": 2.0,
                              "omni.readback": 4.0}
    assert clock.wait_ns == 4 * MS


def test_a_steps_phases_sum_to_the_step_and_its_wait_is_device_time():
    t = FakeTime()
    clock = t.clock()
    with clock.phase("omni.loop"):
        with clock.phase("omni.intake"):
            t.run(7)  # before the step: none of its business
        with clock.phase("omni.step", step_num=41):
            t.wait(100)  # an injected delay before the step's mark
            clock.mark()
            t.run(0.25)
            with clock.phase("omni.schedule"):
                t.run(1)
            with clock.phase("omni.dispatch") as p:
                t.run(2)
                p.waited()
            with clock.phase("omni.readback") as p:
                t.wait(30)
                p.waited()
                t.run(3)  # unpacking: the host's
            t.run(0.75)
            with clock.phase("omni.record"):
                took = clock.since_mark(step=41, waiting=2, running=5)
                t.run(9)  # after the reading: not the step's
    assert took["step_ms"] == pytest.approx(37.0)
    assert took["device_ms"] == pytest.approx(32.0)
    assert took["cpu_ms"] == pytest.approx(7.0)
    assert took["phases_ms"] == {
        "omni.schedule": 1.0, "omni.dispatch": 2.0, "omni.readback": 33.0,
        "omni.step": 1.0}
    assert sum(took["phases_ms"].values()) == pytest.approx(took["step_ms"])


def turn(clock: PhaseClock, t: FakeTime, ms: float, *, step=None,
         compile_in_it=False, cpu=True, phase="omni.readback") -> None:
    with clock.phase("omni.loop"):
        if step is None:
            with clock.phase("omni.idle_wait"):
                t.wait(ms)
            return
        with clock.phase("omni.step", step_num=step):
            clock.mark()
            if compile_in_it:
                clock.note_compile()
            with clock.phase(phase):
                (t.run if cpu else t.wait)(ms)
            with clock.phase("omni.record"):
                clock.since_mark(step=step, waiting=3, running=64)


def test_one_slow_turn_among_quiet_ones_is_a_stall_and_says_where():
    t = FakeTime()
    clock = t.clock()
    for n in range(40):
        turn(clock, t, 20, step=n)
    turn(clock, t, 2020, step=40, cpu=False)
    for n in range(41, 50):
        turn(clock, t, 20, step=n)
    stalls = clock.snapshot()["stalls"]
    assert stalls["total"] == 1
    # the turn's time above the window's 99th percentile
    assert stalls["ms_total"] == pytest.approx(2000.0)
    (rec,) = stalls["records"]
    assert rec["phase"] == "omni.readback" and rec["step"] == 40
    assert rec["phases_ms"]["omni.readback"] == pytest.approx(2020.0)
    assert rec["turn_ms"] == pytest.approx(2020.0)
    assert rec["p99_ms"] == pytest.approx(20.0)
    # the thread was off the CPU: wall far above cpu
    assert rec["cpu_ms"] == pytest.approx(0.0)
    assert (rec["waiting"], rec["running"], rec["stepped"]) == (3, 64, True)
    assert rec["ts"] > 1_700_000_000 and rec["gc_ms"] == 0.0


def test_a_stall_between_steps_is_caught_too():
    """The loop's heartbeat, intake or a descheduled idle wait: a turn
    that ran no step."""
    t = FakeTime()
    clock = t.clock()
    for n in range(20):
        turn(clock, t, 20, step=n)
        turn(clock, t, 2)  # an idle turn: not part of the window
    turn(clock, t, 6150)
    stalls = clock.snapshot()["stalls"]
    assert stalls["total"] == 1
    rec = stalls["records"][0]
    assert rec["phase"] == "omni.idle_wait" and rec["stepped"] is False
    assert rec["step"] == 19  # the last step the engine spoke of


@pytest.mark.parametrize("case", [
    "compile", "mixed_10x", "all_slow", "short_window", "under_a_second"])
def test_what_is_no_stall(case):
    t = FakeTime()
    clock = t.clock()
    if case == "compile":
        for n in range(40):
            turn(clock, t, 20, step=n)
        turn(clock, t, 19_000, step=40, compile_in_it=True,
             phase="omni.dispatch")
    elif case == "mixed_10x":
        # a mixed step among decode steps (165-290 ms among 21 ms)
        for n in range(200):
            turn(clock, t, 290 if n % 3 == 0 else 21, step=n)
    elif case == "all_slow":
        # a delay on every step raises the percentile: rightly no stall
        for n in range(40):
            turn(clock, t, 1500, step=n)
    elif case == "short_window":
        # too few turns to take a percentile from
        for n in range(phase_clock.TURN_WINDOW_MIN - 1):
            turn(clock, t, 20, step=n)
        turn(clock, t, 5000, step=99)
    else:
        for n in range(40):
            turn(clock, t, 20, step=n)
        turn(clock, t, 990, step=40)
    assert clock.snapshot()["stalls"] == {
        "total": 0, "ms_total": 0.0, "records": []}


def test_the_stall_ring_keeps_the_last_sixteen():
    t = FakeTime()
    clock = t.clock()
    for n in range(2000):
        turn(clock, t, 5000 if n % 100 == 99 else 20, step=n)
    stalls = clock.snapshot()["stalls"]
    assert stalls["total"] == 20
    assert len(stalls["records"]) == phase_clock.STALL_RING
    assert stalls["records"][-1]["step"] == 1999


def test_busy_waits_are_counted_apart_from_the_phase():
    t = FakeTime()
    clock = t.clock()
    with clock.phase("omni.loop"):
        with clock.phase("omni.idle_wait") as p:
            t.wait(2)
        clock.note_busy_wait(p.ns)
        with clock.phase("omni.idle_wait"):
            t.wait(2)
    snap = clock.snapshot()["phases"]
    assert snap["by_name"]["omni.idle_wait"] == {"ms": 4.0, "n": 2}
    assert (snap["busy_waits"], snap["busy_wait_ms"]) == (1, 2.0)


def test_the_helper_opens_the_annotation_of_the_same_name(monkeypatch):
    """With a profiler session or without: the same names as before PR
    36; a clock-only child opens none."""
    import jax

    opened = []

    class Spy:
        def __init__(self, name, **kwargs):
            self.what = (name, kwargs)

        def __enter__(self):
            opened.append(self.what)

        def __exit__(self, *exc):
            opened.append(("exit", self.what[0]))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Spy)
    clock = PhaseClock()
    with clock.phase("omni.step", step_num=7):
        with clock.phase("omni.assemble"):
            with clock.phase("omni.assemble.pack", trace=False):
                pass
    assert opened == [("omni.step", {"step_num": 7}), ("omni.assemble", {}),
                      ("exit", "omni.assemble"), ("exit", "omni.step")]
    assert set(by_name(clock)) == {"omni.step", "omni.assemble",
                                   "omni.assemble.pack"}


def test_an_exception_closes_the_phase():
    t = FakeTime()
    clock = t.clock()
    with pytest.raises(RuntimeError):
        with clock.phase("omni.step"):
            with clock.phase("omni.dispatch"):
                t.run(3)
                raise RuntimeError("the device said no")
    assert by_name(clock) == {"omni.step": 0.0, "omni.dispatch": 3.0}
    with clock.phase("omni.step"):  # a new turn starts clean
        t.run(1)
    assert by_name(clock)["omni.step"] == 1.0


def test_collections_are_timed_by_the_one_hook():
    clock = PhaseClock()  # installs the process's timer, once
    PhaseClock()
    assert gc.callbacks.count(phase_clock.gc_timer._on_gc) == 1
    before = clock.snapshot()["gc"]
    gc.collect()
    after = clock.snapshot()["gc"]
    assert after["collections"]["2"] == before["collections"]["2"] + 1
    assert after["ms_total"] >= before["ms_total"]
    assert set(after["collections"]) == {"0", "1", "2"}
