"""Phase clock: the host's phases on the host's own clock, every step.

The engine thread names what it is doing with ``omni.*`` phases
(docs/observability.md).  Until PR 36 a phase was a profiler annotation
and nothing else, so the host's share of a step was visible for the few
seconds a profiler session was open.  ``PhaseClock.phase(name)`` is the
one helper every such ``with`` block goes through: it opens the same
annotation under the same name (a flag test when no profiler runs) and,
with or without a profiler, adds the phase's time on ``perf_counter_ns``
to the clock:

- per name, **exclusive** (self) nanoseconds and entries, lifetime
  totals: a nested phase subtracts from its parent, so the phases of one
  turn of the loop sum to the turn;
- per step (``mark`` .. ``since_mark``), the same split, which the
  engine writes on the step's flight record as ``phases_ms``, with the
  nanoseconds the thread spent *waiting on the device* (``waited``) from
  which the record's ``device_ms`` / ``host_ms`` come;
- per **turn** (one outermost phase: a turn of the orchestrator's loop,
  or a step of an engine driven without it), a stall check: a turn of at
  least ``STALL_MIN_S`` that is at least ``STALL_FACTOR`` times the 99th
  percentile of the last ``TURN_WINDOW`` turns that ran a step and
  compiled nothing, with no fresh compile in it, is a stall.  It is counted and its record kept in
  a ring of ``STALL_RING``.

One clock belongs to one thread (the engine's); other threads only read
``snapshot()``.  Garbage collection is timed by one ``gc.callbacks``
hook for the process (``gc_timer``): it runs only when a collection
does.  Clock, thread-CPU clock and wall clock are injectable, as the
watchdog's are, so the tests drive the stall check with a fake clock.
"""

from __future__ import annotations

import gc
import time
from collections import deque
from typing import Callable, Optional

import jax

from vllm_omni_tpu.metrics.stats import nearest_rank_pct

#: a stall is a turn of at least this long ...
STALL_MIN_S = 1.0
#: ... and at least this many times the 99th percentile of the window
STALL_FACTOR = 4.0
#: turns (those that ran a step) the percentile is taken over, and the
#: fewest it is taken from: with fewer, nothing is called a stall
TURN_WINDOW, TURN_WINDOW_MIN = 1024, 8
#: stall records kept
STALL_RING = 16


class GcTimer:
    """Seconds the interpreter spent in garbage collection, by one
    ``gc.callbacks`` hook: nothing runs unless a collection does.  A
    collection holds the interpreter lock, so its time is lost to every
    thread, whichever triggered it."""

    def __init__(self):
        self._t0 = 0
        self.ns_total = 0
        self.collections = [0, 0, 0]
        self._installed = False

    def install(self) -> None:
        if not self._installed:
            self._installed = True
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter_ns()
        elif self._t0:
            self.ns_total += time.perf_counter_ns() - self._t0
            self.collections[min(info.get("generation", 0), 2)] += 1
            self._t0 = 0

    def snapshot(self) -> dict:
        return {"ms_total": round(self.ns_total / 1e6, 3),
                "collections": {str(g): n
                                for g, n in enumerate(self.collections)}}


#: the process's one collection timer; installed by the first clock
gc_timer = GcTimer()


class _Phase:
    """One open phase: a frame of the clock's stack and, where asked,
    the profiler annotation of the same name."""

    __slots__ = ("clock", "name", "ann", "t0", "child", "ns")

    def __init__(self, clock: "PhaseClock", name: str, ann):
        self.clock = clock
        self.name = name
        self.ann = ann
        self.child = 0
        self.ns = 0

    def __enter__(self) -> "_Phase":
        if self.ann is not None:
            self.ann.__enter__()
        clock = self.clock
        self.t0 = clock.last_ns = clock.now_ns()
        if not clock._stack:
            clock._begin_turn()
        clock._stack.append(self)
        return self

    def waited(self) -> None:
        """What this phase has spent so far outside its children was a
        wait on the device (the jitted call's launch, ``device_get``)."""
        clock = self.clock
        clock.wait_ns += clock.now_ns() - self.t0 - self.child

    def __exit__(self, *exc) -> None:
        clock = self.clock
        now = clock.last_ns = clock.now_ns()
        stack = clock._stack
        # ``with`` blocks nest, so this frame is on top; should a second
        # thread ever have pushed one (the clock is one thread's), the
        # split is wrong for that turn but nothing raises
        while stack and stack.pop() is not self:
            pass
        self.ns = now - self.t0
        own = self.ns - self.child
        total = clock._totals.get(self.name)
        if total is None:
            clock._totals[self.name] = [own, 1]
        else:
            total[0] += own
            total[1] += 1
        acc = clock._turn
        acc[self.name] = acc.get(self.name, 0) + own
        if stack:
            stack[-1].child += self.ns
        else:
            clock._end_turn(self.ns)
        if self.ann is not None:
            self.ann.__exit__(*exc)


class PhaseClock:
    def __init__(self,
                 now_ns: Callable[[], int] = time.perf_counter_ns,
                 cpu_ns: Callable[[], int] = time.thread_time_ns,
                 wall: Callable[[], float] = time.time):
        self.now_ns = now_ns
        self._cpu_ns = cpu_ns
        self._wall = wall
        #: name -> [exclusive ns, entries], lifetime
        self._totals: dict[str, list] = {}
        self._stack: list[_Phase] = []
        #: the stamp of the engine thread's last phase entry or exit:
        #: what the lifetime totals are complete up to
        self.last_ns = now_ns()
        #: ns the thread waited on the device, lifetime (``waited``)
        self.wait_ns = 0
        # the turn in progress: exclusive ns by name, its start on the
        # three clocks, and what the engine said of it
        self._turn: dict[str, int] = {}
        self._turn_cpu0 = 0
        self._turn_gc0 = 0
        self._turn_stepped = False
        self._turn_compiled = False
        self._context = {"step": None, "waiting": 0, "running": 0}
        self._turns: deque = deque(maxlen=TURN_WINDOW)
        # the step in progress (mark .. since_mark)
        self._mark_ns = self.last_ns
        self._mark_cpu = 0
        self._mark_wait = 0
        self._mark_depth = 0
        self._mark_child = 0
        self._mark_turn: dict[str, int] = {}
        self.stalls_total = 0
        self.stall_ns_total = 0
        self._stall_ring: deque = deque(maxlen=STALL_RING)
        #: 2 ms waits the loop took while a stage still had work
        self.busy_waits = 0
        self.busy_wait_ns = 0
        gc_timer.install()

    # ------------------------------------------------------------- phases
    def phase(self, name: str, trace: bool = True,
              step_num: Optional[int] = None) -> _Phase:
        """``with clock.phase("omni.schedule"):`` — the phase on this
        clock and, unless ``trace`` is False, the profiler annotation of
        the same name (a ``StepTraceAnnotation`` where ``step_num`` is
        given).  A child that the trace must not see (the readers of the
        benchmark map exact names) passes ``trace=False``."""
        if not trace:
            ann = None
        elif step_num is None:
            ann = jax.profiler.TraceAnnotation(name)
        else:
            ann = jax.profiler.StepTraceAnnotation(name, step_num=step_num)
        return _Phase(self, name, ann)

    def note_compile(self) -> None:
        """A fresh compile ran in this turn: it is no stall."""
        self._turn_compiled = True

    def note_busy_wait(self, ns: int) -> None:
        self.busy_waits += 1
        self.busy_wait_ns += ns

    # --------------------------------------------------------------- steps
    def mark(self) -> None:
        """A step begins here (inside its ``omni.step`` phase)."""
        self._mark_ns = self.last_ns = self.now_ns()
        self._mark_cpu = self._cpu_ns()
        self._mark_wait = self.wait_ns
        self._mark_depth = len(self._stack)
        self._mark_child = self._stack[-1].child if self._stack else 0
        self._mark_turn = dict(self._turn)

    def since_mark(self, step: Optional[int] = None, waiting: int = 0,
                   running: int = 0) -> dict:
        """The step so far, up to the clock's last stamp (the entry of
        the phase the caller is in): ``step_ms``, ``device_ms`` (waiting
        on the device), ``cpu_ms`` (this thread on a CPU) and
        ``phases_ms``, exclusive by name, open phases included up to
        that stamp, so that they sum to ``step_ms``.  What the engine
        says of the step rides the turn's stall record."""
        now = self.last_ns
        base = self._mark_turn
        ns = {}
        for name, v in self._turn.items():
            d = v - base.get(name, 0)
            if d:
                ns[name] = d
        stack = self._stack
        first = self._mark_depth - 1
        for i in range(max(first, 0), len(stack)):
            f = stack[i]
            start, child = f.t0, f.child
            if i == first:
                start, child = self._mark_ns, child - self._mark_child
            if i + 1 < len(stack):
                child += now - stack[i + 1].t0
            own = now - start - child
            if own:
                ns[f.name] = ns.get(f.name, 0) + own
        self._turn_stepped = True
        self._context = {"step": step, "waiting": waiting,
                         "running": running}
        return {
            "step_ms": (now - self._mark_ns) / 1e6,
            "device_ms": (self.wait_ns - self._mark_wait) / 1e6,
            "cpu_ms": (self._cpu_ns() - self._mark_cpu) / 1e6,
            "phases_ms": {k: round(v / 1e6, 3) for k, v in ns.items()},
        }

    # --------------------------------------------------------------- turns
    def _begin_turn(self) -> None:
        self._turn = {}
        self._turn_cpu0 = self._cpu_ns()
        self._turn_gc0 = gc_timer.ns_total
        self._turn_stepped = self._turn_compiled = False

    def _end_turn(self, dur: int) -> None:
        if dur >= STALL_MIN_S * 1e9 and not self._turn_compiled \
                and len(self._turns) >= TURN_WINDOW_MIN:
            p99 = nearest_rank_pct(self._turns, 0.99)
            if dur >= STALL_FACTOR * p99:
                self._note_stall(dur, p99)
        # the window holds what a turn takes when it runs a step and
        # compiles nothing: idle turns would hide a long step among 2 ms
        # waits, a compile would hide a stall behind its seconds
        if self._turn_stepped and not self._turn_compiled:
            self._turns.append(dur)

    def _note_stall(self, dur: int, p99: int) -> None:
        turn = self._turn
        self.stalls_total += 1
        self.stall_ns_total += dur - p99
        self._stall_ring.append({
            "ts": self._wall(),
            "turn_ms": round(dur / 1e6, 3),
            "p99_ms": round(p99 / 1e6, 3),
            "cpu_ms": round((self._cpu_ns() - self._turn_cpu0) / 1e6, 3),
            "gc_ms": round((gc_timer.ns_total - self._turn_gc0) / 1e6, 3),
            # the phase the thread sat in longest
            "phase": max(turn, key=turn.get),
            "phases_ms": {k: round(v / 1e6, 3) for k, v in turn.items()},
            "stepped": self._turn_stepped,
            **self._context,
        })

    # ------------------------------------------------------------ reading
    def snapshot(self) -> dict:
        """``phases``, ``stalls`` and ``gc`` of the engine's JSON
        snapshot.  Read from any thread: every table is copied in one
        call of the interpreter."""
        totals = dict(self._totals)
        return {
            "phases": {
                "clock_ms": round(self.last_ns / 1e6, 3),
                "by_name": {k: {"ms": round(v[0] / 1e6, 3), "n": v[1]}
                            for k, v in sorted(totals.items())},
                "busy_waits": self.busy_waits,
                "busy_wait_ms": round(self.busy_wait_ns / 1e6, 3),
            },
            "stalls": {
                "total": self.stalls_total,
                "ms_total": round(self.stall_ns_total / 1e6, 3),
                "records": list(self._stall_ring),
            },
            "gc": gc_timer.snapshot(),
        }
