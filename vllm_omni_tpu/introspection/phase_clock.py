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

The set-up is on the same clock (the second half of this file):
``startup``, the process's one record of its stamps and nested spans from
process start to the listening socket, and ``CompileStats``, a
runner's executables by name, each split into JAX's own seconds for
tracing, lowering, compiling or loading, and the rest (the first run).
Nothing of either runs on a step of a warm engine.
"""

from __future__ import annotations

import contextlib
import gc
import os
import threading
import time
import weakref
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
        clock = self.clock
        if clock._on_enter:
            # before the phase's time starts: the caller's is the
            # enclosing phase's, not this one's
            fn = clock._on_enter.get(self.name)
            if fn is not None:
                fn()
        if self.ann is not None:
            self.ann.__enter__()
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
        #: name -> what to call, on this clock's thread, each time that
        #: phase opens (``on_enter``)
        self._on_enter: dict[str, Callable[[], None]] = {}
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

    def on_enter(self, name: str, fn: Callable[[], None]) -> None:
        """Have ``fn()`` called on this clock's thread each time the
        phase ``name`` is about to open, on the enclosing phase's time.
        ``omni.readback`` opens as the engine thread starts to wait for
        the device: the orchestrator hands the streams' partial outputs
        over there (``AsyncOmni._flush``), so that their writer works
        while this thread sleeps and not while it assembles a step."""
        self._on_enter[name] = fn

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
            "startup": startup.snapshot(),
        }


# ------------------------------------------------------------- the set-up
#: spans the start-up record keeps; later ones (an engine built an hour
#: into the process's life is no start-up) are dropped
SPAN_CAP = 256
#: the stamps, each written once
STAMPS = ("process_start", "serving")


def _boottime_s() -> float:
    return time.clock_gettime(time.CLOCK_BOOTTIME)


class _Span:
    """One open span of the start-up record: a frame of the thread's
    stack and the span's row."""

    __slots__ = ("record", "row", "t0", "child", "stack")

    def __init__(self, record: "StartupRecord", name: str,
                 stage: Optional[int]):
        self.record = record
        self.row = {"name": name, "stage": stage, "t0_ms": None,
                    "ms": None, "self_ms": None, "parent": None}
        self.child = 0

    def __enter__(self) -> "_Span":
        record, row = self.record, self.row
        local = record._local
        stack = getattr(local, "stack", None)
        if stack is None:
            stack = local.stack = []
        self.stack = stack
        if stack:
            parent = stack[-1].row
            row["parent"] = parent["name"]
            if row["stage"] is None:
                row["stage"] = parent["stage"]
        self.t0 = record.now_ns()
        row["t0_ms"] = round(self.t0 / 1e6, 3)
        stack.append(self)
        if len(record.spans) < SPAN_CAP:
            record.spans.append(row)
        return self

    def __exit__(self, *exc) -> None:
        ns = self.record.now_ns() - self.t0
        stack = self.stack
        while stack and stack.pop() is not self:
            pass
        if stack:
            stack[-1].child += ns
        self.row["ms"] = round(ns / 1e6, 3)
        self.row["self_ms"] = round((ns - self.child) / 1e6, 3)


class StartupRecord:
    """The process's set-up on the phase clock's own clock
    (``perf_counter_ns``: the clock of ``phases.clock_ms`` and of every
    flight record): *stamps*, each written once, and nested *spans*
    ``{name, stage, t0_ms, ms, self_ms, parent}`` in the order they
    opened (self time = duration minus what its children cover; a span
    still open has ``ms`` None).  A span opens no profiler annotation:
    no profiler runs at set-up, and a new ``omni.*`` name in a traced
    tail would move seconds between the benchmark's idle shares.

    The stamps are the two ends that no span of the program's has:
    ``process_start``, laid on the clock from ``/proc/self/stat``'s
    start time (clock ticks since boot) against the boot-time clock, None
    where ``/proc`` is absent; and ``serving``, written by
    ``build_server`` when the socket listens, None in a process that
    serves no HTTP.  What lies before ``omni.setup`` opens is the
    interpreter and the imports; what lies between its close and
    ``serving`` is the orchestrator and the server."""

    def __init__(self, now_ns: Callable[[], int] = time.perf_counter_ns,
                 stat_path: str = "/proc/self/stat",
                 boottime_s: Callable[[], float] = _boottime_s):
        self.now_ns = now_ns
        self.stamps: dict = dict.fromkeys(STAMPS)
        self.stamps["process_start"] = self._process_start(
            stat_path, boottime_s)
        self.spans: list[dict] = []
        self._local = threading.local()

    def _process_start(self, stat_path: str, boottime_s) -> Optional[float]:
        try:
            with open(stat_path) as f:
                # the fields after the command's name, which may hold
                # spaces and brackets: start time is the 22nd of all
                ticks = int(f.read().rsplit(")", 1)[1].split()[19])
            age_s = boottime_s() - ticks / os.sysconf("SC_CLK_TCK")
        except (OSError, ValueError, IndexError, AttributeError):
            return None
        return round(self.now_ns() / 1e6 - age_s * 1e3, 3)

    def stamp(self, name: str) -> None:
        """Write the stamp ``name`` unless it is written already."""
        if self.stamps.get(name) is None:
            self.stamps[name] = round(self.now_ns() / 1e6, 3)

    def span(self, name: str, stage: Optional[int] = None) -> _Span:
        """``with startup.span("omni.setup.weights", stage=0):``; a span
        given no stage takes the stage of the span it opens in."""
        _listen()  # so that ``outside`` hears the weights' compile too
        return _Span(self, name, stage)

    def snapshot(self) -> dict:
        """``startup`` of the engine's JSON snapshot; ``now_ms`` is the
        clock at the snapshot, so that a reader places its own instant
        on the record's clock without trusting that two Python clocks
        share an epoch."""
        return {"now_ms": round(self.now_ns() / 1e6, 3),
                "stamps": dict(self.stamps),
                "spans": [dict(row) for row in list(self.spans)]}


#: the process's one start-up record
startup = StartupRecord()


# ----------------------------------------------- every executable by name
#: records of fresh executables a runner keeps
EXECUTABLE_RING = 128
#: JAX's own events (jax/_src/dispatch.py, compiler.py) -> the part of a
#: fresh executable's first call they time.  ``backend_s`` is compiling,
#: or loading from the persistent cache; the retrieval lies inside it.
_PARTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
}
_CACHE = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
#: the stats whose fresh executable this thread is building
#: (``building.at``, set by ``CompileStats.first_call`` alone).  JAX's
#: events arrive on the thread that compiles, so the listeners find their
#: runner here.
building = threading.local()
#: events with no fresh executable being built on their thread (the
#: benchmark's float32 reference compiles there; so would a silent
#: retrace of a signature already seen), under the totals' names
outside: dict = {}
_outside_lock = threading.Lock()
_listening = False


def _exclusive_ns(stack: list, end_ns: int, ns: int) -> int:
    """``ns`` less what the events inside this one's interval reported
    before it: a jitted function traced inside another's trace, a trace
    inside a lowering.  ``stack`` holds (start, ns) of the events that no
    later one enclosed yet, in the order they ended."""
    start = end_ns - ns
    inner = 0
    while stack and stack[-1][0] >= start:
        inner += stack.pop()[1]
    stack.append((start, ns))
    if len(stack) > 4096:  # bounded, nothing forgotten: the oldest are one
        stack[:2048] = [(stack[0][0], sum(n for _, n in stack[:2048]))]
    return max(ns - inner, 0)


def _on_duration(event: str, secs: float, **kwargs) -> None:
    part = _PARTS.get(event)
    if part is None:
        return
    at = getattr(building, "at", None)
    if at is not None:
        at._add(part, secs)
        return
    if part != "retrieval_s":
        stack = getattr(building, "stack", None)
        if stack is None:
            stack = building.stack = []
        secs = _exclusive_ns(stack, time.perf_counter_ns(),
                             int(secs * 1e9)) / 1e9
    with _outside_lock:
        outside[part] = outside.get(part, 0.0) + secs


def _on_event(event: str, **kwargs) -> None:
    cache = _CACHE.get(event)
    if cache is None:
        return
    at = getattr(building, "at", None)
    if at is not None:
        at._cached(cache)
        return
    name = "persistent_hits" if cache == "hit" else "persistent_misses"
    with _outside_lock:
        outside[name] = outside.get(name, 0) + 1


def _listen() -> None:
    """Register the process's one pair of listeners, once: by the first
    span of the set-up, or the first runner's stats."""
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)


class CompileStats(dict):
    """A runner's jit telemetry.  The dict's items are the scalars, as
    ``compile_stats`` always held them (``/metrics``, the watchdog):
    ``compiles`` / ``cache_hits`` by the runner's own (kind, shape)
    signatures; ``compile_s``, the fresh signatures' first calls to
    completion (the sum of the four parts below); ``in_flight`` around a
    fresh one; ``trace_s``, ``lower_s``, ``backend_s`` (exclusive: an
    event inside another counts once, under its own name),
    ``retrieval_s`` (inside ``backend_s``), ``persistent_hits`` /
    ``persistent_misses`` of the persistent cache.  Beside them
    ``executables``, a ring of one record a fresh signature, and
    ``current``, the one being built, readable from any thread."""

    def __init__(self, now_ns: Callable[[], int] = time.perf_counter_ns):
        super().__init__(
            compiles=0, cache_hits=0, compile_s=0.0, in_flight=0,
            trace_s=0.0, lower_s=0.0, backend_s=0.0, retrieval_s=0.0,
            persistent_hits=0, persistent_misses=0)
        self.now_ns = now_ns
        self.executables: deque = deque(maxlen=EXECUTABLE_RING)
        self.current: Optional[dict] = None
        self.warming = False  # inside ``warming_up``
        #: weak reference to the engine, whose step number a compile
        #: under traffic is recorded with
        self.engine: Optional[weakref.ref] = None
        self._row: Optional[dict] = None
        self._stack: list = []
        _listen()

    # ------------------------------------------------------- the listeners
    def _add(self, part: str, secs: float) -> None:
        if part != "retrieval_s":
            secs = _exclusive_ns(self._stack, self.now_ns(),
                                 int(secs * 1e9)) / 1e9
            self._row[part] += secs
        self[part] += secs

    def _cached(self, cache: str) -> None:
        self["persistent_hits" if cache == "hit"
             else "persistent_misses"] += 1
        row = self._row
        if row["cache"] != "miss":
            # an executable is several programs (its feed, its dummy
            # inputs): one that had to be compiled makes it a miss
            row["cache"] = cache

    @contextlib.contextmanager
    def warming_up(self):
        """What a runner's ``precompile`` builds is recorded as warm-up."""
        self.warming = True
        try:
            yield
        finally:
            self.warming = False

    # ------------------------------------------------------ the first call
    @contextlib.contextmanager
    def first_call(self, phases: "PhaseClock", key: tuple):
        """A fresh signature's first call, timed TO COMPLETION inside
        ``omni.dispatch`` and recorded by name: what every runner's fresh
        branch goes through::

            with stats.first_call(self.phases, key) as p:
                result = thunk()
                jax.block_until_ready(result)
                p.waited()

        A context manager and not a function that takes the thunk: it
        puts no Python frame above the jitted call, so the locations JAX
        keeps of a traced operation are what they were without it."""
        t0 = self.now_ns()
        step = None
        if not self.warming and self.engine is not None:
            step = getattr(self.engine(), "_steps_completed", None)
        row = {"kind": key[0], "key": list(key[1:]),
               "t0_ms": round(t0 / 1e6, 3), "total_s": None,
               "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
               "run_s": None, "cache": None, "warmup": self.warming,
               "step": step}
        self._stack = []
        self._row = row
        self.current = {"kind": row["kind"], "key": row["key"],
                        "since_ms": row["t0_ms"]}
        self["in_flight"] = 1
        building.at = self
        try:
            with phases.phase("omni.dispatch") as p:
                # after the phase is entered: where it is the outermost
                # one (precompile, a runner driven alone) entering it
                # begins the turn, which forgets what was noted before
                phases.note_compile()  # this turn is no stall
                yield p
        finally:
            building.at = None
            self["in_flight"] = 0
            self.current = self._row = None
        total = p.ns / 1e9
        parts = row["trace_s"] + row["lower_s"] + row["backend_s"]
        # the rest: the first run on the device, the dummy inputs, the
        # feed programs
        row.update(total_s=total, run_s=max(total - parts, 0.0))
        for name in ("total_s", "trace_s", "lower_s", "backend_s", "run_s"):
            row[name] = round(row[name], 6)
        self.executables.append(row)
        self["compiles"] += 1
        self["compile_s"] += total

    def snapshot(self) -> dict:
        """``compile`` of the engine's JSON snapshot and of ``/debug/z``."""
        current = self.current
        if current is not None:
            current = {**current, "for_s": round(
                self.now_ns() / 1e9 - current["since_ms"] / 1e3, 3)}
        with _outside_lock:
            elsewhere = dict(outside)
        return {**self, "executables": list(self.executables),
                "current": current, "outside": elsewhere}
