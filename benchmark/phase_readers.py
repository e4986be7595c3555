"""Readers of the per-layer metrics that read the engine thread's phase
clock (metric files name them ``"benchmark.phase_readers:fn"``).

Since PR 36 the program keeps the time of its host phases (``omni.*``,
the names of ``span_readers.PHASE_LAYER`` and the three children of
``omni.assemble``) on the host's clock on every step, and its JSON
snapshot carries the lifetime totals: ``phases`` (``clock_ms``, the stamp
the totals are complete up to; ``by_name``; the busy waits), ``stalls``
and ``gc``.  The readers here take the difference between the two
snapshots at the edges of the measured window, so they read all of it,
untraced, where the trace readers see a 3 s tail.

``step_device_ms`` reads the trace instead: the device's time inside each
``omni.step`` annotation, which carries the step's number as its
``step_num`` stat; the flight record of that step carries the same number
as ``step``, so the two join exactly (``scripts/phase_check.py`` does, from
a trace file that was kept).  A reader only reads: ``obs`` is shared.

A snapshot without the block (an older program's) gives None, never 0,
and the harness then leaves the metric out.
"""

from __future__ import annotations

import bisect
import functools
from pathlib import Path

from benchmark import span_readers, xplane_proto

STEP = "omni.step"
ASSEMBLE = "omni.assemble"


def _at(snapshot: dict, path: tuple):
    for key in path:
        if not isinstance(snapshot, dict) or key not in snapshot:
            return None
        snapshot = snapshot[key]
    return snapshot


def _difference(obs: dict, *path) -> float | None:
    """``engine1`` minus ``engine0`` at ``path``; None where either
    snapshot lacks the block the path starts in."""
    v0, v1 = _at(obs["engine0"], path), _at(obs["engine1"], path)
    if v0 is None or v1 is None:
        return None
    return v1 - v0


def _steps(obs: dict) -> float | None:
    return _difference(obs, "step_ms", "count")


def _clock_ms(obs: dict) -> float | None:
    ms = _difference(obs, "phases", "clock_ms")
    return ms if ms and ms > 0 else None


def phase_ms_per_step(obs: dict, phase: str) -> float | None:
    """Exclusive milliseconds the engine thread spent in ``phase`` over
    the measured window, a step.  A phase that never ran reads 0."""
    steps = _steps(obs)
    by0 = _at(obs["engine0"], ("phases", "by_name"))
    by1 = _at(obs["engine1"], ("phases", "by_name"))
    if by0 is None or by1 is None or not steps or steps <= 0:
        return None
    ms = by1.get(phase, {}).get("ms", 0.0) - by0.get(phase, {}).get("ms", 0.0)
    return ms / steps


def busy_wait_ms_per_step(obs: dict) -> float | None:
    """Milliseconds a step that the orchestrator's loop slept while a
    stage still had unfinished work (the 2 ms wait it takes after every
    turn that returned no finished request)."""
    ms, steps = _difference(obs, "phases", "busy_wait_ms"), _steps(obs)
    if ms is None or not steps or steps <= 0:
        return None
    return ms / steps


def host_unblocked_pct(obs: dict) -> float | None:
    """Share of the measured window in which the engine thread was NOT
    blocked on the device: 100 x (1 - the steps' ``device_ms`` over the
    clock's time).  A host number on the host's clock: the thread also
    waits while the device is idle (the launch, and from the device's
    last operation to the return of ``device_get``), so this is no idle
    share of the device; that is ``device_idle_pct``, from the trace."""
    device_ms = _difference(obs, "device_ms", "sum")
    clock_ms = _clock_ms(obs)
    if device_ms is None or clock_ms is None:
        return None
    return 100.0 * (1.0 - device_ms / clock_ms)


def stall_share_pct(obs: dict) -> float | None:
    """Share of the measured window lost to stalled turns (a second or
    more and four times the recent 99th percentile, no compile): their
    time above that percentile over the clock's time."""
    ms, clock_ms = _difference(obs, "stalls", "ms_total"), _clock_ms(obs)
    if ms is None or clock_ms is None:
        return None
    return 100.0 * ms / clock_ms


# ------------------------------------------------ device time by step
def _union(spans: list[tuple]) -> tuple[list, list, list]:
    """Intervals ``(start, end)`` -> their union as sorted disjoint
    (starts, ends) and the covered time before each."""
    starts, ends, before = [], [], []
    total = 0.0
    for start, end in sorted(spans):
        if ends and start <= ends[-1]:
            if end > ends[-1]:
                total += end - ends[-1]
                ends[-1] = end
            continue
        starts.append(start)
        ends.append(end)
        before.append(total)
        total += end - start
    return starts, ends, before


def _covered(union: tuple[list, list, list], lo: float, hi: float) -> float:
    """Time of ``union`` inside ``[lo, hi)``."""
    starts, ends, before = union

    def upto(t: float) -> float:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0.0
        return before[i] + min(t, ends[i]) - starts[i]

    return max(upto(hi) - upto(lo), 0.0)


def step_device_seconds(devices: dict, steps: list[tuple]) -> dict:
    """``{device plane: [(start_ns, duration_ns, ...)]}`` and the engine
    thread's ``omni.step`` annotations ``(start_ns, end_ns, step_num)`` ->
    ``{step_num: seconds}``: the union of the device's operations inside
    each step, averaged over the device planes."""
    unions = [_union([(e[0], e[0] + e[1]) for e in events])
              for events in devices.values() if events]
    if not unions or not steps:
        return {}
    return {num: sum(_covered(u, s0, s1) for u in unions)
            / len(unions) / 1e9 for s0, s1, num in steps}


def read_steps(xplane_path: Path) -> tuple[dict, list, list]:
    """(device operations by plane as ``(start_ns, duration_ns)``, the
    ``omni.step`` annotations ``(start_ns, end_ns, step_num)``, the
    ``omni.assemble`` annotations ``(start_ns, end_ns)``) of one
    ``.xplane.pb``, all on its one clock."""
    space = xplane_proto.read(xplane_path)
    devices: dict = {}
    steps: list = []
    assembles: list = []
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (line.timestamp_ns + ev.offset_ps / 1e3,
                         ev.duration_ps / 1e3) for ev in line.events]
        elif plane.name.startswith("/host:") and not steps:
            metadata, stat_names = xplane_proto.tables(plane)
            ours = {k: m.name for k, m in metadata.items()
                    if m.name in (STEP, ASSEMBLE)}
            for line in plane.lines:
                for ev in line.events:
                    name = ours.get(ev.metadata_id)
                    if name is None:
                        continue
                    start = line.timestamp_ns + ev.offset_ps / 1e3
                    end = start + ev.duration_ps / 1e3
                    if name == ASSEMBLE:
                        assembles.append((start, end))
                        continue
                    num = next((xplane_proto.stat_value(st, stat_names)
                                for st in ev.stats
                                if stat_names.get(st.metadata_id)
                                == "step_num"), None)
                    # a trace without the stat still counts its steps
                    steps.append((start, end,
                                  f"#{len(steps)}" if num is None else num))
                if steps:
                    break
                assembles = []
    return devices, steps, assembles


@functools.lru_cache(maxsize=2)
def _steps_of_file(path: str, mtime_ns: int) -> tuple[dict, list, list]:
    return read_steps(Path(path))


def newest_trace(root: Path | None = None) -> Path | None:
    files = sorted(Path(root or span_readers.TRACE_ROOT).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    return files[-1] if files else None


def steps_of(path: Path) -> tuple[dict, list, list]:
    """``read_steps`` of one file, parsed once a process."""
    return _steps_of_file(str(path), Path(path).stat().st_mtime_ns)


def step_device_ms(obs: dict) -> float | None:
    """Mean over the traced steps of the device's time inside each
    ``omni.step`` annotation (the union of its operations there), from
    the newest trace under the checkout's cache."""
    path = newest_trace()
    if path is None:
        return None
    devices, steps, _ = steps_of(path)
    by_step = step_device_seconds(devices, steps)
    if not by_step:
        return None
    return 1e3 * sum(by_step.values()) / len(by_step)
