"""Read a kept run of ``benchmark/run.py`` and say how the engine
thread's phase clock agrees with the trace (PR 36).

    python -m scripts.phase_check .benchmark_cache/runs/<run>.json [...]
    python -m scripts.phase_check <run>.json --xplane .benchmark_cache/trace/<cell>

``run.py`` keeps every run's records and both snapshots under
``.benchmark_cache/runs/`` (gzipped copies are read too).  For each file,
one JSON line with:

- the window (the two snapshots): steps, the mean ``host_ms`` /
  ``device_ms`` / ``step_ms``, ``omni.assemble`` by child and what is
  left of it, every phase a step, the loop's share of the clock, the
  busy waits, the stalls with their records, the collections, and how
  often the two-slot pipeline engaged (the share of steps dispatched
  ahead of their predecessor's retire, the drains, the fallbacks by
  reason; absent for a program that keeps no such counts);
- the tail, where ``--xplane`` names the run's trace (a ``.xplane.pb`` or
  a directory whose newest one is taken; a ``--trace 1`` run leaves its
  own under ``.benchmark_cache/trace/<cell>/``, a ``--trace 2`` run
  reduces its and deletes it): each ``omni.step`` annotation joined to
  the flight record of the same number (``step_num`` = ``step``), and
  over the joined steps the clock's ``omni.assemble*`` against the
  annotation's time, ``device_ms`` (waiting on the device) against the
  device's busy time in the step, and the steps' device time against
  ``busy_s``.

A builder's tool: the driver reads none of it.
"""

from __future__ import annotations

import argparse
import gzip
import json
from pathlib import Path

from benchmark import phase_readers

ASSEMBLE = "omni.assemble"


def load(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def window(e0: dict, e1: dict) -> dict:
    def d(*path):
        a, b = e0, e1
        for key in path:
            a, b = a.get(key, {}), b.get(key, {})
        return (b or 0) - (a or 0)

    steps = d("step_ms", "count")
    clock = d("phases", "clock_ms")
    names = sorted(set(e1["phases"]["by_name"]))
    by = {n: d("phases", "by_name", n, "ms") for n in names}
    children = {n: by[n] for n in names if n.startswith(ASSEMBLE + ".")}
    whole = sum(children.values()) + by.get(ASSEMBLE, 0.0)
    step_sum, host_sum, dev_sum = (d("step_ms", "sum"), d("host_ms", "sum"),
                                   d("device_ms", "sum"))
    records = e1["stalls"]["records"][
        len(e1["stalls"]["records"]) - d("stalls", "total"):] \
        if d("stalls", "total") else []
    return {
        "steps": steps, "clock_s": clock / 1e3,
        "step_ms": step_sum / steps, "host_ms": host_sum / steps,
        "device_ms": dev_sum / steps,
        "host_plus_device_over_step": (host_sum + dev_sum) / step_sum,
        "phases_ms_a_step": {n: round(v / steps, 4) for n, v in by.items()},
        "phases_over_clock": sum(by.values()) / clock,
        "assemble_ms_a_step": whole / steps,
        "assemble_children_share": sum(children.values()) / whole,
        "host_share_of_steps_pct": 100.0 * host_sum / step_sum,
        "loop_share_of_clock_pct": 100.0 * (clock - step_sum) / clock,
        "host_unblocked_pct": 100.0 * (1.0 - dev_sum / clock),
        "busy_waits": d("phases", "busy_waits"),
        "busy_wait_ms_a_step": d("phases", "busy_wait_ms") / steps,
        "idle_wait_ms_a_step": by.get("omni.idle_wait", 0.0) / steps,
        "stalls": d("stalls", "total"),
        "stall_ms": d("stalls", "ms_total"),
        "stall_records": records,
        "gc_ms": d("gc", "ms_total"),
        "gc_collections": {g: d("gc", "collections", g) for g in "012"},
        **pipeline(e1, d),
    }


def pipeline(e1: dict, d) -> dict:
    if "pipeline" not in e1:
        return {}
    ahead, sync = d("pipeline", "pipelined_steps"), d("pipeline", "sync_steps")
    return {
        "pipelined_steps": ahead, "sync_steps": sync,
        "pipelined_share_pct": 100.0 * ahead / max(ahead + sync, 1),
        "pipeline_drains": d("pipeline", "drains"),
        "async_fallback": {k: d("async_fallback", k)
                           for k in e1.get("async_fallback", {})
                           if d("async_fallback", k)},
    }


def joined(records: list, xplane: Path) -> list[dict]:
    """The flight records whose ``step`` the trace holds an ``omni.step``
    annotation for, each with what the trace says of that step:
    ``trace_device_ms`` (the union of the device's operations inside the
    annotation) and ``trace_assemble_ms`` (its ``omni.assemble``)."""
    devices, steps, assembles = phase_readers.steps_of(xplane)
    by_step = phase_readers.step_device_seconds(devices, steps)
    assemble_of = {num: sum(a1 - a0 for a0, a1 in assembles
                            if s0 <= a0 and a1 <= s1) / 1e6
                   for s0, s1, num in steps}
    return [{**r, "trace_device_ms": 1e3 * by_step[r["step"]],
             "trace_assemble_ms": assemble_of[r["step"]]}
            for r in records if r.get("step") in by_step]


def tail(run: dict, xplane: Path) -> dict | None:
    steps = joined(run.get("steps") or [], xplane)
    if not steps:
        return None
    clocked = sum(ms for s in steps for k, ms in s["phases_ms"].items()
                  if k.startswith(ASSEMBLE))
    annotated = sum(s["trace_assemble_ms"] for s in steps)
    device = sum(s["trace_device_ms"] for s in steps)
    waited = sum(s["device_ms"] for s in steps)
    busy_s = (run.get("trace") or {}).get("busy_s")
    gaps = dict((run["result"].get("breakdown") or {}).get("idle_gaps", []))
    return {
        "steps_joined": len(steps),
        "steps_in_tail": len(run["steps"]),
        "assemble_clock_ms": clocked, "assemble_annotation_ms": annotated,
        "assemble_clock_over_annotation": clocked / annotated,
        "idle_in_assemble_ms": 1e3 * gaps.get("in_omni.assemble", 0.0),
        "device_ms_a_step_waited": waited / len(steps),
        "device_ms_a_step_busy": device / len(steps),
        "steps_device_s": device / 1e3, "busy_s": busy_s,
        "steps_device_over_busy": device / 1e3 / busy_s if busy_s else None,
        "host_plus_device_is_the_step": all(
            abs(sum(s["phases_ms"].values())
                - s["host_ms"] - s["device_ms"]) < 0.02 for s in steps),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("runs", nargs="+")
    ap.add_argument("--xplane", type=Path,
                    help="the run's trace, or a directory that holds it")
    args = ap.parse_args()
    xplane = args.xplane
    if xplane is not None and xplane.is_dir():
        xplane = phase_readers.newest_trace(xplane)
    for path in args.runs:
        run = load(path)
        out = {"file": path.rsplit("/", 1)[-1]}
        if "phases" in run.get("engine1", {}):
            out["window"] = window(run["engine0"], run["engine1"])
            if xplane is not None:
                out["tail"] = tail(run, xplane)
        else:
            out["window"] = "no phases block: an older program"
        out["metrics"] = {k: v["value"] for k, v in
                          run["result"]["metrics"].items()}
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
