"""From a profiler trace to device numbers, and the table of peaks.

The program has no ``jax.named_scope`` yet, so device operations are
grouped by what the trace prints: the HLO instruction's name without its
serial number, joined to the type and shape of its result
(``sort_f32_64_152064``, ``copy_bf16_4_8192_16_128``).
"""

from __future__ import annotations

import math
import re
from collections import defaultdict
from pathlib import Path

#: published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
#: A kind that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_s": 197e12, "hbm_byte_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}

#: collective operations by printed name.  The trace prints an instruction
#: under the name its HLO carries, and JAX names the all-reduce made by
#: ``lax.psum`` ``%psum.N`` (my chip run, PR 27: ``psum_bf16_2048_2048``
#: beside ``all-gather_bf16_64_152064``)
COLLECTIVES = ("all-reduce*", "all-gather*", "reduce-scatter*",
               "all-to-all*", "collective-permute*", "psum*")

_SHAPE = re.compile(r"=\s*\(?\s*([a-z]+\d*)\[([\d,]*)\]")


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(f"benchmark: no published peaks for device_kind "
                         f"{device_kind!r}; add it to benchmark/trace.py "
                         "with its source")
    return PEAKS[device_kind]


def group_key(name: str) -> str:
    """The trace prints a device operation as its whole HLO instruction:
    ``%sort.37 = (f32[64,152064]{1,0:T(8,128)}, s32[...]) sort(...)`` ->
    ``sort_f32_64_152064``: the instruction's name without its serial
    number, and the type and shape of its (first) result."""
    head, _, _ = name.partition(" = ")
    base = re.sub(r"[.\d]+$", "", head.lstrip("%")) or head
    m = _SHAPE.search(name)
    if not m:
        return base
    dims = m.group(2).replace(",", "_")
    return f"{base}_{m.group(1)}" + (f"_{dims}" if dims else "")


def device_events(xplane_path: Path) -> dict[str, list[tuple]]:
    """{device plane: [(start_ns, duration_ns, group key)]} from the
    operations line of every TPU plane of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(str(xplane_path)).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            out[plane.name] = [(ev.start_ns, ev.duration_ns,
                                group_key(ev.name)) for ev in line.events]
    return out


def reduce_events(events: list[tuple]) -> dict:
    """One device's operations -> busy time (the union of their
    intervals), the traced window (first start to last end), self time by
    group (an operation that encloses others, such as a loop or a
    conditional, keeps only what its children leave), and idle gaps summed
    by the group that ran before each."""
    if not events:
        return {"busy_s": 0.0, "window_s": 0.0, "self_s": {}, "gaps_s": {}}
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    self_ns: dict[str, float] = defaultdict(float)
    gaps_ns: dict[str, float] = defaultdict(float)
    stack: list[list] = []  # [end_ns, key, children's ns, duration_ns]
    busy_ns, cover_end, last_key = 0, events[0][0], events[0][2]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, key, child, dur = stack.pop()
            self_ns[key] += max(dur - child, 0)
            if stack:
                stack[-1][2] += dur

    for start, dur, key in events:
        close(start)
        if start > cover_end:
            gaps_ns[f"after_{last_key}"] += start - cover_end
        if start + dur > cover_end:
            busy_ns += start + dur - max(start, cover_end)
            cover_end, last_key = start + dur, key
        stack.append([start + dur, key, 0, dur])
    close(math.inf)
    t0 = events[0][0]
    return {"busy_s": busy_ns / 1e9, "window_s": (cover_end - t0) / 1e9,
            "self_s": {k: v / 1e9 for k, v in self_ns.items()},
            "gaps_s": {k: v / 1e9 for k, v in gaps_ns.items()}}


def reduce_trace(trace_dir: Path) -> dict | None:
    """The newest ``.xplane.pb`` under ``trace_dir``, averaged over the
    device planes it holds; None where there is none or it holds no device
    operation."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    per_device = [reduce_events(ev)
                  for ev in device_events(files[-1]).values() if ev]
    if not per_device:
        return None
    n = len(per_device)
    merged = {"busy_s": sum(d["busy_s"] for d in per_device) / n,
              "window_s": sum(d["window_s"] for d in per_device) / n,
              "devices": n, "self_s": defaultdict(float),
              "gaps_s": defaultdict(float)}
    for d in per_device:
        for field in ("self_s", "gaps_s"):
            for k, v in d[field].items():
                merged[field][k] += v / n
    merged["self_s"], merged["gaps_s"] = (dict(merged["self_s"]),
                                          dict(merged["gaps_s"]))
    return merged


def top(table: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


# ------------------------------------------------------------- roofline
def roof_seconds(cost: tuple[float, float], peaks: dict) -> float:
    """The least time one chip could take for ``cost`` = (operations,
    bytes): the larger of operations over the peak rate and bytes over the
    peak bandwidth."""
    flops, nbytes = cost
    return max(flops / peaks["bf16_flop_s"], nbytes / peaks["hbm_byte_s"])


def least_seconds(costs, peaks: dict, chips: int = 1) -> float:
    """The least time the chip(s) could take for a run of steps, each
    given as the (operations, bytes) its architecture counts for it
    (``step_cost`` of the configuration's ``arch`` module), or None for a
    step with no work."""
    total = 0.0
    for cost in costs:
        if cost is not None:
            total += roof_seconds(cost, peaks) / chips
    return total
