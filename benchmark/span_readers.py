"""Readers of the per-layer metrics that read the program's own names in
the profiler's trace (metric files name them ``"benchmark.span_readers:fn"``).

The program names its device work with ``jax.named_scope`` (``omni.attn``,
``omni.moe``, ``omni.sampler``, ``omni.logprobs``, ``omni.kv_write``,
``omni.lm_head``, ``omni.proj``, ``omni.embed``) and its host phases with
``jax.profiler.TraceAnnotation`` in the engine thread (``omni.loop`` around
one turn of the orchestrator's loop, ``omni.step`` around one engine step,
and inside them ``omni.intake``, ``omni.schedule``, ``omni.kv_moves``,
``omni.assemble``, ``omni.dispatch``, ``omni.readback``, ``omni.update``,
``omni.record``, ``omni.emit``, ``omni.idle_wait``).  Both land in one
``.xplane.pb``, on one clock: the device planes hold the operations with
their scope path, the host plane holds the engine thread's annotations.

The harness hands a reader ``obs`` and nothing else, so the readers find
the run's trace themselves: the newest ``.xplane.pb`` under
``.benchmark_cache/trace/``, as ``trace.reduce_trace`` does, reduced once
per file.  A trace without the program's names (an older program) gives
None, never 0, and the harness then leaves the metric out.
"""

from __future__ import annotations

import functools
from collections import defaultdict
from pathlib import Path

from benchmark import spec, trace, xplane_proto

TRACE_ROOT = Path(__file__).resolve().parent.parent / ".benchmark_cache" / "trace"

PREFIX = "omni."
#: the layer whose host code the engine thread was in, by innermost
#: annotation.  ``omni.step`` itself is what a step spends outside its
#: named phases; ``omni.loop`` what a turn of the orchestrator's loop
#: spends outside the step (polling, decoding text, the stats heartbeat).
PHASE_LAYER = {
    "omni.schedule": "scheduler", "omni.kv_moves": "scheduler",
    "omni.assemble": "runner", "omni.dispatch": "runner",
    "omni.readback": "runner",
    "omni.update": "engine", "omni.record": "engine", "omni.step": "engine",
    "omni.intake": "front", "omni.emit": "front", "omni.idle_wait": "front",
    "omni.loop": "front",
}


def scope_of(op_name: str) -> str | None:
    """``jit(_decode_step)/omni.moe/cond/branch_1_fun/omni.sampler/sort``
    -> ``omni.sampler``: the innermost component of the scope path that
    the program named; None where it named none."""
    for part in reversed(op_name.split("/")):
        if part.startswith(PREFIX):
            return part
    return None


# ------------------------------------------------------------ device scopes
def scope_self_seconds(events: list[tuple]) -> tuple[dict, dict]:
    """One device's operations ``(start_ns, duration_ns, group key, scope
    or None)`` -> (self seconds by scope, with None for operations under
    no scope; self seconds of those unnamed operations by group key).
    Self time as ``trace.reduce_events`` counts it: a loop or a
    conditional keeps only what its children leave."""
    labelled = [(s, d, scope if scope else "\0" + key)
                for s, d, key, scope in events]
    by_scope: dict = defaultdict(float)
    unnamed: dict = defaultdict(float)
    for label, secs in trace.reduce_events(labelled)["self_s"].items():
        if label.startswith("\0"):
            by_scope[None] += secs
            unnamed[label[1:]] += secs
        else:
            by_scope[label] += secs
    return dict(by_scope), dict(unnamed)


# ------------------------------------------------------------- host phases
def innermost_segments(phases: list[tuple]) -> list[tuple]:
    """Nested annotations ``(start_ns, end_ns, name)`` of one thread ->
    disjoint ``(start_ns, end_ns, name)`` in time order, each stretch
    under the innermost annotation that covers it."""
    out: list[tuple] = []
    stack: list[tuple] = []  # (end, name)
    cur = 0

    def close(upto: float) -> None:
        nonlocal cur
        while stack and stack[-1][0] <= upto:
            end, name = stack.pop()
            if end > cur:
                out.append((cur, end, name))
                cur = end

    for start, end, name in sorted(phases, key=lambda p: (p[0], -p[1])):
        close(start)
        if stack:
            end = min(end, stack[-1][0])  # a child ends with its parent
            if start > cur:
                out.append((cur, start, stack[-1][1]))
        cur = max(cur, start)
        if end > start:
            stack.append((end, name))
    close(float("inf"))
    return out


def idle_seconds(events: list[tuple], phases: list[tuple]
                 ) -> tuple[dict, dict]:
    """One device's operations ``(start_ns, duration_ns, group key, ...)``
    and the engine thread's annotations -> (idle seconds by innermost
    annotation, None where none covered the gap; the same gaps by name:
    ``in_omni.schedule``, and ``after_<operation>`` for the uncovered
    part, as ``trace.reduce_events`` names it).  The window is the first
    operation's start to the last one's end, so the parts sum to the
    window minus the device's busy time."""
    by_phase: dict = defaultdict(float)
    by_name: dict = defaultdict(float)
    if not events:
        return {}, {}
    segments = innermost_segments(phases)
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    i, cover_end, last_key = 0, events[0][0], events[0][2]
    for ev in events:
        start, dur, key = ev[0], ev[1], ev[2]
        if start > cover_end:
            gap0, gap1 = cover_end, start
            left = gap1 - gap0
            while i < len(segments) and segments[i][1] <= gap0:
                i += 1
            j = i
            while j < len(segments) and segments[j][0] < gap1:
                s0, s1, name = segments[j]
                part = min(s1, gap1) - max(s0, gap0)
                if part > 0:
                    by_phase[name] += part / 1e9
                    by_name[f"in_{name}"] += part / 1e9
                    left -= part
                j += 1
            if left > 0:
                by_phase[None] += left / 1e9
                by_name[f"after_{last_key}"] += left / 1e9
        if start + dur > cover_end:
            cover_end, last_key = start + dur, key
    return dict(by_phase), dict(by_name)


def readback_skew_ms(events: list[tuple], phases: list[tuple]) -> list[float]:
    """Per step, the end of its ``omni.readback`` minus the end of the last
    device operation that began between the start of the step's
    ``omni.dispatch`` and the end of that readback: the readback returns
    when the device is done, so on one clock this is a small positive time
    (the transfer and the unpacking); a device clock ahead of the host's
    reads negative here."""
    dispatches = sorted(p[0] for p in phases if p[2] == "omni.dispatch")
    readbacks = sorted((p[0], p[1]) for p in phases
                       if p[2] == "omni.readback")
    ops = sorted((e[0], e[0] + e[1]) for e in events)
    out, k = [], 0
    for n, d0 in enumerate(dispatches):
        d1 = dispatches[n + 1] if n + 1 < len(dispatches) else float("inf")
        back = next((r for r in readbacks if d0 <= r[0] < d1), None)
        if back is None:
            continue
        while k < len(ops) and ops[k][0] < d0:
            k += 1
        ends = []
        while k < len(ops) and ops[k][0] <= back[1]:
            ends.append(ops[k][1])
            k += 1
        if ends:
            out.append((back[1] - max(ends)) / 1e6)
    return out


# ---------------------------------------------------------------- the file
def instruction_name(printed: str) -> str:
    """``%fusion.38 = (u32[64,1]{...}) fusion(...)`` -> ``fusion.38``: the
    trace prints a device operation as its whole HLO instruction."""
    return printed.partition(" = ")[0].strip().lstrip("%")


def module_hlo_protos(space) -> dict[str, bytes]:
    """{module as the trace names it, ``jit__decode_step(<program id>)``:
    its serialized ``HloProto``}: the profiler keeps one of every module
    it saw run, as the ``Hlo Proto`` stat of an entry of the
    ``/host:metadata`` plane."""
    out: dict = {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        metadata, stat_names = xplane_proto.tables(plane)
        for m in metadata.values():
            for stat in m.stats:
                if stat_names.get(stat.metadata_id) == "Hlo Proto":
                    out[m.name] = stat.bytes_value
    return out


def fill_unscoped(events: list[tuple]) -> list[tuple]:
    """The compiler makes operations to which it gives no scope path: it
    expands ``ragged-dot`` into ``ragged-dot-none`` instructions whose
    ``op_name`` is just that, and adds copies and slices of its own.  Such
    an operation, or a run of them, takes the scope of its neighbours in
    time where the scoped operation before and the one after agree (the
    grouped matmuls sit between the router and the combine of
    ``omni.moe``); where they differ, or one is missing, it stays under no
    scope and is counted as unnamed."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    before, last = [], None
    for e in events:
        before.append(last)
        last = e[3] or last
    out, after = [], None
    for e, prev in zip(reversed(events), reversed(before)):
        out.append(e if e[3] or prev != after else e[:3] + (prev,))
        after = e[3] or after
    return out[::-1]


def device_plane_events(plane, hlo_protos: dict) -> list[tuple]:
    """The ``XLA Ops`` line of one device plane as ``(start_ns,
    duration_ns, group key, scope)``.  An operation's scope path is its
    HLO ``op_name``: the ``tf_op`` stat of the event's metadata where the
    profiler wrote one, else looked up by the instruction's name in the
    module that was running (the ``XLA Modules`` line says which, by
    time); what neither names goes through ``fill_unscoped``."""
    metadata, stat_names = xplane_proto.tables(plane)
    lines = {line.name: line for line in plane.lines}
    if "XLA Ops" not in lines:
        return []

    def spans(line):
        return sorted((line.timestamp_ns + ev.offset_ps / 1e3,
                       ev.duration_ps / 1e3, ev.metadata_id)
                      for ev in line.events)

    modules = [(s, s + d, metadata[m].name)
               for s, d, m in spans(lines["XLA Modules"])] \
        if "XLA Modules" in lines else []
    tables: dict = {}
    by_metadata: dict = {}
    out, k = [], 0
    for start, dur, mid in spans(lines["XLA Ops"]):
        if mid not in by_metadata:
            m = metadata[mid]
            own = next((xplane_proto.stat_value(st, stat_names)
                        for st in m.stats
                        if stat_names.get(st.metadata_id) == "tf_op"), "")
            by_metadata[mid] = (trace.group_key(m.name),
                                instruction_name(m.name), scope_of(own))
        key, instruction, scope = by_metadata[mid]
        if scope is None and modules:
            while k + 1 < len(modules) and modules[k][1] <= start:
                k += 1
            name = modules[k][2]
            if modules[k][0] <= start < modules[k][1] and name in hlo_protos:
                if name not in tables:
                    tables[name] = xplane_proto.op_names(hlo_protos[name])
                scope = scope_of(tables[name].get(instruction, ""))
        out.append((start, dur, key, scope))
    return fill_unscoped(out)


def read_planes(xplane_path: Path) -> tuple[dict, list]:
    """({device plane: [(start_ns, duration_ns, group key, scope)]}, the
    engine thread's annotations [(start_ns, end_ns, name)]) of one
    ``.xplane.pb``.  The engine thread is the host line that holds
    ``omni.step``."""
    space = xplane_proto.read(xplane_path)
    hlo_protos = module_hlo_protos(space)
    devices: dict = {}
    phases: list = []
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            events = device_plane_events(plane, hlo_protos)
            if events:
                devices[plane.name] = events
        elif plane.name.startswith("/host:") and not phases:
            metadata, _ = xplane_proto.tables(plane)
            ours = {k: m.name for k, m in metadata.items()
                    if m.name.startswith(PREFIX)}
            for line in plane.lines:
                found = [(line.timestamp_ns + ev.offset_ps / 1e3,
                          line.timestamp_ns
                          + (ev.offset_ps + ev.duration_ps) / 1e3,
                          ours[ev.metadata_id])
                         for ev in line.events if ev.metadata_id in ours]
                if any(name == "omni.step" for _, _, name in found):
                    phases = found
                    break
    return devices, phases


def reduce_planes(devices: dict, phases: list) -> dict | None:
    """Averaged over the device planes: ``scope_s`` (self seconds by
    scope; None where no operation carries a scope), ``unnamed_s`` (by
    group key), ``idle_s`` (idle seconds by innermost annotation; None
    where the trace holds no annotation), ``gaps_s`` (the same by name),
    ``window_s``, ``busy_s`` and ``skew_ms``; and ``planes``, each
    plane's own busy seconds and scope seconds (under tensor parallelism
    they say whether a piece of work is divided among the chips or done
    on each)."""
    per_device = {name: ev for name, ev in devices.items() if ev}
    if not per_device:
        return None
    n = len(per_device)
    first = next(iter(per_device.values()))
    out = {"scope_s": defaultdict(float), "unnamed_s": defaultdict(float),
           "idle_s": defaultdict(float), "gaps_s": defaultdict(float),
           "window_s": 0.0, "busy_s": 0.0, "devices": n, "planes": {},
           "skew_ms": readback_skew_ms(first, phases)}
    for plane, events in per_device.items():
        scope_s, unnamed_s = scope_self_seconds(events)
        idle_s, gaps_s = idle_seconds(events, phases)
        plain = trace.reduce_events([e[:3] for e in events])
        out["window_s"] += plain["window_s"] / n
        out["busy_s"] += plain["busy_s"] / n
        out["planes"][plane] = {
            "busy_s": plain["busy_s"],
            "scope_s": {k or "": v for k, v in scope_s.items()}}
        for name, table in (("scope_s", scope_s), ("unnamed_s", unnamed_s),
                            ("idle_s", idle_s), ("gaps_s", gaps_s)):
            for k, v in table.items():
                out[name][k] += v / n
    for name in ("scope_s", "unnamed_s", "idle_s", "gaps_s"):
        out[name] = dict(out[name])
    if not any(k for k in out["scope_s"]):
        out["scope_s"] = None
    if not phases:
        out["idle_s"] = out["gaps_s"] = None
    return out


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, mtime_ns: int) -> dict | None:
    return reduce_planes(*read_planes(Path(path)))


def reduction(root: Path = TRACE_ROOT) -> dict | None:
    """The newest ``.xplane.pb`` under ``root``, reduced (once per file);
    None where there is none or it holds no device operation."""
    files = sorted(Path(root).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    if not files:
        return None
    return _reduce_file(str(files[-1]), files[-1].stat().st_mtime_ns)


# ---------------------------------------------------------------- readers
def scope_share_pct(obs: dict, scopes: list[str]) -> float | None:
    """Share of the device's operation time (self time) under one of
    ``scopes`` as the innermost ``omni.*`` scope; ``[""]`` asks for the
    time under no scope at all."""
    red = reduction()
    if not red or not red["scope_s"]:
        return None
    total = sum(red["scope_s"].values())
    hit = sum(v for k, v in red["scope_s"].items() if (k or "") in scopes)
    return 100.0 * hit / total if total > 0 else None


def idle_layer_pct(obs: dict, layer: str) -> float | None:
    """Share of the traced window in which the device ran nothing while
    the engine thread was in the host code of ``layer`` (``PHASE_LAYER``);
    ``"unnamed"`` asks for the idle time that no annotation covered.  The
    layers and ``unnamed`` sum to the device's idle share."""
    red = reduction()
    if not red or red["idle_s"] is None or red["window_s"] <= 0:
        return None
    hit = sum(v for k, v in red["idle_s"].items()
              if (PHASE_LAYER.get(k, "unnamed") if k else "unnamed") == layer)
    return 100.0 * hit / red["window_s"]


# --------------------------------------------------------------- rooflines
def roofline_ctx_pct(obs: dict, part: str) -> float | None:
    """The least time the traced steps need on this chip's published
    peaks, each step with the context it recorded (``prefill_ctx`` /
    ``decode_ctx``) in place of the traffic mix's mean, over device time.
    The operations and bytes are the configuration's architecture's
    (``step_cost``, ``attn_cost``, ``moe_cost`` of its ``arch`` module):
    ``part`` ``"step"`` is the whole step over the device's busy time;
    any other is that kernel's count alone (``<part>_cost``) over the
    device time under its scope (``omni.<part>``)."""
    tr = obs["trace"]
    steps = [s for s in obs["steps"]
             if "prefill_ctx" in s and s["prefill_tokens"] + s["decodes"] > 0]
    if not tr or not steps:
        return None
    peaks = trace.peaks_of(obs["device"]["kind"])
    config, chips = obs["config"], obs["chips"]
    arch = spec.arch_of(config)
    if part == "step":
        least = trace.least_seconds(
            (arch.step_cost(s, config,
                            s["prefill_ctx"] / max(s["prefill_tokens"], 1),
                            s["decode_ctx"] / max(s["decodes"], 1))
             for s in steps), peaks, chips=chips)
        return 100.0 * least / tr["busy_s"] if tr["busy_s"] > 0 else None
    red = reduction()
    if not red or not red["scope_s"]:
        return None
    device_s = red["scope_s"].get(PREFIX + part, 0.0)
    cost_of = getattr(arch, f"{part}_cost")
    costs = [c for s in steps if (c := cost_of(s, config)) is not None]
    if device_s <= 0 or not costs:
        return None
    return 100.0 * sum(trace.roof_seconds(c, peaks)
                       for c in costs) / chips / device_s
