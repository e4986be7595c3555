"""Readers of the per-layer metrics that move ``setup_s`` (metric files
name them ``"benchmark.setup_readers:fn"``).

Since PR 52 the program keeps its own set-up on the clock of its phases
(``vllm_omni_tpu/introspection/phase_clock.py``): the JSON snapshot's
``startup`` block (``now_ms``, the clock at the snapshot; ``stamps``, each
written once; ``spans``, nested, ``{name, stage, t0_ms, ms, self_ms,
parent}``) and, in its ``compile`` block, ``executables``: one record a
fresh executable with JAX's own seconds for tracing, lowering and
compiling-or-loading, the rest of its first call (``run_s``), whether the
persistent cache had it (``cache``) and whether the warm-up built it.

Every reader here reads ``obs["engine0"]`` alone, the snapshot taken at
the window's first instant, so the six ``setup_*_s`` partition the time
from the process's start to that instant: their sum is the run's
``setup_s`` plus what lies between the process's start and ``run.py``'s
first line.  A reader only reads: ``obs`` is shared.

A snapshot without the block (an older program's) gives None, never 0,
and the harness then leaves the metric out.
"""

from __future__ import annotations

SETUP = "omni.setup"


def _at(snapshot: dict, path: tuple):
    for key in path:
        if not isinstance(snapshot, dict) or key not in snapshot:
            return None
        snapshot = snapshot[key]
    return snapshot


def _setup(obs: dict) -> dict | None:
    """The first ``omni.setup`` span, once it has closed; None where the
    snapshot has no ``startup`` block."""
    spans = _at(obs["engine0"], ("startup", "spans")) or ()
    return next((s for s in spans
                 if s["name"] == SETUP and s["ms"] is not None), None)


def _spans_ms(obs: dict, names: list[str]) -> float | None:
    """Milliseconds of the closed spans called one of ``names``, over
    every stage; None until the outermost span has closed."""
    if _setup(obs) is None:
        return None
    return sum(s["ms"] for s in obs["engine0"]["startup"]["spans"]
               if s["name"] in names and s["ms"] is not None)


def start_s(obs: dict) -> float | None:
    """The process's start to the opening of ``omni.setup``: the
    interpreter, the imports, the harness's ``device_gate`` (the chip's
    start)."""
    began = _at(obs["engine0"], ("startup", "stamps", "process_start"))
    setup = _setup(obs)
    if began is None or setup is None:
        return None
    return (setup["t0_ms"] - began) / 1e3


def span_s(obs: dict, names: list[str]) -> float | None:
    ms = _spans_ms(obs, names)
    return None if ms is None else ms / 1e3


def rest_s(obs: dict, less: list[str]) -> float | None:
    """The opening of ``omni.setup`` to the stamp ``serving``, less the
    spans called one of ``less``: what the set-up keeps for itself, the
    children no other metric reads, and the orchestrator and the server
    after the stages stand."""
    ms = _spans_ms(obs, less)
    serving = _at(obs["engine0"], ("startup", "stamps", "serving"))
    if ms is None or serving is None:
        return None
    return (serving - _setup(obs)["t0_ms"] - ms) / 1e3


def harness_s(obs: dict) -> float | None:
    """The stamp ``serving`` to the snapshot's own instant: the
    reference check, the template probe, the connections, the lead-in."""
    serving = _at(obs["engine0"], ("startup", "stamps", "serving"))
    now = _at(obs["engine0"], ("startup", "now_ms"))
    if serving is None or now is None:
        return None
    return (now - serving) / 1e3


def _warmed(obs: dict) -> list | None:
    records = _at(obs["engine0"], ("compile", "executables"))
    if records is None:
        return None
    return [r for r in records if r["warmup"]]


def warmup_sum(obs: dict, field: str) -> float | None:
    """Sum of ``field`` over the executables the warm-up built."""
    records = _warmed(obs)
    return None if records is None else sum(r[field] for r in records)


def warmup_count(obs: dict, cache: str | None = None) -> float | None:
    """The executables the warm-up built; with ``cache``, those whose
    record says so of the persistent cache (``"miss"``: compiled)."""
    records = _warmed(obs)
    if records is None:
        return None
    return float(sum(1 for r in records
                     if cache is None or r["cache"] == cache))
