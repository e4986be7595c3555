"""The per-layer metrics that move ``setup_s`` (benchmark/setup_readers.py):
each metric file against a hand-made snapshot with hand-worked answers, a
snapshot of an older program, its entry in ``BENCHMARK.json``, and a tiny
cell through ``run.py`` whose first six partition its ``setup_s``."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import readers, setup_readers, spec

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
#: the last entry of ``per_layer`` at the parent of PR 52
LAST_THE_PARENT_HAD = "window_keep_pct_mean"

#: the start-up record of one hand-made run, milliseconds on the program's
#: clock: the process starts at 100 000, ``omni.setup`` runs from 114 500 to
#: 174 750, the socket listens at 175 250, the snapshot is taken at 205 250
STARTUP = {
    "now_ms": 205_250.0,
    "stamps": {"process_start": 100_000.0, "serving": 175_250.0},
    "spans": [
        {"name": "omni.setup", "stage": None, "t0_ms": 114_500.0,
         "ms": 60_250.0, "self_ms": 150.0, "parent": None},
        {"name": "omni.setup.weights", "stage": 0, "t0_ms": 114_600.0,
         "ms": 21_000.0, "self_ms": 21_000.0, "parent": "omni.setup"},
        {"name": "omni.setup.draft", "stage": 0, "t0_ms": 135_600.0,
         "ms": 1_500.0, "self_ms": 1_500.0, "parent": "omni.setup"},
        {"name": "omni.setup.engine", "stage": 0, "t0_ms": 137_200.0,
         "ms": 37_600.0, "self_ms": 350.0, "parent": "omni.setup"},
        {"name": "omni.setup.cache", "stage": 0, "t0_ms": 137_300.0,
         "ms": 2_250.0, "self_ms": 2_250.0, "parent": "omni.setup.engine"},
        {"name": "omni.setup.warmup", "stage": 0, "t0_ms": 139_700.0,
         "ms": 35_000.0, "self_ms": 35_000.0, "parent": "omni.setup.engine"},
        # an engine warmed again long after: open, and no part of any sum
        {"name": "omni.setup.warmup", "stage": 0, "t0_ms": 205_000.0,
         "ms": None, "self_ms": None, "parent": None},
    ],
}


def _row(kind, key, trace, lower, backend, run, cache, warmup=True):
    return {"kind": kind, "key": key, "t0_ms": 140_000.0,
            "total_s": trace + lower + backend + run, "trace_s": trace,
            "lower_s": lower, "backend_s": backend, "run_s": run,
            "cache": cache, "warmup": warmup,
            "step": None if warmup else 7}


EXECUTABLES = [
    _row("dispatch", [8, False], 0.5, 0.75, 0.25, 0.125, "hit"),
    _row("dispatch_lp", [8, False], 0.5, 1.0, 4.0, 0.25, "miss"),
    _row("unified", [256, 1, False, False, False], 2.0, 3.0, 0.5, 0.5, "hit"),
    _row("unified", [512, 1, False, False, False], 3.0, 4.25, 9.0, 1.0,
         "miss"),
    # the template probe's variant, compiled by the first request
    _row("unified", [256, 1, True, False, False], 8.0, 8.0, 8.0, 8.0, "miss",
         warmup=False),
]
OBS = {"engine0": {"startup": STARTUP,
                   "compile": {"compiles": 5, "executables": EXECUTABLES}}}
#: the program of PR 51: neither block
OLDER = {"engine0": {"compile": {"compiles": 4, "cache_hits": 9,
                                 "compile_s": 30.0, "in_flight": 0},
                     "phases": {"clock_ms": 1.0, "by_name": {}}}}

#: metric -> (layer, source, unit, what the hand-made run reads)
WANT = {
    "setup_start_s": ("process start", "program_span", "s", 14.5),
    "setup_weights_s": ("model loader", "program_span", "s", 22.5),
    "setup_cache_s": ("runner", "program_span", "s", 2.25),
    "setup_warmup_s": ("runner", "program_span", "s", 35.0),
    "setup_serve_s": ("HTTP and orchestrator", "program_span", "s", 1.0),
    "setup_harness_s": ("load generator", "program_span", "s", 30.0),
    "warmup_trace_s": ("runner", "program_counter", "s", 6.0),
    "warmup_lower_s": ("runner", "program_counter", "s", 9.0),
    "warmup_backend_s": ("runner", "program_counter", "s", 13.75),
    "warmup_run_s": ("runner", "program_counter", "s", 1.875),
    "warmup_executables": ("runner", "program_counter", "executables", 4.0),
    "warmup_cache_miss": ("runner", "program_counter", "executables", 2.0),
}
NAMES = list(WANT)
PARTITION = NAMES[:6]


def _read(name: str, obs: dict):
    body = spec.load_json(ROOT / "benchmark" / "layer_metrics"
                          / f"{name}.json")
    assert body["reader"].startswith("benchmark.setup_readers:")
    return readers.resolve(body["reader"])(obs, **body.get("params", {}))


@pytest.mark.parametrize("name", NAMES)
def test_a_hand_made_snapshot_gives_the_tables_arithmetic(name):
    assert _read(name, OBS) == pytest.approx(WANT[name][3], abs=1e-9)


def test_the_first_six_partition_process_start_to_the_snapshot():
    whole = (STARTUP["now_ms"] - STARTUP["stamps"]["process_start"]) / 1e3
    assert sum(_read(n, OBS) for n in PARTITION) == pytest.approx(
        whole, abs=1e-9)


@pytest.mark.parametrize("name", NAMES)
def test_an_older_programs_snapshot_gives_none_never_zero(name):
    assert _read(name, OLDER) is None
    assert _read(name, {"engine0": {}}) is None


@pytest.mark.parametrize("name", PARTITION)
def test_a_set_up_that_has_not_closed_gives_none(name):
    spans = [dict(STARTUP["spans"][0], ms=None, self_ms=None)]
    started = {**STARTUP, "spans": spans,
               "stamps": {**STARTUP["stamps"], "serving": None}}
    assert _read(name, {"engine0": {"startup": started}}) is None


def test_process_start_unknown_leaves_the_start_out_and_the_rest_in():
    stamps = {**STARTUP["stamps"], "process_start": None}
    obs = {"engine0": {"startup": {**STARTUP, "stamps": stamps}}}
    assert _read("setup_start_s", obs) is None
    assert _read("setup_warmup_s", obs) == 35.0


@pytest.mark.parametrize("name", NAMES)
def test_the_entry_moves_setup_s_in_all_ten_cells_after_the_parents(name):
    layer, source, unit, _ = WANT[name]
    listed = [m["name"] for m in BENCH["per_layer"]]
    entry = BENCH["per_layer"][listed.index(name)]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": "setup_s",
                     "workloads": CELLS[:10]}
    # the twelve stand together, in the table's order, after every entry
    # the parent had: whatever a later PR appends comes after them
    first = listed.index(LAST_THE_PARENT_HAD) + 1
    assert listed[first:first + len(NAMES)] == NAMES
    for cell in CELLS[:10]:
        resolved = {m["name"]: m for m in spec.resolve_cell(cell)["per_layer"]}
        assert resolved[name]["reader"].startswith("benchmark.setup_readers:")
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])


def test_the_readers_name_no_model_and_no_cell():
    text = (ROOT / "benchmark" / "setup_readers.py").read_text()
    assert not any(w in text for w in ("thinker", "qwen", "_sat", "_rate"))
    assert setup_readers.SETUP == "omni.setup"


def test_trace_2_reports_all_twelve_and_the_six_sum_to_setup_s(tmp_path):
    """A tiny cell on the CPU, through ``run.py`` untouched: the last line
    holds the twelve, the first six sum to the run's ``setup_s`` (which
    starts at ``run.py``'s first line, a few hundredths of a second after
    the process), the four parts of the warm-up fit inside its span, and a
    cold cache makes every executable a miss."""
    from tests.benchmark.test_span_readers import TINY, TINY_MIX

    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "benchmark/traffic/tiny_closed.json").write_text(
        json.dumps(TINY_MIX))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][0], "name": "tiny",
                             "file": "benchmark/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny_setup", "config": "tiny",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "thinker_chat_sat" in m.get("workloads", []):
            m["workloads"].append("tiny_setup")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "tiny_setup", "--seed", "5000000013",
         "--seconds", "2", "--trace", "2", "--allow-cpu"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": str(ROOT),
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax")})
    assert done.returncode == 0, done.stderr[-2000:]
    metrics = json.loads(done.stdout.strip().splitlines()[-1])["metrics"]
    assert set(NAMES) <= set(metrics)
    value = {n: metrics[n]["value"] for n in NAMES}
    assert all(v >= 0.0 for v in value.values()), value
    assert sum(value[n] for n in PARTITION) == pytest.approx(
        metrics["setup_s"]["value"], abs=0.5)
    parts = sum(value[f"warmup_{p}_s"]
                for p in ("trace", "lower", "backend", "run"))
    assert 0.0 < parts <= value["setup_warmup_s"]
    assert value["setup_harness_s"] >= TINY_MIX["lead_in_s"]
    assert value["warmup_executables"] > 0
    assert value["warmup_cache_miss"] == value["warmup_executables"]
    runs = sorted((tmp_path / ".benchmark_cache" / "runs").glob(
        "tiny_setup-seed5000000013-trace2-*.json"))
    kept = json.loads(runs[-1].read_text())["engine0"]
    assert value["warmup_executables"] == sum(
        1 for r in kept["compile"]["executables"] if r["warmup"])
    stamps = kept["startup"]["stamps"]
    setup = next(s for s in kept["startup"]["spans"]
                 if s["name"] == "omni.setup")
    assert (stamps["process_start"] < setup["t0_ms"]
            < setup["t0_ms"] + setup["ms"] <= stamps["serving"]
            < kept["startup"]["now_ms"])
    assert set(stamps) == {"process_start", "serving"}
