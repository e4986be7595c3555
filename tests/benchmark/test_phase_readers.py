"""The per-layer metrics that read the engine thread's phase clock
(benchmark/phase_readers.py): each metric file against a synthetic pair of
snapshots with hand-worked answers, a snapshot of an older program, and
``step_device_ms`` over a hand-built plane of three steps."""

from __future__ import annotations

import re

import pytest

from benchmark import phase_readers, readers, spec, xplane_proto

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
US = 1000

SAT = ["thinker_chat_sat", "thinker_doc_sat", "qwen3next_longdoc_sat",
       "glm47flash_agent_sat", "dots3note_agent_sat"]
RATE = ["thinker_chat_rate"]


def _snapshot(steps, clock_ms, device_ms, pack, sampling, put, busy_ms,
              stall_ms):
    return {
        "step_ms": {"count": steps, "sum": 0.0},
        "device_ms": {"count": steps, "sum": device_ms},
        "phases": {
            "clock_ms": clock_ms,
            "by_name": {"omni.assemble.pack": {"ms": pack, "n": steps},
                        "omni.assemble.sampling": {"ms": sampling,
                                                   "n": steps},
                        "omni.assemble.put": {"ms": put, "n": steps},
                        "omni.assemble": {"ms": 1.0, "n": steps}},
            "busy_waits": 0, "busy_wait_ms": busy_ms},
        "stalls": {"total": 0, "ms_total": stall_ms, "records": []},
    }


#: the window: 1 000 steps in 45 s, of which the thread waited 30 s on the
#: device, a 2 s stall in it
ENGINE0 = _snapshot(200, 100_000.0, 5_000.0, 100.0, 50.0, 300.0, 40.0, 0.0)
ENGINE1 = _snapshot(1200, 145_000.0, 35_000.0, 2100.0, 550.0, 3300.0,
                    2440.0, 1980.0)
WANT = {
    "assemble_pack_ms": 2.0, "assemble_sampling_ms": 0.5,
    "assemble_put_ms": 3.0, "busy_wait_ms": 2.4,
    "host_unblocked_pct": 100.0 * (1 - 30_000.0 / 45_000.0),
    "stall_share_pct": 100.0 * 1980.0 / 45_000.0,
}
UNITS = {"assemble_pack_ms": "ms", "assemble_sampling_ms": "ms",
         "assemble_put_ms": "ms", "busy_wait_ms": "ms",
         "host_unblocked_pct": "%", "stall_share_pct": "%",
         "step_device_ms": "ms"}
NAMES = sorted(f"{base}.{twin}" for base in UNITS for twin in ("sat", "rate"))

#: one device: three steps of 10 us on the host; the device busy 4 us of
#: the first (two operations that overlap), 6 of the second (a loop that
#: encloses its body), 3 of the third and 1 after it, under no step
OPS = [(2, 3), (3, 3), (12, 6), (13, 2), (24, 3), (35, 1)]
STEPS = [(0, 10, 7), (10, 20, 8), (20, 30, 9)]
ASSEMBLES = [(1, 2), (10, 12), (21, 22)]


def _metric(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    metric = spec.load_json(ROOT / "benchmark/layer_metrics" / f"{name}.json")
    return entry, readers.resolve(metric["reader"]), metric.get("params", {})


def _recorded_trace(tmp_path, with_step_num=True):
    space = xplane_proto._message_class("XSpace")()
    device = space.planes.add(name="/device:TPU:0")
    device.event_metadata.add(key=1).value.name = "%fusion.1 = f32[8]{0} f()"
    ops = device.lines.add(name="XLA Ops", timestamp_ns=1000)
    for start, dur in OPS:
        ops.events.add(metadata_id=1, offset_ps=start * US * 1000,
                       duration_ps=dur * US * 1000)
    steps_line = device.lines.add(name="Steps", timestamp_ns=1000)
    steps_line.events.add(metadata_id=1, offset_ps=0, duration_ps=5)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "omni.step"
    host.event_metadata.add(key=2).value.name = "omni.assemble"
    host.event_metadata.add(key=3).value.name = "omni.loop"
    host.stat_metadata.add(key=1).value.name = "_r"
    host.stat_metadata.add(key=2).value.name = "step_num"
    other = host.lines.add(name="http", timestamp_ns=1000)
    other.events.add(metadata_id=2, offset_ps=0, duration_ps=5)
    thread = host.lines.add(name="omni-engine", timestamp_ns=1000)
    thread.events.add(metadata_id=3, offset_ps=0, duration_ps=40 * US * 1000)
    for start, end, num in STEPS:
        ev = thread.events.add(metadata_id=1, offset_ps=start * US * 1000,
                               duration_ps=(end - start) * US * 1000)
        ev.stats.add(metadata_id=1, int64_value=1)
        if with_step_num:
            ev.stats.add(metadata_id=2, int64_value=num)
    for start, end in ASSEMBLES:
        thread.events.add(metadata_id=2, offset_ps=start * US * 1000,
                          duration_ps=(end - start) * US * 1000)
    path = tmp_path / "plugins" / "profile" / "t" / "vm.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(space.SerializeToString())
    return path


@pytest.mark.parametrize("name", NAMES)
def test_every_new_metric_file_resolves_and_reads(name, tmp_path,
                                                  monkeypatch):
    entry, reader, params = _metric(name)
    base, twin = name.rsplit(".", 1)
    assert entry["unit"] == UNITS[base] and entry["better"] == "lower"
    assert entry["workloads"] == (SAT if twin == "sat" else RATE)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert set(entry["workloads"]) <= set(e2e[entry["moves"]]["workloads"])
    if twin == "sat":
        assert entry["moves"] == "tok_s"
    monkeypatch.setattr(phase_readers.span_readers, "TRACE_ROOT", tmp_path)
    steps = [{"step": 8, "host_ms": 1.0}, {"step": 9}, {"seq": 3}]
    obs = {"engine0": ENGINE0, "engine1": ENGINE1, "steps": steps}
    if base == "step_device_ms":
        assert reader(obs, **params) is None  # no trace: nothing to read
        _recorded_trace(tmp_path)
        assert reader(obs, **params) == pytest.approx((4 + 6 + 3) / 3 / 1e3)
        # a reader only reads: the observation is every reader's
        assert steps == [{"step": 8, "host_ms": 1.0}, {"step": 9},
                         {"seq": 3}]
        return
    assert reader(obs, **params) == pytest.approx(WANT[base])
    # an older program's snapshots: None, never 0
    older = {k: {kk: vv for kk, vv in v.items()
                 if kk not in ("phases", "stalls")}
             for k, v in (("engine0", ENGINE0), ("engine1", ENGINE1))}
    assert reader({**older, "steps": []}, **params) is None
    # a window in which no step ran has no mean a step, and no share
    assert reader({"engine0": ENGINE1, "engine1": ENGINE1, "steps": []},
                  **params) is None


def test_a_phase_that_never_ran_reads_zero_where_the_block_is_there():
    obs = {"engine0": ENGINE0, "engine1": ENGINE1}
    assert phase_readers.phase_ms_per_step(obs, "omni.kv_moves") == 0.0
    assert phase_readers.stall_share_pct(
        {"engine0": ENGINE0, "engine1": {**ENGINE1, "stalls": {
            "total": 0, "ms_total": 0.0, "records": []}}}) == 0.0


def test_step_device_seconds_over_a_hand_built_plane_of_three_steps():
    """The union of the device's operations inside each ``omni.step``,
    clipped to it, by the step's number; two planes are averaged."""
    events = [(s * US, d * US, "fusion", None) for s, d in OPS]
    steps = [(s * US, e * US, n) for s, e, n in STEPS]
    got = phase_readers.step_device_seconds({"/device:TPU:0": events}, steps)
    assert got == pytest.approx({7: 4e-6, 8: 6e-6, 9: 3e-6})
    # an operation that straddles a step's edge is cut at it
    got = phase_readers.step_device_seconds(
        {"/device:TPU:0": [(8 * US, 4 * US)]}, steps)
    assert got == pytest.approx({7: 2e-6, 8: 2e-6, 9: 0.0})
    # a second chip that ran nothing in the steps halves the mean
    got = phase_readers.step_device_seconds(
        {"/device:TPU:0": events, "/device:TPU:1": [(50 * US, US)]}, steps)
    assert got == pytest.approx({7: 2e-6, 8: 3e-6, 9: 1.5e-6})
    assert phase_readers.step_device_seconds({}, steps) == {}
    assert phase_readers.step_device_seconds({"/device:TPU:0": events},
                                             []) == {}


def test_steps_are_read_from_a_recorded_trace_with_their_numbers(tmp_path):
    path = _recorded_trace(tmp_path)
    devices, steps, assembles = phase_readers.read_steps(path)
    assert devices == {"/device:TPU:0": [
        (1000 + s * US, d * US) for s, d in OPS]}
    assert steps == [(1000 + s * US, 1000 + e * US, n) for s, e, n in STEPS]
    # the engine thread's line only: the other line's event is not ours
    assert assembles == [(1000 + s * US, 1000 + e * US)
                         for s, e in ASSEMBLES]
    assert phase_readers.newest_trace(tmp_path) == path
    # an older trace without the stat still counts its steps
    bare = _recorded_trace(tmp_path / "older", with_step_num=False)
    _, steps, _ = phase_readers.read_steps(bare)
    assert [n for _, _, n in steps] == ["#0", "#1", "#2"]
    assert phase_readers.newest_trace(tmp_path / "nothing") is None


def test_the_checker_joins_a_record_to_its_step_by_number(tmp_path):
    """``scripts.phase_check`` gives each flight record what the trace
    holds for the step of the same number; a record of a step the trace
    lacks, or of none, is left out."""
    from scripts import phase_check

    path = _recorded_trace(tmp_path)
    records = [{"step": 8, "device_ms": 7e-3, "host_ms": 3e-3,
                "phases_ms": {"omni.assemble.put": 1.5e-3,
                              "omni.assemble": 0.5e-3,
                              "omni.readback": 8e-3}},
               {"step": 9, "device_ms": 4e-3, "host_ms": 6e-3,
                "phases_ms": {"omni.assemble.pack": 1e-3,
                              "omni.step": 9e-3}},
               {"step": 12}, {"seq": 3}]
    steps = phase_check.joined(records, path)
    assert [s["step"] for s in steps] == [8, 9]
    assert steps[0]["trace_device_ms"] == pytest.approx(6e-3)
    assert steps[0]["trace_assemble_ms"] == pytest.approx(2e-3)
    assert steps[1]["trace_device_ms"] == pytest.approx(3e-3)
    assert "trace_device_ms" not in records[0]
    said = phase_check.tail(
        {"steps": records, "trace": {"busy_s": 14e-6},
         "result": {"breakdown": {"idle_gaps": [["in_omni.assemble",
                                                 3e-6]]}}}, path)
    assert said["steps_joined"] == 2 and said["steps_in_tail"] == 4
    assert said["assemble_clock_over_annotation"] == pytest.approx(1.0)
    assert said["steps_device_over_busy"] == pytest.approx(9 / 14)
    assert said["device_ms_a_step_waited"] == pytest.approx(5.5e-3)
    assert said["host_plus_device_is_the_step"]
    assert phase_check.tail({"steps": [{"seq": 3}]}, path) is None


def test_the_new_reader_names_no_model_and_edits_no_entry():
    from tests.benchmark import test_new_arch

    text = (ROOT / "benchmark" / "phase_readers.py").read_text()
    assert not test_new_arch.MODEL_WORDS.search(text)
    # the fourteen entries stand at the end of the list, in the issue's
    # order; every entry before them is one the parent had
    tail = [m["name"] for m in BENCH["per_layer"][-14:]]
    assert sorted(tail) == NAMES
    assert not any(re.match(r"(assemble|busy_wait|host_unblocked|stall_share|"
                            r"step_device)", m["name"])
                   for m in BENCH["per_layer"][:-14])
    assert BENCH["trace_in_run"] is True


def test_trace_2_reports_the_clocks_metrics_on_a_tiny_cell(tmp_path):
    """A tiny cell on the CPU, through ``run.py`` untouched: the last line
    holds the metrics that read the two snapshots (no device plane on a
    CPU, so ``step_device_ms`` finds nothing and is left out), the kept
    run holds both snapshots' blocks, and ``scripts.phase_check`` reads
    it."""
    import json
    import os
    import shutil
    import subprocess
    import sys

    from tests.benchmark.test_span_readers import TINY, TINY_MIX

    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "benchmark/traffic/tiny_closed.json").write_text(
        json.dumps(TINY_MIX))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][0], "name": "tiny",
                             "file": "benchmark/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny_phases", "config": "tiny",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "thinker_chat_sat" in m.get("workloads", []):
            m["workloads"].append("tiny_phases")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # the copy's own run.py: its cache, traces and kept runs then lie under
    # tmp_path, out of the way of the other files' tiny cells (the readers
    # take the newest trace under their checkout's cache, and the workers
    # of a parallel run share the checkout's)
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "tiny_phases", "--seed", "5000000011",
         "--seconds", "2", "--trace", "2", "--allow-cpu"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": str(ROOT),
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax")})
    assert done.returncode == 0, done.stderr[-2000:]
    metrics = json.loads(done.stdout.strip().splitlines()[-1])["metrics"]
    clocked = {n for n in NAMES if n.endswith(".sat")} - {"step_device_ms.sat"}
    assert clocked <= set(metrics)
    assert "step_device_ms.sat" not in metrics
    assert not any(n.endswith(".rate") for n in metrics)
    value = {n: metrics[n]["value"] for n in clocked}
    assert all(value[f"assemble_{c}_ms.sat"] > 0.0
               for c in ("pack", "sampling", "put"))
    assert 0.0 < value["host_unblocked_pct.sat"] < 100.0
    assert value["stall_share_pct.sat"] == 0.0
    assert value["busy_wait_ms.sat"] > 0.0
    runs = sorted((tmp_path / ".benchmark_cache" / "runs").glob(
        "tiny_phases-seed5000000011-trace2-*.json"))
    kept = json.loads(runs[-1].read_text())
    assert {"phases", "stalls", "gc"} <= set(kept["engine1"])
    assert all({"step", "phases_ms", "cpu_ms"} <= set(s)
               for s in kept["steps"])
    said = subprocess.run(
        [sys.executable, "-m", "scripts.phase_check", str(runs[-1])],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert said.returncode == 0, said.stderr[-2000:]
    window = json.loads(said.stdout)["window"]
    assert window["host_plus_device_over_step"] == pytest.approx(
        1.0, abs=1e-4)
    assert window["phases_over_clock"] == pytest.approx(1.0, abs=0.02)
    assert window["assemble_children_share"] > 0.9
    assert window["host_unblocked_pct"] == pytest.approx(
        value["host_unblocked_pct.sat"])
