"""The reduction from the program's own names in a profiler trace to
per-layer metrics (benchmark/span_readers.py), on small hand-made events
with hand-worked answers, and ``--trace 2`` end to end on a tiny cell."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import readers, span_readers, spec, trace, xplane_proto

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
US = 1000

#: one device: a conditional of the sampler encloses a sort; an attention
#: call under two levels of the program's scopes; an operation under none
DEVICE = [
    (0, 10 * US, "conditional", "omni.sampler"),
    (2 * US, 6 * US, "sort_f32_8", "omni.sampler"),
    (10 * US, 5 * US, "_paged_attention_bf16_8", "omni.attn"),
    (25 * US, 5 * US, "ragged-dot_bf16_8", "omni.moe"),
    (40 * US, 10 * US, "copy_bf16_4_8", None),
]
#: the engine thread: two turns of the loop, a step with its phases in the
#: first; nothing covers 36-38 us
PHASES = [
    (0, 36 * US, "omni.loop"),
    (1 * US, 30 * US, "omni.step"),
    (2 * US, 4 * US, "omni.schedule"),
    (4 * US, 9 * US, "omni.assemble"),
    (9 * US, 11 * US, "omni.dispatch"),
    (11 * US, 18 * US, "omni.readback"),
    (18 * US, 22 * US, "omni.update"),
    (22 * US, 28 * US, "omni.record"),
    (31 * US, 35 * US, "omni.emit"),
    (38 * US, 60 * US, "omni.loop"),
    (39 * US, 59 * US, "omni.idle_wait"),
]


def test_the_innermost_program_scope_is_an_operations_group():
    assert span_readers.scope_of(
        "jit(_decode_step)/jit(sample_tokens)/omni.sampler/cond/"
        "branch_1_fun/cond/branch_1_fun/sort") == "omni.sampler"
    assert span_readers.scope_of(
        "jit(_unified_core)/omni.moe/while/body/omni.attn/dot") == "omni.attn"
    assert span_readers.scope_of("jit(_unified_core)/while/body/add") is None
    assert span_readers.instruction_name(
        "%sort.37 = (f32[64,152064]{1,0:T(8,128)}, s32[64,152064]{1,0}) "
        "sort(f32[64,152064]{1,0} %copy-done.4)") == "sort.37"


def _small_recorded_trace(tmp_path, with_names=True):
    """An ``.xplane.pb`` as the TPU's profiler writes one: a device plane
    with a modules line and an operations line, the modules' HLO in
    ``/host:metadata``, and the engine thread's line in ``/host:CPU``."""
    space = xplane_proto._message_class("XSpace")()
    hlo = xplane_proto._message_class("HloProto")()
    comp = hlo.hlo_module.computations.add(name="main")
    for name, op_name in [
            ("sort.37", "jit(f)/jit(sample_tokens)/omni.sampler/cond/sort"),
            ("_paged_attention.2", "jit(f)/omni.proj/omni.attn/pallas_call"),
            ("copy.4", ""), ("fusion.9", "")]:
        comp.instructions.add(name=name).metadata.op_name = \
            op_name if with_names else ""
    meta = space.planes.add(name="/host:metadata")
    meta.stat_metadata.add(key=1).value.name = "Hlo Proto"
    entry = meta.event_metadata.add(key=1).value
    entry.name = "jit_f(77)"
    entry.stats.add(metadata_id=1, bytes_value=hlo.SerializeToString())
    device = space.planes.add(name="/device:TPU:0")
    device.stat_metadata.add(key=1).value.name = "tf_op"
    printed = {1: "jit_f(77)",
               2: "%sort.37 = f32[8]{0} sort(f32[8]{0} %p)",
               3: "%_paged_attention.2 = bf16[8]{0} custom-call()",
               4: "%copy.4 = bf16[4,8]{1,0} copy(bf16[4,8]{0,1} %p)",
               5: "%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %p)"}
    for key, name in printed.items():
        device.event_metadata.add(key=key).value.name = name
    if with_names:  # the profiler wrote this one's scope path itself
        device.event_metadata[4].value.stats.add(
            metadata_id=1, str_value="jit(f)/omni.moe/mul")
    modules = device.lines.add(name="XLA Modules", timestamp_ns=1000)
    modules.events.add(metadata_id=1, offset_ps=0, duration_ps=40 * US * 1000)
    ops = device.lines.add(name="XLA Ops", timestamp_ns=1000)
    for mid, start, dur in [(2, 0, 10), (3, 10, 5), (5, 25, 5), (4, 45, 5)]:
        ops.events.add(metadata_id=mid, offset_ps=start * US * 1000,
                       duration_ps=dur * US * 1000)
    host = space.planes.add(name="/host:CPU")
    names = sorted({p[2] for p in PHASES})
    for n, name in enumerate(names):
        host.event_metadata.add(key=n + 1).value.name = name
    host.event_metadata.add(key=99).value.name = "$llm_engine.py:1077 step"
    other = host.lines.add(name="http", timestamp_ns=1000)
    other.events.add(metadata_id=99, offset_ps=0, duration_ps=5)
    thread = host.lines.add(name="python3", timestamp_ns=1000)
    thread.events.add(metadata_id=99, offset_ps=0, duration_ps=5)
    if with_names:
        for start, end, name in PHASES:
            thread.events.add(metadata_id=names.index(name) + 1,
                              offset_ps=start * 1000,
                              duration_ps=(end - start) * 1000)
    path = tmp_path / "plugins" / "profile" / "t" / "vm.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(space.SerializeToString())
    return path


def test_scopes_and_phases_are_read_from_a_small_recorded_trace(tmp_path):
    path = _small_recorded_trace(tmp_path)
    devices, phases = span_readers.read_planes(path)
    assert sorted(phases) == sorted((s + 1000, e + 1000, n)
                                    for s, e, n in PHASES)
    # by the module's HLO; the innermost of two scopes; by the event's own
    # stat; and the copy, outside the module's time, under none
    assert devices == {"/device:TPU:0": [
        (1000, 10 * US, "sort_f32_8", "omni.sampler"),
        (1000 + 10 * US, 5 * US, "_paged_attention_bf16_8", "omni.attn"),
        (1000 + 25 * US, 5 * US, "fusion_bf16_8", "omni.moe"),
        (1000 + 45 * US, 5 * US, "copy_bf16_4_8", None)]}
    red = span_readers.reduction(tmp_path)
    assert red["scope_s"] == pytest.approx({
        "omni.sampler": 10e-6, "omni.attn": 5e-6, "omni.moe": 5e-6,
        None: 5e-6})
    assert red["unnamed_s"] == pytest.approx({"copy_bf16_4_8": 5e-6})
    assert sum(red["idle_s"].values()) == pytest.approx(25e-6)
    assert span_readers.reduction(tmp_path) is red  # once per file
    # an older program's trace: the same operations, none of the names
    bare = _small_recorded_trace(tmp_path / "older", with_names=False)
    devices, phases = span_readers.read_planes(bare)
    assert phases == [] and all(e[3] is None
                                for e in devices["/device:TPU:0"])
    red = span_readers.reduction(tmp_path / "older")
    assert red["scope_s"] is None and red["idle_s"] is None
    assert span_readers.reduction(tmp_path / "nothing_here") is None


def test_what_the_compiler_made_takes_its_neighbours_scope():
    """``ragged-dot-none`` carries no scope path: between the router and
    the combine of ``omni.moe`` it is the MoE's; a copy between two
    different scopes, or at a module's edge, stays unnamed."""
    events = [
        (0, 1, "copy_bf16_4_8", None),              # nothing before it
        (1, 1, "fusion_router", "omni.moe"),
        (2, 1, "ragged-dot-metadata_s32_5", None),
        (3, 4, "ragged-dot-none_bf16_8", None),     # a run of two
        (7, 1, "fusion_silu", "omni.moe"),
        (8, 4, "ragged-dot-none_bf16_8", None),
        (12, 1, "fusion_combine", "omni.moe"),
        (13, 1, "copy-done_bf16_8", None),          # moe before, proj after
        (14, 1, "fusion_norm", "omni.proj"),
        (15, 1, "slice-done_bf16_8", None),         # nothing after it
    ]
    got = span_readers.fill_unscoped(events[::-1])
    assert [e[:3] for e in got] == [e[:3] for e in events]
    assert [e[3] for e in got] == [
        None, "omni.moe", "omni.moe", "omni.moe", "omni.moe", "omni.moe",
        "omni.moe", None, "omni.proj", None]


def test_scope_shares_on_small_hand_made_events():
    scope_s, unnamed_s = span_readers.scope_self_seconds(DEVICE)
    # the conditional keeps the 4 us its sort leaves: the sampler has 10
    assert scope_s == pytest.approx({"omni.sampler": 10e-6, "omni.attn": 5e-6,
                                     "omni.moe": 5e-6, None: 10e-6})
    assert unnamed_s == pytest.approx({"copy_bf16_4_8": 10e-6})


def test_idle_time_is_split_by_the_host_phase_that_covered_it():
    segments = span_readers.innermost_segments(PHASES)
    assert segments[:4] == [(0, 1 * US, "omni.loop"),
                            (1 * US, 2 * US, "omni.step"),
                            (2 * US, 4 * US, "omni.schedule"),
                            (4 * US, 9 * US, "omni.assemble")]
    assert all(a[1] <= b[0] for a, b in zip(segments, segments[1:]))
    idle_s, gaps_s = span_readers.idle_seconds(DEVICE, PHASES)
    # the device is idle 15-25 us and 30-40 us of a 50 us window
    assert idle_s == pytest.approx({
        "omni.readback": 3e-6, "omni.update": 4e-6, "omni.record": 3e-6,
        "omni.loop": (1 + 1 + 1) * 1e-6, "omni.emit": 4e-6, None: 2e-6,
        "omni.idle_wait": 1e-6})
    plain = trace.reduce_events([e[:3] for e in DEVICE])
    assert sum(idle_s.values()) == pytest.approx(
        plain["window_s"] - plain["busy_s"])
    assert gaps_s["in_omni.update"] == pytest.approx(4e-6)
    assert gaps_s["after_ragged-dot_bf16_8"] == pytest.approx(2e-6)
    # the readback of the step ends 3 us after its last device operation
    assert span_readers.readback_skew_ms(DEVICE[:3], PHASES) == \
        pytest.approx([3e-3])


def test_a_trace_without_the_programs_names_gives_none_not_zero():
    bare = {"/device:TPU:0": [e[:3] + (None,) for e in DEVICE]}
    red = span_readers.reduce_planes(bare, [])
    assert red["scope_s"] is None and red["idle_s"] is None
    assert red["gaps_s"] is None
    assert red["busy_s"] == pytest.approx(30e-6)
    assert span_readers.reduce_planes({}, PHASES) is None
    red = span_readers.reduce_planes({"/device:TPU:0": DEVICE}, PHASES)
    assert red["scope_s"][None] == pytest.approx(10e-6)
    assert red["window_s"] == pytest.approx(50e-6)


NEW_METRICS = {
    "scope_attn_share_pct": 100 * 5 / 30, "scope_moe_share_pct": 100 * 5 / 30,
    "scope_sampler_share_pct": 100 * 10 / 30, "scope_kv_write_share_pct": 0.0,
    "scope_lm_head_share_pct": 0.0, "scope_unnamed_share_pct": 100 * 10 / 30,
    "idle_scheduler_pct": 0.0, "idle_runner_pct": 100 * 3 / 50,
    "idle_engine_pct": 100 * 7 / 50, "idle_front_pct": 100 * 8 / 50,
    "idle_unnamed_pct": 100 * 2 / 50, "intake_ms_mean": 4.0,
    "device_roofline_ctx_pct": None, "attn_roofline_pct": None,
    "moe_roofline_pct": None,
}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_every_new_metric_file_resolves_by_name_and_reads(name, monkeypatch):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    metric = spec.load_json(ROOT / "benchmark/layer_metrics" / f"{name}.json")
    reader = readers.resolve(metric["reader"])
    red = span_readers.reduce_planes({"/device:TPU:0": DEVICE}, PHASES)
    monkeypatch.setattr(span_readers, "reduction", lambda: red)
    config = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 2,
              "num_key_value_heads": 1, "num_hidden_layers": 2,
              "num_experts": 4, "num_experts_per_tok": 1,
              "moe_intermediate_size": 8, "vocab_size": 16,
              "arch": "benchmark.archs.qwen3_moe"}
    step = {"prefill_tokens": 0, "prefills": 0, "decodes": 1,
            "prefill_ctx": 0, "decode_ctx": 10}
    obs = {"engine0": {"intake_ms": {"sum": 10.0, "count": 2}},
           "engine1": {"intake_ms": {"sum": 50.0, "count": 12}},
           "trace": {"busy_s": 30e-6, "window_s": 50e-6}, "steps": [step],
           "config": config, "chips": 1, "device": {"kind": "TPU v5 lite"}}
    got = reader(obs, **metric.get("params", {}))
    want = NEW_METRICS[name]
    if want is not None:
        assert got == pytest.approx(want)
        assert entry["unit"] == ("ms" if name == "intake_ms_mean" else "%")
        return
    # rooflines: the step's least time on the chip's peaks over device time
    peaks = trace.peaks_of("TPU v5 lite")
    if name == "device_roofline_ctx_pct":
        # the whole step: 2 560 operations, 2 272 bytes (worked out in
        # test_least_seconds_takes_the_larger_of_the_two_bounds)
        least, device_s = max(2560 / 197e12, 2272 / 819e9), 30e-6
    elif name == "attn_roofline_pct":
        # 64 operations a pair x 10 pairs; 32 bytes a position x 10 read,
        # and 2 layers x 8 wide queries in and out
        least, device_s = max(640 / 197e12,
                              (320 + 2 * 2 * 2 * 8) / 819e9), 5e-6
    else:
        # per layer: router 32 and one expert 192 weights a token;
        # one expert touched, the router, one token in and out
        least, device_s = max(2 * 2 * (32 + 192) / 197e12,
                              2 * (384 + 2 * (32 + 2 * 8)) / 819e9), 5e-6
    assert got == pytest.approx(100 * least / device_s)
    assert reader({**obs, "steps": []}, **metric["params"]) is None
    monkeypatch.setattr(span_readers, "reduction", lambda: None)
    if name != "device_roofline_ctx_pct":
        assert reader(obs, **metric["params"]) is None


def test_idle_layers_sum_to_the_devices_idle_share(monkeypatch):
    red = span_readers.reduce_planes({"/device:TPU:0": DEVICE}, PHASES)
    monkeypatch.setattr(span_readers, "reduction", lambda: red)
    parts = [span_readers.idle_layer_pct({}, layer) for layer in
             ("scheduler", "runner", "engine", "front", "unnamed")]
    assert sum(parts) == pytest.approx(readers.device_idle_pct(
        {"trace": trace.reduce_events([e[:3] for e in DEVICE])}))
    assert set(span_readers.PHASE_LAYER.values()) == {
        "scheduler", "runner", "engine", "front"}


# ------------------------------------------------- --trace 2, end to end
TINY = {
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 64,
    "moe_intermediate_size": 64, "num_experts": 4, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "rope_scaling": {"mrope_section": [4, 2, 2]}, "use_qk_norm": True,
    "vocab_size": 512, "serve_dtype": "float32",
    "arch": "benchmark.archs.qwen3_moe",
    "engine": {"max_num_seqs": 4, "max_num_batched_tokens": 64,
               "max_model_len": 128, "num_pages": 64, "page_size": 16},
}
TINY_MIX = {
    "loop": "closed", "clients": 2, "requests": 8, "order_seed": 0,
    "prompt_tokens": {"median": 30, "sigma": 0.3, "min": 24, "max": 40},
    "output_tokens": {"median": 5, "sigma": 0.3, "min": 3, "max": 8},
    "lead_in_s": 0.5, "stagger_s": 0.2, "request_timeout_s": 30,
    "sampling": {"temperature": 0.7, "top_p": 0.8, "top_k": 20},
}


def test_trace_2_measures_as_trace_0_and_then_traces_a_tail(tmp_path):
    """A tiny cell on the CPU (never correct, says so): the end-to-end
    numbers of a ``--trace 2`` run come from the first phase's records
    alone, the tail starts after the first phase's last completion, and
    the last line holds both kinds of metric."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "benchmark/traffic/tiny_closed.json").write_text(
        json.dumps(TINY_MIX))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][0], "name": "tiny",
                             "file": "benchmark/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny_trace2", "config": "tiny",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "thinker_chat_sat" in m.get("workloads", []):
            m["workloads"].append("tiny_trace2")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax")}

    def run(mode: str):
        done = subprocess.run(
            [sys.executable, str(ROOT / "benchmark" / "run.py"),
             "--workload", "tiny_trace2", "--seed", "5000000007",
             "--seconds", "2", "--trace", mode, "--bench-root",
             str(tmp_path), "--allow-cpu"],
            capture_output=True, text=True, timeout=300, env=env)
        assert done.returncode == 0, done.stderr[-2000:]
        return done, json.loads(done.stdout.strip().splitlines()[-1])

    done, result = run("2")
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert result["correct"] is False and result["device"]["platform"] == "cpu"
    metrics = result["metrics"]
    # both kinds: the cell's end-to-end metrics and its counters and spans
    assert {"tok_s", "setup_s", "step_ms_mean.sat", "rows_per_step_mean.sat",
            "padding_eff_pct", "window_compiles"} <= set(metrics)
    tail = result["breakdown"]["tail"]
    assert tail["failed"] == 0 and tail["requests"] > 0
    assert tail["traced_s"] == pytest.approx(3.0, abs=0.5)
    # what the run kept: the first phase's records and window
    runs = sorted((ROOT / ".benchmark_cache" / "runs").glob(
        "tiny_trace2-seed5000000007-trace2-*.json"))
    kept = json.loads(runs[-1].read_text())
    for path in runs:
        path.unlink()
    from benchmark import e2e

    w0, w1 = kept["window"]
    assert metrics["tok_s"]["value"] == pytest.approx(
        e2e.tok_s(kept["records"], w0, w1))
    assert result["attempted"] == sum(1 for r in kept["records"]
                                      if w0 <= r["sent"] < w1)
    # drained first: what was in flight at the window's end ran to its
    # end before the tail's lead-in began
    last_done = max(r["done"] for r in kept["records"])
    assert last_done >= w1
    assert kept["tail"]["window"][0] - TINY_MIX["lead_in_s"] > last_done
    assert min(r["sent"] for r in kept["tail"]["records"]) > last_done
    assert all(s["decodes"] + s["prefills"] > 0 for s in kept["steps"])
    assert "first start and stop" in done.stdout
    assert done.stdout.index("first start and stop") < \
        done.stdout.index("tail: traced")
    # the trace is deleted once it is reduced
    assert not (ROOT / ".benchmark_cache/trace/tiny_trace2").exists()
    # --trace 0 is as it was: the end-to-end metrics and nothing of a tail
    done0, plain = run("0")
    assert set(plain) == {"correct", "attempted", "failed", "metrics",
                          "device"}
    assert set(plain["metrics"]) == {"tok_s", "setup_s"}
    assert "tail:" not in done0.stdout and "profil" not in done0.stdout
    for path in (ROOT / ".benchmark_cache" / "runs").glob(
            "tiny_trace2-seed5000000007-trace0-*.json"):
        path.unlink()
