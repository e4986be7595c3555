"""The cell benchmark's own tests: fast, on the CPU, no chip.

They hold the yardstick still: the arithmetic of every end-to-end metric
and of the trace reduction is checked on small records with hand-worked
answers, the traffic is shown to offer the same work under every seed, and
the plain reference is compared with the engine at a tiny width.
"""

from __future__ import annotations

import json
import re
import shutil
import subprocess
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

from benchmark import e2e, readers, spec, trace, traffic
from benchmark.archs import qwen3_moe

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
TRAFFIC = sorted(p.stem for p in (ROOT / "benchmark" / "traffic").glob("*.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
#: every configuration file, also one that no cell uses yet
CONFIG_FILES = sorted((ROOT / "benchmark" / "configs").glob("*.json"))


# ------------------------------------------------------------ the contract
def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer",
                          "trace_in_run"}
    # the per-layer metrics are traced in the run that measures (--trace 2)
    assert BENCH["trace_in_run"] is True
    assert 1 <= BENCH["run_seconds"] <= 51
    e2e_names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e_names
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["name"] == "setup_s" or m["name"] in e2e.METRICS
    layers = set()
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        layers.add(m["layer"])
        # the metric it moves is reported in every cell where it is
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", CELLS)) <= set(
            moved.get("workloads", CELLS)), m["name"]
    names = ([m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + CELLS + [c["name"] for c in BENCH["configs"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(CELLS)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(len(CELLS) // 4, 1)
    assert {w["config"] for w in BENCH["workloads"]} == {
        c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_config_file_holds_the_published_numbers(path):
    """Every number of the catalog's config under the same key, but for the
    keys listed as reduced; no width is among those.  The file states its
    own source and cuts, and its entry in BENCHMARK.json says the same."""
    if not CATALOG.exists():
        pytest.skip("the catalog of public architectures is not here")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
    ours = spec.load_json(path)
    published = next(r for r in rows if r["source_url"] == ours["source"])
    for key in ours["reduced"]:
        assert not re.search(r"(_dim|_rank|_size|per_tok)$", key), key
    for key, value in published["config"].items():
        if key not in ours["reduced"]:
            assert ours[key] == value, key
    rel = str(path.relative_to(ROOT))
    assert rel.startswith(tuple(BENCH["paths"]))
    for entry in BENCH["configs"]:
        if entry["file"] == rel:
            assert entry["source"] == ours["source"]
            assert entry["reduced"] == list(ours["reduced"])


# ------------------------------------------------------------ driven by data
@pytest.mark.parametrize("cell", CELLS)
def test_every_file_of_a_cell_resolves_by_name(cell):
    got = spec.resolve_cell(cell)
    assert got["config"]["engine"]["max_num_seqs"] > 0
    assert got["traffic"]["loop"] in ("closed", "open")
    assert {m["name"] for m in got["end_to_end"]} >= {"setup_s"}
    assert len(got["end_to_end"]) >= 2 and got["per_layer"]
    for m in got["per_layer"]:
        assert callable(readers.resolve(m["reader"])), m["name"]


def test_a_cell_added_as_json_alone_is_found(tmp_path):
    """A later PR adds a configuration, a mix, a cell and a per-layer metric
    as new files and new entries, and edits no file that is there."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    first = bench["configs"][0]
    config = spec.load_json(ROOT / first["file"])
    config["engine"]["max_num_seqs"] = 32
    (tmp_path / "benchmark/configs/new-config.json").write_text(
        json.dumps(config))
    mix = spec.load_json(ROOT / "benchmark/traffic" / f"{TRAFFIC[0]}.json")
    (tmp_path / "benchmark/traffic/new_mix.json").write_text(json.dumps(mix))
    (tmp_path / "benchmark/layer_metrics/new_share_pct.json").write_text(
        json.dumps({"reader": "device_op_share_pct",
                    "params": {"patterns": ["fusion*"]}}))
    bench["configs"].append({**first, "name": "new-config",
                             "file": "benchmark/configs/new-config.json"})
    bench["workloads"].append({"name": "new_cell", "config": "new-config",
                               "traffic": "new_mix", "chips": 1, "why": "x"})
    bench["per_layer"].append({
        "name": "new_share_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels", "moves": "setup_s",
        "workloads": ["new_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    got = spec.resolve_cell("new_cell", tmp_path)
    assert got["config"]["engine"]["max_num_seqs"] == 32
    assert got["traffic"] == mix
    assert [m["name"] for m in got["end_to_end"]] == ["setup_s"]
    (metric,) = got["per_layer"]
    obs = {"trace": {"self_s": {"fusion_bf16_8": 1.0, "sort_f32_8": 3.0}}}
    assert readers.resolve(metric["reader"])(obs, **metric["params"]) == 25.0
    with pytest.raises(SystemExit):
        spec.resolve_cell("no_such_cell", tmp_path)


# ------------------------------------------------------------------ traffic
@pytest.mark.parametrize("mix", TRAFFIC)
def test_every_seed_is_offered_the_same_work_in_the_same_order(mix):
    tr = spec.load_json(ROOT / "benchmark/traffic" / f"{mix}.json")
    a = traffic.requests_in_order(tr, 30.0)
    assert a == traffic.requests_in_order(tr, 30.0)
    n = len(a)
    assert sorted(r["prompt_tokens"] for r in a) == sorted(
        traffic.quantile_lengths(tr["prompt_tokens"], n))
    assert sorted(r["output_tokens"] for r in a) == sorted(
        traffic.quantile_lengths(tr["output_tokens"], n))
    # stratified: every eight in a row hold one of each eighth
    key = "output_tokens" if tr.get("order_by") == "output_tokens" \
        else "prompt_tokens"
    ranked = sorted(r[key] for r in a)
    for i in range(0, n - n % 8, 8):
        group = sorted(r[key] for r in a[i:i + 8])
        for b, value in enumerate(group):
            assert ranked[max(n * b // 8 - 1, 0)] <= value <= \
                ranked[min(n * (b + 1) // 8, n - 1)]
    # the seed changes what is said, not how much or when
    b1 = traffic.body(tr, 1, 0, a[0], 18)
    b2 = traffic.body(tr, 3_000_000_019, 0, a[0], 18)
    assert b1["messages"] != b2["messages"] and b1["seed"] != b2["seed"]
    assert len(b1["messages"][0]["content"]) == len(
        b2["messages"][0]["content"])
    if tr["loop"] == "open":
        assert n == round(tr["rate_per_s"] * (tr["lead_in_s"] + 30.0))
        assert a[-1]["due_s"] == pytest.approx(tr["lead_in_s"] + 30.0)
        gaps = [y["due_s"] - x["due_s"] for x, y in zip(a, a[1:])]
        assert min(gaps) > 0


def test_quantiles_and_bodies_are_the_drawn_sizes():
    dist = {"median": 100, "sigma": 0.5, "min": 50, "max": 150}
    lengths = traffic.quantile_lengths(dist, 4)
    # the 1/8, 3/8, 5/8, 7/8 quantiles of a log-normal around 100, cut
    assert lengths == [56, 85, 117, 150]
    gaps = traffic.quantile_gaps(2.0, 100)
    assert sum(gaps) / len(gaps) == pytest.approx(0.5)
    tr = {"sampling": {"temperature": 0.7}}
    req = {"prompt_tokens": 40, "output_tokens": 9}
    body = traffic.body(tr, 5, 3, req, template_tokens=18)
    assert len(body["messages"][0]["content"].encode()) == 22
    assert body["max_tokens"] == 9 and body["stream"] and body["ignore_eos"]
    assert body == traffic.body(tr, 5, 3, req, 18)
    assert body["messages"] != traffic.body(tr, 5, 4, req, 18)["messages"]
    with pytest.raises(ValueError):
        traffic.body(tr, 5, 3, {"prompt_tokens": 18, "output_tokens": 1}, 18)


# --------------------------------------------------------- end-to-end metrics
def _rec(sent, done, prompt, output, due=None, status="ok"):
    r = {"sent": sent, "done": done, "events": [done], "status": status,
         "prompt_tokens": prompt, "output_tokens": output}
    if due is not None:
        r["due"] = due
    return r


def test_tok_s_lays_work_on_the_clock_as_it_is_done():
    records = [
        _rec(0.0, 20.0, 100, 40),    # prompt laid on 0..2; half its output in
        _rec(9.5, 14.5, 300, 50),    # prompt on 9.5..10: none in; 9/10 of output
        _rec(18.0, 28.0, 200, 80),   # prompt on 18..19: all in; 1/5 of output
        _rec(19.8, 21.8, 500, 10),   # prompt on 19.8..20: all in; 1/10 of output
        _rec(21.0, 25.0, 500, 10),   # wholly after
        _rec(12.0, 13.0, 999, 99, status="http_500"),
    ]
    # window 10..20: 20 + 45 + (200 + 16) + (500 + 1) = 782 tokens in 10 s
    assert e2e.tok_s(records, 10.0, 20.0) == pytest.approx(78.2)
    assert e2e.tok_s([], 10.0, 20.0) is None


def test_open_loop_tails_are_timed_from_the_due_instant():
    records = [_rec(sent=d + 0.001, done=d + lat, prompt=10, output=out, due=d)
               for d, lat, out in [(1.0, 0.1, 10), (2.0, 0.2, 10),
                                   (3.0, 0.3, 10), (4.0, 0.4, 10),
                                   (5.0, 0.5, 10), (6.0, 0.6, 10),
                                   (7.0, 0.7, 10), (8.0, 0.8, 10),
                                   (9.0, 0.9, 10), (9.5, 1.0, 100)]]
    records.append(_rec(sent=20.0, done=29.0, prompt=10, output=1, due=20.0))
    # ten requests due in 0..10; nearest rank 0.9 * 10 -> the ninth
    assert e2e.ttft_p90_ms(records, 0.0, 10.0) == pytest.approx(900.0)
    # ms per token: 10, 20, ..., 90 and 1000 / 100 = 10 -> the fifth of
    # [10, 10, 20, 30, 40, ...] is 40
    assert e2e.ms_per_token_p50(records, 0.0, 10.0) == pytest.approx(40.0)
    assert e2e.nearest_rank([3.0, 1.0, 2.0], 0.5) == 2.0
    late = readers.gen_late_p99_ms({"records": records,
                                    "window": (0.0, 10.0)})
    assert late == pytest.approx(1.0)


# ------------------------------------------------------------ trace reduction
def test_trace_reduction_on_a_small_recorded_trace():
    assert trace.group_key(
        "%sort.37 = (f32[64,152064]{1,0:T(8,128)S(1)}, s32[64,152064]{1,0}) "
        "sort(f32[64,152064]{1,0} %copy-done.4, s32[64,152064] %iota.32), "
        "dimensions={1}") == "sort_f32_64_152064"
    assert trace.group_key(
        "%_ragged_attention.7 = bf16[4,256,64,128]{3,2,1,0:T(8,128)(2,1)} "
        "custom-call(s32[256]{0} %copy-done.66)") == \
        "_ragged_attention_bf16_4_256_64_128"
    assert trace.group_key("%copy-done.1 = u32[64]{0:T(128)S(1)} copy-done("
                           "(u32[64]{0}, u32[]{:S(2)}) %copy-start.1)") == \
        "copy-done_u32_64"
    assert trace.group_key("%fusion.3") == "fusion"
    us = 1000
    events = [
        (0, 10 * us, "cond"),            # encloses the sort below
        (2 * us, 6 * us, "sort_f32_8"),
        (10 * us, 5 * us, "attn"),       # back to back: no gap
        (25 * us, 5 * us, "ragged-dot_bf16_8"),   # 10 us idle after attn
        (40 * us, 10 * us, "attn"),      # 10 us idle after ragged-dot
    ]
    got = trace.reduce_events(events)
    assert got["busy_s"] == pytest.approx(30e-6)
    assert got["window_s"] == pytest.approx(50e-6)
    assert got["self_s"] == pytest.approx({
        "cond": 4e-6, "sort_f32_8": 6e-6, "attn": 15e-6,
        "ragged-dot_bf16_8": 5e-6})
    assert got["gaps_s"] == pytest.approx({
        "after_attn": 10e-6, "after_ragged-dot_bf16_8": 10e-6})
    obs = {"trace": got}
    assert readers.device_idle_pct(obs) == pytest.approx(40.0)
    assert readers.device_op_share_pct(obs, ["sort*"]) == pytest.approx(20.0)
    assert readers.device_op_share_pct(obs, ["ragged-dot*", "attn"]) == \
        pytest.approx(100 * 20 / 30)
    assert readers.collective_share_pct(obs) == 0.0
    # collectives by printed name: an all-reduce made by lax.psum is
    # printed as %psum.N
    across = {"trace": {"self_s": {"psum_bf16_64_2048": 1.5,
                                   "all-reduce_bf16_64_2048": 0.5,
                                   "all-gather-start_bf16_64_38016": 0.5,
                                   "all-gather_bf16_64_152064": 0.5,
                                   "fusion_bf16_64_2048": 7.0}}}
    assert readers.collective_share_pct(across) == pytest.approx(30.0)
    assert trace.top(got["self_s"], 2) == [["attn", pytest.approx(15e-6)],
                                           ["sort_f32_8", pytest.approx(6e-6)]]
    assert trace.reduce_events([])["busy_s"] == 0.0
    assert readers.device_idle_pct({"trace": None}) is None
    with pytest.raises(SystemExit):
        trace.peaks_of("a chip nobody listed")


def test_least_seconds_takes_the_larger_of_the_two_bounds():
    config = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 2,
              "num_key_value_heads": 1, "num_hidden_layers": 2,
              "num_experts": 4, "num_experts_per_tok": 1,
              "moe_intermediate_size": 8, "vocab_size": 16}
    costs = qwen3_moe.model_costs(config)
    # per layer: attention 8*8 + 2*8*4 + 8*8 = 192, router 32, expert 192
    assert costs["flops_per_token"] == 2.0 * 2 * (192 + 32 + 192)
    assert costs["attn_flops_per_pair"] == 4.0 * 8 * 2
    assert costs["head_flops_per_row"] == 2.0 * 8 * 16
    assert costs["fixed_weight_bytes"] == 2 * (2 * (192 + 32) + 128)
    assert costs["expert_bytes"] == 2 * 192
    assert costs["kv_bytes_per_pos"] == 2 * 2 * 2 * 4
    step = {"prefill_tokens": 0, "decodes": 1, "prefills": 0}
    flops = 1664 + 64 * 10 + 256
    nbytes = 1152 + 2 * 1.0 * 384 + 32 * (10 + 1)
    assert qwen3_moe.step_cost(step, config, 0.0, 10.0) == (flops, nbytes)
    assert qwen3_moe.step_cost({**step, "decodes": 0}, config, 0.0,
                               10.0) is None
    slow_memory = {"bf16_flop_s": 1e12, "hbm_byte_s": 1.0}
    slow_compute = {"bf16_flop_s": 1.0, "hbm_byte_s": 1e12}
    cost = (flops, nbytes)
    assert trace.roof_seconds(cost, slow_memory) == pytest.approx(nbytes)
    assert trace.least_seconds([cost, None], slow_memory) == \
        pytest.approx(nbytes)
    assert trace.least_seconds([cost, cost], slow_compute, chips=2) == \
        pytest.approx(flops)


def test_counter_readers_take_the_window_between_two_snapshots():
    obs = {
        "engine0": {"step_ms": {"sum": 100.0, "count": 10},
                    "padding": {"useful_tokens_total": 50,
                                "padded_tokens_total": 100}},
        "engine1": {"step_ms": {"sum": 400.0, "count": 40},
                    "padding": {"useful_tokens_total": 350,
                                "padded_tokens_total": 600}},
        "steps": [{"prefills": 1, "decodes": 3}, {"prefills": 0, "decodes": 8},
                  {"prefills": 0, "decodes": 0}],
        "compiles": 0,
    }
    assert readers.histogram_mean(obs, "step_ms") == pytest.approx(10.0)
    assert readers.padding_eff_pct(obs) == pytest.approx(60.0)
    assert readers.rows_per_step_mean(obs) == pytest.approx(6.0)
    assert readers.window_compiles(obs) == 0.0
    obs["engine1"] = obs["engine0"]
    assert readers.histogram_mean(obs, "step_ms") is None


# -------------------------------------------------------- the load generator
class _SSE(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.server.bodies.append(body)
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        for payload in ('{"choices": [{"delta": {"content": "ab"}}]}',
                        "[DONE]"):
            data = f"data: {payload}\n\n".encode()
            self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
        self.wfile.write(b"0\r\n\r\n")


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_load_generator_speaks_sse_over_persistent_connections(loop):
    server = ThreadingHTTPServer(("127.0.0.1", 0), _SSE)
    server.bodies = []
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    tr = {"loop": loop, "clients": 2, "requests": 4, "connections": 3,
          "rate_per_s": 20.0, "lead_in_s": 0.2, "stagger_s": 0.1,
          "request_timeout_s": 10,
          "prompt_tokens": {"median": 30, "sigma": 0.3, "min": 20, "max": 40},
          "output_tokens": {"median": 5, "sigma": 0.3, "min": 2, "max": 9},
          "order_seed": 0, "sampling": {"temperature": 0.7}}
    job = {"host": "127.0.0.1", "port": server.server_address[1],
           "traffic": tr, "seed": 7, "seconds": 0.8, "template_tokens": 18}
    gen = subprocess.Popen(
        [sys.executable, str(ROOT / "benchmark" / "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        gen.stdin.write(json.dumps(job) + "\n")
        gen.stdin.flush()
        ready = json.loads(gen.stdout.readline())
        import time

        t0 = time.monotonic() + 0.1
        gen.stdin.write(json.dumps({"t0": t0}) + "\n")
        gen.stdin.flush()
        records = json.loads(gen.stdout.readline())["records"]
        assert gen.wait(timeout=20) == 0
    finally:
        gen.kill()
        server.shutdown()
        server.server_close()
        thread.join(timeout=5)
    assert len(ready["connect_ms"]) == (2 if loop == "closed" else 3)
    assert records and all(r["status"] == "ok" for r in records)
    assert all(r["sent"] <= r["events"][0] <= r["done"] for r in records)
    assert len(server.bodies) == len(records)
    sizes = sorted((len(b["messages"][0]["content"]) + 18, b["max_tokens"])
                   for b in server.bodies)
    assert sizes == sorted((r["prompt_tokens"], r["output_tokens"])
                           for r in records)
    if loop == "open":
        assert len(records) == 20  # 20 requests/s over 0.2 + 0.8 s
        assert all(r["sent"] >= r["due"] for r in records)
        assert max(r["due"] for r in records) == pytest.approx(t0 + 1.0)


# ------------------------------------------------ the reference and the gate
TINY = {
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 64,
    "moe_intermediate_size": 64, "num_experts": 4, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "rope_scaling": {"mrope_section": [4, 2, 2]}, "use_qk_norm": True,
    "vocab_size": 512, "serve_dtype": "float32",
    "arch": "benchmark.archs.qwen3_moe",
}


def test_reference_agrees_with_the_engine_at_a_tiny_width():
    """Prefill gives the first token's logprob, decode through the paged
    cache the rest; both must be the plain forward's log-softmax.  float32
    on both sides, so the tolerance is float32 rounding through two
    layers."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import serve
    from vllm_omni_tpu.engine import EngineConfig, LLMEngine
    from vllm_omni_tpu.sampling_params import SamplingParams

    params, cfg, _ = serve.model_factory(TINY, seed=3, timings={})
    engine = LLMEngine(params, cfg, EngineConfig(
        num_pages=64, page_size=16, max_model_len=128, max_num_seqs=4,
        max_num_batched_tokens=128, dtype=jnp.float32))
    prompt = np.random.default_rng(0).integers(3, 512, 21).tolist()
    (out,) = engine.generate([prompt], SamplingParams(
        temperature=0.0, max_tokens=5, ignore_eos=True, logprobs=0))
    ids = [int(t) for t in out.outputs[0].token_ids]
    served = [e["logprob"] for e in out.outputs[0].logprobs]
    ref = qwen3_moe.reference_logprobs(params, TINY, prompt + ids[:-1],
                                       len(prompt) + 4)
    assert ref.shape == (len(prompt) + 4, 512)
    want = [float(ref[len(prompt) - 1 + i, t]) for i, t in enumerate(ids)]
    assert served == pytest.approx(want, abs=2e-4)
    # greedy: the served token is the reference's most likely one
    assert ids[0] == int(jnp.argmax(ref[len(prompt) - 1]))
    # the last n positions are the tail of all of them
    tail = qwen3_moe.reference_logprobs(params, TINY, prompt + ids[:-1], 5)
    assert tail.shape == (5, 512)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(ref[-5:]))


def test_published_sizes_become_the_programs_config():
    from vllm_omni_tpu.models.qwen3_omni.thinker import QWEN3_OMNI_THINKER_30B

    config = spec.load_json(ROOT / BENCH["configs"][0]["file"])
    cfg = spec.arch_of(config).program_config(config)
    assert cfg.num_layers == config["num_hidden_layers"]
    assert cfg.hidden_size == QWEN3_OMNI_THINKER_30B.hidden_size == 2048
    drifted = {**config, "head_dim": 64}
    with pytest.raises(SystemExit):
        qwen3_moe.program_config(drifted)
    with pytest.raises(SystemExit):
        spec.arch_of({"name": "a file that names no architecture"})


def test_the_command_refuses_a_cell_without_a_tpu():
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert "no TPU" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())
