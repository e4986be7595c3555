"""The harness names no model: a configuration file names its architecture
module (``"arch"``), and a second architecture arrives as new files alone.
"""

from __future__ import annotations

import hashlib
import inspect
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import readers, span_readers, spec, trace

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
#: what every architecture module offers (benchmark/archs/qwen3_moe.py)
CONTRACT = ("program_config", "init_weights", "param_specs",
            "reference_logprobs", "step_cost", "attn_cost", "moe_cost")
#: the files that must stay closed to a model's keys, constants and imports
HARNESS = ("run.py", "serve.py", "spec.py", "trace.py", "span_readers.py",
           "readers.py", "e2e.py", "loadgen.py", "traffic.py")
MODEL_WORDS = re.compile(
    r"num_key_value_heads|moe_intermediate_size|mrope|TransformerConfig|"
    r"init_params|qwen|num_experts|head_dim|hidden_size|kv_lora|"
    r"benchmark\.archs\.\w|from benchmark\.archs import \w", re.IGNORECASE)

STEP = {"prefill_tokens": 96, "prefills": 2, "decodes": 30,
        "prefill_ctx": 96 * 300, "decode_ctx": 30 * 400}


@pytest.mark.parametrize("name", HARNESS)
def test_the_harness_names_no_model(name):
    text = (ROOT / "benchmark" / name).read_text()
    found = sorted({m.group(0) for m in MODEL_WORDS.finditer(text)})
    assert not found, (f"benchmark/{name} names a model's key, constant or "
                       f"module: {found}; it belongs in the configuration's "
                       "arch module")


@pytest.mark.parametrize(
    "path", sorted((ROOT / "benchmark" / "configs").glob("*.json")),
    ids=lambda p: p.stem)
def test_every_configuration_names_an_architecture_that_keeps_the_contract(
        path):
    config = spec.load_json(path)
    arch = spec.arch_of(config)
    for name in CONTRACT:
        assert callable(getattr(arch, name, None)), name
    assert list(inspect.signature(arch.reference_logprobs).parameters)[:5] == [
        "params", "config", "token_ids", "last_n", "tp"]
    # the program's configuration at the file's depth, from its own keys
    assert arch.program_config(config).num_layers == \
        config["num_hidden_layers"]
    # counts: positive, and a model twice as deep costs twice the layers'
    # part (all of attention's and the sparse MLP's; a step also has a head)
    deep = {**config, "num_hidden_layers": 2 * config["num_hidden_layers"]}
    for fn in (arch.attn_cost, arch.moe_cost):
        cost, twice = fn(STEP, config), fn(STEP, deep)
        if cost is None:
            continue
        assert cost[0] > 0 and cost[1] > 0
        assert twice == pytest.approx((2 * cost[0], 2 * cost[1]))
    flops, nbytes = arch.step_cost(STEP, config, 300.0, 400.0)
    flops2, nbytes2 = arch.step_cost(STEP, deep, 300.0, 400.0)
    assert 0 < flops < flops2 < 2 * flops
    assert 0 < nbytes < nbytes2 < 2 * nbytes
    assert arch.step_cost({**STEP, "prefill_tokens": 0, "prefills": 0,
                           "decodes": 0}, config, 0.0, 0.0) is None
    # the step holds its attention and its sparse MLP
    for part in (arch.attn_cost(STEP, config), arch.moe_cost(STEP, config)):
        assert part is None or part[0] < flops
    # the check's lengths: the file's, over the default every cell had
    from benchmark import serve

    assert serve.CHECK_LENGTHS == {"prompt_tokens": 40, "new_tokens": 8}
    lengths = config.get("reference_check", {})
    assert set(lengths) <= set(serve.CHECK_LENGTHS)
    assert all(isinstance(v, int) and v > 0 for v in lengths.values())


# --------------------------------------------- an architecture as new files
DENSE = {
    "arch": "tests.benchmark.dense_arch", "name": "dense-tiny",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "vocab_size": 384,
    "serve_dtype": "float32", "tensor_parallel_size": 1,
    # past one page of the cache, and more decoded tokens than the default
    "reference_check": {"prompt_tokens": 23, "new_tokens": 11},
    # a float32 cache beside float32 weights: the check is then as tight
    # as float32 rounding through two layers
    "engine": {"max_num_seqs": 4, "max_num_batched_tokens": 64,
               "max_model_len": 128, "num_pages": 64, "page_size": 16,
               "dtype": "float32"},
}
DENSE_MIX = {
    "loop": "closed", "clients": 2, "requests": 8, "order_seed": 0,
    "prompt_tokens": {"median": 30, "sigma": 0.3, "min": 24, "max": 40},
    "output_tokens": {"median": 5, "sigma": 0.3, "min": 3, "max": 8},
    "lead_in_s": 0.5, "stagger_s": 0.2, "request_timeout_s": 30,
    "sampling": {"temperature": 0.7, "top_p": 0.8, "top_k": 20},
}


def _digests(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_an_architecture_added_as_new_files_alone_is_served_and_checked(
        tmp_path, monkeypatch):
    """What a later ``model_config`` PR does: a module (here
    ``tests/benchmark/dense_arch.py``, outside ``benchmark/``), a
    configuration naming it, a cell.  No file of ``benchmark/`` is edited,
    and the cell is built, served, checked against its own reference at its
    own lengths and given its own counts."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path / "benchmark")
    (tmp_path / "benchmark/configs/dense-tiny.json").write_text(
        json.dumps(DENSE))
    (tmp_path / "benchmark/traffic/dense_closed.json").write_text(
        json.dumps(DENSE_MIX))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "dense-tiny", "source": "tests/benchmark/dense_arch.py",
        "file": "benchmark/configs/dense-tiny.json", "reduced": [],
        "why": "a dense decoder: the second architecture"})
    bench["workloads"].append({"name": "dense_cell", "config": "dense-tiny",
                               "traffic": "dense_closed", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "thinker_chat_sat" in m.get("workloads", []):
            m["workloads"].append("dense_cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax")}
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--workload", "dense_cell", "--seed", "2700000011", "--seconds", "2",
         "--trace", "0", "--bench-root", str(tmp_path), "--allow-cpu"],
        capture_output=True, text=True, timeout=300, env=env)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    # served: every request of the window came back, counted token by token
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"tok_s", "setup_s"}
    said = [line for line in done.stdout.splitlines() if "NOT CORRECT" in line]
    assert len(said) == 1 and "not a TPU" in said[0], said
    # checked: the served float32 tokens against the module's own reference,
    # at the lengths its configuration file asks for
    runs = sorted((ROOT / ".benchmark_cache" / "runs").glob(
        "dense_cell-seed2700000011-trace0-*.json"))
    kept = json.loads(runs[-1].read_text())
    for path in runs:
        path.unlink()
    check = kept["reference"]
    assert check["ok"] is True and len(check["served"]) == 11
    assert check["max_abs_diff"] < 2e-4
    # counted: the readers divide what this module counts, not another's
    from tests.benchmark import dense_arch

    peaks = trace.peaks_of("TPU v5 lite")
    obs = {"trace": {"busy_s": 1e-3}, "steps": [STEP], "config": DENSE,
           "chips": 1, "device": {"kind": "TPU v5 lite"}}
    whole = trace.roof_seconds(
        dense_arch.step_cost(STEP, DENSE, 300.0, 400.0), peaks)
    assert span_readers.roofline_ctx_pct(obs, "step") == pytest.approx(
        100 * whole / 1e-3)
    assert readers.device_roofline_pct({**obs, "traffic": DENSE_MIX}) > 0
    monkeypatch.setattr(span_readers, "reduction", lambda: {
        "scope_s": {"omni.attn": 2e-4, "omni.mlp": 3e-4}})
    assert span_readers.roofline_ctx_pct(obs, "attn") == pytest.approx(
        100 * trace.roof_seconds(dense_arch.attn_cost(STEP, DENSE), peaks)
        / 2e-4)
    # a dense decoder has no sparse MLP: nothing to read, not 0
    assert span_readers.roofline_ctx_pct(obs, "moe") is None
    # and nothing that was there was edited
    after = _digests(tmp_path / "benchmark")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/dense-tiny.json", "traffic/dense_closed.json"]


# ------------------------------------- the check fails what it has to fail
class _Served:
    """``state.collect`` of the server, over an engine built here; it can
    alter one token where it is produced."""

    def __init__(self, engine, alter=None):
        self.engine, self.alter = engine, alter

    def collect(self, prompt, sampling):
        from vllm_omni_tpu.sampling_params import SamplingParams

        (out,) = self.engine.generate([prompt["prompt_token_ids"]],
                                      SamplingParams(**sampling))
        if self.alter is not None:
            ids = out.outputs[0].token_ids
            ids[self.alter] = (int(ids[self.alter]) + 1) % 384
        return [out]


@pytest.mark.parametrize("alter", [None, 0, 6])
def test_reference_check_passes_the_served_tokens_and_fails_an_altered_one(
        alter):
    """The check of a run, driven as ``run.py`` drives it (the lengths from
    the configuration file, the reference from its ``arch``), over the
    engine's own tokens and over a token altered where it is produced: the
    first comes from prefill, the seventh through the paged cache."""
    import jax.numpy as jnp

    from benchmark import serve
    from vllm_omni_tpu.engine import EngineConfig, LLMEngine

    params, cfg, _ = serve.model_factory(DENSE, seed=11, timings={})
    engine = LLMEngine(params, cfg, EngineConfig(
        num_pages=64, page_size=16, max_model_len=128, max_num_seqs=4,
        max_num_batched_tokens=128, dtype=jnp.float32))
    check = serve.reference_check(_Served(engine, alter), engine, DENSE,
                                  seed=2700000017)
    assert len(check["served"]) == DENSE["reference_check"]["new_tokens"]
    if alter is None:
        assert check["ok"] is True and check["max_abs_diff"] < 2e-4
    else:
        # the reference scores the token that was put there: far less
        # likely than the one the engine chose
        assert check["ok"] is False
        assert check["max_abs_diff"] > serve.LOGPROB_TOL


# --------------------------------------------------- a cell across chips
TINY_TP4 = {
    "arch": "benchmark.archs.qwen3_moe", "name": "tiny-tp4",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 8,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 64,
    "moe_intermediate_size": 64, "num_experts": 4, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "rope_scaling": {"mrope_section": [4, 2, 2]}, "use_qk_norm": True,
    "vocab_size": 512, "serve_dtype": "float32", "tensor_parallel_size": 4,
    "engine": {"max_num_seqs": 4, "max_num_batched_tokens": 64,
               "max_model_len": 128, "num_pages": 64, "page_size": 16,
               "dtype": "float32"},
}


def test_a_cell_across_four_chips_arrives_as_data_alone(tmp_path):
    """A four-chip cell (the one PERF.md keeps for later) added as files
    and entries alone: under the ``tok_s`` and the ``.sat`` metrics the
    saturated chat cell lists, with a ``collective_share_pct`` of its own.
    Rehearsed at a tiny width on four virtual CPU devices: tensor-parallel
    weights made in the sharded layout, the reference putting the fused
    ``gate_up`` columns back in order, and a last line under those names."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path / "benchmark")
    (tmp_path / "benchmark/configs/tiny-tp4.json").write_text(
        json.dumps(TINY_TP4))
    (tmp_path / "benchmark/traffic/dense_closed.json").write_text(
        json.dumps(DENSE_MIX))
    (tmp_path / "benchmark/layer_metrics/collective_share_pct.json"
     ).write_text(json.dumps({"reader": "collective_share_pct"}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][0], "name": "tiny-tp4",
                             "file": "benchmark/configs/tiny-tp4.json"})
    bench["workloads"].append({"name": "tiny_tp4_cell", "config": "tiny-tp4",
                               "traffic": "dense_closed", "chips": 4,
                               "why": "x"})
    listed = [m for m in bench["end_to_end"] + bench["per_layer"]
              if "thinker_chat_sat" in m.get("workloads", [])]
    assert "tok_s" in {m["name"] for m in listed}
    for m in listed:
        m["workloads"].append("tiny_tp4_cell")
    bench["per_layer"].append({
        "name": "collective_share_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "collectives", "moves": "tok_s",
        "workloads": ["tiny_tp4_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--workload", "tiny_tp4_cell", "--seed", "2700000029", "--seconds",
         "2", "--trace", "2", "--bench-root", str(tmp_path), "--allow-cpu"],
        capture_output=True, text=True, timeout=300, env=env)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4 and result["failed"] == 0
    # the end-to-end metrics and, of the per-layer ones, those that read
    # counters (a CPU trace has no device plane for the others to read)
    assert {"tok_s", "setup_s", "rows_per_step_mean.sat", "window_compiles",
            "step_ms_mean.sat", "padding_eff_pct"} == set(result["metrics"])
    runs = sorted((ROOT / ".benchmark_cache" / "runs").glob(
        "tiny_tp4_cell-seed2700000029-trace2-*.json"))
    kept = json.loads(runs[-1].read_text())
    for path in runs:
        path.unlink()
    assert kept["reference"]["ok"] is True
    assert kept["reference"]["max_abs_diff"] < 2e-4
    after = _digests(tmp_path / "benchmark")
    assert {k: after[k] for k in before} == before
