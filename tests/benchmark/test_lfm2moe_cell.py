"""The LFM2-8B-A1B architecture module, its configuration and its cell's
metric files: the published numbers and the cut, the counts on a hand-made
step, a program constant that drifted, and a tiny cell run on the CPU
through a COPY's ``benchmark/run.py`` (so its cache and its trace are its
own: the workers of a parallel run share the checkout's) whose last line
holds the new metrics."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import readers, spec
from benchmark.archs import lfm2_moe as arch

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
CELL = "lfm2_assist_sat"
CONFIG = spec.load_json(ROOT / "benchmark/configs/lfm2moe-l12.json")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
#: a full decode batch beside one chunk
STEP = {"prefill_tokens": 2040, "prefills": 1, "decodes": 255,
        "prefill_ctx": 2040 * 1500, "decode_ctx": 255 * 1000,
        "state_rows": 256}

TINY = {
    "arch": "benchmark.archs.lfm2_moe", "name": "lfm2-tiny",
    "model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
    "use_expert_bias": True, "vocab_size": 384, "hidden_size": 256,
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 192,
    "moe_intermediate_size": 64, "num_experts": 8, "num_experts_per_tok": 2,
    "num_dense_layers": 2, "norm_topk_prob": True, "norm_eps": 1e-5,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "tie_word_embeddings": True,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv"],
    "stage_layers": {"conv": 4, "attention": 1, "dense": 2, "sparse": 3},
    "serve_dtype": "float32", "tensor_parallel_size": 1,
    # three prefill chunks (budget 64): the taps cross two boundaries and
    # the later chunks attend paired rows
    "reference_check": {"prompt_tokens": 150, "new_tokens": 6},
    "engine": {"max_num_seqs": 4, "max_num_batched_tokens": 64,
               "enable_chunked_prefill": True, "max_model_len": 256,
               "num_pages": 64, "page_size": 16, "dtype": "float32"},
}
TINY_MIX = {
    "loop": "closed", "clients": 3, "requests": 9, "order_seed": 0,
    "prompt_tokens": {"median": 80, "sigma": 0.3, "min": 40, "max": 150},
    "output_tokens": {"median": 6, "sigma": 0.3, "min": 3, "max": 10},
    "lead_in_s": 0.5, "stagger_s": 0.2, "request_timeout_s": 60,
    "sampling": {"temperature": 0.7, "top_p": 0.8, "top_k": 20},
}


def test_the_file_holds_the_catalogs_numbers_but_for_the_cut():
    assert list(CONFIG["reduced"]) == ["num_hidden_layers", "layer_types"]
    assert CONFIG["published"]["num_hidden_layers"] == 24
    full = CONFIG["published"]["layer_types"]
    assert len(full) == 24 and full.count("conv") == 18
    assert CONFIG["layer_types"] == full[:12]
    assert CONFIG["layer_types"] == ["conv", "conv", "full_attention",
                                     "conv"] * 3
    for key in ("assumed", "deployment", "reference_check", "engine"):
        assert CONFIG[key]
    for key in ("tie_word_embeddings", "renormalisation", "serve_dtype",
                "cache_layout", "weights", "sampling"):
        assert CONFIG["assumed"][key]
    assert "two pipeline stages of 12 layers" in CONFIG["deployment"]
    assert CONFIG["reference_check"] == {"prompt_tokens": 4608,
                                         "new_tokens": 8}
    assert CONFIG["engine"] == {
        "max_num_seqs": 256, "max_num_batched_tokens": 4096,
        "enable_chunked_prefill": True, "max_model_len": 6400,
        "num_pages": 32768, "page_size": 16}
    entry = next(c for c in BENCH["configs"] if c["name"] == "lfm2moe-l12")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == list(CONFIG["reduced"])
    if CATALOG.exists():
        row = next(r for r in map(json.loads,
                                  CATALOG.read_text().splitlines())
                   if r["source_url"] == CONFIG["source"])
        assert row["name"] == "LFM2-8B-A1B"
        for key, value in row["config"].items():
            if key not in CONFIG["reduced"]:
                assert CONFIG[key] == value, key
        assert CONFIG["published"]["layer_types"] == \
            row["config"]["layer_types"]


def test_the_program_agrees_and_a_drifted_constant_is_refused():
    cfg = arch.program_config(CONFIG)
    assert (cfg.num_layers, cfg.first_k_dense_replace) == (12, 2)
    assert cfg.layer_types.count("conv") == 9 == cfg.num_state_layers
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (32, 4)
    assert cfg.tie_word_embeddings and cfg.vocab_size == 65536
    layout = cfg.kv_layout
    assert (layout.kind, layout.heads, layout.width, layout.pools) == (
        "kv", 4, 128, 2)
    # bfloat16: 2 048 bytes a token a layer, K and V together
    assert 2 * layout.pools * layout.heads * layout.width == 2048
    assert cfg.state_shapes("conv") == ((2, 2048),)
    with pytest.raises(SystemExit, match="differs from the published"):
        arch.program_config({**CONFIG, "moe_intermediate_size": 1024})
    with pytest.raises(SystemExit, match="differs from the published"):
        arch.program_config({**CONFIG, "conv_L_cache": 4})
    with pytest.raises(SystemExit, match="differs from the published"):
        arch.program_config({**CONFIG, "layer_types": ["conv"] * 12,
                             "stage_layers": {"conv": 12, "attention": 0,
                                              "dense": 2, "sparse": 10}})
    with pytest.raises(SystemExit, match="stage_layers"):
        arch.program_config({**CONFIG, "num_hidden_layers": 11})
    with pytest.raises(SystemExit, match="conv_bias"):
        arch.program_config({**CONFIG, "conv_bias": True})


def test_the_counts_are_the_stated_algorithms():
    """Hand-worked at the published widths (ISSUE 39's arithmetic)."""
    costs = arch.model_costs(CONFIG)
    assert costs["mix"] == {"conv": 9, "attention": 3, "dense": 2,
                            "sparse": 10}
    h, tokens = 2048, 2040 + 255
    conv_mixer = 3 * h * h + h * h + 3 * h          # 16.78 M with the taps
    attn_mixer = 2 * h * h + 2 * h * 512            # 10.49 M
    dense_mlp, expert = 3 * h * 7168, 3 * h * 1792  # 44.04 M, 11.01 M
    assert conv_mixer == 16_783_360 and attn_mixer == 10_485_760
    assert dense_mlp == 44_040_192 and expert == 11_010_048
    weights = (9 * conv_mixer + 3 * attn_mixer + 2 * dense_mlp
               + 10 * (32 * expert + h * 32) + h * 65536)
    # 3 928.7 M parameters but the norms: 7.86 GB in bfloat16
    assert abs(weights - 3_928.7e6) < 0.2e6
    # a cached token: 2 048 B a layer, three layers
    assert costs["kv_bytes_per_pos"] == 3 * 2048 == 6144
    assert costs["state_bytes"] == 2 * 2048 * 4
    # attention: 4 x 64 x ctx operations a query head, three layers
    flops, nbytes = arch.attn_cost(STEP, CONFIG)
    assert flops == 3 * 32 * 4 * 64 * (STEP["prefill_ctx"]
                                       + STEP["decode_ctx"])
    # the decode rows' contexts at 2 048 B a layer, and the chunk's once
    assert nbytes > 3 * 2048 * STEP["decode_ctx"]
    assert nbytes < 3 * 2048 * (STEP["decode_ctx"] + 1500 + 1020) \
        + 3 * 2 * 2 * tokens * 2048 + 1
    # the ten sparse layers only: router and four picks a token
    moe_flops, moe_bytes = arch.moe_cost(STEP, CONFIG)
    assert moe_flops == 10 * tokens * 2 * (h * 32 + 4 * expert)
    # every expert touched by 2 295 tokens: all 32 read once a layer
    assert moe_bytes == pytest.approx(
        10 * (2 * 32 * expert + 2 * h * 32 + 2 * 2 * tokens * h), rel=1e-6)
    # the convolutions: B * u, three taps, the gate C; rows in (3) and out
    # (1); 256 slots of [2, 2048] float32 read and written, nine layers
    conv_flops, conv_bytes = arch.shortconv_cost(STEP, CONFIG)
    assert conv_flops == 9 * tokens * 8 * h
    assert conv_bytes == 9 * (2 * 3 * h + 4 * 2 * tokens * h
                              + 2 * 256 * 2 * h * 4)
    # the state is what a step's convolution moves least of: a decode
    # batch's rows weigh 2 x 256 x 2 048 x 8 B against 2 x 256 x 16 384 B
    only = {**STEP, "prefill_tokens": 0, "prefills": 0, "prefill_ctx": 0,
            "decodes": 256}
    assert arch.shortconv_cost(only, CONFIG)[1] == 9 * (
        2 * 3 * h + 4 * 2 * 256 * h + 2 * 256 * 2 * h * 4)
    deep = {**CONFIG, "num_hidden_layers": 24}  # two such stages
    assert arch.shortconv_cost(STEP, deep) == pytest.approx(
        (2 * conv_flops, 2 * conv_bytes))
    # the step holds each of its parts, the dense MLPs and the head
    step_flops, step_bytes = arch.step_cost(STEP, CONFIG, 1500.0, 1000.0)
    assert step_flops > flops + moe_flops + conv_flops \
        + 2 * tokens * 2 * dense_mlp
    # 7.86 GB of weights, 255 rows x 1 000 and a chunk's 2 x 1 500 tokens
    # of pages at 6 144 B, the slots
    assert 9.4e9 < step_bytes < 9.6e9
    assert arch.step_cost({**STEP, "prefill_tokens": 0, "decodes": 0},
                          CONFIG, 0.0, 0.0) is None


def test_the_weights_relate_the_experts_and_keep_the_bias():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_omni_tpu.models.common import transformer as tfm

    cfg = arch.program_config(TINY)
    key = jax.random.PRNGKey(7)
    plain = tfm.init_params(key, cfg=cfg, dtype=jnp.bfloat16)
    ours = arch.init_weights(key, cfg, jnp.bfloat16)
    assert "lm_head" not in ours and "router" not in ours["layers"][1]
    for a, b in zip(plain["layers"], ours["layers"]):
        a, b = dict(a), dict(b)
        if "experts" in a:
            got = np.asarray(b["experts"]["down"], np.float32)
            corr = np.corrcoef(got[1].ravel(), got[2].ravel())[0, 1]
            assert 0.99 < corr < 0.999
            bias = np.asarray(b["router"]["e_bias"])
            assert bias.dtype == np.float32 and np.abs(bias).max() > 0.02
            del a["experts"], b["experts"]
        assert all(jax.tree.leaves(jax.tree.map(
            lambda x, y: bool(jnp.array_equal(x, y)), a, b)))


def test_the_cell_and_its_three_metrics_resolve_by_name():
    cell = spec.resolve_cell(CELL)
    assert cell["cell"] == {
        "name": CELL, "config": "lfm2moe-l12", "traffic": "assist_closed",
        "chips": 1, "why": cell["cell"]["why"]}
    assert {m["name"] for m in cell["end_to_end"]} == {"tok_s", "setup_s"}
    got = {m["name"]: m for m in cell["per_layer"]}
    assert got["shortconv_roofline_pct"]["params"] == {"part": "shortconv"}
    assert got["scope_shortconv_share_pct"]["params"] == {
        "scopes": ["omni.shortconv"]}
    assert got["scope_conv_state_share_pct"]["params"] == {
        "scopes": ["omni.conv_state"]}
    for name in ("shortconv_roofline_pct", "scope_shortconv_share_pct",
                 "scope_conv_state_share_pct"):
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["source"], entry["moves"]) == (
            "kernels", "device_trace", "tok_s")
        assert callable(readers.resolve(got[name]["reader"]))
    assert callable(arch.shortconv_cost)
    # what the other saturated cells report, and the two occupancies
    for name in ("attn_roofline_pct", "moe_roofline_pct",
                 "device_roofline_ctx_pct", "scope_unnamed_share_pct",
                 "rows_per_step_mean.sat", "padding_eff_pct",
                 "assemble_pack_ms.sat", "assemble_sampling_ms.sat",
                 "assemble_put_ms.sat", "step_device_ms.sat",
                 "state_slots_mean", "kv_pages_used_mean"):
        assert name in got, name
    assert not [n for n in got if n.startswith("dev_") or "gdn" in n
                or "mla" in n or "attn_window" in n or n.endswith(".rate")]
    mix = cell["traffic"]
    assert (mix["loop"], mix["clients"], mix["requests"]) == (
        "closed", 256, 512)
    assert mix["prompt_tokens"] == {"median": 512, "sigma": 0.6,
                                    "min": 64, "max": 4096}
    assert mix["output_tokens"] == {"median": 512, "sigma": 0.5,
                                    "min": 128, "max": 2048}
    assert (mix["order_by"], mix["order_seed"], mix["stagger_s"],
            mix["lead_in_s"], mix["request_timeout_s"]) == (
        "prompt_tokens", 0, 30, 40, 300)
    # the longest request fits the model's length and the pool holds what
    # 256 of the mean do
    assert 4096 + 2048 + 18 <= CONFIG["engine"]["max_model_len"]
    assert 256 * 1700 < 32768 * 16


#: the per-layer metrics that read the engine thread's phase clock (PR 36),
#: in the order ``BENCHMARK.json`` holds them
PHASE_BASES = ("assemble_pack_ms", "assemble_sampling_ms", "assemble_put_ms",
               "busy_wait_ms", "host_unblocked_pct", "stall_share_pct",
               "step_device_ms")


@pytest.mark.parametrize("base", PHASE_BASES)
def test_the_phase_metrics_keep_their_cells_and_gain_this_one(base):
    """What ``tests/benchmark/test_phase_readers.py`` pinned to the cells
    of its day (``entry["workloads"] == SAT``, which a cell appended by a
    later PR turns red: PERF.md Open question 13a), held in the form that
    survives an appended cell: the ``.sat`` twin lists the five cells it
    had, in their order, then this one; the ``.rate`` twin is untouched;
    every listed cell reports the end-to-end metric the twin moves."""
    from tests.benchmark import test_phase_readers as pinned

    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    sat, rate = by_name[f"{base}.sat"], by_name[f"{base}.rate"]
    assert sat["workloads"] == pinned.SAT + [CELL]
    assert rate["workloads"] == pinned.RATE
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for entry in (sat, rate):
        assert set(entry["workloads"]) <= set(
            e2e[entry["moves"]]["workloads"])
    assert sat["moves"] == "tok_s" and sat["better"] == "lower"


def test_the_new_entries_stand_after_every_entry_the_parent_had():
    """The three metrics of this PR are the list's last three, in the
    issue's order; the fourteen of PR 36 stand unbroken right before them
    (the pinned test read them as the list's tail), and nothing else in
    the list names a phase metric."""
    from tests.benchmark import test_phase_readers as pinned

    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-3:] == ["scope_shortconv_share_pct",
                          "scope_conv_state_share_pct",
                          "shortconv_roofline_pct"]
    assert sorted(names[-17:-3]) == pinned.NAMES
    assert not set(names[:-17]) & set(pinned.NAMES)
    assert len(names) == len(set(names))


def test_a_tiny_cell_is_served_checked_and_counted(tmp_path):
    """A copy's ``benchmark/run.py --trace 2`` on a tiny LFM2 cell: the
    harness serves it through ``build_server``, the reference check agrees
    with the served path to float32 rounding through chunked prefill, the
    paired pages and the slots, the engine counts exactly the tokens drawn,
    and the last line holds the cell's metrics."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "benchmark/traffic/tiny_closed.json").write_text(
        json.dumps(TINY_MIX))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][-1], "name": "tiny",
                             "file": "benchmark/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny_lfm2", "config": "tiny",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_lfm2")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "tiny_lfm2", "--seed", "5000000039", "--seconds", "2",
         "--trace", "2", "--allow-cpu"],
        capture_output=True, text=True, timeout=420, cwd=tmp_path,
        env={**os.environ, "PYTHONPATH": str(ROOT),
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax")})
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    (kept,) = [json.loads(p.read_text()) for p in
               (tmp_path / ".benchmark_cache" / "runs").glob("*.json")]
    # the only thing wrong with the run is where it ran
    wrong = [ln for ln in done.stdout.splitlines() if "NOT CORRECT" in ln]
    assert len(wrong) == 1 and "not a TPU" in wrong[0], wrong
    assert kept["reference"]["ok"]
    assert kept["reference"]["max_abs_diff"] < 2e-5
    metrics = result["metrics"]
    assert {"tok_s", "setup_s", "step_ms_mean.sat", "padding_eff_pct",
            "window_compiles", "state_slots_mean", "kv_pages_used_mean",
            "assemble_pack_ms.sat"} <= set(metrics)
    assert 1 <= metrics["state_slots_mean"]["value"] <= 3
    assert 3 <= metrics["kv_pages_used_mean"]["value"] <= 40
    kv = kept["engine1"]["kv"]
    assert (kv["layout"], kv["row_width"], kv["row_used"]) == (
        "kv", 128, 128)
    assert kv["bytes_per_token_layer"] == 2 * 2 * 64 * 4
    assert kept["engine1"]["state"]["slots_total"] == 4
