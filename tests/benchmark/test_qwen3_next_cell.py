"""The Qwen3-Next architecture module and its cell's metric files: the
counts, the configuration, and a tiny cell run through ``benchmark/run.py``
on the CPU (never correct, says so) whose last line holds the new
metrics."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import spec
from benchmark.archs import qwen3_next as arch

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
CELL = "qwen3next_longdoc_sat"
CONFIG = spec.load_json(ROOT / "benchmark/configs/qwen3next-l8-ep4.json")
STEP = {"prefill_tokens": 2038, "prefills": 1, "decodes": 10,
        "prefill_ctx": 2038 * 5000, "decode_ctx": 10 * 9000,
        "state_rows": 11}

TINY = {
    "arch": "benchmark.archs.qwen3_next", "name": "qwen3next-tiny",
    "vocab_size": 384, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "intermediate_size": 128, "rope_theta": 10000000, "rms_norm_eps": 1e-6,
    "partial_rotary_factor": 0.25, "full_attention_interval": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4,
    # a share that does not start at expert 0: ids 2-3 of an 8-wide router
    "num_experts": 2, "published": {"num_experts": 8}, "expert_offset": 2,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "tie_word_embeddings": False, "serve_dtype": "float32",
    "tensor_parallel_size": 1,
    # three prefill chunks (budget 64), a ragged last chunk of the rule
    "reference_check": {"prompt_tokens": 150, "new_tokens": 6},
    "engine": {"max_num_seqs": 4, "max_num_batched_tokens": 64,
               "enable_chunked_prefill": True, "max_model_len": 256,
               "num_pages": 64, "page_size": 16, "dtype": "float32"},
}
TINY_MIX = {
    "loop": "closed", "clients": 2, "requests": 8, "order_seed": 0,
    "prompt_tokens": {"median": 80, "sigma": 0.3, "min": 40, "max": 150},
    "output_tokens": {"median": 5, "sigma": 0.3, "min": 3, "max": 8},
    "lead_in_s": 0.5, "stagger_s": 0.2, "request_timeout_s": 60,
    "sampling": {"temperature": 0.7, "top_p": 0.8, "top_k": 20},
}


def test_the_file_states_the_cut_and_the_program_agrees():
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512}
    assert list(CONFIG["reduced"]) == ["num_hidden_layers", "num_experts"]
    for key in ("assumed", "deployment", "reference_check", "engine"):
        assert CONFIG[key]
    cfg = arch.program_config(CONFIG)
    # 128 experts held of a 512-wide router, two periods of 3 + 1
    assert (cfg.num_experts, cfg.router_experts, cfg.expert_offset) == (
        128, 512, 0)
    assert cfg.layer_types == (("linear_attention",) * 3
                               + ("full_attention",)) * 2
    assert cfg.num_kv_layers == 2 and cfg.num_state_layers == 6
    # a width that drifted in the program's constant is refused
    with pytest.raises(SystemExit, match="differs from the published"):
        arch.program_config({**CONFIG, "linear_key_head_dim": 64})


def test_the_counts_are_the_recurrent_algorithms():
    """Hand-worked at the published widths (ISSUE 28's arithmetic)."""
    costs = arch.model_costs(CONFIG)
    assert (costs["full"], costs["linear"]) == (2, 6)
    # per token and linear layer: 32 heads x 7 x 128 x 128 for the rule,
    # 2 x 4 x 8192 for the convolution, 12 a value channel for gates+norm
    assert costs["gdn_flops_per_token"] == (
        32 * 7 * 128 * 128 + 2 * 4 * 8192 + 12 * 4096)
    # a slot in a layer: 32 x 128 x 128 float32 and 3 x 8192 bfloat16
    assert costs["state_bytes"] == 4 * 32 * 128 * 128 + 2 * 3 * 8192
    flops, nbytes = arch.gdn_cost(STEP, CONFIG)
    assert flops == 6 * 2048 * costs["gdn_flops_per_token"]
    assert nbytes > 6 * 2 * 11 * costs["state_bytes"]
    deep = {**CONFIG, "num_hidden_layers": 16}
    assert arch.gdn_cost(STEP, deep) == pytest.approx((2 * flops, 2 * nbytes))
    # of a token's 10 picks 2.5 land on the 128 held experts
    moe_flops, _ = arch.moe_cost(STEP, CONFIG)
    per_token = 2 * (2048 * 512 + 3 * 2048 * 512 + 2048
                     + 2.5 * 3 * 2048 * 512)
    assert moe_flops == pytest.approx(8 * 2048 * per_token)
    # attention: the 2 full layers at 16 heads x 256, and the gate
    attn_flops, _ = arch.attn_cost(STEP, CONFIG)
    assert attn_flops == pytest.approx(
        4 * 4096 * 2 * (STEP["prefill_ctx"] + STEP["decode_ctx"])
        + 4 * 2 * 2048 * 4096)
    # the step holds each of its parts, and the states' traffic
    step_flops, step_bytes = arch.step_cost(STEP, CONFIG, 5000.0, 9000.0)
    assert step_flops > flops + moe_flops + attn_flops
    less = arch.step_cost({**STEP, "state_rows": 1}, CONFIG, 5000.0, 9000.0)
    assert step_bytes - less[1] == 6 * 2 * 10 * costs["state_bytes"]


def test_the_weights_are_the_programs_draw_with_a_decisive_router():
    """``init_weights`` is the program's initialiser but for the routers,
    multiplied by ``ROUTER_GAIN`` (a power of two: exact in bfloat16), so
    that a token's ten picks fall off as a trained router's do and a pick
    swapped by a rounding moves a hundredth of the routed sum, not a
    tenth."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_omni_tpu.models.common import transformer as tfm

    cfg = arch.program_config(TINY)
    key = jax.random.PRNGKey(7)
    plain = tfm.init_params(key, cfg=cfg, dtype=jnp.bfloat16)
    ours = arch.init_weights(key, cfg, jnp.bfloat16)
    assert arch.ROUTER_GAIN == 8.0
    for a, b in zip(plain["layers"], ours["layers"]):
        assert b["router"]["w"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(b["router"]["w"], np.float32),
            np.asarray(a["router"]["w"], np.float32) * arch.ROUTER_GAIN)
        a, b = dict(a), dict(b)
        del a["router"], b["router"]
        assert all(jax.tree.leaves(jax.tree.map(
            lambda x, y: bool(jnp.array_equal(x, y)), a, b)))
    # at the published width: the first pick about 0.6, the tenth 0.01
    logits = 8.0 * 0.577 * np.random.default_rng(0).standard_normal(
        (4096, 512))
    top = -np.sort(-logits, axis=-1)[:, :10]
    w = np.exp(top - top[:, :1])
    w /= w.sum(-1, keepdims=True)
    assert 0.5 < w[:, 0].mean() < 0.65 and 0.005 < w[:, 9].mean() < 0.02


def test_the_new_metrics_are_data_files_of_the_cell():
    got = {m["name"]: m for m in spec.resolve_cell(CELL)["per_layer"]}
    assert got["gdn_roofline_pct"]["params"] == {"part": "gdn"}
    assert got["scope_gdn_share_pct"]["params"] == {"scopes": ["omni.gdn"]}
    assert got["scope_gdn_state_share_pct"]["params"] == {
        "scopes": ["omni.gdn_state"]}
    assert got["state_slots_mean"]["params"] == {"histogram": "state_slots"}
    assert got["state_slots_mean"]["layer"] == "scheduler"
    # the printed-name metrics and the mix-mean roofline stay the thinker's
    assert not [n for n in got if n.startswith("dev_")]
    assert "device_roofline_pct" not in got
    for name in ("attn_roofline_pct", "moe_roofline_pct",
                 "device_roofline_ctx_pct", "scope_unnamed_share_pct",
                 "idle_runner_pct", "device_idle_pct.sat"):
        assert name in got


def test_a_tiny_cell_is_served_checked_and_counted(tmp_path):
    """``benchmark/run.py --trace 2`` on a tiny Qwen3-Next cell: the
    harness serves it through ``build_server``, the reference check agrees
    to float32 rounding through chunked prefill and both caches, the
    engine counts exactly the tokens drawn, and the state cache's
    occupancy is a metric of the last line."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "benchmark/traffic/tiny_closed.json").write_text(
        json.dumps(TINY_MIX))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][-1], "name": "tiny",
                             "file": "benchmark/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny_next", "config": "tiny",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_next")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--workload", "tiny_next", "--seed", "5000000011", "--seconds", "2",
         "--trace", "2", "--bench-root", str(tmp_path), "--allow-cpu"],
        capture_output=True, text=True, timeout=420,
        env={**os.environ,
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax")})
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    for path in (ROOT / ".benchmark_cache" / "runs").glob(
            "tiny_next-seed5000000011-*.json"):
        kept = json.loads(path.read_text())
        path.unlink()
    # the only thing wrong with the run is where it ran
    wrong = [ln for ln in done.stdout.splitlines() if "NOT CORRECT" in ln]
    assert len(wrong) == 1 and "not a TPU" in wrong[0], wrong
    assert kept["reference"]["ok"]
    assert kept["reference"]["max_abs_diff"] < 2e-5
    metrics = result["metrics"]
    assert {"tok_s", "setup_s", "step_ms_mean.sat", "padding_eff_pct",
            "window_compiles", "state_slots_mean"} <= set(metrics)
    assert 1 <= metrics["state_slots_mean"]["value"] <= 2  # two clients
    assert all(s["state_rows"] == s["prefills"] + s["decodes"]
               for s in kept["steps"])
    state = kept["engine1"]["state"]
    assert state["state_slot_allocs_total"] > 0
    assert state["state_resets_total"] == 0
