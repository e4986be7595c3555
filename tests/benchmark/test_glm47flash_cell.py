"""The GLM-4.7-Flash architecture module and its cell's metric files: the
configuration and its cut, the counts, the weights' draw, and a tiny cell
run through ``benchmark/run.py`` on the CPU (never correct, says so) whose
last line holds the new metrics."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import spec
from benchmark.archs import glm4_moe_lite as arch

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
CELL = "glm47flash_agent_sat"
CONFIG = spec.load_json(ROOT / "benchmark/configs/glm47flash-l8.json")
STEP = {"prefill_tokens": 2040, "prefills": 1, "decodes": 23,
        "prefill_ctx": 2040 * 5000, "decode_ctx": 23 * 8600}

TINY = {
    "arch": "benchmark.archs.glm4_moe_lite", "name": "glm-tiny",
    "model_type": "glm4_moe_lite", "attention_bias": False,
    "hidden_act": "silu", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "partial_rotary_factor": 1, "rope_scaling": None,
    "vocab_size": 384, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 160, "rope_theta": 1000000, "rms_norm_eps": 1e-5,
    "q_lora_rank": 24, "kv_lora_rank": 64, "qk_nope_head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 32, "first_k_dense_replace": 1,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "routed_scaling_factor": 1.8, "tie_word_embeddings": False,
    "stage_layers": {"dense": 1, "sparse": 2},
    "serve_dtype": "float32", "tensor_parallel_size": 1,
    # three prefill chunks (budget 64): the later ones attend cached rows
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
    assert CONFIG["published"] == {"num_hidden_layers": 47}
    assert list(CONFIG["reduced"]) == ["num_hidden_layers"]
    for key in ("assumed", "deployment", "reference_check", "engine"):
        assert CONFIG[key]
    for key in ("cache_layout", "rotary", "mtp", "loader", "weights"):
        assert CONFIG["assumed"][key]
    assert CONFIG["reference_check"] == {"prompt_tokens": 2560,
                                         "new_tokens": 8}
    cfg = arch.program_config(CONFIG)
    # the dense layer and seven sparse ones, every expert, whole vocabulary
    assert (cfg.num_layers, cfg.first_k_dense_replace) == (8, 1)
    assert (cfg.num_experts, cfg.router_experts) == (64, 0)
    assert cfg.vocab_size == 154880
    layout = cfg.kv_layout
    assert (layout.kind, layout.heads, layout.pools) == ("latent", 1, 1)
    assert (layout.used, layout.width) == (576, 640)
    assert cfg.attn_kwargs == {"scale": 1 / 16, "v_dim": 512}
    # a width that drifted in the program's constant is refused, and so is
    # a routing the module does not build
    with pytest.raises(SystemExit, match="differs from the published"):
        arch.program_config({**CONFIG, "kv_lora_rank": 256})
    with pytest.raises(SystemExit, match="n_group"):
        arch.program_config({**CONFIG, "n_group": 8})
    with pytest.raises(SystemExit, match="stage_layers"):
        arch.program_config({**CONFIG, "num_hidden_layers": 9})


def test_the_counts_are_the_stated_algorithms():
    """Hand-worked at the published widths (ISSUE 32's arithmetic)."""
    costs = arch.model_costs(CONFIG)
    assert (costs["dense"], costs["sparse"]) == (1, 7)
    assert costs["kvb_params"] == 512 * 20 * (192 + 256)
    # attention a layer, kv_b included: 21.76 M
    per_layer = (2048 * 768 + 768 * 5120 + 2048 * 576 + 5120 * 2048
                 + costs["kvb_params"])
    assert per_layer == 21_757_952
    # a cached position: 576 numbers a layer, and nothing per head
    assert costs["kv_bytes_per_pos"] == 8 * 576 * 2
    # a decode row absorbed: 2 x 20 x 1088 a pair a layer; a prefill token
    # expanded: 2 x 20 x (256 + 256)
    assert costs["decode_flops_per_pair"] == 8 * 2 * 20 * 1088
    assert costs["prefill_flops_per_pair"] == 8 * 2 * 20 * 512
    flops, nbytes = arch.attn_cost(STEP, CONFIG)
    assert flops == (8 * 2 * 20 * 512 * STEP["prefill_ctx"]
                     + 8 * 2 * 20 * 1088 * STEP["decode_ctx"])
    # the decode rows' context at 1152 bytes a position a layer, at least
    assert nbytes > 8 * 1152 * STEP["decode_ctx"]
    assert nbytes < 8 * 1152 * (STEP["decode_ctx"] + 8000) + 8 * 2063 * 60e3
    # every product with kv_b, each token's once, whichever form
    up_flops, up_bytes = arch.mla_up_cost(STEP, CONFIG)
    assert up_flops == 8 * 2063 * 2 * costs["kvb_params"]
    assert up_bytes > 8 * 2 * costs["kvb_params"]
    deep = {**CONFIG, "num_hidden_layers": 16}  # two such stages
    assert arch.mla_up_cost(STEP, deep) == pytest.approx(
        (2 * up_flops, 2 * up_bytes))
    # the sparse MLP: router, 4 picks and the shared expert, 7 layers
    moe_flops, _ = arch.moe_cost(STEP, CONFIG)
    assert moe_flops == 7 * 2063 * 2 * (2048 * 64 + 5 * 3 * 2048 * 1536)
    # the step holds each of its parts, the dense layer and the head
    step_flops, step_bytes = arch.step_cost(STEP, CONFIG, 5000.0, 8600.0)
    assert step_flops > flops + up_flops + moe_flops \
        + 2063 * 2 * 3 * 2048 * 10240
    # the 10.3 GB of weights, all but a few experts touched, and 1.9 GB
    # of latent rows (23 rows x 8600 and a chunk's 2 x 5000, x 9216 B)
    assert 11.4e9 < step_bytes < 12.3e9
    assert arch.step_cost({**STEP, "prefill_tokens": 0, "decodes": 0},
                          CONFIG, 0.0, 0.0) is None


def test_the_weights_are_the_programs_draw_with_related_experts():
    """``init_weights`` is the program's initialiser but for the routed
    experts, each ``EXPERT_OWN_SHARE`` its own and the rest the layer's
    shared draw (a swapped pick then moves what it moves between two
    experts that share most of what they do), every entry's variance as
    drawn; the correction bias is non-zero and float32, wide enough to
    change most tokens' selection and narrow enough that every expert is
    picked."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_omni_tpu.models.common import transformer as tfm

    cfg = arch.program_config(TINY)
    key = jax.random.PRNGKey(7)
    plain = tfm.init_params(key, cfg=cfg, dtype=jnp.bfloat16)
    ours = arch.init_weights(key, cfg, jnp.bfloat16)
    assert arch.EXPERT_OWN_SHARE == 0.0625
    assert "router" not in ours["layers"][0]  # the dense layer
    for a, b in zip(plain["layers"], ours["layers"]):
        a, b = dict(a), dict(b)
        if "experts" in a:
            for name in ("gate_up", "down"):
                drawn = np.asarray(a["experts"][name], np.float32)
                got = np.asarray(b["experts"][name], np.float32)
                assert b["experts"][name].dtype == jnp.bfloat16
                want = (1 - 0.0625 ** 2) ** 0.5 * drawn[:1] + 0.0625 * drawn
                np.testing.assert_allclose(got, want, atol=2e-3)
                # two experts differ by their own parts alone
                corr = np.corrcoef(got[1].ravel(), got[2].ravel())[0, 1]
                assert 0.99 < corr < 0.999
                assert abs(got[1:].std() / drawn[1:].std() - 1) < 0.05
            bias = np.asarray(b["router"]["e_bias"])
            assert bias.dtype == np.float32
            assert 0.02 < np.abs(bias).max() <= 0.05
            del a["experts"], b["experts"]
        assert all(jax.tree.leaves(jax.tree.map(
            lambda x, y: bool(jnp.array_equal(x, y)), a, b)))
    # at the published width: scores of deviation 0.13, a bias of +-0.05
    rng = np.random.default_rng(0)
    scores = 1 / (1 + np.exp(-0.577 * rng.standard_normal((4096, 64))))
    bias = rng.uniform(-0.05, 0.05, 64)
    with_b = np.argsort(-(scores + bias), -1)[:, :4]
    without = np.argsort(-scores, -1)[:, :4]
    changed = (np.sort(with_b, -1) != np.sort(without, -1)).any(-1).mean()
    assert changed > 0.5
    picks = np.bincount(with_b.reshape(-1), minlength=64)
    assert picks.min() > 0.25 * picks.mean()


def test_the_new_metrics_are_data_files_of_the_cell():
    got = {m["name"]: m for m in spec.resolve_cell(CELL)["per_layer"]}
    assert got["mla_up_roofline_pct"]["params"] == {"part": "mla_up"}
    assert got["scope_mla_up_share_pct"]["params"] == {
        "scopes": ["omni.mla_up"]}
    assert got["kv_pages_used_mean"]["params"] == {
        "histogram": "kv_pages_used"}
    assert got["kv_pages_used_mean"]["layer"] == "scheduler"
    assert callable(arch.mla_up_cost)
    # the printed-name metrics, the mix-mean roofline and the other
    # models' scopes stay theirs
    assert not [n for n in got if n.startswith("dev_") or "gdn" in n]
    assert "device_roofline_pct" not in got
    for name in ("attn_roofline_pct", "moe_roofline_pct",
                 "device_roofline_ctx_pct", "scope_unnamed_share_pct",
                 "scope_kv_write_share_pct", "idle_runner_pct",
                 "device_idle_pct.sat", "rows_per_step_mean.sat"):
        assert name in got
    mix = spec.resolve_cell(CELL)["traffic"]
    assert (mix["loop"], mix["clients"], mix["requests"]) == (
        "closed", 24, 48)
    assert mix["prompt_tokens"] == {"median": 8192, "sigma": 0.4,
                                    "min": 2048, "max": 16384}
    assert mix["output_tokens"] == {"median": 256, "sigma": 0.5,
                                    "min": 64, "max": 1024}
    # everything this PR adds under the benchmark's paths but the
    # architecture module and this test is data
    for name in ("scope_mla_up_share_pct", "mla_up_roofline_pct",
                 "kv_pages_used_mean"):
        assert (ROOT / "benchmark/layer_metrics" / f"{name}.json").exists()


def test_a_tiny_cell_is_served_checked_and_counted(tmp_path):
    """``benchmark/run.py --trace 2`` on a tiny GLM cell: the harness
    serves it through ``build_server``, the reference check (expanded,
    float32) agrees with the served absorbed form to float32 rounding
    through chunked prefill and the latent cache, the engine counts exactly
    the tokens drawn, and the pool's occupancy is a metric of the last
    line."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "benchmark/traffic/tiny_closed.json").write_text(
        json.dumps(TINY_MIX))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][-1], "name": "tiny",
                             "file": "benchmark/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny_glm", "config": "tiny",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_glm")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--workload", "tiny_glm", "--seed", "5000000017", "--seconds", "2",
         "--trace", "2", "--bench-root", str(tmp_path), "--allow-cpu"],
        capture_output=True, text=True, timeout=420,
        env={**os.environ,
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax")})
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    for path in (ROOT / ".benchmark_cache" / "runs").glob(
            "tiny_glm-seed5000000017-*.json"):
        kept = json.loads(path.read_text())
        path.unlink()
    # the only thing wrong with the run is where it ran
    wrong = [ln for ln in done.stdout.splitlines() if "NOT CORRECT" in ln]
    assert len(wrong) == 1 and "not a TPU" in wrong[0], wrong
    assert kept["reference"]["ok"]
    assert kept["reference"]["max_abs_diff"] < 2e-5
    metrics = result["metrics"]
    assert {"tok_s", "setup_s", "step_ms_mean.sat", "padding_eff_pct",
            "window_compiles", "kv_pages_used_mean"} <= set(metrics)
    # two clients of 40-150 tokens: 3 to 10 pages each
    assert 3 <= metrics["kv_pages_used_mean"]["value"] <= 40
    kv = kept["engine1"]["kv"]
    assert (kv["layout"], kv["row_width"], kv["row_used"]) == (
        "latent", 128, 72)
    assert kv["bytes_per_token_layer"] == 128 * 4
