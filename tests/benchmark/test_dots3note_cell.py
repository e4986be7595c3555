"""The dots3-note-prev architecture module and its cell's metric files: the
configuration and its cut, the counts, the weights' draw, the share of the
experts against the uncut layer, and a tiny cell run through ``benchmark/run.py`` on the CPU
(never correct, says so) whose last line holds the new metrics."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import spec
from benchmark.archs import dots3_note as arch

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
CELL = "dots3note_agent_sat"
CONFIG = spec.load_json(ROOT / "benchmark/configs/dots3note-l5-ep8.json")
#: a 2040-token chunk ending at 7040 of context beside 23 decode rows at 8600
STEP = {"prefill_tokens": 2040, "prefills": 1, "decodes": 23,
        "prefill_ctx": 2040 * 6020, "decode_ctx": 23 * 8600,
        "prefill_ctx_kept": 2040 * 2048, "decode_ctx_kept": 23 * 2048,
        "prefill_ctx_window": 2040 * 513, "decode_ctx_window": 23 * 513}

TINY = {
    "arch": "benchmark.archs.dots3_note", "name": "dots-tiny",
    "model_type": "dots3_note", "attention_bias": False,
    "hidden_act": "silu", "topk_method": "noaux_tc", "rope_scaling": None,
    "scoring_func": "sigmoid", "attention_gate_type": "headwise",
    "swa_attention_gate_type": "headwise",
    "apply_mla_qkv_lora_rescale": True, "moe_layer_freq": 1,
    "vocab_size": 384, "hidden_size": 64, "num_hidden_layers": 5,
    "layer_types": ["full_attention", "full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 160, "rope_theta": 80000000, "rms_norm_eps": 1e-5,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2,
    "swa_q_lora_rank": 24, "swa_kv_lora_rank": 48,
    "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8,
    "swa_v_head_dim": 16, "swa_rope_theta": 50000,
    "sliding_window_size": 5, "index_n_heads": 2, "index_head_dim": 16,
    "index_topk": 8, "first_k_dense_replace": 1,
    "n_routed_experts": 4, "expert_offset": 4,
    "published": {"n_routed_experts": 16}, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "tie_word_embeddings": False,
    "stage_layers": {"dense_full": 1, "sparse_full": 1, "sparse_sliding": 3},
    "serve_dtype": "float32", "tensor_parallel_size": 1,
    # three prefill chunks (budget 64), every checked token past the window
    # and past the selection
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
    assert CONFIG["published"]["num_hidden_layers"] == 46
    assert CONFIG["published"]["n_routed_experts"] == 256
    assert list(CONFIG["reduced"]) == ["num_hidden_layers", "layer_types",
                                       "n_routed_experts"]
    for key in ("assumed", "deployment", "reference_check", "engine"):
        assert CONFIG[key]
    for key in ("apply_mla_qkv_lora_rescale", "attention_gate_type",
                "indexer", "window", "rotary", "cache_layout", "towers",
                "mtp", "loader", "weights", "serve_dtype"):
        assert CONFIG["assumed"][key]
    assert CONFIG["reference_check"] == {"prompt_tokens": 4096,
                                         "new_tokens": 8}
    cfg = arch.program_config(CONFIG)
    assert cfg.layer_types == ("full_attention",) * 2 + (
        "sliding_attention",) * 3
    assert (cfg.num_layers, cfg.first_k_dense_replace) == (5, 1)
    # 32 of 256 experts under the whole router and its 8 picks
    assert (cfg.num_experts, cfg.router_experts, cfg.expert_offset) == (
        32, 256, 0)
    assert cfg.num_experts_per_tok == 8 and cfg.vocab_size == 152064
    full, wide = cfg.kv_layout, cfg.kv_layout_of("sliding_attention")
    assert (full.used, full.width) == (576, 640)
    assert (wide.used, wide.width) == (1088, 1152)
    assert cfg.attn_kwargs == {"scale": 192 ** -0.5, "v_dim": 512}
    assert cfg.attn_kwargs_of("sliding_attention") == {
        "scale": 1 / 16, "v_dim": 1024, "window": 513}
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        64, 128, 2048)
    # a width that drifted in the program's constant is refused, and so is
    # what the module does not build
    with pytest.raises(SystemExit, match="differs from the published"):
        arch.program_config({**CONFIG, "swa_kv_lora_rank": 512})
    with pytest.raises(SystemExit, match="attention_gate_type"):
        arch.program_config({**CONFIG, "attention_gate_type": "elementwise"})
    with pytest.raises(SystemExit, match="layer_types"):
        arch.program_config({**CONFIG, "num_hidden_layers": 6})


def test_the_counts_are_the_stated_algorithms():
    """Hand-worked at the published widths (ISSUE 34's arithmetic)."""
    costs = arch.model_costs(CONFIG)
    full, slide = (costs["kinds"][k] for k in arch.KINDS)
    assert (full["layers"], slide["layers"], costs["sparse"]) == (2, 3, 4)
    # a full mixer 144.0 M parameters, a sliding one 90.8 M
    assert full["proj_params"] + full["kvb_params"] \
        + costs["index_params"] == 144_048_128
    assert slide["proj_params"] + slide["kvb_params"] == 90_832_896
    # the full layers attend min(ctx, 2048) keys a token: a prefill token
    # expanded (2 x 128 x 320 a pair), a decode row absorbed (2 x 128 x
    # 1088), whatever a dense walk would touch
    flops, nbytes = arch.attn_cost(STEP, CONFIG)
    assert flops == 2 * 2 * 128 * (320 * 2040 * 2048 + 1088 * 23 * 2048)
    dense = arch.attn_cost({k: v for k, v in STEP.items()
                            if not k.endswith(("kept", "window"))}, CONFIG)
    assert dense[0] > 2.9 * flops
    assert nbytes > 2 * 1152 * 23 * 2048
    # the sliding layers min(ctx, 513): 2 x 64 x 384 and 2 x 64 x 2112
    w_flops, w_bytes = arch.attn_window_cost(STEP, CONFIG)
    assert w_flops == 3 * 2 * 64 * (384 * 2040 * 513 + 2112 * 23 * 513)
    assert w_bytes > 3 * 2176 * 23 * 513
    # the indexer scores EVERY visible key: 2 x 64 x 128 + 2 x 64 a pair
    i_flops, i_bytes = arch.indexer_cost(STEP, CONFIG)
    pairs = STEP["prefill_ctx"] + STEP["decode_ctx"]
    assert i_flops == 2 * (2.0 * 2063 * costs["index_params"]
                           + pairs * (2.0 * 64 * 128 + 2.0 * 64))
    assert i_bytes > 2 * 256 * STEP["decode_ctx"]
    # every product with either kv_b, each token's once a layer
    up_flops, _ = arch.mla_up_cost(STEP, CONFIG)
    assert up_flops == 2063 * 2.0 * (2 * 512 * 128 * 256
                                     + 3 * 1024 * 64 * 320)
    deep = {**CONFIG, "num_hidden_layers": 10}  # two such stages
    for fn in (arch.mla_up_cost, arch.indexer_cost, arch.attn_window_cost):
        once, twice = fn(STEP, CONFIG), fn(STEP, deep)
        assert twice == pytest.approx((2 * once[0], 2 * once[1]))
    # the sparse MLP: the 256-wide router, 8 x 32 / 256 = 1 pick expected
    # on a held expert, the shared expert; 4 layers
    moe_flops, _ = arch.moe_cost(STEP, CONFIG)
    assert moe_flops == 4 * 2063 * 2 * (5120 * 256 + 2 * 3 * 5120 * 1536)
    step_flops, step_bytes = arch.step_cost(STEP, CONFIG, 6020.0, 8600.0)
    assert step_flops > flops + w_flops + i_flops + up_flops + moe_flops \
        + 2063 * 2 * 3 * 5120 * 13824
    # the 10.9 GB of weights but the embedding's 1.56 GB, of which a step
    # reads its tokens' rows (all 32 held experts touched by 2063 tokens)
    assert 10.2e9 < step_bytes < 10.8e9
    # a record without the bounded pairs (a mix's means) caps the means
    bare = {k: STEP[k] for k in ("prefill_tokens", "prefills", "decodes")}
    assert arch.step_cost(bare, CONFIG, 6020.0, 8600.0)[0] == pytest.approx(
        step_flops, rel=1e-6)
    assert arch.step_cost({**STEP, "prefill_tokens": 0, "decodes": 0},
                          CONFIG, 0.0, 0.0) is None


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The share test: at a small size the four shares' routed parts, the
    shared expert counted once, add up to the uncut reference's layer
    output, under the sigmoid router with its correction bias; and the
    program's share dispatch gives each share's part."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_omni_tpu.models.common import transformer as tfm
    from vllm_omni_tpu.ops import moe as moe_ops

    whole = {**TINY, "n_routed_experts": 16, "expert_offset": 0}
    cfg = arch.program_config(whole)
    layer = tfm.init_params(jax.random.PRNGKey(3), cfg, jnp.float32)[
        "layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(4), (37, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = arch._sparse_mlp(layer, h, 2, True, 1.0, 0)
        shared = arch._swiglu(h, layer["shared_expert"]["gate_up"]["w"],
                              layer["shared_expert"]["down"]["w"])
        total = shared
        for offset in range(0, 16, 4):
            held = dict(layer, experts={
                k: v[offset:offset + 4] for k, v in layer["experts"].items()})
            part = arch._sparse_mlp(held, h, 2, True, 1.0, offset) - shared
            served = moe_ops.routed_moe_share(
                h, layer["router"]["w"], held["experts"]["gate_up"],
                held["experts"]["down"], 2, offset, renormalize=True,
                scoring="sigmoid", bias=layer["router"]["e_bias"], scale=1.0)
            np.testing.assert_allclose(served, part, atol=2e-5)
            total = total + part
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    # every token's two picks are somewhere: no share is empty of work
    assert float(jnp.abs(uncut - shared).min(axis=-1).max()) > 0


def test_the_weights_are_the_programs_draw_but_for_experts_and_latent_norms():
    """``init_weights`` is the program's initialiser but for the routed
    experts (related, as GLM-4.7-Flash's) and the latent norms' weights,
    ``(rank / hidden) ** (LATENT_NORM_POWER / 2)`` a kind; everything else
    is the program's draw to the bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_omni_tpu.models.common import transformer as tfm

    cfg = arch.program_config(TINY)
    key = jax.random.PRNGKey(7)
    plain = tfm.init_params(key, cfg=cfg, dtype=jnp.float32)
    ours = arch.init_weights(key, cfg, jnp.float32)
    assert 0.0 < arch.LATENT_NORM_POWER < 1.0
    for a, b, kind in zip(plain["layers"], ours["layers"], cfg.layer_types):
        a, b, lk = dict(a), dict(b), cfg.latent_kind(kind)
        am, bm = dict(a.pop("mla")), dict(b.pop("mla"))
        for name, rank in (("q_a_norm", lk.q_lora_rank),
                           ("kv_a_norm", lk.kv_lora_rank)):
            np.testing.assert_allclose(
                bm.pop(name)["w"], am.pop(name)["w"]
                * (rank / 64) ** (arch.LATENT_NORM_POWER / 2), rtol=1e-6)
        if "experts" in a:
            ours_e, drawn = b.pop("experts"), a.pop("experts")
            for name in ("gate_up", "down"):
                got, was = np.asarray(ours_e[name]), np.asarray(drawn[name])
                want = (1 - 0.0625 ** 2) ** 0.5 * was[:1] + 0.0625 * was
                np.testing.assert_allclose(got, want, atol=1e-6)
                assert 0.99 < np.corrcoef(got[1].ravel(),
                                          got[2].ravel())[0, 1]
        assert all(jax.tree.leaves(jax.tree.map(
            lambda x, y: bool(jnp.array_equal(x, y)), (a, am), (b, bm))))


def test_the_new_metrics_are_data_files_of_the_cell():
    got = {m["name"]: m for m in spec.resolve_cell(CELL)["per_layer"]}
    assert got["indexer_roofline_pct"]["params"] == {"part": "indexer"}
    assert got["attn_window_roofline_pct"]["params"] == {
        "part": "attn_window"}
    assert got["scope_indexer_share_pct"]["params"] == {
        "scopes": ["omni.indexer"]}
    assert got["scope_attn_window_share_pct"]["params"] == {
        "scopes": ["omni.attn_window"]}
    assert got["kv_window_pages_used_mean"]["params"] == {
        "histogram": "kv_window_pages_used"}
    assert got["sparse_keep_pct_mean"]["params"] == {
        "histogram": "sparse_keep_pct"}
    for name in ("indexer_cost", "attn_window_cost", "mla_up_cost"):
        assert callable(getattr(arch, name))
    assert not [n for n in got if n.startswith("dev_") or "gdn" in n]
    for name in ("attn_roofline_pct", "moe_roofline_pct",
                 "mla_up_roofline_pct", "scope_mla_up_share_pct",
                 "kv_pages_used_mean", "device_roofline_ctx_pct",
                 "device_idle_pct.sat", "rows_per_step_mean.sat",
                 "window_compiles"):
        assert name in got
    cell = spec.resolve_cell(CELL)
    assert cell["cell"]["traffic"] == "agent_closed"
    assert {m["name"] for m in cell["end_to_end"]} == {"tok_s", "setup_s"}
    # the six new metrics are the new cell's alone
    for m in BENCH["per_layer"][-6:]:
        assert m["workloads"] == [CELL] and m["moves"] == "tok_s"


def test_a_tiny_cell_is_served_checked_and_counted(tmp_path):
    """``benchmark/run.py --trace 2`` on a tiny cell: served through
    ``build_server``, the reference check (expanded, selection by
    ``lax.top_k``, float32) agrees with the served path to float32
    rounding through chunked prefill and both kinds of page, the engine
    counts exactly the tokens drawn, and the window's pages and the
    selection's share are metrics of the last line."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "benchmark/traffic/tiny_closed.json").write_text(
        json.dumps(TINY_MIX))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][-1], "name": "tiny",
                             "file": "benchmark/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny_dots", "config": "tiny",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_dots")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--workload", "tiny_dots", "--seed", "5000000021", "--seconds", "2",
         "--trace", "2", "--bench-root", str(tmp_path), "--allow-cpu"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ,
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax")})
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    for path in (ROOT / ".benchmark_cache" / "runs").glob(
            "tiny_dots-seed5000000021-*.json"):
        kept = json.loads(path.read_text())
        path.unlink()
    wrong = [ln for ln in done.stdout.splitlines() if "NOT CORRECT" in ln]
    assert len(wrong) == 1 and "not a TPU" in wrong[0], wrong
    assert kept["reference"]["ok"]
    assert kept["reference"]["max_abs_diff"] < 2e-5
    metrics = result["metrics"]
    assert {"tok_s", "setup_s", "step_ms_mean.sat", "window_compiles",
            "kv_pages_used_mean", "kv_window_pages_used_mean",
            "sparse_keep_pct_mean"} <= set(metrics)
    # two clients, a 5-key window and 64-token chunks: a few pages each
    assert 1 <= metrics["kv_window_pages_used_mean"]["value"] <= 12
    assert 0 < metrics["sparse_keep_pct_mean"]["value"] < 60
    kinds = kept["engine1"]["kv"]["kinds"]
    assert kinds["full_attention"]["index_width"] == 16
    assert kinds["sliding_attention"]["row_used"] == 56
    assert kept["engine1"]["window"]["pages_freed_total"] > 0
