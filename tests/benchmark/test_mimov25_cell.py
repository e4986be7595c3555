"""The MiMo-V2.5 architecture module, its configuration and its cell: the
published numbers against the catalog's row pinned beside them, the cut,
the bytes, the counts on a hand-made step (window keys capped at 128, keys
of 192 beside values of 128 USED, whatever lanes the pools pad them to),
the seeded draw, the appended entries and the three new metric files, and a
tiny cell run on the CPU through a COPY's ``benchmark/run.py`` (so its
cache and its trace are its own).

In the form that survives a later append (ROADMAP D11): positions by
``index``, lists as prefix or ``<=`` checks, never ``[-1]`` or ``==`` on a
list that a later PR extends."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import readers, spec
from benchmark.archs import mimo_v2 as arch

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
CELL = "mimov25_agent_sat"
NAME = "mimov25-l7-ep16"
CONFIG = spec.load_json(ROOT / f"benchmark/configs/{NAME}.json")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
SOURCE = "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts"]
NEW = {"scope_proj_share_pct": ("scope_share_pct", {"scopes": ["omni.proj"]}),
       "proj_roofline_pct": ("roofline_ctx_pct", {"part": "proj"}),
       "scope_mlp_share_pct": ("scope_share_pct", {"scopes": ["omni.mlp"]})}
#: the catalog's row, pinned: the numbers of its ``config`` (the two lists
#: by their sums and lengths)
PUBLISHED = {
    "attention_bias": False, "attention_chunk_size": 128,
    "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192,
    "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
    "hidden_size": 4096, "hybrid_block_size": None,
    "intermediate_size": 16384, "layernorm_epsilon": 1e-05,
    "max_position_embeddings": 1048576, "model_type": "mimo_v2",
    "moe_intermediate_size": 2048, "n_group": 1, "n_shared_experts": None,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_key_value_heads": 4,
    "partial_rotary_factor": 0.334,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "routed_scaling_factor": None,
    "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_size": 128, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152576}
PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
#: the cells that were on the lists this cell joined, in their order (the
#: accepted benchmark at PR 53); a later PR appends after this cell
SAT = ["thinker_chat_sat", "thinker_doc_sat", "qwen3next_longdoc_sat",
       "glm47flash_agent_sat", "dots3note_agent_sat", "lfm2_assist_sat",
       "granite4h_assist_sat", "nemotron3s_assist_sat",
       "commandaplus_agent_sat"]
ALL = SAT[:1] + ["thinker_chat_rate"] + SAT[1:]
WINDOWED = ["dots3note_agent_sat", "commandaplus_agent_sat"]
HAD = {
    "kv_pages_used_mean": SAT[3:],
    **{name: WINDOWED for name in (
        "kv_window_pages_used_mean", "scope_attn_window_share_pct",
        "attn_window_roofline_pct", "window_keep_pct_mean")},
    **{name: SAT for name in (
        "tok_s", "rows_per_step_mean.sat", "padding_eff_pct",
        "window_compiles", "step_ms_mean.sat", "device_idle_pct.sat",
        "scope_attn_share_pct", "scope_moe_share_pct",
        "scope_sampler_share_pct", "scope_kv_write_share_pct",
        "scope_lm_head_share_pct", "scope_unnamed_share_pct",
        "idle_scheduler_pct", "idle_runner_pct", "idle_engine_pct",
        "idle_front_pct", "idle_unnamed_pct", "device_roofline_ctx_pct",
        "attn_roofline_pct", "moe_roofline_pct", "assemble_pack_ms.sat",
        "assemble_sampling_ms.sat", "assemble_put_ms.sat",
        "busy_wait_ms.sat", "host_unblocked_pct.sat", "stall_share_pct.sat",
        "step_device_ms.sat")},
    **{name: ALL for name in (
        "setup_start_s", "setup_weights_s", "setup_cache_s",
        "setup_warmup_s", "setup_serve_s", "setup_harness_s",
        "warmup_trace_s", "warmup_lower_s", "warmup_backend_s",
        "warmup_run_s", "warmup_executables", "warmup_cache_miss")},
}
#: one 2 048-token chunk that ends an 8 192-token prompt beside 23 decode
#: rows at 9 000 of context, as ``LLMEngine._record_step`` would stamp it
STEP = {"prefill_tokens": 2048, "prefills": 1, "decodes": 23,
        "prefill_ctx": sum(range(6145, 8193)), "decode_ctx": 23 * 9000,
        "prefill_ctx_window": 2048 * 128, "decode_ctx_window": 23 * 128}

TINY = {
    **{k: v for k, v in CONFIG.items() if k in PUBLISHED},
    "arch": "benchmark.archs.mimo_v2", "name": "mimo-tiny",
    "vocab_size": 384, "hidden_size": 64, "num_hidden_layers": 5,
    "hybrid_layer_pattern": [0, 1, 1, 1, 0],
    "moe_layer_freq": [0, 1, 1, 1, 1],
    "num_attention_heads": 4, "swa_num_attention_heads": 4,
    "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "published": {"n_routed_experts": 8},
    "expert_offset": 4, "num_experts_per_tok": 2, "sliding_window": 21,
    "sliding_window_size": 21,
    "stage_layers": {"sliding": 3, "full": 2, "dense": 1, "sparse": 4},
    "serve_dtype": "float32", "tensor_parallel_size": 1,
    # three prefill chunks (budget 64) and seven windows: by the compared
    # tokens eight window pages have been given back
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
    assert list(CONFIG["reduced"]) == REDUCED
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    assert CONFIG["published"] == {
        "num_hidden_layers": 48, "hybrid_layer_pattern": PATTERN,
        "moe_layer_freq": [0] + [1] * 47, "n_routed_experts": 256}
    # published layer 0 and layers 6-11: the first whole period of six
    # after the irregular first five
    assert CONFIG["hybrid_layer_pattern"] == [PATTERN[0]] + PATTERN[6:12] \
        == [0, 1, 1, 1, 1, 1, 0]
    assert CONFIG["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["expert_offset"]) == (7, 16, 0)
    for key, said in (("num_hidden_layers", "48 -> 7"),
                      ("n_routed_experts", "256 -> 16"),
                      ("hybrid_layer_pattern", "0 and 6-11"),
                      ("moe_layer_freq", "0 and 6-11")):
        assert said in CONFIG["reduced"][key], key
    # the floors of a model_config cut: a whole period and four layers
    # after the leading dense one, 8 experts a layer, an eighth of the
    # vocabulary (here: all of it)
    assert CONFIG["hybrid_layer_pattern"][1:] == PATTERN[6:12]
    assert CONFIG["n_routed_experts"] >= 8
    for key in ("assumed", "deployment", "reference_check", "engine"):
        assert CONFIG[key]
    for key in ("hybrid_layer_pattern", "sink", "value_scale", "rotary",
                "window", "attention_chunk_size",
                "attention_projection_layout", "router", "sizes",
                "serve_dtype", "cache_layout", "towers", "loader",
                "weights", "sampling", "vocabulary", "reference_check"):
        assert CONFIG["assumed"][key], key
    assert "DENOMINATOR" in CONFIG["assumed"]["sink"]
    assert "t - 128 < s <= t" in CONFIG["assumed"]["window"]
    assert "FIRST 64" in CONFIG["assumed"]["rotary"]
    for said in ("[4, 16384, 16, 256]", "[8, 3289, 16, 128]", "3 072 B",
                 "2 560", "6 144 B", "5 120", "a sixth"):
        assert said in CONFIG["assumed"]["cache_layout"], said
    for said in ("sixteen chips share each layer", "16 a chip", "B/32 rows",
                 "B/2", "sixteen times their share", "2 of 7", "9 of 48",
                 "1 of 48", "240 absent experts",
                 "nothing stands in for the fifteen absent chips"):
        assert said in CONFIG["deployment"], said
    assert CONFIG["stage_layers"] == {"sliding": 5, "full": 2, "dense": 1,
                                      "sparse": 6}
    # two chunk boundaries past the window, over 250 window pages given
    # back, the largest of 64 tokens
    assert CONFIG["reference_check"] == {"prompt_tokens": 4224,
                                         "new_tokens": 64}
    assert (4224 - 128) // 16 > 250
    assert CONFIG["engine"] == {
        "max_num_seqs": 24, "max_num_batched_tokens": 2048,
        "enable_chunked_prefill": True, "max_model_len": 17664,
        "num_pages": 16384, "page_size": 16}
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200
    if CATALOG.exists():
        row = next(r for r in map(json.loads,
                                  CATALOG.read_text().splitlines())
                   if r["source_url"] == SOURCE)
        assert row["name"] == "MiMo-V2.5"
        assert {k: v for k, v in row["config"].items()
                if k not in REDUCED} == PUBLISHED
        for key in REDUCED:
            assert CONFIG["published"][key] == row["config"][key], key


def test_the_program_agrees_with_the_file():
    cfg = arch.program_config(CONFIG)
    assert cfg.layer_types == ("full_attention",) + (
        "sliding_attention",) * 5 + ("full_attention",)
    assert (cfg.num_experts, cfg.router_experts, cfg.expert_offset) == (
        16, 256, 0)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.rotary_dim,
            cfg.sliding_window) == (192, 128, 64, 128)
    with pytest.raises(SystemExit, match="tensor-parallel"):
        arch.param_specs(None)
    with pytest.raises(SystemExit, match="tensor-parallel"):
        arch.reference_logprobs({}, CONFIG, [1, 2], 1, tp=4)
    with pytest.raises(SystemExit, match="stage_layers"):
        arch.program_config({**CONFIG, "stage_layers": {
            "sliding": 4, "full": 3, "dense": 1, "sparse": 6}})


def test_the_bytes_are_the_issues():
    costs = arch.model_costs(CONFIG)
    sliding = 4096 * (64 * 192 + 8 * 320) + 64 * 128 * 4096
    full = 4096 * (64 * 192 + 4 * 320) + 64 * 128 * 4096
    assert (sliding, full) == (94_371_840, 89_128_960)
    assert costs["proj_params"] == 5 * sliding + 2 * full
    assert costs["expert_params"] == 25_165_824
    assert costs["dense_params"] == 201_326_592
    assert costs["router_params"] == 1_048_576
    # 4 523 M parameters, 9.05 GB
    params = (costs["proj_params"] + costs["dense_params"]
              + 6 * (costs["router_params"] + 16 * costs["expert_params"])
              + 2 * 152576 * 4096)
    assert 4.52e9 < params < 4.53e9 and 9.04e9 < 2 * params < 9.06e9
    # the pools, held (a key in 256 lanes) and used (a key of 192)
    assert costs["kv_bytes_per_pos"] == {"sliding": 5120, "full": 2560}
    held_full = 2 * 4 * (256 + 128) * 2 * 16384 * 16
    held_window = 5 * 8 * (256 + 128) * 2 * 3289 * 16
    assert held_full == pytest.approx(1.61e9, rel=0.01)
    assert held_window == pytest.approx(1.62e9, rel=0.01)
    assert held_full * 320 / 384 == pytest.approx(1.34e9, rel=0.01)
    # half a pick a token lands here
    assert costs["top_k"] * costs["held"] / costs["width"] == 0.5


def test_the_counts_are_the_stated_algorithms():
    costs = arch.model_costs(CONFIG)
    tokens = 2048 + 23
    # the sliding layers: at most 128 keys a token, keys of 192 and values
    # of 128 of 8 kv heads, 2 x (192 + 128) operations a pair a head
    flops, nbytes = arch.attn_window_cost(STEP, CONFIG)
    assert flops == 5 * 2 * 64 * 320 * (2048 * 128 + 23 * 128)
    assert nbytes == 5 * (5120 * (23 * 128 + 128 + 2048)
                          + tokens * 2 * 64 * 320)
    # the full layers: every key, 4 kv heads
    flops, nbytes = arch.attn_cost(STEP, CONFIG)
    assert flops == 2 * 2 * 64 * 320 * (STEP["prefill_ctx"]
                                        + STEP["decode_ctx"])
    reach = STEP["prefill_ctx"] / 2048 + 1024
    assert nbytes == 2 * (2560 * (23 * 9000 + reach)
                          + tokens * 2 * 64 * 320)
    # the window's reads are some 2% of what the full layers' rows see
    assert 128 / 8192 < 0.02
    flops, nbytes = arch.proj_cost(STEP, CONFIG)
    assert flops == 2 * tokens * costs["proj_params"]
    assert nbytes > 2 * costs["proj_params"]
    assert arch.proj_cost({**STEP, "prefill_tokens": 0, "decodes": 0},
                          CONFIG) is None
    flops, nbytes = arch.moe_cost(STEP, CONFIG)
    assert flops == 6 * tokens * 2 * (1_048_576 + 0.5 * 25_165_824)
    assert 6 * 2 * 1_048_576 < nbytes < 6 * (2 * 1_048_576
                                             + 16.01 * 50_331_648
                                             + 4 * tokens * 4096)
    whole = arch.step_cost(STEP, CONFIG, 7000.0, 9000.0)
    parts = [arch.attn_cost(STEP, CONFIG), arch.attn_window_cost(STEP, CONFIG),
             arch.moe_cost(STEP, CONFIG), arch.proj_cost(STEP, CONFIG)]
    assert whole[0] > sum(p[0] for p in parts)
    assert arch.step_cost({**STEP, "prefill_tokens": 0, "decodes": 0},
                          CONFIG, 0.0, 0.0) is None
    # linear in depth, as the harness's contract has it
    twice = {**CONFIG, "num_hidden_layers": 14}
    for fn in (arch.attn_cost, arch.attn_window_cost, arch.moe_cost):
        assert fn(STEP, twice)[0] == 2 * fn(STEP, CONFIG)[0]


def test_the_weights_are_the_programs_draw_but_for_the_stated_changes():
    import jax
    import jax.numpy as jnp

    from vllm_omni_tpu.models.common import transformer as tfm

    assert (arch.QK_GAIN, arch.SINK_MASS, arch.ROUTED_DOWN_GAIN,
            arch.HEAD_GAIN, arch.EMBED_GAIN, arch.ROUTER_FAMILY,
            arch.ROUTER_OWN_SHARE) == (
        (4.0, 2.0, 2.0, 2.0, 2.0, 2.0, 3.0), (0.1, 0.5), 0.0625, 3.0, 16.0,
        16, 0.25)
    tiny = {k: v for k, v in TINY.items()
            if k not in ("published", "expert_offset")}
    tiny["n_routed_experts"] = 32  # two families of 16
    cfg = arch.program_config(tiny)
    key = jax.random.PRNGKey(3)
    plain = tfm.init_params(key, cfg, jnp.float32)
    drawn = arch.init_weights(key, cfg, jnp.float32)
    for i, (a, b) in enumerate(zip(plain["layers"], drawn["layers"])):
        gain = arch.QK_GAIN[i]
        for name in ("q_proj", "k_proj"):
            assert jnp.allclose(b[name]["w"], gain * a[name]["w"])
        for name in ("v_proj", "o_proj"):
            assert jnp.array_equal(b[name]["w"], a[name]["w"])
        if "sinks" in b:
            # log(window) + sigma^2 / 2 + logit(mass), mass in 0.1-0.5
            base = jnp.log(21.0) + (gain * gain / 3) ** 2 / 2
            odds = b["sinks"] - base
            assert b["sinks"].dtype == jnp.float32
            assert float(odds.min()) >= -2.2 and float(odds.max()) <= 0.0
    assert jnp.array_equal(drawn["lm_head"]["w"],
                           3.0 * plain["lm_head"]["w"])
    assert jnp.array_equal(drawn["embed"]["w"], 16.0 * plain["embed"]["w"])
    assert jnp.array_equal(drawn["layers"][0]["gate_up"]["w"],
                           plain["layers"][0]["gate_up"]["w"])
    router = drawn["layers"][1]["router"]["w"]
    # experts of a family share most of their direction
    unit = router / jnp.linalg.norm(router, axis=0)
    assert float(unit[:, 0] @ unit[:, 2]) > 0.8
    assert abs(float(unit[:, 0] @ unit[:, 1])) < 0.5


def test_the_cell_and_its_metrics_resolve_by_name():
    cell = spec.resolve_cell(CELL)
    assert cell["cell"] == {
        "name": CELL, "config": NAME, "traffic": "agent_closed", "chips": 1,
        "why": cell["cell"]["why"]}
    assert len(cell["cell"]["why"]) <= 200
    for said in ("24 closed clients", "128 window", "8 kv heads", "4",
                 "16x share"):
        assert said in cell["cell"]["why"], said
    assert {m["name"] for m in cell["end_to_end"]} == {"tok_s", "setup_s"}
    got = {m["name"]: m for m in cell["per_layer"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    for name, (reader, params) in NEW.items():
        assert got[name]["reader"] == f"benchmark.span_readers:{reader}"
        assert got[name]["params"] == params
        assert callable(readers.resolve(got[name]["reader"]))
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry == {
            "name": name, "unit": "%", "better": entry["better"],
            "source": "device_trace", "layer": "kernels", "moves": "tok_s",
            "workloads": entry["workloads"]}
        # this cell first on its list; what the parent had stands before
        assert entry["workloads"][:1] == [CELL]
        assert names.index(name) > names.index("warmup_cache_miss")
        assert json.loads((ROOT / "benchmark/layer_metrics"
                           / f"{name}.json").read_text()) == {
            "reader": got[name]["reader"], "params": params}
    assert callable(arch.proj_cost)
    # PREFIX checks: each list holds the cells it had, in their order,
    # then this one; a later PR appends after it
    listed = {m["name"]: m["workloads"]
              for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [])}
    assert set(HAD) | set(NEW) <= set(listed)
    for name, before in HAD.items():
        assert listed[name][:len(before) + 1] == before + [CELL], name
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) == 10 and cells[:10] == ALL
    assert [c["name"] for c in BENCH["configs"]].index(NAME) == 9
    # no second cell of this configuration came with it
    assert [w["name"] for w in BENCH["workloads"][:11]
            if w["config"] == NAME] == [CELL]
    # the mix of the three other long-context cells, unedited
    for other in ("glm47flash_agent_sat", "dots3note_agent_sat",
                  "commandaplus_agent_sat"):
        assert cell["traffic"] == spec.resolve_cell(other)["traffic"]
    assert cell["traffic"]["prompt_tokens"] == {
        "median": 8192, "sigma": 0.4, "min": 2048, "max": 16384}


def test_a_tiny_cell_is_served_checked_and_counted(tmp_path):
    """A copy's ``benchmark/run.py --trace 2`` on a tiny MiMo cell (an
    expert share: experts 4-7 of an 8-wide router; the published head
    widths, so that K rows are 256 lanes and V rows 128): the harness
    serves it through ``build_server``, the reference check agrees with the
    served path to float32 rounding through chunked prefill over both kinds
    of page with eight window pages given back, and the last line holds
    the cell's metrics; the engine's ``kv`` record states the pools a
    kind."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "benchmark/traffic/tiny_closed.json").write_text(
        json.dumps(TINY_MIX))
    bench = json.loads(json.dumps(BENCH))
    (ours,) = [c for c in bench["configs"] if c["name"] == NAME]
    bench["configs"].append({**ours, "name": "tiny",
                             "file": "benchmark/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny_mimo", "config": "tiny",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_mimo")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "tiny_mimo", "--seed", "5500000055",
         "--seconds", "2", "--trace", "2", "--allow-cpu"],
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
    assert kept["reference"]["max_abs_diff"] < 3e-5
    metrics = result["metrics"]
    assert {"tok_s", "setup_s", "step_ms_mean.sat", "padding_eff_pct",
            "window_compiles", "kv_pages_used_mean",
            "kv_window_pages_used_mean", "window_keep_pct_mean"} <= set(
        metrics)
    assert 10 < metrics["window_keep_pct_mean"]["value"] < 70
    window = kept["engine1"]["window"]
    assert window["window"] == 21 and window["pages_freed_total"] > 8
    kinds = kept["engine1"]["kv"]["kinds"]
    assert (kinds["sliding_attention"]["layers"],
            kinds["full_attention"]["layers"]) == (3, 2)
    for kind, heads in (("sliding_attention", 2), ("full_attention", 1)):
        rec = kinds[kind]
        assert (rec["heads"], rec["row_width"], rec["row_used"],
                rec["value_width"]) == (heads, 256, 192, 128)
        assert rec["bytes_per_token_layer"] == heads * 384 * 4
    steps = [s for s in kept["steps"] if s["prefill_tokens"] + s["decodes"]]
    assert steps and all(
        s["prefill_ctx_window"] + s["decode_ctx_window"]
        <= s["prefill_ctx"] + s["decode_ctx"] for s in steps)
