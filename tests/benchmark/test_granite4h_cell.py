"""The granite-4.0-h-small architecture module, its configuration and its
cell's metric files: the published numbers and the cut, the bytes, the
counts on a hand-made step, a program constant that drifted, and a tiny
cell run on the CPU through a COPY's ``benchmark/run.py`` (so its cache and
its trace are its own: the workers of a parallel run share the checkout's)
whose last line holds the new metrics."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import readers, spec
from benchmark.archs import granite_moe_hybrid as arch

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
CELL = "granite4h_assist_sat"
CONFIG = spec.load_json(ROOT / "benchmark/configs/granite4h-l10-ep4.json")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW = ["scope_ssd_share_pct", "scope_ssd_state_share_pct",
       "ssd_roofline_pct", "ssd_chunk_fill_pct"]
#: a full decode batch beside one chunk, and the pure decode step
STEP = {"prefill_tokens": 1016, "prefills": 1, "decodes": 127,
        "prefill_ctx": 1016 * 1500, "decode_ctx": 127 * 1000,
        "state_rows": 128}
DECODE = {"prefill_tokens": 0, "prefills": 0, "decodes": 128,
          "prefill_ctx": 0, "decode_ctx": 128 * 1000, "state_rows": 128}

TINY = {
    "arch": "benchmark.archs.granite_moe_hybrid", "name": "granite-tiny",
    "model_type": "granitemoehybrid", "vocab_size": 384, "hidden_size": 64,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 32,
    "shared_intermediate_size": 48, "num_local_experts": 4,
    "published": {"num_local_experts": 8}, "expert_offset": 0,
    "num_experts_per_tok": 3, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 128,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_chunk_size": 16, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False,
    "position_embedding_type": "nope", "hidden_act": "silu",
    "normalization_function": "rmsnorm", "tie_word_embeddings": True,
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 16,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "stage_layers": {"mamba": 3, "attention": 1},
    "serve_dtype": "float32", "tensor_parallel_size": 1,
    # three prefill chunks (budget 64): the state crosses two steps'
    # boundaries and ten chunks of the rule
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
    assert list(CONFIG["reduced"]) == ["num_hidden_layers", "layer_types",
                                       "num_local_experts"]
    assert CONFIG["published"]["num_hidden_layers"] == 40
    assert CONFIG["published"]["num_local_experts"] == 72
    full = CONFIG["published"]["layer_types"]
    assert len(full) == 40 and full.count("mamba") == 36
    assert CONFIG["layer_types"] == full[:10]
    assert CONFIG["layer_types"] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    assert (CONFIG["num_hidden_layers"], CONFIG["num_local_experts"]) == (
        10, 18)
    for key in ("40 -> 10", "the first ten", "72 -> 18"):
        assert any(key in v for v in CONFIG["reduced"].values()), key
    for key in ("assumed", "deployment", "reference_check", "engine"):
        assert CONFIG[key]
    for key in ("gate_before_norm", "state_dtype", "serve_dtype", "weights",
                "tie_word_embeddings", "sampling", "loader", "dt_limits"):
        assert CONFIG["assumed"][key]
    for said in ("four chips of a host share each layer", "18 a chip",
                 "two pipeline stages of 20 layers",
                 "18 rows of a 128-row step", "would give it 71"):
        assert said in CONFIG["deployment"], said
    assert CONFIG["reference_check"] == {"prompt_tokens": 2560,
                                         "new_tokens": 8}
    assert CONFIG["engine"] == {
        "max_num_seqs": 128, "max_num_batched_tokens": 2048,
        "enable_chunked_prefill": True, "max_model_len": 6400,
        "num_pages": 16384, "page_size": 16}
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "granite4h-l10-ep4")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == list(CONFIG["reduced"])
    if CATALOG.exists():
        row = next(r for r in map(json.loads,
                                  CATALOG.read_text().splitlines())
                   if r["source_url"] == CONFIG["source"])
        assert row["name"] == "granite-4.0-h-small"
        for key, value in row["config"].items():
            if key not in CONFIG["reduced"]:
                assert CONFIG[key] == value, key
        assert CONFIG["published"]["layer_types"] == \
            row["config"]["layer_types"]
        # no width is cut: the multipliers and every size as published
        assert (CONFIG["mamba_d_state"], CONFIG["mamba_d_head"],
                CONFIG["mamba_n_heads"], CONFIG["intermediate_size"]) == (
            128, 64, 128, 768)


def test_the_program_agrees_and_a_drifted_constant_is_refused():
    cfg = arch.program_config(CONFIG)
    assert cfg.num_layers == 10
    assert cfg.layer_types.count("mamba") == 9 == cfg.num_state_layers
    assert cfg.layer_types[5] == "full_attention" and cfg.num_kv_layers == 1
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 8, 128)
    assert (cfg.num_experts, cfg.router_experts, cfg.expert_offset,
            cfg.num_experts_per_tok) == (18, 72, 0, 10)
    assert cfg.tie_word_embeddings and cfg.vocab_size == 100352
    assert not cfg.use_rope and cfg.attn_kwargs == {"scale": 1 / 128}
    assert cfg.state_shapes("mamba") == ((3, 8448), (128, 64, 128))
    layout = cfg.kv_layout
    assert (layout.kind, layout.heads, layout.width, layout.pools) == (
        "kv", 8, 128, 2)
    for key, value in (("intermediate_size", 1024), ("mamba_d_state", 64),
                       ("mamba_chunk_size", 128),
                       ("residual_multiplier", 1.0),
                       ("attention_multiplier", 128 ** -0.5),
                       ("embedding_multiplier", 1),
                       ("logits_scaling", 1),
                       ("shared_intermediate_size", 768)):
        with pytest.raises(SystemExit, match="differs from the published"):
            arch.program_config({**CONFIG, key: value})
    with pytest.raises(SystemExit, match="differs from the published"):
        arch.program_config({**CONFIG, "layer_types": ["mamba"] * 10,
                             "stage_layers": {"mamba": 10, "attention": 0}})
    with pytest.raises(SystemExit, match="stage_layers"):
        arch.program_config({**CONFIG, "num_hidden_layers": 9})
    for key, value in (("mamba_n_groups", 8), ("mamba_conv_bias", False),
                       ("position_embedding_type", "rope"),
                       ("tie_word_embeddings", False)):
        with pytest.raises(SystemExit, match=key):
            arch.program_config({**CONFIG, key: value})
    with pytest.raises(SystemExit, match="mamba_expand"):
        arch.program_config({**CONFIG, "mamba_d_head": 32})


def test_the_bytes_are_the_issues():
    """ISSUE 42's arithmetic, from the file's keys."""
    costs = arch.model_costs(CONFIG)
    assert costs["mix"] == {"mamba": 9, "attention": 1}
    h = 4096
    mamba = h * (8192 + 8448 + 128) + 8192 * h + 5 * 8448 + 3 * 128 + 8192
    attn = 2 * h * h + 2 * h * 1024
    shared, router, expert = 3 * h * 1536, h * 72, 3 * h * 768
    assert round(mamba / 1e6, 2) == 102.29 and costs["mamba_params"] == mamba
    assert round(attn / 1e6, 2) == 41.94 and costs["attn_params"] == attn
    assert (round(shared / 1e6, 2), round(router / 1e6, 2),
            round(expert / 1e6, 2)) == (18.87, 0.29, 9.44)
    # whole: 40 layers of 72 experts, the embedding once: 32.2 B parameters
    whole = 36 * mamba + 4 * attn + 40 * (shared + router + 72 * expert) \
        + h * 100352
    assert round(whole / 1e9, 1) == 32.2
    # here: nine mamba layers and one attention layer of 18 experts
    here = 9 * mamba + attn + 10 * (shared + router + 18 * expert) \
        + h * 100352
    assert round(2 * here / 1e9, 2) == 6.53
    # a sequence's state: 4.19 MB + 0.10 MB a layer, 38.66 MB over nine;
    # 128 slots 4.95 GB; the attention layer's pages 4096 B a token
    assert costs["state_bytes"] == 128 * 64 * 128 * 4 == 4_194_304
    assert costs["conv_state_bytes"] == 3 * 8448 * 4
    a_slot = 9 * (costs["state_bytes"] + costs["conv_state_bytes"])
    assert round(a_slot / 1e6, 2) == 38.66
    assert round(128 * a_slot / 1e9, 2) == 4.95
    assert costs["kv_bytes_per_pos"] == 2 * 8 * 128 * 2 == 4096
    assert round(16384 * 16 * 4096 / 1e9, 2) == 1.07
    # the longest request fits the model's length; the pool holds what 128
    # of the mean do
    assert 4096 + 2048 + 18 <= CONFIG["engine"]["max_model_len"]
    assert 128 * 1700 < 16384 * 16


def test_the_counts_are_the_stated_algorithms():
    """Hand-worked at the published widths."""
    costs = arch.model_costs(CONFIG)
    h, tokens = 4096, 1016 + 127
    expert = 3 * h * 768
    # the rule by the recurrent algorithm: 5 x 64 x 128 a head a token; the
    # taps, bias and activations over 8448 channels; skip, gate and norm
    per_token = 128 * 5 * 64 * 128 + (2 * 4 + 6) * 8448 + 12 * 8192
    assert costs["ssd_flops_per_token"] == per_token
    flops, nbytes = arch.ssd_cost(STEP, CONFIG)
    assert flops == 9 * tokens * per_token
    state = 2 * 128 * (4_194_304 + 101_376)
    rows = tokens * (4 * (8192 + 8448 + 128) + 2 * 8192)
    small = 2 * (5 * 8448 + 3 * 128 + 8192)
    assert nbytes == 9 * (state + rows + small)
    # a pure decode step: 128 live rows' state once in and once out is
    # nearly all of it, 9.9 GB a step
    d_flops, d_bytes = arch.ssd_cost(DECODE, CONFIG)
    assert 9 * state / d_bytes > 0.99 and 9.8e9 < d_bytes < 10.0e9
    assert d_flops == 9 * 128 * per_token
    deep = {**CONFIG, "num_hidden_layers": 20}  # two such periods
    assert arch.ssd_cost(STEP, deep) == pytest.approx(
        (2 * flops, 2 * nbytes))
    # attention: 4 x 128 x ctx operations a query head, one layer
    a_flops, a_bytes = arch.attn_cost(STEP, CONFIG)
    assert a_flops == 32 * 4 * 128 * (STEP["prefill_ctx"]
                                      + STEP["decode_ctx"])
    assert a_bytes > 4096 * STEP["decode_ctx"]
    assert a_bytes < 4096 * (STEP["decode_ctx"] + 1500 + 508) \
        + 2 * 2 * tokens * 4096 + 1
    # every layer's MLP: the router at 72, 2.5 picks landing here, the
    # shared SwiGLU
    m_flops, m_bytes = arch.moe_cost(STEP, CONFIG)
    assert m_flops == 10 * tokens * 2 * (h * 72 + 3 * h * 1536
                                         + 2.5 * expert)
    # 1 143 tokens touch all 18 held experts: read once a layer
    assert m_bytes == pytest.approx(
        10 * (2 * 18 * expert + 2 * (h * 72 + 3 * h * 1536)
              + 2 * 2 * tokens * h), rel=1e-6)
    # 128 tokens of a decode step touch all 18 too (1 - (62/72)^128)
    assert arch._touched(costs, 128) == pytest.approx(18.0, rel=1e-6)
    # the whole step holds each of its parts and the head
    s_flops, s_bytes = arch.step_cost(STEP, CONFIG, 1500.0, 1000.0)
    assert s_flops > flops + a_flops + m_flops
    # 6.53 GB of weights, the state 9.9 GB, pages
    assert 16.9e9 < s_bytes < 17.3e9
    assert arch.step_cost({**STEP, "prefill_tokens": 0, "decodes": 0},
                          CONFIG, 0.0, 0.0) is None


def test_the_weights_are_the_programs_draw_but_for_the_stated_gains():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_omni_tpu.models.common import transformer as tfm

    cfg = arch.program_config(TINY)
    key = jax.random.PRNGKey(7)
    plain = tfm.init_params(key, cfg=cfg, dtype=jnp.bfloat16)
    ours = arch.init_weights(key, cfg, jnp.bfloat16)
    assert "lm_head" not in ours

    def times(ours, plain, gain):
        return np.array_equal(np.asarray(ours, np.float32),
                              np.asarray(plain, np.float32) * gain)

    assert times(ours["embed"]["w"], plain["embed"]["w"], arch.EMBED_SHRINK)
    assert np.all(np.asarray(ours["final_norm"]["w"], np.float32)
                  == arch.HEAD_GAIN)
    for a, b in zip(plain["layers"], ours["layers"]):
        a, b = dict(a), dict(b)
        assert times(b["router"]["w"], a["router"]["w"], arch.ROUTER_GAIN)
        got = np.asarray(b["experts"]["down"], np.float32)
        corr = np.corrcoef(got[1].ravel(), got[2].ravel())[0, 1]
        assert 0.99 < corr < 0.999
        for name in ("q_proj", "k_proj"):
            if name in a:
                assert times(b[name]["w"], a[name]["w"], arch.QK_GAIN)
                del a[name], b[name]
        del a["experts"], b["experts"], a["router"], b["router"]
        assert all(jax.tree.leaves(jax.tree.map(
            lambda x, y: bool(jnp.array_equal(x, y)), a, b)))
    mb = ours["layers"][0]["mamba"]
    a_neg = np.exp(np.asarray(mb["A_log"], np.float32))
    assert a_neg.min() >= 0.99 and a_neg.max() <= 16.1
    dt = np.log1p(np.exp(np.asarray(mb["dt_bias"], np.float32)))
    assert dt.min() >= 0.9e-3 and dt.max() <= 0.11
    assert np.all(np.asarray(mb["D"], np.float32) == 1.0)


def test_the_cell_and_its_four_metrics_resolve_by_name():
    cell = spec.resolve_cell(CELL)
    assert cell["cell"] == {
        "name": CELL, "config": "granite4h-l10-ep4",
        "traffic": "assist128_closed", "chips": 1,
        "why": cell["cell"]["why"]}
    assert {m["name"] for m in cell["end_to_end"]} == {"tok_s", "setup_s"}
    got = {m["name"]: m for m in cell["per_layer"]}
    assert got["ssd_roofline_pct"]["params"] == {"part": "ssd"}
    assert got["scope_ssd_share_pct"]["params"] == {"scopes": ["omni.ssd"]}
    assert got["scope_ssd_state_share_pct"]["params"] == {
        "scopes": ["omni.ssd_state"]}
    assert got["ssd_chunk_fill_pct"]["params"] == {
        "histogram": "ssd_chunk_fill"}
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW[0])
    # this PR's four entries stand together, in the issue's order, after
    # every entry the parent had
    assert names[at:at + 4] == NEW and at >= 61
    for name in NEW:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"][0] == CELL
        assert (entry["moves"], entry["unit"]) == ("tok_s", "%")
        assert callable(readers.resolve(got[name]["reader"]))
    assert [(m["layer"], m["source"], m["better"])
            for m in BENCH["per_layer"][at:at + 4]] == [
        ("kernels", "device_trace", "lower"),
        ("kernels", "device_trace", "lower"),
        ("kernels", "device_trace", "higher"),
        ("runner", "program_counter", "higher")]
    assert callable(arch.ssd_cost)
    # what the other saturated cells report, and the two occupancies
    for name in ("attn_roofline_pct", "moe_roofline_pct",
                 "device_roofline_ctx_pct", "scope_unnamed_share_pct",
                 "rows_per_step_mean.sat", "padding_eff_pct",
                 "assemble_pack_ms.sat", "step_device_ms.sat",
                 "device_idle_pct.sat", "window_compiles",
                 "state_slots_mean", "kv_pages_used_mean"):
        assert name in got, name
    assert not [n for n in got if n.startswith("dev_") or "gdn" in n
                or "mla" in n or "attn_window" in n or "shortconv" in n
                or n.endswith(".rate")]
    # every list this cell joined kept the cells it had, in their order
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    before = ["thinker_chat_sat", "thinker_doc_sat", "qwen3next_longdoc_sat",
              "glm47flash_agent_sat", "dots3note_agent_sat",
              "lfm2_assist_sat"]
    assert e2e["tok_s"]["workloads"][:7] == before + [CELL]
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", []) and m["name"] not in NEW:
            cells = m["workloads"]
            assert cells.index(CELL) == len(
                [c for c in cells if c in before]), m["name"]
            assert set(cells) <= set(e2e[m["moves"]]["workloads"])
    mix = cell["traffic"]
    assert (mix["loop"], mix["clients"], mix["requests"]) == (
        "closed", 128, 256)
    assert mix["clients"] == CONFIG["engine"]["max_num_seqs"]
    base = spec.load_json(ROOT / "benchmark/traffic/assist_closed.json")
    for key in ("prompt_tokens", "output_tokens", "order_by", "order_seed",
                "sampling", "request_timeout_s"):
        assert mix[key] == base[key], key


def test_a_tiny_cell_is_served_checked_and_counted(tmp_path):
    """A copy's ``benchmark/run.py --trace 2`` on a tiny Granite-H cell:
    the harness serves it through ``build_server``, the reference check
    agrees with the served path to float32 rounding through chunked
    prefill, the slots updated in place and the pages, the engine counts
    exactly the tokens drawn, and the last line holds the cell's
    metrics."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "benchmark/traffic/tiny_closed.json").write_text(
        json.dumps(TINY_MIX))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][-1], "name": "tiny",
                             "file": "benchmark/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny_granite", "config": "tiny",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_granite")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "tiny_granite", "--seed", "5000000042", "--seconds",
         "2", "--trace", "2", "--allow-cpu"],
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
            "ssd_chunk_fill_pct"} <= set(metrics)
    assert 1 <= metrics["state_slots_mean"]["value"] <= 3
    assert 50 < metrics["ssd_chunk_fill_pct"]["value"] <= 100
    state = kept["engine1"]["state"]
    assert state["slots_total"] == 4
    per_layer = 4 * (3 * (128 + 256) + 4 * 32 * 128)
    assert state["bytes_per_slot_layer"] == per_layer
    assert state["bytes_per_slot"] == 3 * per_layer
