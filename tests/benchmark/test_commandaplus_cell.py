"""The command-a-plus-05-2026 architecture module, its configuration and its
cell: the published numbers and the cut, the bytes, the counts on a
hand-made step (window keys capped at 4 096, 4 096 B a token a layer), a
program constant that drifted and what the module refuses by name, the
seeded draw, the metric lists (each holds the cells it had, in their
order, then this one: a PREFIX check, which a later appended cell leaves
green; ``test_lfm2moe_cell.py``'s docstring has why), and a tiny cell run on
the CPU through a COPY's ``benchmark/run.py`` (so its cache and its trace
are its own) whose last line holds the new metric."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import readers, spec
from benchmark.archs import cohere2_moe as arch

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
CELL = "commandaplus_agent_sat"
NAME = "commandaplus-l4-ep8"
CONFIG = spec.load_json(ROOT / f"benchmark/configs/{NAME}.json")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW = "window_keep_pct_mean"
REDUCED = ["num_hidden_layers", "layer_types", "num_experts"]
#: the draw the chip's readings decided (benchmark/archs/cohere2_moe.py)
QK_GAIN = (3.0, 2.0, 2.0, 3.0)
ROUTED_DOWN_GAIN, FINAL_NORM_GAIN = 0.0625, 1.125
#: the metric lists this cell joined, each with the cells it had before,
#: in their order (the accepted benchmark at PR 49)
SAT = ["thinker_chat_sat", "thinker_doc_sat", "qwen3next_longdoc_sat",
       "glm47flash_agent_sat", "dots3note_agent_sat", "lfm2_assist_sat",
       "granite4h_assist_sat", "nemotron3s_assist_sat"]
HAD = {
    "kv_pages_used_mean": SAT[3:],
    "kv_window_pages_used_mean": ["dots3note_agent_sat"],
    "scope_attn_window_share_pct": ["dots3note_agent_sat"],
    "attn_window_roofline_pct": ["dots3note_agent_sat"],
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
}
#: one 2 048-token chunk that ends an 8 192-token prompt beside 23 decode
#: rows at 9 000 of context, as ``LLMEngine._record_step`` would stamp it
STEP = {"prefill_tokens": 2048, "prefills": 1, "decodes": 23,
        "prefill_ctx": sum(range(6145, 8193)), "decode_ctx": 23 * 9000,
        "prefill_ctx_window": 2048 * 4096, "decode_ctx_window": 23 * 4096}

TINY = {
    **{k: v for k, v in CONFIG.items() if k in arch.BUILT},
    "arch": "benchmark.archs.cohere2_moe", "name": "commandaplus-tiny",
    "model_type": "cohere2_moe", "vocab_size": 384, "hidden_size": 64,
    "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 32, "num_experts": 4,
    "published": {"num_experts": 8}, "expert_offset": 4,
    "num_experts_per_tok": 2, "num_shared_experts": 4,
    "layer_norm_eps": 1e-5, "rope_theta": 50000, "sliding_window": 21,
    "stage_layers": {"sliding": 3, "full": 1},
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
    whole = CONFIG["published"]["layer_types"]
    assert CONFIG["published"] == {"num_hidden_layers": 32,
                                   "num_experts": 128, "layer_types": whole}
    assert len(whole) == 32 and whole == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 8
    # published layers 0-3: one whole period
    assert CONFIG["layer_types"] == whole[:4]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["expert_offset"]) == (4, 16, 0)
    for key, said in (("num_hidden_layers", "32 -> 4"),
                      ("num_experts", "128 -> 16"),
                      ("layer_types", "first four")):
        assert said in CONFIG["reduced"][key], key
    # the floors of a model_config cut: a whole period and four layers, 8
    # experts a layer, an eighth of the vocabulary (here: all of it)
    assert CONFIG["num_experts"] >= 8 and CONFIG["vocab_size"] == 262144
    # every published width, unchanged
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["head_dim"],
            CONFIG["intermediate_size"], CONFIG["num_experts_per_tok"],
            CONFIG["num_shared_experts"], CONFIG["sliding_window"],
            CONFIG["rope_theta"], CONFIG["layer_norm_eps"],
            CONFIG["rms_norm_eps"]) == (
        4096, 128, 8, 128, 4096, 8, 4, 4096, 50000, 1e-5, None)
    for key in ("assumed", "deployment", "reference_check", "engine"):
        assert CONFIG[key]
    for key in ("shared_expert_combination_strategy", "window", "rotary",
                "intermediate_size", "first_k_dense_replace", "router",
                "serve_dtype", "cache_layout", "towers", "loader", "weights",
                "sampling", "vocabulary"):
        assert CONFIG["assumed"][key], key
    assert "pairs_to_halves" in CONFIG["assumed"]["rotary"]
    assert "mean of the four" in CONFIG["assumed"][
        "shared_expert_combination_strategy"]
    assert "t - 4096 < s <= t" in CONFIG["assumed"]["window"]
    for said in ("eight chips share each layer", "16 a chip",
                 "64 chips hold the model's 436 GB", "B/16 rows", "B/2",
                 "eight times their share", "112 absent experts",
                 "nothing stands in for the seven absent chips"):
        assert said in CONFIG["deployment"], said
    assert CONFIG["stage_layers"] == {"sliding": 3, "full": 1}
    # twice the window: half of a sliding layer's keys are outside it; the
    # largest of 64 tokens, which tells float8 operands from a sound run
    assert CONFIG["reference_check"] == {"prompt_tokens": 8192,
                                         "new_tokens": 64}
    for said in ("EPLB", "ASSUMED", "one pick a token", "times 1/16",
                 "random signs times 1.125", "3, 2, 2"):
        assert said in CONFIG["assumed"]["weights"], said
    assert "LARGEST" in CONFIG["assumed"]["reference_check"]
    assert CONFIG["engine"] == {
        "max_num_seqs": 24, "max_num_batched_tokens": 2048,
        "enable_chunked_prefill": True, "max_model_len": 17664,
        "num_pages": 16384, "page_size": 16}
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    if CATALOG.exists():
        row = next(r for r in map(json.loads,
                                  CATALOG.read_text().splitlines())
                   if r["source_url"] == CONFIG["source"])
        assert row["name"] == "command-a-plus-05-2026"
        for key, value in row["config"].items():
            if key not in CONFIG["reduced"]:
                assert CONFIG[key] == value, key
            else:
                assert CONFIG["published"][key] == value, key


def test_the_program_agrees_and_a_drifted_constant_is_refused():
    cfg = arch.program_config(CONFIG)
    assert cfg.num_layers == 4 and cfg.layer_types == (
        "sliding_attention",) * 3 + ("full_attention",)
    assert (cfg.num_kv_layers, cfg.num_window_layers,
            cfg.num_state_layers) == (4, 3, 0)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (128, 8, 128)
    assert (cfg.num_experts, cfg.router_experts, cfg.expert_offset,
            cfg.num_experts_per_tok) == (16, 128, 0, 8)
    assert (cfg.shared_expert_size, cfg.shared_expert_scale) == (16384, 0.25)
    assert cfg.tie_word_embeddings and cfg.vocab_size == 262144
    assert cfg.parallel_block and cfg.norm_subtract_mean
    assert cfg.rope_kinds == ("sliding_attention",)
    assert cfg.attn_kwargs_of("sliding_attention") == {"window": 4096}
    assert cfg.attn_kwargs_of("full_attention") == {}
    for key, value in (("hidden_size", 2048), ("num_attention_heads", 64),
                       ("num_key_value_heads", 4), ("head_dim", 64),
                       ("intermediate_size", 2048),
                       ("num_experts_per_tok", 4),
                       ("num_shared_experts", 2), ("sliding_window", 513),
                       ("rope_theta", 10000), ("layer_norm_eps", 1e-6),
                       ("vocab_size", 32768)):
        with pytest.raises(SystemExit, match="differs from the published"):
            arch.program_config({**CONFIG, key: value})
    with pytest.raises(SystemExit, match="layer_types"):
        arch.program_config({**CONFIG, "num_hidden_layers": 5})
    with pytest.raises(SystemExit, match="stage_layers"):
        arch.program_config({**CONFIG, "layer_types": [
            "sliding_attention", "sliding_attention", "full_attention",
            "full_attention"]})
    with pytest.raises(SystemExit, match="differs from the published"):
        arch.program_config({**CONFIG, "layer_types": [
            "full_attention"] + ["sliding_attention"] * 3})
    # what the module refuses by name: each is a switch of the family that
    # this model leaves one way
    for key, value in (("use_parallel_block", False), ("use_qk_norm", True),
                       ("position_embedding_type", "rope"),
                       ("rotary_pct", 0.5), ("attention_bias", True),
                       ("expert_selection_fn", "softmax"),
                       ("norm_topk_prob", False),
                       ("shared_expert_combination_strategy", "sum"),
                       ("first_k_dense_replace", 1), ("logit_scale", 0.25),
                       ("tie_word_embeddings", False),
                       ("use_gated_activation", False),
                       ("hidden_act", "gelu")):
        with pytest.raises(SystemExit, match=key):
            arch.program_config({**CONFIG, key: value})
    with pytest.raises(SystemExit, match="tensor-parallel"):
        arch.param_specs(None)


def test_the_bytes_are_the_issues():
    """The weights, the two pools and their sum, from the file's own
    numbers: 11.35 GB, 1.07 GB, 1.82 GB, 14.2 GB of the chip's 16."""
    costs = arch.model_costs(CONFIG)
    assert costs["attn_params"] == 142_606_336
    assert costs["expert_params"] == 50_331_648
    assert costs["fixed_mlp_params"] == 4 * 50_331_648 + 524_288
    layer = (costs["attn_params"] + costs["fixed_mlp_params"]
             + 16 * costs["expert_params"] + 4096)        # and its norm
    assert layer == 1_149_763_584 + 4096
    embedding = 262144 * 4096
    weights = 2 * (4 * layer + embedding + 4096)
    assert 11.34e9 < weights < 11.36e9
    assert costs["kv_bytes_per_pos"] == 4096
    engine = CONFIG["engine"]
    full = engine["num_pages"] * 16 * 4096 * 1
    assert full == 1_073_741_824 and engine["num_pages"] * 16 == 262144
    window_pages = -(-24 * (4096 + 2048) // 16) + 24 + 1
    assert window_pages == 9241
    window = window_pages * 16 * 4096 * 3
    assert 1.81e9 < window < 1.82e9
    assert 14.2e9 < weights + full + window < 14.3e9
    # the engine sizes the same pool from the same numbers
    from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine

    kinds = LLMEngine._kinds_of(
        arch.program_config(CONFIG), EngineConfig(**engine),
        engine["num_pages"], 2)
    assert kinds["sliding_attention"]["pages_total"] == window_pages
    assert kinds["sliding_attention"]["layers"] == 3
    assert kinds["full_attention"]["pages_total"] == engine["num_pages"]
    for kind in kinds.values():
        assert kind["bytes_per_token_layer"] == 4096
    # 24 sequences of the mix's mean hold what the full kind's pool has
    mix = spec.load_json(ROOT / "benchmark/traffic/agent_closed.json")
    assert mix["clients"] == engine["max_num_seqs"] == 24
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= \
        engine["max_model_len"]


def test_the_counts_are_the_stated_algorithms():
    h, q_dim, tokens = 4096, 16384, 2048 + 23
    per_token = 2 * 4 * (142_606_336 + 201_326_592 + 524_288
                         + 1.0 * 50_331_648)      # one pick of 8 lands here
    # the full layer: every key at or below the token; the three sliding
    # layers: at most 4 096, as the flight record says
    full_pairs = STEP["prefill_ctx"] + STEP["decode_ctx"]
    win_pairs = STEP["prefill_ctx_window"] + STEP["decode_ctx_window"]
    assert win_pairs == (2048 + 23) * 4096 < full_pairs
    flops, nbytes = arch.attn_cost(STEP, CONFIG)
    assert flops == 1 * 4.0 * q_dim * full_pairs
    reach = STEP["prefill_ctx"] / 2048 + 2048 / 2.0
    assert nbytes == pytest.approx(
        4096 * (23 * 9000 + reach) + 2 * 2 * tokens * q_dim)
    wflops, wbytes = arch.attn_window_cost(STEP, CONFIG)
    assert wflops == 3 * 4.0 * q_dim * win_pairs
    assert wbytes == pytest.approx(3 * (
        4096 * (23 * 4096 + min(reach, 4096 + 2048))
        + 2 * 2 * tokens * q_dim))
    # a chunk at the start of a prompt reads what it has, not a window
    early = {**STEP, "decodes": 0, "decode_ctx": 0, "decode_ctx_window": 0,
             "prefill_ctx": sum(range(1, 2049)),
             "prefill_ctx_window": sum(range(1, 2049))}
    assert arch.attn_window_cost(early, CONFIG)[0] == \
        3 * arch.attn_cost(early, CONFIG)[0]
    assert arch.attn_window_cost(early, CONFIG)[1] == pytest.approx(
        3 * arch.attn_cost(early, CONFIG)[1])
    mflops, mbytes = arch.moe_cost(STEP, CONFIG)
    assert mflops == 4 * tokens * 2.0 * (201_326_592 + 524_288 + 50_331_648)
    touched = 16 * (1 - (1 - 8 / 128) ** tokens)
    assert 15.99 < touched <= 16
    assert mbytes == pytest.approx(4 * (
        touched * 2 * 50_331_648 + 2 * (201_326_592 + 524_288)
        + 2 * 2 * tokens * h))
    sflops, sbytes = arch.step_cost(STEP, CONFIG, 0.0, 0.0)
    assert sflops == pytest.approx(
        per_token * tokens + 2.0 * h * 262144 * 24 + flops + wflops)
    fixed = 2 * (4 * (142_606_336 + 201_326_592 + 524_288) + h * 262144)
    assert sbytes == pytest.approx(
        fixed + 4 * touched * 2 * 50_331_648 + nbytes + wbytes
        + 4 * 4096 * tokens)
    # the issue's estimate: 4.3-4.6 GFLOP a prompt token
    assert 4.3e9 < sflops / tokens < 4.6e9
    # a record without the window's pairs (a mix's means) caps the means
    bare = {k: v for k, v in STEP.items() if "ctx" not in k}
    assert arch.step_cost(bare, CONFIG, 7000.0, 9000.0)[0] == pytest.approx(
        per_token * tokens + 2.0 * h * 262144 * 24
        + 4.0 * q_dim * (2048 * 7000 + 23 * 9000)
        + 3 * 4.0 * q_dim * tokens * 4096)
    # a pure decode step of 24 rows reads 12-13 GB, as the issue reckons
    decode = {"prefill_tokens": 0, "prefills": 0, "decodes": 24,
              "prefill_ctx": 0, "decode_ctx": 24 * 9000,
              "decode_ctx_window": 24 * 4096, "prefill_ctx_window": 0}
    assert 11.5e9 < arch.step_cost(decode, CONFIG, 0.0, 0.0)[1] < 13.5e9


def test_the_weights_are_the_programs_draw_but_for_the_stated_changes():
    """At a tiny width (a router of 32: four families of eight): every
    leaf is the program's own draw but the routed experts (related, their
    ``down`` times ``ROUTED_DOWN_GAIN``), q and k (times ``QK_GAIN`` by the
    layer's place in its period, the sliding layers' columns in the served
    order), the final norm (random signs times ``FINAL_NORM_GAIN``) and
    the routers' columns (in families, one member of each among the held
    experts)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_omni_tpu.models.common import transformer as tfm
    from vllm_omni_tpu.ops.rope import pairs_to_halves

    cfg = arch.program_config({**TINY, "published": {"num_experts": 32},
                               "expert_offset": 0})
    key = jax.random.PRNGKey(3)
    plain = tfm.init_params(key, cfg, jnp.float32)
    made = arch.init_weights(key, cfg, jnp.float32)
    assert jax.tree.structure(plain) == jax.tree.structure(made)
    assert arch.QK_GAIN == QK_GAIN and len(QK_GAIN) == 4
    assert arch.ROUTED_DOWN_GAIN == ROUTED_DOWN_GAIN
    assert arch.FINAL_NORM_GAIN == FINAL_NORM_GAIN
    assert (arch.ROUTER_FAMILY, arch.ROUTER_OWN_SHARE) == (8, 0.25)
    np.testing.assert_array_equal(made["embed"]["w"], plain["embed"]["w"])
    # the final norm: the program's ones become +-FINAL_NORM_GAIN, about
    # half of either sign, so that the tied head does not read the input
    # token's own row back
    final = np.asarray(made["final_norm"]["w"])
    np.testing.assert_array_equal(np.abs(final), arch.FINAL_NORM_GAIN)
    assert 16 < (final > 0).sum() < 48
    for i, (ours, theirs, kind) in enumerate(zip(
            made["layers"], plain["layers"], cfg.layer_types)):
        for name in ("q_proj", "k_proj"):
            want = theirs[name]["w"] * arch.QK_GAIN[i]
            if kind == "sliding_attention":
                want = pairs_to_halves(want, 16)
            np.testing.assert_allclose(ours[name]["w"], want)
        for name in ("v_proj", "o_proj", "input_norm"):
            np.testing.assert_array_equal(ours[name]["w"], theirs[name]["w"])
        for name in ("gate_up", "down"):
            np.testing.assert_array_equal(ours["shared_expert"][name]["w"],
                                          theirs["shared_expert"][name]["w"])
            # related: expert 0's draw stands for the common part
            a = arch.glm4_moe_lite.EXPERT_OWN_SHARE
            w = theirs["experts"][name]
            gain = arch.ROUTED_DOWN_GAIN if name == "down" else 1.0
            np.testing.assert_allclose(
                ours["experts"][name],
                gain * ((1 - a * a) ** 0.5 * w[:1] + a * w), atol=1e-6)
        # the router: a column is its own draw at 0.25 and, for the rest,
        # the direction of its family (e mod 4), which is no expert's own
        own = arch.ROUTER_OWN_SHARE
        kin = (np.asarray(ours["router"]["w"])
               - own * np.asarray(theirs["router"]["w"])) / (1 - own ** 2) ** 0.5
        for e in range(4, 32):
            np.testing.assert_allclose(kin[:, e], kin[:, e % 4], atol=1e-6)
        assert np.abs(kin[:, 0] - kin[:, 1]).max() > 0.05
        cols = np.asarray(ours["router"]["w"])
        cols = cols / np.linalg.norm(cols, axis=0)
        assert (cols[:, 1] @ cols[:, 5]) > 0.85        # one family
        assert abs(cols[:, 1] @ cols[:, 6]) < 0.5      # two families
        # the held experts (ids 0-3) are one of each family
        assert sorted(e % 4 for e in range(cfg.num_experts)) == [0, 1, 2, 3]
    assert made["layers"][0]["experts"]["gate_up"].shape == (4, 64, 64)
    assert made["layers"][0]["router"]["w"].shape == (64, 32)
    # a router no wider than a family keeps the plain draw
    narrow = arch.program_config(TINY)
    np.testing.assert_array_equal(
        arch.init_weights(key, narrow, jnp.float32)["layers"][0]["router"][
            "w"],
        tfm.init_params(key, narrow, jnp.float32)["layers"][0]["router"]["w"])


def test_the_cell_and_its_metrics_resolve_by_name():
    cell = spec.resolve_cell(CELL)
    assert cell["cell"] == {
        "name": CELL, "config": NAME, "traffic": "agent_closed", "chips": 1,
        "why": cell["cell"]["why"]}
    assert len(cell["cell"]["why"]) <= 200
    for said in ("24 closed clients", "at most 4096 keys", "8x share"):
        assert said in cell["cell"]["why"], said
    assert {m["name"] for m in cell["end_to_end"]} == {"tok_s", "setup_s"}
    got = {m["name"]: m for m in cell["per_layer"]}
    assert got[NEW]["params"] == {"histogram": "window_keep_pct"}
    assert got[NEW]["reader"] == "benchmark.optional_readers:histogram_mean"
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NEW]
    assert entry == {"name": NEW, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "scheduler",
                     "moves": "tok_s", "workloads": entry["workloads"]}
    # PREFIX checks, all of them: what this PR found stands first, in its
    # order, and a later PR appends after it
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NEW) > names.index("moe_latent_roofline_pct")
    assert entry["workloads"][:2] == ["dots3note_agent_sat", CELL]
    listed = {m["name"]: m["workloads"]
              for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", [])}
    assert set(listed) == set(HAD) | {NEW}
    for name, before in HAD.items():
        assert listed[name][:len(before) + 1] == before + [CELL], name
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[:len(SAT) + 2].count(CELL) == 1 and cells.index(CELL) == 9
    assert [c["name"] for c in BENCH["configs"]].index(NAME) == 8
    for name in ("attn_window_roofline_pct", "attn_roofline_pct",
                 "moe_roofline_pct", "scope_attn_window_share_pct",
                 "kv_window_pages_used_mean", "kv_pages_used_mean",
                 "device_roofline_ctx_pct", NEW):
        assert callable(readers.resolve(got[name]["reader"])), name
    assert callable(arch.attn_window_cost)
    # the mix of GLM's and dots3's cells, unedited: one schedule, one
    # generator, three ways of making long context cheap
    for other in ("glm47flash_agent_sat", "dots3note_agent_sat"):
        assert cell["traffic"] == spec.resolve_cell(other)["traffic"]
    assert cell["traffic"]["prompt_tokens"] == {
        "median": 8192, "sigma": 0.4, "min": 2048, "max": 16384}


def test_the_optional_reader_finds_nothing_on_a_program_without_it():
    """``window_keep_pct_mean`` is read in dots3's cell too, which the
    parent's program serves without the histogram: the reader says None
    there (the harness leaves the metric out) where ``histogram_mean``
    would raise."""
    from benchmark import optional_readers

    hist = {"count": 4, "sum": 200.0}
    later = {"count": 10, "sum": 560.0}
    obs = {"engine0": {"window_keep_pct": hist, "kv_pages_used": hist},
           "engine1": {"window_keep_pct": later, "kv_pages_used": later}}
    assert optional_readers.histogram_mean(obs, "window_keep_pct") == 60.0
    assert optional_readers.histogram_mean(
        obs, "window_keep_pct") == readers.histogram_mean(
        obs, "window_keep_pct")
    parent = {"engine0": {"kv_pages_used": hist},
              "engine1": {"kv_pages_used": later}}
    assert optional_readers.histogram_mean(parent, "window_keep_pct") is None
    with pytest.raises(KeyError):
        readers.histogram_mean(parent, "window_keep_pct")
    idle = {"engine0": {"window_keep_pct": hist},
            "engine1": {"window_keep_pct": hist}}
    assert optional_readers.histogram_mean(idle, "window_keep_pct") is None


def test_a_tiny_cell_is_served_checked_and_counted(tmp_path):
    """A copy's ``benchmark/run.py --trace 2`` on a tiny Command A+ cell
    (an expert share: experts 4-7 of an 8-wide router): the harness serves
    it through ``build_server``, the reference check agrees with the served
    path to float32 rounding through chunked prefill over both kinds of
    page with eight window pages given back, the engine counts exactly the
    tokens drawn, and the last line holds the cell's metrics, the new one
    among them."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "benchmark/traffic/tiny_closed.json").write_text(
        json.dumps(TINY_MIX))
    bench = json.loads(json.dumps(BENCH))
    (ours,) = [c for c in bench["configs"] if c["name"] == NAME]
    bench["configs"].append({**ours, "name": "tiny",
                             "file": "benchmark/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny_commandaplus", "config": "tiny",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_commandaplus")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "tiny_commandaplus", "--seed", "5000000050",
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
            "kv_window_pages_used_mean", NEW} <= set(metrics)
    # prompts of 40-150 tokens against a window of 21: a step's rows keep
    # a fifth to a half of the keys they see
    assert 10 < metrics[NEW]["value"] < 70
    assert 1 <= metrics["kv_window_pages_used_mean"]["value"] <= 12
    window = kept["engine1"]["window"]
    assert window["window"] == 21 and window["pages_freed_total"] > 8
    kinds = kept["engine1"]["kv"]["kinds"]
    assert kinds["sliding_attention"]["layers"] == 3
    assert kinds["sliding_attention"]["bytes_per_token_layer"] == \
        kinds["full_attention"]["bytes_per_token_layer"] == 2 * 2 * 16 * 4
    steps = [s for s in kept["steps"] if s["prefill_tokens"] + s["decodes"]]
    assert steps and all(
        s["prefill_ctx_window"] + s["decode_ctx_window"]
        <= s["prefill_ctx"] + s["decode_ctx"] for s in steps)
