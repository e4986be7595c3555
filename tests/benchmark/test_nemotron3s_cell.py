"""The NVIDIA-Nemotron-3-Super architecture module, its configuration and
its cell's metric files: the published numbers and the cut, the bytes, the
counts on a hand-made step, a program constant that drifted and what the
module refuses by name, and a tiny cell run on the CPU through a COPY's
``benchmark/run.py`` (so its cache and its trace are its own: the workers
of a parallel run share the checkout's) whose last line holds the new
metrics."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import readers, spec
from benchmark.archs import nemotron_h as arch

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
CELL = "nemotron3s_assist_sat"
NAME = "nemotron3super-l11-ep4"
CONFIG = spec.load_json(ROOT / f"benchmark/configs/{NAME}.json")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW = ["scope_moe_latent_share_pct", "moe_latent_roofline_pct"]
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
#: a full decode batch beside one chunk, and the pure decode step
STEP = {"prefill_tokens": 1016, "prefills": 1, "decodes": 127,
        "prefill_ctx": 1016 * 1500, "decode_ctx": 127 * 1000,
        "state_rows": 128}
DECODE = {"prefill_tokens": 0, "prefills": 0, "decodes": 128,
          "prefill_ctx": 0, "decode_ctx": 128 * 1000, "state_rows": 128}

TINY = {
    "arch": "benchmark.archs.nemotron_h", "name": "nemotron-tiny",
    "model_type": "nemotron_h", "vocab_size": 384, "hidden_size": 64,
    "num_hidden_layers": 6, "hybrid_override_pattern": "ME*EME",
    "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16,
    "intermediate_size": 24, "moe_intermediate_size": 24,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_routed_experts": 4, "published": {"n_routed_experts": 8},
    "expert_offset": 4, "num_experts_per_tok": 3,
    "routed_scaling_factor": 2.5, "n_group": 1, "topk_group": 1,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "moe_shared_expert_overlap": False, "mlp_hidden_act": "relu2",
    "mlp_bias": False, "use_bias": False, "attention_bias": False,
    "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5, "rope_theta": 10000,
    "mamba_num_heads": 4, "mamba_head_dim": 32, "ssm_state_size": 128,
    "conv_kernel": 4, "expand": 2, "n_groups": 2, "chunk_size": 16,
    "use_conv_bias": True, "mamba_proj_bias": False,
    "mamba_hidden_act": "silu", "tie_word_embeddings": False,
    "residual_in_fp32": False, "sliding_window": None,
    "num_nextn_predict_layers": 0,
    "stage_layers": {"mamba": 2, "attention": 1, "moe": 3},
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
    assert list(CONFIG["reduced"]) == REDUCED
    assert CONFIG["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072, "num_nextn_predict_layers": 1,
        "hybrid_override_pattern": CONFIG["published"][
            "hybrid_override_pattern"]}
    whole = CONFIG["published"]["hybrid_override_pattern"]
    assert (len(whole), whole.count("M"), whole.count("*"),
            whole.count("E")) == (88, 40, 8, 40)
    # published layers 25-35: the first whole period, 1 : 5 : 5
    assert CONFIG["hybrid_override_pattern"] == whole[25:36] == "*EMEMEMEMEM"
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"], CONFIG["num_nextn_predict_layers"],
            CONFIG["expert_offset"]) == (11, 128, 32768, 0, 0)
    for key, said in (("num_hidden_layers", "88 -> 11"),
                      ("n_routed_experts", "512 -> 128"),
                      ("vocab_size", "131072 -> 32768"),
                      ("num_nextn_predict_layers", "1 -> 0"),
                      ("hybrid_override_pattern", "25-35")):
        assert said in CONFIG["reduced"][key], key
    # the floors of a model_config cut: a whole period, 8 experts a layer,
    # an eighth of the vocabulary
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= 131072
    for key in ("assumed", "deployment", "reference_check", "engine"):
        assert CONFIG[key]
    for key in ("positions", "dt_limits", "gate_before_norm", "chunk_size",
                "state_dtype", "conv_state_layout", "serve_dtype", "sampling",
                "weights", "loader"):
        assert CONFIG["assumed"][key], key
    for said in ("eight pipeline stages of 11 layers", "241 GB",
                 "four chips of a host share each layer", "128 experts a chip",
                 "split by vocabulary", "128 x 22 / 512 = 5.5 rows",
                 "would give it 22", "memory allows no more slots"):
        assert said in CONFIG["deployment"], said
    assert CONFIG["stage_layers"] == {"mamba": 5, "attention": 1, "moe": 5}
    assert CONFIG["reference_check"] == {"prompt_tokens": 2560,
                                         "new_tokens": 8}
    granite = spec.load_json(ROOT / "benchmark/configs/granite4h-l10-ep4.json")
    assert CONFIG["engine"] == granite["engine"] == {
        "max_num_seqs": 128, "max_num_batched_tokens": 2048,
        "enable_chunked_prefill": True, "max_model_len": 6400,
        "num_pages": 16384, "page_size": 16}
    entry = BENCH["configs"][-1]
    assert entry["name"] == NAME and entry["source"] == CONFIG["source"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    if CATALOG.exists():
        row = next(r for r in map(json.loads,
                                  CATALOG.read_text().splitlines())
                   if r["source_url"] == CONFIG["source"])
        assert row["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        for key, value in row["config"].items():
            if key not in CONFIG["reduced"]:
                assert CONFIG[key] == value, key
            else:
                assert CONFIG["published"][key] == value, key
        # no width is cut
        assert (CONFIG["hidden_size"], CONFIG["moe_latent_size"],
                CONFIG["moe_intermediate_size"],
                CONFIG["moe_shared_expert_intermediate_size"],
                CONFIG["ssm_state_size"], CONFIG["mamba_head_dim"],
                CONFIG["head_dim"], CONFIG["num_experts_per_tok"],
                CONFIG["n_groups"]) == (4096, 1024, 2688, 5376, 128, 64, 128,
                                        22, 8)


def test_the_program_agrees_and_a_drifted_constant_is_refused():
    from vllm_omni_tpu.models.common.transformer import FEED_FORWARD

    cfg = arch.program_config(CONFIG)
    assert cfg.num_layers == 11 and cfg.one_sublayer
    assert cfg.layer_types == ("full_attention",) + (FEED_FORWARD,
                                                     "mamba") * 5
    assert (cfg.num_kv_layers, cfg.num_state_layers) == (1, 5)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.num_experts, cfg.router_experts, cfg.expert_offset,
            cfg.num_experts_per_tok) == (128, 512, 0, 22)
    assert not cfg.tie_word_embeddings and cfg.vocab_size == 32768
    assert not cfg.use_rope and cfg.attn_kwargs == {}
    assert (cfg.mamba_n_groups, cfg.mamba_chunk_size) == (8, 128)
    assert cfg.state_shapes("mamba") == ((3, 10240), (128, 64, 128))
    layout = cfg.kv_layout
    assert (layout.kind, layout.heads, layout.width, layout.pools) == (
        "kv", 2, 128, 2)
    for key, value in (("moe_latent_size", 2048), ("ssm_state_size", 64),
                       ("chunk_size", 256), ("n_groups", 1),
                       ("routed_scaling_factor", 2.5),
                       ("num_experts_per_tok", 8),
                       ("moe_shared_expert_intermediate_size", 2688),
                       ("num_key_value_heads", 8),
                       ("moe_intermediate_size", 1344)):
        with pytest.raises(SystemExit, match="differs from the published"):
            arch.program_config({**CONFIG, key: value})
    with pytest.raises(SystemExit, match="stage_layers"):
        arch.program_config({**CONFIG, "num_hidden_layers": 10})
    with pytest.raises(SystemExit, match="stage_layers"):
        arch.program_config({**CONFIG,
                             "hybrid_override_pattern": "MEMEMEMEMEM"})
    with pytest.raises(SystemExit, match="no run of the published"):
        arch.program_config({**CONFIG,
                             "hybrid_override_pattern": "*MEMEMEMEME"})
    with pytest.raises(SystemExit, match="letters"):
        arch.program_config({**CONFIG,
                             "hybrid_override_pattern": "*EMEMEMEM-M"})
    # what the module refuses by name: a time-step limit, biases, a group
    # limit in the router, the shared expert's overlap, the MTP module
    for key, value in (("time_step_limit", [0.0, 0.1]), ("mlp_bias", True),
                       ("use_bias", True), ("n_group", 2),
                       ("topk_group", 2),
                       ("moe_shared_expert_overlap", True),
                       ("num_nextn_predict_layers", 1),
                       ("use_conv_bias", False),
                       ("mlp_hidden_act", "silu"),
                       ("tie_word_embeddings", True)):
        with pytest.raises(SystemExit, match=key):
            arch.program_config({**CONFIG, key: value})
    with pytest.raises(SystemExit, match="expand"):
        arch.program_config({**CONFIG, "mamba_head_dim": 32})
    with pytest.raises(SystemExit, match="tensor-parallel"):
        arch.param_specs(None)
    # granite's module is not edited and still says what it is built for
    from benchmark.archs import granite_moe_hybrid

    assert granite_moe_hybrid.BUILT["mamba_n_groups"] == 1


def test_the_bytes_are_the_issues():
    """ISSUE 46's arithmetic, from the file's keys."""
    costs = arch.model_costs(CONFIG)
    assert costs["mix"] == {"mamba": 5, "attention": 1, "moe": 5}
    h = 4096
    mamba = h * (8192 + 10240 + 128) + 8192 * h + 5 * 10240 + 3 * 128 \
        + 8192 + h
    attn = 2 * h * h + 2 * h * 256 + h
    expert = 2 * 1024 * 2688
    fixed = h * 512 + 512 + 2 * h * 1024 + 2 * h * 5376 + h
    assert round(mamba / 1e6, 2) == 109.64 and costs["mamba_params"] == mamba
    assert round(attn / 1e6, 2) == 35.66 and costs["attn_params"] == attn
    assert round(expert / 1e6, 3) == 5.505 == round(
        costs["expert_params"] / 1e6, 3)
    assert round(fixed / 1e6, 2) == 54.53
    assert costs["fixed_mlp_params"] == fixed
    # whole: 40 + 8 + 40 layers of 512 experts, embedding and head:
    # 120.67 B parameters, the model's stated 120 B
    whole = 40 * mamba + 8 * attn + 40 * (fixed + 512 * expert) \
        + 2 * 131072 * h + h
    assert round(whole / 1e9, 2) == 120.67
    # here: one period of 128 experts: 4 379.7 M parameters, 8.76 GB
    layer = fixed + 128 * expert
    assert round(layer / 1e6, 2) == 759.17
    period = 5 * layer + 5 * mamba + attn
    assert round(period / 1e6, 1) == 4379.7
    assert round(2 * period / 1e9, 2) == 8.76
    # the embedding's and the head's slice 0.54 GB; whole they are 2.15 GB
    assert round(2 * 2 * 32768 * h / 1e9, 2) == 0.54
    assert round(2 * 2 * 131072 * h / 1e9, 2) == 2.15
    # the program's own tree holds exactly these
    import functools

    import jax
    import jax.numpy as jnp

    cfg = arch.program_config(CONFIG)
    shapes = jax.eval_shape(functools.partial(
        arch.init_weights, cfg=cfg, dtype=jnp.bfloat16),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    # (the routers' correction biases are float32: 512 x 2 B more a layer)
    assert held == 2 * (period + 2 * 32768 * h + h) + 5 * 2 * 512
    assert round(held / 1e9, 2) == 9.30
    # a sequence's state: 4.19 MB + 0.12 MB a layer, 21.59 MB over five;
    # 128 slots 2.76 GB; the attention layer's pages 1 KB a token, 0.27 GB
    assert costs["state_bytes"] == 128 * 64 * 128 * 4 == 4_194_304
    assert costs["conv_state_bytes"] == 3 * 10240 * 4
    a_slot = 5 * (costs["state_bytes"] + costs["conv_state_bytes"])
    assert round(a_slot / 5e6, 2) == 4.32
    assert round(128 * a_slot / 1e9, 2) == 2.76
    assert costs["kv_bytes_per_pos"] == 2 * 2 * 128 * 2 == 1024
    assert round(16384 * 16 * 1024 / 1e9, 2) == 0.27
    # 12.3 GB before activations, of the chip's 17.2 (16 GiB)
    assert round((held + 128 * a_slot + 16384 * 16 * 1024) / 1e9, 1) == 12.3
    # the longest request fits the model's length; the pool holds what 128
    # of the mean do
    assert 4096 + 2048 + 18 <= CONFIG["engine"]["max_model_len"]
    assert 128 * 1700 < 16384 * 16


def test_the_counts_are_the_stated_algorithms():
    """Hand-worked at the published widths."""
    costs = arch.model_costs(CONFIG)
    h, tokens = 4096, 1016 + 127
    expert = 2 * 1024 * 2688
    # the rule by the recurrent algorithm: 5 x 64 x 128 a head a token; the
    # taps, bias and activations over 10 240 channels (B and C a group);
    # skip, gate and norm
    per_token = 128 * 5 * 64 * 128 + (2 * 4 + 6) * 10240 + 12 * 8192
    assert costs["ssd_flops_per_token"] == per_token
    flops, nbytes = arch.ssd_cost(STEP, CONFIG)
    assert flops == 5 * tokens * per_token
    state = 2 * 128 * (4_194_304 + 122_880)
    rows = tokens * (4 * (8192 + 10240 + 128) + 2 * 8192)
    small = 2 * (5 * 10240 + 3 * 128 + 8192)
    assert nbytes == 5 * (state + rows + small)
    # a pure decode step: 128 live rows' state once in and once out is
    # nearly all of it, 5.5 GB a step
    d_flops, d_bytes = arch.ssd_cost(DECODE, CONFIG)
    assert 5 * state / d_bytes > 0.98 and 5.5e9 < d_bytes < 5.6e9
    assert d_flops == 5 * 128 * per_token
    deep = {**CONFIG, "num_hidden_layers": 22}  # two such periods
    for cost in (arch.ssd_cost, arch.moe_cost, arch.moe_latent_cost,
                 arch.attn_cost):
        one, two = cost(STEP, CONFIG), cost(STEP, deep)
        assert two == pytest.approx((2 * one[0], 2 * one[1]))
    # attention: 4 x 128 x ctx operations a query head, one layer, 1 KB a
    # cached token
    a_flops, a_bytes = arch.attn_cost(STEP, CONFIG)
    assert a_flops == 32 * 4 * 128 * (STEP["prefill_ctx"]
                                      + STEP["decode_ctx"])
    assert a_bytes > 1024 * STEP["decode_ctx"]
    assert a_bytes < 1024 * (STEP["decode_ctx"] + 1500 + 508) \
        + 2 * 2 * tokens * 4096 + 1
    # the experts' scope: the router at 512, 5.5 picks landing here at the
    # LATENT's width, the shared expert; not the two projections
    m_flops, m_bytes = arch.moe_cost(STEP, CONFIG)
    assert m_flops == 5 * tokens * 2 * (h * 512 + 2 * h * 5376
                                        + 5.5 * expert)
    # 1 143 tokens touch all 128 held experts: read once a layer
    assert m_bytes == pytest.approx(
        5 * (2 * 128 * expert + 2 * (h * 512 + 512 + 2 * h * 5376)
             + 2 * 2 * tokens * (h + 1024)), rel=1e-6)
    # 128 tokens of a decode step touch 127.5 of them (1 - (490/512)^128):
    # 7.0 GB of experts, 7.5 GB with router and shared expert
    assert arch._touched(costs, 128) == pytest.approx(127.54, abs=0.01)
    _, dm_bytes = arch.moe_cost(DECODE, CONFIG)
    assert 7.4e9 < dm_bytes < 7.6e9
    # the two projections' scope: 2 x 2 x 4096 x 1024 operations a token,
    # 16.8 MB of matrices a layer
    l_flops, l_bytes = arch.moe_latent_cost(STEP, CONFIG)
    assert l_flops == 5 * tokens * 2 * 2 * h * 1024
    assert l_bytes == 5 * (2 * 2 * h * 1024 + 2 * 2 * tokens * (h + 1024))
    assert round(2 * 2 * h * 1024 / 1e6, 1) == 16.8
    # the whole step holds each of its parts and the head
    s_flops, s_bytes = arch.step_cost(STEP, CONFIG, 1500.0, 1000.0)
    assert s_flops > flops + a_flops + m_flops + l_flops
    # 1.98 GB of weights outside the experts, 7.05 GB of experts, the
    # state 5.5 GB, pages
    assert 14.6e9 < s_bytes < 14.8e9
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
    assert "lm_head" in ours

    def same(a, b):
        return all(jax.tree.leaves(jax.tree.map(
            lambda x, y: bool(jnp.array_equal(x, y)), a, b)))

    assert same({k: v for k, v in plain.items() if k != "layers"},
                {k: v for k, v in ours.items() if k != "layers"})
    for a, b in zip(plain["layers"], ours["layers"]):
        a, b = dict(a), dict(b)
        if "experts" in a:
            # the router's matrix as drawn; its correction bias balanced
            assert same(a["router"]["w"], b["router"]["w"])
            assert not same(a["router"]["e_bias"], b["router"]["e_bias"])
            del a["router"], b["router"]
            assert sorted(b["experts"]) == ["down", "up"]
            got = np.asarray(b["experts"]["down"], np.float32)
            corr = np.corrcoef(got[1].ravel(), got[2].ravel())[0, 1]
            assert 0.99 < corr < 0.999
            del a["experts"], b["experts"]
        for name in ("q_proj", "k_proj"):
            if name in a:
                assert np.array_equal(
                    np.asarray(b[name]["w"], np.float32),
                    np.asarray(a[name]["w"], np.float32) * arch.QK_GAIN)
                del a[name], b[name]
        assert same(a, b)
    mb = ours["layers"][0]["mamba"]
    a_neg = np.exp(np.asarray(mb["A_log"], np.float32))
    assert a_neg.min() >= 0.99 and a_neg.max() <= 16.1
    dt = np.log1p(np.exp(np.asarray(mb["dt_bias"], np.float32)))
    assert dt.min() >= 0.9e-3 and dt.max() <= 0.11
    assert np.all(np.asarray(mb["D"], np.float32) == 1.0)


def test_the_routers_bias_is_drawn_as_the_load_balance():
    """``_balance_routers`` at a width where one direction common to every
    token is most of a router's input (a large offset added to every
    embedding row): with the plain bias nearly every token picks the same
    experts; with the balanced one every expert is picked, about equally
    often, over fresh tokens.  The scores are untouched: only the
    selection moves."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_omni_tpu.models.common import transformer as tfm

    cfg = arch.program_config({**TINY, "n_routed_experts": 8,
                               "expert_offset": 0, "num_experts_per_tok": 2})
    key = jax.random.PRNGKey(3)
    plain = tfm.init_params(key, cfg=cfg, dtype=jnp.float32)
    common = jax.random.normal(jax.random.PRNGKey(4), (cfg.hidden_size,))
    plain["embed"]["w"] = plain["embed"]["w"] * 50.0 + 0.8 * common
    balanced = arch._balance_routers(
        jax.tree.map(lambda x: x, plain), cfg, jax.random.PRNGKey(5))
    ids = jnp.asarray(np.random.default_rng(6).integers(3, 384, 2048))

    def first_router_picks(params):
        layer = params["layers"][1]
        x = params["embed"]["w"][ids] + arch._mamba(
            params["layers"][0], arch._rms(
                params["embed"]["w"][ids],
                params["layers"][0]["input_norm"]["w"], 1e-5),
            (4, 32, 128, 2), 1e-5)
        scores = jax.nn.sigmoid(arch._rms(
            x, layer["post_norm"]["w"], 1e-5) @ layer["router"]["w"])
        _, picks = jax.lax.top_k(scores + layer["router"]["e_bias"], 2)
        return np.bincount(np.asarray(picks).ravel(), minlength=8) / 2048

    before, after = first_router_picks(plain), first_router_picks(balanced)
    # two picks a token over eight experts: 0.25 each when balanced
    # plain: one expert answers three tokens in four, another hardly any
    # (0.78 and 0.03); balanced, over tokens the balance never saw: 0.22 to
    # 0.27
    assert before.max() > 0.6 and before.min() < 0.05
    assert after.max() < 0.3 and after.min() > 0.2
    assert np.array_equal(balanced["layers"][1]["router"]["w"],
                          plain["layers"][1]["router"]["w"])


def test_the_cell_and_its_two_metrics_resolve_by_name():
    cell = spec.resolve_cell(CELL)
    assert cell["cell"] == {
        "name": CELL, "config": NAME, "traffic": "assist128_closed",
        "chips": 1, "why": cell["cell"]["why"]}
    assert BENCH["workloads"][-1]["name"] == CELL
    assert {m["name"] for m in cell["end_to_end"]} == {"tok_s", "setup_s"}
    got = {m["name"]: m for m in cell["per_layer"]}
    assert got["scope_moe_latent_share_pct"]["params"] == {
        "scopes": ["omni.moe_latent"]}
    assert got["scope_moe_latent_share_pct"]["reader"] == \
        "benchmark.span_readers:scope_share_pct"
    assert got["moe_latent_roofline_pct"]["params"] == {"part": "moe_latent"}
    assert got["moe_latent_roofline_pct"]["reader"] == \
        "benchmark.span_readers:roofline_ctx_pct"
    # this PR's two entries stand last, in the issue's order, after every
    # entry the parent had
    assert [m["name"] for m in BENCH["per_layer"][-2:]] == NEW
    assert [(m["layer"], m["source"], m["better"], m["moves"], m["unit"],
             m["workloads"]) for m in BENCH["per_layer"][-2:]] == [
        ("kernels", "device_trace", "lower", "tok_s", "%", [CELL]),
        ("kernels", "device_trace", "higher", "tok_s", "%", [CELL])]
    for name in NEW:
        assert callable(readers.resolve(got[name]["reader"]))
    assert callable(arch.moe_latent_cost) and callable(arch.ssd_cost)
    # every metric granite's cell reports, this cell reports, listed after
    # the cells each list had
    granite = {m["name"] for m in spec.resolve_cell(
        "granite4h_assist_sat")["per_layer"]}
    assert len(granite) == 32 and granite | set(NEW) == set(got)
    for name in ("ssd_roofline_pct", "moe_roofline_pct", "attn_roofline_pct",
                 "device_roofline_ctx_pct", "state_slots_mean",
                 "kv_pages_used_mean", "ssd_chunk_fill_pct",
                 "scope_unnamed_share_pct"):
        assert name in got, name
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        cells = m.get("workloads", [])
        if "granite4h_assist_sat" in cells:
            assert cells[-1] == CELL and cells[-2] == "granite4h_assist_sat"
        elif m["name"] not in NEW:
            assert CELL not in cells, m["name"]
    # the same traffic file as granite's cell: ops/ssd.py on two models
    # under one mix
    assert cell["traffic"] == spec.resolve_cell(
        "granite4h_assist_sat")["traffic"]
    assert cell["traffic"]["clients"] == CONFIG["engine"]["max_num_seqs"]


def test_a_tiny_cell_is_served_checked_and_counted(tmp_path):
    """A copy's ``benchmark/run.py --trace 2`` on a tiny Nemotron-H cell
    (an expert share: experts 4-7 of an 8-wide router): the harness serves
    it through ``build_server``, the reference check agrees with the served
    path to float32 rounding through chunked prefill, the slots updated in
    place and the pages, the engine counts exactly the tokens drawn, and
    the last line holds the cell's metrics."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "benchmark/traffic/tiny_closed.json").write_text(
        json.dumps(TINY_MIX))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({**bench["configs"][-1], "name": "tiny",
                             "file": "benchmark/configs/tiny.json"})
    bench["workloads"].append({"name": "tiny_nemotron", "config": "tiny",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_nemotron")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "tiny_nemotron", "--seed", "5000000046", "--seconds",
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
    # a CPU trace has no device plane: the two new metrics, which read the
    # device's scopes, find nothing and are left out, as on a parent whose
    # program has no such scope
    assert not set(NEW) & set(metrics)
    assert 1 <= metrics["state_slots_mean"]["value"] <= 3
    assert 50 < metrics["ssd_chunk_fill_pct"]["value"] <= 100
    state = kept["engine1"]["state"]
    assert state["slots_total"] == 4
    # two Mamba-2 layers of six: the carried rows over 128 + 2 x 2 x 128
    # channels and the rule's [4, 32, 128]
    per_layer = 4 * (3 * (128 + 512) + 4 * 32 * 128)
    assert state["bytes_per_slot_layer"] == per_layer
    assert state["bytes_per_slot"] == 2 * per_layer
