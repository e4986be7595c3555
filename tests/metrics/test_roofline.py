"""Live roofline attribution (metrics/roofline.py): geometry math,
tracker sanity, and the engine-wired gauges on a tiny live model."""

import jax
import jax.numpy as jnp
import pytest

from vllm_omni_tpu.metrics.roofline import (
    ModelGeometry,
    RooflineTracker,
    ctx_positions,
)
from vllm_omni_tpu.models.common import transformer as tfm


@pytest.fixture(scope="module")
def geometry():
    cfg = tfm.TransformerConfig.tiny(vocab_size=64)
    return ModelGeometry.from_transformer_config(cfg, dtype_bytes=4)


# ------------------------------------------------------------- geometry
def test_ctx_positions_causal_sum():
    # 4 tokens appended from position 0: 1+2+3+4 attended positions
    assert ctx_positions(0, 4) == 10.0
    # 1 decode token at position 8 attends over 9 positions
    assert ctx_positions(8, 1) == 9.0
    assert ctx_positions(5, 0) == 0.0


def test_geometry_costs_positive_and_scale(geometry):
    g = geometry
    assert g.flops_per_token > 0 and g.weight_bytes > 0
    assert g.kv_bytes_per_pos > 0
    f1 = g.step_flops(1, ctx_positions(8, 1), 1)
    f8 = g.step_flops(8, ctx_positions(0, 8), 1)
    assert f8 > f1, "more computed tokens must cost more FLOPs"
    assert g.step_bytes(8, ctx_positions(0, 8)) \
        > g.step_bytes(1, ctx_positions(8, 1)) - g.kv_bytes_per_pos * 8


def test_prefill_denser_than_decode(geometry):
    """The structural roofline ordering: a prefill-shaped step (many
    new tokens per dispatch) has strictly higher arithmetic intensity
    than a single-token decode step — weights are read once per
    dispatch either way, so FLOPs/byte grows with the token count.
    This is the geometry-level face of the prefill/decode MBU/MFU
    ordering; the live gauges inherit it modulo wall-clock noise."""
    g = geometry
    prefill = g.arithmetic_intensity(32, ctx_positions(0, 32), 1)
    decode = g.arithmetic_intensity(1, ctx_positions(32, 1), 1)
    assert prefill > decode
    # per-STEP achieved bytes: a prefill step moves at least as much
    # (same weight read + strictly more KV writes)
    assert g.step_bytes(32, ctx_positions(0, 32)) \
        >= g.step_bytes(1, ctx_positions(32, 1))


def test_moe_counts_active_params_only():
    dense = tfm.TransformerConfig.tiny(vocab_size=64)
    import dataclasses

    moe = dataclasses.replace(dense, moe=True, num_experts=8,
                              num_experts_per_tok=2)
    g_dense = ModelGeometry.from_transformer_config(dense, 4)
    g_moe = ModelGeometry.from_transformer_config(moe, 4)
    # 2 of 8 experts active: flops reflect the ROUTED cost, not 8x
    assert g_moe.flops_per_token < 4 * g_dense.flops_per_token


# -------------------------------------------------------------- tracker
def test_tracker_bounds_and_phase_split(geometry):
    t = RooflineTracker(geometry, peak_tflops=0.5, peak_gbps=50.0)
    # equal wall budget: the prefill-shaped step achieves >= the
    # decode step on both axes (strictly more work, same denominator)
    pre = t.on_step(prefill_tokens=32, prefill_ctx=ctx_positions(0, 32),
                    decode_tokens=0, decode_ctx=0.0, sampled_rows=1,
                    wall_s=0.01)
    dec = t.on_step(prefill_tokens=0, prefill_ctx=0.0, decode_tokens=1,
                    decode_ctx=ctx_positions(32, 1), sampled_rows=1,
                    wall_s=0.01)
    for r in (pre, dec):
        assert 0.0 < r["mfu"] <= 1.0
        assert 0.0 < r["mbu"] <= 1.0
    assert pre["phase"] == "prefill" and dec["phase"] == "decode"
    assert pre["mbu"] >= dec["mbu"]
    assert pre["mfu"] >= dec["mfu"]
    # a token-packed step carrying BOTH row kinds reports as "mixed" —
    # its (mostly decode) bytes must not bias the prefill gauge
    mix = t.on_step(prefill_tokens=8, prefill_ctx=ctx_positions(0, 8),
                    decode_tokens=3, decode_ctx=3 * 20.0,
                    sampled_rows=4, wall_s=0.01)
    assert mix["phase"] == "mixed"
    snap = t.snapshot()
    assert snap["window_steps"] == 3
    assert set(snap["mbu"]) == {"prefill", "decode", "mixed"}
    assert 0.0 < snap["mfu"] <= 1.0
    assert len(snap["recent"]) == 3
    assert t.snapshot(recent=0)["recent"] == [], \
        "recent=0 means NO per-step list, not the whole window"


def test_tracker_clamps_and_skips_degenerate(geometry):
    t = RooflineTracker(geometry, peak_tflops=1e-12, peak_gbps=1e-9)
    r = t.on_step(prefill_tokens=64, prefill_ctx=ctx_positions(0, 64),
                  decode_tokens=0, decode_ctx=0.0, sampled_rows=64,
                  wall_s=1e-6)
    assert r["mfu"] == 1.0 and r["mbu"] == 1.0, "clamped, never > 1"
    assert t.on_step(prefill_tokens=0, prefill_ctx=0, decode_tokens=0,
                     decode_ctx=0, sampled_rows=0, wall_s=0.01) is None
    assert t.on_step(prefill_tokens=1, prefill_ctx=1, decode_tokens=0,
                     decode_ctx=0, sampled_rows=1, wall_s=0.0) is None
    # unknown peaks (0.0): utilization reads 0, never a ZeroDivision
    t0 = RooflineTracker(geometry, peak_tflops=0.0, peak_gbps=0.0)
    r = t0.on_step(prefill_tokens=4, prefill_ctx=10.0, decode_tokens=0,
                   decode_ctx=0.0, sampled_rows=1, wall_s=0.01)
    assert r["mfu"] == 0.0 and r["mbu"] == 0.0


# ------------------------------------------------------- live engine e2e
def test_live_engine_gauges_render_and_bound():
    """MFU/MBU gauge sanity on a live tiny engine: both phases present,
    every value in (0, 1], the flight records carry the v3 fields, and
    the /metrics render is validate-clean with the new series."""
    from vllm_omni_tpu.engine import EngineConfig, LLMEngine
    from vllm_omni_tpu.metrics.prometheus import (
        render_exposition,
        validate_exposition,
    )
    from vllm_omni_tpu.sampling_params import SamplingParams

    cfg = tfm.TransformerConfig.tiny(vocab_size=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    eng = LLMEngine(params, cfg, EngineConfig(
        num_pages=64, page_size=4, max_model_len=128, max_num_seqs=4,
        dtype=jnp.float32))
    # no peak is assumed for the CPU (the gauges would read 0 =
    # unknown); tell the tracker some so the live wiring has values
    assert eng.roofline.peak_flops == eng.roofline.peak_bytes == 0.0
    eng.roofline.peak_flops, eng.roofline.peak_bytes = 0.5e12, 50e9
    eng.generate([[1, 2, 3, 4, 5, 6, 7, 8]] * 2,
                 SamplingParams(temperature=0.0, max_tokens=6))
    snap = eng.metrics_snapshot()
    rf = snap["roofline"]
    assert 0.0 < rf["mfu"] <= 1.0
    assert set(rf["mbu"]) == {"prefill", "decode"}
    for v in rf["mbu"].values():
        assert 0.0 < v <= 1.0
    assert rf["window_steps"] > 0
    # flight records: record schema v3 fields on every executed step
    recs = [r for r in eng.flight.tail() if r.get("mfu") is not None]
    assert recs, "executed steps must carry roofline attribution"
    for r in recs:
        assert 0.0 < r["mfu"] <= 1.0
        assert r["roofline_phase"] in ("prefill", "decode")
        assert isinstance(r["trace_ids"], list)
    # /debug/engine rolling window
    from vllm_omni_tpu.introspection.debugz import engine_debug

    doc = engine_debug(eng)
    assert doc["roofline"]["recent"], "the /debug window must be live"
    # exposition: new series render and validate clean
    text = render_exposition({}, {0: snap})
    assert validate_exposition(text) == []
    assert 'vllm_omni_tpu_engine_step_mfu{stage="0"}' in text
    assert 'vllm_omni_tpu_engine_step_mbu{stage="0",phase="decode"}' \
        in text
    assert 'vllm_omni_tpu_engine_step_mbu{stage="0",phase="prefill"}' \
        in text


@pytest.mark.parametrize("rows,experts", [(1, 8.0), (64, 125.942)])
def test_moe_bytes_count_the_experts_a_step_touches(rows, experts):
    """A step reads the experts its tokens are expected to route to,
    E x (1 - (1 - k/E)^tokens): top-8 of 128 for one row, nearly all 128
    for 64 rows (what benchmark/trace.py counts), not 8 whatever the
    batch."""
    import dataclasses

    cfg = dataclasses.replace(
        tfm.TransformerConfig.tiny(vocab_size=64), moe=True,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=32)
    g = ModelGeometry.from_transformer_config(cfg, dtype_bytes=2)
    assert g.experts_touched(rows) == pytest.approx(experts, abs=0.01)
    one_expert = 2 * 3 * cfg.hidden_size * 32
    assert g.expert_bytes == one_expert
    assert g.step_bytes(rows, 0.0) == pytest.approx(
        g.weight_bytes + cfg.num_layers * experts * one_expert
        + g.kv_bytes_per_pos * rows, rel=1e-4)
    # FLOPs stay the routed cost: 8 experts a token
    router = cfg.hidden_size * 128
    attn = 4 * cfg.hidden_size * cfg.num_heads * cfg.head_dim \
        - 2 * cfg.hidden_size * (cfg.num_heads - cfg.num_kv_heads) \
        * cfg.head_dim
    assert g.flops_per_token == 2.0 * cfg.num_layers * (
        attn + router + 8 * one_expert // 2)
