"""E2E: int8-resident paged KV (--kv-cache-dtype int8).

The int8 pool must (1) decode the tiny model greedily IDENTICALLY to
the full-precision engine (KV rounding on these activations never flips
an argmax at vocab 64), (2) fit >= 1.8x the pages of the bf16 layout in
the same HBM budget, (3) account its bytes exactly in the device
ledger / debug snapshots, and (4) round-trip through every KV movement
path (tier offload, wire handoff, shard/merge) bit-exactly — once
quantized at write time, nothing may quantize it again."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.engine import EngineConfig, LLMEngine
from vllm_omni_tpu.kvcache.quant import (
    bytes_per_token,
    is_quant_payload,
    quantize_payload,
)
from vllm_omni_tpu.kvcache.tiers import TieredKVStore
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.sampling_params import SamplingParams

GREEDY = SamplingParams(temperature=0.0, max_tokens=6)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tfm.TransformerConfig.tiny(vocab_size=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return params, cfg


def _engine(params, cfg, **kw):
    defaults = dict(num_pages=64, page_size=4, max_model_len=128,
                    max_num_seqs=4, dtype=jnp.float32)
    defaults.update(kw)
    return LLMEngine(params, cfg, EngineConfig(**defaults))


def _payloads_equal(a, b):
    for (k, v), (k2, v2) in zip(a, b):
        for h, h2 in ((k, k2), (v, v2)):
            if isinstance(h, (tuple, list)):
                np.testing.assert_array_equal(np.asarray(h[0]),
                                              np.asarray(h2[0]))
                np.testing.assert_array_equal(np.asarray(h[1]),
                                              np.asarray(h2[1]))
            else:
                np.testing.assert_array_equal(np.asarray(h),
                                              np.asarray(h2))


# ----------------------------------------------------------- numerics
def test_int8_engine_greedy_stream_matches_dense_oracle(tiny_model):
    params, cfg = tiny_model
    prompts = [[1, 5, 9, 2, 7], [3, 1, 4, 1, 5, 9, 2, 6], [10]]
    dense = _engine(params, cfg)
    want = [o.outputs[0].token_ids
            for o in dense.generate([list(p) for p in prompts], GREEDY)]
    q = _engine(params, cfg, kv_cache_dtype="int8")
    got = [o.outputs[0].token_ids
           for o in q.generate([list(p) for p in prompts], GREEDY)]
    assert got == want


def test_rejects_unknown_kv_cache_dtype(tiny_model):
    params, cfg = tiny_model
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        _engine(params, cfg, kv_cache_dtype="fp4")


def test_untileable_geometry_refused_on_a_kernel_platform(monkeypatch):
    """On a platform that compiles the paged kernels (stubbed: the CPU
    lane interprets them), an engine whose pages the kernels cannot tile
    is refused at construction — int8 or dense — instead of being served
    through the gather reference.  The CPU lane keeps constructing it."""
    from vllm_omni_tpu.ops import _dispatch

    cfg = tfm.TransformerConfig(
        vocab_size=64, hidden_size=128, num_layers=1, num_heads=1,
        num_kv_heads=1, head_dim=128, intermediate_size=128)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    _engine(params, cfg, page_size=12, kv_cache_dtype="int8")
    monkeypatch.setattr(_dispatch, "pallas_mode", lambda: "native")
    for dtype in ("int8", "auto"):
        with pytest.raises(ValueError, match="page_size 12 % 8"):
            _engine(params, cfg, page_size=12, kv_cache_dtype=dtype)
    tiny = tfm.TransformerConfig.tiny(vocab_size=64)
    with pytest.raises(ValueError, match="head_dim 16 % 128"):
        _engine(tfm.init_params(jax.random.PRNGKey(0), tiny, jnp.float32),
                tiny, page_size=16)


# ----------------------------------------------------------- capacity
def test_int8_pool_holds_1p8x_the_bf16_pages(tiny_model):
    """Same HBM budget (the bf16 config's num_pages worth of bytes):
    the int8 engine's page pool must be >= 1.8x — the ISSUE's headline
    capacity claim, and what lets it hold more concurrent sessions."""
    params, cfg = tiny_model
    bf16 = _engine(params, cfg, dtype=jnp.bfloat16, num_pages=32)
    q = _engine(params, cfg, dtype=jnp.bfloat16, num_pages=32,
                kv_cache_dtype="int8")
    assert q.scheduler.kv.num_pages >= 1.8 * bf16.scheduler.kv.num_pages
    assert q.scheduler.kv.num_pages > 32  # config value was re-derived


def test_explicit_hbm_budget_sizes_the_pool(tiny_model):
    params, cfg = tiny_model
    budget = 1 << 20
    q = _engine(params, cfg, kv_cache_dtype="int8",
                kv_hbm_budget_bytes=budget)
    kv_bytes = q.runner.memory_components()["kv_pages"]
    assert kv_bytes <= budget
    # the pool actually uses the budget (not stuck at the config count)
    assert kv_bytes > 0.9 * budget


# ---------------------------------------------------------- accounting
def test_ledger_kv_pages_counts_data_and_scales_exactly(tiny_model):
    params, cfg = tiny_model
    q = _engine(params, cfg, kv_cache_dtype="int8")
    want = 0
    for k_half, v_half in q.runner.kv_caches:
        for half in (k_half, v_half):
            assert isinstance(half, tuple)
            data, scale = half
            assert data.dtype == jnp.int8
            assert scale.dtype == jnp.float32
            want += data.nbytes + scale.nbytes
    assert q.runner.memory_components()["kv_pages"] == want


def test_snapshots_report_dtype_and_bytes_per_token(tiny_model):
    params, cfg = tiny_model
    q = _engine(params, cfg, kv_cache_dtype="int8")
    snap = q.metrics_snapshot()
    assert snap["kv"]["cache_dtype"] == "int8"
    want_bpt = bytes_per_token(
        cfg.num_layers, cfg.num_kv_heads, q.config.page_size,
        cfg.head_dim, quantized=True)
    assert snap["kv"]["bytes_per_token"] == want_bpt
    dbg = q.scheduler.kv.debug_snapshot()
    assert dbg["cache_dtype"] == "int8"
    assert dbg["bytes_per_token"] == want_bpt
    dense = _engine(params, cfg)
    snap2 = dense.metrics_snapshot()
    assert snap2["kv"]["cache_dtype"] == "float32"
    assert snap2["kv"]["bytes_per_token"] > want_bpt


# ------------------------------------------------ cross-path round trip
def test_offload_restore_never_double_quantizes(tiny_model):
    """The satellite-1 contract: extract from the int8 pool -> park in
    the tier store -> fetch -> inject into FRESH pages -> extract again
    must be BIT-exact (data bytes and scales) — a second absmax pass
    anywhere in the loop would drift the bytes."""
    params, cfg = tiny_model
    q = _engine(params, cfg, kv_cache_dtype="int8")
    runner = q.runner
    rng = np.random.default_rng(11)
    seq_len = 10
    dense_payload = [
        (rng.standard_normal((cfg.num_kv_heads, seq_len, cfg.head_dim))
         .astype(np.float32),
         rng.standard_normal((cfg.num_kv_heads, seq_len, cfg.head_dim))
         .astype(np.float32))
        for _ in range(cfg.num_layers)]
    # quantized ONCE here, by the shared write-op rounding
    runner.inject_kv([1, 2, 3], dense_payload)
    wire = runner.extract_kv([1, 2, 3], seq_len)
    assert is_quant_payload(wire)
    # ... even through a tier store configured to int8-quantize its
    # cold payloads: resident-quant parks verbatim
    store = TieredKVStore(quant="int8")
    store.put("prefix/a", wire)
    back = store.fetch("prefix/a")
    assert is_quant_payload(back)
    _payloads_equal(back, wire)
    runner.inject_kv([5, 6, 7], back)
    again = runner.extract_kv([5, 6, 7], seq_len)
    _payloads_equal(again, wire)


def test_quant_payload_into_dense_engine_dequantizes(tiny_model):
    """A quantized handoff landing on a bf16/f32 pool dequantizes at
    inject: the restored context must match the dequantized values to
    f32 cast precision (one rounding), never a second quant step."""
    params, cfg = tiny_model
    q = _engine(params, cfg, kv_cache_dtype="int8")
    dense = _engine(params, cfg)
    rng = np.random.default_rng(13)
    seq_len = 8
    payload = [
        (rng.standard_normal((cfg.num_kv_heads, seq_len, cfg.head_dim))
         .astype(np.float32),
         rng.standard_normal((cfg.num_kv_heads, seq_len, cfg.head_dim))
         .astype(np.float32))
        for _ in range(cfg.num_layers)]
    q.runner.inject_kv([1, 2], payload)
    wire = q.runner.extract_kv([1, 2], seq_len)
    dense.runner.inject_kv([3, 4], wire)
    got = dense.runner.extract_kv([3, 4], seq_len)
    assert not is_quant_payload(got)
    for (k, v), ((kq, ks), (vq, vs)) in zip(got, wire):
        kd = kq.astype(np.float32) * np.repeat(
            ks, q.config.page_size, axis=1)[:, :seq_len, None]
        np.testing.assert_allclose(np.asarray(k), kd, rtol=1e-6,
                                   atol=1e-6)


def test_injected_kv_session_decodes_identically(tiny_model):
    """Full disagg-style handoff at the ENGINE api: prefill on an int8
    engine with a kv sink (the payload leaves in the quant wire
    layout), re-add the request on a SECOND int8 engine via
    injected_kv, and require the identical greedy stream."""
    from vllm_omni_tpu.core.scheduler import KVTransferConfig

    params, cfg = tiny_model
    prompt = [1, 5, 9, 2, 7, 3, 8, 4]
    want = _engine(params, cfg, kv_cache_dtype="int8") \
        .generate([list(prompt)], GREEDY)[0].outputs[0].token_ids

    pre = _engine(params, cfg, kv_cache_dtype="int8",
                  kv_transfer=KVTransferConfig(trigger="prefill_finished"))
    shipped = []
    pre.kv_transfer_sink = lambda req, payload: shipped.append(payload)
    first = pre.generate(
        [list(prompt)], SamplingParams(temperature=0.0, max_tokens=1)
    )[0].outputs[0].token_ids
    assert first == want[:1]
    (payload,) = shipped
    assert is_quant_payload(payload)

    dec = _engine(params, cfg, kv_cache_dtype="int8")
    dec.add_request(list(prompt), GREEDY, request_id="d",
                    injected_kv=payload)
    # injected prefix skips recompute: only the last prompt token left
    assert dec.scheduler.waiting[0].num_computed_tokens == len(prompt) - 1
    outs = []
    while dec.has_unfinished_requests:
        outs.extend(dec.step())
    assert outs[0].outputs[0].token_ids == want


# --------------------------------------------------- transport + shards
def test_ship_recv_quant_payload_roundtrip():
    from vllm_omni_tpu.distributed.tcp import TCPConnector
    from vllm_omni_tpu.distributed.kv_transfer import recv_kv, ship_kv

    rng = np.random.default_rng(5)
    payload = quantize_payload(
        [(rng.standard_normal((2, 9, 8)).astype(np.float32),
          rng.standard_normal((2, 9, 8)).astype(np.float32))
         for _ in range(3)], page_size=4)
    conn = TCPConnector(serve=True)
    try:
        ship_kv(conn, "req0/0_1", payload)
        got = recv_kv(conn, "req0/0_1", timeout=10.0)
    finally:
        conn.close()
    assert is_quant_payload(got)
    _payloads_equal(got, payload)


def test_tampered_scale_fails_integrity_check():
    """The CRC chains data -> scale: corrupting ONLY the scale array
    (data bytes intact) must fail verification — a flipped scale
    silently rescales every token of its page."""
    from vllm_omni_tpu.distributed.kv_transfer import (
        KVIntegrityError,
        _layer_spec,
        _verify_layer,
    )

    rng = np.random.default_rng(6)
    payload = quantize_payload(
        [(rng.standard_normal((2, 8, 8)).astype(np.float32),
          rng.standard_normal((2, 8, 8)).astype(np.float32))],
        page_size=4)
    (kq, ks), (vq, vs) = payload[0]
    spec = _layer_spec((kq, ks), (vq, vs))
    _verify_layer("req", 0, (kq, ks), (vq, vs), spec)  # clean passes
    bad = ks.copy()
    bad[0, 0] *= 2.0
    with pytest.raises(KVIntegrityError, match="checksum"):
        _verify_layer("req", 0, (kq, bad), (vq, vs), spec)
    # dense payload against a quant header: layout mismatch, not crc
    with pytest.raises(KVIntegrityError, match="layout"):
        _verify_layer("req", 0, kq, vq, spec)


def test_shard_merge_quant_payload_roundtrip():
    from vllm_omni_tpu.disagg.roles import merge_kv_shards, shard_kv_payload

    rng = np.random.default_rng(8)
    payload = quantize_payload(
        [(rng.standard_normal((4, 9, 8)).astype(np.float32),
          rng.standard_normal((4, 9, 8)).astype(np.float32))
         for _ in range(2)], page_size=4)
    shards = shard_kv_payload(payload, 2)
    assert len(shards) == 2
    assert shards[0][0][0][0].shape[0] == 2  # Hkv split across shards
    merged = merge_kv_shards(shards)
    _payloads_equal(merged, payload)
