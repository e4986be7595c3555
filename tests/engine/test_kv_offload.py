"""Tiered KV offload, end to end on a tiny random-weight transformer.

The contract (docs/kv_cache.md): a preempted-then-restored session and
an evicted-then-readopted prefix must continue their GREEDY streams
bit-identically to a never-offloaded run — parking KV is an execution
detail, not a numerics change — while actually avoiding the recompute
(restored_tokens > 0).  Failure of any tier degrades to recompute, never
to wrong tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.engine import EngineConfig, LLMEngine
from vllm_omni_tpu.kvcache.tiers import (
    TieredKVStore,
    dequantize_kv_payload,
    payload_nbytes,
    quantize_kv_payload,
)
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.sampling_params import SamplingParams


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


def _offload_engine(params, cfg, **kw):
    defaults = dict(kv_offload=True, kv_offload_policy="always")
    defaults.update(kw)
    return _engine(params, cfg, **defaults)


def _toks(outs):
    return [o.outputs[0].token_ids for o in outs]


GREEDY = SamplingParams(temperature=0.0, max_tokens=6)


# ------------------------------------------------- park on preemption
def test_preempted_session_restores_bit_identically(tiny_model):
    params, cfg = tiny_model
    # 6 pages of 4 = 24 slots: two prompt-8/max-6 requests (14 tokens =
    # 4 pages each) cannot coexist -> one gets preempted mid-decode
    prompts = [[1, 5, 9, 2, 7, 3, 8, 4], [2, 6, 1, 7, 3, 9, 5, 8]]
    want = _toks(_engine(params, cfg).generate(
        [list(p) for p in prompts], GREEDY))

    eng = _offload_engine(params, cfg, num_pages=6,
                          enable_prefix_caching=False)
    got = _toks(eng.generate([list(p) for p in prompts], GREEDY))
    assert got == want, "offload-restore changed the greedy stream"
    kv = eng.scheduler.kv
    assert eng.scheduler.num_preemptions > 0, \
        "scenario must actually preempt"
    assert kv.parked_tokens > 0, "preemption must park, not discard"
    assert kv.restored_tokens > 0, "re-admission must restore the park"
    assert eng.kv_tiers.bytes_moved.get(("host", "out"), 0) > 0
    assert eng.kv_tiers.bytes_moved.get(("host", "in"), 0) > 0
    # one-shot park payloads are dropped after injection
    assert eng.kv_tiers.host_entries() == 0


def test_preempted_restore_skips_recompute(tiny_model):
    """The restored request resumes as a 1-token continuation, not a
    full re-prefill: recompute-tokens-avoided is the parked run."""
    params, cfg = tiny_model
    eng = _offload_engine(params, cfg, num_pages=6,
                          enable_prefix_caching=False)
    prompts = [[1, 5, 9, 2, 7, 3, 8, 4], [2, 6, 1, 7, 3, 9, 5, 8]]
    _ = eng.generate([list(p) for p in prompts], GREEDY)
    kv = eng.scheduler.kv
    # every parked token came back (nothing recomputed from scratch)
    assert kv.restored_tokens == kv.parked_tokens > 0


# ------------------------------------------- eviction offload + re-adopt
def _multi_turn(params, cfg, engine_kw, mutate=None):
    """Turn 1 caches a prompt prefix; a filler request evicts it under
    pool pressure; turn 2 shares the prefix.  Returns (eng, turn1_out,
    turn2_out, turn2_prompt, turn2_params)."""
    eng = _engine(params, cfg, **engine_kw)
    p1 = [1, 5, 9, 2, 7, 3, 8, 4]
    sp = SamplingParams(temperature=0.0, max_tokens=4)
    out1 = _toks(eng.generate([list(p1)], sp))[0]
    # filler: a 26-token prompt needs 7 pages (6 free after turn 1), so
    # its prefill evicts one cached turn-1 node into the cold tier
    filler = [list(range(10, 36))]
    eng.generate(filler, SamplingParams(temperature=0.0, max_tokens=1))
    if mutate is not None:
        mutate(eng)
    p2 = list(p1) + list(out1) + [11, 13]
    out2 = _toks(eng.generate([p2], sp))[0]
    return eng, out1, out2, p2, sp


def test_evicted_prefix_restores_from_host_tier(tiny_model):
    params, cfg = tiny_model
    kw = dict(num_pages=8, kv_offload=True, kv_offload_policy="always")
    eng, _, out2, p2, sp = _multi_turn(params, cfg, kw)
    oracle = _toks(_engine(params, cfg,
                           enable_prefix_caching=False).generate(
        [list(p2)], sp))[0]
    assert out2 == oracle, "cold-prefix restore changed the stream"
    kv = eng.scheduler.kv
    assert kv.offload_evictions > 0, "pressure must offload-evict"
    assert kv.restored_tokens > 0, "turn 2 must restore a cold node"
    assert kv.prefix_hit_tokens > 0


def test_lost_cold_payload_degrades_to_recompute(tiny_model):
    """Shed/lost host-tier payloads: the match stops at the hot prefix
    and the rest recomputes — same tokens, no restore."""
    params, cfg = tiny_model

    def nuke_host(eng):
        eng.kv_tiers._host.clear()
        eng.kv_tiers._host_bytes = 0

    kw = dict(num_pages=8, kv_offload=True, kv_offload_policy="always")
    eng, _, out2, p2, sp = _multi_turn(params, cfg, kw,
                                       mutate=nuke_host)
    oracle = _toks(_engine(params, cfg,
                           enable_prefix_caching=False).generate(
        [list(p2)], sp))[0]
    assert out2 == oracle


def test_restore_failure_mid_drain_rewinds_and_recomputes(tiny_model):
    """Payload vanishes BETWEEN match and fetch (the drain-time race):
    the engine rewinds the request past the injected prefix and
    recomputes — stream still bit-identical."""
    params, cfg = tiny_model

    def break_fetch(eng):
        eng.kv_tiers.fetch = lambda key: None

    kw = dict(num_pages=8, kv_offload=True, kv_offload_policy="always")
    eng, _, out2, p2, sp = _multi_turn(params, cfg, kw,
                                       mutate=break_fetch)
    oracle = _toks(_engine(params, cfg,
                           enable_prefix_caching=False).generate(
        [list(p2)], sp))[0]
    assert out2 == oracle


# ------------------------------------------------------------ remote tier
def test_remote_tier_round_trip(tiny_model):
    """A ~0-byte host tier demotes every payload to the remote
    connector; restores promote back through it — still bit-exact."""
    params, cfg = tiny_model
    kw = dict(num_pages=8, kv_offload=True, kv_offload_policy="always",
              kv_host_tier_bytes=1,
              kv_offload_connector="inproc",
              kv_offload_connector_args={
                  "namespace": "test-kv-remote"})
    eng, _, out2, p2, sp = _multi_turn(params, cfg, kw)
    oracle = _toks(_engine(params, cfg,
                           enable_prefix_caching=False).generate(
        [list(p2)], sp))[0]
    assert out2 == oracle
    moved = eng.kv_tiers.bytes_moved
    assert moved.get(("remote", "out"), 0) > 0, "host tier must demote"


# ----------------------------------------------------------- async engine
def test_async_pipeline_with_offload_stays_bit_identical(tiny_model):
    params, cfg = tiny_model
    prompts = [[1, 5, 9, 2, 7, 3, 8, 4], [2, 6, 1, 7, 3, 9, 5, 8]]
    want = _toks(_engine(params, cfg).generate(
        [list(p) for p in prompts], GREEDY))
    eng = _offload_engine(params, cfg, num_pages=6,
                          enable_prefix_caching=False,
                          async_scheduling=True)
    got = _toks(eng.generate([list(p) for p in prompts], GREEDY))
    assert got == want
    assert eng.scheduler.kv.parked_tokens > 0


# ----------------------------------------------------- cold-path payloads
def test_int8_kv_quant_round_trip_bounded_error():
    rng = np.random.default_rng(7)
    payload = [
        (rng.standard_normal((2, 8, 16)).astype(np.float32),
         rng.standard_normal((2, 8, 16)).astype(np.float32))
        for _ in range(3)]
    q = quantize_kv_payload(payload)
    assert q["quant"] == "int8"
    # int8 bodies + f32 scales must be well under half the f32 source
    assert payload_nbytes(q) < payload_nbytes(payload) * 0.30
    back = dequantize_kv_payload(q)
    for (k, v), (k2, v2) in zip(payload, back):
        assert k2.dtype == np.float32
        # absmax/127 per (layer, head) bounds the roundtrip error
        for a, b in ((k, k2), (v, v2)):
            bound = np.abs(a).max(axis=(1, 2), keepdims=True) / 127.0
            assert np.all(np.abs(a - b) <= bound + 1e-7)


def test_quantized_store_halves_host_bytes():
    rng = np.random.default_rng(3)
    payload = [(rng.standard_normal((2, 4, 8)).astype(np.float32),
                rng.standard_normal((2, 4, 8)).astype(np.float32))]
    raw = TieredKVStore(quant="none")
    raw.put("k", payload)
    q = TieredKVStore(quant="int8")
    q.put("k", payload)
    assert q.host_bytes() < raw.host_bytes() * 0.5
    got = q.fetch("k")
    assert got[0][0].shape == payload[0][0].shape


def test_int8_cold_path_engine_still_decodes(tiny_model):
    """Quantized cold path: streams may differ from the oracle by
    design (KV rounded), but the engine must stay healthy and the
    restored session must keep decoding valid tokens."""
    params, cfg = tiny_model
    kw = dict(num_pages=8, kv_offload=True, kv_offload_policy="always",
              kv_offload_quant="int8")
    eng, _, out2, _, sp = _multi_turn(params, cfg, kw)
    assert len(out2) == sp.max_tokens
    assert all(0 <= t < cfg.vocab_size for t in out2)
    assert eng.scheduler.kv.restored_tokens > 0


# -------------------------------------------------------------- /metrics
def test_offload_metrics_render_and_validate(tiny_model):
    from vllm_omni_tpu.metrics.prometheus import (
        render_exposition,
        validate_exposition,
    )

    params, cfg = tiny_model
    eng = _offload_engine(params, cfg, num_pages=6,
                          enable_prefix_caching=False)
    prompts = [[1, 5, 9, 2, 7, 3, 8, 4], [2, 6, 1, 7, 3, 9, 5, 8]]
    eng.generate([list(p) for p in prompts], GREEDY)
    snap = eng.metrics_snapshot()
    tiers = snap["kv_tiers"]
    assert tiers["parked_tokens"] > 0
    assert tiers["restored_tokens"] > 0
    text = render_exposition(
        {"stages": {}, "edges": {}, "e2e": {}}, {0: snap})
    assert validate_exposition(text) == []
    assert "vllm_omni_tpu_kv_offload_bytes_total" in text
    assert "vllm_omni_tpu_kv_restore_seconds_count" in text
    assert "vllm_omni_tpu_kv_parked_tokens_total" in text


def test_policy_auto_refuses_until_told_the_rates(tiny_model):
    """mode=auto assumes no host bandwidth or prefill rate: an engine
    asking for it (the config default) is refused with the way out, and
    a policy TOLD slow-link rates vetoes tiny runs — parking a handful
    of tokens with fixed overhead loses to recompute."""
    from vllm_omni_tpu.kvcache import OffloadPolicy

    params, cfg = tiny_model
    with pytest.raises(ValueError, match="kv_offload_policy"):
        _offload_engine(params, cfg, num_pages=6,
                        kv_offload_policy="auto")
    told = OffloadPolicy.for_model(
        cfg.num_layers, cfg.num_kv_heads, cfg.head_dim, 4, mode="auto",
        host_bandwidth_bytes_s=1e8, prefill_tokens_per_s=2000.0)
    assert not told.worth_offloading(8)
    assert told.worth_offloading(100_000) == (
        2 * told.transfer_seconds(100_000)
        < told.recompute_seconds(100_000))
