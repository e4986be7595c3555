"""Cache-economics e2e on a real 2x1 in-proc topology: digests flow
from live radix trees into the router's board, dispatch spans carry the
expected-vs-actual prefix hit, the prefix_hit journey instant joins at
prefill output, and /debug/cache serves the fleet board."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from vllm_omni_tpu.disagg.router import DIGEST_MAX_NODES
from vllm_omni_tpu.disagg.service import build_inproc_router
from vllm_omni_tpu.engine import EngineConfig
from vllm_omni_tpu.introspection import debugz
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.sampling_params import SamplingParams
from vllm_omni_tpu.tracing import get_recorder, new_trace_context


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tfm.TransformerConfig.tiny(vocab_size=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return params, cfg


@pytest.fixture(autouse=True)
def _clean_recorder():
    get_recorder().drain()
    yield
    get_recorder().drain()


BASE = dict(num_pages=64, page_size=4, max_model_len=128,
            max_num_seqs=4, dtype=jnp.float32)
GREEDY = SamplingParams(temperature=0.0, max_tokens=4)
# one shared 2-page system prompt + per-request suffix pages
PREFIX = [1, 5, 9, 2, 7, 3, 8, 4]
SUFFIXES = [[11, 12, 13, 14], [21, 22, 23, 24], [31, 32, 33, 34]]


def _serve(router, prompts, prefix):
    ctxs = {}
    for i, p in enumerate(prompts):
        rid = f"{prefix}-{i}"
        ctxs[rid] = new_trace_context(rid)
        router.submit(list(p), GREEDY, request_id=rid,
                      additional_information={"trace": ctxs[rid]})
    finished = {}
    for _ in range(2000):
        if not router.has_unfinished:
            break
        router.step()
        for out in router.poll():
            finished[out.request_id] = out
    for out in router.poll():
        finished[out.request_id] = out
    assert not router.has_unfinished
    return ctxs, finished


def test_board_spans_and_debug_endpoint(tiny_model):
    params, cfg = tiny_model
    router = build_inproc_router(params, cfg, EngineConfig(**BASE),
                                 2, 1)
    prompts = [PREFIX + s for s in SUFFIXES]
    # wave 1 seeds the prefill radix trees with the shared prefix
    _, finished = _serve(router, prompts, "warm")
    assert all(not o.is_error for o in finished.values())
    # fold the freshly cached trees into the board NOW instead of
    # waiting for the step stride — wave 2's dispatch scoring must see
    # wave 1's caches deterministically
    router._refresh_digests()

    expo = router.cache.exposition()
    live = {rid: n for rid, n in expo["digest_nodes"].items() if n}
    assert live, "wave 1 must have populated at least one digest"
    assert all(n <= DIGEST_MAX_NODES for n in expo["digest_nodes"]
               .values())

    hot_ctxs, finished = _serve(router, prompts, "hot")
    assert all(not o.is_error for o in finished.values())
    hot_traces = {c["trace_id"] for c in hot_ctxs.values()}

    spans = get_recorder().drain()
    # every dispatch span quotes the board's expectation
    dispatches = [s for s in spans if s["name"] == "router_dispatch"]
    assert dispatches
    assert all("expected_hit_tokens" in s["args"]
               and "peer_hit_tokens" in s["args"] for s in dispatches)
    # wave 2 runs against warm caches: the prefix_hit instant joins
    # the dispatch-time expectation with the engine's actual count
    hits = [s for s in spans if s["name"] == "prefix_hit"
            and s["trace_id"] in hot_traces]
    assert hits, "no prefix_hit span on the warm wave"
    assert any(s["args"]["actual_hit_tokens"] >= len(PREFIX)
               for s in hits), hits
    for s in hits:
        assert {"expected_hit_tokens", "peer_hit_tokens",
                "actual_hit_tokens", "wasted_tokens"} <= set(s["args"])

    expo = router.cache.exposition()
    assert expo["fleet_hit_tokens"] >= len(PREFIX)
    assert expo["hit_rate"] > 0.0

    # the /debug/cache face over an omni-shaped object
    board = debugz.debug_cache(SimpleNamespace(router=router))
    assert board["enabled"] is True
    assert board["fleet"]["dispatches"] == 2 * len(prompts)
    assert board["regret_ledger"], "resolved dispatches must ledger"
    ledgered = {e["request_id"] for e in board["regret_ledger"]}
    assert any(r.startswith("hot") for r in ledgered)
    assert board["pending_dispatches"] == 0


@pytest.mark.parametrize("affinity", [False, True],
                         ids=["cacheblind", "affinity"])
def test_shared_prefix_replay_over_a_live_fleet(tiny_model, affinity):
    """The shared-prefix workload replayed open-loop over a live 2
    prefill x 2 decode service, under either dispatcher: the curve point
    is schema-valid, every replica's digest stays under the node cap
    whatever the traffic pushed, and the service's own /metrics render
    validates clean with every cache-economics series live."""
    from vllm_omni_tpu.disagg.service import DisaggService
    from vllm_omni_tpu.loadgen import (
        build_workload,
        poisson_arrivals,
        run_inproc,
        shared_prefix_catalog,
        summarize,
        validate_curve_point,
    )
    from vllm_omni_tpu.metrics.prometheus import validate_exposition

    params, cfg = tiny_model
    trace = build_workload(
        poisson_arrivals(20.0, 8, seed=0),
        catalog=shared_prefix_catalog(n_tenants=4, prefix_len=16),
        seed=0, vocab_size=60, id_prefix="cachereplay")
    router = build_inproc_router(
        params, cfg, EngineConfig(**{**BASE, "max_num_seqs": 2}), 2, 2,
        affinity_routing=affinity)
    service = DisaggService(router)
    try:
        records = run_inproc(service, trace, timeout_s=300.0)
        point = summarize(records, offered_rps=20.0)
        assert validate_curve_point(point) == []
        assert point["errors"] == 0
        assert point["completed"] == point["num_requests"], point
        text = service.render_metrics()
        expo = router.cache.exposition()
    finally:
        service.shutdown()
    assert all(n <= DIGEST_MAX_NODES
               for n in expo["digest_nodes"].values())
    assert validate_exposition(text) == []
    series = ["fleet_prefix_hit_tokens_total", "fleet_prefill_tokens_total",
              "fleet_prefix_hit_rate",
              "fleet_duplicate_prefill_tokens_total",
              "fleet_duplicate_prefix_tokens", "cache_digest_nodes"]
    if affinity:
        series.append("router_affinity_dispatch_total")
    assert [s for s in series if s not in text] == []
