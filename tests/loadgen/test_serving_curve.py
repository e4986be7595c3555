"""Fast in-process end-to-end: the open-loop runner drives AsyncOmni
and produces a schema-valid ``serving_curve`` record (the shape
``loadgen.runner.validate_curve_point`` defines)."""

import json

import pytest

from vllm_omni_tpu.config.stage import StageConfig
from vllm_omni_tpu.loadgen import (
    SLOTargets,
    build_workload,
    poisson_arrivals,
    run_inproc,
    summarize,
    validate_curve_point,
)
from vllm_omni_tpu.loadgen.workload import Scenario


def _stage(extra=None):
    args = {"model_factory": "tests.helpers:tiny_lm_factory",
            "num_pages": 128, "page_size": 4, "max_model_len": 128}
    args.update(extra or {})
    return StageConfig(
        stage_id=0, stage_type="llm", engine_args=args,
        engine_input_source=[-1], final_output=True,
        final_output_type="text",
        default_sampling_params={"temperature": 0.0},
    )


_CATALOG = [Scenario("chat", weight=1.0, prompt_len=(4, 12),
                     output_len=(2, 5))]


# module-scoped: the tiny model's XLA compiles dominate this file's
# runtime; the first test's exact-count assertions rely on running
# before the second (pytest file order — tier-1 disables randomization)
@pytest.fixture(scope="module")
def async_omni():
    from vllm_omni_tpu.entrypoints.async_omni import AsyncOmni

    omni = AsyncOmni(stage_configs=[_stage(
        {"slo_ttft_ms": 60_000.0, "slo_tpot_ms": 60_000.0})])
    yield omni
    omni.shutdown()


def test_inproc_end_to_end_serving_curve(async_omni, tmp_path):
    rate = 20.0
    wl = build_workload(poisson_arrivals(rate, 6, seed=0),
                        catalog=_CATALOG, seed=1, vocab_size=60,
                        tenants=("a", "b"))
    records = run_inproc(async_omni, wl)
    assert len(records) == 6
    assert all(r.status == "ok" for r in records), \
        [(r.request_id, r.status) for r in records]
    assert all(r.first_s is not None and r.end_s >= r.first_s
               for r in records)
    assert all(r.tokens_out > 0 for r in records)
    point = summarize(records, rate,
                      SLOTargets(ttft_ms=60_000.0, tpot_ms=60_000.0))
    assert validate_curve_point(point) == []
    assert point["completed"] == 6 and point["attained_tok_per_s"] > 0
    assert point["slo_attainment"] == 1.0  # wide-open targets
    # the artifact round-trips as JSON (the BENCH_*.json contract)
    path = tmp_path / "curve.json"
    path.write_text(json.dumps({"serving_curve": [point]}))
    loaded = json.loads(path.read_text())["serving_curve"][0]
    assert validate_curve_point(loaded) == []
    # the engine accounted the same traffic per tenant, mid-run
    # scrape-able through the stage snapshot
    snap = async_omni._omni.stages[0].engine.metrics_snapshot()
    tenants = snap["slo"]["tenants"]
    assert tenants["a"]["finished"] + tenants["b"]["finished"] == 6
    assert snap["queue_wait_ms"]["count"] == 6


def test_inproc_open_loop_never_gates_arrivals(async_omni):
    """Open-loop invariant: every arrival fires at (or past) its
    scheduled offset even while earlier requests are still in flight —
    fired times never collapse onto completion times."""
    wl = build_workload(poisson_arrivals(50.0, 8, seed=3),
                        catalog=_CATALOG, seed=3, vocab_size=60)
    records = run_inproc(async_omni, wl)
    by_id = {r.request_id: r for r in records}
    for lr in wl:
        assert by_id[lr.request_id].fired_s >= lr.at_s - 1e-3


def test_inproc_shed_classified():
    from vllm_omni_tpu.entrypoints.async_omni import AsyncOmni

    omni = AsyncOmni(stage_configs=[_stage({"max_queue_depth": 0})])
    try:
        wl = build_workload([0.0, 0.01], catalog=_CATALOG, seed=0,
                            vocab_size=60)
        records = run_inproc(omni, wl)
        assert [r.status for r in records] == ["shed", "shed"]
        point = summarize(records, 10.0, SLOTargets(ttft_ms=1.0))
        assert point["shed"] == 2 and point["completed"] == 0
        assert point["slo_attainment"] == 0.0
        assert validate_curve_point(point) == []
    finally:
        omni.shutdown()
