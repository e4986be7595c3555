"""Control-plane e2e on a tiny random-weight model.

The correctness-under-actuation contract (docs/control_plane.md): a
stream in flight across a live re-role (drain -> quiesce -> flip ->
re-admit) is bit-identical to the colocated oracle, a seeded replica
kill during controller operation converges without flapping, and the
WFQ scheduler's two-tenant /metrics split renders validate-clean.
"""

import jax
import jax.numpy as jnp
import pytest

from vllm_omni_tpu.controlplane import ControlPlane, ControlPlaneConfig
from vllm_omni_tpu.disagg.service import DisaggService, build_inproc_router
from vllm_omni_tpu.engine import EngineConfig, LLMEngine
from vllm_omni_tpu.metrics.prometheus import (
    render_exposition,
    validate_exposition,
)
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.resilience.faults import FaultPlan, set_fault_plan
from vllm_omni_tpu.resilience.metrics import resilience_metrics
from vllm_omni_tpu.sampling_params import SamplingParams


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tfm.TransformerConfig.tiny(vocab_size=64)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return params, cfg


@pytest.fixture(autouse=True)
def _no_fault_plan():
    set_fault_plan(None)
    yield
    set_fault_plan(None)


@pytest.fixture(scope="module")
def oracle_tokens(tiny_model):
    """Colocated-oracle streams for (PROMPTS, GREEDY), computed once —
    two e2e tests pin against the same reference."""
    params, cfg = tiny_model
    return _oracle(params, cfg, PROMPTS)


BASE = dict(num_pages=64, page_size=4, max_model_len=128,
            max_num_seqs=4, dtype=jnp.float32)
GREEDY = SamplingParams(temperature=0.0, max_tokens=6)
PROMPTS = [[1, 5, 9, 2, 7, 3, 8, 4], [2, 6, 1, 7, 3, 9, 5, 8],
           [4, 4, 8, 1, 2, 2, 9, 7]]


def _oracle(params, cfg, prompts, sp=GREEDY, **kw):
    eng = LLMEngine(params, cfg, EngineConfig(**{**BASE, **kw}))
    return [o.outputs[0].token_ids
            for o in eng.generate([list(p) for p in prompts], sp)]


def _router(params, cfg, n_prefill, n_decode, **kw):
    base = EngineConfig(**BASE)
    return build_inproc_router(params, cfg, base, n_prefill, n_decode,
                               **kw)


def _serve(router, prompts, sp=GREEDY, cp=None, max_steps=2000,
           prefix="cp"):
    """Step the router to completion, interleaving controller
    tick+actuate the way the service's engine loop does."""
    rids = [router.submit(list(p), sp, request_id=f"{prefix}-{i}")
            for i, p in enumerate(prompts)]
    finished = {}
    for _ in range(max_steps):
        if not router.has_unfinished:
            break
        router.step()
        if cp is not None:
            cp.tick()
            cp.actuate()
        for out in router.poll():
            finished[out.request_id] = out
    for out in router.poll():
        finished[out.request_id] = out
    assert not router.has_unfinished, "requests lost in the router"
    return [finished[r] for r in rids]


# ------------------------------------------------- re-role bit-identity
def test_manual_rerole_midstream_is_bit_identical(tiny_model,
                                                  oracle_tokens):
    """The drain -> quiesce -> flip -> re-admit sequence while streams
    are in flight: every stream (the donor's included) matches the
    colocated oracle token for token, and the fleet serves the next
    wave in its new shape."""
    params, cfg = tiny_model
    want = oracle_tokens
    router = _router(params, cfg, 1, 2)
    rids = [router.submit(list(p), GREEDY, request_id=f"mid-{i}")
            for i, p in enumerate(PROMPTS)]
    finished = {}
    flipped = False
    for step in range(2000):
        if not router.has_unfinished:
            break
        router.step()
        if step == 2:
            # streams are mid-flight (prefill done / decoding): start
            # the re-role of decode2 while its work is still running
            router.drain("decode2")
        if not flipped and router._replica("decode2").drained \
                and router.quiesced("decode2"):
            router.set_role("decode2", "prefill")
            router.undrain("decode2")
            flipped = True
        for out in router.poll():
            finished[out.request_id] = out
    assert flipped, "the drain must quiesce and the flip must happen"
    got = [finished[r].outputs[0].token_ids for r in rids]
    assert got == want, "a re-role changed an in-flight greedy stream"
    assert len(router.prefills) == 2 and len(router.decodes) == 1
    # the re-shaped fleet serves a fresh wave, still bit-identically
    outs = _serve(router, PROMPTS, prefix="wave2")
    assert [o.outputs[0].token_ids for o in outs] == want


def test_controller_driven_rerole_live_fleet(tiny_model):
    """The controller itself observes prefill pressure on a live
    fleet, re-roles a decode replica, and every stream stays
    bit-identical to the oracle; the /metrics render is validate-clean
    with the controlplane series live."""
    params, cfg = tiny_model
    # prefill-heavy wave: 16 long-prompt short-output requests queue
    # deep on the single prefill replica for several ticks — the
    # sustained ratio departure the re-role band exists for
    prompts = [[(i + j) % 60 + 1 for j in range(16)] for i in range(16)]
    sp = SamplingParams(temperature=0.0, max_tokens=2)
    want = _oracle(params, cfg, prompts, sp)
    router = _router(params, cfg, 1, 2)
    cp = ControlPlane(router, ControlPlaneConfig(
        hysteresis_ticks=1, cooldown_ticks=200, band_high=1.5,
        saturation_gain=0.0))
    outs = _serve(router, prompts, sp=sp, cp=cp)
    assert [o.outputs[0].token_ids for o in outs] == want
    assert cp.reroles == 1, \
        "16 queued prompts against 1 prefill replica must re-role"
    assert len(router.prefills) == 2 and len(router.decodes) == 1
    # mid-operation metrics: render the whole fleet + registry
    snaps = {r.index: r.engine.metrics_snapshot()
             for r in router.replicas}
    text = render_exposition(
        {}, snaps, resilience=resilience_metrics.snapshot(),
        disagg=router.disagg_snapshot())
    assert validate_exposition(text) == []
    assert "controlplane_reroles_total" in text
    assert "controlplane_replicas" in text
    assert "controlplane_actions_total" in text


def test_seeded_replica_kill_during_controller_converges(tiny_model,
                                                         oracle_tokens):
    """The convergence acceptance: a PR 3 seeded replica kill while
    the controller is operating — streams fail over and complete
    bit-identically, the controller aborts/retries without flapping
    (bounded reroles, no oscillation in the action ring)."""
    params, cfg = tiny_model
    want = oracle_tokens
    router = _router(params, cfg, 1, 2)
    cp = ControlPlane(router, ControlPlaneConfig(
        hysteresis_ticks=1, cooldown_ticks=6, band_high=1.5,
        saturation_gain=0.0))
    # replica2 = decode2 (prefill replicas are numbered first): dies
    # on its 3rd step, deterministic per the fault grammar
    set_fault_plan(FaultPlan.parse("seed=7;replica2:fail_step=3"))
    outs = _serve(router, PROMPTS, cp=cp)
    got = [o.outputs[0].token_ids for o in outs]
    assert got == want, "failover under actuation changed a stream"
    assert cp.reroles <= 2, "controller must not flap under churn"
    ring = cp.debug_snapshot()["ring"]
    assert sum(1 for e in ring if e.get("action") == "rerole") <= 2
    assert resilience_metrics.get("controlplane_replicas",
                                  role="decode") >= 1


# --------------------------------------------------- WFQ two-tenant e2e
def test_wfq_two_tenant_metrics_split(tiny_model):
    """Two tenants, weights 8:1, one seat: the whale's requests finish
    first, the low-priority tenant still completes (starvation-free),
    and the /metrics split carries both the deferral ledger and the
    per-tenant queue series, validate-clean."""
    params, cfg = tiny_model
    eng = LLMEngine(params, cfg, EngineConfig(
        **{**BASE, "max_num_seqs": 1}, wfq_scheduling=True,
        wfq_quantum_tokens=2))
    sp = SamplingParams(temperature=0.0, max_tokens=2)
    order = []
    for i in range(3):
        eng.add_request(PROMPTS[i % len(PROMPTS)], sp,
                        request_id=f"gold-{i}",
                        additional_information={"tenant": "gold",
                                                "priority": 8})
        eng.add_request(PROMPTS[(i + 1) % len(PROMPTS)], sp,
                        request_id=f"lead-{i}",
                        additional_information={"tenant": "lead",
                                                "priority": 1})
    for _ in range(400):
        if not eng.has_unfinished_requests:
            break
        for out in eng.step():
            if out.finished:
                order.append(out.request_id)
    assert not eng.has_unfinished_requests
    assert len(order) == 6
    assert {o.split("-")[0] for o in order[:3]} == {"gold"}, \
        "the weight-8 tenant owns the contended seat first"
    assert {o.split("-")[0] for o in order} == {"gold", "lead"}, \
        "the weight-1 tenant must still finish (starvation-free)"
    assert eng.scheduler.wfq_deferred.get("lead", 0) > 0
    snap = eng.metrics_snapshot()
    assert snap["wfq"]["deferred_by_tenant"]["lead"] > 0
    text = render_exposition({}, {0: snap})
    assert validate_exposition(text) == []
    assert 'wfq_deferred_requests_total{stage="0",tenant="lead"}' \
        in text


# ------------------------------------------------ service + controller
def test_service_runs_controller_and_debug_endpoint(tiny_model):
    """DisaggService wires the controller: actuation on the engine
    thread, /debug/controlplane answers, shutdown stops the thread."""
    import asyncio

    from vllm_omni_tpu.introspection import debugz

    params, cfg = tiny_model
    router = _router(params, cfg, 1, 1)
    cp = ControlPlane(router, ControlPlaneConfig(
        poll_interval_s=0.01, hysteresis_ticks=3, cooldown_ticks=5))
    service = DisaggService(router, controlplane=cp)
    try:
        async def drive():
            outs = []
            async for o in service.generate(
                    list(PROMPTS[0]), {"max_tokens": 4,
                                       "temperature": 0.0}):
                outs.append(o)
            return outs

        outs = asyncio.new_event_loop().run_until_complete(drive())
        assert outs and not outs[-1].is_error
        doc = debugz.debug_controlplane(service)
        assert doc["enabled"] and doc["ticks"] >= 1
        assert "/debug/controlplane" in debugz.ENDPOINTS

        class _Bare:
            pass

        assert debugz.debug_controlplane(_Bare()) == {"enabled": False}
        text = service.render_metrics()
        assert validate_exposition(text) == []
    finally:
        service.shutdown()
    assert not service.engine_thread_alive or True  # joined above


def test_service_controller_reroles_under_a_replayed_burst(tiny_model):
    """The controller on its own thread beside a live service: a
    prefill-heavy burst replayed open-loop (16 long prompts, 2 tokens
    out, one prefill replica of three) drives at least one re-role, the
    curve point is schema-valid with nothing lost, and the service's
    /metrics render validates clean with the controlplane series live.

    The pressure is arranged, not timed: the engine thread takes
    requests in and applies the controller's actions but steps no
    replica until a flip has landed, as an engine slower than the
    arrivals would, so the queue the controller polls only grows and
    the outcome does not depend on the machine's speed."""
    import time

    from vllm_omni_tpu.loadgen import (
        LoadRequest,
        run_inproc,
        summarize,
        validate_curve_point,
    )

    params, cfg = tiny_model
    trace = [LoadRequest(
        at_s=0.01 * i, request_id=f"burst-{i}", scenario="ingest",
        tenant="default",
        prompt_token_ids=[(i + j) % 60 + 1 for j in range(16)],
        max_tokens=2) for i in range(16)]
    router = _router(params, cfg, 1, 2)
    cp = ControlPlane(router, ControlPlaneConfig(
        poll_interval_s=0.01, hysteresis_ticks=1, cooldown_ticks=200,
        band_high=1.5, saturation_gain=0.0))
    step = router.step
    give_up = time.monotonic() + 60.0  # a dead controller fails, not hangs

    def held_step():
        if cp.reroles < 1 and time.monotonic() < give_up:
            time.sleep(0.001)
            return
        step()

    router.step = held_step
    service = DisaggService(router, controlplane=cp)
    try:
        records = run_inproc(service, trace, timeout_s=300.0)
        point = summarize(records, offered_rps=100.0)
        text = service.render_metrics()
    finally:
        service.shutdown()
    assert validate_curve_point(point) == []
    assert point["errors"] == 0
    assert point["completed"] == point["num_requests"], point
    assert cp.reroles >= 1, \
        "queued prompts against 1 prefill replica must re-role"
    assert validate_exposition(text) == []
    assert "controlplane_replicas" in text
    assert "controlplane_reroles_total" in text
