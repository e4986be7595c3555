"""Benchmark driver: the two BASELINE.md north-star metrics on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

1. FLAGSHIP (the "metric"/"value" pair): Qwen-Image text->image at the
   REAL geometry (60-layer / 24-head / 3584 MMDiT, 20.4B params) at
   1024px / 50-step / bs=1.  41 GB of bf16 weights exceed one v5e's
   16 GB HBM, so the run pins what fits resident and streams the rest
   per step (vllm_omni_tpu/diffusion/offload.py) — host->HBM transfers
   overlapped with compute; the number is transfer-bound and honest.
   Weights are tiled host randoms (TPU matmul timing is
   value-independent); the geometry is real.
2. SECONDARY ("secondary_metrics" key): Qwen3-Omni-style AR serving —
   thinker tok/s/chip + p50 TTFT from a bench-scale MoE thinker (real
   head_dim/GQA/top-k structure, layer/expert counts sized to fit one
   16 GB chip resident; arch disclosed) through the real engine path
   (paged attention, continuous batching).
3. OPTIONAL ("step_cache_variant" key, budget permitting): the flagship
   with TeaCache step skipping (reference claims 1.5-2x,
   docs/user_guide/diffusion_acceleration.md:15).

The reference publishes no absolute numbers (BASELINE.json
"published": {}), so vs_baseline is null.  Extra keys report analytic
DiT MFU and the benched architectures so the numbers are interpretable.

If the real-geometry run fails (e.g. insufficient host RAM), the bench
falls back to the resident 16-layer preset and says so in the arch block.

Env knobs: OMNI_BENCH_PX / OMNI_BENCH_STEPS / OMNI_BENCH_ITERS /
OMNI_BENCH_SIZE (config preset; "real" [default] => streaming) /
OMNI_BENCH_SCHEDULER (euler|unipc) / OMNI_BENCH_CACHE=1 (force TeaCache
on the flagship itself) / OMNI_BENCH_PEAK_TFLOPS / OMNI_BENCH_BUDGET_S
(wall-clock budget; variants are skipped when exceeded) /
OMNI_BENCH_SKIP_AR=1 / OMNI_BENCH_AR_ASYNC=1 (AR bench runs the async
pipelined step — the round-trip amortization that replaced the retired
multi-step window; the emitted "step_phase" block reports host/device
ms + overlap ratio either way) /
OMNI_BENCH_AR_UNIFIED=1 (unified SCHEDULER packing policy — decodes
claim the budget first, chunked prefill as mechanism; execution is
always one token-packed dispatch per non-pure-decode step since PR 11,
and step_phase reports padding efficiency either way) /
OMNI_BENCH_SKIP_CACHE_VARIANT=1 /
OMNI_BENCH_QUANT (int8|fp8 weight-only on the flagship; int8 halves the
streamed transfer bytes) / OMNI_BENCH_SKIP_QUANT_VARIANT=1 /
OMNI_BENCH_KV_REUSE=1 (kvcache scenario: shared system prompt +
multi-turn sessions with idle gaps on an undersized page pool — reports
prefix hit-rate, recompute-tokens-avoided, offload bytes moved per
tier, and greedy bit-equality vs a never-offloaded oracle; see
docs/kv_cache.md.  OMNI_BENCH_KV_SESSIONS / OMNI_BENCH_KV_TURNS /
OMNI_BENCH_KV_QUANT=int8 tune it) /
OMNI_BENCH_SERVING=1 (STANDALONE serving-curve scenario, CPU-runnable:
open-loop offered-load sweep through vllm_omni_tpu/loadgen against a
live OpenAI server — per-rate attained throughput, goodput, SLO
attainment, TTFT/TPOT/E2E percentiles, shed counts, plus a mid-flight
/metrics scrape; OMNI_BENCH_SERVING_RATES / _SLO_TTFT_MS / _SLO_TPOT_MS
/ _DURATION_S / _QUEUE_DEPTH / _TENANTS tune it; docs/load_testing.md).
"""

from __future__ import annotations

import json
import os
import sys
import time

_T0 = time.time()


def _progress(msg: str) -> None:
    # stderr: visible in the driver's tail without polluting the single
    # stdout JSON line
    print(f"[bench {time.time() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def _budget_s() -> float:
    return float(os.environ.get("OMNI_BENCH_BUDGET_S", 3000))


def dit_flops_per_image(cfg, height: int, width: int, steps: int,
                        txt_len: int, cfg_scale_doubling: bool) -> float:
    """Analytic bf16 FLOPs for the denoise loop of one image (DiT only —
    text encode + VAE are excluded, making the MFU figure conservative).

    Per block per token: attention projections (4 * d^2 matmuls), joint
    attention (2 * S * d per query row), MLP (2 * d * mlp each way);
    2 FLOPs per MAC."""
    d = cfg.dit.inner_dim
    mlp = int(d * cfg.dit.mlp_ratio)
    lat_tokens = (height // (cfg.vae.spatial_ratio * cfg.dit.patch_size)) \
        * (width // (cfg.vae.spatial_ratio * cfg.dit.patch_size))
    s = lat_tokens + txt_len  # joint sequence
    per_token = (
        4 * d * d      # q/k/v/out projections (per stream, amortized)
        + 2 * s * d    # attention scores + values
        + 2 * d * mlp * 2  # gated/2-layer MLP up + down
    )
    per_block = 2 * s * per_token  # 2 FLOPs/MAC over the joint sequence
    per_step = cfg.dit.num_layers * per_block
    if cfg_scale_doubling:
        per_step *= 2  # CFG runs positive + negative branches
    return float(per_step * steps)


def chip_peak_tflops() -> float:
    """Peak bf16 TFLOP/s of the attached chip (platform layer; env
    override for unlisted generations)."""
    env = os.environ.get("OMNI_BENCH_PEAK_TFLOPS")
    if env:
        return float(env)
    from vllm_omni_tpu.platforms import current_platform

    peak = current_platform().peak_tflops_bf16()
    if peak <= 0:
        raise RuntimeError(
            "no peak FLOP/s known for this platform "
            f"({current_platform().name}); MFU needs an identified chip")
    return peak


def _host_to_hbm_gbps() -> float:
    """Measure host->HBM transfer throughput.  The streamed real-geometry
    preset moves ~30 GB per denoise step, so its feasibility is decided
    by this number, not by FLOPs.  In-process: a chip belongs to one
    process, so a probing child would have to exit before the parent's
    first JAX call."""
    import jax
    import numpy as np

    x = np.ones((64, 1024, 1024), np.float32)
    b = jax.device_put(np.ones(4, np.float32))
    b.block_until_ready()
    t0 = time.perf_counter()
    b = jax.device_put(x)
    b.block_until_ready()
    return 0.25 / (time.perf_counter() - t0)


_PROBE_GBPS = None  # measured host->HBM GB/s, reported in the JSON


def _pick_size() -> tuple:
    """Choose the flagship (preset, quantization, offload): the REAL
    streamed 60-layer geometry when the host->HBM path can sustain it
    inside the bench budget — bf16 first, int8 weight-only streaming
    (half the bytes) when bf16 can't — else the real geometry packed to
    int4 and RESIDENT (10.3 GB of the 41 GB bf16 DiT fits one 16 GB
    chip; quantization disclosed, DiT depth/width fully real).  The
    reduced-layer bf16 ``resident`` preset remains the runtime fallback
    if the int4 build fails."""
    global _PROBE_GBPS
    env = os.environ.get("OMNI_BENCH_SIZE")
    quant_env = os.environ.get("OMNI_BENCH_QUANT", "")
    if env:  # explicit size always wins
        if env == "real_q":
            # real_q only exists as the quantized-resident config (bf16
            # at this depth is 41 GB — a guaranteed OOM)
            return "real_q", quant_env or "int4", ""
        return env, quant_env, "layerwise" if env == "real" else ""
    if quant_env == "int4":  # int4 means resident — no probe needed
        return "real_q", "int4", ""
    gbps = _host_to_hbm_gbps()
    _PROBE_GBPS = round(gbps, 3)
    _progress(f"host->HBM throughput: {gbps:.2f} GB/s")
    # ~30 GB streamed per step after pinning (bf16; int8/fp8 weight-only
    # halves it); 50 steps must fit the budget with room for warmup +
    # the AR bench
    steps = int(os.environ.get("OMNI_BENCH_STEPS", 50))
    est = steps * 30.0 / max(gbps, 1e-6)
    est_q = est / 2
    feasible = _budget_s() * 0.6
    if quant_env:  # explicit streamed mode: honor it, bytes halved
        if est_q < feasible:
            return "real", quant_env, "layerwise"
    elif est < feasible:
        return "real", "", "layerwise"
    elif est_q < feasible:
        _progress(
            f"bf16 streaming infeasible (~{est:.0f}s of transfers for "
            f"{steps} steps vs {_budget_s():.0f}s budget) — real "
            "geometry with int8 streamed weights instead")
        return "real", "int8", "layerwise"
    _progress(
        f"streamed real preset infeasible (~{est:.0f}s bf16 / "
        f"~{est_q:.0f}s quantized of transfers for {steps} steps vs "
        f"{_budget_s():.0f}s budget at {gbps:.2f} GB/s) — real "
        "geometry int4-resident instead")
    return "real_q", "int4", ""


def _require_tpu() -> None:
    """A measurement path with no chip fails; only the serving-curve
    scenario (counts, not speeds) runs on the CPU."""
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py: no TPU found (jax.default_backend() = "
            f"{jax.default_backend()!r}); the flagship and AR benches "
            "measure the chip")


def _release_device_memory() -> None:
    """Free HBM still held by dead engines before building the next one.

    Engine/pipeline/closure graphs are cyclic, so dropping the last name
    does NOT refcount the param trees to zero — the r05 first on-chip run
    OOMed the AR bench and the step-cache variant this exact way.  A
    forced gc pass plus clearing jit caches (whose entries can pin traced
    constants) releases the buffers; the recompile a cleared cache costs
    (~1 min) is noise next to a lost phase."""
    import gc

    gc.collect()
    import jax

    jax.clear_caches()
    gc.collect()


# ---------------------------------------------------------- serving curve
def _serving_tiny_factory():
    """loadgen serving-curve stage model: a tiny dense LM so a CPU
    sweep finishes in seconds — the scenario measures the SERVING stack
    (admission control, queueing, SLO/goodput accounting), not model
    FLOPs; the AR bench owns those."""
    import jax
    import jax.numpy as jnp

    from vllm_omni_tpu.models.common import transformer as tfm

    cfg = tfm.TransformerConfig.tiny(vocab_size=2048)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return params, cfg, None


def bench_serving() -> dict:
    """OMNI_BENCH_SERVING=1: the open-loop serving curve (ROADMAP item
    5 / docs/load_testing.md).  Sweeps >= 3 offered-load rates against
    a live OpenAI server driven by the loadgen harness and emits one
    ``serving_curve`` point per rate — attained throughput, goodput
    (SLO-met completions only), TTFT/TPOT/E2E percentiles, shed and
    expired counts — plus a MID-FLIGHT /metrics scrape proving the
    SLO/goodput/shed/queue-depth series are live while traffic runs.

    Knobs: OMNI_BENCH_SERVING_RATES (req/s, comma list),
    OMNI_BENCH_SERVING_SLO_TTFT_MS / _SLO_TPOT_MS,
    OMNI_BENCH_SERVING_DURATION_S (per rate),
    OMNI_BENCH_SERVING_QUEUE_DEPTH (admission cap),
    OMNI_BENCH_SERVING_TENANTS (comma list, round-robined)."""
    import threading
    import urllib.request

    from vllm_omni_tpu.config.stage import StageConfig
    from vllm_omni_tpu.entrypoints.openai.api_server import build_server
    from vllm_omni_tpu.loadgen import (
        SLOTargets,
        build_workload,
        poisson_arrivals,
        run_http,
        summarize,
    )
    from vllm_omni_tpu.loadgen.workload import Scenario
    from vllm_omni_tpu.metrics.prometheus import validate_exposition

    rates = [float(x) for x in os.environ.get(
        "OMNI_BENCH_SERVING_RATES", "2,4,8").split(",") if x.strip()]
    slo = SLOTargets(
        ttft_ms=float(os.environ.get(
            "OMNI_BENCH_SERVING_SLO_TTFT_MS", "2000")),
        tpot_ms=float(os.environ.get(
            "OMNI_BENCH_SERVING_SLO_TPOT_MS", "500")))
    duration = float(os.environ.get("OMNI_BENCH_SERVING_DURATION_S", "5"))
    queue_depth = int(os.environ.get(
        "OMNI_BENCH_SERVING_QUEUE_DEPTH", "32"))
    tenants = [t for t in os.environ.get(
        "OMNI_BENCH_SERVING_TENANTS", "tenant_a,tenant_b").split(",")
        if t.strip()]
    # CPU-scale catalog: the default long-context lengths would make a
    # tiny-model CPU sweep prefill-bound for minutes; keep the same mix
    # SHAPE at bench-scale lengths
    # stream=True on most legs: SSE is how the client MEASURES TTFT —
    # a non-streaming request can't judge the TTFT SLO leg (unmeasured
    # legs pass), so the curve would under-constrain attainment
    catalog = [
        Scenario("chat", weight=0.5, prompt_len=(16, 48),
                 output_len=(8, 16), stream=True),
        Scenario("long_context", weight=0.2, prompt_len=(96, 160),
                 output_len=(8, 12)),
        Scenario("multi_turn", weight=0.2, prompt_len=(8, 32),
                 output_len=(8, 12), shared_prefix_len=48,
                 stream=True),
        Scenario("streaming", weight=0.1, prompt_len=(16, 32),
                 output_len=(8, 16), stream=True),
    ]
    stage = StageConfig(
        stage_id=0, stage_type="llm",
        engine_args={
            "model_factory": _serving_tiny_factory,
            "num_pages": 1024, "page_size": 16, "max_model_len": 2048,
            "max_num_seqs": 8, "max_num_batched_tokens": 1024,
            "enable_chunked_prefill": True,
            # precompile every decode batch bucket before the server
            # reports ready; prefill buckets are warmed by the catalog
            # warmup below — a mid-sweep XLA compile would bill its
            # stall to the lowest rate's latencies
            "warmup": True,
            "max_queue_depth": queue_depth,
            "slo_ttft_ms": slo.ttft_ms, "slo_tpot_ms": slo.tpot_ms,
        },
        engine_input_source=[-1], final_output=True,
        final_output_type="text",
        default_sampling_params={"temperature": 0.0},
    )
    _progress(f"serving: starting OpenAI server (queue_depth="
              f"{queue_depth}, SLO ttft {slo.ttft_ms}ms / tpot "
              f"{slo.tpot_ms}ms)")
    server, state = build_server(model="loadgen-bench",
                                 stage_configs=[stage],
                                 host="127.0.0.1", port=0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{port}"

    probe: dict = {"scraped_mid_flight": False}

    def scrape_mid_flight():
        # fire mid-sweep: the acceptance contract is that the series
        # are scrape-able WHILE traffic runs, not post-hoc
        time.sleep(duration * 0.5)
        try:
            with urllib.request.urlopen(f"{base}/metrics",
                                        timeout=30) as r:
                text = r.read().decode()
            required = ("slo_attainment_ratio", "goodput_tokens_total",
                        "request_queue_depth", "queue_wait_ms",
                        "phase_saturation_ratio")
            probe.update(
                scraped_mid_flight=True,
                violations=validate_exposition(text),
                series_present={
                    name: f"vllm_omni_tpu_{name}" in text
                    for name in required},
                tenant_label_present='tenant="' in text,
            )
        except Exception as e:  # re-raised by the sweep after join()
            probe["error"] = e

    curve = []
    try:
        # warmup: compile the executables before the first rate point —
        # an XLA compile inside the sweep bills tens of seconds of
        # one-time cost to the lowest rate's latencies (observed: TTFT
        # p50 10.8s at rate 2 with a 2-request warmup).  Drawing the
        # warmup from the SAME catalog walks the same prompt-length
        # buckets the sweep will hit
        _progress("serving: warmup requests (compiles)")
        top = max(rates)
        n_warm = max(int(round(top * duration)), 10)
        warm = build_workload(
            [0.0] * n_warm, catalog, seed=99,
            vocab_size=2000, tenants=tenants, id_prefix="warm")
        # closed-loop ON PURPOSE (warmup is not measured): groups
        # small enough to stay under both the seat count and the
        # admission cap fire together and fully drain before the next
        # group — open-loop warmup at the top rate against a cold,
        # compiling server would queue past max_queue_depth and SHED
        # the very requests meant to compile the prompt-length
        # buckets, leaving those compiles to stall a measured rate
        # point (and polluting the cumulative shed ledger)
        group = max(1, min(8, queue_depth if queue_depth > 0 else 8))
        for lo in range(0, len(warm), group):
            run_http(base, warm[lo:lo + group])
        for i, rate in enumerate(rates):
            n = max(int(round(rate * duration)), 3)
            arrivals = poisson_arrivals(rate, n, seed=1000 + i)
            wl = build_workload(arrivals, catalog, seed=2000 + i,
                                vocab_size=2000, tenants=tenants,
                                id_prefix=f"r{i}")
            _progress(f"serving: rate {rate} req/s ({n} requests)")
            scraper = None
            if i == len(rates) - 1:  # scrape during the hottest point
                scraper = threading.Thread(target=scrape_mid_flight)
                scraper.start()
            records = run_http(base, wl)
            if scraper is not None:
                scraper.join()
                if "error" in probe:
                    raise RuntimeError(
                        "mid-flight /metrics scrape failed"
                    ) from probe["error"]
            curve.append(summarize(records, rate, slo))
            _progress(
                f"serving: rate {rate} -> goodput "
                f"{curve[-1]['goodput_tok_per_s']} tok/s, attainment "
                f"{curve[-1]['slo_attainment']}, shed "
                f"{curve[-1]['shed']}")
    finally:
        server.shutdown()
        state.shutdown()
    peak = max((p["goodput_tok_per_s"] for p in curve), default=None)
    return {
        "metric": "serving_peak_goodput_tok_per_s",
        "value": peak,
        "unit": "tok/s",
        "vs_baseline": None,
        "serving_curve": curve,
        "slo": slo.as_dict(),
        "offered_rates_rps": rates,
        "tenants": tenants,
        "max_queue_depth": queue_depth,
        "metrics_probe": probe,
        "arch": {
            "note": "tiny dense LM on purpose — the scenario benches "
                    "the serving stack (admission, queueing, SLO "
                    "accounting), not model FLOPs",
            "weights": "random-init",
        },
    }


# ------------------------------------------------------------- diffusion
def _build_engine(size: str, scheduler: str, use_cache: bool,
                  quant: str = "", offload: str = "",
                  scm_mask=None):
    from vllm_omni_tpu.config.diffusion import OmniDiffusionConfig
    from vllm_omni_tpu.diffusion.engine import DiffusionEngine

    extra = {"size": size}
    if scheduler:
        extra["scheduler"] = scheduler
    cfg = OmniDiffusionConfig(
        model="qwen-image-bench", model_arch="QwenImagePipeline",
        dtype="bfloat16", extra=extra,
        cache_backend="teacache" if use_cache else "",
        cache_config=({"scm_steps_mask": list(scm_mask)}
                      if use_cache and scm_mask is not None else {}),
        offload=offload,
        quantization=quant,
    )
    return DiffusionEngine(cfg, warmup=False)


def bench_diffusion(size: str, scheduler: str, use_cache: bool,
                    height: int, width: int, steps: int,
                    iters: int, quant: str = "",
                    offload: str = "", scm_mask=None) -> dict:
    from vllm_omni_tpu.diffusion.request import (
        OmniDiffusionRequest,
        OmniDiffusionSamplingParams,
    )

    fallback = ""
    engine = None
    _release_device_memory()  # a prior phase's engine may still pin HBM

    def one(n_steps):
        sp = OmniDiffusionSamplingParams(
            height=height, width=width, num_inference_steps=n_steps,
            guidance_scale=4.0, seed=0,
        )
        req = OmniDiffusionRequest(
            prompt=["a photo of a cat"], sampling_params=sp)
        return engine.step(req)

    # The WHOLE phase (build + warmup compiles + timed run) retries with
    # preset demotion: first hardware contact breaks after the build as
    # often as during it.  The demotion is disclosed in arch.weights.
    def measure_step():
        # A second 1-step pass runs with all compiles warm; the
        # pipeline's own denoise timing separates the per-step streamed
        # cost from the per-run text-encode/VAE overhead.
        tw = time.perf_counter()
        one(1)
        pass2_s = time.perf_counter() - tw
        s = getattr(engine.pipeline, "last_stream_denoise_s", pass2_s)
        return s, max(pass2_s - s, 0.0)

    def rebuild(new_size, new_quant, new_offload):
        # release the old pipeline FIRST: its pinned HBM blocks plus
        # the replacement's weights would exceed one chip
        nonlocal engine
        del engine
        engine = None
        _release_device_memory()
        engine = _build_engine(new_size, scheduler, use_cache,
                               new_quant, new_offload,
                               scm_mask=scm_mask)
        one(1)

    while True:
        try:
            engine = _build_engine(size, scheduler, use_cache, quant,
                                   offload, scm_mask=scm_mask)
            # compile warmup: 1 step warms every executable.  Small
            # presets then run one untimed full-length pass (measured: a
            # ~4.5 s one-time autotune cost would pollute a 2-3 iter
            # average by 3x); the big presets skip it — for streaming the
            # per-piece executables are already warm and the run is
            # transfer-bound, for real_q the 1-step warmup warmed the
            # same dynamic-step-bound executable and ~4.5 s is <3% of a
            # 60-layer image.
            _progress(f"diffusion[{size}] warmup (1 step + compiles)")
            tw = time.perf_counter()
            one(1)
            warm_s = time.perf_counter() - tw
            _progress(f"diffusion[{size}] warmup done in {warm_s:.1f}s")
            if size == "real" and offload == "layerwise":
                # Feasibility check on MEASURED streamed timings (one
                # 256 MB probe transfer predicts a 30 GB/step walk
                # poorly).
                step_s, overhead_s = measure_step()
                est_total = overhead_s + steps * step_s
                remaining = _budget_s() - (time.time() - _T0)
                _progress(
                    f"streamed step {step_s:.1f}s + {overhead_s:.1f}s"
                    f"/run overhead => ~{est_total:.0f}s for {steps} "
                    f"steps ({remaining:.0f}s left in budget)")
                if est_total > remaining and not quant:
                    # int8 weight-only halves the streamed bytes the
                    # walk is bound by — try it before abandoning
                    # streaming
                    _progress("bf16 streaming measured-infeasible — "
                              "retrying with int8 streamed weights")
                    fallback = (f"bf16 streaming measured-infeasible "
                                f"({step_s:.0f}s/streamed-step); ")
                    quant = "int8"
                    rebuild(size, quant, offload)
                    step_s, overhead_s = measure_step()
                    est_total = overhead_s + steps * step_s
                    remaining = _budget_s() - (time.time() - _T0)
                    _progress(f"int8 streamed step {step_s:.1f}s => "
                              f"~{est_total:.0f}s for {steps} steps "
                              f"({remaining:.0f}s left)")
                if est_total > remaining:
                    _progress("streamed real preset measured-"
                              "infeasible — switching to the "
                              "int4-resident real geometry")
                    fallback += (f"streaming measured-infeasible "
                                 f"({step_s:.0f}s/streamed-step); ")
                    size, quant, offload = "real_q", "int4", ""
                    rebuild(size, quant, offload)
            elif size not in ("real_q",):
                one(steps)
            _progress(f"diffusion[{size}] timed run: {iters}x {steps} "
                      f"steps @{height}px")
            t0 = time.perf_counter()
            for _ in range(iters):
                one(steps)
            dt = (time.perf_counter() - t0) / iters
            break
        except Exception as e:  # e.g. OOM / compile-service failure
            engine = None
            _release_device_memory()  # drop the failed build's partials
            if size in ("real", "real_q"):
                _progress(f"{size}/{quant or 'bf16'} preset failed "
                          f"({type(e).__name__}: {e}); falling back to "
                          "HBM-resident reduced-layer preset")
                fallback += (f"{size}/{quant or 'bf16'} failed "
                             f"({type(e).__name__}: {e}); ")
                size, quant, offload = "resident", "", ""
            elif size == "resident":
                _progress(f"resident preset failed ({type(e).__name__}: "
                          f"{e}); falling back to 16-layer bench preset")
                fallback += f"resident failed ({type(e).__name__}: {e}); "
                size, height, width, steps, iters = \
                    "bench", 512, 512, 20, 3
                quant = offload = ""
            else:
                raise
    _progress(f"diffusion[{size}] done: {dt:.1f}s/image")

    pcfg = engine.pipeline.cfg
    # step-cache skipping means fewer DiT evaluations actually ran: count
    # executed steps or the MFU would overstate by the skip ratio
    skipped = int(getattr(engine.pipeline, "last_skipped_steps", 0))
    flops = dit_flops_per_image(
        pcfg, height, width, max(steps - skipped, 1),
        txt_len=pcfg.max_text_len, cfg_scale_doubling=True,
    )
    peak = chip_peak_tflops()
    mfu = flops / dt / (peak * 1e12)
    streamer = engine.pipeline.__dict__.get("_dit_streamer")
    return {
        "metric": f"qwen_image_imgs_per_sec_chip_{height}px_{steps}step",
        "value": round(1.0 / dt, 5),
        "unit": "imgs/s",
        "seconds_per_image": round(dt, 2),
        "mfu": round(mfu, 4),
        "dit_tflops_per_image": round(flops / 1e12, 2),
        "peak_tflops_assumed": peak,
        "arch": {
            "dit_layers": pcfg.dit.num_layers,
            "dit_heads": pcfg.dit.num_heads,
            "dit_inner_dim": pcfg.dit.inner_dim,
            "size_preset": size,
            "scheduler": getattr(pcfg, "scheduler", "euler"),
            "step_cache": use_cache,
            "skipped_steps": skipped,
            "offload": getattr(engine.pipeline, "offload", ""),
            "quantization": quant,
            "host_to_hbm_gbps": _PROBE_GBPS,
            "hbm_pinned_blocks": getattr(streamer, "pinned", None),
            "weights": fallback + "random-init (real-weight loader "
                       "exists, no checkpoint in the image)",
        },
    }


# -------------------------------------------------------------------- AR
def bench_ar() -> dict:
    """Qwen3-Omni-style thinker serving on the real engine path.

    The real 30B-A3B thinker (48 layers / 128 experts) is 60 GB bf16 —
    it does not fit one 16 GB chip resident, and token-by-token decode
    cannot hide weight streaming, so the honest single-chip config is a
    REDUCED-DEPTH thinker with the real per-token structure: hidden
    2048, head_dim 128, GQA 16q/4kv, top-8-of-32 routed experts
    (reference geometry: Qwen3-Omni-MoE config; arch disclosed in the
    result).  Paged attention + continuous batching + APC are the
    production path (engine/llm_engine.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _release_device_memory()  # the flagship engine's HBM must be gone
    from vllm_omni_tpu.engine import EngineConfig, LLMEngine
    from vllm_omni_tpu.models.common import transformer as tfm
    from vllm_omni_tpu.sampling_params import SamplingParams

    cfg = tfm.TransformerConfig(
        vocab_size=151936,
        hidden_size=2048,
        num_layers=24,
        num_heads=16,
        num_kv_heads=4,
        head_dim=128,
        intermediate_size=6144,
        moe=True,
        num_experts=32,
        num_experts_per_tok=8,
        moe_intermediate_size=768,
        qk_norm=True,
    )
    _progress("ar: init bench-scale MoE thinker (~8.8 GB bf16)")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    # The 8192-token prefill budget admits all 16 default requests in ONE
    # prefill call (4 calls at the old 2048), so TTFT measures prefill,
    # not queueing.  64 pages/request = full prompt+gen headroom for
    # every seat, so the whole fleet decodes concurrently.
    n_reqs = int(os.environ.get("OMNI_BENCH_AR_REQS", "16"))
    mbt = int(os.environ.get("OMNI_BENCH_AR_BATCHED", "8192"))
    # OMNI_BENCH_AR_ASYNC=1: the async pipelined step — per-step host
    # work overlaps device compute via device-resident sampled tokens
    # (docs/async_engine.md); the multi-step scan window it replaced is
    # retired (PR 11).  The step-phase breakdown below quantifies it.
    use_async = os.environ.get("OMNI_BENCH_AR_ASYNC", "") == "1"
    # OMNI_BENCH_AR_UNIFIED=1: the SCHEDULER packing policy (decodes
    # claim the budget first, chunked prefill as the mechanism).  The
    # execution mechanism is always unified since PR 11 — every
    # non-pure-decode step is ONE token-packed ragged dispatch
    # (docs/ragged_batching.md); step_phase padding_efficiency
    # quantifies the win over the retired (batch, seq) bucket grid.
    use_unified = os.environ.get("OMNI_BENCH_AR_UNIFIED", "") == "1"
    engine = LLMEngine(params, cfg, EngineConfig(
        num_pages=64 * n_reqs, page_size=16, max_model_len=2048,
        max_num_seqs=n_reqs, max_num_batched_tokens=mbt,
        dtype=jnp.bfloat16,
        async_scheduling=use_async,
        unified_batching=use_unified,
    ))

    rng = np.random.default_rng(0)
    prompt_len, max_tokens = 512, 128
    prompts = [rng.integers(1, 150000, prompt_len).tolist()
               for _ in range(n_reqs)]
    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens,
                        ignore_eos=True)

    _progress("ar: compile warmup (prefill + decode executables)")
    # DIFFERENT random prompts at the SAME shapes as the timed run: the
    # prefill bucket (512) and every decode executable compile here,
    # while the timed prompts stay cold in the prefix cache (identical
    # warmup prompts would hand the timed run cached prefills and fake
    # its TTFT).  max_tokens must keep the FIRST prefill wave decoding
    # until the LAST wave joins or the full-batch decode executable
    # never compiles in warmup — a measured 23 s compile stall inside
    # the r05 timed run.  (waves + 2) one-token steps cover the prefill
    # drain at any request count / token budget.
    waves = -(-n_reqs * prompt_len // mbt)
    warm = [rng.integers(1, 150000, prompt_len).tolist()
            for _ in range(n_reqs)]
    engine.generate(warm, SamplingParams(
        temperature=0.0, max_tokens=waves + 2, ignore_eos=True))

    _progress(f"ar: timed run ({n_reqs} reqs, prompt {prompt_len}, "
              f"gen {max_tokens})")
    # omnilint: disable=OL4 - engine.step() syncs internally (sampled
    # tokens are device_get'd every step), so wall-clock here measures
    # real end-to-end serving latency, not enqueue
    t0 = time.perf_counter()
    first_token_ms: dict = {}
    for p in prompts:
        engine.add_request(list(p), sp)
    done = 0
    total_tokens = 0
    # tokens already emitted when the LAST request got its first token —
    # from here on the whole fleet is pure decode (the MBU window)
    tokens_at_full_decode = None
    while engine.has_unfinished_requests:
        outs = engine.step()
        now_ms = (time.perf_counter() - t0) * 1e3
        for r in engine.scheduler.running:
            if (r.request_id not in first_token_ms
                    and r.num_tokens > len(r.prompt_token_ids)):
                first_token_ms[r.request_id] = now_ms
        for o in outs:
            done += 1
            first_token_ms.setdefault(o.request_id, now_ms)
            for c in o.outputs:
                total_tokens += len(c.token_ids)
        if (tokens_at_full_decode is None
                and len(first_token_ms) >= n_reqs):
            tokens_at_full_decode = total_tokens + sum(
                len(r.output_token_ids)
                for r in engine.scheduler.running)
    dur = time.perf_counter() - t0
    _progress(f"ar: done ({done} finished, {total_tokens} tokens, "
              f"{dur:.1f}s)")

    from vllm_omni_tpu.metrics.stats import nearest_rank_pct
    from vllm_omni_tpu.platforms import current_platform

    # Model-bandwidth utilization: decode is weight-read-bound — every
    # decode iteration streams the full resident weights from HBM once
    # (the batch shares the read).  Numerator AND denominator cover the
    # same window — the pure-decode phase after the last request's
    # first token (tokens emitted before it, during mixed
    # prefill+decode waves, are excluded): the old total-duration
    # denominator deflated the ratio by the prefill + host-RTT
    # fraction, while a decode-phase denominator under the full
    # max_tokens numerator would overcount whenever prefill runs in
    # more than one wave (ADVICE round 5).
    weights_gb = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(params)) / 1e9
    peak_bw = current_platform().peak_hbm_gbps()
    ttfts = list(first_token_ms.values())
    decode_dur = max(dur - (max(ttfts) / 1e3 if ttfts else 0.0), 1e-9)
    decode_tokens = total_tokens - (tokens_at_full_decode or 0)
    # per-request decode iterations in the window (the batch shares
    # each weight read)
    decode_iters = decode_tokens / max(n_reqs, 1)
    # 0 = platform doesn't publish a bandwidth (CPU runs): report null
    # rather than a confident-looking number against absent hardware
    mbu = ((weights_gb * decode_iters / decode_dur) / peak_bw if peak_bw
           else None)
    # step-phase breakdown: host-ms vs. device-ms per engine step and
    # how much host work overlapped in-flight device compute — the
    # async pipeline's win stays visible in the trajectory even when the
    # sync baseline is the mode that ran
    sm = engine.step_metrics
    host_snap, dev_snap = sm.host_ms.snapshot(), sm.device_ms.snapshot()
    step_phase = {
        "host_ms_p50": host_snap["p50"],
        "host_ms_p99": host_snap["p99"],
        "device_ms_p50": dev_snap["p50"],
        "device_ms_p99": dev_snap["p99"],
        "host_ms_total": round(sm.host_ms_total, 1),
        "overlapped_host_ms_total": round(sm.overlapped_host_ms_total, 1),
        "overlap_ratio": round(sm.overlap_ratio, 4),
        # useful tokens / padded device rows over the whole run — the
        # number the unified ragged path exists to raise
        "padding_efficiency": round(sm.padding_efficiency, 4),
        "useful_tokens_total": sm.useful_tokens_total,
        "padded_tokens_total": sm.padded_tokens_total,
    }
    return {
        "metric": "qwen3_omni_thinker_tok_per_sec_chip",
        "value": round(total_tokens / dur, 2),
        "unit": "tok/s",
        "p50_ttft_ms": round(nearest_rank_pct(ttfts, 0.50), 1),
        "p99_ttft_ms": round(nearest_rank_pct(ttfts, 0.99), 1),
        "model_bandwidth_utilization": (round(mbu, 4)
                                        if mbu is not None else None),
        "mbu_decode_phase_s": round(decode_dur, 2),
        "mbu_decode_tokens": decode_tokens,
        "mbu_note": "numerator and denominator both cover the "
                    "pure-decode phase (after the last request's first "
                    "token); prefill waves + host RTT excluded",
        "weights_gb": round(weights_gb, 2),
        "peak_hbm_gbps_assumed": peak_bw or None,
        "num_requests": n_reqs,
        "prompt_len": prompt_len,
        "gen_len": max_tokens,
        "duration_s": round(dur, 2),
        "step_phase": step_phase,
        "arch": {
            "layers": cfg.num_layers,
            "hidden": cfg.hidden_size,
            "heads": f"{cfg.num_heads}q/{cfg.num_kv_heads}kv",
            "experts": f"top{cfg.num_experts_per_tok}of"
                       f"{cfg.num_experts}",
            "moe_intermediate": cfg.moe_intermediate_size,
            "async_scheduling": use_async,
            "unified_batching": use_unified,
            "max_num_seqs": n_reqs,
            "max_num_batched_tokens": mbt,
            "note": "bench-scale thinker (real 30B-A3B is 60 GB bf16 — "
                    "exceeds one 16 GB chip; depth/expert count reduced "
                    "to fit resident, per-token structure real)",
            "weights": "random-init",
        },
    }


def bench_kv_reuse() -> dict:
    """kv_reuse scenario (OMNI_BENCH_KV_REUSE=1): fleet-scale KV
    economics on an UNDERSIZED page pool (docs/kv_cache.md).

    N chat sessions share one system prompt and run several turns with
    idle gaps between them (a finished turn's pages drop to the radix
    prefix index; the next turn re-adopts them).  The pool holds only a
    fraction of the live session set, so turns evict each other's
    cached prefixes into the host tier and re-admission restores them —
    the scenario measures prefix hit-rate, recompute-tokens-avoided,
    and bytes moved per tier, then replays the identical traffic on a
    never-offloaded oracle engine and checks the greedy streams are
    bit-identical.

    A deliberately small dense model: the scenario benches the CACHE
    machinery (hashing, radix walks, tier transfers), not model FLOPs —
    the AR serving bench owns those."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_omni_tpu.engine import EngineConfig, LLMEngine
    from vllm_omni_tpu.models.common import transformer as tfm
    from vllm_omni_tpu.sampling_params import SamplingParams

    n_sessions = int(os.environ.get("OMNI_BENCH_KV_SESSIONS", "8"))
    n_turns = int(os.environ.get("OMNI_BENCH_KV_TURNS", "3"))
    quant = os.environ.get("OMNI_BENCH_KV_QUANT", "none")
    sys_len, user_len, gen_len = 256, 64, 32
    page_size = 16
    # pool sized for ~3 concurrent session footprints: the remaining
    # sessions' cached prefixes MUST spill to the host tier
    session_pages = -(-(sys_len + n_turns * (user_len + gen_len))
                      // page_size)
    num_pages = max(3 * session_pages, 48)

    cfg = tfm.TransformerConfig(
        vocab_size=32768, hidden_size=1024, num_layers=4, num_heads=8,
        num_kv_heads=4, head_dim=128, intermediate_size=2816)
    _progress("kv_reuse: init small dense model")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)

    def build(offload: bool):
        return LLMEngine(params, cfg, EngineConfig(
            num_pages=num_pages if offload else 4096,
            page_size=page_size, max_model_len=4096,
            max_num_seqs=n_sessions, max_num_batched_tokens=4096,
            dtype=jnp.bfloat16,
            enable_prefix_caching=offload,
            kv_offload=offload,
            # BOTH engines: preemptions shrink the offload run's decode
            # batches across bucket shapes the oracle never sees, and
            # per-row decode numerics vary in the last bf16 bit per
            # bucket — on this random-init model's near-flat logits
            # that flips greedy argmaxes that have nothing to do with
            # KV correctness.  One fixed bucket makes the bit-equality
            # check test the offload machinery, not XLA fusion luck.
            deterministic_decode=True,
            # "always": the scenario must exercise the tiers whatever
            # the break-even math would say about its tiny turns
            kv_offload_policy="always",
            kv_offload_quant=quant if offload else "none",
        ))

    rng = np.random.default_rng(0)
    system = rng.integers(1, 30000, sys_len).tolist()
    users = [[rng.integers(1, 30000, user_len).tolist()
              for _ in range(n_turns)] for _ in range(n_sessions)]
    sp = SamplingParams(temperature=0.0, max_tokens=gen_len,
                        ignore_eos=True)

    def run(engine):
        """All sessions, turn by turn (the inter-turn boundary IS the
        idle gap: a finished turn's KV sits cache-resident or parked
        until the next turn re-adopts it).  Returns per-session streams
        + total prompt tokens submitted."""
        histories = [list(system) + list(users[s][0])
                     for s in range(n_sessions)]
        streams: list[list[int]] = [[] for _ in range(n_sessions)]
        prompt_tokens = 0
        for turn in range(n_turns):
            prompts = [list(h) for h in histories]
            prompt_tokens += sum(len(p) for p in prompts)
            # generate() returns outputs in submission order, which IS
            # session order (a lexicographic request-id sort would
            # cross-wire sessions past 10 requests: req-10 < req-8)
            outs = engine.generate(prompts, sp)
            for s, o in enumerate(outs):
                toks = list(o.outputs[0].token_ids)
                streams[s].extend(toks)
                histories[s].extend(toks)
                if turn + 1 < n_turns:
                    histories[s].extend(users[s][turn + 1])
        return streams, prompt_tokens

    _progress(f"kv_reuse: offload run ({n_sessions} sessions x "
              f"{n_turns} turns, pool {num_pages} pages)")
    eng = build(offload=True)
    # omnilint: disable=OL4 - engine.generate() is fully synchronous
    # (every sampled token is device_get'd before it returns), so the
    # wall clock measures end-to-end serving, not enqueue
    t0 = time.perf_counter()
    streams, prompt_tokens = run(eng)
    dur = time.perf_counter() - t0
    _progress("kv_reuse: oracle run (no offload, no prefix cache)")
    oracle_streams, _ = run(build(offload=False))

    kv = eng.scheduler.kv
    tiers = eng.kv_tiers
    restore_snap = eng.step_metrics.kv_restore_s.snapshot()
    bit_identical = streams == oracle_streams
    return {
        "metric": "kv_reuse_prefix_hit_rate",
        "value": round(kv.prefix_hit_tokens / max(prompt_tokens, 1), 4),
        "unit": "hit_tokens/prompt_tokens",
        "prefix_hit_tokens": kv.prefix_hit_tokens,
        "prompt_tokens_submitted": prompt_tokens,
        "recompute_tokens_avoided": kv.restored_tokens,
        "parked_tokens": kv.parked_tokens,
        "offload_evictions": kv.offload_evictions,
        "preemptions": eng.scheduler.num_preemptions,
        "offload_bytes_moved": {
            f"{tier}/{d}": n
            for (tier, d), n in sorted(tiers.bytes_moved.items())},
        "restore_s_p50": restore_snap["p50"],
        "restore_s_p99": restore_snap["p99"],
        "greedy_bit_identical_to_oracle": bit_identical,
        "duration_s": round(dur, 2),
        "quant_mode": quant,
        "pool": {"num_pages": num_pages, "page_size": page_size,
                 "session_pages": session_pages,
                 "sessions": n_sessions, "turns": n_turns},
        "arch": {"layers": cfg.num_layers, "hidden": cfg.hidden_size,
                 "heads": f"{cfg.num_heads}q/{cfg.num_kv_heads}kv",
                 "weights": "random-init",
                 "note": "small dense model on purpose — the scenario "
                         "benches cache machinery, not model FLOPs"},
    }


def main():
    os.environ.setdefault("OMNI_TPU_LOG_LEVEL", "WARNING")

    if os.environ.get("OMNI_BENCH_SERVING", "") == "1":
        # serving-curve scenario: a standalone mode (CPU-runnable; no
        # chip probe — the scenario's tiny model runs wherever jax
        # does) that sweeps offered-load rates through the loadgen
        # harness and emits the serving_curve block
        print(json.dumps(bench_serving()))
        return

    _require_tpu()
    size, quant, offload = _pick_size()
    big = size in ("real", "real_q", "resident")
    default_px = "1024" if big else "512"
    default_steps = "50" if big else "20"
    default_iters = "1" if big else "3"
    height = width = int(os.environ.get("OMNI_BENCH_PX", default_px))
    steps = int(os.environ.get("OMNI_BENCH_STEPS", default_steps))
    iters = int(os.environ.get("OMNI_BENCH_ITERS", default_iters))
    scheduler = os.environ.get("OMNI_BENCH_SCHEDULER", "")
    use_cache = os.environ.get("OMNI_BENCH_CACHE", "") == "1"

    flagship = bench_diffusion(size, scheduler, use_cache, height, width,
                               steps, iters, quant, offload)
    out = dict(flagship)
    out["vs_baseline"] = None

    # quantized-streaming companion: the bf16-vs-int8 streamed pair is
    # the headline transfer-bound comparison (int8 halves the ~30 GB/step
    # weight traffic) — run whichever streamed variant the flagship
    # didn't, budget permitting
    ran_size = flagship["arch"]["size_preset"]
    ran_quant = flagship["arch"]["quantization"]
    if ran_size == "real_q":
        cause = (f"host->HBM measured {_PROBE_GBPS} GB/s — too slow "
                 "for any streamed variant" if _PROBE_GBPS is not None
                 else "see arch.weights for the demotion cause")
        out["quantized_stream_variant"] = {
            "skipped": "flagship ran the real geometry int4-RESIDENT "
                       f"({cause})"}
    elif ran_size != "real":
        out["quantized_stream_variant"] = {
            "skipped": f"flagship ran the {ran_size} preset (the "
                       "bf16-vs-int8 pair is a streamed-real comparison)"}
    elif ran_quant:
        out["quantized_stream_variant"] = {
            "skipped": f"flagship itself ran {ran_quant}-quantized "
                       "streaming (bf16 streaming was infeasible or "
                       "OMNI_BENCH_QUANT forced the mode)"}
    else:  # flagship ran real bf16 streaming — run the int8 companion
        q_remaining = _budget_s() - (time.time() - _T0)
        est_q = flagship.get("seconds_per_image", 1e9) * 0.55 + 180
        if os.environ.get("OMNI_BENCH_SKIP_QUANT_VARIANT", "") == "1":
            out["quantized_stream_variant"] = {
                "skipped": "OMNI_BENCH_SKIP_QUANT_VARIANT=1"}
        elif est_q + 480 > q_remaining:
            # keep ~8 min back for the AR bench — it has never had a
            # number and must not be starved by a variant
            out["quantized_stream_variant"] = {
                "skipped": f"budget ({q_remaining:.0f}s left, "
                           f"~{est_q:.0f}s needed + AR reserve)"}
        else:
            qvar = bench_diffusion(size, scheduler, use_cache,
                                   height, width, steps, iters,
                                   "int8", "layerwise")
            # report the arch the variant ACTUALLY ran (its internal
            # feasibility fallback may have stripped quant or
            # changed preset) — never stamp the requested mode
            out["quantized_stream_variant"] = {
                k: qvar[k] for k in ("metric", "value", "unit",
                                     "seconds_per_image", "mfu")}
            out["quantized_stream_variant"].update(
                quantization=qvar["arch"]["quantization"],
                size_preset=qvar["arch"]["size_preset"],
                weights=qvar["arch"]["weights"])

    ar_remaining = _budget_s() - (time.time() - _T0)
    if os.environ.get("OMNI_BENCH_SKIP_AR", "") == "1":
        out["secondary_metrics"] = {
            "ar_serving": {"skipped": "OMNI_BENCH_SKIP_AR=1"}}
    elif ar_remaining < 420:
        # ~7 min covers engine init + compiles + the timed run; starting
        # an unfinishable AR bench would lose the flagship line entirely
        # if the driver kills the process at its deadline
        out["secondary_metrics"] = {"ar_serving": {
            "skipped": f"budget ({ar_remaining:.0f}s left, ~420s needed)"}}
    else:
        out["secondary_metrics"] = {"ar_serving": bench_ar()}

    if os.environ.get("OMNI_BENCH_KV_REUSE", "") == "1":
        sec = out.setdefault("secondary_metrics", {})
        kv_remaining = _budget_s() - (time.time() - _T0)
        if kv_remaining < 300:
            sec["kv_reuse"] = {"skipped": f"budget ({kv_remaining:.0f}s "
                                          "left, ~300s needed)"}
        else:
            sec["kv_reuse"] = bench_kv_reuse()

    # budget-aware step-cache variant (a second full run)
    elapsed = time.time() - _T0
    est_variant = flagship.get("seconds_per_image", 1e9) * 0.8 + 120
    skip_reason = None
    if os.environ.get("OMNI_BENCH_SKIP_CACHE_VARIANT", "") == "1":
        skip_reason = "OMNI_BENCH_SKIP_CACHE_VARIANT=1"
    elif use_cache:
        skip_reason = "flagship already ran with the step cache"
    elif flagship["arch"]["size_preset"] != size:
        skip_reason = (f"flagship fell back to "
                       f"{flagship['arch']['size_preset']} preset")
    elif elapsed + est_variant >= _budget_s():
        skip_reason = (f"budget ({elapsed:.0f}s elapsed, "
                       f"~{est_variant:.0f}s needed, "
                       f"{_budget_s():.0f}s budget)")
    if skip_reason is None:
        # rerun what the flagship ACTUALLY ran (it may have demoted
        # quant mid-flight, e.g. bf16 streaming -> int8, without
        # changing size_preset) — never repeat a cascade the
        # flagship already proved infeasible.  Random-init weights
        # make teacache's drift gate meaningless, so the variant
        # runs a DETERMINISTIC steps-cache-mask (compute the first
        # 2 and last 2 steps plus every other step between —
        # reference scm_steps_mask, cache_dit_backend.py:46-55);
        # the skip pattern is disclosed via skipped_steps and the
        # MFU accounting counts executed steps only.
        mask = [i < 2 or i >= steps - 2 or i % 2 == 0
                for i in range(steps)]
        var = bench_diffusion(size, scheduler, True, height, width,
                              steps, iters, ran_quant,
                              flagship["arch"]["offload"],
                              scm_mask=mask)
        out["step_cache_variant"] = {
            k: var[k] for k in ("metric", "value", "unit",
                                "seconds_per_image", "mfu")}
        out["step_cache_variant"]["skipped_steps"] = \
            var["arch"]["skipped_steps"]
        out["step_cache_variant"]["mode"] = (
            "teacache + deterministic scm mask (random-init "
            "weights make the drift gate meaningless)")
    else:
        out["step_cache_variant"] = {"skipped": skip_reason}

    print(json.dumps(out))


if __name__ == "__main__":
    main()
