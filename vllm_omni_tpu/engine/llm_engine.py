"""AR engine facade: scheduler + runner step loop.

The TPU-native collapse of the reference's LLMEngine → EngineCore →
worker-process chain (reference call stack SURVEY.md §3.2: OmniARScheduler
.schedule → GPUARModelRunner.execute_model/sample_tokens →
update_from_output).  On TPU the intra-stage fan-out is pjit over a mesh,
so the engine is a single-process object: schedule → jitted step → update.

``worker_type`` selects the scheduler the way the reference's
OmniModelConfig.worker_type picks AR vs generation workers
(reference: config/model.py:46-60).
"""

from __future__ import annotations

import dataclasses
import math
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from vllm_omni_tpu.core.kv_cache_manager import KVCacheManager
from vllm_omni_tpu.kvcache import layouts as cache_layouts
from vllm_omni_tpu.kvcache.quant import (
    concat_payloads,
    payload_seq_len,
    trim_payload,
)
from vllm_omni_tpu.introspection import (
    DeviceMemoryLedger,
    FlightRecorder,
    PhaseClock,
    register_engine,
)
from vllm_omni_tpu.introspection.phase_clock import startup
from vllm_omni_tpu.logger import init_logger
from vllm_omni_tpu.metrics.roofline import (
    capped_ctx_positions,
    ctx_positions,
)
from vllm_omni_tpu.metrics.stats import EngineStepMetrics
from vllm_omni_tpu.resilience.faults import fault_point
from vllm_omni_tpu.tracing import get_recorder
from vllm_omni_tpu.core.scheduler import (
    ARScheduler,
    GenerationScheduler,
    KVTransferConfig,
    SchedulerConfig,
    SchedulerOutput,
)
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.ops.ragged_paged_attention import (
    DEFAULT_TOKEN_BLOCK,
    wide_q_tokens,
)
from vllm_omni_tpu.ops.ssd import chunk_slots
from vllm_omni_tpu.outputs import OmniRequestOutput
from vllm_omni_tpu.request import Request, RequestStatus
from vllm_omni_tpu.sample.sampler import PATH_BOUNDED, PATH_FULL_SORT
from vllm_omni_tpu.sampling_params import SamplingParams
from vllm_omni_tpu.worker.model_runner import ARModelRunner

logger = init_logger(__name__)


@dataclass
class EngineConfig:
    num_pages: int = 256
    page_size: int = 16
    max_model_len: int = 4096
    max_num_seqs: int = 8
    max_num_batched_tokens: int = 2048
    worker_type: str = "ar"  # "ar" | "generation"
    # disaggregated prefill/decode serving (docs/disaggregation.md):
    # "prefill" engines run requests to the end of prompt processing
    # and ship the paged KV per-layer to a decode tier (kv_transfer is
    # auto-armed with the prefill_finished trigger); "decode" engines
    # adopt streamed KV into their paged cache and resume as decode
    # (the PR 6 resume-as-decode executable-identity rule — the decode
    # tier's first step runs the SAME decode executable an
    # uninterrupted colocated stream would).  "colocated" is the
    # classic single-engine shape and the degradation target when a
    # peer tier has no healthy replicas (disagg/router.py).
    engine_role: str = "colocated"  # "prefill" | "decode" | "colocated"
    enable_chunked_prefill: bool = False
    # automatic prefix caching: full prompt pages register under a
    # content hash when their producer frees; later requests sharing the
    # prefix skip recomputing it (vLLM-core APC; cached pages stay
    # allocatable via LRU eviction, so capacity is unaffected)
    enable_prefix_caching: bool = True
    # speculative decoding: drafts per step (needs a draft_fn — the MTP
    # head, models/qwen3_omni/mtp.py).  Verify rows are k+1-token ragged
    # rows of the unified dispatch; greedy requests verify by on-device
    # accept-mask, sampled requests by on-device rejection sampling
    num_speculative_tokens: int = 0
    # unified ragged batching POLICY (the execution mechanism is always
    # on since PR 11 — every non-pure-decode step is ONE token-packed
    # dispatch, ops/ragged_paged_attention.py, and the split executor
    # is deleted).  This flag controls the SCHEDULER's packing policy:
    # decodes claim the token budget first, prefill chunks fill the
    # remainder, and chunked prefill becomes the mechanism (implied
    # ON).  Off keeps the classic admission order and prompt-length
    # limits.  See docs/ragged_batching.md.
    unified_batching: bool = False
    # async pipelined step: two-slot pipeline — dispatch step N
    # (forward + ON-DEVICE sampling/verify/logprobs, the sampled tokens
    # stay device-resident and feed step N+1's dispatch directly), then
    # do step N-1's host work (readback, stop checks, metrics) while
    # the device computes.  Host readback lags exactly one step.  Since
    # PR 11 every batch shape pipelines — spec decode, logprobs,
    # collect_hidden, and embeds ride the unified dispatch; only
    # host-synchronous KV movement (cross-stage transfer, tier-offload
    # drains) and streaming chunk intake run a synchronous step.
    # Greedy token streams are bit-identical to sync mode.
    # None (nobody set it) is on for an AR engine and off for any other;
    # False keeps the synchronous step (the oracle of
    # tests/engine/test_async_step.py builds its sync side with it).
    # See docs/async_engine.md.
    async_scheduling: Optional[bool] = None
    # tiered KV offload (docs/kv_cache.md): evicted prefix-cache pages
    # and preempted requests PARK their KV in a host-RAM pool (and
    # optionally a remote store) instead of dropping it; restores
    # promote the bytes back before the scheduler re-admits.  The
    # radix prefix index tracks which tier each cached node lives in.
    kv_offload: bool = False
    # cold-path storage: "none" keeps parked payloads bit-exact
    # (restored greedy streams match the never-offloaded oracle);
    # "int8" halves the bytes moved between host and HBM
    kv_offload_quant: str = "none"
    # HBM-RESIDENT KV dtype (docs/performance.md): "int8" stores the
    # paged pool as int8 bytes + per-(head, page) absmax scales — the
    # attention kernels dequantize in-register during the page DMA
    # pipeline, and the same HBM budget holds ~2x the pages.
    # "auto"/"bf16" keep the dense layout in ``dtype``
    kv_cache_dtype: str = "auto"  # "auto" | "bf16" | "int8"
    # HBM budget the page pool is sized from.  None derives it from
    # ``num_pages`` at the DENSE layout — flipping to int8 then converts
    # the SAME budget into ~2x pages rather than keeping the page count
    kv_hbm_budget_bytes: Optional[int] = None
    # bytes-vs-recompute admission (kvcache/policy.py): "always" /
    # "never" pin the decision; "auto" runs the break-even math on
    # measured rates and, having none here, is refused — so kv_offload
    # needs one of the other two
    kv_offload_policy: str = "auto"
    # host tier capacity; overflow demotes LRU payloads to the remote
    # tier (or drops them without one).  None = unbounded
    kv_host_tier_bytes: Optional[int] = None
    # remote tier transport: a ConnectorFactory name ("inproc" | "shm"
    # | "tcp", distributed/connectors.py) + its constructor kwargs;
    # the edge runs under the PR 3 retry policy + circuit breaker
    kv_offload_connector: Optional[str] = None
    kv_offload_connector_args: Optional[dict] = None
    # precompile bucketed executables before serving: True warms every
    # decode batch bucket; a list of (batch, seq_len) pairs additionally
    # warms those prefill shapes.  A shape-cache miss mid-traffic stalls
    # all in-flight requests for a full XLA compile (about 19 s per
    # executable for the thinker on a v5e host, CHANGES.md PR 21) — see
    # ARModelRunner.precompile.
    warmup: Any = False  # bool | list[(batch, seq_len)]
    dtype: Any = jnp.bfloat16
    kv_transfer: Optional[KVTransferConfig] = None
    collect_hidden: bool = False
    # serving SLO targets (docs/load_testing.md): per-request TTFT and
    # TPOT upper bounds the engine accounts every finished request
    # against — slo_attainment_ratio / goodput_tokens_total on
    # /metrics, split per tenant.  None = that leg always passes
    # (goodput degenerates to throughput), so unconfigured serving
    # keeps its old behavior
    slo_ttft_ms: Optional[float] = None
    slo_tpot_ms: Optional[float] = None
    # admission control (load shedding): waiting-queue cap — arrivals
    # past it are refused with error_kind "shed" (HTTP 429,
    # shed_requests_total{reason="queue_depth"}) before any engine
    # admission work.  None = unbounded
    max_queue_depth: Optional[int] = None
    # shed arrivals whose remaining deadline is below this floor
    # (reason="deadline_headroom"); 0.0 disables
    admission_deadline_headroom_s: float = 0.0
    # weighted-fair overload scheduling (docs/control_plane.md): order
    # waiting-queue admission by per-tenant deficit round robin keyed
    # on Request.priority (the sanitized x-omni-priority metadata) and
    # make the max_queue_depth shed priority-ordered — under overload
    # low-priority work defers or sheds instead of everyone starving
    # equally.  Off keeps strict arrival order
    wfq_scheduling: bool = False
    # DRR quantum per unit of priority weight, in prompt tokens (the
    # tenant-interleave granularity; see core/scheduler.py)
    wfq_quantum_tokens: int = 256
    seed: Optional[int] = None  # pins sampling entropy for reproducibility
    # tensor parallelism over the first N devices (reference:
    # tensor_parallel_size, stage_configs/qwen3_omni_moe.yaml:27)
    tensor_parallel_size: int = 1


def _prompt_tokens_computed(sched_out: SchedulerOutput) -> int:
    """Prompt tokens a step computed, for the ``prefill_tokens`` counter:
    its prefill chunks, and a decode row that is still inside its prompt
    (a chunked prompt whose remainder is one token rides the decode rows,
    core/scheduler.py ``remaining > 1``; counted as a chunk or not, it is a
    prompt token, and the counter must add up to the prompts served)."""
    return (sum(s.num_new_tokens for s in sched_out.prefills)
            + sum(s.num_new_tokens for s in sched_out.decodes
                  if s.is_prefill))


@dataclass
class _InflightStep:
    """One slot of the two-slot pipeline: a dispatched-but-unretired
    decode step.  The engine retires it (token readback + stop checks +
    metrics) while the NEXT step's forward runs on the device."""

    sched_out: SchedulerOutput
    handle: Any                    # worker InflightDecode (device tokens)


class LLMEngine:
    def __init__(self, params, model_cfg: tfm.TransformerConfig,
                 config: Optional[EngineConfig] = None,
                 eos_token_id: Optional[int] = None,
                 draft_fn=None):
        config = config if config is not None else EngineConfig()
        if config.engine_role not in ("prefill", "decode", "colocated"):
            raise ValueError(
                f"engine_role must be prefill|decode|colocated, got "
                f"{config.engine_role!r}")
        if (config.engine_role == "prefill"
                and config.kv_transfer is None
                and config.worker_type == "ar"):
            # a prefill-role engine EXISTS to ship KV: arm the transfer
            # trigger so every request that finishes prompt processing
            # pins + extracts its pages for the decode tier.  Private
            # copy — the caller may build other roles from the same
            # config object.
            config = dataclasses.replace(
                config, kv_transfer=KVTransferConfig(
                    trigger="prefill_finished"))
        if (config.async_scheduling or config.unified_batching) \
                and config.worker_type != "ar":
            logger.warning(
                "async_scheduling/unified_batching only apply to AR "
                "engines; disabled for worker_type=%s", config.worker_type)
            # private copy — writing through would silently disable
            # async for other engines built from the same config object.
            # unified too: a generation-stage scheduler never emits
            # unified batches, so the runner must not warm a token-bucket
            # line of executables that can never dispatch
            config = dataclasses.replace(config, async_scheduling=False,
                                         unified_batching=False)
        if config.async_scheduling is None:
            # the two-slot step is the engine's step (a private copy, as
            # above: the caller may build other engines from the object)
            config = dataclasses.replace(
                config, async_scheduling=config.worker_type == "ar")
        # what this model's cache layouts cannot carry is refused here, by
        # name, before anything is built (kvcache/layouts.py)
        refused = cache_layouts.refusal(model_cfg, [
            mechanism for mechanism, asked in (
                (cache_layouts.INT8, config.kv_cache_dtype == "int8"),
                (cache_layouts.TRANSFER, config.kv_transfer is not None
                 or config.engine_role != "colocated"),
                (cache_layouts.OFFLOAD, config.kv_offload),
                (cache_layouts.SPECULATIVE,
                 config.num_speculative_tokens > 0 or draft_fn is not None),
                (cache_layouts.TENSOR_PARALLEL,
                 config.tensor_parallel_size > 1)) if asked])
        if refused:
            raise ValueError(refused)
        self.config = config
        self.eos_token_id = eos_token_id
        # tiered KV offload: the cold-side store + break-even policy
        # (docs/kv_cache.md).  AR engines only — the one-shot
        # generation scheduler never preempts or prefix-caches.
        self.kv_tiers = None
        kv_policy = None
        if config.kv_offload and config.worker_type == "ar" \
                and isinstance(model_cfg, tfm.TransformerConfig):
            remote = None
            if config.kv_offload_connector:
                from vllm_omni_tpu.distributed.connectors import (
                    ConnectorFactory,
                )

                remote = ConnectorFactory.create(
                    config.kv_offload_connector,
                    **(config.kv_offload_connector_args or {}))
            from vllm_omni_tpu.kvcache import OffloadPolicy, TieredKVStore

            self.kv_tiers = TieredKVStore(
                quant=config.kv_offload_quant,
                host_capacity_bytes=config.kv_host_tier_bytes,
                remote=remote)
            kv_policy = OffloadPolicy.for_model(
                model_cfg.num_kv_layers, model_cfg.kv_layout.heads,
                model_cfg.kv_layout.width,
                jnp.dtype(config.dtype).itemsize,
                mode=config.kv_offload_policy,
                quant_mode=config.kv_offload_quant)
        # HBM-resident KV layout: resolve the page-pool size from the
        # HBM budget under the chosen dtype.  int8 pages cost roughly
        # half a bf16 page (data + per-(head, page) scales), so the
        # SAME budget yields ~2x pages — capacity, not just bytes, is
        # the point of the quantized pool (docs/performance.md)
        if config.kv_cache_dtype not in ("auto", "bf16", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be auto|bf16|int8, got "
                f"{config.kv_cache_dtype!r}")
        self._kv_quant = config.kv_cache_dtype == "int8"
        num_pages = config.num_pages
        self._kv_bytes_per_token: Optional[float] = None
        self._kv_layout = self._kv_bytes_per_token_layer = None
        # a window, a selection and a layout a kind of layer: only a
        # model that has them sets these (below)
        self._kv_kinds = None
        self._window = self._index_topk = num_window_pages = 0
        if isinstance(model_cfg, tfm.TransformerConfig) \
                and config.worker_type == "ar":
            from vllm_omni_tpu.kvcache.quant import (
                bytes_per_token,
                page_bytes,
                pages_for_budget,
            )

            itemsize = jnp.dtype(config.dtype).itemsize
            # bytes follow the layout the runner allocates (per-head K and
            # V pools, or a latent layer's one padded row a token)
            layout = model_cfg.kv_layout
            self._kv_layout = layout
            if self._kv_quant or config.kv_hbm_budget_bytes is not None:
                budget = config.kv_hbm_budget_bytes
                if budget is None:
                    budget = config.num_pages * model_cfg.num_kv_layers * \
                        page_bytes(layout.heads,
                                   config.page_size, layout.width,
                                   quantized=False, itemsize=itemsize,
                                   pools=layout.pools)
                num_pages = pages_for_budget(
                    budget, model_cfg.num_kv_layers, layout.heads,
                    config.page_size, layout.width,
                    quantized=self._kv_quant, itemsize=itemsize,
                    pools=layout.pools)
                logger.info(
                    "kv_cache_dtype=%s: %d pages in a %.1f MiB HBM "
                    "budget (config asked %d at the dense layout)",
                    config.kv_cache_dtype, num_pages, budget / 2**20,
                    config.num_pages)
            # a cached token's bytes in ONE layer, declared padding (and
            # an int8 page's scales) included, and in all of them
            self._kv_bytes_per_token_layer = bytes_per_token(
                1, layout.heads, config.page_size,
                layout.lanes // layout.pools,
                quantized=self._kv_quant, itemsize=itemsize,
                pools=layout.pools)
            self._kv_bytes_per_token = (
                model_cfg.num_kv_layers * self._kv_bytes_per_token_layer)
            # a model whose attention layers are of two kinds (or keep an
            # index key beside the row) is accounted a kind: the full
            # kind's row and index key on ``num_pages``, the sliding
            # kind's row on a pool sized from what the window can hold
            self._window = (model_cfg.sliding_window
                            if model_cfg.num_window_layers else 0)
            self._index_topk = model_cfg.index_topk
            # what a sequence's slot holds in ONE layer that carries a
            # state, and in all of them (float32: the runner's slots)
            per_layer = [4 * sum(math.prod(shape) for shape in
                                 model_cfg.state_shapes(kind))
                         for kind in model_cfg.layer_types
                         if kind in tfm.STATE_KINDS]
            self._state_bytes_per_slot = sum(per_layer)
            self._state_bytes_per_slot_layer = max(per_layer, default=0)
            # the chunk of a model with Mamba-2 layers: ``ssd_chunk_fill``
            self._ssd_chunk = (model_cfg.mamba_chunk_size
                               if "mamba" in model_cfg.layer_types else 0)
            if self._window or model_cfg.index_topk:
                self._kv_kinds = self._kinds_of(model_cfg, config,
                                                num_pages, itemsize)
                self._kv_bytes_per_token = sum(
                    k["layers"] * k["bytes_per_token_layer"]
                    for k in self._kv_kinds.values())
            if self._window:
                num_window_pages = self._kv_kinds["sliding_attention"][
                    "pages_total"]
        # prefix caching skips the forward for cached positions, so it
        # cannot coexist with collect_hidden (downstream stages need the
        # hidden row of EVERY prompt position) — thinker-style stages
        # run uncached, plain LM serving gets APC
        kv = KVCacheManager(num_pages, config.page_size,
                            enable_prefix_caching=(
                                config.enable_prefix_caching
                                and config.worker_type == "ar"
                                and not config.collect_hidden),
                            tiers=self.kv_tiers, policy=kv_policy,
                            cache_dtype=(
                                "int8" if self._kv_quant
                                else str(jnp.dtype(config.dtype))),
                            bytes_per_token=self._kv_bytes_per_token,
                            # one state slot a sequence for a model with
                            # recurrent layers (docs/kv_cache.md)
                            num_state_slots=(
                                max(config.max_num_seqs, 1)
                                if getattr(model_cfg, "num_state_layers", 0)
                                else 0),
                            # and a second table a sequence over the
                            # window kind of page, for sliding layers
                            window=self._window,
                            num_window_pages=num_window_pages)
        sched_cfg = SchedulerConfig(
            max_num_seqs=config.max_num_seqs,
            max_num_batched_tokens=config.max_num_batched_tokens,
            max_model_len=config.max_model_len,
            enable_chunked_prefill=config.enable_chunked_prefill,
            num_speculative_tokens=config.num_speculative_tokens,
            kv_transfer=config.kv_transfer,
            unified_batching=config.unified_batching,
            kv_offload=self.kv_tiers is not None,
            max_queue_depth=config.max_queue_depth,
            admission_deadline_headroom_s=(
                config.admission_deadline_headroom_s),
            wfq_scheduling=config.wfq_scheduling,
            wfq_quantum_tokens=config.wfq_quantum_tokens,
        )
        sched_cls = (GenerationScheduler if config.worker_type == "generation"
                     else ARScheduler)
        self.scheduler = sched_cls(sched_cfg, kv)
        if not isinstance(model_cfg, tfm.TransformerConfig):
            # custom generation model object (code2wav vocoder etc.) —
            # only valid under the one-shot generation scheduler
            if config.worker_type != "generation":
                raise TypeError(
                    "model_cfg must be a TransformerConfig for AR stages; "
                    f"got {type(model_cfg).__name__}"
                )
            from vllm_omni_tpu.worker.generation_runner import (
                GenerationModelRunner,
            )

            self.runner = GenerationModelRunner(
                params, model_cfg,
                max_num_seqs=config.max_num_seqs,
                max_model_len=config.max_model_len,
            )
        else:
            mesh = None
            if config.tensor_parallel_size > 1:
                import numpy as _np
                from jax.sharding import Mesh

                from vllm_omni_tpu.parallel.mesh import AXIS_TP

                devs = jax.devices()
                tp = config.tensor_parallel_size
                if len(devs) < tp:
                    raise ValueError(
                        f"tensor_parallel_size={tp} but only "
                        f"{len(devs)} devices visible")
                mesh = Mesh(_np.array(devs[:tp]), (AXIS_TP,))
            # the pages, the state slots, the window pools
            with startup.span("omni.setup.cache"):
                self.runner = ARModelRunner(
                    params, model_cfg,
                    num_pages=num_pages, page_size=config.page_size,
                    kv_cache_dtype=config.kv_cache_dtype,
                    max_model_len=config.max_model_len, dtype=config.dtype,
                    collect_hidden=config.collect_hidden, seed=config.seed,
                    max_num_seqs=config.max_num_seqs, mesh=mesh,
                    max_num_batched_tokens=config.max_num_batched_tokens,
                    num_window_pages=num_window_pages,
                )
        # the host's phases on the host's clock (introspection/
        # phase_clock.py): one clock for the engine thread, which the
        # runner's halves of a step and the orchestrator's loop charge too
        self.phases = self.runner.phases = PhaseClock()
        # a compile under traffic is recorded with this engine's step
        self.runner.compile_stats.engine = weakref.ref(self)
        if (draft_fn is not None and config.num_speculative_tokens > 0
                and hasattr(self.runner, "set_draft_fn")):
            self.runner.set_draft_fn(
                draft_fn, config.num_speculative_tokens
            )
        if sched_cls is ARScheduler:
            # the runner's packing contract (token block, bucket line):
            # the scheduler sizes a prefill chunk against the bucket its
            # step already needs.  The one-shot generation scheduler
            # ignores the budget and takes none.
            self.scheduler.packing = self.runner.packing
        # connector hook: called with (request, kv_payload) when a
        # cross-stage KV extraction completes (OmniKVTransferManager put)
        self.kv_transfer_sink: Optional[Callable] = None
        self._req_counter = 0
        self._starved_ticks = 0
        # async pipelined step: the dispatched-but-unretired slot
        self._inflight: Optional[_InflightStep] = None
        # observability: step-level gauges/histograms (TTFT/TPOT/ITL) +
        # per-request span recording.  stage_id is stamped by OmniStage
        # so spans and /metrics series carry the pipeline position.
        self.stage_id = 0
        # fleet span identity (tracing/journey.py): empty for pipeline
        # stages; EngineReplica stamps {"replica_id", "role"} so this
        # engine's spans render on its own Perfetto replica track
        self.span_tags: dict = {}
        self.step_metrics = EngineStepMetrics()
        # SLO accounting targets: every finished request is judged
        # against them per tenant (slo_attainment_ratio, goodput)
        self.step_metrics.slo_ttft_ms = config.slo_ttft_ms
        self.step_metrics.slo_tpot_ms = config.slo_tpot_ms
        # per-tenant heavy-hitter attribution (metrics/attribution.py):
        # bounded-memory space-saving sketches metering prefill/decode
        # tokens, KV page·seconds per tier, handoff bytes, queue wait,
        # and sheds — the answer to "which tenant is eating this
        # engine" that survives millions of distinct tenants.  The
        # scheduler's shed path and the KV manager's occupancy clock
        # feed it; top-k renders on /metrics and /debug/tenants
        from vllm_omni_tpu.metrics.attribution import TenantAttribution

        self.attribution = TenantAttribution()
        self.scheduler.attribution_sink = self.attribution.add
        # async pipeline drain granularity: how many steps fell back to
        # the synchronous path, PER REASON ("prefill", "spec",
        # "logprobs", "kv_transfer", ...) — under unified batching the
        # prefill row stops growing, which makes the unified win
        # directly visible on /metrics (async_fallback_total)
        self.async_fallback: dict[str, int] = {}
        # how often the pipeline engages: steps by the path they took
        # (the flight record's ``path``), and drains — an in-flight step
        # retired with nothing dispatched behind it (a bubble, a fallback,
        # or the queue ran dry), after which the next step overlaps nothing
        self.steps_by_path: dict[str, int] = {"pipelined": 0, "sync": 0}
        self.pipeline_drains = 0
        # request_id -> [first_token_ts, last_token_ts, tokens_seen]
        self._req_lat: dict[str, list] = {}
        self._trace_started: set[str] = set()
        # introspection (docs/debugging.md): the per-step flight
        # recorder (bounded ring, appended with zero device syncs),
        # the per-component device-memory ledger, and registration in
        # the process registry so crash dumps / the stall watchdog /
        # the /debug/z endpoints can find this engine
        from vllm_omni_tpu import envs as _envs2

        self.flight = FlightRecorder(
            capacity=max(int(_envs2.OMNI_TPU_FLIGHT_CAPACITY), 1),
            name=f"{config.worker_type}-engine")
        # live roofline attribution (metrics/roofline.py): per-step
        # achieved FLOPs / HBM bytes from static geometry × the step's
        # token mix, against the platform peaks — engine_step_mfu /
        # engine_step_mbu{phase} on /metrics, per-record fields in the
        # flight recorder, the rolling window on /debug/engine.  Host
        # math only (zero device syncs); AR transformers only — the
        # one-shot generation runner has no token-mix geometry.
        self.roofline = None
        if isinstance(model_cfg, tfm.TransformerConfig):
            from vllm_omni_tpu.metrics.roofline import (
                ModelGeometry,
                RooflineTracker,
            )
            from vllm_omni_tpu.platforms import current_platform

            p = current_platform()
            self.roofline = RooflineTracker(
                ModelGeometry.from_transformer_config(
                    model_cfg, jnp.dtype(config.dtype).itemsize),
                peak_tflops=p.peak_tflops_bf16(),
                peak_gbps=p.peak_hbm_gbps())
        self.memory = DeviceMemoryLedger(self._memory_components)
        # kv tier moves drained this step — recorded per step so the
        # flight tail shows offload/restore churn around a bad minute
        self._last_kv_moves = (0, 0)
        # watchdog progress signal: step() COMPLETIONS.  Distinct from
        # flight.total_steps on purpose — zero-scheduled ticks (e.g. a
        # streaming request idling for its next chunk, pages pinned by
        # an in-flight transfer) append no record but ARE the step loop
        # turning; a watchdog keyed on records would false-trip on
        # those documented-normal busy-idle states, while a step
        # wedged mid-flight freezes this counter exactly as intended
        self._steps_completed = 0
        register_engine(self)
        if config.warmup:
            shapes = (config.warmup if isinstance(
                config.warmup, (list, tuple)) else ())
            n = self.warmup(prefill_shapes=shapes)
            logger.info(
                "engine warmup: %d executables precompiled before "
                "serving", n)

    @staticmethod
    def _kinds_of(model_cfg, config: "EngineConfig", num_pages: int,
                  itemsize: int) -> dict:
        """{layer kind: layers, the rows a token has in a pool (its kv
        heads), row widths, bytes a cached token a layer, pages} of a model
        whose attention layers are of two kinds or keep an index key beside
        the row (``kv.kinds`` of the snapshot).  ``row_width`` and
        ``row_used`` are the K pool's row (the one pool's of a latent
        layer); ``value_width`` the V pool's, and ``lanes_padding_pct``
        the share of a cached token's bytes that are declared padding."""
        kinds = {}
        page = config.page_size
        for kind in ("full_attention", "sliding_attention"):
            layers = model_cfg.layer_types.count(kind)
            if not layers:
                continue
            layout = model_cfg.kv_layout_of(kind)
            index = (model_cfg.index_head_dim
                     if kind == "full_attention" and model_cfg.index_topk
                     else 0)
            pages = num_pages
            if kind == "sliding_attention":
                # what max_num_seqs sequences can hold of a window and a
                # step's tokens, a page of slack each, and the null page
                seqs = max(config.max_num_seqs, 1)
                pages = -(-seqs * (model_cfg.sliding_window
                                   + config.max_num_batched_tokens)
                          // page) + seqs + 1
            kinds[kind] = {
                "layers": layers, "heads": layout.heads,
                "row_width": layout.width, "row_used": layout.used,
                "value_width": layout.value_width,
                "lanes_padding_pct": round(
                    100.0 * (1 - layout.lanes_used / layout.lanes), 2),
                "index_width": index,
                "bytes_per_token_layer": float(
                    itemsize * layout.heads
                    * (layout.lanes + layout.pools * index)),
                "pages_total": pages}
        return kinds

    # ------------------------------------------------------------- warmup
    def warmup(self, prefill_shapes=(), progress_fn=None) -> int:
        """Precompile the runner's bucketed executables before serving
        (every decode batch bucket, plus the given (batch, seq_len)
        prefill shapes).  A shape-cache miss mid-traffic stalls all
        in-flight requests for a full XLA compile.  Returns executables
        requested.
        Reference analogue: worker warmup / graph capture before the
        engine goes live."""
        fn = getattr(self.runner, "precompile", None)
        if fn is None:
            return 0
        with startup.span("omni.setup.warmup"):
            built = fn(prefill_shapes=prefill_shapes,
                       progress_fn=progress_fn)
        stats = self.runner.compile_stats
        parts = stats["trace_s"] + stats["lower_s"] + stats["backend_s"]
        # the shape-cache telemetry baseline: compiles past this line
        # are mid-traffic stalls (jit_compiles_total on /metrics keeps
        # counting them)
        logger.info(
            "warmup compiled %d executables in %.1fs: tracing %.1fs, "
            "lowering %.1fs, compiling or loading %.1fs (%d of the "
            "persistent cache's, %d new to it), first runs %.1fs "
            "(%d cache hits)", stats["compiles"], stats["compile_s"],
            stats["trace_s"], stats["lower_s"], stats["backend_s"],
            stats["persistent_hits"], stats["persistent_misses"],
            max(stats["compile_s"] - parts, 0.0), stats["cache_hits"])
        return built

    # ------------------------------------------------------------- intake
    def add_request(
        self,
        prompt_token_ids: list[int],
        sampling_params: Optional[SamplingParams] = None,
        request_id: Optional[str] = None,
        injected_kv: Optional[list] = None,
        injected_first_token: Optional[int] = None,
        **kwargs,
    ) -> str:
        """``injected_kv``: per-layer [(k, v)] dense KV of a prompt prefix
        computed by an upstream engine (disaggregated prefill / cross-stage
        KV reuse).  The prefix lands in this engine's paged cache and only
        the remainder of the prompt is (re)computed — at least the last
        prompt token always recomputes so there are logits to sample from
        (the receive half of OmniKVTransferManager, reference:
        kv_transfer_manager.py:100+).

        ``injected_first_token``: the first sampled token, when the
        upstream PREFILL engine already produced it (disaggregated
        prefill, docs/disaggregation.md).  With it, the injected KV may
        cover the WHOLE prompt and the request resumes through the
        DECODE executable (the scheduler's resume-as-decode branch) —
        the same executable an uninterrupted colocated stream runs, so
        greedy continuations stay bit-identical to the colocated
        oracle.  Without it the prefix is capped at prompt-1 tokens and
        the last prompt position recomputes for its logits."""
        if request_id is None:
            request_id = f"req-{self._req_counter}"
            self._req_counter += 1
        req = Request(
            request_id=request_id,
            prompt_token_ids=list(prompt_token_ids),
            sampling_params=sampling_params or SamplingParams(),
            eos_token_id=self.eos_token_id,
            arrival_time=time.time(),
            arrival_mono=time.monotonic(),
            **kwargs,
        )
        if injected_first_token is not None:
            # appended BEFORE admission: num_tokens includes it, so the
            # remainder-to-compute is exactly one sampling position
            req.append_output_token(int(injected_first_token))
        injected_len = 0
        if injected_kv is not None:
            injected_len = min(payload_seq_len(injected_kv),
                               max(req.num_tokens - 1, 0))
        accepted = req.additional_information.get("accept_mono")
        if isinstance(accepted, float):
            # the front end's stamp at HTTP accept, on this host's
            # monotonic clock (entrypoints/openai/api_server.py)
            self.step_metrics.intake_ms.observe(
                max(req.arrival_mono - accepted, 0.0) * 1e3)
        self.scheduler.add_request(req, injected_len=injected_len)
        if injected_kv is not None and req.status is RequestStatus.WAITING:
            self._inject_prefix_kv(req, injected_kv)
        return request_id

    def _inject_prefix_kv(self, req: Request, payload: list) -> None:
        # with a pre-appended first token (disaggregated prefill) the
        # whole PROMPT may inject — the one remaining position is the
        # sampling one and re-enters as a decode; otherwise the last
        # prompt token recomputes for its logits
        seq_len = payload_seq_len(payload)
        use = min(seq_len, req.num_tokens - 1)
        if use <= 0:
            if req.output_token_ids:
                req.output_token_ids.pop()  # unbackable first token
            return
        # provenance split (PR 19): a payload the router pulled from
        # the cluster KV fabric admits through adopt_prefix and counts
        # as prefix_pull_tokens; a disaggregated-prefill handoff stays
        # on the adopt_streamed/streamed_tokens path.  Same allocator,
        # same guards — only the accounting differs.
        pulled = bool(req.additional_information.get("prefix_pull"))
        kv = self.scheduler.kv
        table = (kv.adopt_prefix(req, use) if pulled
                 else kv.adopt_streamed(req, use))
        if table is not None:
            try:
                t0, w0 = time.perf_counter(), time.time()
                # format-agnostic trim: dense slices the token axis;
                # quantized wire payloads also trim the per-page scale
                # axis (kvcache/quant.py)
                trimmed = trim_payload(payload, use,
                                       self.config.page_size)
                self.runner.inject_kv(table, trimmed)
                req.num_computed_tokens = use
                (kv.note_pulled if pulled else kv.note_streamed)(use)
                get_recorder().record(
                    req.additional_information.get("trace"), "kv_inject",
                    w0, time.perf_counter() - t0, stage_id=self.stage_id,
                    cat="kv",
                    args={"tokens": use,
                          "src": "fabric" if pulled else "peer"},
                    **self.span_tags,
                )
                return
            except (ValueError, IndexError) as e:
                # malformed payload (e.g. upstream layer-count mismatch):
                # fall back to full recompute — the prefix is re-derivable
                # from the prompt tokens, and the already-allocated pages
                # cover the same positions the recompute will write
                logger.warning(
                    "request %s: injected KV rejected (%s); recomputing "
                    "the full prompt", req.request_id, e,
                )
        # fallback taken (pool pressure or bad payload): the request
        # recomputes from scratch.  A pre-appended first token whose
        # backing KV never landed is STRIPPED first — keeping it would
        # compute its successor position through a (prompt+1)-token
        # prefill chunk, while the colocated oracle samples that
        # position through full-prefill + decode; recompute must
        # re-derive t1 through the oracle's own executables
        # (bit-exactness rule, docs/disaggregation.md)
        if req.output_token_ids and req.num_computed_tokens == 0:
            req.output_token_ids.pop()
        # recheck it can actually be scheduled as a full recompute
        if (not self.scheduler.config.chunking_enabled
                and req.num_tokens
                > self.scheduler.config.max_num_batched_tokens):
            self.scheduler.waiting.remove(req)
            self.scheduler.kv.free(req)
            self.scheduler.reject(
                req,
                "prompt exceeds max_num_batched_tokens and its injected "
                "KV prefix could not be applied (chunked prefill off)",
            )

    def append_prompt_chunk(
        self,
        request_id: str,
        token_ids: list[int] = (),
        prompt_embeds=None,
        final: bool = False,
    ) -> None:
        """Extend a streaming request's prompt (async_chunk intake,
        reference: OmniChunkTransferAdapter feeding WAITING_FOR_CHUNK
        requests, transfer_adapter/chunk_transfer_adapter.py:19).

        The request must have been added with ``awaiting_chunks=True``;
        arrived tokens prefill as chunks while later ones are still being
        produced upstream, and sampling starts only after ``final=True``.
        Embeds-based requests append matching ``prompt_embeds`` rows.
        """
        queue, req = self.scheduler.find_request(request_id)
        if req is None:
            raise KeyError(f"no in-flight request {request_id!r}")
        if not req.awaiting_chunks:
            raise ValueError(
                f"request {request_id!r} is not a streaming request "
                "(awaiting_chunks=False)"
            )
        token_ids = list(token_ids)
        # embeds/token mode is fixed by the first content chunk; mixing
        # silently corrupts positions, so it is an error OUTPUT (the
        # caller is usually a remote stage that cannot handle a raise)
        embeds_based = req.prompt_embeds is not None
        if token_ids and req.num_prompt_tokens > 0:
            if embeds_based and prompt_embeds is None:
                self.scheduler.fail_request(
                    request_id,
                    "embeds-based streaming request: every chunk must "
                    "carry prompt_embeds rows matching its token_ids",
                )
                return
            if not embeds_based and prompt_embeds is not None:
                self.scheduler.fail_request(
                    request_id,
                    "token-based streaming request received an embeds "
                    "chunk (mode is fixed by the first chunk)",
                )
                return
        new_len = req.num_prompt_tokens + len(token_ids)
        over = (new_len > self.config.max_model_len
                or self.scheduler.kv.pages_needed(new_len)
                > self.scheduler.kv.num_pages)
        # a request still in WAITING is admitted whole: without chunked
        # prefill its remainder must fit one step's budget (add_request
        # enforces the same at intake; a grown waiting request would pin
        # the queue head forever). RUNNING streams are exempt — the
        # continuation branch chunks them under the budget regardless.
        if (not over and queue is self.scheduler.waiting
                and not self.scheduler.config.chunking_enabled
                and new_len - req.num_computed_tokens
                > self.config.max_num_batched_tokens):
            over = True
        if over:
            self.scheduler.fail_request(
                request_id,
                f"streamed prompt grew to {new_len} tokens, exceeding "
                "the engine limits",
            )
            return
        if token_ids:
            req.prompt_token_ids.extend(int(t) for t in token_ids)
            if prompt_embeds is not None:
                import numpy as np

                pe = np.asarray(prompt_embeds)
                if pe.shape[0] != len(token_ids):
                    self.scheduler.fail_request(
                        request_id,
                        f"chunk embeds rows {pe.shape[0]} != chunk "
                        f"tokens {len(token_ids)}",
                    )
                    return
                req.prompt_embeds = (
                    pe if req.prompt_embeds is None
                    else np.concatenate([req.prompt_embeds, pe], axis=0)
                )
        if final:
            req.awaiting_chunks = False
            if req.num_tokens == 0:
                # a stream that never produced content can neither sample
                # nor finish; error-finish instead of wedging the engine
                self.scheduler.fail_request(
                    request_id, "streaming request finalized empty")
                return
            if req.num_computed_tokens >= req.num_tokens:
                # every arrived token was already prefilled with sampling
                # suppressed — the final position's logits were discarded,
                # so recompute it (same slot, one-token chunk) to sample
                req.num_computed_tokens = req.num_tokens - 1

    def add_errored_request(
        self, request_id: str, reason: str, kind: str = "invalid_request"
    ) -> str:
        """Register a request already known to be invalid (e.g. multimodal
        preprocessing failed) so it surfaces as an error output through the
        normal step() drain instead of raising into the submitter."""
        req = Request(
            request_id=request_id, prompt_token_ids=[],
            sampling_params=SamplingParams(), arrival_time=time.time(),
        )
        self.scheduler.reject(req, reason, kind)
        return request_id

    def abort_request(self, request_id: str) -> None:
        self.scheduler.abort_request(request_id)
        self._req_lat.pop(request_id, None)
        self._trace_started.discard(request_id)

    @property
    def has_unfinished_requests(self) -> bool:
        # Pending errored (intake-rejected) requests count as unfinished so
        # the stage polling loop keeps stepping until step() drains them —
        # otherwise a lone invalid request is silently dropped and its
        # client hangs forever (ADVICE r1 medium).
        return (self.scheduler.has_unfinished
                or self.scheduler.has_pending_errored)

    # ----------------------------------------------------------- re-roling
    def set_engine_role(self, role: str) -> None:
        """Flip a QUIESCED engine's disaggregated-serving role
        (docs/control_plane.md live re-roling): prefill arms the
        prefill_finished KV-transfer trigger, decode/colocated disarm
        it.  The same compiled executables serve every role — a role is
        scheduler policy plus transfer arming, never a recompile — so a
        flip is O(host state).  Refused while requests are in flight:
        an armed/disarmed trigger changing under a live request would
        split its stream across transfer regimes (the caller drains
        first — the router's drain -> quiesce -> flip sequence)."""
        if role not in ("prefill", "decode", "colocated"):
            raise ValueError(
                f"engine_role must be prefill|decode|colocated, got "
                f"{role!r}")
        if self.has_unfinished_requests:
            raise RuntimeError(
                "cannot re-role an engine with unfinished requests; "
                "drain it first (router.drain -> quiesced)")
        if role == self.config.engine_role:
            return
        if (self.config.engine_role == "colocated"
                and self.config.kv_transfer is not None):
            # a colocated engine whose transfer trigger serves the
            # CROSS-STAGE pipeline (thinker -> talker) is not a disagg
            # tier: flipping it would silently unhook the next stage
            raise RuntimeError(
                "refusing to re-role an engine with a cross-stage "
                "kv_transfer config")
        kv_cfg = (KVTransferConfig(trigger="prefill_finished")
                  if role == "prefill" else None)
        self.config = dataclasses.replace(
            self.config, engine_role=role, kv_transfer=kv_cfg)
        self.scheduler.config.kv_transfer = kv_cfg

    # ---------------------------------------------------------------- step
    @property
    def prefix_cache_stats(self) -> dict:
        """APC effectiveness counters (vLLM-core cache hit metrics)."""
        kv = self.scheduler.kv
        return {"hits": getattr(kv, "prefix_hits", 0),
                "hit_tokens": getattr(kv, "prefix_hit_tokens", 0),
                "enabled": getattr(kv, "enable_prefix_caching", False)}

    def reset_prefix_cache(self) -> int:
        """Release every unreferenced APC page (reference:
        reset_prefix_cache — cached KV is stale after a weight swap);
        returns pages released."""
        kv = self.scheduler.kv
        fn = getattr(kv, "reset_prefix_cache", None)
        return fn() if fn is not None else 0

    # --------------------------------------------------------- introspection
    def _memory_components(self) -> dict:
        """Attributable device-memory components for the ledger (the
        runner's static buffer sizes; empty for runners that don't
        account themselves)."""
        fn = getattr(self.runner, "memory_components", None)
        return fn() if fn is not None else {}

    def introspect_progress(self) -> dict:
        """Stall-watchdog probe: busy-ness, a monotone step counter,
        and the compile telemetry that separates an XLA-compile stall
        from a true hang (docs/debugging.md).  Host-side reads only."""
        compile_stats = getattr(self.runner, "compile_stats", {}) or {}
        return {
            "busy": self.has_unfinished_requests,
            "progress": self._steps_completed,
            "compiles": int(compile_stats.get("compiles", 0)),
            "compile_in_flight": bool(compile_stats.get("in_flight", 0)),
            "detail": {
                "stage_id": self.stage_id,
                "waiting": len(self.scheduler.waiting),
                "running": len(self.scheduler.running),
                # the executable being built, where a compile is in
                # flight: a trip's dump names it
                "compiling": getattr(compile_stats, "current", None),
            },
        }

    def _record_step(self, path: str, sched_out: SchedulerOutput,
                     scheduled, new_tokens: int, host_ms: float,
                     device_ms: float,
                     fallback: Optional[str] = None,
                     phases_ms: Optional[dict] = None,
                     cpu_ms: Optional[float] = None) -> None:
        """Append one flight-recorder record.  Every field is a host
        int/str the step already computed — NO device syncs here (the
        recorder path is omnilint OL2 HOT_PATHS scoped)."""
        compile_stats = getattr(self.runner, "compile_stats", {}) or {}
        inflight = self._inflight
        rows = (getattr(inflight.handle, "rows", None)
                if inflight is not None else None)
        # consume the drain counts: pipelined steps never run
        # _drain_kv_moves, so without the reset every pipelined record
        # would replay the LAST sync step's tier churn
        offloads, restores = self._last_kv_moves
        self._last_kv_moves = (0, 0)
        # spec decode honesty (record schema v2, docs/debugging.md): a
        # verify-heavy step is distinguishable from plain decode —
        # spec_rows counts k+1-token verify rows, verify_tokens their
        # total candidate positions.  ``unified`` reflects the EXECUTED
        # path (spec steps ride the unified dispatch since PR 11), not
        # just the scheduler's packing-policy flag.
        spec_rows = [s for s in sched_out.decodes if s.num_new_tokens > 1]
        unified = bool(getattr(sched_out, "unified", False)
                       or sched_out.prefills or spec_rows)
        # record schema v3 additions (docs/debugging.md): live roofline
        # attribution + the journey-trace cross-link.  All host ints —
        # start_pos/num_new_tokens are scheduler state, the wall time
        # is the host_ms/device_ms sum already computed; NO device syncs
        roofline = None
        # attended (new token x context position) pairs of the step, by
        # phase: what a roofline of the attention kernels needs, and
        # what the benchmark reads in place of a traffic mix's mean
        prefill_ctx = sum(ctx_positions(s.start_pos, s.num_new_tokens)
                          for s in sched_out.prefills)
        decode_ctx = sum(ctx_positions(s.start_pos, s.num_new_tokens)
                         for s in sched_out.decodes)
        # new tokens the ragged kernel attends as whole q-block tiles
        # (decodes pack first; a pure-decode step never reaches it)
        wide = wide_q_tokens(
            [s.num_new_tokens
             for s in sched_out.decodes + sched_out.prefills],
            getattr(self.runner, "q_block", DEFAULT_TOKEN_BLOCK),
        ) if unified else 0
        # what the step's chunks gave up to fit its bucket, and whether
        # its decode rows alone had opened a bucket above the budget's
        # (core/scheduler.py PackingContract)
        trimmed = getattr(sched_out, "chunk_tokens_trimmed", 0)
        raised = bool(getattr(sched_out, "bucket_raised", False))
        step_metrics = getattr(self, "step_metrics", None)  # fakes
        if step_metrics is not None:
            step_metrics.wide_q_tokens_total += wide
            step_metrics.chunk_tokens_trimmed_total += trimmed
            step_metrics.bucket_raised_steps_total += raised
        # dispatches retired since the last record whose sampler applied
        # a top-k / top-p filter, by the way it found its thresholds (this
        # step's own on the sync path, its predecessor's when pipelined)
        tally = getattr(self.runner, "sampler_steps", None)  # fakes
        bounded = full_sort = 0
        if tally is not None:
            bounded, full_sort = tally[PATH_BOUNDED], tally[PATH_FULL_SORT]
            tally[:] = [0, 0, 0]  # consumed, as _last_kv_moves is
            if step_metrics is not None:
                step_metrics.sampler_bounded_steps_total += bounded
                step_metrics.sampler_full_sort_steps_total += full_sort
        # dispatches since the last record whose executable holds the
        # experts' few-rows kernel (ops/grouped_matmul.py)
        held = getattr(self.runner, "grouped_kernel_steps", 0)  # fakes
        if held and step_metrics is not None:
            step_metrics.moe_grouped_kernel_steps_total += held
            self.runner.grouped_kernel_steps = 0
        # sequences whose recurrent state this step read and wrote (every
        # scheduled row of a model with recurrent layers; 0 otherwise):
        # what a roofline of the Gated DeltaNet layers needs
        kv = getattr(self.scheduler, "kv", None)  # duck-typed fakes
        state_rows = (len(sched_out.prefills) + len(sched_out.decodes)
                      if getattr(kv, "num_state_slots", 0) else 0)
        if step_metrics is not None and kv is not None and (
                sched_out.prefills or sched_out.decodes):
            # pages held while the step ran (live tables and cached
            # prefixes), every model: what fills the pool
            step_metrics.kv_pages_used.observe(
                kv.num_pages - kv.num_free_pages)
        # keys the step's new tokens attend where attention is bounded:
        # the best ``index_topk`` on the full layers of a model with
        # learned sparse attention, the last ``sliding_window`` on its
        # sliding layers (what their rooflines count; absent otherwise)
        capped = {}
        for name, cap in (("kept", getattr(self, "_index_topk", 0)),
                          ("window", getattr(self, "_window", 0))):
            if cap:
                for phase, scs in (("prefill", sched_out.prefills),
                                   ("decode", sched_out.decodes)):
                    capped[f"{phase}_ctx_{name}"] = int(sum(
                        capped_ctx_positions(s.start_pos, s.num_new_tokens,
                                             cap) for s in scs))
        if step_metrics is not None and prefill_ctx + decode_ctx > 0:
            if "decode_ctx_kept" in capped:
                step_metrics.sparse_keep_pct.observe(
                    100.0 * (capped["prefill_ctx_kept"]
                             + capped["decode_ctx_kept"])
                    / (prefill_ctx + decode_ctx))
            if "decode_ctx_window" in capped:
                step_metrics.window_keep_pct.observe(
                    100.0 * (capped["prefill_ctx_window"]
                             + capped["decode_ctx_window"])
                    / (prefill_ctx + decode_ctx))
            if getattr(kv, "window", 0):
                step_metrics.kv_window_pages_used.observe(
                    kv.window_pages_in_use)
        if state_rows and step_metrics is not None:
            # slots in use while the step ran: a sequence that finished in
            # it has given its slot back by now, but was one of its rows
            step_metrics.state_slots.observe(
                max(kv.state_slots_in_use, state_rows))
            chunk = getattr(self, "_ssd_chunk", 0)
            if chunk and unified:
                # how full a Mamba-2 model's chunks ran (ops/ssd.py)
                tokens, slots = chunk_slots(
                    [s.num_new_tokens for s in scheduled], chunk)
                if slots:
                    step_metrics.ssd_chunk_fill.observe(
                        100.0 * tokens / slots)
        tracker = getattr(self, "roofline", None)  # duck-typed fakes
        if tracker is not None:
            roofline = tracker.on_step(
                prefill_tokens=sum(s.num_new_tokens
                                   for s in sched_out.prefills),
                prefill_ctx=prefill_ctx,
                decode_tokens=sum(s.num_new_tokens
                                  for s in sched_out.decodes),
                decode_ctx=decode_ctx,
                sampled_rows=len(sched_out.prefills)
                + len(sched_out.decodes),
                wall_s=(host_ms + device_ms) / 1e3,
            )
        # capped trace-id cross-link: a watchdog-trip dump pivots from
        # the bad step straight to the journey timeline (the ids to
        # grep in the .trace.jsonl / Perfetto search box)
        trace_ids = []
        for s in scheduled[:32]:
            t = (getattr(s.request, "additional_information", None)
                 or {}).get("trace")
            if t and t.get("trace_id") and len(trace_ids) < 8:
                if t["trace_id"] not in trace_ids:
                    trace_ids.append(t["trace_id"])
        self.flight.append({
            "path": path,
            "unified": unified,
            "fallback": fallback,
            "prefills": len(sched_out.prefills),
            "decodes": len(sched_out.decodes),
            "spec_rows": len(spec_rows),
            "verify_tokens": sum(s.num_new_tokens for s in spec_rows),
            "new_tokens": new_tokens,
            "prefill_tokens": sum(s.num_new_tokens
                                  for s in sched_out.prefills),
            "prefill_ctx": int(prefill_ctx),
            "decode_ctx": int(decode_ctx),
            **capped,
            "wide_q_tokens": wide,
            "chunk_tokens_trimmed": trimmed,
            "bucket_raised": raised,
            "sampler_path": ("full_sort" if full_sort
                             else "bounded" if bounded else None),
            "state_rows": state_rows,
            "waiting": len(self.scheduler.waiting),
            "running": len(self.scheduler.running),
            # the step's number, as its ``omni.step`` annotation carries
            # it (``step_num``): a record and its annotation join exactly
            "step": getattr(self, "_steps_completed", None),
            # host_ms + device_ms is the step; device_ms is what the
            # engine thread waited on the device, cpu_ms what it spent
            # on a CPU, phases_ms the step by phase (exclusive; they sum
            # to the step): introspection/phase_clock.py
            "host_ms": round(host_ms, 3),
            "device_ms": round(device_ms, 3),
            "cpu_ms": None if cpu_ms is None else round(cpu_ms, 3),
            "phases_ms": phases_ms or {},
            "kv_offloads": offloads,
            "kv_restores": restores,
            "slot": {"occupied": inflight is not None,
                     "rows": (len(rows) if isinstance(rows, dict)
                              else None)},
            "compiles": int(compile_stats.get("compiles", 0)),
            # which requests rode this step (capped: the record must
            # stay small at any batch size)
            "requests": [s.request.request_id for s in scheduled[:32]],
            # v3: roofline attribution + journey cross-link (capped)
            "mfu": roofline["mfu"] if roofline else None,
            "mbu": roofline["mbu"] if roofline else None,
            "roofline_phase": roofline["phase"] if roofline else None,
            "trace_ids": trace_ids,
        })

    def _padding_totals(self) -> tuple[int, int]:
        """Runner-side lifetime (useful, padded) token counters — the
        per-step deltas feed the padding-efficiency metrics."""
        return (getattr(self.runner, "useful_tokens", 0),
                getattr(self.runner, "padded_tokens", 0))

    def _observe_padding(self, useful_before: int, padded_before: int
                         ) -> None:
        useful, padded = self._padding_totals()
        self.step_metrics.on_padding(useful - useful_before,
                                     padded - padded_before)

    def _note_first_scheduled(self, scheduled) -> None:
        """First-time-scheduled bookkeeping shared by the synchronous
        and pipelined paths: the queue_wait trace span and the
        queue_wait_ms histogram (arrival -> first scheduled, monotonic
        duration — the queueing term the serving curve bends on)."""
        rec = get_recorder()
        now_w = time.time()
        now_m = time.monotonic()
        for s in scheduled:
            req = s.request
            if req.request_id in self._trace_started:
                continue
            self._trace_started.add(req.request_id)
            wait_s = (max(now_m - req.arrival_mono, 0.0)
                      if req.arrival_mono else 0.0)
            self.step_metrics.queue_wait_ms.observe(wait_s * 1e3)
            self.attribution.add(req.tenant, "queue_wait_ms",
                                 wait_s * 1e3)
            ctx = req.additional_information.get("trace")
            if ctx and req.arrival_time:
                # span START stays wall-clock (trace timelines align on
                # wall timestamps); the DURATION is monotonic.  The
                # tenant rides the args so WFQ queue-wait reads
                # per-tenant straight off the timeline
                rec.record(ctx, "queue_wait", req.arrival_time,
                           wait_s if req.arrival_mono
                           else now_w - req.arrival_time,
                           stage_id=self.stage_id, cat="queue",
                           args={"tenant": getattr(req, "tenant",
                                                   "default")},
                           **self.span_tags)

    def _observe_saturation(self, sched_out: SchedulerOutput) -> None:
        """Per-phase saturation gauges from this schedule: prefill and
        decode token-budget fractions + running-seat fraction — the
        axis that pins first is where the serving curve knees."""
        budget = max(self.config.max_num_batched_tokens, 1)
        prefill_toks = sum(s.num_new_tokens for s in sched_out.prefills)
        decode_toks = sum(s.num_new_tokens for s in sched_out.decodes)
        self.step_metrics.on_saturation(
            prefill=prefill_toks / budget,
            decode=decode_toks / budget,
            seats=(len(self.scheduler.running)
                   / max(self.config.max_num_seqs, 1)),
        )

    def metrics_snapshot(self) -> dict:
        """Step-level engine metrics for /metrics (Prometheus + JSON):
        latency histograms, scheduler depth + preemption/rejection
        counters, KV page utilization, prefix-cache effectiveness."""
        kv = self.scheduler.kv
        used = kv.num_pages - kv.num_free_pages
        snap = self.step_metrics.snapshot()
        snap["scheduler"] = {
            "waiting": len(self.scheduler.waiting),
            "running": len(self.scheduler.running),
            "preemptions": getattr(self.scheduler, "num_preemptions", 0),
            "rejections": getattr(self.scheduler, "num_rejections", 0),
        }
        # serving-curve observability: per-tenant queue depth + the
        # admission-control shed ledger (docs/load_testing.md)
        snap["queue"] = {
            "depth_by_tenant": self.scheduler.queue_depth_by_tenant(),
        }
        snap["shed"] = {
            f"{reason}/{tenant}": n
            for (reason, tenant), n in sorted(
                self.scheduler.shed_counts.items())
        }
        # weighted-fair queueing deferral ledger (only the AR
        # scheduler keeps one; wfq_deferred_requests_total on /metrics)
        wfq = getattr(self.scheduler, "wfq_deferred", None)
        if wfq:
            snap["wfq"] = {"deferred_by_tenant": dict(wfq)}
        snap["kv"] = {
            "pages_total": kv.num_pages,
            "pages_used": used,
            "utilization": round(used / kv.num_pages, 4),
            # resident layout label + amortized HBM cost per cached
            # token (all layers) — the capacity story the int8 pool
            # exists for (docs/performance.md)
            "cache_dtype": getattr(
                self.runner, "kv_cache_dtype",
                str(jnp.dtype(self.config.dtype))),
            "bytes_per_token": self._kv_bytes_per_token,
        }
        if self._kv_layout is not None:
            # what a page holds: "kv" (a K and a V pool of per-head rows)
            # or "latent" (one shared row a token)
            layout = self._kv_layout
            snap["kv"].update(
                layout=layout.kind,
                row_width=layout.width,
                row_used=layout.used,
                bytes_per_token_layer=self._kv_bytes_per_token_layer)
        if self._kv_kinds:
            # the layout a kind of layer: rows, index keys, bytes, pages
            snap["kv"]["kinds"] = self._kv_kinds
        if getattr(kv, "window", 0):
            # the second kind of page; the ``kv_window_pages_used``
            # histogram rides step_metrics' snapshot
            snap["window"] = {
                "window": kv.window,
                "pages_total": kv.num_window_pages,
                "pages_in_use": kv.window_pages_in_use,
                "pages_high_water": kv.window_pages_high_water,
                "pages_freed_total": kv.window_pages_freed,
                "prefix_hits_refused_total": kv.window_prefix_hits_refused,
            }
        snap["prefix_cache"] = self.prefix_cache_stats
        if getattr(kv, "num_state_slots", 0):
            # the second kind of cache (docs/kv_cache.md); the
            # ``state_slots`` histogram rides step_metrics' snapshot
            snap["state"] = {
                "slots_total": kv.num_state_slots,
                "slots_in_use": kv.state_slots_in_use,
                "state_slot_allocs_total": kv.state_slot_allocs,
                "state_resets_total": getattr(
                    self.scheduler, "num_state_resets", 0),
                "prefix_hits_refused_total": kv.prefix_hits_refused,
                # float32 bytes a sequence holds in all the layers that
                # carry a state, and in the largest of them (beside
                # ``kv.bytes_per_token``)
                "bytes_per_slot": getattr(
                    self, "_state_bytes_per_slot", None),
                "bytes_per_slot_layer": getattr(
                    self, "_state_bytes_per_slot_layer", None),
            }
        if self.kv_tiers is not None:
            st = self.kv_tiers.stats()
            snap["kv_tiers"] = {
                # pages holding live KV on the device (tables + hot
                # cache nodes) vs. payload entries parked per cold tier
                "hbm_pages": kv.num_pages - len(kv._free),
                "host_pages": st["host_entries"],
                "remote_pages": st["remote_entries"],
                "host_bytes": st["host_bytes"],
                "bytes_moved": st["bytes_moved"],
                "prefix_hit_tokens": kv.prefix_hit_tokens,
                "restored_tokens": kv.restored_tokens,
                "parked_tokens": kv.parked_tokens,
                "offload_evictions": kv.offload_evictions,
            }
        # the executables by name (engine_compile_seconds_total{part})
        snap["compile"] = self.runner.compile_stats.snapshot()
        # the host's phases, stalls and collections, lifetime totals
        # (engine_host_phase_ms_total{phase}, engine_stalls_total,
        # engine_stall_ms_total on /metrics)
        snap.update(self.phases.snapshot())
        if self.roofline is not None:
            # rolling-window MFU/MBU (engine_step_mfu /
            # engine_step_mbu{phase}); the per-step series rides the
            # flight recorder and /debug/engine
            rf = self.roofline.snapshot(recent=0)
            snap["roofline"] = {"mfu": rf["mfu"], "mbu": rf["mbu"],
                                "window_steps": rf["window_steps"]}
        if self.config.async_scheduling:
            snap["async_fallback"] = dict(self.async_fallback)
            snap["pipeline"] = {
                "pipelined_steps": self.steps_by_path["pipelined"],
                "sync_steps": self.steps_by_path["sync"],
                "drains": self.pipeline_drains,
            }
        # per-tenant heavy-hitter boards (metrics/attribution.py):
        # top-k per meter, inside the tenant-cardinality budget
        snap["attribution"] = self.attribution.snapshot()
        # device-memory ledger: per-component live/peak bytes
        # (device_memory_bytes{component} on /metrics; refresh is a
        # cold-path metadata walk + optional allocator probe)
        snap["device_memory"] = self.memory.refresh()
        return snap

    def step(self) -> list[OmniRequestOutput]:
        """One engine step.  The step and its host phases (``omni.*``,
        docs/observability.md) are annotations in the profiler's own
        trace, where they share a clock with the device's operations
        (with no profiler session each is a flag test), and phases of
        the engine's own clock, which keeps their time on every step."""
        with self.phases.phase("omni.step",
                               step_num=self._steps_completed):
            return self._step()

    def _step(self) -> list[OmniRequestOutput]:
        # deterministic stall injection for the watchdog/debugz tests
        # (resilience/faults.py site "step": delay_ms stalls every step,
        # fail_step raises into the caller) — one dict lookup when no
        # fault plan is installed
        fault_point("step")
        self.phases.mark()
        # deadline sweep BEFORE scheduling: expired requests become
        # deadline_exceeded outputs this very step instead of consuming
        # another forward (resilience/deadline.py)
        with self.phases.phase("omni.schedule"):
            self.scheduler.expire_deadlines()
            # surface intake-rejected requests as errored outputs instead
            # of silently dropping them
            errored_reqs = self.scheduler.drain_errored()
        for r in errored_reqs:
            self._req_lat.pop(r.request_id, None)
            self._trace_started.discard(r.request_id)
            if (r.additional_information.get("error_kind")
                    == "deadline_exceeded"):
                from vllm_omni_tpu.resilience.metrics import (
                    resilience_metrics,
                )

                resilience_metrics.inc("deadline_exceeded_total",
                                       stage=self.stage_id)
        errored = [OmniRequestOutput.from_pipeline(r)
                   for r in errored_reqs]
        if self.config.async_scheduling:
            outs = errored + self._step_async()
        else:
            with self.phases.phase("omni.schedule"):
                sched_out = self.scheduler.schedule()
            outs = errored + self._run_scheduled(sched_out)
        # counted at COMPLETION: a step wedged mid-flight never
        # advances the watchdog's progress signal
        self._steps_completed += 1
        return outs

    # ------------------------------------------------ async pipelined step
    def _note_fallback(self, reason: str) -> None:
        self.async_fallback[reason] = self.async_fallback.get(
            reason, 0) + 1

    def _step_async(self) -> list[OmniRequestOutput]:
        """Two-slot pipelined step: dispatch step N BEFORE retiring step
        N-1 — the device starts computing N while the host does N-1's
        token readback, stop checks, and bookkeeping, plus (on the next
        call) N+1's scheduling.  Since PR 11 EVERY batch shape rides the
        pipeline (mixed prefill+decode, spec verify, logprobs,
        collect_hidden, embeds — the unified executable serves them
        all); only host-synchronous KV movement (cross-stage transfer,
        tier offload drains) and streaming chunk intake drain to the
        synchronous path, counted per reason in ``async_fallback``."""
        ready, reason = self._pipeline_ready()
        if ready:
            with self.phases.phase("omni.schedule"):
                sched_out = self.scheduler.schedule()
                self.step_metrics.on_schedule(
                    waiting=len(self.scheduler.waiting),
                    running=len(self.scheduler.running),
                )
            if sched_out.num_scheduled == 0 and self._inflight is not None:
                # pipeline bubble: everything schedulable is waiting on
                # the in-flight retire (e.g. a spec verify's accept
                # count pins the request's next KV position) — retire
                # now; the freed knowledge schedules next step
                return self._drain_pipeline()
            if self._pipeline_eligible(sched_out):
                return self._step_pipelined(sched_out)
            # scheduled but not dispatchable (e.g. page pressure
            # preempted the whole batch, or the reshaped batch fell off
            # the unified fast path): drain the pipeline, drop requests
            # the retire just finished from the stale schedule, and run
            # the remainder synchronously
            self._note_fallback("reshaped")
            outs = self._drain_pipeline()
            drop = lambda ss: [  # noqa: E731
                s for s in ss
                if not s.request.is_finished
                and s.request.status is RequestStatus.RUNNING
            ]
            sched_out.decodes = drop(sched_out.decodes)
            sched_out.prefills = drop(sched_out.prefills)
            return outs + self._run_scheduled(
                sched_out, skip_on_schedule=True, fallback="reshaped")
        # fallback step (prefills / spec / logprobs / streaming / ...):
        # retire FIRST so scheduling sees post-retire state and decode
        # inputs are host-visible for the synchronous runner
        if reason is not None:
            self._note_fallback(reason)
        outs = self._drain_pipeline()
        with self.phases.phase("omni.schedule"):
            sched_out = self.scheduler.schedule()
        return outs + self._run_scheduled(sched_out, fallback=reason)

    def _pipeline_ready(self) -> "tuple[bool, Optional[str]]":
        """Cheap pre-schedule check: can the NEXT step be dispatched
        ahead of token knowledge?  Since PR 11 the list of drain
        reasons is exactly the host-synchronous ones — KV movement and
        streaming chunk intake; spec/logprobs/collect_hidden/embeds
        batches pipeline through the unified dispatch and CANNOT
        produce a fallback (docs/async_engine.md).  Returns (ready,
        fallback_reason) — reason is None when there is simply nothing
        to dispatch."""
        s = self.scheduler
        if not s.running and not s.waiting:
            return False, None  # idle: nothing to pipeline
        if self.config.kv_transfer is not None or s._pending_kv_transfers:
            return False, "kv_transfer"
        if self.kv_tiers is not None and (
                s.kv.has_pending_moves()
                or any(r.additional_information.get("_parked_len")
                       for r in s.waiting)):
            # tier moves are host-synchronous (batched extract/inject
            # between schedule and execute): run those steps sync
            return False, "kv_offload"
        for r in list(s.running) + list(s.waiting):
            if r.awaiting_chunks:
                # chunk intake mutates the prompt between steps — the
                # one remaining host-state hazard
                return False, "streaming"
        if not s.running_rows_fit():
            # the coming schedule may preempt: it runs with every token
            # retired, so that a victim loses no token it had sampled
            return False, "kv_pressure"
        return True, None

    def _pipeline_eligible(self, sched_out: SchedulerOutput) -> bool:
        """Post-schedule check on the actual output (preemption may have
        reshaped it): every decode input token either host-visible or
        device-resident in the in-flight handle, no KV movement queued
        by this very schedule, and the batch packs into ONE unified
        group (multi-group steps exist only under the one-shot
        generation scheduler, which is never async)."""
        if not sched_out.decodes and not sched_out.prefills:
            return False
        if sched_out.kv_transfer_requests:
            return False
        if self.kv_tiers is not None \
                and self.scheduler.kv.has_pending_moves():
            # this very schedule() queued tier moves (eviction offload
            # or a cold-prefix restore): they must drain before the
            # forward runs, so the step goes synchronous
            return False
        prev = self._inflight
        for s in sched_out.decodes:
            if s.start_pos >= s.request.num_tokens and (
                    prev is None
                    or s.request.request_id not in prev.handle.rows):
                return False
        if not self.runner._plain_decode_only(sched_out) \
                and not self.runner.fits_unified(sched_out):
            return False
        return True

    def _step_pipelined(self, sched_out: SchedulerOutput
                        ) -> list[OmniRequestOutput]:
        prev = self._inflight
        scheduled = sched_out.prefills + sched_out.decodes
        with self.phases.phase("omni.record"):
            self._note_first_scheduled(scheduled)
            self._observe_saturation(sched_out)
        t_d0 = self.phases.last_ns
        u0, p0 = self._padding_totals()
        if self.runner._plain_decode_only(sched_out):
            handle = self.runner.dispatch_decode(
                sched_out.decodes,
                prev.handle if prev is not None else None,
            )
        else:
            # unified dispatch: prefill chunks, spec verify rows,
            # logprobs, and embeds batches pipeline too
            handle = self.runner.dispatch_unified(
                sched_out, prev.handle if prev is not None else None)
        with self.phases.phase("omni.record"):
            # schedule-ahead accounting: the dispatched rows' tokens are
            # now in flight; the next schedule() counts them without seeing
            # their values
            self.scheduler.note_async_dispatch(sched_out)
            self._observe_padding(u0, p0)
            self._record_spans(self._traced_rows(scheduled), "dispatch",
                               t_d0, lambda s: {"batch": len(scheduled)})
        self._inflight = _InflightStep(sched_out=sched_out, handle=handle)
        outs: list[OmniRequestOutput] = []
        new_total = 0
        if prev is not None:
            # step N-1's host work, overlapped with step N's compute
            outs, new_total = self._retire_step(prev)
            if not self.scheduler.has_unfinished:
                # the step just dispatched is pure overshoot (every
                # request finished at this retire): drain it now instead
                # of dangling device buffers + finished requests until
                # the next traffic burst
                outs += self._drain_pipeline()
        with self.phases.phase("omni.record"):
            # with a predecessor in flight, schedule+dispatch overlapped ITS
            # compute and the retire's post-wait work overlaps the step just
            # dispatched — the only unoverlapped host time is the wait
            self._close_step("pipelined", sched_out, scheduled, new_total,
                             overlapped=prev is not None)
            return outs

    @staticmethod
    def _traced_rows(scheds) -> list:
        """The rows of a step whose request carries a trace context, each
        with it.  Walked once a step; the loops that record a journey
        span a row are entered only for these (``rec.record(None, ..)``
        returns at once, but at 64 rows a step the calls alone cost tens
        of microseconds)."""
        return [(s, ctx) for s in scheds
                if (ctx := s.request.additional_information.get("trace"))]

    def _record_spans(self, traced: list, name: str, t0_ns: int,
                      args_of) -> None:
        """One journey span a traced row, from the clock's reading
        ``t0_ns`` to its last stamp (the entry of the phase the caller
        is in, or the exit of the one before)."""
        if not traced:
            return
        rec = get_recorder()
        dur_s = (self.phases.last_ns - t0_ns) / 1e9
        # span starts stay wall-clock (timelines align on wall stamps)
        start_w = time.time() - (self.phases.now_ns() - t0_ns) / 1e9
        for s, ctx in traced:
            rec.record(ctx, name, start_w, dur_s, stage_id=self.stage_id,
                       args=args_of(s), **self.span_tags)

    def _close_step(self, path: str, sched_out: SchedulerOutput, scheduled,
                    new_total: int, overlapped: bool = False,
                    fallback: Optional[str] = None) -> None:
        """The step's time, read off the clock at the entry of the
        ``omni.record`` the caller is in: ``device_ms`` is what the
        engine thread spent waiting on the device (``omni.dispatch`` and
        ``omni.readback`` up to the return of ``device_get``, a drained
        predecessor's included), ``host_ms`` the rest of the step."""
        took = self.phases.since_mark(
            step=self._steps_completed,
            waiting=len(self.scheduler.waiting),
            running=len(self.scheduler.running))
        self.steps_by_path[path] += 1
        device_ms = took["device_ms"]
        host_ms = max(took["step_ms"] - device_ms, 0.0)
        self.step_metrics.on_step(
            step_ms=host_ms + device_ms, new_tokens=new_total,
            prefill_tokens=_prompt_tokens_computed(sched_out),
            host_ms=host_ms, device_ms=device_ms,
            overlapped_host_ms=host_ms if overlapped else 0.0,
        )
        self._record_step(path, sched_out, scheduled, new_total,
                          host_ms=host_ms, device_ms=device_ms,
                          fallback=fallback, phases_ms=took["phases_ms"],
                          cpu_ms=took["cpu_ms"])

    def _consolidate_hidden(self, finished) -> None:
        """Fold per-step hidden chunks into the next-stage payload
        (reference pooler_output routing, engine/output_processor.py:246)
        — shared by the sync step and the async lagged retire, which
        both finish requests."""
        if not self.config.collect_hidden:
            return
        import numpy as np

        for r in finished:
            chunks = r.additional_information.pop("_hidden_chunks", None)
            if chunks:
                r.multimodal_output["hidden_states"] = np.concatenate(
                    chunks, axis=0
                )

    def _retire_step(self, inflight: _InflightStep):
        """Retire a dispatched step: the single lagged device_get, then
        token append / stop checks / latency bookkeeping.  Returns
        (outputs, new_tokens); what the get waited on the device is on
        the clock (``omni.readback``)."""
        t_g0 = self.phases.last_ns
        sampled = self.runner.retire_step(inflight.handle)
        with self.phases.phase("omni.update"):
            finished = self.scheduler.update_from_async_retire(
                inflight.sched_out, sampled)
            self._consolidate_hidden(finished)
            scheds = (inflight.sched_out.prefills
                      + inflight.sched_out.decodes)
            # only requests that could have appended a token this retire:
            # an overshoot row for a request that finished at the PREVIOUS
            # retire (or was aborted/expired mid-flight) already had its
            # latency entry popped — setdefault would resurrect it with a
            # zero token count, re-counting the whole stream into
            # tokens_generated/TTFT and leaking the entry forever
            just_finished = {r.request_id for r in finished}
            live = [s for s in scheds
                    if not s.request.is_finished
                    or s.request.request_id in just_finished]
            new_total = self._observe_token_latencies(live, finished)
        with self.phases.phase("omni.record"):
            self._record_spans(self._traced_rows(scheds), "retire", t_g0,
                               lambda s: {"batch": len(scheds)})
        outs = step_outputs(live, finished)
        return outs, new_total

    def _drain_pipeline(self) -> list[OmniRequestOutput]:
        """Retire the in-flight step (if any) so the host state is fully
        caught up before a synchronous step runs.  Its wait on the
        device is on the clock, so it lands in the device time of the
        step that drained it and the host/device breakdown stays honest
        across pipeline-to-sync transitions."""
        if self._inflight is None:
            return []
        inflight, self._inflight = self._inflight, None
        self.pipeline_drains += 1
        outs, new_total = self._retire_step(inflight)
        # the drained step has no on_step of its own (the sync step that
        # follows records this call's single on_step, and its per-request
        # deltas were already consumed here): credit the tokens directly
        # so throughput counters stay exact
        self.step_metrics.tokens_generated += new_total
        return outs

    # ------------------------------------------------------ kv tier moves
    def _drain_kv_moves(self) -> set[str]:
        """Drain the KV manager's queued tier moves between schedule()
        and execute(): batched extraction of evicted/parked pages (ONE
        pytree transfer for every payload this step), then injection of
        queued restores (per-request contiguous runs, one transfer
        each).  Extractions run FIRST — a page reclaimed by eviction
        may be the very page a restore was just given.  Returns the
        request_ids whose restore came up short (payload vanished
        between match and fetch); the caller must drop their scheds
        from this step before executing."""
        kv = self.scheduler.kv
        self._last_kv_moves = (0, 0)
        if self.kv_tiers is None or not kv.has_pending_moves():
            return set()
        offloads, restores = kv.take_pending_moves()
        self._last_kv_moves = (len(offloads), len(restores))
        failed: set[str] = set()
        if offloads:
            payloads = self.runner.extract_kv_batch(
                [(o.pages, o.n_tokens) for o in offloads])
            for o, payload in zip(offloads, payloads):
                self.kv_tiers.put(o.key, payload)
                kv.note_park_extracted(o.key)
        by_req: dict[str, list] = {}
        for r in restores:
            by_req.setdefault(r.request_id, []).append(r)
        for rid, entries in by_req.items():
            t0 = time.perf_counter()
            pages: list[int] = []
            parts: list[list] = []
            keep_tokens = 0
            fail_at: Optional[int] = None
            for i, e in enumerate(entries):
                payload = self.kv_tiers.fetch(e.key)
                if payload is None:
                    fail_at = i
                    # the contiguous valid prefix ends where the failed
                    # payload would have STARTED (cold entries can
                    # interleave with already-hot pages, so a sum of
                    # injected lengths would overshoot)
                    keep_tokens = e.start_tokens
                    break
                pages.extend(e.pages)
                parts.append(payload)
                keep_tokens = e.start_tokens + e.n_tokens
                if e.drop_after:
                    self.kv_tiers.drop(e.key)
            if parts:
                if len(parts) == 1:
                    payload = parts[0]
                else:
                    # format-agnostic stitch (kvcache/quant.py): dense
                    # parts concat on the token axis; quantized parts
                    # concat data + per-page scales (radix node runs
                    # are page-aligned, so scales never split a page)
                    payload = concat_payloads(
                        parts, self.config.page_size)
                self.runner.inject_kv(pages, payload)
                self.kv_tiers.restored_tokens += sum(
                    e.n_tokens for e in entries[:len(parts)])
                self.step_metrics.kv_restore_s.observe(
                    time.perf_counter() - t0)
            if fail_at is not None:
                unwound = entries[fail_at:]
                kv.restored_tokens -= sum(e.n_tokens for e in unwound)
                # restore_failed also truncates any request that
                # co-adopted a failed node hot in the same pass — its
                # scheds are misaligned too and must drop with ours
                failed |= self.scheduler.restore_failed(
                    rid, unwound, keep_tokens)
                failed.add(rid)
        return failed

    # --------------------------------------------------- synchronous step
    def _run_scheduled(self, sched_out: SchedulerOutput,
                       skip_on_schedule: bool = False,
                       fallback: Optional[str] = None
                       ) -> list[OmniRequestOutput]:
        with self.phases.phase("omni.kv_moves"):
            failed_restores = self._drain_kv_moves()
        if failed_restores:
            # a restore came up short: this step's chunks for those
            # requests are positionally misaligned (start_pos past the
            # rewound num_computed_tokens) — drop them; the scheduler
            # re-chunks the remainder next step
            sched_out.prefills = [
                s for s in sched_out.prefills
                if s.request.request_id not in failed_restores]
            sched_out.decodes = [
                s for s in sched_out.decodes
                if s.request.request_id not in failed_restores]
            if sched_out.num_scheduled == 0:
                # everything scheduled this step was a casualty: the
                # rewound requests are RUNNING and re-chunk next step —
                # don't fall through to the starvation/deadlock checks
                return []
        if not skip_on_schedule:
            self.step_metrics.on_schedule(
                waiting=len(self.scheduler.waiting),
                running=len(self.scheduler.running),
            )
        if sched_out.num_scheduled == 0:
            if self.scheduler.waiting:
                if any(r.awaiting_chunks for r in self.scheduler.running):
                    # an idle streaming request makes zero-scheduled ticks
                    # a NORMAL long-lived state (upstream may be slow) —
                    # the tick counter would error-finish healthy waiting
                    # requests within milliseconds
                    self._starved_ticks = 0
                    return []
                # Transient zero-scheduled ticks happen while pages are
                # pinned by an in-flight KV-transfer awaiting its ACK —
                # only declare starvation after a few consecutive ticks.
                self._starved_ticks += 1
                if self._starved_ticks < 3:
                    return []
                self._starved_ticks = 0
                # Starved: the head waiting request can never fit (e.g. its
                # recompute footprint outgrew the pool). Error-finish it so
                # one bad request can't wedge the whole engine.
                victim = self.scheduler.waiting.pop(0)
                self._req_lat.pop(victim.request_id, None)
                self._trace_started.discard(victim.request_id)
                # error-finished outside scheduler.reject(): count it so
                # rejections_total covers starvation too
                self.scheduler.num_rejections += 1
                victim.status = RequestStatus.FINISHED_ERROR
                victim.additional_information.setdefault(
                    "error",
                    "request starved: does not fit in the KV cache "
                    f"({self.scheduler.kv.num_free_pages} pages free)",
                )
                # an injected-KV request may already own prefix pages
                # while WAITING — evicting without freeing would leak
                # them; a parked payload of the dead request likewise
                self.scheduler.kv.free(victim)
                self.scheduler.kv.drop_park(victim)
                return [OmniRequestOutput.from_pipeline(victim)]
            stalled = [
                r for r in self.scheduler.running
                if not (r.awaiting_chunks
                        and r.num_computed_tokens >= r.num_tokens)
            ]
            if stalled or self.scheduler.waiting:
                raise RuntimeError(
                    "scheduler deadlock: running requests but nothing "
                    "schedulable"
                )
            # only streaming requests idling for their next chunk remain
            return []
        self._starved_ticks = 0
        scheduled = sched_out.prefills + sched_out.decodes
        with self.phases.phase("omni.record"):
            self._note_first_scheduled(scheduled)
            self._observe_saturation(sched_out)
        # the clock's stamps bound the intervals the journey spans
        # report: no timer of the step's own
        t_ex0 = self.phases.last_ns
        u0, p0 = self._padding_totals()
        run_out = self.runner.execute(
            sched_out, extract_kv=self.kv_transfer_sink is not None
        )
        with self.phases.phase("omni.record"):
            self._observe_padding(u0, p0)
            traced_p = self._traced_rows(sched_out.prefills)
            traced_d = self._traced_rows(sched_out.decodes)
            self._record_spans(
                traced_p, "prefill", t_ex0,
                lambda s: {"tokens": s.num_new_tokens,
                           "start_pos": s.start_pos})
            self._record_spans(
                traced_d, "decode", t_ex0,
                lambda s: {"tokens": s.num_new_tokens})
            if self.kv_transfer_sink is not None:
                for req, _, _ in sched_out.kv_transfer_requests:
                    payload = run_out.extracted_kv.get(req.request_id)
                    if payload is not None:
                        self.kv_transfer_sink(req, payload)
        t_up0 = self.phases.last_ns
        with self.phases.phase("omni.update"):
            finished = self.scheduler.update_from_output(
                sched_out, run_out.sampled, run_out.kv_extracted_req_ids
            )
            new_total = self._observe_token_latencies(scheduled, finished)
        with self.phases.phase("omni.record"):
            self._record_spans(traced_p + traced_d, "sampling", t_up0,
                               lambda s: {"batch": len(scheduled)})
            # execute() syncs internally: no host work overlaps the wait
            self._close_step("sync", sched_out, scheduled, new_total,
                             fallback=fallback)
            self._consolidate_hidden(finished)
            if not self.scheduler.has_unfinished:
                # no further step will run: drain transfers triggered
                # just now so finished requests still ship their KV
                for req, block_ids, seq_len in \
                        self.scheduler.drain_pending_kv_transfers():
                    if self.kv_transfer_sink is not None:
                        payload = self.runner.extract_kv(block_ids, seq_len)
                        self.kv_transfer_sink(req, payload)
                    self.scheduler.update_from_output(
                        SchedulerOutput(), {}, {req.request_id})
            return step_outputs(scheduled, finished)

    def _observe_token_latencies(self, scheduled, finished) -> int:
        """TTFT / ITL / TPOT bookkeeping from the host-visible token
        deltas (shared by the sync step and the async lagged retire);
        returns the number of new tokens observed.  All durations are
        monotonic-to-monotonic (``Request.arrival_mono``) — a wall
        clock stepped by NTP mid-request must never corrupt the
        latency histograms or the SLO verdicts built on them."""
        now = time.monotonic()
        sm = self.step_metrics
        new_total = 0
        for s in scheduled:
            req = s.request
            n_out = len(req.output_token_ids)
            # [first_token_mono, last_token_mono, tokens_seen, ttft_ms]
            st = self._req_lat.setdefault(req.request_id,
                                          [0.0, 0.0, 0, None])
            if n_out <= st[2]:
                continue
            new = n_out - st[2]
            new_total += new
            if st[2] == 0:
                if req.arrival_mono:
                    st[3] = (now - req.arrival_mono) * 1e3
                    sm.ttft_ms.observe(st[3])
                st[0] = now
                new -= 1  # the first token is TTFT, not an ITL
            if new > 0 and st[1]:
                # a speculative step emits several tokens in one host
                # round trip: amortize the gap over them
                sm.itl_ms.observe((now - st[1]) * 1e3 / new, n=new)
            st[1] = now
            st[2] = n_out
        for req in finished:
            st = self._req_lat.pop(req.request_id, None)
            self._trace_started.discard(req.request_id)
            n_out = len(req.output_token_ids)
            tpot = None
            if st and st[0] and n_out > 1:
                tpot = (now - st[0]) * 1e3 / (n_out - 1)
                sm.tpot_ms.observe(tpot)
            # SLO verdict per finished request (per-tenant attainment +
            # goodput): TTFT unknown (e.g. a request that finished on
            # its first observed token batch before a TTFT stamp
            # existed) judges as inf against a configured target
            if st is not None:
                ttft = st[3] if st[3] is not None else (
                    float("inf") if sm.slo_ttft_ms is not None else 0.0)
                sm.on_request_slo(req.tenant, ttft, tpot, n_out)
            # heavy-hitter token attribution, metered at finish (one
            # sketch update per request, not per token)
            self.attribution.add(req.tenant, "prefill_tokens",
                                 req.num_prompt_tokens)
            self.attribution.add(req.tenant, "decode_tokens", n_out)
        # KV occupancy attribution: fold the manager's host-int
        # interval clock into the sketch (engine thread — the KV
        # manager is single-threaded by contract)
        drained = self.scheduler.kv.drain_page_seconds()
        for tier, by_tenant in drained.items():
            for tenant, secs in by_tenant.items():
                self.attribution.add(tenant, f"kv_page_seconds_{tier}",
                                     secs)
        return new_total

    # ---------------------------------------------------------- generate()
    def generate(
        self,
        prompts_token_ids: list[list[int]],
        sampling_params: Optional[SamplingParams | list[SamplingParams]] = None,
    ) -> list[OmniRequestOutput]:
        """Blocking batch generate — the reference's OmniLLM._run_engine
        step loop (reference: entrypoints/omni_llm.py:199-241)."""
        if isinstance(sampling_params, list):
            if len(sampling_params) != len(prompts_token_ids):
                raise ValueError(
                    f"sampling_params length {len(sampling_params)} != "
                    f"prompts length {len(prompts_token_ids)}"
                )
            params_list = sampling_params
        else:
            params_list = [sampling_params] * len(prompts_token_ids)
        order = {}
        for toks, sp in zip(prompts_token_ids, params_list):
            rid = self.add_request(toks, sp)
            order[rid] = len(order)
        results: dict[str, OmniRequestOutput] = {}
        while self.has_unfinished_requests:
            for out in self.step():
                results[out.request_id] = out
        # requests rejected at intake when no step ran afterwards
        for req in self.scheduler.drain_errored():
            out = OmniRequestOutput.from_pipeline(req)
            results[out.request_id] = out
        return [results[rid] for rid in
                sorted(results, key=lambda r: order.get(r, 1 << 30))]

    # (last in the file so that no line above it moved: the persistent
    # compile cache's key holds the source positions of what is traced)
    @property
    def work_steps(self) -> int:
        """Steps that did device work, lifetime: one a flight record (a
        step dispatched or run) and one a predecessor retired without a
        record of its own (``_drain_pipeline``).  A zero-scheduled tick
        moves ``_steps_completed`` and not this: the orchestrator's loop
        waits after a turn in which it stood still (OmniStage.poll)."""
        return self.flight.total_steps + self.pipeline_drains


# (imported after the file's last traced line, for the same reason: what
# a step hands over, partial outputs for streaming requests included)
from vllm_omni_tpu.engine.stream import step_outputs  # noqa: E402
