"""AR model runner: every step is ONE ragged dispatch.

TPU-native counterpart of the reference's GPUARModelRunner (reference:
worker/gpu_ar_model_runner.py:59).  Where the CUDA runner manages
CUDA-graph capture + padded dispatch (:180-205), this runner packs every
scheduled batch onto a flat token axis and launches ONE token-packed
executable per step (ops/ragged_paged_attention.py) — the split
bucketed-jit executor (fresh prefill / chunked continuation / decode /
spec verify as separately padded launches, deleted in PR 11) survives
only as the dedicated [B]-row executable for pure single-token decode
batches, where one row per sequence beats token-block alignment.

Everything the split path used to drain the async pipeline for now
rides the unified dispatch ON DEVICE:

- speculative verify: a k+1-token ragged row; accept-mask + rejection
  sampling run in the executable (sample/sampler.py
  ``spec_verify_tokens``) — no per-verify-step ``device_get``
- logprobs: chosen + top-k log-softmax computed in the step and carried
  on the in-flight handle to the one lagged retire
- collect_hidden: the packed hidden state rides the handle; per-request
  rows are sliced host-side after the single retire transfer
- embeds/deepstack inputs: scattered onto the packed token axis and fed
  through ``forward_unified``

Responsibilities (mirroring :90-396 / :398-588):
- assemble packed device inputs from ``SchedulerOutput``
- run the jitted unified / decode steps with donated KV caches
- sample next tokens ON DEVICE (sample/sampler.py)
- extract KV pages for cross-stage transfer and ACK them
  (device half of OmniKVTransferManager, reference:
  distributed/omni_connectors/kv_transfer_manager.py:47)
"""

from __future__ import annotations

import functools
import secrets
import zlib
from dataclasses import dataclass, field
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from vllm_omni_tpu.core.scheduler import (
    PackingContract,
    ScheduledRequest,
    SchedulerOutput,
    packed_rows_needed,
)
from vllm_omni_tpu.introspection.phase_clock import CompileStats, PhaseClock
from vllm_omni_tpu.kvcache import layouts as cache_layouts
from vllm_omni_tpu.kvcache.quant import (
    dequantize_payload,
    is_quant_payload,
    payload_seq_len,
)
from vllm_omni_tpu.logger import init_logger
from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.ops import _dispatch
from vllm_omni_tpu.ops.autotune import auto_ragged_blocks
from vllm_omni_tpu.ops.grouped_matmul import kernel_calls
from vllm_omni_tpu.ops.paged_attention import (
    init_kv_cache,
    row_tiling_reason,
    shared_tiling_reason,
    write_kv_cache,
)
from vllm_omni_tpu.ops.ragged_paged_attention import (
    DEFAULT_TOKEN_BLOCK,
    align_to_block,
)
from vllm_omni_tpu.sample.sampler import (
    SamplingTensors,
    compute_logprobs,
    sample_tokens,
    spec_verify_tokens,
)
from vllm_omni_tpu.sampling_params import SamplingParams

logger = init_logger(__name__)

#: top-k width of the on-device logprob computation — the OpenAI API
#: caps requests at 20, so one static width serves every request and
#: the host trims per-request (a per-k executable would be a shape per
#: distinct logprobs value)
LOGPROBS_K = 20


@jax.jit
def _take_rows(tokens: jax.Array, cols: jax.Array) -> jax.Array:
    """Rows ``cols[1]`` ([S]) of an in-flight step's tokens ([b]): one
    program a batch bucket, however many rows are fed."""
    return tokens[cols[1]]


@jax.jit
def _put_rows(token_ids: jax.Array, cols: jax.Array,
              picked: jax.Array) -> jax.Array:
    """``token_ids`` ([n]) with ``picked`` ([S]) written at ``cols[0]``
    ([S]); an entry of ``n`` there is past the end and dropped: one
    program a bucket of ``n``."""
    return token_ids.at[cols[0]].set(picked, mode="drop")


def _bucket(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"size {n} exceeds largest bucket {buckets[-1]}")


def _bucketed_prefill_shapes(prefill_shapes, batch_buckets,
                             seq_buckets) -> list[tuple[int, int]]:
    """Expand declared (batch, seq_len) traffic shapes into the bucketed
    (b, s) set to warm: every batch bucket up to the declared batch (the
    scheduler admits whatever arrived, so smaller waves bucket lower),
    seq clamped to its bucket.  Kept for the generation runner's padded
    precompile; the AR runner's unified warmup walks token buckets."""
    todo = set()
    for raw_b, raw_s in prefill_shapes:
        b_top = _bucket(min(raw_b, batch_buckets[-1]), batch_buckets)
        s = _bucket(min(raw_s, seq_buckets[-1]), seq_buckets)
        todo.update((b, s) for b in batch_buckets if b <= b_top)
    return sorted(todo)


def _make_buckets(start: int, limit: int) -> tuple[int, ...]:
    """Powers of two from ``start`` up to (and covering) ``limit``."""
    buckets = []
    b = start
    while b < limit:
        buckets.append(b)
        b *= 2
    buckets.append(limit)
    return tuple(buckets)


@dataclass
class RunnerOutput:
    # request_id -> sampled token (only for requests that reached
    # sampling); a spec-decode verify step stores the LIST of accepted
    # tokens instead of a single int
    sampled: dict[str, "int | list[int]"] = field(default_factory=dict)
    # request_id -> extracted KV payload (per-layer (k, v) numpy arrays)
    extracted_kv: dict[str, list] = field(default_factory=dict)
    kv_extracted_req_ids: set[str] = field(default_factory=set)


class UnifiedBatch(NamedTuple):
    """Host-assembled device inputs for one token-packed unified step
    (the layout contract of ops/ragged_paged_attention.py)."""

    token_ids: np.ndarray   # [T_pad]
    positions: np.ndarray   # [T_pad] ([3, T_pad] under mrope)
    slots: np.ndarray       # [T_pad] flat KV slots (-1 padding)
    tables: np.ndarray      # [S_max, max_pages]
    seq_lens: np.ndarray    # [S_max]
    cu_q_lens: np.ndarray   # [S_max + 1] aligned segment starts
    q_lens: np.ndarray      # [S_max]
    last_idx: np.ndarray    # [S_max] packed row of each seq's last token
    t_pad: int              # token bucket the batch padded to
    total: int              # aligned rows actually occupied
    verify_idx: np.ndarray  # [S_max, V] packed rows of candidate logits
    n_cand: np.ndarray      # [S_max] candidates per row (1 = plain)
    drafts: np.ndarray      # [S_max, V-1] draft token ids (0-padded)
    embeds: Optional[np.ndarray] = None       # [T_pad, W]
    embeds_mask: Optional[np.ndarray] = None  # [T_pad]
    deepstack: Optional[np.ndarray] = None    # [n_deep, T_pad, H]


@dataclass
class InflightDecode:
    """Handle for a dispatched-but-not-retired step (decode or unified).

    ``tokens`` stays DEVICE-resident: the next dispatch gathers its
    input tokens straight from it (no host round trip) — for a unified
    handle it is each row's LAST ACCEPTED token, so a spec verify row
    feeds the following step exactly like a plain decode row.  The
    engine retires the handle one step later with the single lagged
    ``device_get`` of ``outs`` (the async pipeline's whole point — host
    readback leaves the critical path)."""

    tokens: jax.Array                 # [rows] i32, on device
    rows: dict[str, int]              # request_id -> row index
    outs: Any = None                  # device output pytree of the step
    kind: str = "decode"              # "decode" | "unified"
    scheds: list = field(default_factory=list)  # row-ordered scheds
    # per-row (async_generation at dispatch) — retire skips side
    # effects (logprobs/hidden appends, spec stats) for rows whose
    # request finished or was preempted-and-readmitted mid-flight
    gens: list = field(default_factory=list)
    asm: Optional[UnifiedBatch] = None
    # indices of rows ASSEMBLED as spec verify rows.  Retire must key
    # on this, not on a (width, is_prefill) predicate: a preempt-resume
    # recompute chunk can start past the prompt with width > 1 and
    # would otherwise be mistaken for a verify row, rewinding its
    # multi-token advance to 1
    spec_rows: set = field(default_factory=set)


def _params_key(sp: SamplingParams) -> tuple:
    """The fields SamplingTensors actually consumes, by VALUE — cache
    keys must not use id(sp): CPython reuses freed addresses, so a
    recycled request_id could silently hit a stale entry built from a
    dead request's params."""
    return (sp.temperature, sp.top_k, sp.top_p, sp.seed)


# Bucket-padding rows must be GREEDY: sample_tokens skips its sampling
# branch (filter and draw) only when no row has temperature > 0, and
# default-temperature padding would defeat that fast path for every
# batch that doesn't exactly fill its bucket (padding tokens are
# discarded either way).
_PAD_SAMPLING = SamplingParams(temperature=0.0)


class ARModelRunner:
    def __init__(
        self,
        params,
        cfg: tfm.TransformerConfig,
        num_pages: int,
        page_size: int,
        max_model_len: int = 4096,
        dtype=jnp.bfloat16,
        collect_hidden: bool = False,
        seed: Optional[int] = None,
        max_num_seqs: int = 64,
        mesh=None,  # 1-axis "tp" Mesh => tensor-parallel execution
        max_num_batched_tokens: int = 2048,  # sizes the token buckets
        kv_cache_dtype: str = "auto",  # auto | bf16 | int8 resident layout
        num_window_pages: int = 0,  # the window kind's pool (null page incl.)
    ):
        if kv_cache_dtype not in ("auto", "bf16", "int8"):
            raise ValueError(
                f"unknown kv_cache_dtype {kv_cache_dtype!r} "
                "(expected auto, bf16, or int8)")
        # a model with recurrent (Gated DeltaNet) layers holds two kinds
        # of cache: pages for its attention layers and one state slot a
        # sequence for the others (docs/kv_cache.md)
        self._hybrid = cfg.num_state_layers > 0
        # what an attention layer's pages hold (transformer.KVLayout):
        # a K and a V pool of per-head rows, or the one latent pool of a
        # model with multi-head latent attention, whose row is key and
        # value at once (docs/kv_cache.md)
        layout = cfg.kv_layout
        self.kv_layout = layout
        self._latent = layout.kind == "latent"
        # a model with sliding-window layers holds two kinds of PAGE: its
        # sliding layers' rows live in a pool of their own, under a second
        # table a sequence (docs/kv_cache.md, "Two kinds of page")
        self._window = cfg.sliding_window if cfg.num_window_layers else 0
        if self._window and (self._hybrid or num_window_pages < 2):
            raise ValueError(
                "sliding-window layers need num_window_pages (the engine "
                "sizes it from max_num_seqs x (window + "
                "max_num_batched_tokens)), and are not built beside "
                "recurrent layers")
        # what this model's cache layouts cannot carry (a runner can be
        # built without an engine, so it refuses for itself, from the
        # engine's table)
        self._cannot_carry = cache_layouts.cannot_carry(cfg)
        if kv_cache_dtype == "int8":
            self._refuse(cache_layouts.INT8)
        if mesh is not None:
            self._refuse(cache_layouts.TENSOR_PARALLEL)
        # int8 = the quantized resident layout (per-(head, page) absmax
        # scales, ops/paged_attention.py); auto/bf16 keep the dense
        # layout in the runner ``dtype``.  The flag is part of every
        # dispatch cache key: the quantized executables are a distinct
        # jit variant and warmup must prove it compiled (OL11).
        self._kv_quant = kv_cache_dtype == "int8"
        self.kv_cache_dtype = ("int8" if self._kv_quant
                               else str(jnp.dtype(dtype)))
        # the geometry is known here: on a platform that compiles the
        # paged kernels, one they cannot tile is refused now rather than
        # served through the O(T * max_ctx) gather reference
        reason = row_tiling_reason(layout.width, page_size) \
            or row_tiling_reason(layout.value_width or layout.width,
                                 page_size)
        if self._latent and not reason:
            reason = shared_tiling_reason(None, cfg.kv_lora_rank)
        if self._window and not reason:
            reason = row_tiling_reason(
                cfg.kv_layout_of("sliding_attention").width, page_size)
            if self._latent and not reason:
                reason = shared_tiling_reason(None,
                                              cfg.sliding.kv_lora_rank)
        if reason and _dispatch.pallas_mode() == "native":
            raise ValueError(
                f"the paged attention kernels cannot tile this geometry "
                f"({reason}; kv_cache_dtype={self.kv_cache_dtype}): pick "
                "a page_size that is a multiple of 8 and a model whose "
                "cache rows are whole 128-lane tiles (a head_dim of 128 "
                "or 256, an even count of kv heads of 64, or a latent row "
                "padded to 640, are served)")
        self.mesh = mesh
        # where a step's token ids live under a mesh (``_feed_tokens``)
        self._replicated = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._replicated = NamedSharding(mesh, PartitionSpec())
            # Megatron-style TP inside shard_map: heads and MLP columns
            # divide across the tp axis; the per-layer code runs on LOCAL
            # shapes and cfg.tp_axis inserts the psum/all_gather
            # collectives (reference: tensor_parallel_size,
            # stage_configs/qwen3_omni_moe.yaml:27).
            import dataclasses as _dc

            from vllm_omni_tpu.parallel.mesh import AXIS_TP
            from vllm_omni_tpu.parallel.sharding import shard_ar_params

            tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
                AXIS_TP, 1)
            if cfg.num_heads % tp or cfg.num_kv_heads % tp \
                    or layout.heads % tp:
                raise ValueError(
                    f"tp={tp} must divide num_heads={cfg.num_heads} and "
                    f"num_kv_heads={cfg.num_kv_heads} (the pool's "
                    f"{layout.heads} rows a token)")
            cfg = _dc.replace(cfg, tp_axis=AXIS_TP)
            params = shard_ar_params(params, mesh)
        self.params = params
        self.cfg = cfg
        self.params_dtype = jax.tree_util.tree_leaves(params)[0].dtype
        self.page_size = page_size
        self.max_pages_per_seq = -(-max_model_len // page_size)
        # bucket tables sized to the engine limits — the scheduler never
        # emits a batch beyond them, so _bucket cannot overflow
        self._batch_buckets = _make_buckets(1, max(max_num_seqs, 1))
        self._seq_buckets = _make_buckets(16, max(max_model_len, 16))
        # ragged block choice (ops/autotune.py).  Two different things:
        # the packer's segment alignment is the kernel's packing
        # contract (DEFAULT_TOKEN_BLOCK, honored by the assembler: a
        # decode row costs 8 packed rows); the q block, the pages per
        # KV block and the DMA depth are the kernel's own tile, derived
        # from the shapes.  They are recorded here for the warmup log
        # and the flight record's wide_q_tokens — the kernel re-derives
        # the identical values through the same lru-cached helper at
        # dispatch.
        self.q_block, self._pages_per_block, self._dma_slots = (
            auto_ragged_blocks(
                head_dim=layout.width, page_size=page_size,
                group=max(cfg.num_heads // max(layout.heads, 1), 1),
                kv_itemsize=(1 if self._kv_quant
                             else jnp.dtype(dtype).itemsize),
                q_itemsize=jnp.dtype(dtype).itemsize,
                quantized=self._kv_quant, kv_pools=layout.pools,
                kv_heads=max(layout.heads, 1)))
        self._token_block = DEFAULT_TOKEN_BLOCK
        self._max_num_batched_tokens = max_num_batched_tokens
        self._max_model_len = max_model_len
        self.collect_hidden = collect_hidden
        # --- telemetry (metrics/stats.py pulls these per step) ---
        # device dispatches: one jitted-executable launch each; tests
        # assert a mixed unified step is exactly ONE of these
        self.dispatch_count = 0
        # padding efficiency: real tokens vs. padded device rows
        self.useful_tokens = 0
        self.padded_tokens = 0
        # jit shape-cache telemetry: fresh compiles vs. cache hits, keyed
        # by this runner's own (kind, shape) signatures.  "compile_s" is
        # the fresh signatures' first calls to completion: the sum of
        # the four parts kept beside it and an executable (trace_s,
        # lower_s, backend_s and the rest, the first run).
        # "in_flight" is the stall watchdog's compile-stall signal: set
        # around the fresh-compile branch of _run_jit so a mid-traffic
        # XLA compile reads as "compiling", never as a hung engine
        self.compile_stats = CompileStats()
        # the host's phases on the host's clock; the engine replaces it
        # with its own, so that a step's phases land on one clock
        self.phases = PhaseClock()
        self._jit_seen: set[tuple] = set()
        # one (k, v) for each softmax-attention layer only; (pool, None)
        # where the layout is the shared latent one
        pages = init_kv_cache(
            cfg.num_kv_layers, num_pages, page_size, layout.heads,
            layout.width, dtype, quantized=self._kv_quant,
            shared=self._latent, v_head_dim=layout.v_width or None,
        )
        if self._window or cfg.index_topk:
            # pools by the layer's kind, in layer order: a sliding layer's
            # over the window kind's pages in the kind's own layout (a
            # latent row at its own width, or (K, V) rows a head, of the
            # kind's own count of kv heads); a full layer with an indexer
            # holds, on the same pages as its rows, the index keys' pool
            # as a third entry
            kinds = cfg.kv_layer_types
            wide = cfg.kv_layout_of("sliding_attention")
            for i, kind in enumerate(kinds):
                if kind == "sliding_attention":
                    pages[i] = init_kv_cache(
                        1, num_window_pages, page_size, wide.heads,
                        wide.width, dtype, shared=wide.kind == "latent",
                        v_head_dim=wide.v_width or None)[0]
                elif cfg.index_topk:
                    pages[i] += init_kv_cache(
                        1, num_pages, page_size, 1, cfg.index_head_dim,
                        dtype, shared=True)[0][:1]
        # and, for each layer that carries a state, what a sequence holds
        # there (``cfg.state_shapes``: a Gated DeltaNet layer's last
        # inputs of the convolution and the rule's matrices, a gated short
        # convolution's last inputs alone), float32 (the rule's as the
        # published implementation keeps it; the convolutions' because
        # their inputs are, see the mixers of models/common/state_layers),
        # one slot a sequence (slots = max_num_seqs; layouts:
        # ops/gated_delta_rule.py).  A row's slot index rides
        # next to its page table; padding rows carry ``num_state_slots``,
        # past the end, so their write is dropped
        self.num_state_slots = max(max_num_seqs, 1) if self._hybrid else 0
        state = [
            tuple(jnp.zeros((self.num_state_slots, *shape), jnp.float32)
                  for shape in cfg.state_shapes(kind))
            for kind in cfg.layer_types if kind in tfm.STATE_KINDS]
        # the operand every step donates and rebinds
        self.kv_caches = (tfm.HybridCaches(pages, state) if self._hybrid
                          else pages)
        # device-memory ledger components (introspection/memory_ledger):
        # static buffer sizes, summed ONCE from array metadata — .nbytes
        # never syncs the device.  Spec-decode verify buffers are added
        # by set_draft_fn.  The tree walk counts int8 page bodies AND
        # their scale arrays, so kv_pages is exact under either layout.
        self._weights_bytes = sum(
            getattr(x, "nbytes", 0)
            for x in jax.tree_util.tree_leaves(params))
        self._kv_bytes = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(pages))
        self._state_bytes = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(state))
        self._spec_bytes = 0
        if mesh is not None:
            from jax.sharding import NamedSharding

            from vllm_omni_tpu.parallel.sharding import ar_kv_cache_spec

            k_spec, v_spec = ar_kv_cache_spec(quantized=self._kv_quant)

            def _put(half, spec):
                if isinstance(half, tuple):
                    return tuple(
                        jax.device_put(a, NamedSharding(mesh, s))
                        for a, s in zip(half, spec))
                return jax.device_put(half, NamedSharding(mesh, spec))

            self.kv_caches = [
                (_put(k, k_spec), _put(v, v_spec))
                for k, v in self.kv_caches
            ]
        self._step = 0
        # engine-level entropy for unseeded requests (fresh per process
        # unless a seed is pinned for reproducibility)
        self._base_seed = seed if seed is not None else secrets.randbits(31)
        # host-side hot-path caches: crc32 sampling salts per request_id
        # and assembled SamplingTensors per batch composition — a
        # pure-decode batch keeps the same (requests, params) for
        # hundreds of steps, and rebuilding both every step was
        # measurable in the step-phase breakdown
        self._salt_cache: dict[str, int] = {}
        self._st_cache: dict[tuple, tuple] = {}
        # multimodal 3D-RoPE: positions carry 3 streams ([3, T] packed)
        self.use_mrope = cfg.mrope_sections is not None

        cfg_ = cfg
        collect_ = collect_hidden

        hybrid_, window_ = self._hybrid, bool(self._window)

        def extra_kwargs(extra):
            # what rides next to the page tables (``_extra_operands``)
            if hybrid_:
                return {"state_slots": extra[0]}
            if window_:
                return {"window_pages": tuple(extra)}
            return {}

        def _decode_core(params, token_ids, kv_caches, positions,
                         slot_mapping, block_tables, context_lens,
                         temperature, top_k, top_p, keys,
                         *extra, want_lp: bool):
            # the [B]-row pure-decode step: forward + ON-DEVICE sampling
            # (the hoist that enables the async pipelined engine step —
            # sampled tokens stay device-resident and feed the NEXT
            # dispatch, so jax.device_get becomes a one-step-lagged
            # retire, engine/llm_engine.py).  The want_lp variant also
            # computes chosen/top-k logprobs in the step, so logprobs
            # decode batches pipeline instead of draining.
            # ``kv_caches`` is the donated operand: the pages, or for a
            # model with recurrent layers HybridCaches(pages, state slots)
            hidden, new_caches = tfm.forward_decode(
                params, cfg_, token_ids, positions, kv_caches,
                slot_mapping, block_tables, context_lens,
                **extra_kwargs(extra),
            )
            logits = tfm.logits_from_hidden(params, cfg_, hidden)
            toks, path = sample_tokens(logits, temperature, top_k, top_p,
                                       keys)
            out = {"tokens": toks, "sampler_path": path}
            if want_lp:
                chosen, top_v, top_i = compute_logprobs(
                    logits, toks, LOGPROBS_K)
                out.update(lp_chosen=chosen, lp_topv=top_v, lp_topi=top_i)
            if collect_:
                out["hidden"] = hidden
            return out, new_caches

        def _decode_step(*args):
            return _decode_core(*args, want_lp=False)

        def _decode_step_lp(*args):
            return _decode_core(*args, want_lp=True)

        def _unified_core(params, token_ids, kv_caches, positions,
                          slot_mapping, page_tables, seq_lens, cu_q_lens,
                          q_lens, num_seqs, verify_idx, n_cand, drafts,
                          temperature, top_k, top_p, keys,
                          *extra, inputs_embeds=None,
                          embeds_mask=None, deepstack=None):
            # ONE executable for every non-pure-decode step: the
            # token-packed ragged forward serves prefill chunks,
            # decode rows, and k+1-token spec verify rows in the same
            # flat [T] axis; candidate logits are gathered at
            # ``verify_idx`` (all rows point at the sampling position
            # for plain sequences), verify/accept + sampling run on
            # device, and logprobs ride the output pytree.  Shapes vary
            # only in the token axis, so the jit cache is a 1-D
            # token-bucket line instead of a (batch, seq) grid.
            hidden, new_caches = tfm.forward_unified(
                params, cfg_, token_ids, positions, kv_caches,
                slot_mapping, page_tables, seq_lens, cu_q_lens, q_lens,
                num_seqs, inputs_embeds=inputs_embeds,
                embeds_mask=embeds_mask, deepstack=deepstack,
                **extra_kwargs(extra),
            )
            with jax.named_scope("omni.lm_head"):
                cand_hidden = hidden[verify_idx]      # [S, V, H]
            logits = tfm.logits_from_hidden(params, cfg_, cand_hidden)
            toks, counts, path = spec_verify_tokens(
                logits, drafts, n_cand, temperature, top_k, top_p, keys)
            with jax.named_scope("omni.sampler"):
                ar = jnp.arange(toks.shape[0])
                last = jnp.maximum(counts - 1, 0)
                last_tok = toks[ar, last]
            chosen, top_v, top_i = compute_logprobs(
                logits[:, 0], toks[:, 0], LOGPROBS_K)
            out = {"tokens": toks, "counts": counts,
                   "last_tok": last_tok, "sampler_path": path,
                   "lp_chosen": chosen, "lp_topv": top_v,
                   "lp_topi": top_i}
            if drafts.shape[1] > 0:
                # the accept-position hidden rows feed the next draft
                # proposal — only a drafted runner (V > 1, a STATIC
                # shape) needs them; without a draft head the [S, H]
                # array would be dead weight on every lagged retire
                # transfer
                with jax.named_scope("omni.sampler"):
                    out["accept_hidden"] = hidden[verify_idx[ar, last]]
            if collect_:
                out["hidden"] = hidden
            return out, new_caches

        if mesh is None:
            jit2 = functools.partial(jax.jit, donate_argnums=(2,))
            self._decode_sample_fn = jit2(_decode_step)
            self._decode_lp_fn = jit2(_decode_step_lp)
            self._unified_fn = jit2(_unified_core)
        else:
            # TP: shard_map over the tp axis — params/KV are the only
            # sharded operands; token inputs replicate, and the psums in
            # _layer_step make activations (logits/hidden) replicated
            # outputs. shard_map (not GSPMD) because the Pallas attention
            # kernels cannot be auto-partitioned by XLA.  Sampling is
            # deterministic in (logits, keys) and logits replicate, so
            # every shard samples the same token.
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            from vllm_omni_tpu.parallel.sharding import (
                ar_kv_cache_spec,
                ar_param_specs_tree,
            )

            pspecs = ar_param_specs_tree(params)
            kv_specs = ([ar_kv_cache_spec(quantized=True)] * cfg.num_layers
                        if self._kv_quant
                        else [ar_kv_cache_spec()] * cfg.num_layers)
            rep = P()

            def wrap(f, n_rest, out_keys):
                out_spec = ({k: rep for k in out_keys}, kv_specs)
                sm = shard_map(
                    f, mesh=mesh,
                    in_specs=(pspecs, rep, kv_specs) + (rep,) * n_rest,
                    out_specs=out_spec,
                    check_vma=False,
                )
                return jax.jit(sm, donate_argnums=(2,))

            dec_keys = ("tokens", "sampler_path") + (
                ("hidden",) if collect_ else ())
            self._decode_sample_fn = wrap(_decode_step, 8, dec_keys)
            self._decode_lp_fn = wrap(
                _decode_step_lp, 8,
                dec_keys + ("lp_chosen", "lp_topv", "lp_topi"))
            # the unified step's embeds/deepstack tail is optional and
            # accept_hidden exists only for drafted runners (drafts
            # width > 0), and shard_map needs a fixed arity + output
            # tree — build one wrap per variant on first use (same
            # shape-cache stance as jit itself)
            uni_wraps: dict[tuple, Any] = {}

            def unified_dispatch(*args, inputs_embeds=None,
                                 embeds_mask=None, deepstack=None):
                has_e = inputs_embeds is not None
                has_d = deepstack is not None
                has_dr = args[12].shape[1] > 0  # drafts operand
                uni_keys = ("tokens", "counts", "last_tok", "sampler_path",
                            "lp_chosen", "lp_topv", "lp_topi")
                if has_dr:
                    uni_keys += ("accept_hidden",)
                if collect_:
                    uni_keys += ("hidden",)
                fn = uni_wraps.get((has_e, has_d, has_dr))
                if fn is None:
                    extra = (2 if has_e else 0) + (1 if has_d else 0)

                    def make_core(he: bool, hd: bool):
                        # he/hd are CLOSED-OVER python bools fixed per
                        # wrap arity — never traced values
                        def core(p, t, k, *rest):
                            base, tail = rest[:14], rest[14:]
                            emb = tail[0] if he else None
                            mask = tail[1] if he else None
                            deep = tail[2 if he else 0] if hd else None
                            return _unified_core(
                                p, t, k, *base, inputs_embeds=emb,
                                embeds_mask=mask, deepstack=deep)

                        return core

                    fn = uni_wraps[(has_e, has_d, has_dr)] = wrap(
                        make_core(has_e, has_d), 14 + extra, uni_keys)
                extras = tuple(x for x in (inputs_embeds, embeds_mask,
                                           deepstack) if x is not None)
                return fn(*args, *extras)

            self._unified_fn = unified_dispatch
        # speculative decoding (MTP draft head): draft_fn(last_hidden [M,H],
        # last_token [M], positions [M]) -> [M, k] proposals
        self.draft_fn = None
        self.num_draft_tokens = 0
        self._lay_token_line()
        self.spec_stats = {"verify_steps": 0, "proposed": 0, "accepted": 0}
        # dispatches retired since the engine last looked, by the way the
        # sampler's filter went (indexed by sample/sampler.py PATH_*; read
        # in the step's own readback, consumed by LLMEngine._record_step)
        self.sampler_steps = [0, 0, 0]
        # dispatches since the engine last looked whose executable holds
        # the experts' few-rows kernel, and the executables that do: the
        # op's own choice, seen as each is traced (``_run_jit``)
        self.grouped_kernel_steps = 0
        self._grouped_kernel_keys: set = set()
        # width of upstream embeds accepted by this model: the embed_proj
        # input dim when present (thinker width for the talker), else the
        # model's own hidden size
        self.embeds_width = (
            params["embed_proj"]["w"].shape[0]
            if "embed_proj" in params else cfg.hidden_size
        )

    @property
    def _spec_v(self) -> int:
        """Candidate rows per sequence in the unified executable: the
        regular sample plus every possible draft.  1 without a draft
        head — the verify machinery degenerates to plain sampling in
        the same executable."""
        return 1 + self.num_draft_tokens

    def _lay_token_line(self) -> None:
        """The token-bucket line of the unified executable, how far up
        it a budgeted step can reach, and the packing contract the
        engine hands the scheduler (core/scheduler.py PackingContract).

        Unified ragged batching pads to TOKEN-count buckets: a 1-D line
        replacing the (batch, seq) grid of the split path.  The AR
        scheduler holds a prefill chunk to the bucket its step already
        needs, so the most rows a step packs follow from the rule that
        sizes the chunk (``packed_rows_needed``): the token budget, or a
        chunk's block beside every other seat's decode row, or every
        seat a decode row (a verify row of 1 + k candidates where a
        draft head is installed: laid again by ``set_draft_fn``).  The
        one-shot generation scheduler ignores the token budget, so the
        line extends to max_model_len for CAPACITY, but warmup only
        walks the prefix a budgeted step reaches: each compile costs
        tens of seconds, and a generation deployment takes the one-time
        first-hit compile instead."""
        tb = self._token_block
        seats = self._batch_buckets[-1]
        row = align_to_block(self._spec_v, tb)
        reach = max(
            packed_rows_needed(self._max_num_batched_tokens,
                               (seats - 1) * row, tb),
            seats * row)
        t_cap = align_to_block(max(reach, self._max_model_len, 16), tb)
        self._token_buckets = _make_buckets(16, t_cap)
        self._warm_token_cap = max(reach, 16)
        self.packing = PackingContract(tb, self._token_buckets)

    def set_draft_fn(self, draft_fn, num_draft_tokens: int) -> None:
        """Install the MTP draft head (talker spec decode, reference:
        gpu_ar_model_runner.py:466-497 EAGLE propose).  A draft_fn taking
        a ``contexts`` kwarg also receives each drafted request's full
        post-step token history (oracle/tree drafters).  Install BEFORE
        warmup: the candidate width V = 1 + k is part of the unified
        executable's input shapes."""
        import inspect

        self._refuse(cache_layouts.SPECULATIVE)
        self.draft_fn = draft_fn
        self.num_draft_tokens = num_draft_tokens
        self._lay_token_line()
        # memory-ledger estimate of the verify-path buffers: the widest
        # batch's (k+1)-row logits at float32 (deterministic — the
        # ledger's CPU fallback must not depend on allocator probes)
        self._spec_bytes = (self._batch_buckets[-1]
                            * (num_draft_tokens + 1)
                            * self.cfg.vocab_size * 4)
        try:
            sig = inspect.signature(draft_fn)
            self._draft_takes_contexts = "contexts" in sig.parameters
        except (TypeError, ValueError):
            self._draft_takes_contexts = False

    # -------------------------------------------------- dispatch telemetry
    def _run_jit(self, kind: str, shape_key: tuple, thunk):
        """Invoke one jitted executable through the telemetry choke
        point: counts the device dispatch (mixed-step tests assert ONE
        per unified step) and classifies it fresh-compile vs cache-hit
        by this runner's own (kind, shape) signature.  A fresh signature
        is timed TO COMPLETION (block_until_ready) so compile_s measures
        the real compile+first-run stall — warmup prepopulates the
        signatures, so steady-state traffic takes the unsynced branch."""
        self.dispatch_count += 1
        key = (kind,) + tuple(shape_key)
        if key in self._jit_seen:
            self.compile_stats["cache_hits"] += 1
            self.grouped_kernel_steps += key in self._grouped_kernel_keys
            # a host phase (docs/observability.md): the launch is time
            # the engine thread waits on the device's runtime
            with self.phases.phase("omni.dispatch") as p:
                result = thunk()
                p.waited()
            return result
        self._jit_seen.add(key)
        traced = kernel_calls()
        with self.compile_stats.first_call(self.phases, key) as p:
            result = thunk()
            jax.block_until_ready(result)
            p.waited()
        if kernel_calls() > traced:
            self._grouped_kernel_keys.add(key)
            self.grouped_kernel_steps += 1
        return result

    def memory_components(self) -> dict:
        """Attributable device-memory components for the engine's
        ledger (introspection/memory_ledger.py): static buffer sizes
        from array metadata — never a device sync."""
        comps = {"weights": self._weights_bytes,
                 "kv_pages": self._kv_bytes}
        if self._state_bytes:
            comps["state_slots"] = self._state_bytes
        if self._spec_bytes:
            comps["spec_buffers"] = self._spec_bytes
        return comps

    def _note_padding(self, useful: int, padded: int) -> None:
        self.useful_tokens += int(useful)
        self.padded_tokens += int(padded)

    # ------------------------------------------------ the state cache
    @property
    def state_caches(self) -> list:
        """For each recurrent layer its (conv, recurrent) state slots; []
        for a model without such layers.  Held inside ``kv_caches``
        (``HybridCaches``) so that a step donates both kinds together."""
        return self.kv_caches.state if self._hybrid else []

    @state_caches.setter
    def state_caches(self, value) -> None:
        self.kv_caches = self.kv_caches._replace(state=list(value))

    def _slot_column(self, scheds, width: int, cu=None,
                     t_pad: Optional[int] = None) -> tuple:
        """What a step carries next to its page tables: the state-slot
        column of a model with recurrent layers; the window kind's slots
        (``[t_pad]``, at the packed offsets ``cu``; a row a sequence
        where ``t_pad`` is None) and page tables of a model with
        sliding-window layers; ``()`` for every other."""
        if self._window:
            slots = np.full((width if t_pad is None else t_pad,), -1,
                            np.int32)
            tables = np.zeros((width, self.max_pages_per_seq), np.int32)
            for i, sc in enumerate(scheds):
                lo = i if cu is None else int(cu[i])
                slots[lo: lo + len(sc.window_slots)] = sc.window_slots
                t = sc.window_table[: self.max_pages_per_seq]
                tables[i, : len(t)] = t
            return (slots, tables)
        if not self._hybrid:
            return ()
        col = np.full((width,), self.num_state_slots, np.int32)
        for i, sc in enumerate(scheds):
            if sc.state_slot < 0:
                raise RuntimeError(
                    f"request {sc.request.request_id} of a model with "
                    "recurrent layers was scheduled without a state slot")
            col[i] = sc.state_slot
        return (col,)

    # ---------------------------------------------------------- precompile
    def precompile(self, prefill_shapes=(), decode: bool = True,
                   progress_fn=None) -> int:
        """Build the executables BEFORE serving traffic.

        XLA compiles one executable per input-shape signature, and a
        cache miss mid-traffic stalls every in-flight request for the
        full compile — about 19 s per executable for the 8-layer
        thinker on a v5e host, most of it the full-vocabulary sampler
        (CHANGES.md PR 21).  The unified refactor shrank the warmup
        surface from the (batch, seq) grid × {prefill, chunk, decode,
        verify, multi} to:

        - the 1-D token-bucket line of the unified executable (one
          shape per bucket; the candidate width V = 1 + draft k is
          fixed per runner — install the draft head first), and
        - the decode batch buckets × {plain, logprobs} of the dedicated
          pure-decode step, and
        - beside each of either, the two small programs by which a
          pipelined step takes its in-flight input tokens from the step
          before it (``_feed_tokens``): one a shape of tokens left, one
          a shape of tokens fed.

        ``prefill_shapes`` is accepted for API compatibility; every
        packed size a prefill can produce already lands on a token
        bucket.  Embeds/deepstack batches add an argument-tree variant
        that compiles on first hit.  Dummy inputs write to KV slot -1,
        which the paged cache update drops, so the live KV pool is
        untouched.  Returns the number of executables requested."""
        del prefill_shapes  # the token-bucket line covers prefills
        built = 0

        def note(msg):
            if progress_fn is not None:
                progress_fn(msg)

        def warm(kind, key, thunk):
            nonlocal built
            with self.compile_stats.warming_up():
                res = self._run_jit(kind, key, thunk)
            built += 1
            return res

        logger.info(
            "ragged blocks: token_block=%d q_block=%d pages_per_block=%d "
            "dma_slots=%d (row width=%d page_size=%d kv_cache_dtype=%s) "
            "— ops/autotune.py picks per layout", self._token_block,
            self.q_block, self._pages_per_block, self._dma_slots,
            self.kv_layout.width, self.page_size, self.kv_cache_dtype)

        def pos_shape(b):
            return (b, 3) if self.use_mrope else (b,)

        def warm_feed(n, tokens):
            # the pipelined step's feed (``_feed_tokens``): a step of ``n``
            # token rows fed from a step that left ``tokens``, as placed
            # and shaped as the executable just warmed leaves them
            self._feed_tokens(np.zeros((n,), np.int32), [(0, 0)],
                              InflightDecode(tokens=tokens, rows={}))

        if decode and self.draft_fn is None:
            # a runner with a draft head never dispatches the [B]-row
            # decode path at all (_plain_decode_only routes every
            # decode batch unified), so its buckets would be pure
            # warmup waste.
            for b in self._batch_buckets:
                tables = jnp.zeros((b, self.max_pages_per_seq), jnp.int32)
                zeros_b = self._feed_tokens(np.zeros((b,), np.int32), [],
                                            None)
                t = SamplingTensors.build(
                    [_PAD_SAMPLING] * b, step=0,
                    base_seed=self._base_seed)
                for kind, fn in (("dispatch", self._decode_sample_fn),
                                 ("dispatch_lp", self._decode_lp_fn)):
                    note(f"precompile {kind} b={b}")
                    outs, self.kv_caches = warm(
                        kind, (b, self._kv_quant), lambda fn=fn: fn(
                            self.params, zeros_b, self.kv_caches,
                            jnp.zeros(pos_shape(b), jnp.int32),
                            jnp.full((b,), -1, jnp.int32), tables,
                            jnp.ones((b,), jnp.int32),
                            t.temperature, t.top_k, t.top_p, t.keys,
                            *map(jnp.asarray, self._slot_column((), b))))
                warm_feed(b, outs["tokens"])
        # ONE executable per token bucket — the 1-D shape-cache line
        # that replaces the (batch, seq) grid
        s_max = self._batch_buckets[-1]
        v = self._spec_v
        t = SamplingTensors.build(
            [_PAD_SAMPLING] * s_max, step=0, base_seed=self._base_seed)
        for i, t_pad in enumerate(self._token_buckets):
            if i and self._token_buckets[i - 1] >= self._warm_token_cap:
                # past the first bucket that covers the AR scheduler's
                # worst packed step: reachable only by the one-shot
                # generation scheduler's whole-prompt packs — first-hit
                # compile there, never under the budget-capped AR
                # scheduler
                break
            note(f"precompile unified t={t_pad} v={v}")
            pos = (jnp.zeros((3, t_pad), jnp.int32) if self.use_mrope
                   else jnp.zeros((t_pad,), jnp.int32))
            outs, self.kv_caches = warm(
                "unified", (t_pad, v, False, False, self._kv_quant),
                lambda: self._unified_fn(
                    self.params,
                    self._feed_tokens(np.zeros((t_pad,), np.int32), [],
                                      None),
                    self.kv_caches, pos,
                    jnp.full((t_pad,), -1, jnp.int32),
                    jnp.zeros((s_max, self.max_pages_per_seq),
                              jnp.int32),
                    jnp.zeros((s_max,), jnp.int32),
                    jnp.zeros((s_max + 1,), jnp.int32),
                    jnp.zeros((s_max,), jnp.int32),
                    jnp.zeros((1,), jnp.int32),
                    jnp.zeros((s_max, v), jnp.int32),
                    jnp.ones((s_max,), jnp.int32),
                    jnp.zeros((s_max, v - 1), jnp.int32),
                    t.temperature, t.top_k, t.top_p, t.keys,
                    *map(jnp.asarray, self._slot_column(
                        (), s_max, t_pad=t_pad))))
            warm_feed(t_pad, outs["last_tok"])
        self.grouped_kernel_steps = 0  # a warm-up dispatch is no step
        return built

    # ---------------------------------------------------------------- step
    def execute(
        self, sched_out: SchedulerOutput, extract_kv: bool = True
    ) -> RunnerOutput:
        """Synchronous step: dispatch + immediate retire of the SAME
        handles the async pipeline uses — one executable family, one
        numerics contract, so sync and pipelined streams cannot drift."""
        out = RunnerOutput()
        decodes, prefills = sched_out.decodes, sched_out.prefills
        if self._plain_decode_only(sched_out):
            handle = self.dispatch_decode(decodes)
            out.sampled.update(self.retire_step(handle))
        elif decodes or prefills:
            for g_decodes, g_prefills in self._pack_groups(decodes,
                                                           prefills):
                handle = self._dispatch_unified(g_decodes, g_prefills,
                                                None)
                out.sampled.update(self.retire_step(handle))
        for req, block_ids, seq_len in sched_out.kv_transfer_requests:
            # skip the device→host gather when no sink consumes it, but
            # still ACK so the scheduler releases the pinned pages
            if extract_kv:
                out.extracted_kv[req.request_id] = self.extract_kv(
                    block_ids, seq_len
                )
            out.kv_extracted_req_ids.add(req.request_id)
        return out

    # ------------------------------------------------------------ routing
    def _plain_decode_only(self, sched_out: SchedulerOutput) -> bool:
        """Pure single-token decode batches keep the dedicated [B]
        executable — 1 row per sequence beats token-block alignment.
        Anything else (prefill chunks, spec verify rows) packs onto
        the unified token axis.  This is a ROUTING choice between two
        always-available single-dispatch paths, not a fallback: both
        ride the async handle, and logprobs/collect_hidden are served
        by either.  A runner with a draft head routes every decode
        batch unified — the step's ``accept_hidden`` is what the draft
        proposal reads, and a drafted request's rows are verify rows
        (num_new_tokens > 1) on the very next step anyway."""
        if self.draft_fn is not None:
            return False
        return (bool(sched_out.decodes) and not sched_out.prefills
                and all(s.num_new_tokens == 1 for s in sched_out.decodes))

    def fits_unified(self, sched_out: SchedulerOutput) -> bool:
        """One packed group?  The engine pipelines single-group steps;
        a multi-group step (possible only under the one-shot generation
        scheduler, which ignores the token budget) runs synchronously
        as several dispatches."""
        scheds = sched_out.decodes + sched_out.prefills
        if len(scheds) > self._batch_buckets[-1]:
            return False
        total = sum(align_to_block(s.num_new_tokens, self._token_block)
                    for s in scheds)
        return total <= self._token_buckets[-1]

    def _pack_groups(self, decodes, prefills):
        """Split an oversized step into sequential unified dispatches
        (decodes first, arrival order preserved — the same admission
        order the scheduler emitted)."""
        s_cap = self._batch_buckets[-1]
        t_cap = self._token_buckets[-1]
        groups: list[tuple[list, list]] = []
        cur_d: list[ScheduledRequest] = []
        cur_p: list[ScheduledRequest] = []
        tot = 0
        for sched, is_decode in ([(s, True) for s in decodes]
                                 + [(s, False) for s in prefills]):
            need = align_to_block(sched.num_new_tokens, self._token_block)
            if (cur_d or cur_p) and (
                    len(cur_d) + len(cur_p) + 1 > s_cap
                    or tot + need > t_cap):
                groups.append((cur_d, cur_p))
                cur_d, cur_p, tot = [], [], 0
            (cur_d if is_decode else cur_p).append(sched)
            tot += need
        if cur_d or cur_p:
            groups.append((cur_d, cur_p))
        return groups

    # ---------------------------------------------------- unified ragged
    def _assemble_unified(self, scheds: list[ScheduledRequest],
                          spec_rows: set[int]) -> UnifiedBatch:
        """Token-packed device inputs for a mixed batch: each sequence's
        chunk occupies a token-block-aligned segment of the flat token
        axis (the layout contract of ops/ragged_paged_attention.py);
        metadata arrays are fixed [S_max] width so shapes vary only in
        the token bucket.  ``spec_rows``: indices of verify rows, whose
        segment is [last_sampled, draft_1..draft_k] and whose candidate
        logits cover every position."""
        s_max = self._batch_buckets[-1]
        v = self._spec_v
        tb = self._token_block
        n = len(scheds)
        cu = np.zeros((s_max + 1,), np.int32)
        q_lens = np.zeros((s_max,), np.int32)
        seq_lens = np.zeros((s_max,), np.int32)
        tables = np.zeros((s_max, self.max_pages_per_seq), np.int32)
        total = 0
        for i, sc in enumerate(scheds):
            cu[i] = total
            q_lens[i] = sc.num_new_tokens
            seq_lens[i] = sc.start_pos + sc.num_new_tokens
            t = sc.block_table[: self.max_pages_per_seq]
            tables[i, : len(t)] = t
            total += align_to_block(sc.num_new_tokens, tb)
        cu[n:] = total
        t_pad = _bucket(max(total, self._token_buckets[0]),
                        self._token_buckets)
        token_ids = np.zeros((t_pad,), np.int32)
        positions = (np.zeros((3, t_pad), np.int32) if self.use_mrope
                     else np.zeros((t_pad,), np.int32))
        slots = np.full((t_pad,), -1, np.int32)
        last_idx = np.zeros((s_max,), np.int32)
        verify_idx = np.zeros((s_max, v), np.int32)
        n_cand = np.ones((s_max,), np.int32)
        drafts = np.zeros((s_max, max(v - 1, 0)), np.int32)
        use_embeds = any(s.request.prompt_embeds is not None
                         for s in scheds)
        embeds = (np.zeros((t_pad, self.embeds_width), np.float32)
                  if use_embeds else None)
        embeds_mask = np.zeros((t_pad,), bool) if use_embeds else None
        # deepstack multiscale visual features, shipped as sparse
        # (offset, [n_deep, T_item, hidden]) spans on the request and
        # scattered here (zeros at non-visual rows): level i adds to the
        # residual stream after decoder layer i
        n_deep = max((arr.shape[0]
                      for s in scheds
                      for off, arr in (s.request.deepstack_embeds or ())
                      if off < s.start_pos + s.num_new_tokens
                      and off + arr.shape[1] > s.start_pos),
                     default=0)
        deep = (np.zeros((n_deep, t_pad, self.cfg.hidden_size),
                         np.float32) if n_deep else None)
        for i, sc in enumerate(scheds):
            req = sc.request
            m = sc.num_new_tokens
            lo = int(cu[i])
            if i in spec_rows:
                # verify row: [last_sampled, drafts...] — drafts are
                # inputs from the previous step's proposal, verified by
                # this step's candidate logits.  A pipelined verify
                # whose first input token is still in flight leaves a
                # placeholder; _dispatch_unified scatters the real
                # token device-side from the previous handle
                first = (req.all_token_ids[sc.start_pos]
                         if sc.start_pos < req.num_tokens else 0)
                row = ([first]
                       + [int(x) for x in
                          req.spec_draft_tokens[: m - 1]])
                token_ids[lo: lo + m] = row
                drafts[i, : m - 1] = row[1:]
                n_cand[i] = m
                verify_idx[i] = lo + np.minimum(np.arange(v), m - 1)
            else:
                # an async-fed decode row's input token is still in
                # flight (all_token_ids slice comes back empty):
                # _dispatch_unified scatters it device-side from the
                # previous handle
                toks = req.all_token_ids[sc.start_pos: sc.start_pos + m]
                token_ids[lo: lo + len(toks)] = toks
                # plain rows: every candidate slot points at the
                # sampling position (the segment's last token)
                verify_idx[i] = lo + m - 1
            p = np.arange(sc.start_pos, sc.start_pos + m)
            if self.use_mrope:
                positions[:, lo: lo + m] = self._mrope_cols(req, p)
            else:
                positions[lo: lo + m] = p
            slots[lo: lo + m] = sc.slot_mapping
            last_idx[i] = lo + m - 1
            if use_embeds and req.prompt_embeds is not None:
                # embeds cover prompt rows only; a recompute-resumed
                # request also re-prefills its generated tokens, which
                # embed from the table (mask False)
                pe = np.asarray(req.prompt_embeds)
                elo = min(sc.start_pos, pe.shape[0])
                ehi = min(sc.start_pos + m, pe.shape[0])
                if ehi > elo:
                    embeds[lo: lo + ehi - elo] = pe[elo:ehi]
                    embeds_mask[lo: lo + ehi - elo] = True
            if deep is not None:
                # intersect each visual span with this chunk's window
                # [start_pos, start_pos+m); rows outside any span (text,
                # re-prefilled generated tokens) stay zero
                for off, arr in req.deepstack_embeds or ():
                    dlo = max(off, sc.start_pos)
                    dhi = min(off + arr.shape[1], sc.start_pos + m)
                    if dlo < dhi:
                        deep[: arr.shape[0],
                             lo + dlo - sc.start_pos:
                             lo + dhi - sc.start_pos] = (
                            arr[:, dlo - off: dhi - off])
        return UnifiedBatch(token_ids, positions, slots, tables,
                            seq_lens, cu, q_lens, last_idx, t_pad, total,
                            verify_idx, n_cand, drafts, embeds,
                            embeds_mask, deep)

    def _unified_sampling(self, scheds, key_tag: str, t_pad: int):
        """[S_max]-wide SamplingTensors: real params on rows whose chunk
        reaches the sequence's last token (the sequence-final flag —
        verify rows included), greedy padding elsewhere (keeps
        sample_tokens' fast path)."""
        s_max = self._batch_buckets[-1]
        params_list = [_PAD_SAMPLING] * s_max
        salts = [0] * s_max
        final = []
        for i, sc in enumerate(scheds):
            req = sc.request
            if sc.samples_final:
                final.append((i, sc))
                params_list[i] = req.sampling_params
                salts[i] = self._salt_of(req.request_id)
        key = (key_tag, t_pad) + tuple(
            (i, sc.request.request_id)
            + _params_key(sc.request.sampling_params) for i, sc in final)
        return self._sampling_tensors(key, params_list, salts), final

    def dispatch_unified(
        self, sched_out: SchedulerOutput,
        prev: Optional[InflightDecode] = None,
    ) -> InflightDecode:
        """Dispatch a unified step on the async handle: prefill chunks,
        spec verify rows, logprobs, collect_hidden, and embeds inputs
        all ride the two-slot pipeline (engine/llm_engine.py).  Decode
        rows whose input token is still in flight gather it device-side
        from ``prev.tokens`` — each row's last ACCEPTED token, so the
        feed works across decode and unified handles alike."""
        return self._dispatch_unified(sched_out.decodes,
                                      sched_out.prefills, prev)

    def _dispatch_unified(self, decodes, prefills,
                          prev: Optional[InflightDecode]
                          ) -> InflightDecode:
        self._step += 1
        scheds = decodes + prefills
        spec_rows = {i for i, s in enumerate(decodes)
                     if s.num_new_tokens > 1}
        # host phases (docs/observability.md): packing, sampling tensors
        # and host-to-device puts are omni.assemble, one annotation in
        # the profiler's trace and three children on the host's clock
        # alone; the jitted call alone is omni.dispatch (_run_jit)
        phase = self.phases.phase
        with phase("omni.assemble"):
            with phase("omni.assemble.pack", trace=False):
                asm = self._assemble_unified(scheds, spec_rows)
                # (packed row, row of ``prev``) of every input token the
                # previous dispatch sampled, still device-resident
                feed = [(int(asm.cu_q_lens[i]),
                         prev.rows[sc.request.request_id])
                        for i, sc in enumerate(scheds)
                        if sc.start_pos >= sc.request.num_tokens
                        and prev is not None
                        and sc.request.request_id in prev.rows]
                # the slot column of a model with recurrent layers
                slot_cols = self._slot_column(
                    scheds, self._batch_buckets[-1], asm.cu_q_lens,
                    asm.t_pad)
                # verify tokens are USEFUL work (each is a candidate
                # position the model scores); only block-alignment slack
                # pads
                self._note_padding(int(asm.q_lens.sum()), asm.t_pad)
                if spec_rows:
                    self.spec_stats["verify_steps"] += 1
            with phase("omni.assemble.sampling", trace=False):
                tensors, final = self._unified_sampling(scheds, "unified",
                                                        asm.t_pad)
            with phase("omni.assemble.put", trace=False):
                token_ids = self._feed_tokens(asm.token_ids, feed, prev)
                kwargs = {}
                if asm.embeds is not None:
                    kwargs["inputs_embeds"] = jnp.asarray(
                        asm.embeds, dtype=self.params_dtype)
                    kwargs["embeds_mask"] = jnp.asarray(asm.embeds_mask)
                if asm.deepstack is not None:
                    kwargs["deepstack"] = jnp.asarray(
                        asm.deepstack, dtype=self.params_dtype)
                args = (
                    jnp.asarray(asm.positions), jnp.asarray(asm.slots),
                    jnp.asarray(asm.tables), jnp.asarray(asm.seq_lens),
                    jnp.asarray(asm.cu_q_lens), jnp.asarray(asm.q_lens),
                    # numpy first: a Python list would compile a convert op
                    jnp.asarray(np.asarray([len(scheds)], np.int32)),
                    jnp.asarray(asm.verify_idx), jnp.asarray(asm.n_cand),
                    jnp.asarray(asm.drafts),
                    tensors.temperature, tensors.top_k, tensors.top_p,
                    tensors.keys, *map(jnp.asarray, slot_cols))
        outs, self.kv_caches = self._run_jit(
            "unified",
            # the deepstack LEVEL COUNT is part of the operand shape —
            # omitting it would misclassify a real mid-traffic compile
            # as a cache hit and blind the compile-stall introspection;
            # the KV layout flag keeps the int8 executables a distinct
            # signature family (quantized caches are a different pytree)
            (asm.t_pad, self._spec_v, asm.embeds is not None,
             asm.deepstack.shape[0] if asm.deepstack is not None else 0,
             self._kv_quant),
            lambda: self._unified_fn(
                self.params, token_ids, self.kv_caches, *args, **kwargs))
        return InflightDecode(
            tokens=outs["last_tok"],
            rows={sc.request.request_id: i for i, sc in final},
            outs=outs, kind="unified", scheds=list(scheds),
            gens=[s.request.async_generation for s in scheds],
            asm=asm, spec_rows=spec_rows,
        )

    # ------------------------------------------------ pipelined dispatch
    def dispatch_decode(
        self, scheds: list[ScheduledRequest],
        prev: Optional[InflightDecode] = None,
    ) -> InflightDecode:
        """Dispatch half of the async pipelined step for a pure
        single-token decode batch: forward + on-device sampling (+
        logprobs when any row wants them), returning WITHOUT waiting.
        Input tokens that are not host-visible yet (sampled by ``prev``,
        still in flight) are gathered device-side from ``prev.tokens``.
        The engine retires the handle one step later (``retire_step``)."""
        self._step += 1
        phase = self.phases.phase
        with phase("omni.assemble"):
            with phase("omni.assemble.pack", trace=False):
                b = _bucket(len(scheds), self._batch_buckets)
                token_host = np.zeros((b,), np.int32)
                feed: list[tuple[int, int]] = []
                params_list = [_PAD_SAMPLING] * b
                salts = [0] * b
                want_lp = False
                for i, sc in enumerate(scheds):
                    req = sc.request
                    if sc.start_pos < req.num_tokens:
                        token_host[i] = req.all_token_ids[sc.start_pos]
                    else:
                        # input token still in flight from the previous
                        # dispatch
                        feed.append((i, prev.rows[req.request_id]))
                    params_list[i] = req.sampling_params
                    salts[i] = self._salt_of(req.request_id)
                    if req.sampling_params.logprobs is not None:
                        want_lp = True
                positions, slots, tables, ctx = self._assemble_decode_rows(
                    scheds, b)
                slot_cols = self._slot_column(scheds, b)
                self._note_padding(len(scheds), b)
            with phase("omni.assemble.sampling", trace=False):
                kind = "dispatch_lp" if want_lp else "dispatch"
                fn = (self._decode_lp_fn if want_lp
                      else self._decode_sample_fn)
                key = (kind, b) + tuple(
                    (sc.request.request_id,) + _params_key(
                        sc.request.sampling_params) for sc in scheds)
                tensors = self._sampling_tensors(key, params_list, salts)
            with phase("omni.assemble.put", trace=False):
                token_ids = self._feed_tokens(token_host, feed, prev)
                args = (
                    jnp.asarray(positions), jnp.asarray(slots),
                    jnp.asarray(tables), jnp.asarray(ctx),
                    tensors.temperature, tensors.top_k, tensors.top_p,
                    tensors.keys, *map(jnp.asarray, slot_cols))
        outs, self.kv_caches = self._run_jit(
            kind, (b, self._kv_quant), lambda: fn(
                self.params, token_ids, self.kv_caches, *args))
        return InflightDecode(
            tokens=outs["tokens"],
            rows={sc.request.request_id: i for i, sc in enumerate(scheds)},
            outs=outs, kind="decode", scheds=list(scheds),
            gens=[s.request.async_generation for s in scheds],
        )

    def _feed_tokens(self, token_host: np.ndarray,
                     feed: list[tuple[int, int]],
                     prev: Optional[InflightDecode]) -> jax.Array:
        """A step's token ids on the device.  ``feed`` pairs a row of
        ``token_host`` with the row of ``prev.tokens`` that holds its
        input token (sampled by the dispatch still in flight, never seen
        by the host).  The pairs ride two columns (row fed, row taken) as
        long as the top batch bucket, so the two programs' shapes follow the buckets of
        ``prev.tokens`` and of ``token_host`` and never the number of rows
        fed; ``precompile`` warms both lines.  Under a mesh the ids are
        placed replicated whether fed or not (a fed array comes back
        committed to the mesh, and an executable warmed on an uncommitted
        one would compile again for it)."""
        rep = self._replicated
        token_ids = (jnp.asarray(token_host) if rep is None
                     else jax.device_put(token_host, rep))
        if not feed:
            return token_ids
        cols = np.empty((2, self._batch_buckets[-1]), np.int32)
        cols[0], cols[1] = token_host.shape[0], 0
        cols[:, : len(feed)] = np.asarray(feed, np.int32).T
        cols = jnp.asarray(cols)  # one put for both columns
        token_ids = _put_rows(token_ids, cols,
                              _take_rows(prev.tokens, cols))
        return token_ids if rep is None else jax.device_put(token_ids, rep)

    # ------------------------------------------------------------- retire
    def retire_step(self, handle: InflightDecode
                    ) -> dict[str, "int | list[int]"]:
        """Retire half: the ONE host readback of a step, lagged a full
        step behind dispatch in the async pipeline so it overlaps the
        next step's device compute.  Unpacks tokens (plain ints or
        spec-accepted lists), appends logprob entries and hidden
        chunks, and proposes the next drafts — every per-request side
        effect of the step happens here, behind the single transfer."""
        with self.phases.phase("omni.readback") as p:
            # omnilint: disable=OL2 - the single lagged retire sync of the
            # async pipeline: by the time the engine calls this, the NEXT
            # step is already dispatched, so this get overlaps its compute
            outs = jax.device_get(handle.outs)
            # up to here the phase waited on the device; the rest unpacks
            p.waited()
            return self._unpack(handle, outs)

    def _unpack(self, handle: InflightDecode, outs: dict
                ) -> dict[str, "int | list[int]"]:
        self.sampler_steps[int(outs["sampler_path"])] += 1
        sampled: dict[str, "int | list[int]"] = {}
        if handle.kind == "decode":
            toks = np.asarray(outs["tokens"])
            for rid, i in handle.rows.items():
                sampled[rid] = int(toks[i])
            self._retire_side_effects(handle, outs, sampled)
            return sampled
        toks = np.asarray(outs["tokens"])      # [S, V]
        counts = np.asarray(outs["counts"])    # [S]
        for rid, i in handle.rows.items():
            sc = handle.scheds[i]
            if i in handle.spec_rows:
                # spec verify row: the accepted run, trimmed at the
                # first stop condition so downstream payloads align
                acc = [int(x) for x in toks[i, : max(int(counts[i]), 1)]]
                acc = self._truncate_at_stop(sc.request, acc)
                sampled[rid] = acc
                if not sc.request.is_finished \
                        and handle.gens[i] == sc.request.async_generation:
                    # overshoot / preempt-readmit rows are discarded by
                    # the scheduler — keep them out of the acceptance
                    # telemetry the flight-recorder honesty rides on
                    self.spec_stats["proposed"] += sc.num_new_tokens - 1
                    self.spec_stats["accepted"] += len(acc) - 1
            else:
                sampled[rid] = int(toks[i, 0])
        self._retire_side_effects(handle, outs, sampled)
        return sampled

    # engine compatibility alias (the PR 4 pipeline called the pure
    # decode retire by this name)
    retire_decode = retire_step

    def _retire_side_effects(self, handle: InflightDecode, outs: dict,
                             sampled: dict) -> None:
        """Logprob entries, hidden chunks, and draft proposals for the
        retired step.  Rows whose request finished or was
        preempted-and-readmitted while the step was in flight are
        SKIPPED — the scheduler discards their token (the overshoot
        contract), so appending their side effects would misalign the
        per-token streams."""
        live: list[tuple[int, ScheduledRequest]] = []
        for i, sc in enumerate(handle.scheds):
            req = sc.request
            if req.is_finished or handle.gens[i] != req.async_generation:
                # overshoot (finished at a previous retire) or
                # preempt-and-readmit mid-flight: the scheduler discards
                # the token; discard its side effects with it
                sampled.pop(req.request_id, None)
                continue
            live.append((i, sc))
        # logprobs: trim the static top-K to each request's ask
        if "lp_chosen" in outs:
            chosen = np.asarray(outs["lp_chosen"])
            top_v = np.asarray(outs["lp_topv"])
            top_i = np.asarray(outs["lp_topi"])
            for i, sc in live:
                req = sc.request
                if req.sampling_params.logprobs is None:
                    continue
                if sc.request.request_id not in sampled:
                    continue
                kk = min(LOGPROBS_K,
                         int(req.sampling_params.logprobs or 0))
                req.output_logprobs.append({
                    "logprob": float(chosen[i]),
                    "top_ids": top_i[i, :kk].tolist(),
                    "top_logprobs": top_v[i, :kk].tolist(),
                })
        if self.collect_hidden and "hidden" in outs:
            hidden = np.asarray(outs["hidden"])
            for i, sc in live:
                req = sc.request
                if handle.kind == "decode":
                    rows = hidden[i: i + 1]
                else:
                    lo = int(handle.asm.cu_q_lens[i])
                    s = sampled.get(req.request_id)
                    if isinstance(s, list):
                        # verify row: only accepted positions shipped
                        rows = hidden[lo: lo + len(s)]
                    else:
                        rows = hidden[lo: lo + sc.num_new_tokens]
                prev = req.additional_information.get("_hidden_chunks")
                if prev is None:
                    req.additional_information["_hidden_chunks"] = [
                        np.asarray(rows)]
                else:
                    prev.append(np.asarray(rows))
        self._maybe_draft(handle, outs, sampled, live)

    # ------------------------------------------------- speculative drafts
    def _maybe_draft(self, handle: InflightDecode, outs: dict,
                     sampled: dict, live) -> None:
        """Propose the next k tokens for every request that sampled this
        step (spec decode draft phase).  The hidden rows at each row's
        last ACCEPTED position were gathered ON DEVICE by the step
        (``accept_hidden``); one draft-head dispatch serves the whole
        batch.

        Known pipelined transient: on ENTRY into spec mode (the step
        after a prefill or pipeline bubble), the next schedule may pair
        these drafts with an input token that was still in flight when
        they were proposed — that one verify tests the drafts one
        position late, so its acceptance is ~0 and it degrades to
        plain-decode progress for a step.  Steady-state verifies (the
        hold-then-retire cadence) always pair fresh drafts with a
        host-visible input; correctness is unaffected either way (the
        accept mask only ever admits true target tokens)."""
        if self.draft_fn is None or self.num_draft_tokens <= 0:
            return
        ah = outs.get("accept_hidden")
        if ah is None:
            # pure-decode handle: the row's hidden IS the accept hidden
            ah = outs.get("hidden")
        rows, toks, poss, reqs, ctxs = [], [], [], [], []
        for i, sc in live:
            req = sc.request
            s = sampled.get(req.request_id)
            if s is None:
                continue
            if req.sampling_params.logprobs is not None:
                # multi-token verify accepts have no per-token sampling
                # distribution to report — logprobs requests stay on the
                # one-token-per-step path so entries align 1:1
                continue
            if req.is_finished:
                continue
            new = s if isinstance(s, list) else [s]
            # position where the just-sampled token will be computed:
            # the per-token advance for spec lists, the full chunk
            # width for int samples (a prefill covers num_new_tokens
            # positions, not one); mrope models shift generated
            # positions by delta
            adv = len(new) if isinstance(s, list) else sc.num_new_tokens
            pos = sc.start_pos + adv
            if self.use_mrope:
                pos += req.mrope_delta
            rows.append(i)
            toks.append(new[-1])
            poss.append(pos)
            reqs.append(req)
            if self._draft_takes_contexts:
                # full post-step history (the just-sampled tokens are
                # not yet appended to the request at draft time); built
                # only for drafters that want it — it is an O(n) copy
                ctxs.append(req.all_token_ids + list(new))
        if not rows:
            return
        if ah is None:
            # a decode handle built without hidden output (no
            # collect_hidden): decode batches cannot draft — the
            # engine routes drafted requests through the unified
            # dispatch (their verify rows have num_new_tokens > 1), so
            # this only skips the very first post-prefill proposal of
            # a request that landed in a pure-decode batch; it drafts
            # at its next unified step
            return
        ah = np.asarray(ah)
        m = len(rows)
        mb = _bucket(m, self._batch_buckets)
        hh = np.zeros((mb,) + ah.shape[1:], ah.dtype)
        hh[:m] = ah[np.asarray(rows)]
        tt = np.zeros((mb,), np.int32)
        tt[:m] = toks
        pp = np.zeros((mb,), np.int32)
        pp[:m] = poss
        kwargs = {"contexts": ctxs} if self._draft_takes_contexts else {}
        # omnilint: disable=OL2 - batch boundary: drafts feed next schedule
        drafts = np.asarray(jax.device_get(
            self.draft_fn(jnp.asarray(hh), jnp.asarray(tt),
                          jnp.asarray(pp), **kwargs)
        ))
        for r, req in enumerate(reqs):
            req.spec_draft_tokens = [int(x) for x in drafts[r]]

    # ---------------------------------------------------- mrope positions
    def _mrope_cols(self, req, p: np.ndarray) -> np.ndarray:
        """[3, len(p)] position columns for global token indices ``p``:
        prompt rows come from the request's precomputed table, generated
        rows sit at p + delta on all three streams."""
        mp = req.mrope_positions
        if mp is None:
            return np.broadcast_to(p, (3, len(p)))
        mp = np.asarray(mp)
        out = np.empty((3, len(p)), np.int32)
        in_prompt = p < mp.shape[1]
        out[:, in_prompt] = mp[:, p[in_prompt]]
        out[:, ~in_prompt] = p[~in_prompt][None, :] + req.mrope_delta
        return out

    # -------------------------------------------------------------- decode
    def _assemble_decode_rows(self, scheds: list[ScheduledRequest], b: int):
        """Padded (positions, slots, tables, ctx) rows for a
        single-token decode batch — ONE assembly shared by the
        synchronous decode and the pipelined dispatch, so their input
        semantics (mrope columns, ctx = start_pos + 1, table
        truncation) cannot drift apart."""
        positions = (np.zeros((b, 3), np.int32) if self.use_mrope
                     else np.zeros((b,), np.int32))
        slots = np.full((b,), -1, np.int32)
        tables = np.zeros((b, self.max_pages_per_seq), np.int32)
        ctx = np.zeros((b,), np.int32)
        for i, sc in enumerate(scheds):
            if self.use_mrope:
                positions[i] = self._mrope_cols(
                    sc.request, np.asarray([sc.start_pos]))[:, 0]
            else:
                positions[i] = sc.start_pos
            slots[i] = sc.slot_mapping[0]
            t = sc.block_table[: self.max_pages_per_seq]
            tables[i, : len(t)] = t
            ctx[i] = sc.start_pos + 1
        return positions, slots, tables, ctx

    # ----------------------------------------------- sampling host caches
    def _salt_of(self, request_id: str) -> int:
        """Cached zlib.crc32 sampling salt (recomputing it for every
        request every step was measurable in the step-phase breakdown)."""
        s = self._salt_cache.get(request_id)
        if s is None:
            if len(self._salt_cache) > 8192:
                self._salt_cache.clear()
            s = self._salt_cache[request_id] = zlib.crc32(
                request_id.encode())
        return s

    def _sampling_tensors(self, key: tuple, params_list, salts
                          ) -> SamplingTensors:
        """SamplingTensors for this batch, reused across steps while the
        (request set, params) composition is unchanged.  Only the PRNG
        keys fold the step index, so a cache hit re-keys in one tiny
        dispatch — and an all-greedy batch (keys unused by argmax) skips
        even that."""
        hit = self._st_cache.get(key)
        if hit is not None:
            tensors, any_sampling = hit
            return tensors.rekey(self._step) if any_sampling else tensors
        tensors = SamplingTensors.build(
            params_list, step=self._step, base_seed=self._base_seed,
            salts=salts,
        )
        if len(self._st_cache) > 8:
            self._st_cache.clear()
        self._st_cache[key] = (
            tensors, any(p.temperature > 0.0 for p in params_list))
        return tensors

    # ----------------------------------------------------------- stopping
    @staticmethod
    def _truncate_at_stop(req, acc: list[int]) -> list[int]:
        """Trim an accepted spec run at the first stop condition (eos /
        stop token / max_tokens), keeping the stopping token.  The
        scheduler re-checks per appended token; trimming here keeps the
        collect_hidden payload aligned with the tokens actually emitted
        (hidden rows past the stop would otherwise ship downstream)."""
        sp = req.sampling_params
        eos = req.eos_token_id
        n_out = len(req.output_token_ids)
        for idx, t in enumerate(acc):
            n = n_out + idx + 1
            if n >= sp.min_tokens:
                eos_hit = (t in eos if isinstance(eos, (list, tuple))
                           else t == eos) if eos is not None else False
                if (not sp.ignore_eos and eos_hit) \
                        or t in sp.stop_token_ids:
                    return acc[: idx + 1]
            if n >= sp.max_tokens:
                return acc[: idx + 1]
        return acc

    # -------------------------------------------------------- kv injection
    def inject_kv(self, block_ids: list[int], payload: list) -> int:
        """Scatter a per-layer KV payload into the given pages — the
        receive half of the transfer manager (reference:
        omni_connectors/kv_transfer_manager.py:100+ receive path, which r1
        lacked: extracted KV had nowhere to land) and of the kvcache
        tier-restore path (docs/kv_cache.md).  The whole payload ships
        host->device as ONE pytree transfer, not 2 transfers per layer.
        Returns seq_len.

        Payloads arrive dense ([Hkv, seq, D]) or quantized (the
        kvcache/quant.py wire layout).  Quantized into an int8 pool is
        an EXACT page set (data bytes + per-page scales land verbatim —
        the cross-path no-double-quantize contract); quantized into a
        dense pool dequantizes first; dense into an int8 pool quantizes
        through the write op's shared rounding."""
        self._refuse(cache_layouts.TRANSFER, "inject_kv",
                     NotImplementedError)
        if len(payload) != len(self.kv_caches):
            raise ValueError(
                f"KV payload has {len(payload)} layers, cache has "
                f"{len(self.kv_caches)}"
            )
        quant_in = is_quant_payload(payload)
        if quant_in and not self._kv_quant:
            payload = dequantize_payload(payload, self.page_size)
            quant_in = False
        seq_len = payload_seq_len(payload)
        if quant_in:
            return self._inject_kv_exact(block_ids, payload, seq_len)
        pos = np.arange(seq_len)
        slots = jnp.asarray(
            np.asarray(block_ids, np.int64)[pos // self.page_size]
            * self.page_size + pos % self.page_size,
            jnp.int32,
        )
        device_payload = jax.device_put(
            [(np.asarray(k), np.asarray(v)) for k, v in payload])
        new_caches = []
        for (k_cache, v_cache), (k, v) in zip(self.kv_caches,
                                              device_payload):
            kt = jnp.moveaxis(k, 0, 1)  # [seq, Hkv, D]
            vt = jnp.moveaxis(v, 0, 1)
            k_cache, v_cache = write_kv_cache(k_cache, v_cache, kt, vt, slots)
            new_caches.append((k_cache, v_cache))
        self.kv_caches = new_caches
        return seq_len

    def _inject_kv_exact(self, block_ids: list[int], payload: list,
                         seq_len: int) -> int:
        """int8 wire payload -> int8 pool: page-granular set of data
        bytes and scales, bit-exact (no re-quantization).  The run's
        trailing partial page pads with zeros — those rows sit past
        every context length, and the settled page scale stays valid
        for later decode appends into the same page."""
        ps = self.page_size
        n_pages = min(len(block_ids), -(-seq_len // ps))
        ids = jnp.asarray(block_ids[:n_pages], jnp.int32)
        pad = n_pages * ps - seq_len

        def to_pages(q):
            a = np.asarray(q)[:, : n_pages * ps]
            if pad:
                a = np.pad(a, ((0, 0), (0, pad), (0, 0)))
            return a.reshape(a.shape[0], n_pages, ps, a.shape[-1])

        host = [((to_pages(kq), np.asarray(ks)[:, :n_pages]),
                 (to_pages(vq), np.asarray(vs)[:, :n_pages]))
                for (kq, ks), (vq, vs) in payload]
        dev = jax.device_put(host)
        new_caches = []
        for (k_half, v_half), ((kp, ks), (vp, vs)) in zip(
                self.kv_caches, dev):
            kd, ksc = k_half
            vd, vsc = v_half
            new_caches.append((
                (kd.at[:, ids].set(kp), ksc.at[:, ids].set(ks)),
                (vd.at[:, ids].set(vp), vsc.at[:, ids].set(vs)),
            ))
        self.kv_caches = new_caches
        return seq_len

    # -------------------------------------------------------- kv extraction
    def _refuse(self, mechanism: str, what: Optional[str] = None,
                error=ValueError) -> None:
        """Raise where this model's cache layouts cannot carry
        ``mechanism`` (kvcache/layouts.py), naming the entry point."""
        reason = self._cannot_carry[mechanism]
        if reason:
            raise error(f"{what or mechanism}: {reason}")

    def _extract_layer_slices(self, ids, seq_len: int) -> list:
        """Per-layer device slices for one page run.  Dense pools emit
        [Hkv, seq_len, D] halves; int8 pools emit the quantized wire
        layout ((data[:, :seq_len], page scales)) — the bytes leave the
        device as stored, so a later inject restores them bit-exact."""
        self._refuse(cache_layouts.TRANSFER, "extract_kv",
                     NotImplementedError)
        slices = []
        for k_cache, v_cache in self.kv_caches:
            if isinstance(k_cache, tuple):
                layer = []
                for data, scale in (k_cache, v_cache):
                    q = data[:, ids].reshape(
                        data.shape[0], -1, data.shape[-1])
                    layer.append((q[:, :seq_len], scale[:, ids]))
                slices.append(tuple(layer))
            else:
                k = k_cache[:, ids].reshape(
                    k_cache.shape[0], -1, k_cache.shape[-1])
                v = v_cache[:, ids].reshape(
                    v_cache.shape[0], -1, v_cache.shape[-1])
                slices.append((k[:, :seq_len], v[:, :seq_len]))
        return slices

    @staticmethod
    def _host_payload(slices: list) -> list:
        return [tuple(
            tuple(np.asarray(a) for a in half)
            if isinstance(half, tuple) else np.asarray(half)
            for half in layer) for layer in slices]

    def extract_kv(self, block_ids: list[int], seq_len: int) -> list:
        """Gather the pages holding ``seq_len`` tokens into a per-layer
        payload (device half of OmniKVTransferManager): dense
        [Hkv, seq_len, D] halves, or the kvcache/quant.py wire layout
        when the pool is int8."""
        ids = jnp.asarray(block_ids, jnp.int32)
        slices = self._extract_layer_slices(ids, seq_len)
        # ONE transfer for the whole payload — 2 syncs per LAYER before
        # the first omnilint OL2 harvest (a 28-layer model paid 56
        # host round trips per extraction)
        # omnilint: disable=OL2
        payload = jax.device_get(slices)
        return self._host_payload(payload)

    def extract_kv_batch(self, specs: list[tuple[list[int], int]]
                         ) -> list[list]:
        """``extract_kv`` for SEVERAL page runs in one device round
        trip: [(block_ids, seq_len)] -> one payload each.  The kvcache
        tier drain uses this so a step that evicts/park-extracts many
        payloads still costs ONE host sync (docs/kv_cache.md)."""
        all_slices = []
        for block_ids, seq_len in specs:
            ids = jnp.asarray(block_ids, jnp.int32)
            all_slices.append(self._extract_layer_slices(ids, seq_len))
        # omnilint: disable=OL2 - ONE batched transfer for every
        # payload this step parks (the whole point of the batch API)
        payloads = jax.device_get(all_slices)
        return [self._host_payload(sl) for sl in payloads]
