"""Live roofline attribution: per-step achieved MFU / MBU, zero syncs.

"The Anatomy of a Triton Attention Kernel" (PAPERS.md) makes the case
for attributing achieved FLOPs and bytes per dispatch.  This module is
the live face (the offline one is benchmark/trace.py, over device time):
every engine step self-reports how close it ran to the hardware
roofline, from quantities the step loop already holds on the host:

- **achieved FLOPs** = static model geometry × the step's useful-token
  mix.  Dense matmul cost is ``2 × active-params`` per token (MoE
  counts the routed top-k experts + shared expert, not the resident
  total); attention adds ``4 × heads × head_dim × layers`` per
  (new-token × context-position) pair; the LM head bills per sampled
  row.  Context sums come from the scheduler's ``start_pos`` /
  ``num_new_tokens`` — host ints, **zero device syncs** (the same
  stance as the PR 8 memory ledger; this module lives in the omnilint
  OL2 HOT_PATHS manifest).
- **achieved HBM bytes** = the weights a dispatch touches, read once
  (everything but the routed experts, plus as many experts per layer
  as the step's tokens are expected to route to,
  ``E x (1 - (1 - k/E)^tokens)``: 8 of 128 for one row, ~126 for 64 —
  the count ``benchmark/trace.py`` takes) + KV read over every
  attended context position + KV write for every new position.
  Decode is the bandwidth-bound phase; this is the quantity that
  explains why its MFU is structurally low.
- **denominators** come from ``platforms/`` (``peak_tflops_bf16`` /
  ``peak_hbm_gbps``) and the step's WALL time — the operator quantity:
  host stalls and pipeline bubbles count against utilization, exactly
  as they count against goodput.  Shares of a roofline over DEVICE
  time are the benchmark's job (benchmark/span_readers.py).

Surfaces: ``engine_step_mfu`` / ``engine_step_mbu{phase}`` gauges on
/metrics (rolling-window means), per-record ``mfu``/``mbu``/``phase``
fields in the flight recorder (record schema v3), and the rolling
window on ``/debug/engine``.
"""

from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass
from typing import Optional

from vllm_omni_tpu.analysis.runtime import traced

#: rolling-window length (steps) for the /metrics gauges — long enough
#: to smooth scheduler jitter, short enough that a regime change (batch
#: collapse, drained replica) shows within seconds
DEFAULT_WINDOW = 128


@dataclass(frozen=True)
class ModelGeometry:
    """Static per-token cost model of one transformer forward.

    All quantities are per-DEVICE (divide by TP degree upstream if the
    runner shards; today the engine computes per-process totals against
    the per-chip peak, which is exact for TP=1 and conservative
    otherwise)."""

    #: dense matmul FLOPs per token (projections + MLP + norms ~ 0)
    flops_per_token: float
    #: attention FLOPs per (new token × attended context position):
    #: QK^T + AV = 4 × heads × head_dim per layer, summed over layers
    attn_flops_per_ctx: float
    #: LM-head FLOPs per sampled row (2 × hidden × vocab)
    lm_head_flops_per_row: float
    #: bytes of the weights every dispatch reads: all but the routed
    #: experts
    weight_bytes: float
    #: KV-cache bytes per token position (all layers, K+V)
    kv_bytes_per_pos: float
    #: routed experts (all 0 for a dense model): bytes of one expert in
    #: one layer, experts per layer, experts per token, layers
    expert_bytes: float = 0.0
    num_experts: int = 0
    experts_per_tok: int = 0
    num_layers: int = 0

    @classmethod
    def from_transformer_config(cls, cfg, dtype_bytes: int
                                ) -> "ModelGeometry":
        """Derive the cost model from a ``TransformerConfig``.  MoE
        FLOPs count ACTIVE parameters per token (top-k routed + shared
        expert); MoE bytes count the experts a step's tokens touch
        (``step_bytes``); attention uses the dense per-layer shape.
        In a hybrid stack only the softmax-attention layers attend a
        context and hold pages (``num_kv_layers``); a linear-attention
        layer's mixer is counted at an attention layer's parameters, a
        few per cent from its own (the benchmark's arch module counts
        it exactly, benchmark/archs/).  A latent-attention model's
        projections and leading dense layers are counted likewise at an
        attention layer's and a sparse layer's parameters; its cached
        bytes are its layout's."""
        h = cfg.hidden_size
        kv_layers = getattr(cfg, "num_kv_layers", cfg.num_layers)
        q_dim = cfg.num_heads * cfg.head_dim
        kv_dim = cfg.num_kv_heads * cfg.head_dim
        layout = cfg.kv_layout
        attn_params = h * q_dim + 2 * h * kv_dim + q_dim * h
        expert_params = experts = top_k = 0
        if getattr(cfg, "moe", False):
            inter = cfg.moe_intermediate_size or cfg.intermediate_size
            experts, top_k = cfg.num_experts, cfg.num_experts_per_tok
            expert_params = 3 * h * inter
            # read by every dispatch: the router and the shared expert
            fixed_mlp = h * experts + (
                3 * h * cfg.shared_expert_size
                if getattr(cfg, "shared_expert_size", 0) else 0)
        else:
            fixed_mlp = 3 * h * cfg.intermediate_size
        active_params = cfg.num_layers * (
            attn_params + fixed_mlp + top_k * expert_params)
        return cls(
            flops_per_token=2.0 * active_params,
            attn_flops_per_ctx=(4.0 * cfg.num_heads * cfg.head_dim
                                * kv_layers),
            lm_head_flops_per_row=2.0 * h * cfg.vocab_size,
            weight_bytes=float(dtype_bytes * (
                cfg.num_layers * (attn_params + fixed_mlp)
                + h * cfg.vocab_size)),
            # what a cached position holds, by the layout (a K and a V
            # row a kv head, or a latent layer's one row)
            kv_bytes_per_pos=float(
                kv_layers * layout.heads * layout.lanes_used
                * dtype_bytes),
            expert_bytes=float(expert_params * dtype_bytes),
            num_experts=experts, experts_per_tok=top_k,
            num_layers=cfg.num_layers,
        )

    # ----------------------------------------------------------- costs
    def step_flops(self, new_tokens: int, ctx_positions: float,
                   sampled_rows: int) -> float:
        """Achieved FLOPs of one step: ``new_tokens`` computed
        positions attending over ``ctx_positions`` total (new × ctx
        pairs, summed by the caller from start_pos/num_new_tokens),
        with ``sampled_rows`` LM-head rows."""
        return (self.flops_per_token * new_tokens
                + self.attn_flops_per_ctx * ctx_positions
                + self.lm_head_flops_per_row * sampled_rows)

    def experts_touched(self, new_tokens: int) -> float:
        """Experts of one layer that ``new_tokens`` tokens are expected
        to route to under uniform routing, ``E x (1 - (1 - k/E)^n)``:
        ``k`` for one token, nearly all ``E`` for a full batch."""
        e, k = self.num_experts, self.experts_per_tok
        if e <= 0 or new_tokens <= 0:
            return 0.0
        return e * (1.0 - (1.0 - k / e) ** new_tokens)

    def step_bytes(self, new_tokens: int, ctx_positions: float) -> float:
        """Achieved HBM traffic of one step: the weights it touches
        read once (a 64-row step reads ~126 of 128 experts a layer,
        not 8), KV read per attended position, KV written per new
        position."""
        return (self.weight_bytes
                + (self.num_layers * self.experts_touched(new_tokens)
                   * self.expert_bytes)
                + self.kv_bytes_per_pos * ctx_positions
                + self.kv_bytes_per_pos * new_tokens)

    def arithmetic_intensity(self, new_tokens: int, ctx_positions: float,
                             sampled_rows: int) -> float:
        """FLOPs per HBM byte for a given token mix — the roofline
        x-axis.  Structural property of the geometry: prefill (many new
        tokens per dispatch) is always denser than single-token decode."""
        b = self.step_bytes(new_tokens, ctx_positions)
        if b <= 0:
            return 0.0
        return self.step_flops(new_tokens, ctx_positions,
                               sampled_rows) / b


def ctx_positions(start_pos: int, num_new: int) -> float:
    """Total attended context positions for ``num_new`` tokens appended
    from ``start_pos`` under causal attention: token i attends over
    ``start_pos + i + 1`` positions."""
    n = max(int(num_new), 0)
    return n * max(int(start_pos), 0) + n * (n + 1) / 2.0


def capped_ctx_positions(start_pos: int, num_new: int, cap: int) -> float:
    """``ctx_positions`` where a token attends at most ``cap`` keys (a
    sliding window, or a learned selection of the ``cap`` best): token i
    attends ``min(start_pos + i + 1, cap)`` positions."""
    n, start = max(int(num_new), 0), max(int(start_pos), 0)
    under = min(max(cap - start, 0), n)  # tokens that still see < cap keys
    return ctx_positions(start, under) + (n - under) * float(cap)


class RooflineTracker:
    """Rolling per-step MFU/MBU window for one engine.

    Thread contract: ``on_step`` is called by the engine thread inside
    the step loop (host math only); ``snapshot`` by the /metrics and
    /debug HTTP threads — ``_lock`` guards the window and the phase
    aggregates (declared in the omnilint LOCK_GUARDS manifest)."""

    def __init__(self, geometry: ModelGeometry, peak_tflops: float,
                 peak_gbps: float, window: int = DEFAULT_WINDOW):
        self.geometry = geometry
        self.peak_flops = max(float(peak_tflops), 0.0) * 1e12
        self.peak_bytes = max(float(peak_gbps), 0.0) * 1e9
        self._lock = traced(threading.Lock(), "RooflineTracker._lock")
        # (phase, mfu, mbu) per recent step
        self._window: deque = deque(maxlen=max(int(window), 1))
        self._flops_total = 0.0
        self._bytes_total = 0.0

    def on_step(self, *, prefill_tokens: int, prefill_ctx: float,
                decode_tokens: int, decode_ctx: float,
                sampled_rows: int, wall_s: float) -> Optional[dict]:
        """Account one step; returns {"mfu","mbu","phase"} for the
        flight record, or None when nothing was computed.  Values are
        clamped to [0, 1] — the cost model is an estimate and the wall
        clock is host-observed; a >1 reading is model error, not free
        FLOPs."""
        new_tokens = prefill_tokens + decode_tokens
        if new_tokens <= 0 or wall_s <= 0:
            return None
        g = self.geometry
        ctx = prefill_ctx + decode_ctx
        flops = g.step_flops(new_tokens, ctx, sampled_rows)
        nbytes = g.step_bytes(new_tokens, ctx)
        mfu = (min(flops / (wall_s * self.peak_flops), 1.0)
               if self.peak_flops > 0 else 0.0)
        mbu = (min(nbytes / (wall_s * self.peak_bytes), 1.0)
               if self.peak_bytes > 0 else 0.0)
        # phase honesty: a token-packed step carrying BOTH prefill and
        # decode rows (the norm under unified batching) is "mixed" — a
        # one-phase label would bill its bytes (mostly decode KV
        # traffic) to the prefill gauge and starve the decode one
        # exactly when traffic is heaviest
        if prefill_tokens > 0 and decode_tokens > 0:
            phase = "mixed"
        elif prefill_tokens > 0:
            phase = "prefill"
        else:
            phase = "decode"
        with self._lock:
            self._window.append((phase, mfu, mbu))
            self._flops_total += flops
            self._bytes_total += nbytes
        # no rounding: a compile-laden step on a tiny model reads
        # ~1e-9 MFU, and rounding that to 0.0 would turn "barely
        # utilized" into "did nothing"
        return {"mfu": mfu, "mbu": mbu, "phase": phase}

    def snapshot(self, recent: int = 32) -> dict:
        """JSON-ready rolling view: window means for the gauges
        (``mfu``; ``mbu`` split per phase) + the last ``recent`` steps
        for /debug/engine."""
        with self._lock:
            win = list(self._window)
            flops_total, bytes_total = self._flops_total, self._bytes_total
        by_phase: dict[str, list] = {}
        for phase, _, mbu in win:
            by_phase.setdefault(phase, []).append(mbu)
        mfus = [m for _, m, _ in win]
        return {
            "mfu": sum(mfus) / len(mfus) if mfus else 0.0,
            "mbu": {p: sum(v) / len(v)
                    for p, v in sorted(by_phase.items())},
            "window_steps": len(win),
            "peak_tflops": round(self.peak_flops / 1e12, 3),
            "peak_hbm_gbps": round(self.peak_bytes / 1e9, 3),
            "model_flops_total": flops_total,
            "model_hbm_bytes_total": bytes_total,
            "recent": ([{"phase": p, "mfu": m, "mbu": b}
                        for p, m, b in win[-int(recent):]]
                       if recent and int(recent) > 0 else []),
        }


__all__ = ["ModelGeometry", "RooflineTracker", "ctx_positions",
           "DEFAULT_WINDOW"]
