"""Bytes-saved-vs-recompute admission for the KV cold path.

Parking KV is only a win when moving the bytes (twice: out now, back on
restore) beats recomputing the same tokens through a prefill.  The
break-even (docs/performance.md):

    t_offload + t_restore  <  t_recompute
    2 * (fixed_s + n*Bpt/bw)  <  n / prefill_tps

with ``Bpt`` = 2 (K+V) * layers * kv_heads * head_dim * dtype_bytes per
token, halved under int8 cold-path quantization.  Both sides are linear
in ``n`` past the fixed per-transfer overhead, so the policy reduces to
a per-token comparison plus a minimum-size gate: tiny payloads never
amortize the dispatch + connector round trip.

``mode``: ``always`` / ``never`` pin the decision (``never`` is the kill
switch — the scheduler degrades to recompute-preemption); ``auto`` runs
the break-even math on the host<->HBM bandwidth and prefill rate the
deployment MEASURED and passed in.  No rate is assumed for a machine the
code does not know: ``auto`` without them is refused.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass
class OffloadPolicy:
    mode: str = "auto"                 # "auto" | "always" | "never"
    # the deployment's measured host<->HBM bandwidth, and what recompute
    # costs: the engine's sustained prefill throughput.  Required by
    # "auto", unused otherwise
    host_bandwidth_bytes_s: Optional[float] = None
    prefill_tokens_per_s: Optional[float] = None
    fixed_transfer_s: float = 5e-3           # dispatch + gather overhead
    # per-token KV footprint; 0 until bound to a model config
    bytes_per_token: int = 0
    # cold-path storage: "none" keeps bf16/f32 payloads bit-exact
    # (restored greedy streams match the never-offloaded oracle);
    # "int8" halves-to-quarters the moved bytes at a bounded KV error
    quant_mode: str = "none"
    # margin: offload only when the transfer wins by this factor
    safety: float = 1.0

    def __post_init__(self):
        if self.mode not in ("auto", "always", "never"):
            raise ValueError(f"unknown offload policy mode {self.mode!r}")
        if self.quant_mode not in ("none", "int8"):
            raise ValueError(
                f"unknown cold-path quant mode {self.quant_mode!r}")
        if self.mode == "auto" and not (self.host_bandwidth_bytes_s
                                        and self.prefill_tokens_per_s):
            raise ValueError(
                "offload policy 'auto' needs the measured "
                "host_bandwidth_bytes_s and prefill_tokens_per_s of this "
                "deployment (none is assumed); pass them to "
                "OffloadPolicy, or pin kv_offload_policy to 'always' or "
                "'never'")

    @classmethod
    def for_model(cls, num_layers: int, num_kv_heads: int, head_dim: int,
                  dtype_bytes: int, **kw) -> "OffloadPolicy":
        bpt = 2 * num_layers * num_kv_heads * head_dim * dtype_bytes
        return cls(bytes_per_token=bpt, **kw)

    # ------------------------------------------------------------- sizes
    def cold_bytes_per_token(self) -> float:
        """Bytes per token actually moved on the cold path (quantized
        payloads ship int8 values + a float32 scale per head)."""
        if self.quant_mode == "int8" and self.bytes_per_token:
            # int8 body is bytes_per_token / dtype_bytes... the scale
            # overhead is per (layer, head), amortized over page_size
            # tokens — negligible; approximate as a clean ratio
            return self.bytes_per_token / 2.0
        return float(self.bytes_per_token)

    # ---------------------------------------------------------- decision
    def transfer_seconds(self, num_tokens: int) -> float:
        """One direction: fixed overhead + bytes over the host link."""
        return (self.fixed_transfer_s
                + num_tokens * self.cold_bytes_per_token()
                / self.host_bandwidth_bytes_s)

    def recompute_seconds(self, num_tokens: int) -> float:
        return num_tokens / self.prefill_tokens_per_s

    def worth_offloading(self, num_tokens: int) -> bool:
        """Should ``num_tokens`` of KV be parked instead of dropped?
        Counts BOTH directions of the round trip — parked bytes only
        pay off if they come back cheaper than recomputing them."""
        if self.mode == "always":
            return num_tokens > 0
        if self.mode == "never" or num_tokens <= 0:
            return False
        round_trip = 2.0 * self.transfer_seconds(num_tokens)
        return round_trip * self.safety < self.recompute_seconds(
            num_tokens)

    def worth_offloading_page(self, num_tokens: int) -> bool:
        """The per-PAGE eviction decision: like ``worth_offloading``
        but WITHOUT the fixed per-transfer overhead — evicted pages
        ride the step's batched extraction (one device round trip for
        every payload, ``extract_kv_batch``), so the fixed cost
        amortizes across the batch and the benefit scales with the
        whole adopted chain.  Judging one page against the full fixed
        cost would make 'auto' a de-facto 'never' for prefix pages."""
        if self.mode == "always":
            return num_tokens > 0
        if self.mode == "never" or num_tokens <= 0:
            return False
        stream = 2.0 * num_tokens * self.cold_bytes_per_token() \
            / self.host_bandwidth_bytes_s
        return stream * self.safety < self.recompute_seconds(num_tokens)
