"""Shared absmax int8 quantization for KV caches (resident + cold).

One module owns the scale layout and rounding so the PR 6 cold-offload
quantizer (``kvcache/tiers.py``) and the PR 20 HBM-resident quantized
page pool (``ops/paged_attention.py`` / ``ops/ragged_paged_attention.py``)
can never drift apart:

- rounding:   ``q = clip(round(x / scale), -127, 127)`` as int8,
  ``scale = max(absmax / 127, SCALE_EPS)`` as float32 — symmetric absmax,
  the same stance as ``diffusion/quantization``.
- resident layout: each paged cache half becomes a 2-tuple
  ``(data int8 [Hkv, P, page_size, D], scale f32 [Hkv, P])`` — ONE scale
  per (kv-head, page) so the ragged kernel's page DMA fetches a page's
  bytes plus a single scalar per head and dequantizes in-register.
- wire layout (extract/inject/disagg handoff): per-layer
  ``[((kq, ks), (vq, vs))]`` with ``kq`` int8 ``[Hkv, S, D]`` and ``ks``
  f32 ``[Hkv, ceil(S / page_size)]`` run-relative page scales — the
  resident layout with the page pool indirection flattened out, so an
  int8→int8 handoff round-trips bit-exactly (no re-quantization).
- cold layout (tiers.py dict): per-(layer, tensor, head) scales over the
  whole run; coarser, kept for the ``kv_offload_quant`` path whose
  payloads start dense.

Capacity math lives here too (``page_bytes`` / ``pages_for_budget``):
an int8 page costs ``Hkv*(page_size*D + 4)`` bytes per half vs
``Hkv*page_size*D*itemsize`` for bf16 — ~2x more pages in the same HBM
budget (1.94x at the tiny test dims, 2.0x at D=128/page_size=16).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

QMAX = 127.0
SCALE_EPS = 1e-12


# ------------------------------------------------------- primitives (np)
def quantize_np(a: np.ndarray, axis) -> tuple[np.ndarray, np.ndarray]:
    """Absmax-quantize ``a`` over ``axis`` (kept as size-1 dims).

    Returns (int8 body, float32 scale) with the module's single rounding
    definition; ``dequantize_np`` inverts it up to rounding error."""
    a = np.asarray(a, dtype=np.float32)
    absmax = np.max(np.abs(a), axis=axis, keepdims=True)
    scale = np.maximum(absmax / QMAX, SCALE_EPS).astype(np.float32)
    q = np.clip(np.round(a / scale), -QMAX, QMAX).astype(np.int8)
    return q, scale


def dequantize_np(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    return q.astype(np.float32) * scale


# ------------------------------------------------- wire-payload helpers
def is_quant_payload(payload) -> bool:
    """True for the quantized wire layout ``[((kq, ks), (vq, vs))]``
    (each half a (data, scale) pair) vs the dense ``[(k, v)]`` layout
    (each half a bare array)."""
    if not payload:
        return False
    return isinstance(payload[0][0], (tuple, list))


def payload_seq_len(payload) -> int:
    """Token-run length of a dense or quantized wire payload."""
    half = payload[0][0]
    return int(half[0].shape[1] if is_quant_payload(payload)
               else half.shape[1])


def payload_num_heads(payload) -> int:
    half = payload[0][0]
    return int(half[0].shape[0] if is_quant_payload(payload)
               else half.shape[0])


def trim_payload(payload, use: int, page_size: int):
    """First ``use`` tokens of a wire payload (either layout).

    Quantized payloads trim the data on the token axis and the scales on
    the run-page axis — scales stay valid because a page scale bounds
    every token it covered, a superset of the kept prefix."""
    if not is_quant_payload(payload):
        return [(k[:, :use], v[:, :use]) for k, v in payload]
    pages = max(1, -(-use // page_size))
    return [((kq[:, :use], ks[:, :pages]), (vq[:, :use], vs[:, :pages]))
            for (kq, ks), (vq, vs) in payload]


def concat_payloads(parts: list, page_size: int) -> Optional[list]:
    """Concatenate per-part wire payloads along the token axis into one
    payload (the radix restore path stitches per-page node payloads).

    All parts must share a layout.  Quantized parts additionally need
    page-aligned token runs (every part but the last a multiple of
    ``page_size``) so the per-page scale axes concatenate without
    splitting a page across parts; radix node payloads are single full
    pages, so this always holds there.  A mixed or misaligned set falls
    back to dense concat via ``dequantize_payload``."""
    if not parts:
        return None
    quant_flags = [is_quant_payload(p) for p in parts]
    if any(quant_flags):
        aligned = all(
            payload_seq_len(p) % page_size == 0 for p in parts[:-1])
        if not (all(quant_flags) and aligned):
            parts = [dequantize_payload(p, page_size)
                     if q else p for p, q in zip(parts, quant_flags)]
            return concat_payloads(parts, page_size)
        n_layers = len(parts[0])
        out = []
        for i in range(n_layers):
            kq = np.concatenate([np.asarray(p[i][0][0]) for p in parts],
                                axis=1)
            ks = np.concatenate([np.asarray(p[i][0][1]) for p in parts],
                                axis=1)
            vq = np.concatenate([np.asarray(p[i][1][0]) for p in parts],
                                axis=1)
            vs = np.concatenate([np.asarray(p[i][1][1]) for p in parts],
                                axis=1)
            out.append(((kq, ks), (vq, vs)))
        return out
    n_layers = len(parts[0])
    return [
        (np.concatenate([np.asarray(p[i][0]) for p in parts], axis=1),
         np.concatenate([np.asarray(p[i][1]) for p in parts], axis=1))
        for i in range(n_layers)
    ]


def _dequant_half(q: np.ndarray, s: np.ndarray,
                  page_size: int) -> np.ndarray:
    """(int8 [Hkv, S, D], f32 [Hkv, n_pages]) -> f32 [Hkv, S, D]."""
    q = np.asarray(q)
    s = np.asarray(s)
    seq = q.shape[1]
    per_tok = np.repeat(s, page_size, axis=1)[:, :seq]
    return q.astype(np.float32) * per_tok[:, :, None]


def dequantize_payload(payload, page_size: int) -> list:
    """Quantized wire payload -> dense float32 ``[(k, v)]`` payload."""
    if not is_quant_payload(payload):
        return payload
    return [(_dequant_half(kq, ks, page_size),
             _dequant_half(vq, vs, page_size))
            for (kq, ks), (vq, vs) in payload]


def quantize_payload(payload, page_size: int) -> list:
    """Dense ``[(k, v)]`` ([Hkv, S, D]) -> quantized wire payload with
    per-(head, run-page) scales — the exact scales an int8-resident pool
    would hold for these tokens, so injecting the result re-quantizes
    nothing."""
    if is_quant_payload(payload):
        return payload
    out = []
    for k, v in payload:
        halves = []
        for arr in (k, v):
            a = np.asarray(arr, dtype=np.float32)
            hkv, seq, d = a.shape
            n_pages = max(1, -(-seq // page_size))
            pad = n_pages * page_size - seq
            ap = np.pad(a, ((0, 0), (0, pad), (0, 0)))
            ap = ap.reshape(hkv, n_pages, page_size, d)
            absmax = np.max(np.abs(ap), axis=(2, 3))
            scale = np.maximum(absmax / QMAX, SCALE_EPS).astype(np.float32)
            q = np.clip(np.round(ap / scale[:, :, None, None]),
                        -QMAX, QMAX).astype(np.int8)
            halves.append((q.reshape(hkv, -1, d)[:, :seq], scale))
        out.append((halves[0], halves[1]))
    return out


def payload_wire_nbytes(payload) -> int:
    """Handoff bytes of a wire payload (either layout)."""
    total = 0
    for layer in payload:
        for half in layer:
            if isinstance(half, (tuple, list)):
                total += sum(np.asarray(a).nbytes for a in half)
            else:
                total += np.asarray(half).nbytes
    return total


# --------------------------------------------------------- capacity math
def page_bytes(num_kv_heads: int, page_size: int, head_dim: int,
               quantized: bool, itemsize: int = 2, pools: int = 2) -> int:
    """HBM bytes of ONE page for ONE layer over its ``pools`` arrays (the
    k and v halves; 1 for a latent layer's shared pool, whose ``head_dim``
    is the row's width), including the per-(head, page) scales on the
    quantized layout — the unit the page pool is sized in and the ledger
    accounts."""
    if quantized:
        return pools * num_kv_heads * (page_size * head_dim + 4)
    return pools * num_kv_heads * page_size * head_dim * itemsize


def bytes_per_token(num_layers: int, num_kv_heads: int, page_size: int,
                    head_dim: int, quantized: bool,
                    itemsize: int = 2, pools: int = 2) -> float:
    """Amortized HBM bytes per cached token across all layers."""
    return num_layers * page_bytes(
        num_kv_heads, page_size, head_dim, quantized, itemsize, pools
    ) / page_size


def pages_for_budget(budget_bytes: int, num_layers: int,
                     num_kv_heads: int, page_size: int, head_dim: int,
                     quantized: bool, itemsize: int = 2,
                     pools: int = 2) -> int:
    """Page-pool size that fits ``budget_bytes`` of HBM under the given
    layout; with int8 this lands >=1.8x the bf16 count for the same
    budget (the acceptance floor — exactly 2x minus the scale array)."""
    per_page = num_layers * page_bytes(
        num_kv_heads, page_size, head_dim, quantized, itemsize, pools)
    return max(1, int(budget_bytes) // per_page)
