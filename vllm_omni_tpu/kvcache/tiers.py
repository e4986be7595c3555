"""KV tier hierarchy: HBM → pinned host-RAM pool → remote store.

Holds the COLD copies of parked KV payloads and the bytes-moved
discipline around them.  A payload is the per-layer ``[(k, v)]`` numpy
arrays for one radix node (one full page) or one parked request
(arbitrary token run) — always moved as ONE pytree transfer
(``worker/model_runner.py`` batches the device halves), never per-page.

Tiers:

- **host** — an LRU ``OrderedDict`` of payloads in (pinned) host RAM,
  capped by ``host_capacity_bytes``; overflow demotes the oldest
  entries to the remote tier (or drops them when no remote edge is
  configured — the radix index prunes the now-unbacked nodes at the
  next match).
- **remote** — the existing connector/TCP-store layer
  (``distributed/connectors.py`` / ``distributed/tcp.py``), wrapped in
  the PR 3 retry policy + circuit breaker so a flapping remote store
  degrades to recompute instead of wedging the scheduler.

Cold payloads optionally quantize to int8 (per-(layer, head) absmax
scales, the same scale machinery stance as ``diffusion/quantization``):
every byte moved between host and HBM is restore latency, and int8
halves the bf16 cold path.  ``quant == "none"`` (default) keeps payloads
bit-exact so restored greedy streams match the never-offloaded oracle.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Optional

import numpy as np

from vllm_omni_tpu.kvcache.quant import (
    dequantize_np,
    is_quant_payload,
    quantize_np,
)
from vllm_omni_tpu.logger import init_logger

logger = init_logger(__name__)

TIER_HBM = "hbm"
TIER_HOST = "host"
TIER_REMOTE = "remote"


# ---------------------------------------------------------- quantization
def quantize_kv_payload(payload: list) -> dict:
    """Dense [(k, v)] float arrays ([Hkv, S, D]) -> int8 bodies + per-head
    float32 absmax scales (rounding shared with ``kvcache/quant.py``).
    Mirrors diffusion/quantization's per-out-channel absmax stance,
    applied per (layer, tensor, head)."""
    layers = []
    for k, v in payload:
        out = []
        for arr in (k, v):
            q, scale = quantize_np(arr, axis=(1, 2))
            out.append((q, scale, str(np.asarray(arr).dtype)))
        layers.append(tuple(out))
    return {"quant": "int8", "layers": layers}


def dequantize_kv_payload(obj: dict) -> list:
    payload = []
    for (kq, ks, kd), (vq, vs, vd) in obj["layers"]:
        payload.append((dequantize_np(kq, ks).astype(kd),
                        dequantize_np(vq, vs).astype(vd)))
    return payload


def _resident_wrap(payload: list) -> dict:
    """Already-quantized wire payload (int8-resident extraction,
    ``kvcache/quant.py`` layout) stored AS-IS — re-quantizing int8 data
    would double the rounding error, and dequantizing to bf16 to satisfy
    the cold format would double the bytes.  ``fetch`` hands the list
    straight back for an exact int8->int8 restore."""
    layers = [((np.asarray(kq), np.asarray(ks)),
               (np.asarray(vq), np.asarray(vs)))
              for (kq, ks), (vq, vs) in payload]
    return {"quant": "int8", "resident": True, "layers": layers}


def payload_nbytes(payload) -> int:
    """Stored size of a payload (raw [(k, v)], quantized wire list, or
    stored dict of either cold flavor)."""
    if isinstance(payload, dict):
        payload = payload["layers"]

    def walk(node) -> int:
        if isinstance(node, (tuple, list)):
            return sum(walk(x) for x in node)
        if isinstance(node, str):
            return 0
        return np.asarray(node).nbytes

    return walk(payload)


class TieredKVStore:
    """Cold-side owner of parked KV payloads, keyed by radix node key
    (shared prefixes) or ``park/{request_id}`` (preempted sessions).

    Counters are cumulative and feed the ``kv_offload_bytes_total
    {tier,dir}`` / ``kv_tier_*_pages`` series on ``/metrics``."""

    def __init__(self, quant: str = "none",
                 host_capacity_bytes: Optional[int] = None,
                 remote: Optional[Any] = None,
                 remote_namespace: str = "kvcache"):
        if quant not in ("none", "int8"):
            raise ValueError(f"unknown kv quant mode {quant!r}")
        self.quant = quant
        self.host_capacity_bytes = host_capacity_bytes
        self._host: "OrderedDict[str, Any]" = OrderedDict()
        self._host_bytes = 0
        # keys known to live remotely (the remote store is write-once
        # per key; this set is the host-side directory)
        self._remote_keys: set[str] = set()
        self._remote = remote
        self._ns = remote_namespace
        if remote is not None:
            from vllm_omni_tpu.resilience.retry import (
                CircuitBreaker,
                RetryPolicy,
            )

            self._retry = RetryPolicy(max_attempts=3, base_delay_s=0.01)
            self._breaker = CircuitBreaker(site="kvcache_remote")
        # bytes moved per (tier, dir) — dir "out" = away from HBM,
        # "in" = back toward it
        self.bytes_moved: dict[tuple[str, str], int] = {}
        self.restored_tokens = 0

    # ------------------------------------------------------------ lookup
    def tier_of(self, key: str) -> Optional[str]:
        if key in self._host:
            return TIER_HOST
        if key in self._remote_keys:
            return TIER_REMOTE
        return None

    def has(self, key: str) -> bool:
        return self.tier_of(key) is not None

    # ------------------------------------------------------------- sizes
    def host_entries(self) -> int:
        return len(self._host)

    def remote_entries(self) -> int:
        return len(self._remote_keys)

    def host_bytes(self) -> int:
        return self._host_bytes

    def _count(self, tier: str, direction: str, n: int) -> None:
        k = (tier, direction)
        self.bytes_moved[k] = self.bytes_moved.get(k, 0) + int(n)

    # --------------------------------------------------------------- put
    def put(self, key: str, payload: list) -> int:
        """Park a payload in the host tier (quantizing per policy);
        returns stored bytes.  Overflow demotes LRU host entries to the
        remote tier, or drops them without one."""
        if is_quant_payload(payload):
            # int8-resident extraction: already quantized once at
            # KV-write time — park it verbatim (never double-quantize)
            stored: Any = _resident_wrap(payload)
        elif self.quant == "int8":
            stored = quantize_kv_payload(payload)
        else:
            stored = [(np.asarray(k), np.asarray(v)) for k, v in payload]
        n = payload_nbytes(stored)
        old = self._host.pop(key, None)
        if old is not None:
            self._host_bytes -= payload_nbytes(old)
        self._host[key] = stored
        self._host_bytes += n
        self._count(TIER_HOST, "out", n)
        self._shed()
        return n

    def _shed(self) -> None:
        if self.host_capacity_bytes is None:
            return
        while (self._host_bytes > self.host_capacity_bytes
               and len(self._host) > 1):
            key, stored = self._host.popitem(last=False)
            n = payload_nbytes(stored)
            self._host_bytes -= n
            if self._remote is not None and self._remote_put(key, stored):
                self._count(TIER_REMOTE, "out", n)
                self._remote_keys.add(key)
            else:
                logger.debug("kv tier store: dropped %s (%d B, no "
                             "remote tier)", key, n)

    # --------------------------------------------------------------- get
    def fetch(self, key: str) -> Optional[list]:
        """Payload for ``key``, promoted back through the tiers:
        remote hits re-park in the host tier (the next restore of a
        popular prefix skips the slow edge).  Returns the DEQUANTIZED
        per-layer [(k, v)] list, or None when the payload is gone."""
        stored = self._host.get(key)
        if stored is not None:
            self._host.move_to_end(key)
            self._count(TIER_HOST, "in", payload_nbytes(stored))
        elif key in self._remote_keys:
            stored = self._remote_get(key)
            if stored is None:
                self._remote_keys.discard(key)
                return None
            n = payload_nbytes(stored)
            self._count(TIER_REMOTE, "in", n)
            # promote: popular prefixes climb back to the faster tier
            self._host[key] = stored
            self._host_bytes += n
            self._shed()
        else:
            return None
        if isinstance(stored, dict):
            if stored.get("resident"):
                # quantized wire payload: hand back as-is — an int8
                # runner re-injects it bit-exactly, a bf16 runner's
                # inject path dequantizes (kvcache/quant.py)
                return [((kq, ks), (vq, vs))
                        for (kq, ks), (vq, vs) in stored["layers"]]
            return dequantize_kv_payload(stored)
        return [(k, v) for k, v in stored]

    def drop(self, key: str) -> None:
        stored = self._host.pop(key, None)
        if stored is not None:
            self._host_bytes -= payload_nbytes(stored)
        if key in self._remote_keys:
            self._remote_keys.discard(key)
            if self._remote is not None:
                try:
                    self._remote.cleanup(self._rkey(key))
                except Exception:  # noqa: BLE001 - best-effort GC
                    pass

    def clear(self) -> None:
        for key in list(self._host) + list(self._remote_keys):
            self.drop(key)

    # ------------------------------------------------------- remote edge
    def _rkey(self, key: str) -> str:
        return f"{self._ns}/{key}"

    def _remote_put(self, key: str, stored: Any) -> bool:
        from vllm_omni_tpu.resilience.retry import call_with_retry

        try:
            call_with_retry(
                lambda: self._remote.put(self._rkey(key), stored),
                policy=self._retry, breaker=self._breaker,
                site="kvcache_remote",
            )
            return True
        except Exception as e:  # noqa: BLE001 - any failure = payload
            # unavailable; callers degrade to recompute.  Transient
            # errors were already retried; a non-transient one (store
            # ST_ERR, serialization) must not kill the engine step
            logger.warning("kv remote tier put failed for %s: %s",
                           key, e)
            return False

    def _remote_get(self, key: str) -> Optional[Any]:
        from vllm_omni_tpu.resilience.retry import call_with_retry

        try:
            stored = call_with_retry(
                lambda: self._remote.get(self._rkey(key), timeout=None),
                policy=self._retry, breaker=self._breaker,
                site="kvcache_remote",
            )
        except Exception as e:  # noqa: BLE001 - any failure = payload
            # unavailable (incl. a corrupt payload failing to decode);
            # the lost-payload path recomputes instead of wedging
            logger.warning("kv remote tier get failed for %s: %s",
                           key, e)
            return None
        if stored is None:
            return None
        # connector get() semantics pop the key on some transports
        # (the TCP store's blocking pop): re-publish so other replicas
        # and a later fall-from-host still find it
        self._remote_put(key, stored)
        return stored

    # ------------------------------------------------------------- stats
    def stats(self) -> dict:
        return {
            "host_entries": self.host_entries(),
            "remote_entries": self.remote_entries(),
            "host_bytes": self._host_bytes,
            "bytes_moved": {
                f"{tier}/{d}": n
                for (tier, d), n in sorted(self.bytes_moved.items())},
            "restored_tokens": self.restored_tokens,
            "quant": self.quant,
        }

    def debug_snapshot(self, key_limit: int = 32) -> dict:
        """stats() plus a BOUNDED sample of resident keys per tier for
        /debug/kv — enough to see which park runs / prefix nodes are
        parked where, without serializing a fleet-sized directory."""
        doc = self.stats()
        doc["host_capacity_bytes"] = self.host_capacity_bytes
        # oldest-first (the LRU's next demotion victims lead the list)
        doc["host_keys"] = list(self._host)[:key_limit]
        doc["remote_keys"] = sorted(self._remote_keys)[:key_limit]
        doc["has_remote"] = self._remote is not None
        return doc
