"""Device-memory ledger: per-component HBM accounting with watermarks.

Serving TPUs live or die on exact HBM accounting (the Gemma-4-31B
serving report in PAPERS.md): the difference between "we can admit 40
more sessions" and a RESOURCE_EXHAUSTED abort mid-request is knowing
what actually occupies the device.  ``hbm_bytes`` (one gauge) and the
stage accountant's post-build snapshot say *how much* is used; this
ledger says *by what*:

- **weights** — model parameters (static after load; the runner sums
  leaf ``nbytes`` once, a metadata walk with no device sync);
- **kv_pages** — the paged KV cache arrays, summed from the arrays the
  runner allocated (so by the layout: per-head K and V pools and, when
  quantized, their scales; or a latent layer's one pool of padded rows);
- **spec_buffers** — speculative-decode verify buffers when a draft
  head is attached (deterministic estimate from the config);
- **workspace** — everything the components above can't name: compiled
  executables, XLA scratch, collective buffers.  On a real device it is
  the residual ``bytes_in_use − Σ(known components)``; on backends
  without allocator stats (CPU tier-1) it is 0.

Conservation is the ledger's contract either way: **components sum to
total**, and every per-component ``peak`` watermark is monotone.  The
CPU fallback defines total := Σ components, so the invariant is exact
and deterministic — which is what lets tier-1 exercise the same code
path the TPU fleet scrapes (``device_memory_bytes{component}`` /
``device_memory_peak_bytes{component}`` on /metrics).
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

from vllm_omni_tpu.analysis.runtime import traced

COMPONENT_WORKSPACE = "workspace"


class DeviceMemoryLedger:
    """Per-component live/peak device-memory accounting for one engine.

    ``components_fn`` returns {name: live_bytes} for everything the
    owner can attribute (the runner's static buffers); ``stats_fn``
    returns the platform allocator stats (``bytes_in_use`` /
    ``peak_bytes_in_use``) or None — the default probes the current
    platform, which reports None on CPU.
    """

    def __init__(self, components_fn: Callable[[], dict],
                 stats_fn: Optional[Callable[[], Optional[dict]]] = None):
        if stats_fn is None:
            from vllm_omni_tpu.platforms.memory import device_memory_stats

            stats_fn = device_memory_stats
        self._components_fn = components_fn
        self._stats_fn = stats_fn
        self._lock = traced(threading.Lock(),
                            "DeviceMemoryLedger._lock")
        self._peaks: dict[str, int] = {}
        self._peak_total = 0
        self._last: dict = {}

    def refresh(self) -> dict:
        """Re-read the components + allocator stats and return the
        JSON-ready snapshot.  Cold path only (called from
        ``metrics_snapshot`` / the /debug endpoints, never per step)."""
        comps = {str(k): max(int(v), 0)
                 for k, v in (self._components_fn() or {}).items()}
        known = sum(comps.values())
        stats = None
        try:
            stats = self._stats_fn()
        except Exception:  # a broken probe must not break /metrics
            stats = None
        if stats and stats.get("bytes_in_use") is not None:
            total = int(stats["bytes_in_use"])
            comps[COMPONENT_WORKSPACE] = max(total - known, 0)
            # allocator total can lag the components it doesn't know
            # about; conservation is re-established by definition
            total = sum(comps.values())
            source = "device"
            limit = stats.get("bytes_limit")
            device_peak = stats.get("peak_bytes_in_use")
        else:
            comps[COMPONENT_WORKSPACE] = 0
            total = known
            source = "fallback"
            limit = None
            device_peak = None
        with self._lock:
            for name, v in comps.items():
                if v > self._peaks.get(name, 0):
                    self._peaks[name] = v
            self._peak_total = max(self._peak_total, total)
            snap = {
                "source": source,
                "total_bytes": total,
                "peak_total_bytes": self._peak_total,
                "bytes_limit": limit,
                "device_peak_bytes_in_use": device_peak,
                "components": {
                    name: {"bytes": v,
                           "peak_bytes": self._peaks.get(name, v)}
                    for name, v in sorted(comps.items())
                },
            }
            self._last = snap
        return snap

    def snapshot(self) -> dict:
        """Last refreshed view (refreshes on first use)."""
        with self._lock:
            last = self._last
        return last if last else self.refresh()
