"""Fleet-scale KV economics: shared radix prefix index + tiered offload.

The subsystem behind ROADMAP item 2 — serving millions of users means
massive system-prompt / multi-turn prefix overlap and far more live
sessions than one chip's HBM can hold:

- ``radix.py``  — token-keyed radix (page-trie) prefix index over full
  KV pages: reference-counted nodes shared across concurrent requests
  and tenants, longest-prefix match at admission, leaf-first LRU
  eviction (a prefix is never evicted before its extensions, unlike the
  flat chained-hash map it replaces), per-node tier residency.
- ``tiers.py``  — the KV tier hierarchy HBM → pinned host RAM → remote
  store (connector/TCP-store layer with PR 3 retry/breaker policies),
  with optional int8 quantization on the cold path and bytes-moved /
  occupancy counters for ``/metrics``.
- ``policy.py`` — bytes-saved-vs-recompute admission heuristic: the
  cold path must earn its transfers at the host↔HBM bandwidth the
  deployment measured.

``core/kv_cache_manager.py`` owns page ids and queues device moves;
``engine/llm_engine.py`` drains those queues between schedule() and
execute() with ONE batched pytree transfer per direction per step.
See docs/kv_cache.md.
"""

from vllm_omni_tpu.kvcache.policy import OffloadPolicy
from vllm_omni_tpu.kvcache.radix import RadixNode, RadixPrefixIndex
from vllm_omni_tpu.kvcache.tiers import (
    TIER_HBM,
    TIER_HOST,
    TIER_REMOTE,
    TieredKVStore,
)

__all__ = [
    "OffloadPolicy",
    "RadixNode",
    "RadixPrefixIndex",
    "TieredKVStore",
    "TIER_HBM",
    "TIER_HOST",
    "TIER_REMOTE",
]
