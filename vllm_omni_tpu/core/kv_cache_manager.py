"""Host-side paged KV-cache block pool over a radix prefix index.

The behavioral port of vLLM's KVCacheManager slice that the reference's
``OmniARScheduler`` leans on (reference: core/sched/omni_ar_scheduler.py —
block allocation during schedule(), block-id snapshots for KV transfer at
:553-594, delayed free until extraction ACK at :444-546), grown from the
flat chained-hash prefix cache into fleet-scale KV economics
(docs/kv_cache.md):

- **Radix prefix index** (kvcache/radix.py): full prompt pages register
  as reference-counted trie nodes when their producing request frees; a
  new request adopts the longest matching root-path — shared across
  concurrent requests and tenants — and starts computing mid-prompt.
  Eviction is deepest-first LRU, so a prefix outlives its extensions
  and the index never holds unmatchable orphan entries (the failure
  mode of the flat map under pressure).
- **Tiered offload** (kvcache/tiers.py + kvcache/policy.py): when the
  pool is under pressure, evicted pages whose round trip beats
  recompute PARK their KV in the host/remote tiers instead of dropping
  it, and preempted requests park their whole computed run.  Cold
  nodes stay matchable; adoption allocates fresh pages and queues a
  restore.  This class only QUEUES device moves (pending_offloads /
  pending_parks / pending_restores) — the engine drains the queues
  between schedule() and execute() with batched pytree transfers
  (``LLMEngine._drain_kv_moves``).

Device arrays never appear here: this class hands out integer page ids;
the model runner turns them into ``block_tables`` / ``slot_mapping``
arrays for the Pallas paged-attention kernel (ops/paged_attention.py).
One pool is shared by all layers — every layer uses the same page ids,
so the per-layer caches stay aligned (same layout the TPU kernel wants).
"""

from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Optional

from vllm_omni_tpu.kvcache.policy import OffloadPolicy
from vllm_omni_tpu.kvcache.radix import RadixNode, RadixPrefixIndex
from vllm_omni_tpu.kvcache.tiers import TIER_HOST, TieredKVStore
from vllm_omni_tpu.logger import init_logger
from vllm_omni_tpu.request import Request

logger = init_logger(__name__)

# unclaimed per-request prefix-hit entries age out past this many ids
# (an engine whose router never joins them — decode tier, aborts —
# must not accumulate them forever)
_REQUEST_HIT_CAP = 1024


#: page 0 of the window kind's pool: what a table entry points at once its
#: page slid out of reach; never handed out, never written
NULL_WINDOW_PAGE = 0


def park_key(request_id: str) -> str:
    """Tier-store key of a preempted request's parked KV run."""
    return f"park/{request_id}"


@dataclass
class PendingOffload:
    """Extract ``n_tokens`` of KV from ``pages`` and park under ``key``
    (drained by the engine BEFORE this step's forward reuses the
    pages)."""

    key: str
    pages: list[int]
    n_tokens: int


@dataclass
class PendingRestore:
    """Inject the tier payload at ``key`` into freshly allocated
    ``pages`` for ``request_id`` (drained before the forward attends
    them).  ``start_tokens`` is the payload's position offset within
    the request — on a fetch failure the contiguous valid prefix ends
    exactly there (cold nodes can interleave with hot ones, so a sum
    of injected lengths would be wrong).  ``nodes`` are the adopted
    radix nodes the payloads back (empty for a park restore);
    ``drop_after`` deletes the one-shot park payload once injected."""

    request_id: str
    key: str
    pages: list[int]
    n_tokens: int
    start_tokens: int = 0
    nodes: list[RadixNode] = field(default_factory=list)
    drop_after: bool = False


class KVCacheManager:
    def __init__(self, num_pages: int, page_size: int,
                 enable_prefix_caching: bool = True,
                 tiers: Optional[TieredKVStore] = None,
                 policy: Optional[OffloadPolicy] = None,
                 cache_dtype: Optional[str] = None,
                 bytes_per_token: Optional[float] = None,
                 num_state_slots: int = 0,
                 window: int = 0, num_window_pages: int = 0):
        if num_pages < 1 or page_size < 1:
            raise ValueError("num_pages and page_size must be positive")
        self.num_pages = num_pages
        self.page_size = page_size
        # ---- the second kind of state (docs/kv_cache.md): a model with
        # recurrent layers keeps, beside its pages, one STATE SLOT a
        # sequence (the runner's per-layer conv + recurrent state arrays
        # are indexed by it).  A sequence takes a slot with its first
        # page and gives it back in free(); the runner reads zeros for a
        # sequence's first chunk, which is the slot's zeroing.  0 => the
        # model has no recurrent layer and nothing below applies.
        self.num_state_slots = num_state_slots
        self._free_slots: list[int] = list(range(num_state_slots))[::-1]
        self._state_slots: dict[str, int] = {}
        self.state_slot_allocs = 0
        # prefix hits found in the index and NOT handed out: pages hold
        # no recurrent state, so a recurrent model recomputes them
        self.prefix_hits_refused = 0
        # ---- the second kind of PAGE (docs/kv_cache.md, "Two kinds of
        # page"): a model with sliding-window layers keeps their rows in a
        # pool of its own, and a sequence holds a second table over it.
        # A query at position t reads keys t - window < s <= t there, so
        # a page that lies wholly below ``computed - window + 1`` is given
        # back when the sequence next grows (``_slide``) and its table
        # entry points at ``NULL_WINDOW_PAGE``, page 0 of that pool, which
        # is never handed out: the kernels may still copy it (a KV block
        # starts below the window's first key) and mask what it holds.
        # window 0 => the model has no such layer and nothing below applies
        if window and num_window_pages < 2:
            raise ValueError("a window needs its null page and one more")
        self.window = window
        self.num_window_pages = num_window_pages
        self._wfree: list[int] = list(range(1, num_window_pages))[::-1]
        self._wtables: dict[str, list[int]] = {}
        self.window_pages_freed = 0
        self.window_pages_high_water = 0
        # hits refused because a freed window page no longer holds the
        # rows at the prefix's boundary (counted once a request)
        self.window_prefix_hits_refused = 0
        # resident KV layout metadata for /debug/kv (informational —
        # the allocator is layout-agnostic): the pool dtype label
        # ("int8" / "bfloat16" / None when the engine didn't say) and
        # the amortized all-layer HBM bytes per cached token
        self.cache_dtype = cache_dtype
        self.bytes_per_token = bytes_per_token
        self.enable_prefix_caching = enable_prefix_caching
        self._free: list[int] = list(range(num_pages))
        # request_id -> allocated page ids, in sequence order
        self._tables: dict[str, list[int]] = {}
        # pages pinned by an in-flight KV transfer even after request free
        # (reference: delayed _free_request while transfer ACTIVE)
        self._pinned: dict[str, list[int]] = {}
        # GLOBAL pin refcounts: a page can be pinned through one
        # request's snapshot while another request (or the prefix
        # cache) still owns it — eviction and free() consult THIS, not
        # the per-request snapshot, so a pinned page can never sit in
        # the evictable pool (the evict-under-pressure-vs-pin race)
        self._pin_count: dict[int, int] = {}
        # ---- prefix cache state: the radix index over full pages ----
        self.index = RadixPrefixIndex(page_size)
        # request_id -> adopted radix nodes (released on free)
        self._adopted: dict[str, list[RadixNode]] = {}
        # ---- tiered offload ----
        self.tiers = tiers
        self.policy = policy or OffloadPolicy(mode="never")
        self.pending_offloads: list[PendingOffload] = []
        self.pending_restores: list[PendingRestore] = []
        # keys queued for extraction but not yet drained (park runs
        # AND offload-evicted nodes): their payload is not fetchable
        # yet — park admission waits a step, and match_prefix must not
        # mistake an in-flight cold node for a dead one
        self._extract_in_flight: set[str] = set()
        # cache effectiveness counters (surfaced by engine stats)
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        # per-request hit sizes for the fleet cache-economics board
        # (metrics/cache_economics.py): the router joins the ACTUAL
        # prefix hit onto its dispatch-time expectation.  MUST survive
        # free() — a prefill-tier engine frees the request inside the
        # same step() that emits its output, before the router's join
        # runs — so entries live until take_request_hit pops them,
        # bounded by an LRU cap instead (never-claimed ids age out)
        self._request_hit_tokens: "OrderedDict[str, int]" = OrderedDict()
        # recompute avoided by tier restores (cold prefix adoptions +
        # park restores), in tokens
        self.restored_tokens = 0
        self.parked_tokens = 0
        self.offload_evictions = 0
        self.drop_evictions = 0
        # prompt tokens whose KV arrived as a STREAMED payload from a
        # peer engine (disaggregated prefill adoption, docs/
        # disaggregation.md) rather than being computed here
        self.streamed_tokens = 0
        # prompt tokens whose KV was PULLED from the cluster KV fabric
        # (a shared-prefix page another replica published to the
        # connector store) instead of being re-prefilled here
        self.prefix_pull_tokens = 0
        # ---- per-tenant attribution hooks (metrics/attribution.py):
        # host-int timestamp accounting of page occupancy — every
        # table-size change closes the previous (pages x elapsed)
        # interval into the per-tenant accumulator.  Pure monotonic
        # host arithmetic, zero device syncs; the engine drains the
        # accumulators into its heavy-hitter sketch each step.
        # request_id -> (pages, since_mono, tenant) for live HBM
        # tables; parked host-tier payloads tracked separately
        self._page_time: dict[str, tuple[int, float, str]] = {}
        self._park_time: dict[str, tuple[int, float, str]] = {}
        # tenant -> page·seconds accumulated since the last drain
        self._page_seconds: dict[str, float] = {}
        self._park_seconds: dict[str, float] = {}

    # ------------------------------------------------------------- queries
    def _pinned_pages(self) -> set[int]:
        return {p for p, c in self._pin_count.items() if c > 0}

    @property
    def num_free_pages(self) -> int:
        # evictable cached pages are allocatable on demand; pinned
        # pages are NOT (an in-flight transfer is still reading them)
        return len(self._free) + self.index.evictable(self._pinned_pages())

    def pages_needed(self, num_tokens: int) -> int:
        return -(-num_tokens // self.page_size)

    def block_table(self, request_id: str) -> list[int]:
        return list(self._tables.get(request_id, ()))

    def state_slot(self, request_id: str) -> int:
        """The sequence's state slot; -1 where it holds none."""
        return self._state_slots.get(request_id, -1)

    @property
    def state_slots_in_use(self) -> int:
        return len(self._state_slots)

    def can_allocate(self, request: Request, num_new_tokens: int) -> bool:
        grow, wgrow = self.pages_to_grow(request, num_new_tokens)
        if self.window and (
                wgrow > len(self._wfree) + self._slidable(request)):
            return False
        return grow <= self.num_free_pages

    def pages_to_grow(self, request: Request,
                      num_new_tokens: int) -> tuple[int, int]:
        """Pages ``allocate`` would take for the request to hold
        ``num_new_tokens`` more, of the full kind and of the window kind
        (what slid out of reach is not counted back: an upper bound)."""
        need = self.pages_needed(request.num_computed_tokens + num_new_tokens)
        rid = request.request_id
        grow = max(need - len(self._tables.get(rid, ())), 0)
        if not self.window:
            return grow, 0
        return grow, max(need - len(self._wtables.get(rid, ())), 0)

    # ------------------------------------------------- the window's pages
    @property
    def window_pages_free(self) -> int:
        return len(self._wfree)

    @property
    def window_pages_in_use(self) -> int:
        return self.num_window_pages - 1 - len(self._wfree) \
            if self.window else 0

    def _slid_out(self, request: Request) -> int:
        """Leading window pages the request's next query cannot reach: it
        sits at ``num_computed_tokens`` and reads down to ``window - 1``
        keys below itself."""
        return max(request.num_computed_tokens - self.window + 1,
                   0) // self.page_size

    def _slidable(self, request: Request) -> int:
        table = self._wtables.get(request.request_id, ())
        return sum(1 for p in table[:self._slid_out(request)]
                   if p != NULL_WINDOW_PAGE)

    def _slide(self, request: Request) -> None:
        """Give back the window pages that slid out of the request's
        reach; their table entries point at the null page."""
        table = self._wtables.get(request.request_id)
        if not table:
            return
        for i in range(min(self._slid_out(request), len(table))):
            if table[i] != NULL_WINDOW_PAGE:
                self._wfree.append(table[i])
                table[i] = NULL_WINDOW_PAGE
                self.window_pages_freed += 1

    def window_table(self, request_id: str) -> list[int]:
        return list(self._wtables.get(request_id, ()))

    def window_slot_mapping(self, request_id: str, start: int,
                            num_new_tokens: int) -> list[int]:
        """Flat slots in the window kind's pool for the tokens at
        ``start .. start + num_new_tokens``."""
        table = self._wtables[request_id]
        return [table[pos // self.page_size] * self.page_size
                + pos % self.page_size
                for pos in range(start, start + num_new_tokens)]

    @property
    def offload_enabled(self) -> bool:
        return self.tiers is not None and self.policy.mode != "never"

    def has_pending_moves(self) -> bool:
        return bool(self.pending_offloads or self.pending_restores)

    def take_request_hit(self, request_id: str) -> int:
        """Pop the request's recorded prefix-hit token count (0 when
        the prompt missed the cache entirely).  One-shot by design:
        the router's cache-economics join reads it exactly once.
        Deliberately NOT swept by free() — a prefill-tier engine frees
        the request before the router sees its output, so the entry
        must outlive the table; the LRU cap bounds unclaimed ids."""
        return self._request_hit_tokens.pop(request_id, 0)

    def _record_request_hit(self, request_id: str, matched: int) -> None:
        self._request_hit_tokens[request_id] = matched
        self._request_hit_tokens.move_to_end(request_id)
        while len(self._request_hit_tokens) > _REQUEST_HIT_CAP:
            self._request_hit_tokens.popitem(last=False)

    def debug_snapshot(self) -> dict:
        """JSON-ready occupancy view for /debug/kv (docs/debugging.md):
        page pool state, per-request table sizes, pin refcounts, radix
        node/tier counts, and the pending tier-move queues.  Read-only
        host bookkeeping — safe from the HTTP thread mid-step."""
        # C-level dict copies first: the engine thread mutates these
        # dicts mid-step while the /debug HTTP thread snapshots, and a
        # Python-level iteration over the live dicts could raise
        # "dictionary changed size during iteration"
        tables = dict(self._tables)
        pin_count = dict(self._pin_count)
        per_req_pins = dict(self._pinned)
        pinned = {p: c for p, c in pin_count.items() if c > 0}
        return {
            "pages_total": self.num_pages,
            "pages_free_list": len(self._free),
            "pages_allocatable": self.num_free_pages,
            "page_size": self.page_size,
            "cache_dtype": self.cache_dtype,
            "bytes_per_token": self.bytes_per_token,
            "tables": {rid: len(pages)
                       for rid, pages in sorted(tables.items())},
            "pins": {
                "pages_pinned": len(pinned),
                "refcounts": {str(p): c
                              for p, c in sorted(pinned.items())},
                "by_request": {rid: len(pages) for rid, pages
                               in sorted(per_req_pins.items())},
            },
            "prefix_index": (self.index.debug_stats()
                             if self.enable_prefix_caching
                             else {"enabled": False}),
            # the second kind of state: one slot a sequence of a model
            # with recurrent layers (num 0 => the model has none)
            "state_slots": {
                "total": self.num_state_slots,
                "in_use": len(self._state_slots),
                "by_request": dict(sorted(self._state_slots.items())),
                "allocs": self.state_slot_allocs,
                "prefix_hits_refused": self.prefix_hits_refused,
            },
            # the second kind of page: a sequence's table over the
            # sliding-window layers' pool (window 0 => the model has none)
            "window_pages": {
                "window": self.window,
                "total": self.num_window_pages,
                "in_use": self.window_pages_in_use,
                "high_water": self.window_pages_high_water,
                "freed": self.window_pages_freed,
                "prefix_hits_refused": self.window_prefix_hits_refused,
            },
            "pending_moves": {
                "offloads": len(self.pending_offloads),
                "restores": len(self.pending_restores),
                "extract_in_flight": len(self._extract_in_flight),
            },
            "counters": {
                "prefix_hits": self.prefix_hits,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "restored_tokens": self.restored_tokens,
                "parked_tokens": self.parked_tokens,
                "offload_evictions": self.offload_evictions,
                "drop_evictions": self.drop_evictions,
                "streamed_tokens": self.streamed_tokens,
                "prefix_pull_tokens": self.prefix_pull_tokens,
            },
        }

    # ------------------------------------------------ tenant attribution
    def _stamp_pages(self, request: Request) -> None:
        """Close the request's open (pages x elapsed) HBM interval into
        the per-tenant accumulator and re-open it at the CURRENT table
        size (0 pages closes for good).  Called at every table-size
        change; the interval's tenant is captured at open so a free()
        after the request object is otherwise forgotten still lands on
        the right tenant."""
        rid = request.request_id
        now = time.monotonic()
        prev = self._page_time.pop(rid, None)
        if prev is not None:
            pages, since, tenant = prev
            self._page_seconds[tenant] = (
                self._page_seconds.get(tenant, 0.0)
                + pages * (now - since))
        else:
            tenant = getattr(request, "tenant", "default")
        n = len(self._tables.get(rid, ()))
        if n:
            self._page_time[rid] = (n, now, tenant)

    def _close_park(self, request: Request) -> None:
        """Close the request's parked host-tier interval (restore or
        drop)."""
        prev = self._park_time.pop(request.request_id, None)
        if prev is not None:
            pages, since, tenant = prev
            self._park_seconds[tenant] = (
                self._park_seconds.get(tenant, 0.0)
                + pages * (time.monotonic() - since))

    def drain_page_seconds(self) -> dict[str, dict[str, float]]:
        """Per-tenant KV page·seconds accumulated since the last drain,
        per tier: ``{"hbm": {tenant: s}, "host": {tenant: s}}``.  Live
        intervals are folded up to now and re-stamped, so repeated
        drains partition time exactly (no interval is counted twice or
        dropped).  The engine calls this on its own thread each step
        and meters the result through its attribution sketch."""
        now = time.monotonic()
        for table, acc in ((self._page_time, self._page_seconds),
                           (self._park_time, self._park_seconds)):
            for rid, (pages, since, tenant) in table.items():
                acc[tenant] = (acc.get(tenant, 0.0)
                               + pages * (now - since))
                table[rid] = (pages, now, tenant)
        hbm, self._page_seconds = self._page_seconds, {}
        host, self._park_seconds = self._park_seconds, {}
        return {"hbm": hbm, "host": host}

    # ------------------------------------------------------- prefix cache
    def match_prefix(self, request: Request) -> int:
        """Adopt cached nodes covering the longest full-page prefix of
        the request's prompt; returns the number of tokens whose KV the
        request now starts with (``num_computed_tokens`` is updated and
        the pages seed its block table).  Cold nodes (KV parked in the
        host/remote tiers) are adopted too: a fresh page is allocated
        and a restore queued — the engine injects the payload before
        the forward attends it.  At least one prompt token is always
        left to compute — its forward produces the first logits.
        Embeds-based prompts never match (their placeholder ids carry
        no content)."""
        if (not self.enable_prefix_caching
                or request.prompt_embeds is not None
                or request.num_computed_tokens
                or request.request_id in self._tables):
            return 0
        # leave >= 1 token to compute; keys memoize on the request —
        # a head-of-queue request blocked on pages re-matches every
        # scheduler step and must not re-hash its whole prompt each time
        usable = len(request.prompt_token_ids) - 1
        keys = getattr(request, "_apc_keys", None)
        if keys is None:
            keys = self.index.page_keys(request.prompt_token_ids,
                                        max_pages=usable // self.page_size)
            request._apc_keys = keys
        nodes = self.index.match(keys=keys)
        if not nodes:
            return 0
        if self.window:
            # a hit hands out the full kind's pages; the rows the sliding
            # layers need at the prefix's boundary lay in window pages
            # that were given back.  Refused (and counted, once a
            # request) until window snapshots exist (ROADMAP)
            if not getattr(request, "_apc_refused", False):
                request._apc_refused = True
                self.window_prefix_hits_refused += 1
            return 0
        if self.num_state_slots:
            # a hit hands out pages, and pages hold no recurrent state:
            # serving it would skip the prefix in the linear layers.
            # Refused (and counted, once a request) until state snapshots
            # exist (ROADMAP: state snapshots for prefix reuse)
            if not getattr(request, "_apc_refused", False):
                request._apc_refused = True
                self.prefix_hits_refused += 1
            return 0
        # acquire the WHOLE match up front: referenced nodes are
        # invisible to eviction, so allocating pages for cold restores
        # below can never evict a node this very match adopted
        for node in nodes:
            self.index.acquire(node)
        adopted: list[RadixNode] = []
        restores: list[PendingRestore] = []
        restored = 0
        dead: Optional[RadixNode] = None
        for pos, node in enumerate(nodes):
            if node.page is None:
                # cold node: verify the payload still exists (the host
                # tier may have shed it with no remote edge), then give
                # it fresh HBM storage and queue the restore.  A key
                # whose extraction is queued-but-undrained (evicted
                # EARLIER IN THIS VERY schedule pass) counts as alive:
                # the engine drains extractions before restores, so
                # the payload exists by fetch time
                if (self.tiers is None
                        or not (self.tiers.has(node.key)
                                or node.key in self._extract_in_flight)):
                    dead = node
                    break
                page = self._take_free_page()
                if page is None:
                    break
                self.index.rebind_page(node, page)
                restores.append(PendingRestore(
                    request_id=request.request_id, key=node.key,
                    pages=[page], n_tokens=self.page_size,
                    start_tokens=pos * self.page_size,
                    nodes=[node]))
                restored += self.page_size
            adopted.append(node)
        for node in nodes[len(adopted):]:
            self.index.release(node)
        if (dead is not None and dead.ref == 0
                and not dead.children):
            # unbacked cold leaf: its payload is gone for good — drop
            # it so later matches don't keep stubbing their toe on it
            # (interior unbacked nodes stay: dropping them would
            # orphan live descendants)
            self.index.drop(dead)
        if not adopted:
            return 0
        self.pending_restores.extend(restores)
        self._adopted[request.request_id] = adopted
        self._tables[request.request_id] = [n.page for n in adopted]
        matched = len(adopted) * self.page_size
        request.num_computed_tokens = matched
        self.prefix_hits += 1
        self.prefix_hit_tokens += matched
        self._record_request_hit(request.request_id, matched)
        self.restored_tokens += restored
        self._stamp_pages(request)
        return matched

    def reset_prefix_cache(self) -> int:
        """Drop EVERY unreferenced cached node back to the free pool
        and purge the WHOLE tier store (reference: reset_prefix_cache
        during pause_generation, async_omni.py:771 — weight updates
        invalidate cached KV).  Nodes still referenced by live
        requests stay, but every cold copy is stale after a weight
        swap — in-tree keys, restored hot nodes' dedup copies, and
        ``park/{rid}`` preemption runs alike — so all tier payloads
        and queued extractions go: a parked victim falls back to
        recompute under the new weights, and a pending restore fails
        its fetch and unwinds through the normal lost-payload path.
        Returns the number of HBM pages released."""
        freed, _ = self.index.reset(self._pinned_pages())
        self._free.extend(freed)
        if self.tiers is not None:
            self.tiers.clear()
        self.pending_offloads = []
        self._extract_in_flight.clear()
        return len(freed)

    # ----------------------------------------------------------- eviction
    def _take_free_page(self) -> Optional[int]:
        if self._free:
            return self._free.pop()
        return self._evict_one()

    def _evict_one(self) -> Optional[int]:
        """Reclaim the LRU unreferenced, unpinned cached page.  When the
        offload policy says the page's KV earns its transfer, the node
        goes COLD (stays matchable; extraction queued for the engine to
        drain before the page is overwritten); otherwise the node — and
        its now-unmatchable cold subtree — drops outright."""
        node = self.index.pick_victim(self._pinned_pages())
        if node is None:
            return None
        if (self.offload_enabled
                and self.policy.worth_offloading_page(self.page_size)):
            page = node.page
            if not (self.tiers.has(node.key)
                    or node.key in self._extract_in_flight):
                # content not parked yet: extract before reuse.  The
                # in-flight mark keeps a same-pass match_prefix from
                # mistaking the node for dead (payload lands at drain)
                self.pending_offloads.append(PendingOffload(
                    key=node.key, pages=[page],
                    n_tokens=self.page_size))
                self._extract_in_flight.add(node.key)
            self.index.mark_cold(node, TIER_HOST)
            self.offload_evictions += 1
            return page
        page, purge = self.index.drop(node)
        doomed = set(purge)
        doomed.add(node.key)
        if self.tiers is not None:
            for key in doomed:
                self.tiers.drop(key)
        # an extraction queued for a now-dropped key must not land:
        # the drain would park its payload under a key no node
        # references — an unreachable orphan (and, keys being
        # content-addressed, a stale hit for a same-content node
        # offloaded after a weight reset)
        if self.pending_offloads:
            self.pending_offloads = [
                o for o in self.pending_offloads if o.key not in doomed]
        self._extract_in_flight -= doomed
        self.drop_evictions += 1
        return page

    # ---------------------------------------------------------- allocation
    def allocate(self, request: Request, num_new_tokens: int) -> Optional[list[int]]:
        """Grow the request's table to cover ``num_computed_tokens +
        num_new_tokens``; returns the full table, or None if out of
        pages.  Failure is side-effect free: partial growth rolls back
        and a table entry that didn't pre-exist is removed — a stale
        empty entry would permanently disable ``match_prefix`` for the
        request (its guard treats any registered table as already
        matched)."""
        rid = request.request_id
        fresh = rid not in self._tables
        if fresh and self.num_state_slots and not self._free_slots:
            return None  # cannot happen while slots == max_num_seqs
        table = self._tables.setdefault(rid, [])
        need = self.pages_needed(request.num_computed_tokens + num_new_tokens)
        grow = need - len(table)
        base = len(table)
        ok = grow <= self.num_free_pages
        if ok:
            for _ in range(max(grow, 0)):
                page = self._take_free_page()
                if page is None:
                    ok = False
                    break
                table.append(page)
        if ok and self.window:
            ok = self._grow_window(request, need)
        if not ok:
            self._free.extend(table[base:])
            del table[base:]
            if fresh:
                del self._tables[rid]
            return None
        if grow > 0:
            self._stamp_pages(request)
        if fresh and self.num_state_slots:
            self._state_slots[rid] = self._free_slots.pop()
            self.state_slot_allocs += 1
        return list(table)

    def _grow_window(self, request: Request, need: int) -> bool:
        """The window kind's half of ``allocate``: give back what slid out
        of reach, then grow the second table to ``need`` pages (every new
        page holds tokens this step writes).  False, with nothing grown,
        where the pool cannot."""
        self._slide(request)
        wtable = self._wtables.setdefault(request.request_id, [])
        grow = need - len(wtable)
        if grow > len(self._wfree):
            if not wtable:
                del self._wtables[request.request_id]
            return False
        for _ in range(max(grow, 0)):
            wtable.append(self._wfree.pop())
        self.window_pages_high_water = max(self.window_pages_high_water,
                                           self.window_pages_in_use)
        return True

    def adopt_streamed(self, request: Request, n_tokens: int
                       ) -> Optional[list[int]]:
        """Streamed-page admission (disaggregated prefill): allocate
        pages for ``n_tokens`` of KV that a PEER engine computed and is
        about to inject — the decode tier's receive half.  Same failure
        contract as ``allocate`` (None = out of pages, side-effect
        free); the caller injects the payload before any forward
        attends the pages, then calls ``note_streamed`` — counting at
        allocation would claim tokens the injection later rejected."""
        return self.allocate(request, n_tokens)

    def note_streamed(self, n_tokens: int) -> None:
        """Count tokens whose KV actually INJECTED from a peer engine
        (vs. prefix-cache or tier-restore adoption) — /debug/kv's
        answer to where a decode tier's KV came from."""
        self.streamed_tokens += n_tokens

    def adopt_prefix(self, request: Request, n_tokens: int
                     ) -> Optional[list[int]]:
        """Fabric-pull admission (cluster KV fabric, PR 19): allocate
        pages for ``n_tokens`` of a shared-prefix payload fetched from
        the connector store — the same side-effect-free contract as
        ``adopt_streamed``, kept as a distinct entry so the two KV
        provenances (peer handoff vs fabric pull) stay separately
        accountable.  The payload rode the kv_transfer integrity/
        deadline guards on the way in; the caller injects before any
        forward attends the pages, then calls ``note_pulled``."""
        return self.allocate(request, n_tokens)

    def note_pulled(self, n_tokens: int) -> None:
        """Count tokens whose KV actually INJECTED from a fabric pull
        (a prefix a sibling replica published) — the saved-re-prefill
        half of /debug/kv's provenance story."""
        self.prefix_pull_tokens += n_tokens

    def slot_mapping(self, request: Request, num_new_tokens: int) -> list[int]:
        """Flat slots (page*page_size + offset) for the next
        ``num_new_tokens`` tokens starting at num_computed_tokens."""
        table = self._tables[request.request_id]
        start = request.num_computed_tokens
        slots = []
        for i in range(num_new_tokens):
            pos = start + i
            slots.append(table[pos // self.page_size] * self.page_size
                         + pos % self.page_size)
        return slots

    # ---------------------------------------------------------------- free
    def free(self, request: Request) -> None:
        """Release the request's pages — unless a KV transfer pinned
        them (then they are released by ack_transfer).  Full prompt
        pages register in the radix index instead of returning to the
        free pool (they remain allocatable via LRU eviction); adopted
        shared nodes drop this request's reference."""
        table = self._tables.pop(request.request_id, None)
        if table is None:
            return
        slot = self._state_slots.pop(request.request_id, None)
        if slot is not None:
            self._free_slots.append(slot)
        self._wfree.extend(
            p for p in self._wtables.pop(request.request_id, ())
            if p != NULL_WINDOW_PAGE)
        for node in self._adopted.pop(request.request_id, ()):
            self.index.release(node)
        owned = set(self.index._by_page)
        private = [p for p in table if p not in owned]
        consumed: set[int] = set()
        if (self.enable_prefix_caching
                and request.prompt_embeds is None):
            # register this request's full, computed PROMPT pages (pages
            # become shareable once their producer completes); the
            # insert consumes only pages backing NEW nodes — positions
            # already cached by another producer free normally
            valid = min(
                len(request.prompt_token_ids) // self.page_size,
                request.num_computed_tokens // self.page_size,
                len(table))
            consumed = self.index.insert(
                request.prompt_token_ids, table[:valid], max_pages=valid)
        pinned = self._pinned_pages()
        for page in private:
            if page in consumed:
                continue  # the index owns it now (evictable, unpinned)
            if page in pinned:
                continue  # released by ack_transfer
            self._free.append(page)
        # table gone: closes the request's HBM page·seconds interval
        self._stamp_pages(request)

    # -------------------------------------------------------- park/restore
    def park_request(self, request: Request) -> int:
        """Preemption offload: queue the request's computed KV run for
        extraction to the host tier instead of discarding it (the
        engine drains the extraction this very step, before the freed
        pages are overwritten).  Returns the parked token count, or 0
        when parking is off / not worth the bytes."""
        if not self.offload_enabled:
            return 0
        # park only positions whose tokens are COMMITTED (host-visible):
        # an async in-flight step's sampled token will be discarded by
        # the lagged retire, so its KV slot may describe a token the
        # recompute re-samples differently — exclude the in-flight
        # slots and always leave >= 1 position to compute on resume
        seq_len = min(
            request.num_computed_tokens - request.num_inflight_tokens,
            request.num_tokens - 1)
        if seq_len <= 0 or not self.policy.worth_offloading(seq_len):
            return 0
        table = self._tables.get(request.request_id)
        if not table:
            return 0
        keep = self.pages_needed(seq_len)
        if keep > len(table):
            return 0
        key = park_key(request.request_id)
        self.pending_offloads.append(PendingOffload(
            key=key, pages=list(table[:keep]), n_tokens=seq_len))
        self._extract_in_flight.add(key)
        request.additional_information["_parked_len"] = seq_len
        self.parked_tokens += seq_len
        # host-tier occupancy interval opens at park (closed by
        # restore_parked / drop_park)
        self._park_time[request.request_id] = (
            keep, time.monotonic(), getattr(request, "tenant",
                                            "default"))
        return seq_len

    def park_in_flight(self, request: Request) -> bool:
        """The request's park extraction is queued but not yet drained
        (its payload can't be fetched yet — admission waits a step)."""
        return park_key(request.request_id) in self._extract_in_flight

    def note_park_extracted(self, key: str) -> None:
        self._extract_in_flight.discard(key)

    def parked_available(self, request: Request) -> bool:
        """The parked payload can be fetched right now (extraction
        drained and the tiers still hold it)."""
        return (self.tiers is not None
                and self.tiers.has(park_key(request.request_id)))

    def restore_parked(self, request: Request) -> bool:
        """Re-admit a parked request: allocate pages for its parked run,
        queue the injection, and fast-forward ``num_computed_tokens`` —
        the recompute the park exists to avoid.  Returns False when the
        payload is gone or pages don't fit (caller decides whether to
        wait or recompute)."""
        parked = request.additional_information.get("_parked_len", 0)
        key = park_key(request.request_id)
        if (not parked or self.tiers is None
                or not self.tiers.has(key)):
            return False
        table = self.allocate(request, parked)
        if table is None:
            return False
        self.pending_restores.append(PendingRestore(
            request_id=request.request_id, key=key,
            pages=table[: self.pages_needed(parked)], n_tokens=parked,
            drop_after=True))
        request.num_computed_tokens = parked
        request.additional_information.pop("_parked_len", None)
        self.restored_tokens += parked
        self._close_park(request)
        return True

    def drop_park(self, request: Request) -> None:
        """Forget a parked payload (request finished/aborted/errored
        while parked)."""
        request.additional_information.pop("_parked_len", None)
        key = park_key(request.request_id)
        self._extract_in_flight.discard(key)
        self.pending_offloads = [
            o for o in self.pending_offloads if o.key != key]
        if self.tiers is not None:
            self.tiers.drop(key)
        self._close_park(request)

    def take_pending_moves(self) -> tuple[list[PendingOffload],
                                          list[PendingRestore]]:
        offloads, self.pending_offloads = self.pending_offloads, []
        restores, self.pending_restores = self.pending_restores, []
        return offloads, restores

    def restore_failed_entries(self, request: Request,
                               failed: list[PendingRestore],
                               keep_tokens: int) -> dict[str, int]:
        """A restore came up short at drain time: the ``failed``
        entries' payloads never injected, so their nodes are bound to
        GARBAGE pages — unwind them back to cold (a later-entry payload
        may still exist and restore fine next time; the truly lost one
        is pruned by the has() check at the next match), then rewind
        the request to the contiguous ``keep_tokens`` prefix.

        Returns ``{request_id: keep_tokens}`` for OTHER requests that
        co-adopted a failed node: a second request admitted in the same
        schedule pass saw the rebound node hot (page set) and adopted
        it with NO restore entry of its own — its table references the
        same garbage page.  The caller must truncate each co-adopter at
        its first failed node and drop its scheds this step, or it
        executes attending never-injected KV (and the page, freed by
        this request's truncation, could be re-allocated while still in
        the co-adopter's table — silent cross-request corruption)."""
        failed_nodes = {id(n) for e in failed for n in e.nodes}
        released: list[int] = []
        for e in failed:
            for node in e.nodes:
                if node.page is not None:
                    # usually the page stays in the request's table and
                    # the truncate below frees it; ``released`` catches
                    # the rest (e.g. this request was already truncated
                    # as a co-adopter of an earlier failure this drain)
                    released.append(self.index.mark_cold(node, TIER_HOST))
        co: dict[str, int] = {}
        for rid, adopted in self._adopted.items():
            if rid == request.request_id:
                continue
            cut = next((i for i, n in enumerate(adopted)
                        if id(n) in failed_nodes), None)
            if cut is not None:
                co[rid] = cut * self.page_size
        self.restore_truncated(request, keep_tokens)
        if released:
            pinned = self._pinned_pages()
            live = {p for t in self._tables.values() for p in t}
            unplaced = set(self._free)
            for page in released:
                if (page in pinned or page in unplaced
                        or page in self.index._by_page or page in live):
                    continue
                self._free.append(page)
                unplaced.add(page)
        return co

    def restore_truncated(self, request: Request, keep_tokens: int
                          ) -> None:
        """Keep the contiguous ``keep_tokens`` prefix that is actually
        valid, release everything after it, and rewind
        ``num_computed_tokens`` so the scheduler recomputes the rest."""
        rid = request.request_id
        keep_pages = self.pages_needed(keep_tokens)
        table = self._tables.get(rid, [])
        adopted = self._adopted.get(rid, [])
        owned = set(self.index._by_page)
        for node in adopted[keep_pages:]:
            self.index.release(node)
        self._adopted[rid] = adopted[:keep_pages]
        self._tables[rid] = table[:keep_pages]
        pinned = self._pinned_pages()
        live = {p for t in self._tables.values() for p in t}
        for page in table[keep_pages:]:
            if page in owned or page in pinned:
                continue
            if page in live:
                # a co-adopter of the same failed-restore node still
                # references it — the LAST truncation frees it
                continue
            self._free.append(page)
        request.num_computed_tokens = min(request.num_computed_tokens,
                                          keep_tokens)
        self._stamp_pages(request)

    # --------------------------------------------------------- transfers
    def pin_for_transfer(self, request: Request, seq_len: int) -> list[int]:
        """Snapshot + pin the pages holding the first ``seq_len`` tokens
        (reference: block-id snapshot truncated to seq_len,
        omni_ar_scheduler.py:553-594).  Pins are GLOBAL refcounts:
        however the page is also owned (live table, shared cache node),
        it cannot be evicted or freed until ``ack_transfer``."""
        table = self._tables.get(request.request_id, [])
        keep = self.pages_needed(seq_len)
        snapshot = table[:keep]
        self._pinned[request.request_id] = list(snapshot)
        for page in snapshot:
            self._pin_count[page] = self._pin_count.get(page, 0) + 1
        return list(snapshot)

    def ack_transfer(self, request_id: str) -> None:
        """Extraction ACK: unpin the snapshot; pages owned by nobody
        else (no live table, not a cache node) return to the free pool
        (reference: free on kv_extracted_req_ids,
        omni_ar_scheduler.py:444).  Cached nodes simply become
        evictable again now that the pin is gone."""
        pinned = self._pinned.pop(request_id, [])
        live: Optional[set[int]] = None
        for page in pinned:
            c = self._pin_count.get(page, 0) - 1
            if c > 0:
                self._pin_count[page] = c
                continue
            self._pin_count.pop(page, None)
            if page in self.index._by_page:
                continue  # cache node: evictable via the index now
            if live is None:
                # built once per ack, not per page: a long pinned
                # snapshot over many live tables must not go quadratic
                live = {p for t in self._tables.values() for p in t}
            if page in live:
                continue  # still part of a live table
            self._free.append(page)
