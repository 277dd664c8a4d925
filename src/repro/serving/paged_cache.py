"""Paged (block-table) KV/state cache for the serving engine.

vLLM/L3-style paged residency: instead of reserving a dense
``max_batch x max_seq`` cache, sequence-bearing cache leaves live in a pool
of fixed-size pages and each slot owns a block table mapping its logical
context positions to pages.  KV memory held by a request is then
proportional to its actual context length, which is what lets the engine
admit long-context / skewed-length traffic without reserving for the worst
case.

Prefix sharing (``share=True``): pages carry refcounts and a host-side
prefix trie maps full pages of prompt tokens to the physical page already
holding their KV, so a new request's leading prompt pages are *mapped*
onto existing pages instead of recomputed storage — the shared-system-
prompt workload multiplies admissible batch size per resident page.  A
request with an *identical* prompt additionally shares the ragged tail
page; since both requests will decode-write into that page, any write
targeting a page with refcount > 1 must first ``fork_page`` (copy-on-write:
copy the page on device and remap just that slot's table entry).  The
engine performs that fork in its pre-decode pass, so the jitted scatter
and the Pallas read-through kernel only ever write exclusively-owned
pages.

Generic across all four registry state families via shape probing: we
``eval_shape`` the family's ``cache_zeros`` at two different ``max_seq``
values — leaves whose shape changes are *sequence leaves* and get paged
(KVCache.k/v, EncDecCache.self_k/self_v); everything else (RWKV/RG
recurrent state, cross-attention caches, ``lengths``) is O(1) per request
and stays slot-dense.  For the recurrent families there are no sequence
leaves at all and the paged cache degenerates to the dense layout, which is
already proportional.

Layout: a sequence leaf ``(L, B, S, ...)`` (batch axis 1, seq axis 2 per
the engine's batch-axis rule) becomes a pool ``(L, P+1, page, ...)``; page
index ``P`` is a scratch/trash page so masked scatters and gathers of
unmapped table entries (-1) never touch live data.  Block tables are a host
``(max_batch, max_blocks)`` int32 array mirrored to device on change.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gemm import ceil_div
from repro.core.noc import CollectiveCost, page_ship
from repro.core.placement import (COMMUNAL, PLACEMENT_POLICIES, GatherCost,
                                  PlacementMap, default_system, gather_cost)
from repro.obs.tracer import NULL_TRACER
from repro.serving.replica_api import PlacementReport


# ---------------------------------------------------------------------------
# Host-side block allocator
# ---------------------------------------------------------------------------
class PageAllocator:
    """Refcounted free-list page allocator (host side).

    Pages are plain ints ``0..num_pages-1``.  ``alloc`` returns ``None``
    (allocating nothing) when the request cannot be satisfied — admission
    control, not an error.  Freshly allocated pages start at refcount 1;
    prefix sharing ``incref``s them when a second block table maps the same
    page, and ``decref``/``free`` return a page to the free list only when
    the last reference drops — no page is ever freed while its refcount is
    still positive.

    **Placement** (``placement`` + ``policy``): with a
    :class:`~repro.core.placement.PlacementMap` the allocator places
    pages substrate-aware.  ``free-first`` keeps the legacy LIFO layout
    (wherever the free list points); ``affinity`` prefers the caller's
    ``home`` region (or the communal region for ``communal=True`` shared
    prefix pages), spilling to the emptiest other region only when the
    preferred one runs dry; ``interleave`` stripes pages round-robin
    across slot regions.  Placement only changes WHICH free pages are
    picked — success/failure depends solely on the global free count, so
    admission control (and therefore scheduling) is identical across
    policies.
    """

    def __init__(self, num_pages: int,
                 placement: Optional[PlacementMap] = None,
                 policy: str = "free-first"):
        if num_pages <= 0:
            raise ValueError("num_pages must be positive")
        if policy not in PLACEMENT_POLICIES:
            raise ValueError(f"unknown placement policy {policy!r}; "
                             f"choose from {PLACEMENT_POLICIES}")
        if placement is not None and placement.num_pages != num_pages:
            raise ValueError(
                f"placement map covers {placement.num_pages} pages, "
                f"allocator has {num_pages}")
        self.num_pages = num_pages
        self.placement = placement
        self.policy = policy
        self._rr = 0                    # interleave striping cursor
        self._refs: Dict[int, int] = {}
        self._init_free()

    def _init_free(self) -> None:
        if self.placed:
            # persistent per-region free lists (placed mode): descending
            # so pop() hands out each region's lowest index first — the
            # same LIFO invariant as the global list, at O(1) per page
            self._region_lists: Dict[int, List[int]] = {
                r: sorted(self.placement.region_pages(r), reverse=True)
                for r in self.placement.regions()}
            self._free: List[int] = []      # unused in placed mode
        else:
            self._free = list(range(self.num_pages - 1, -1, -1))

    @property
    def free_pages(self) -> int:
        if self.placed:
            return sum(len(v) for v in self._region_lists.values())
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return len(self._refs)

    @property
    def shared_pages(self) -> int:
        """Pages currently mapped by more than one block-table entry."""
        return sum(1 for rc in self._refs.values() if rc > 1)

    def live_pages(self) -> List[int]:
        return sorted(self._refs)

    def highest_used(self) -> int:
        """Highest allocated page index (-1 when empty)."""
        return max(self._refs, default=-1)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    # -- placement geometry ------------------------------------------------
    @property
    def placed(self) -> bool:
        """True when allocation actively steers placement (a map under a
        non-legacy policy)."""
        return self.placement is not None and self.policy != "free-first"

    def region_free(self) -> Dict[int, int]:
        """Free pages per region (requires a placement map)."""
        assert self.placement is not None
        if self.placed:
            return {r: len(v) for r, v in self._region_lists.items()}
        out = {r: 0 for r in self.placement.regions()}
        for p in self._free:
            out[self.placement.region_of(p)] += 1
        return out

    def region_used(self) -> Dict[int, int]:
        """Allocated pages per region (requires a placement map)."""
        assert self.placement is not None
        out = {r: 0 for r in self.placement.regions()}
        for p in self._refs:
            out[self.placement.region_of(p)] += 1
        return out

    def _select(self, n: int, home: Optional[int],
                n_communal: int) -> List[int]:
        """Pop ``n`` free pages off the per-region lists under the
        placement policy (caller has checked the global free count).
        The first ``n_communal`` picks prefer the communal region; the
        rest follow the policy.  O(1) per page."""
        pmap = self.placement
        lists = self._region_lists
        picks: List[int] = []

        def take_from(region: int, k: int) -> int:
            pool = lists.get(region, [])
            got = min(k, len(pool))
            for _ in range(got):
                picks.append(pool.pop())
            return got

        # shared (publishable) pages go communal under every placement
        # policy: all slots read them, so no slot channel is favored —
        # overflow falls through to the private-page policy below
        want = n - take_from(COMMUNAL, min(n_communal, n)) \
            if pmap.communal_pages else n
        if self.policy == "interleave":
            ring = list(range(pmap.n_regions))
            while want > 0:
                if not any(lists[r] for r in ring):
                    want -= take_from(COMMUNAL, want)   # only communal left
                    break
                r = ring[self._rr % len(ring)]
                self._rr += 1
                want -= take_from(r, min(1, want)) if lists[r] else 0
            return picks
        # affinity: home region first, then spill to the emptiest-used
        # (most-free) other regions, deterministic ties by region id
        order = [home] if home is not None else []
        order.extend(sorted(
            (r for r in pmap.regions() if r not in order),
            key=lambda r: (r == COMMUNAL, -len(lists[r]), r)))
        for r in order:
            want -= take_from(r, want)
            if want == 0:
                break
        return picks

    def alloc(self, n: int, *, home: Optional[int] = None,
              communal: int = 0) -> Optional[List[int]]:
        """Allocate ``n`` pages; ``home`` steers private pages and the
        first ``communal`` of them prefer the communal region (both
        ignored under the legacy free-first policy).  Atomic: returns
        ``None`` without mutating when fewer than ``n`` pages are free."""
        if n < 0:
            raise ValueError("alloc size must be >= 0")
        if n > self.free_pages:
            return None
        if not self.placed:
            pages = [self._free.pop() for _ in range(n)]
        else:
            pages = self._select(n, home, communal)
            assert len(pages) == n
        for p in pages:
            self._refs[p] = 1
        return pages

    def alloc_in(self, region: int, n: int = 1) -> Optional[List[int]]:
        """Allocate ``n`` pages strictly from ``region`` (placed mode
        only) — the page-migration primitive: unlike :meth:`alloc` it
        never spills, returning ``None`` when the region cannot satisfy
        the request in full.  Atomic."""
        assert self.placed, "alloc_in needs active placement"
        pool = self._region_lists.get(region, [])
        if n < 0:
            raise ValueError("alloc size must be >= 0")
        if len(pool) < n:
            return None
        pages = [pool.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def incref(self, page: int) -> None:
        if page not in self._refs:
            raise ValueError(f"incref of unallocated page {page}")
        self._refs[page] += 1

    def decref(self, page: int) -> bool:
        """Drop one reference; True iff the page was returned to the free
        list (refcount reached zero)."""
        rc = self._refs.get(page)
        if rc is None:
            raise ValueError(f"double free / foreign page {page}")
        if rc == 1:
            del self._refs[page]
            if self.placed:
                self._region_lists[self.placement.region_of(page)] \
                    .append(page)
            else:
                self._free.append(page)
            return True
        self._refs[page] = rc - 1
        return False

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference per page (frees those reaching zero)."""
        for p in pages:
            self.decref(p)

    def rebuild(self, refcounts: Dict[int, int]) -> None:
        """Reset the allocator to an explicit live set (the public defrag
        API).

        ``refcounts`` maps live page id -> its refcount.  The free list is
        rebuilt in descending index order, so subsequent allocations hand
        out the lowest free indices first — the same LIFO invariant a
        freshly constructed allocator starts with.
        """
        for p, rc in refcounts.items():
            if not 0 <= p < self.num_pages:
                raise ValueError(f"page {p} out of range")
            if rc <= 0:
                raise ValueError(f"page {p} has non-positive refcount {rc}")
        self._refs = dict(refcounts)
        if self.placed:
            self._region_lists = {
                r: [p for p in sorted(self.placement.region_pages(r),
                                      reverse=True)
                    if p not in self._refs]
                for r in self.placement.regions()}
        else:
            self._free = [p for p in range(self.num_pages - 1, -1, -1)
                          if p not in self._refs]

    def reset(self) -> None:
        self._refs.clear()
        self._rr = 0
        self._init_free()


# ---------------------------------------------------------------------------
# Host-side prompt-prefix trie (page-granular)
# ---------------------------------------------------------------------------
class _TrieNode:
    __slots__ = ("children", "partial")

    def __init__(self):
        # full-page token chunk -> (page id, subtree)
        self.children: Dict[bytes, Tuple[int, "_TrieNode"]] = {}
        # trailing sub-page token chunk -> page id
        self.partial: Dict[bytes, int] = {}


def _chunk_key(tokens: np.ndarray) -> bytes:
    # canonical dtype so int32 prompts and int64 literals key identically
    return np.ascontiguousarray(tokens, dtype=np.int64).tobytes()


class PrefixIndex:
    """Page-granular prompt-prefix trie (host side).

    Each edge keys one full page of prompt tokens (raw token bytes — exact
    matching, no hash collisions) and carries the physical page holding
    that chunk's KV.  A node's ``partial`` table maps a trailing sub-page
    chunk to its page, which is what lets two requests with *identical*
    prompts share the ragged tail page — the case that exercises
    copy-on-write, since both holders decode-write into that page.

    Entries are registered only after the page contents have actually been
    written (``PagedCache`` commits at insert time, not at admission) and
    are dropped when the page's last reference is released, so a hit always
    points at live, fully materialized prompt KV.  CoW forks and decode
    growth pages are never registered: their contents diverge from the
    prompt.
    """

    def __init__(self):
        self.root = _TrieNode()
        # page -> (owning node, edge key, is_partial) for O(1) removal and
        # defrag renumbering
        self._by_page: Dict[int, Tuple[_TrieNode, bytes, bool]] = {}

    def __len__(self) -> int:
        return len(self._by_page)

    def match(self, tokens: np.ndarray, page_size: int) -> List[int]:
        """Longest shared prefix of ``tokens`` in whole pages, plus the
        ragged tail page when the remainder matches exactly."""
        node, pages = self.root, []
        k = len(tokens) // page_size
        for i in range(k):
            hit = node.children.get(
                _chunk_key(tokens[i * page_size:(i + 1) * page_size]))
            if hit is None:
                return pages
            pages.append(hit[0])
            node = hit[1]
        tail = tokens[k * page_size:]
        if len(tail):
            page = node.partial.get(_chunk_key(tail))
            if page is not None:
                pages.append(page)
        return pages

    def register(self, tokens: np.ndarray, pages: Sequence[int],
                 page_size: int) -> None:
        """Publish ``pages`` (page-chunked KV of ``tokens``) for reuse.

        First-writer-wins: chunks already present keep their existing page
        (the caller's duplicate copy simply stays private); chunks missing
        from the walk are inserted with the caller's page.
        """
        node = self.root
        k = len(tokens) // page_size
        for i in range(k):
            key = _chunk_key(tokens[i * page_size:(i + 1) * page_size])
            hit = node.children.get(key)
            if hit is None:
                child = _TrieNode()
                node.children[key] = (pages[i], child)
                self._by_page[pages[i]] = (node, key, False)
                node = child
            else:
                node = hit[1]
        tail = tokens[k * page_size:]
        if len(tail) and k < len(pages):
            key = _chunk_key(tail)
            if key not in node.partial:
                node.partial[key] = pages[k]
                self._by_page[pages[k]] = (node, key, True)

    def remove(self, page: int) -> None:
        """Forget a freed page.  Children of a removed full-page edge are
        unreachable afterwards, which is safe: any request mapping a child
        chunk also held a reference on this page, so the whole chain dies
        together."""
        info = self._by_page.pop(page, None)
        if info is None:
            return
        node, key, is_partial = info
        if is_partial:
            node.partial.pop(key, None)
        else:
            node.children.pop(key, None)

    def remap(self, mapping: Dict[int, int]) -> None:
        """Apply a defrag old->new page renumbering in place."""
        by_page = {}
        for old, (node, key, is_partial) in self._by_page.items():
            new = mapping.get(old, old)
            if is_partial:
                node.partial[key] = new
            else:
                node.children[key] = (new, node.children[key][1])
            by_page[new] = (node, key, is_partial)
        self._by_page = by_page


# ---------------------------------------------------------------------------
# Shape probing: which leaves page, and where
# ---------------------------------------------------------------------------
SEQ_AXIS = 2    # engine batch-axis rule: (L, B, S, ...) for seq leaves
BATCH_AXIS = 1


def probe_seq_leaves(entry, max_batch: int, tp: int = 1) -> List[bool]:
    """True per flattened cache leaf iff its shape depends on ``max_seq``."""
    sa = jax.eval_shape(lambda: entry.cache_zeros(max_batch, 16, tp))
    sb = jax.eval_shape(lambda: entry.cache_zeros(max_batch, 32, tp))
    la, _ = jax.tree.flatten(sa)
    lb, _ = jax.tree.flatten(sb)
    out = []
    for a, b in zip(la, lb):
        if a.shape == b.shape:
            out.append(False)
        else:
            diff = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                    if x != y]
            if diff != [SEQ_AXIS]:
                raise ValueError(
                    f"seq leaf with unsupported layout {a.shape} vs "
                    f"{b.shape}: expected the seq axis at {SEQ_AXIS}")
            out.append(True)
    return out


def num_blocks(n_tokens: int, page_size: int) -> int:
    return ceil_div(n_tokens, page_size)


# ---------------------------------------------------------------------------
# Cross-pool page shipment (prefill -> decode tier handoff, PR 10)
# ---------------------------------------------------------------------------
@dataclass
class PageShipment:
    """A slot's KV pages packaged for transfer between two pools.

    Built by :meth:`PagedCache.export_slot_pages` (which also releases
    the slot at the source) and consumed by
    :meth:`PagedCache.import_slot_pages`.  Carries everything the
    destination needs to reconstruct the slot bit-identically:

    * ``seq_payload`` — per cache leaf, the gathered page block
      ``(L, n_pages, page, ...)`` for sequence leaves (``None`` for
      dense leaves);
    * ``slot_payload`` — per cache leaf, the slot column for dense
      leaves (recurrent state, lengths; ``None`` for sequence leaves);
    * ``tokens`` — the prompt, so the destination can consult *its own*
      prefix trie (mapping already-resident pages instead of writing
      duplicates) and re-register the coverage after import;
    * ``cost_s`` / ``bytes_on_wire`` — the priced cross-stack movement
      (:func:`~repro.core.noc.page_ship`), charged once at export.

    The engine layer annotates ``req`` (the live request object),
    ``next_tok`` (the first decoded token, produced on the prefill
    tier) and ``src``/``dst`` replica ids for the ``ship`` trace event.
    """

    n_tokens: int
    page_size: int
    n_pages: int
    tokens: Optional[np.ndarray]
    seq_payload: List[Optional[jax.Array]]
    slot_payload: List[Optional[jax.Array]]
    bytes_on_wire: int = 0
    cost_s: float = 0.0
    # engine-layer annotations (router handoff)
    req: Any = None
    next_tok: int = -1
    src: int = -1
    dst: int = -1


#: legal ``kind`` values for :meth:`PagedCache.transfer_pages` — every
#: priced page movement in the repo is one of these.
TRANSFER_KINDS = ("migrate", "defrag", "ship")


# ---------------------------------------------------------------------------
# Device-side paged cache
# ---------------------------------------------------------------------------
@dataclass
class PagedCache:
    """Page pools + block tables for one engine instance.

    ``store`` is the cache pytree where every sequence leaf has been
    replaced by its pool ``(L, P+1, page, ...)``; non-sequence leaves keep
    their dense slot layout.  ``tables`` is host-resident; ``tables_dev``
    is refreshed lazily before any gather/scatter.

    ``max_seq`` is rounded up to a whole number of pages so the block
    tables tile the logical window exactly; callers that size buffers or
    occupancy math off ``max_seq`` must read it back after construction
    (the engine adopts the rounded value and asserts agreement in
    ``kv_report``).
    """
    entry: Any
    max_batch: int
    max_seq: int
    page_size: int
    num_pages: int
    tp: int = 1
    share: bool = False
    placement: Optional[PlacementMap] = None
    placement_policy: str = "free-first"
    device: Any = None     # where the pools and tables live (None: default)

    def __post_init__(self):
        if self.page_size <= 0:
            raise ValueError(f"page_size must be positive, "
                             f"got {self.page_size}")
        if self.max_seq % self.page_size:
            # round the logical window up so tables tile it exactly
            self.max_seq = num_blocks(self.max_seq,
                                      self.page_size) * self.page_size
        self.max_blocks = self.max_seq // self.page_size
        self.alloc = PageAllocator(self.num_pages,
                                   placement=self.placement,
                                   policy=self.placement_policy)
        # per-slot home region (affinity placement); -1 = unassigned
        self.home_region: Dict[int, int] = {}
        self.tables = np.full((self.max_batch, self.max_blocks), -1,
                              np.int32)
        self._tables_dev = None
        dense = self.entry.cache_zeros(self.max_batch, self.page_size,
                                       self.tp)
        leaves, self.treedef = jax.tree.flatten(dense)
        self.is_seq = probe_seq_leaves(self.entry, self.max_batch, self.tp)
        store = []
        for leaf, seq in zip(leaves, self.is_seq):
            if seq:
                # (L, B, page, ...) -> (L, P+1, page, ...): drop the batch
                # axis, add the page axis (+1 scratch page at index P)
                shape = (leaf.shape[0], self.num_pages + 1,
                         self.page_size) + leaf.shape[3:]
                store.append(jnp.zeros(shape, leaf.dtype,
                                       device=self.device))
            else:
                # dense slot layout, as allocated
                store.append(jax.device_put(leaf, self.device))
        # non-seq leaves don't depend on max_seq, so the probe-sized
        # cache_zeros call above produced them at exactly the right shape
        self.store = store
        # recurrent families have no sequence leaves: their per-request
        # state is O(1) and lives slot-dense, so they consume no pages
        self.has_seq = any(self.is_seq)
        self.prefix = PrefixIndex() if self.share else None
        # leading table entries mapped onto shared pages at admission —
        # write_slot skips re-writing them (their KV is already resident)
        self.shared_count = np.zeros((self.max_batch,), np.int64)
        self._pending_prompt: Dict[int, np.ndarray] = {}
        self.cow_forks = 0
        # cross-region home migration (defrag's spilled-page repair pass)
        self.migrated_pages = 0
        self.migration_cost_s = 0.0
        # in-pool compaction moves (defrag) and cross-pool shipments
        # (tier handoff), both priced through transfer_pages
        self.defrag_move_cost_s = 0.0
        self.shipped_pages = 0
        self.ship_cost_s = 0.0
        self._bytes_per_page: Optional[int] = None
        # lifecycle-event sink; the engine rebinds this to its own
        # (replica-bound) tracer when one is attached
        self.tracer = NULL_TRACER

    # -- block-table bookkeeping -------------------------------------------
    def _invalidate(self):
        """Drop the device mirror entirely — only the bulk rewrites
        (defrag / migrate / reset) pay a full rebuild; the per-slot hot
        ops maintain the mirror incrementally via ``_mirror_row`` /
        ``_mirror_set``."""
        self._tables_dev = None

    def tables_device(self) -> jax.Array:
        if self._tables_dev is None:
            # unmapped entries -> scratch page P (safe for gather/scatter)
            t = np.where(self.tables < 0, self.num_pages, self.tables)
            self._tables_dev = jnp.asarray(t, jnp.int32, device=self.device)
        return self._tables_dev

    def _mirror_row(self, slot: int) -> None:
        """Refresh one slot's row of the device table mirror in place
        (alloc/extend/free touch a single row — rebuilding the whole
        ``(B, nblk)`` table per tick was the serving loop's biggest
        host->device transfer)."""
        if self._tables_dev is None:
            return
        row = np.where(self.tables[slot] < 0, self.num_pages,
                       self.tables[slot]).astype(np.int32)
        self._tables_dev = self._tables_dev.at[slot].set(jnp.asarray(row))

    def _mirror_set(self, slot: int, blk: int, page: int) -> None:
        """Point one mirror entry at a new physical page (CoW fork)."""
        if self._tables_dev is None:
            return
        self._tables_dev = self._tables_dev.at[slot, blk].set(page)

    def mirror_consistent(self) -> bool:
        """True iff the incrementally maintained device mirror equals a
        fresh rebuild of the host tables.  An unbuilt mirror (None) is
        trivially consistent.  The allocator-model checker drives a
        scripted op sequence through this after every mutation."""
        if self._tables_dev is None:
            return True
        ref = np.where(self.tables < 0, self.num_pages, self.tables)
        return bool(np.array_equal(np.asarray(self._tables_dev), ref))

    def blocks_of(self, slot: int) -> List[int]:
        return [int(p) for p in self.tables[slot] if p >= 0]

    def pages_in_use(self) -> int:
        return self.alloc.used_pages

    def kv_tokens_resident(self) -> int:
        """Capacity (in tokens) of all allocated pages."""
        return self.alloc.used_pages * self.page_size

    def logical_pages(self) -> int:
        """Block-table entries mapped across all slots (>= physical pages
        whenever prefix sharing deduplicates)."""
        return int((self.tables >= 0).sum())

    def fragmentation(self) -> float:
        """Fraction of holes below the high-water page index (0 = the live
        set is compact at the lowest indices).  With a placement map the
        high-water mark is per region — affinity deliberately spreads
        slots across regions, which is placement, not fragmentation."""
        used = self.alloc.used_pages
        if used == 0:
            return 0.0
        if self.placement is None:
            return 1.0 - used / (self.alloc.highest_used() + 1)
        pmap = self.placement
        high: Dict[int, int] = {}       # region -> high-water page
        for p in self.alloc.live_pages():
            r = pmap.region_of(p)
            high[r] = max(high.get(r, p), p)
        span = sum(hw - pmap.region_pages(r).start + 1
                   for r, hw in high.items())
        return 1.0 - used / span if span else 0.0

    def sharing_report(self) -> Dict[str, Any]:
        logical = self.logical_pages()
        physical = self.alloc.used_pages
        return {"logical_pages": logical,
                "physical_pages": physical,
                "shared_pages": self.alloc.shared_pages,
                "dedup_ratio": logical / physical if physical else 1.0,
                "cow_forks": self.cow_forks}

    def prefix_residency(self, tokens: Optional[np.ndarray]) -> int:
        """Leading prompt pages of ``tokens`` already resident in the
        prefix trie — the front-end router's prefix-affinity probe.
        Counts only what admission would actually map (full pages plus an
        exact-match ragged tail); 0 when sharing is off."""
        if (not self.share or not self.has_seq or tokens is None
                or not len(tokens)):
            return 0
        return len(self.prefix.match(np.asarray(tokens), self.page_size))

    def alloc_slot(self, slot: int, n_tokens: int,
                   tokens: Optional[np.ndarray] = None) -> bool:
        """Allocate pages to cover ``n_tokens`` for an empty slot.

        With ``share=True`` and the prompt ``tokens`` given, the leading
        pages whose token chunks are already resident are *mapped* onto
        the existing shared pages (incref) and only the unshared tail is
        allocated.  Publication of the new pages into the trie is deferred
        to ``write_slot``, so a prefix can never be matched before its KV
        has actually been written.  Atomic: on failure nothing is mapped,
        incref'd, or allocated.
        """
        if not self.has_seq:
            return True
        assert not self.blocks_of(slot), "slot already mapped"
        need = num_blocks(n_tokens, self.page_size)
        shared: List[int] = []
        if self.share and tokens is not None and len(tokens):
            shared = self.prefix.match(np.asarray(tokens), self.page_size)
        home = self._assign_home(slot)
        # full prompt pages are publishable as trie edges, so they go
        # communal (any future holder reads them — no slot channel is
        # favored); the ragged tail + decode pages are private -> home
        n_communal = 0
        if self.share and tokens is not None:
            n_communal = max(0, len(tokens) // self.page_size
                             - len(shared))
        fresh = self.alloc.alloc(need - len(shared), home=home,
                                 communal=n_communal)
        if fresh is None:
            return False
        for p in shared:
            self.alloc.incref(p)
        pages = shared + fresh
        self.tables[slot, : len(pages)] = pages
        self.shared_count[slot] = len(shared)
        if self.share and tokens is not None:
            self._pending_prompt[slot] = np.asarray(tokens).copy()
        self._mirror_row(slot)
        return True

    def _assign_home(self, slot: int) -> Optional[int]:
        """Pick (and remember) the slot's home region: the slot region
        with the most free pages at admission, deterministic ties to the
        lowest id.  None without active placement."""
        if not self.alloc.placed:
            return None
        free = self.alloc.region_free()
        home = min((r for r in free if r != COMMUNAL),
                   key=lambda r: (-free[r], r))
        self.home_region[slot] = home
        return home

    def extend_slot(self, slot: int, n_tokens: int) -> bool:
        """Grow a slot's mapping to cover ``n_tokens`` total (on-demand
        decode growth).  Growth pages are private to the slot, so they
        prefer its home region.  No-op if already covered."""
        if not self.has_seq:
            return True
        have = len(self.blocks_of(slot))
        need = num_blocks(n_tokens, self.page_size)
        if need <= have:
            return True
        if need > self.max_blocks:
            return False
        pages = self.alloc.alloc(need - have,
                                 home=self.home_region.get(slot))
        if pages is None:
            return False
        self.tables[slot, have:need] = pages
        self._mirror_row(slot)
        return True

    def free_slot(self, slot: int) -> None:
        for p in self.blocks_of(slot):
            if self.alloc.decref(p) and self.prefix is not None:
                self.prefix.remove(p)
        self.tables[slot, :] = -1
        self.shared_count[slot] = 0
        self._pending_prompt.pop(slot, None)
        self.home_region.pop(slot, None)
        self._mirror_row(slot)

    def reset(self) -> None:
        self.alloc.reset()
        self.tables[:, :] = -1
        self.shared_count[:] = 0
        self._pending_prompt.clear()
        self.home_region.clear()
        if self.share:
            self.prefix = PrefixIndex()
        self.cow_forks = 0
        self.migrated_pages = 0
        self.migration_cost_s = 0.0
        self.defrag_move_cost_s = 0.0
        self.shipped_pages = 0
        self.ship_cost_s = 0.0
        self._invalidate()

    # -- copy-on-write -----------------------------------------------------
    def cow_for_write(self, slot: int, pos: int) -> bool:
        """Ensure the page a write at ``pos`` will hit is exclusively owned.

        Called by the engine before every decode scatter.  Forks (copies)
        the page when its refcount is > 1; returns False only when the fork
        could not allocate a page — the caller preempts a victim and
        retries.  No-op for unmapped / out-of-window targets (those land in
        the scratch page) and for already-exclusive pages.
        """
        if not self.has_seq:
            return True
        blk = pos // self.page_size
        if blk >= self.max_blocks:
            return True
        page = int(self.tables[slot, blk])
        if page < 0 or self.alloc.refcount(page) <= 1:
            return True
        return self.fork_page(slot, blk)

    def fork_page(self, slot: int, blk: int) -> bool:
        """Copy-on-write fork: give ``slot`` a private copy of the page at
        table entry ``blk``.  The original page (and its trie entry) stays
        in place for the remaining holders."""
        old = int(self.tables[slot, blk])
        assert old >= 0, "fork of unmapped table entry"
        # the fork is a private copy: it belongs in the slot's home region
        got = self.alloc.alloc(1, home=self.home_region.get(slot))
        if got is None:
            return False
        new = got[0]
        self.store = [
            _copy_page(pool, old, new) if seq else pool
            for pool, seq in zip(self.store, self.is_seq)]
        self.tables[slot, blk] = new
        if blk < self.shared_count[slot]:
            self.shared_count[slot] = blk
        if self.alloc.decref(old) and self.prefix is not None:
            # last holder raced away (defensive: cow_for_write only forks
            # at refcount > 1, so this should not trigger)
            self.prefix.remove(old)
        self.cow_forks += 1
        if self.tracer.enabled:
            self.tracer.emit("cow_fork", slot=slot,
                             blk=blk, old_page=old, new_page=new)
        self._mirror_set(slot, blk, new)
        return True

    # -- device ops --------------------------------------------------------
    def gather(self) -> Any:
        """Assemble the dense ``(L, B, max_seq, ...)`` cache view.

        The reference decode path runs the ordinary ``decode_step`` on this
        view (token-exact vs. the dense engine); the Pallas paged path
        skips this and reads pages through the block table instead.
        """
        tables = self.tables_device()
        out = []
        for leaf, seq in zip(self.store, self.is_seq):
            if seq:
                g = _gather_pool(leaf, tables)
                out.append(g)
            else:
                out.append(leaf)
        return jax.tree.unflatten(self.treedef, out)

    def scatter_token(self, cache: Any, positions: np.ndarray,
                      active: np.ndarray) -> None:
        """Write back one decode step.

        ``cache`` is the updated dense view returned by ``decode_step``;
        the single new token per slot was written at ``positions[b]``
        (the pre-step length).  Sequence leaves scatter just that token
        into their pools; non-sequence leaves (recurrent state, lengths)
        are replaced wholesale.  ``active`` masks slots whose write should
        land in the scratch page, as do writes past a slot's mapped
        window.  With sharing enabled the caller must have run
        ``cow_for_write`` for every active slot first, so no write here
        ever lands on a page with refcount > 1.
        """
        tables = self.tables_device()
        pos = jnp.asarray(np.where(active, positions, 0), jnp.int32)
        act = jnp.asarray(active)
        leaves, _ = jax.tree.flatten(cache)
        new_store = []
        for pool, leaf, seq in zip(self.store, leaves, self.is_seq):
            if seq:
                new_store.append(
                    _scatter_token_jit(pool, leaf, tables, pos, act,
                                       self.page_size))
            else:
                new_store.append(leaf)
        self.store = new_store

    def write_slot(self, slot: int, cache1: Any, n_tokens: int) -> None:
        """Insert a freshly prefilled request (batch-1 cache) into ``slot``.

        Sequence leaves are chopped into pages and scattered to the slot's
        block table — pages mapped from the shared-prefix trie are skipped
        (their KV is already resident and other holders may be reading
        them); non-sequence leaves use the dense ``_insert_slot`` rule
        (rank-1 -> axis 0, else axis 1).  The slot's freshly written pages
        are then published to the trie.
        """
        pages = self.blocks_of(slot)
        need = num_blocks(n_tokens, self.page_size)
        skip = int(self.shared_count[slot])
        if self.has_seq:
            assert len(pages) >= need, \
                "write_slot without enough pages mapped"
        idx = jnp.asarray(pages[skip:need], jnp.int32)
        leaves, _ = jax.tree.flatten(cache1)
        new_store = []
        for pool, leaf, seq in zip(self.store, leaves, self.is_seq):
            if seq:
                if skip < need:
                    pool = _write_pages(pool, leaf, idx, skip, need,
                                        self.page_size)
                new_store.append(pool)
            else:
                if leaf.ndim == 1:
                    new_store.append(pool.at[slot].set(leaf[0]))
                else:
                    new_store.append(pool.at[:, slot].set(leaf[:, 0]))
        self.store = new_store
        self.commit_prefix(slot)

    # -- direct chunked prefill (no dense staging buffer) ------------------
    def gather_slot(self, slot: int, pos: int) -> Any:
        """Assemble a batch-1 dense cache view of one slot's block-table
        window for an ``extend_step`` chunk at offset ``pos``.

        Sequence leaves gather the slot's full page window (unmapped
        entries read the scratch page — the causal mask blanks everything
        past ``pos`` + chunk anyway); non-sequence leaves slice the slot
        column, except the rank-1 lengths leaf which is pinned to ``pos``
        (the slot-dense copy is stale until the first chunk commits).
        """
        row = np.where(self.tables[slot] < 0, self.num_pages,
                       self.tables[slot])
        t_dev = jnp.asarray(row[None, :], jnp.int32)
        out = []
        for leaf, seq in zip(self.store, self.is_seq):
            if seq:
                out.append(_gather_pool(leaf, t_dev))
            elif leaf.ndim == 1:
                out.append(jnp.full((1,), pos, leaf.dtype))
            else:
                out.append(leaf[:, slot: slot + 1])
        return jax.tree.unflatten(self.treedef, out)

    def scatter_chunk(self, slot: int, cache1: Any, pos: int,
                      take: int) -> None:
        """Write one prefill chunk (``take`` tokens at offset ``pos``)
        from the batch-1 view returned by ``extend_step`` straight into
        the slot's block-table pages.

        This is what lets the paged engine's chunk scheduler skip the
        dense per-request staging buffer (and the admission-time
        ``write_slot`` copy) entirely.  Positions inside shared-prefix
        pages are routed to the scratch page: their KV is already
        resident and other holders may be reading it — re-writing would
        perturb it with this request's (numerically different) recompute.
        Non-sequence leaves (lengths, recurrent state) are written to the
        slot column wholesale each chunk.
        """
        ps = self.page_size
        idx = np.arange(pos, pos + take)
        blk = idx // ps
        row = self.tables[slot]
        assert (row[blk] >= 0).all(), "scatter_chunk into unmapped pages"
        pages = np.where(blk < int(self.shared_count[slot]),
                         self.num_pages, row[blk])
        pages_dev = jnp.asarray(pages, jnp.int32)
        offs_dev = jnp.asarray(idx % ps, jnp.int32)
        leaves, _ = jax.tree.flatten(cache1)
        new_store = []
        for pool, leaf, seq in zip(self.store, leaves, self.is_seq):
            if seq:
                new_store.append(_scatter_chunk_jit(pool, leaf, pages_dev,
                                                    offs_dev, pos))
            elif leaf.ndim == 1:
                new_store.append(pool.at[slot].set(leaf[0]))
            else:
                new_store.append(pool.at[:, slot].set(leaf[:, 0]))
        self.store = new_store

    def commit_prefix(self, slot: int) -> None:
        """Publish the slot's prompt pages now that their KV is written."""
        tokens = self._pending_prompt.pop(slot, None)
        if tokens is None or self.prefix is None:
            return
        covered = num_blocks(len(tokens), self.page_size)
        self.prefix.register(tokens, self.blocks_of(slot)[:covered],
                             self.page_size)

    # -- priced page movement (the one code path) --------------------------
    def transfer_pages(self, n_pages: int, *, sys=None, hops: int = 0,
                       kind: str = "migrate") -> CollectiveCost:
        """Price (and account) the movement of ``n_pages`` physical pages.

        Every page movement in the cache goes through here, costed by
        :func:`~repro.core.noc.page_ship`: spilled-page re-homing
        (``kind="migrate"``, ``hops=0`` — intra-stack, exactly the
        legacy ``page_gather`` number), defrag compaction moves
        (``kind="defrag"``, ``hops=0``), and cross-stack tier shipments
        (``kind="ship"``, ``hops>=1`` — adds the inter-stack link and
        destination-scatter terms).  Accumulates the matching counters
        (``migrated_pages``/``migration_cost_s``,
        ``defrag_move_cost_s``, ``shipped_pages``/``ship_cost_s``) and
        emits the ``migrate`` lifecycle event; ``defrag``/``ship``
        events are emitted by their callers, which own the span
        context (moved counts, src/dst replicas)."""
        if kind not in TRANSFER_KINDS:
            raise ValueError(f"unknown transfer kind {kind!r}; "
                             f"choose from {TRANSFER_KINDS}")
        if n_pages <= 0:
            return CollectiveCost(0, 0.0)
        cost = page_ship(sys if sys is not None else default_system(),
                         n_pages * self.bytes_per_page(), n_pages,
                         hops=hops)
        if kind == "migrate":
            self.migrated_pages += n_pages
            self.migration_cost_s += cost.time_s
            if self.tracer.enabled:
                self.tracer.emit("migrate", pages=n_pages,
                                 cost_s=cost.time_s)
        elif kind == "defrag":
            self.defrag_move_cost_s += cost.time_s
        else:
            self.shipped_pages += n_pages
            self.ship_cost_s += cost.time_s
        return cost

    def migrate_spilled(self, sys=None) -> int:
        """Move exclusively-owned pages that spilled out of their slot's
        home region back home (placed mode only).

        Under pressure ``alloc`` deliberately spills to a foreign region
        rather than fail admission — but once the pool relaxes the slot
        keeps paying the cross-region gather tax on every decode step,
        forever.  This pass repairs that: each spilled page whose home
        region has free capacity again is physically copied home through
        the NoC, priced through :meth:`transfer_pages` (``hops=0`` —
        the intra-stack :func:`~repro.core.noc.page_ship` degradation)
        and accumulated into ``migrated_pages`` / ``migration_cost_s``.

        Shared pages stay put — refcount > 1 means holders with
        different homes read them — and trie-registered pages are
        communal by design.  Returns the number of pages moved.
        """
        if not (self.has_seq and self.alloc.placed):
            return 0
        moved = 0
        for slot, home in sorted(self.home_region.items()):
            for blk in range(self.max_blocks):
                page = int(self.tables[slot, blk])
                if (page < 0 or self.alloc.refcount(page) != 1
                        or self.placement.region_of(page) == home):
                    continue
                if self.prefix is not None \
                        and page in self.prefix._by_page:
                    continue
                got = self.alloc.alloc_in(home, 1)
                if got is None:
                    break                    # home is full again
                new = got[0]
                self.store = [
                    _copy_page(pool, page, new) if seq else pool
                    for pool, seq in zip(self.store, self.is_seq)]
                self.tables[slot, blk] = new
                self.alloc.decref(page)
                moved += 1
        if moved:
            self.transfer_pages(moved, sys=sys, hops=0, kind="migrate")
            self._invalidate()
        return moved

    def defrag(self, sys=None) -> Dict[int, int]:
        """Compact live pages to the lowest indices.

        Returns the old->new mapping applied.  Pool data is permuted on
        device; block tables, the prefix trie, and the allocator (via its
        public ``rebuild``, refcounts preserved) are renumbered so the
        logical contents (``gather()``) are unchanged.

        With a placement map, compaction is **region-preserving**: each
        region's live pages compact to that region's lowest indices and
        never migrate across regions (a cross-region move would be a
        physical DMA copy through the NoC — exactly the traffic placement
        exists to avoid).  The one exception is deliberate and priced:
        under an active placement policy a :meth:`migrate_spilled` repair
        pass runs first, copying exclusively-owned spilled pages back to
        their slot's home region through the NoC (charged via
        ``page_gather``) so a slot squeezed during a pressure spike is
        not fragmented across regions forever.  The prefix trie is
        renumbered through the same constrained mapping, so a trie hit
        after defrag still points at a live page in the original channel
        region; both invariants are asserted below.
        """
        if self.alloc.placed:
            self.migrate_spilled(sys)
        live = self.alloc.live_pages()
        if self.placement is None:
            mapping = {old: new for new, old in enumerate(live)}
        else:
            mapping = {}
            for r in self.placement.regions():
                live_r = [p for p in live
                          if self.placement.region_of(p) == r]
                for p, tgt in zip(live_r, self.placement.region_pages(r)):
                    mapping[p] = tgt
            assert all(self.placement.region_of(o)
                       == self.placement.region_of(n)
                       for o, n in mapping.items()), \
                "defrag target crossed a placement region"
        if all(o == n for o, n in mapping.items()):
            return mapping
        with self.tracer.span("defrag") as sp:
            perm = np.arange(self.num_pages + 1)
            for old, new in mapping.items():
                perm[new] = old
            perm_dev = jnp.asarray(perm, jnp.int32)
            # the largest copy the host issues: a whole new pool
            self.store = [
                _permute_pool(pool, perm_dev) if seq else pool
                for pool, seq in zip(self.store, self.is_seq)]
            lut = np.full(self.num_pages + 1, -1, np.int32)
            for old, new in mapping.items():
                lut[old] = new
            self.tables = np.where(self.tables < 0, -1,
                                   lut[np.maximum(self.tables, 0)]
                                   ).astype(np.int32)
            self.alloc.rebuild({mapping[p]: self.alloc.refcount(p)
                                for p in live})
            moved_n = sum(1 for o, n in mapping.items() if o != n)
            cost = self.transfer_pages(moved_n, sys=sys, hops=0,
                                       kind="defrag")
            if self.tracer.enabled:
                sp.args.update(live_pages=len(live), moved=moved_n,
                               cost_s=cost.time_s)
        if self.prefix is not None:
            self.prefix.remap(mapping)
            # region-constrained targets must keep the trie consistent:
            # every registered page is still allocated after renumbering
            assert all(self.alloc.refcount(p) > 0
                       for p in self.prefix._by_page), \
                "defrag left the prefix trie pointing at a dead page"
        self._invalidate()
        return mapping

    # -- cross-pool shipment (prefill -> decode tier, PR 10) ---------------
    def export_slot_pages(self, slot: int, n_tokens: int,
                          tokens: Optional[np.ndarray] = None, *,
                          sys=None, hops: int = 1) -> PageShipment:
        """Package ``slot``'s resident state for another pool and
        release the slot here.

        Sequence leaves gather the slot's first ``ceil(n_tokens /
        page)`` pages into a contiguous ``(L, n, page, ...)`` block
        (shared-prefix pages included — the destination decides what it
        can dedup against its own trie); dense leaves copy the slot
        column.  The movement is priced once, here, through
        :meth:`transfer_pages` (``kind="ship"``): the source pays the
        gather + ``hops`` inter-stack link crossings + the destination
        scatter.  The slot is then freed exactly as a finished request
        would be — shared pages survive under their remaining holders'
        references, and trie entries drop only with their last holder.
        """
        pages = self.blocks_of(slot)
        if self.has_seq:
            need = num_blocks(n_tokens, self.page_size)
            assert len(pages) >= need, \
                "export_slot_pages of an under-mapped slot"
            pages = pages[:need]
        seq_payload: List[Optional[jax.Array]] = []
        slot_payload: List[Optional[jax.Array]] = []
        idx = jnp.asarray(pages, jnp.int32)
        for pool, seq in zip(self.store, self.is_seq):
            if seq:
                seq_payload.append(pool[:, idx] if pages else None)
                slot_payload.append(None)
            else:
                seq_payload.append(None)
                slot_payload.append(pool[slot] if pool.ndim == 1
                                    else pool[:, slot])
        cost = self.transfer_pages(len(pages), sys=sys, hops=hops,
                                   kind="ship")
        shipment = PageShipment(
            n_tokens=n_tokens, page_size=self.page_size,
            n_pages=len(pages),
            tokens=(np.asarray(tokens).copy()
                    if tokens is not None else None),
            seq_payload=seq_payload, slot_payload=slot_payload,
            bytes_on_wire=cost.bytes_on_wire, cost_s=cost.time_s)
        self.free_slot(slot)
        return shipment

    def import_slot_pages(self, slot: int,
                          shipment: PageShipment) -> bool:
        """Splice a :class:`PageShipment` into an empty ``slot`` here.

        Refcount/region reconciliation: the destination's *own* prefix
        trie is consulted first — leading prompt pages already resident
        are mapped (incref) instead of re-written, exactly as a local
        admission would dedup; only the unshared tail pages allocate
        (home-region assignment + communal steering for publishable
        full prompt pages) and receive the shipped payload.  The
        imported coverage is then registered in the destination trie so
        later arrivals dedup against it.  Atomic: returns ``False``
        with nothing mapped, incref'd, or written when the pool cannot
        hold the unshared pages — the caller retries or re-targets.
        """
        if shipment.page_size != self.page_size:
            raise ValueError(
                f"shipment page_size {shipment.page_size} != pool "
                f"page_size {self.page_size} (tiers must agree)")
        if not self.has_seq:
            self._import_dense(slot, shipment)
            return True
        assert not self.blocks_of(slot), "import into a mapped slot"
        need = shipment.n_pages
        tokens = shipment.tokens
        shared: List[int] = []
        if self.share and tokens is not None and len(tokens):
            shared = self.prefix.match(np.asarray(tokens),
                                       self.page_size)[:need]
        home = self._assign_home(slot)
        n_communal = 0
        if self.share and tokens is not None:
            n_communal = max(0, len(tokens) // self.page_size
                             - len(shared))
        fresh = self.alloc.alloc(need - len(shared), home=home,
                                 communal=n_communal)
        if fresh is None:
            self.home_region.pop(slot, None)
            return False
        for p in shared:
            self.alloc.incref(p)
        pages = shared + fresh
        self.tables[slot, :need] = pages
        self.shared_count[slot] = len(shared)
        dst_idx = jnp.asarray(fresh, jnp.int32)
        src_idx = jnp.asarray(np.arange(len(shared), need), jnp.int32)
        new_store = []
        for pool, seq, payload, col in zip(self.store, self.is_seq,
                                           shipment.seq_payload,
                                           shipment.slot_payload):
            # the shipment may come from a replica on another device
            payload, col = jax.device_put((payload, col), pool.sharding)
            if seq:
                if fresh:
                    pool = pool.at[:, dst_idx].set(payload[:, src_idx])
                new_store.append(pool)
            elif pool.ndim == 1:
                new_store.append(pool.at[slot].set(col))
            else:
                new_store.append(pool.at[:, slot].set(col))
        self.store = new_store
        if self.share and tokens is not None:
            self._pending_prompt[slot] = np.asarray(tokens).copy()
            self.commit_prefix(slot)
        self._mirror_row(slot)
        return True

    def _import_dense(self, slot: int, shipment: PageShipment) -> None:
        """Recurrent families: no pages — just restore the slot column."""
        new_store = []
        for pool, col in zip(self.store, shipment.slot_payload):
            col = jax.device_put(col, pool.sharding)
            if pool.ndim == 1:
                new_store.append(pool.at[slot].set(col))
            else:
                new_store.append(pool.at[:, slot].set(col))
        self.store = new_store

    # -- placement scoring -------------------------------------------------
    def bytes_per_page(self) -> int:
        """Bytes one physical page holds across all paged leaves/layers
        (the per-page gather payload).  Pool shapes are fixed at
        construction, so the first computation is cached."""
        if self._bytes_per_page is None:
            self._bytes_per_page = sum(
                int(np.prod([d for i, d in enumerate(pool.shape)
                             if i != 1])) * pool.dtype.itemsize
                for pool, seq in zip(self.store, self.is_seq) if seq)
        return self._bytes_per_page

    def slot_region_counts(self, slot: int) -> Dict[int, int]:
        """Region histogram of the slot's mapped pages (requires a
        placement map)."""
        assert self.placement is not None
        counts: Dict[int, int] = {}
        for p in self.blocks_of(slot):
            r = self.placement.region_of(p)
            counts[r] = counts.get(r, 0) + 1
        return counts

    def gather_cost_slot(self, sys, slot: int) -> Optional[GatherCost]:
        """DMA/NoC cost of this slot's block-table gather on ``sys``
        (None when the slot has no pages mapped).  Scored from the
        majority region for every policy — the scheduling half of the
        co-design issues the gather from the PU already holding most of
        the table."""
        if self.placement is None or not self.blocks_of(slot):
            return None
        counts = self.slot_region_counts(slot)
        return gather_cost(sys, counts, self.bytes_per_page())

    def gather_cost_mean(self, sys, slots: Optional[Sequence[int]] = None
                         ) -> Tuple[float, float]:
        """Mean (gather time, home-channel concentration) over the given
        slots (default: every slot with pages mapped)."""
        if slots is None:
            slots = [s for s in range(self.max_batch) if self.blocks_of(s)]
        costs = [c for c in (self.gather_cost_slot(sys, s) for s in slots)
                 if c is not None]
        if not costs:
            return 0.0, 1.0
        return (float(np.mean([c.time_s for c in costs])),
                float(np.mean([c.concentration for c in costs])))

    def placement_report(self) -> PlacementReport:
        """Per-region pressure snapshot, typed (PR 10).

        Returns an *empty* :class:`~repro.serving.replica_api.
        PlacementReport` without a placement map; ``to_dict()`` at the
        JSON/metrics boundary reproduces the legacy dict (``{}`` when
        empty) key-for-key."""
        if self.placement is None:
            return PlacementReport()
        used = self.alloc.region_used()
        free = self.alloc.region_free()
        return PlacementReport(
            placement_policy=self.placement_policy,
            n_regions=self.placement.n_regions,
            communal_pages=self.placement.communal_pages,
            region_used={str(r): used[r] for r in used},
            region_free={str(r): free[r] for r in free})


# ---------------------------------------------------------------------------
# jitted pool primitives (shapes static per engine instance)
# ---------------------------------------------------------------------------
@jax.jit
def _gather_pool(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """pool (L, P+1, ps, ...) + tables (B, nblk) -> (L, B, nblk*ps, ...)."""
    g = pool[:, tables]                      # (L, B, nblk, ps, ...)
    l, b, nblk, ps = g.shape[:4]
    return g.reshape((l, b, nblk * ps) + g.shape[4:])


@jax.jit
def _permute_pool(pool: jax.Array, perm: jax.Array) -> jax.Array:
    return pool[:, perm]


@jax.jit
def _copy_page(pool: jax.Array, src, dst) -> jax.Array:
    return pool.at[:, dst].set(pool[:, src])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _write_pages_impl(pool, leaf, idx, skip, page_size):
    # leaf (L, 1, S, ...) with S >= (skip+n)*ps; chop the unshared span
    # into (L, n, ps, ...) and scatter it at idx
    l = leaf.shape[0]
    n = idx.shape[0]
    chunk = leaf[:, 0, skip * page_size:(skip + n) * page_size]
    chunk = chunk.reshape((l, n, page_size) + leaf.shape[3:])
    return pool.at[:, idx].set(chunk)


def _write_pages(pool, leaf, idx, skip, need, page_size):
    s = leaf.shape[SEQ_AXIS]
    if s < need * page_size:                 # pad ragged tail to page edge
        pad = [(0, 0)] * leaf.ndim
        pad[SEQ_AXIS] = (0, need * page_size - s)
        leaf = jnp.pad(leaf, pad)
    return _write_pages_impl(pool, leaf, idx, skip, page_size)


@jax.jit
def _scatter_chunk_jit(pool, leaf, pages, offs, start):
    """Scatter ``take`` consecutive tokens (``leaf[:, 0, start:start+take]``)
    into ``(pages[j], offs[j])`` pool positions.  ``pages`` already routes
    shared-prefix positions to the scratch page."""
    take = pages.shape[0]
    vals = jax.lax.dynamic_slice_in_dim(leaf[:, 0], start, take, axis=1)
    return pool.at[:, pages, offs].set(vals)


@jax.jit
def _scatter_token_jit(pool, leaf, tables, pos, active, page_size):
    """Scatter leaf[:, b, pos[b]] into pool at the page holding pos[b].

    A write whose position falls outside the slot's mapped window (block
    index past the table) is routed to the scratch page together with
    inactive slots — clipping ``blk`` alone used to alias such writes onto
    the window's *last live page*, corrupting resident KV.
    """
    b = leaf.shape[BATCH_AXIS]
    blk = pos // page_size                   # (B,)
    off = pos % page_size
    nblk = tables.shape[1]
    in_window = blk < nblk
    blk = jnp.clip(blk, 0, nblk - 1)
    page = jnp.take_along_axis(tables, blk[:, None], axis=1)[:, 0]
    trash = pool.shape[1] - 1                # scratch page index P
    page = jnp.where(active & in_window, page, trash)
    pos = jnp.clip(pos, 0, leaf.shape[SEQ_AXIS] - 1)
    val = jnp.take_along_axis(
        leaf, pos.reshape((1, b) + (1,) * (leaf.ndim - 2)),
        axis=SEQ_AXIS)                       # (L, B, 1, ...)
    val = jnp.squeeze(val, axis=SEQ_AXIS)    # (L, B, ...)
    return pool.at[:, page, off].set(val)
