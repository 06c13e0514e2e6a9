"""Slot-paged KV cache for the continuous-batching engine.

Layout: one shared physical page pool per layer stack,

    k_pool, v_pool: (L, n_pages, page_size, hkv, dh)

plus a per-slot page table ``(n_slots, pages_per_slot)`` of physical page
ids. A *slot* is a decode lane in the fused step executable; a slot's
logical sequence dim is the concatenation of its pages, so admission only
needs ``ceil(need / page_size)`` free pages anywhere in the pool — no
contiguous-region allocation, no per-request max_len reservation in one
monolithic ``{"k","v","len"}`` buffer.

Physical page 0 is reserved as a scratch page: inactive slots point every
page-table entry at it, so the fused step (which always runs all n_slots
rows — static shapes) can scatter its dead-lane writes somewhere harmless
instead of corrupting pages that were freed and re-issued to live streams.

**Pages are refcounted** (`ref`): the prefix cache (serve/prefix.py)
shares one physical page between every slot whose prompt starts with the
same tokens, and keeps finished prompts' pages resident for future reuse.
``allocate`` takes an optional leading run of already-filled shared pages
(ref + 1 each), ``release`` *decrements* instead of freeing, and a page
returns to the free list exactly when its last reference — slot or prefix
tree — drops. The page-leak invariant generalises: ``ref[p] == (# slot
page-table entries pointing at p) + (1 if the prefix tree caches p)``,
and ``ref == 0  <=>  p is on the free list`` (``check_refs``). Shared
pages are immutable to slots: admission places them strictly *before* a
slot's first written position, and a partially-filled shared tail page is
copied first (``copy_page`` — copy-on-write at admission).

Per-slot serving state carried here besides the pool:
  * ``lens``   — host-mirrored valid prefix length per slot (int64 np);
                 the device copy is an input of every fused step, so the
                 decode loop never does an ``int(cache["len"])`` sync.
  * ``ranks``  — per-slot rank bucket, device-resident (jnp int32).
  * ``basis``  — per-slot per-layer K eigenbasis (top r_max columns) from
                 the last segment decision. The fused decode step projects
                 q and the K view onto this cached basis (factor padding +
                 per-row rank masking), so the eigh cost is paid once per
                 segment — paper Eq. 12's refresh — and the layer-0 slice
                 also feeds the drift trigger.
  * ``spectra``— per-slot layer-0 K spectra (sigma^2, descending) persisted
                 from the last segment decision: the "before" side of the
                 Eq. 9 transition veto, so the veto measures the actual
                 segment-to-segment transition instead of comparing the
                 current spectra against themselves.
  * ``mass_pool`` — per-key accumulated softmax attention mass,
                 **slot-indexed** ``(L, n_slots, max_len, hkv)``: seeded by
                 the prefill's causal attention mass and advanced in-graph
                 by every fused decode step. The segment decision builds
                 its eigenbasis from the *weighted* Gram K^T diag(w) K, so
                 the basis concentrates on directions that actually
                 receive score mass. Mass is per-*stream* state (which
                 queries attended), not per-page state — a physical page
                 shared between two prefix-hit slots receives different
                 mass from each — so unlike K/V it is NOT paged; the row
                 is zeroed at admission and, on a prefix hit, re-seeded
                 from the tree's snapshot.
  * ``kt_pool``— the K cache in factor form, kt = K . B_r (top r_max
                 columns of the slot's segment basis), **slot-indexed**
                 ``(L, n_slots + 1, max_len, hkv, r_keep)`` (+1 scratch
                 row for dead-lane writes): rewritten for the whole slot
                 when a decision refreshes the basis, appended per token
                 by the fused step. Like the basis it factors against, kt
                 is per-slot state — two slots sharing prefix K pages hold
                 different bases, so their factors of the *same* physical
                 page differ. The decode score contraction reads kt
                 (r_max/d of the dense K bytes); dense K stays resident
                 only for basis refresh and drift.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, Iterable, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro import nn
from repro.configs.base import ModelConfig


class PagedKVCache:
    """Refcounted page pool + page tables + per-slot serving state."""

    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 factored: Optional[bool] = None):
        self.cfg = cfg
        self.n_slots = n_slots
        self.page_size = page_size
        self.pages_per_slot = -(-max_len // page_size)
        self.max_len = self.pages_per_slot * page_size   # logical view M
        # +1 for the reserved scratch page 0
        self.n_pages = (n_pages if n_pages is not None
                        else n_slots * self.pages_per_slot + 1)
        dtype = nn.dt(cfg.dtype)
        dh = cfg.resolved_head_dim()
        L, hkv = cfg.num_layers, cfg.num_kv_heads
        self.k_pool = jnp.zeros((L, self.n_pages, page_size, hkv, dh), dtype)
        self.v_pool = jnp.zeros((L, self.n_pages, page_size, hkv, dh), dtype)
        self.page_table = np.zeros((n_slots, self.pages_per_slot), np.int32)
        self._free: List[int] = list(range(self.n_pages - 1, 0, -1))  # not 0
        self.ref = np.zeros((self.n_pages,), np.int32)
        self.lens = np.zeros((n_slots,), np.int64)
        # leading pages each slot was admitted onto already shared (a
        # prefix hit): the slot never writes them (see shared_floor)
        self.n_shared = np.zeros((n_slots,), np.int64)
        self.rank_on = cfg.rank.mode != "off"
        r_max = int(cfg.rank.rank_grid[-1]) if self.rank_on else dh
        self.r_keep = min(r_max, dh)
        if factored and not self.rank_on:
            raise ValueError("factor-form K cache requires a rank mode: "
                             "kt = K . B_r needs a segment basis to "
                             "project onto")
        # default: factor form only when it actually cuts read bytes
        # (r_max < dh); at r_keep == dh the factor pool costs a full extra
        # K-sized pool + per-token appends for a 1.0 read ratio. Explicit
        # factored=True still opts in (the bench's full-rank parity check).
        self.factored = (self.rank_on and self.r_keep < dh
                         if factored is None else bool(factored))
        self.ranks = jnp.full((n_slots,), r_max, jnp.int32)
        self.basis = jnp.zeros((L, n_slots, hkv, dh, self.r_keep),
                               jnp.float32)
        # weighted-Gram + veto state only exist on the rank path; the
        # factor pool additionally needs the engine to opt in (it trades
        # r_max/d of the K bytes for r_max/d extra cache memory)
        self.mass_pool = (jnp.zeros((L, n_slots, self.max_len, hkv),
                                    jnp.float32) if self.rank_on else None)
        self.spectra = (jnp.zeros((n_slots, hkv, dh), jnp.float32)
                        if self.rank_on else None)
        self.kt_pool = (jnp.zeros((L, n_slots + 1, self.max_len, hkv,
                                   self.r_keep), dtype)
                        if self.factored else None)

    # -- host-side page accounting --------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_needed(self, total_len: int) -> int:
        return -(-total_len // self.page_size)

    def retain(self, pages: Iterable[int]) -> None:
        """Add one reference to each page (prefix-tree insertion)."""
        for p in pages:
            if p == 0:
                raise ValueError("cannot retain the scratch page")
            self.ref[p] += 1

    def unref(self, pages: Iterable[int]) -> None:
        """Drop one reference per page; a page whose last reference drops
        returns to the free list."""
        for p in pages:
            r = int(self.ref[p]) - 1
            if r < 0:
                raise AssertionError(f"refcount underflow on page {p}")
            self.ref[p] = r
            if r == 0:
                self._free.append(int(p))

    def allocate(self, slot: int, total_len: int,
                 prefix_pages: Sequence[int] = ()) -> bool:
        """Reserve pages covering ``total_len`` tokens for ``slot``.

        ``prefix_pages`` is a leading run of already-filled shared pages
        (a prefix-cache hit): they become the slot's first page-table
        entries with ref + 1 each, and only the remainder is drawn from
        the free list. Returns False (no mutation) when the free pool
        can't cover the fresh remainder."""
        need = self.pages_needed(total_len)
        fresh = need - len(prefix_pages)
        if need > self.pages_per_slot or fresh < 0 or fresh > len(self._free):
            return False
        pages = list(prefix_pages) + [self._free.pop() for _ in range(fresh)]
        self.retain(prefix_pages)
        for p in pages[len(prefix_pages):]:
            self.ref[p] += 1            # fresh pages: 0 -> 1
        self.page_table[slot, :] = 0
        self.page_table[slot, :need] = pages
        self.lens[slot] = 0
        self.n_shared[slot] = len(prefix_pages)
        return True

    def release(self, slot: int) -> None:
        """Drop the slot's references and park it on scratch. Pages still
        held by the prefix tree (or another sharing slot) stay out of the
        free list until their last reference drops."""
        self.unref(int(p) for p in self.page_table[slot] if p != 0)
        self.page_table[slot, :] = 0
        self.lens[slot] = 0
        self.n_shared[slot] = 0

    def shared_floor(self, slot: int) -> int:
        """First logical position of ``slot`` past the pages it was
        admitted onto already shared (a prefix hit's leading run, still
        held by the prefix tree or another slot): everything before it is
        immutable to this slot.

        This is the rewind floor for speculative decoding: a draft/verify
        step writes (and a rejection logically rewinds, by not advancing
        ``lens`` past the accepted prefix) only positions >= this floor.
        The invariant holds by construction — shared pages are placed
        strictly before the slot's first written position and a partial
        shared tail page is COWed at admission — so speculative writes at
        positions >= lens can never land on a shared page; the engine
        asserts it per step rather than trusting the construction.

        Pages the slot filled itself stay writable past its prompt even
        once the prefix tree holds them too: the tree claims only the
        prompt's positions, and a later hit that ends inside such a page
        copies it first."""
        return int(self.n_shared[slot]) * self.page_size

    def live_pages(self) -> Dict[int, List[int]]:
        """slot -> referenced physical pages (for invariant checks)."""
        return {s: [int(p) for p in row if p != 0]
                for s, row in enumerate(self.page_table)}

    def check_refs(self, tree_pages: Iterable[int] = ()) -> None:
        """Assert the refcount invariant: every page's refcount equals its
        slot page-table references plus its prefix-tree references, free
        pages are exactly the zero-ref pages, each listed once."""
        counts: Counter = Counter()
        for row in self.page_table:
            for p in row:
                if p:
                    counts[int(p)] += 1
        for p in tree_pages:
            counts[int(p)] += 1
        free = Counter(self._free)
        assert 0 not in counts and 0 not in free, "scratch page referenced"
        assert all(v == 1 for v in free.values()), "free-list duplicate"
        for p in range(1, self.n_pages):
            expect = counts.get(p, 0)
            got = int(self.ref[p])
            assert got == expect, \
                f"page {p}: refcount {got} != {expect} references"
            assert (free.get(p, 0) == 1) == (expect == 0), \
                f"page {p}: ref {expect} but free-list presence " \
                f"{free.get(p, 0)}"

    # -- device-side page ops -------------------------------------------

    def copy_page(self, dst: int, src: int) -> None:
        """Copy one physical page's K/V contents (copy-on-write: a prefix
        hit whose shared tail page is only partially filled gets a private
        copy to append into — the shared original stays immutable)."""
        self.k_pool = self.k_pool.at[:, dst].set(self.k_pool[:, src])
        self.v_pool = self.v_pool.at[:, dst].set(self.v_pool[:, src])

    def write_prefill(self, slot: int, k_layers: jnp.ndarray,
                      v_layers: jnp.ndarray,
                      mass_layers: Optional[jnp.ndarray] = None) -> None:
        """Scatter a prefilled (L, s, hkv, dh) K/V run into the slot's pages
        and set its length. The slot's attention-mass row is zeroed (a
        recycled slot must not keep its previous occupant's mass) and,
        when ``mass_layers`` (L, s, hkv) is given, re-seeded with the
        prompt's per-key causal attention mass. Control-plane op (one
        dispatch per admission)."""
        s = k_layers.shape[1]
        pos = np.arange(s)
        phys = jnp.asarray(self.page_table[slot][pos // self.page_size])
        off = jnp.asarray(pos % self.page_size)
        self.k_pool = self.k_pool.at[:, phys, off].set(
            k_layers.astype(self.k_pool.dtype))
        self.v_pool = self.v_pool.at[:, phys, off].set(
            v_layers.astype(self.v_pool.dtype))
        if self.mass_pool is not None:
            mp = self.mass_pool.at[:, slot].set(0.0)
            if mass_layers is not None:
                mp = mp.at[:, slot, :s].set(
                    mass_layers.astype(self.mass_pool.dtype))
            self.mass_pool = mp
        self.lens[slot] = s

    # -- logical views ---------------------------------------------------

    def gather_slot(self, slot: int):
        """(L, max_len, hkv, dh) contiguous K/V view of one slot (testing /
        debugging; the fused step gathers all slots in-graph)."""
        pt = jnp.asarray(self.page_table[slot])
        def view(pool):
            g = pool[:, pt]                           # (L, pages, ps, hkv, dh)
            return g.reshape(g.shape[0], -1, *g.shape[3:])
        return view(self.k_pool), view(self.v_pool)


def gather_views(k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                 page_table: jnp.ndarray):
    """In-graph gather of every slot's logical K/V view.

    k_pool/v_pool: (L, P, ps, hkv, dh); page_table: (n_slots, pages).
    Returns (L, n_slots, M, hkv, dh) x2 with M = pages * ps."""
    def view(pool):
        g = pool[:, page_table]              # (L, n_slots, pages, ps, hkv, dh)
        L, ns = g.shape[0], g.shape[1]
        return g.reshape(L, ns, -1, *g.shape[4:])
    return view(k_pool), view(v_pool)
