"""Block-paged KV pool for the serving engine (vLLM-style PagedAttention
bookkeeping, host side).

The device arrays are ``[L, P, page_size, nh, d]`` — P physical pages shared
by every slot — plus a host-authoritative slot->page table ``[B, MP]``
(uploaded as a traced operand each step, like every other per-slot
quantity). This module owns everything that is pure bookkeeping:

* **free-page allocator** — refcounted physical pages. Page 0 is the
  reserved TRASH page: never handed out, the write target for padding
  lanes and inactive slots, and the read target of unmapped table entries
  (always masked out by the causal mask, so its garbage is never observed).
* **prefix cache** — hash-matched prompt prefixes map the SAME physical
  pages (refcount++) instead of recomputing their KV. Two entry kinds:
  cumulative full-page hashes (``prompt[:k*page_size]`` -> page) and an
  exact-prompt entry (whole prompt -> all its pages, including a partial
  last page). LRU entries are evicted when admission needs pages.
* **copy-on-write** — a slot may only WRITE a page it exclusively owns.
  ``make_writable`` copies any shared page in the write range to a fresh
  page first (the engine executes the device copy); sharing therefore
  never lets one request's decode corrupt another's prefix.

Sharing is bitwise-safe because the KV of a token depends only on the
token prefix before it: two requests whose prompts agree on ``m`` tokens
compute bit-identical K/V for those positions, so reading the cached pages
is indistinguishable from recomputing them. When the engine serves
per-slot adapters (serving/adapters.py) that premise needs one more
input: the adapted out/up/down projections feed the residual stream the
NEXT layer's K/V is computed from, so an adapted request's prompt KV
depends on its delta bits too. The engine therefore passes a ``salt``
(adapter id + content version) into ``lookup``/``register`` — base
traffic (id 0) keeps the unsalted keys and stays shared across every
tenant, while adapted entries only ever match the exact delta content
that produced them (a ``swap_adapter`` strands the old version's
entries, which age out of the LRU; no flush needed).

Quantized pool (``kv_dtype`` int8/fp8, serving/quant.py): the pool
additionally owns per-PAGE dequant scales ``k_scale``/``v_scale``
``[L, P]`` float32, stored host-side beside the page table and uploaded
as traced operands each step. Pages are the quantization block: a CoW
split copies the source page's scale entries with its bytes, prefix
sharing shares a page and its scale, and the trash page keeps scale 1.0
(its garbage is never read unmasked). The values come from calibrated
per-layer |K|/|V| clip ranges divided by the dtype's qmax. All the
sharing arguments above carry over verbatim — two requests with the same
prefix quantize bit-identical pages (same values, same scales).
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as np


class PagePoolExhausted(RuntimeError):
    """No free physical page available (after cache eviction)."""


def pages_for(tokens, page_size):
    """Number of pages covering `tokens` positions."""
    return -(-int(tokens) // int(page_size))


def ring_pages(window, chunk, page_size):
    """Pages of a window group's ring: a dispatch that writes positions
    [s, s + chunk) reads back to s - window + 1, and the page its last
    write lands in must not be the ring slot of the first page it reads;
    s need not be page-aligned. (window 2048, chunk 512, page 16: 161.)"""
    return (int(window) + int(chunk) - 2) // int(page_size) + 2


class PagedKVPool:
    """Host-side page bookkeeping: allocator + slot page table + prefix
    cache. Device KV arrays live in the engine; this class only decides
    WHICH physical page each (slot, logical page) maps to."""

    def __init__(self, num_slots, max_seq_len, page_size, num_pages=0,
                 prefix_cache=True, kv_dtype="bf16", num_layers=0,
                 k_clip=None, v_clip=None, qmax=127.0):
        self.page_size = int(page_size)
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.slot_pages = pages_for(max_seq_len, self.page_size)  # MP
        self.num_slots = int(num_slots)
        auto = self.num_slots * self.slot_pages + 1
        self.num_pages = int(num_pages) or auto
        if self.num_pages < 2:
            raise ValueError("need at least 2 pages (one is the trash page)")
        P = self.num_pages
        # quantized pool: per-PAGE dequant scales beside the table (the
        # page is the quantization block). Static calibration seeds every
        # page of a layer with clip/qmax; the trash page keeps 1.0.
        self.kv_dtype = str(kv_dtype)
        self.k_scale = self.v_scale = None
        if self.kv_dtype != "bf16":
            if not num_layers or k_clip is None or v_clip is None:
                raise ValueError(
                    "a quantized pool needs num_layers and per-layer "
                    "k_clip/v_clip ranges (calibrate via serving.quant)")
            from .quant import page_scales
            k_clip = np.broadcast_to(np.asarray(k_clip, np.float64),
                                     (int(num_layers),))
            v_clip = np.broadcast_to(np.asarray(v_clip, np.float64),
                                     (int(num_layers),))
            self.k_scale = page_scales(k_clip, P, qmax)
            self.v_scale = page_scales(v_clip, P, qmax)
        # slot -> physical page, logical order; 0 = unmapped/trash
        self.table = np.zeros((self.num_slots, self.slot_pages), np.int32)
        self.ref = np.zeros(P, np.int64)
        self.ref[0] = 1                      # trash page pinned forever
        self._free = list(range(P - 1, 0, -1))   # LIFO; pops ascending ids
        self._spare = [None] * self.num_slots    # per-slot CoW reserve page
        self.prefix_cache_enabled = bool(prefix_cache)
        # LRU: key -> page id (full-page entries, key=(b"P", bytes)) or
        # (tuple(pages), plen) (exact entries, key=(b"E", bytes))
        self._cache = OrderedDict()
        # staged pages: request_id -> [pages] held for an incoming KV
        # transfer that has not been seated into a slot yet (disaggregated
        # decode worker). Ref-held like slot pages; adopt_staged moves
        # them into map_slot without touching the refcounts.
        self._staged = {}
        # audit counters (the leak gate sums these)
        self.allocated = 0
        self.freed = 0

    # -- allocator -----------------------------------------------------------
    @property
    def free_count(self):
        return len(self._free)

    @property
    def pages_in_use(self):
        return self.num_pages - 1 - len(self._free)

    def _alloc_one(self):
        if not self._free:
            self._evict_until(1)
        if not self._free:
            raise PagePoolExhausted(
                f"no free KV page ({self.num_pages - 1} pages all in use)")
        p = self._free.pop()
        assert self.ref[p] == 0
        self.ref[p] = 1
        self.allocated += 1
        return p

    def try_alloc(self, n):
        """Allocate n pages (evicting LRU cache entries if needed) or None
        if the pool can't cover them; all-or-nothing."""
        if self.free_count < n:
            self._evict_until(n)
        if self.free_count < n:
            return None
        return [self._alloc_one() for _ in range(n)]

    def can_alloc(self, n):
        """Non-destructive capacity check: could ``try_alloc(n)`` succeed?
        True when free pages plus the pages the LRU cache COULD release
        (pages whose every reference is a cache pin) cover ``n``. Unlike
        ``try_alloc`` this never evicts — capacity PROBES (the engine's
        preemption policy polls one per boundary) must not churn the hot
        cache entries they are trying to preserve."""
        n = int(n)
        if self.free_count >= n:
            return True
        cache_refs = {}
        for key, val in self._cache.items():
            for p in ([val] if key[0] == b"P" else list(val[0])):
                cache_refs[p] = cache_refs.get(p, 0) + 1
        reclaimable = sum(1 for p, c in cache_refs.items()
                          if self.ref[p] == c)
        return self.free_count + reclaimable >= n

    def incref(self, pages):
        for p in pages:
            assert p != 0
            self.ref[p] += 1

    def decref(self, pages):
        for p in pages:
            assert p != 0 and self.ref[p] > 0
            self.ref[p] -= 1
            if self.ref[p] == 0:
                self._free.append(int(p))
                self.freed += 1

    # -- slot mapping --------------------------------------------------------
    def map_slot(self, b, pages, spare=None):
        """Bind `pages` (already ref-held by the caller) to slot b's logical
        pages 0..len-1; optionally park a pre-allocated CoW spare page."""
        self.table[b] = 0
        self.table[b, :len(pages)] = pages
        self._spare[b] = spare

    def release_slot(self, b):
        """Unmap slot b: decref every mapped page and the CoW spare."""
        mapped = [int(p) for p in self.table[b] if p != 0]
        self.table[b] = 0
        self.decref(mapped)
        if self._spare[b] is not None:
            self.decref([self._spare[b]])
            self._spare[b] = None

    # -- transfer staging ----------------------------------------------------
    def stage(self, rid, n=1):
        """Allocate ``n`` pages for an in-flight KV transfer and park them
        under ``rid`` until the request is seated. Returns the new pages
        (appended to any already staged) or None when the pool can't cover
        them right now — the transfer waits for the next boundary."""
        got = self.try_alloc(n)
        if got is None:
            return None
        self._staged.setdefault(rid, []).extend(got)
        return got

    def staged_pages(self, rid):
        return list(self._staged.get(rid, ()))

    def adopt_staged(self, rid):
        """Hand the staged pages to the caller for ``map_slot`` — the ref
        each page carries from ``stage`` becomes the slot-table ref."""
        return self._staged.pop(rid, [])

    def release_staged(self, rid):
        """Drop a transfer's staged pages (abort/failure path)."""
        pages = self._staged.pop(rid, None)
        if pages:
            self.decref(pages)

    def clear_staged(self):
        for rid in list(self._staged):
            self.release_staged(rid)

    def make_writable(self, b, start, end):
        """Ensure slot b exclusively owns every page covering positions
        [start, end): any page with refcount > 1 (shared with another slot
        or pinned by the prefix cache) is remapped to a fresh page. Returns
        [(src, dst), ...] physical copies the engine must execute BEFORE
        the step that writes this range (the CoW split)."""
        ps = self.page_size
        copies = []
        for li in range(start // ps, (end - 1) // ps + 1):
            phys = int(self.table[b, li])
            assert phys != 0, f"slot {b} writing unmapped logical page {li}"
            if self.ref[phys] == 1:
                continue
            if self._spare[b] is not None:
                dst = self._spare[b]
                self._spare[b] = None
            else:
                dst = self._alloc_one()
            copies.append((phys, dst))
            self.table[b, li] = dst
            self.decref([phys])
            if self.k_scale is not None:
                # the CoW destination inherits the source page's dequant
                # scales with its bytes (identical under static
                # calibration; the invariant is maintained regardless)
                self.k_scale[:, dst] = self.k_scale[:, phys]
                self.v_scale[:, dst] = self.v_scale[:, phys]
        return copies

    # -- prefix cache --------------------------------------------------------
    def lookup(self, prompt, salt=b""):
        """Longest cached prefix of `prompt` (np.int32 [plen]). Returns
        (matched_tokens, pages, exact): `pages` cover logical pages
        0..ceil(matched/page_size)-1 and are NOT ref-held yet (caller
        increfs). exact=True when the whole prompt matched an exact entry
        (prefill reduces to re-forwarding the last prompt token).
        ``salt`` namespaces the keys (adapter id + version for adapted
        requests — see the module docstring); b"" is the shared base."""
        if not self.prefix_cache_enabled:
            return 0, [], False
        raw = salt + prompt.tobytes()
        hit = self._cache.get((b"E", raw))
        if hit is not None:
            self._cache.move_to_end((b"E", raw))
            pages, plen = hit
            return plen, list(pages), True
        ps = self.page_size
        pages = []
        for j in range(1, len(prompt) // ps + 1):
            key = (b"P", salt + prompt[:j * ps].tobytes())
            page = self._cache.get(key)
            if page is None:
                break
            self._cache.move_to_end(key)
            pages.append(page)
        return len(pages) * ps, pages, False

    def peek_coverage(self, prompt, salt=b""):
        """Longest cached prefix of ``prompt`` in TOKENS, without touching
        LRU recency or refcounts. The supervisor's affinity router probes
        every decode replica with this — a probe that bumped recency would
        let routing traffic keep cold entries pinned hot."""
        if not self.prefix_cache_enabled:
            return 0
        hit = self._cache.get((b"E", salt + prompt.tobytes()))
        if hit is not None:
            return hit[1]
        ps = self.page_size
        n = 0
        for j in range(1, len(prompt) // ps + 1):
            if (b"P", salt + prompt[:j * ps].tobytes()) not in self._cache:
                break
            n += 1
        return n * ps

    def register(self, prompt, b, min_free_frac=0.25, salt=b""):
        """Publish slot b's prompt pages into the cache (cumulative
        full-page hashes + the exact-prompt entry). The engine calls this
        on slot RELEASE (cache-on-free): the prompt KV is complete on
        device and the slot will never write these pages again, so
        registration never forces a copy-on-write against its own owner.
        Already-cached keys are left untouched.

        Under page pressure (free < min_free_frac of the pool) new
        registrations are SKIPPED: pinning a one-off prompt's pages when
        the allocator is tight just evicts hotter entries (the shared
        system prompts every request re-reads) in an endless churn. Hot
        entries registered at low pressure survive — every lookup hit
        refreshes their LRU recency."""
        if not self.prefix_cache_enabled:
            return
        if self.free_count < max(1, int((self.num_pages - 1)
                                        * min_free_frac)):
            return
        ps = self.page_size
        row = self.table[b]
        for j in range(1, len(prompt) // ps + 1):
            key = (b"P", salt + prompt[:j * ps].tobytes())
            if key not in self._cache:
                page = int(row[j - 1])
                self._cache[key] = page
                self.incref([page])
        ekey = (b"E", salt + prompt.tobytes())
        if ekey not in self._cache:
            pages = tuple(int(p) for p in
                          row[:pages_for(len(prompt), ps)])
            self._cache[ekey] = (pages, len(prompt))
            self.incref(pages)

    def _evict_until(self, need_free):
        """Drop LRU cache entries until `need_free` pages are free (or the
        cache is empty). Pages still mapped by running slots survive the
        decref — eviction only forgets the cache's pin."""
        while self._cache and self.free_count < need_free:
            key, val = self._cache.popitem(last=False)
            pages = [val] if key[0] == b"P" else list(val[0])
            self.decref(pages)

    def clear_cache(self):
        self._evict_until(self.num_pages)

    @property
    def cache_entries(self):
        return len(self._cache)

    # -- snapshot ------------------------------------------------------------
    def _meta(self):
        return {"page_size": self.page_size,
                "num_pages": self.num_pages,
                "num_slots": self.num_slots,
                "slot_pages": self.slot_pages,
                "prefix_cache": self.prefix_cache_enabled,
                "kv_dtype": self.kv_dtype}

    def state_dict(self):
        """Serializable snapshot of the WHOLE allocator: slot->page table,
        refcounts, free list, CoW spares, prefix-cache entries (in LRU
        order) and the leak-audit counters. Paired with the engine's device
        KV arrays this reconstructs the paged pool exactly."""
        state = {
            "meta": self._meta(),
            "table": self.table.copy(),
            "ref": self.ref.copy(),
            "free": list(self._free),
            "spare": list(self._spare),
            "cache": [(k, v) for k, v in self._cache.items()],
            "staged": {rid: list(pp) for rid, pp in self._staged.items()},
            "allocated": int(self.allocated),
            "freed": int(self.freed),
        }
        if self.k_scale is not None:
            state["k_scale"] = self.k_scale.copy()
            state["v_scale"] = self.v_scale.copy()
        return state

    def load_state_dict(self, state):
        """Restore a ``state_dict()`` snapshot. The pool geometry must
        match — a snapshot indexes PHYSICAL pages, so restoring into a
        differently-sized pool would alias them."""
        meta = dict(state["meta"])
        meta.setdefault("kv_dtype", "bf16")   # pre-quant snapshots
        mine = self._meta()
        if meta != mine:
            raise ValueError(
                f"paged-pool snapshot geometry {meta} does not match this "
                f"pool {mine}")
        if self.k_scale is not None:
            self.k_scale = np.asarray(state["k_scale"], np.float32).copy()
            self.v_scale = np.asarray(state["v_scale"], np.float32).copy()
        self.table = np.asarray(state["table"], np.int32).copy()
        self.ref = np.asarray(state["ref"], np.int64).copy()
        self._free = [int(p) for p in state["free"]]
        self._spare = [None if s is None else int(s) for s in state["spare"]]
        self._cache = OrderedDict(
            (tuple(k), v) for k, v in state["cache"])
        # pre-disagg snapshots carry no staged pages
        self._staged = {rid: [int(p) for p in pp]
                        for rid, pp in state.get("staged", {}).items()}
        self.allocated = int(state["allocated"])
        self.freed = int(state["freed"])

    # -- audit ---------------------------------------------------------------
    def balance(self):
        """Allocator conservation snapshot for the leak gate: free + in-use
        must always equal num_pages - 1, and refcounts must account for
        every mapped/cached pin."""
        slot_refs = np.zeros(self.num_pages, np.int64)
        for b in range(self.num_slots):
            for p in self.table[b]:
                if p != 0:
                    slot_refs[p] += 1
            if self._spare[b] is not None:
                slot_refs[self._spare[b]] += 1
        for pages in self._staged.values():
            for p in pages:
                slot_refs[p] += 1
        cache_refs = np.zeros(self.num_pages, np.int64)
        for key, val in self._cache.items():
            for p in ([val] if key[0] == b"P" else val[0]):
                cache_refs[p] += 1
        accounted = bool((self.ref[1:] ==
                          (slot_refs + cache_refs)[1:]).all())
        return {
            "num_pages": self.num_pages,
            "free": self.free_count,
            "in_use": self.pages_in_use,
            "conserved": self.free_count + self.pages_in_use
            == self.num_pages - 1,
            "refcounts_accounted": accounted,
            "cache_entries": len(self._cache),
            "allocated": self.allocated,
            "freed": self.freed,
        }
