"""Host-side page accounting for the paged KV pool — free-list
allocator, refcounts, and the hash-based prefix index.

Everything in this module is pure host python: page *indices* are data
the engine threads into its compiled calls (a page table is values, never
shapes), so allocation policy lives out here where it can be unit-tested
without a device. The device-side pool itself is
:class:`~apex_tpu.serve.kv_cache.PagedKVCache`.

Invariants the engine relies on:

- **page 0 is the null page** — never allocated, never written with live
  data. Masked-off slots' decode write-backs are routed to it so a stale
  page-table entry can never collide with a live slot's append in the
  same scatter, and unmapped table entries read zeros that the
  reachability mask discards.
- **refcount = number of slot page-table references + 1 if the page is
  held by the prefix index.** A page returns to the free list exactly
  when its refcount reaches zero; shared prefix pages therefore survive
  the requests that created them until LRU pressure evicts the index
  entry.
- **shared pages are read-only.** Appends only ever target pages a
  single slot owns: prefill writes start at the first non-shared
  position (the partial tail page is copied — copy-on-write — before it
  is written), and decode appends land past the prompt. Nothing enforces
  this on-device; the allocator's job is to make it structurally true.
- **page indices are rank-invariant.** Under tensor parallelism the
  pool's bytes shard on the HEAD axis (each mesh rank holds every
  page's slice of its own heads), so one page index addresses all
  ranks' shards of that page simultaneously — this ONE allocator, the
  prefix index, and copy-on-write serve the whole mesh unchanged, and
  the page table rides into the sharded step as replicated data.
"""

from __future__ import annotations

import collections
import hashlib
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

NULL_PAGE = 0


class PagePoolExhausted(RuntimeError):
    """No free page available (after prefix-index LRU eviction). The
    scheduler treats this as an admission stall, not an error: the
    request stays queued and ``serve_page_alloc_fail`` charges the
    waiting time once pages free up."""


def chunk_hashes(tokens: Sequence[int], page_size: int) -> List[str]:
    """Chained content hashes of the full ``page_size``-token chunks of
    ``tokens`` — hash ``i`` commits to chunks ``0..i``, so an index hit
    on hash ``i`` certifies the *entire* prefix up to ``(i+1) *
    page_size`` tokens, not just one chunk. Stable across processes
    (blake2b over the token bytes, never python ``hash``)."""
    import numpy as np

    out: List[str] = []
    h = b""
    for i in range(len(tokens) // page_size):
        chunk = np.asarray(tokens[i * page_size:(i + 1) * page_size],
                           np.int64).tobytes()
        h = hashlib.blake2b(h + chunk, digest_size=16).digest()
        out.append(h.hex())
    return out


def page_payload_digest(chain_hash: str, k_bytes: bytes,
                        v_bytes: bytes, *extra: bytes) -> str:
    """Transport digest for one migrated KV page: blake2b over the chain
    hash it claims plus the raw K/V bytes. The sender stamps it at
    export; the receiver recomputes it over what actually arrived, so a
    bit flip or torn copy in flight fails certification even though the
    *claimed* chain hash still matches the receiver's expectation. Two
    independent checks, two failure classes: the chain hash certifies
    "these are the pages for THIS prompt prefix", the payload digest
    certifies "these bytes are the ones the prefill replica committed".

    ``extra`` carries any further byte planes the page's meaning depends
    on — a quantized page passes its K/V scale planes here, so the
    digest certifies codes ‖ scales TOGETHER: a flipped bit in a scale
    (which would silently rescale a whole (token, head) block at
    dequant) is refused exactly like a flipped payload bit."""
    h = hashlib.blake2b(digest_size=16)
    h.update(bytes.fromhex(chain_hash))
    h.update(k_bytes)
    h.update(v_bytes)
    for b in extra:
        h.update(b)
    return h.hexdigest()


class PagePool:
    """Free-list page allocator with refcounts over ``num_pages`` device
    pages (page 0 reserved as the null page)."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError(
                f"num_pages={num_pages} must be >= 2 (page 0 is the "
                f"reserved null page)")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        # ascending allocation order (deterministic: identical request
        # traces produce identical page tables, which tests rely on)
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self.refcount: List[int] = [0] * num_pages

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def capacity(self) -> int:
        """Allocatable pages (the null page is never allocatable)."""
        return self.num_pages - 1

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` fresh pages (refcount 1 each). Raises
        :class:`PagePoolExhausted` without allocating anything when the
        free list is short — the caller probes first, so this firing
        means a bookkeeping bug, not load."""
        if n > len(self._free):
            raise PagePoolExhausted(
                f"need {n} pages, {len(self._free)} free "
                f"(of {self.capacity})")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self.refcount[p] = 1
        return pages

    def retain(self, page: int) -> None:
        """Add a reference to an already-live page (a slot sharing a
        prefix page, or the prefix index pinning one)."""
        if page == NULL_PAGE or self.refcount[page] <= 0:
            raise ValueError(f"retain of dead page {page}")
        self.refcount[page] += 1

    def release(self, page: int) -> bool:
        """Drop one reference; returns True when the page went back to
        the free list."""
        if page == NULL_PAGE:
            raise ValueError("release of the null page")
        if self.refcount[page] <= 0:
            raise ValueError(f"release of dead page {page}")
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            self._free.append(page)
            return True
        return False


class PrefixIndex:
    """Page-granular prompt-prefix index: chained chunk hash → resident
    read-only page, LRU-ordered.

    Pages inserted here carry one index reference in the
    :class:`PagePool`, so they outlive the request that prefilled them;
    :meth:`evict` drops least-recently-used entries (index-only pages go
    straight back to the free list) when allocation needs room.
    """

    def __init__(self, page_size: int):
        self.page_size = int(page_size)
        # chain hash -> page index, in LRU order (oldest first)
        self._entries: "collections.OrderedDict[str, int]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, chain_hash: str) -> bool:
        return chain_hash in self._entries

    def pages(self) -> Set[int]:
        return set(self._entries.values())

    def lookup(self, tokens: Sequence[int], *,
               touch: bool = True) -> List[Tuple[str, int]]:
        """The longest indexed prefix of ``tokens``: ``[(chain_hash,
        page), ...]`` for consecutive full chunks from position 0. With
        ``touch`` (the default) hit entries are refreshed in LRU order;
        admission *probes* pass ``touch=False`` so a rejected probe does
        not reorder the index."""
        out: List[Tuple[str, int]] = []
        for h in chunk_hashes(tokens, self.page_size):
            page = self._entries.get(h)
            if page is None:
                break
            out.append((h, page))
        if touch:
            for h, _ in out:
                self._entries.move_to_end(h)
            self.hits += len(out)
            if len(tokens) // self.page_size > len(out):
                self.misses += 1
        return out

    def insert(self, chain_hash: str, page: int, pool: PagePool) -> None:
        """Pin ``page`` (already live — the inserting slot references
        it) under ``chain_hash``; no-op when the hash is already
        indexed."""
        if chain_hash in self._entries:
            return
        pool.retain(page)
        self._entries[chain_hash] = page

    def evict(self, pool: PagePool, need: int,
              protect: Iterable[int] = ()) -> int:
        """Drop LRU entries until ``need`` pages have returned to the
        free list. Entries whose page a live slot still references are
        skipped — dropping them frees nothing and loses a prefix some
        request is actively using. ``protect`` names pages an in-progress
        admission is about to share — evicting those would free pages the
        caller is counting on reusing."""
        protected = set(protect)
        freed = 0
        for h in list(self._entries):
            if freed >= need:
                break
            page = self._entries[h]
            if page in protected or pool.refcount[page] > 1:
                continue
            del self._entries[h]
            self.evictions += 1
            if pool.release(page):
                freed += 1
        return freed

    def evictable(self, pool: PagePool,
                  protect: Iterable[int] = ()) -> int:
        """How many pages an :meth:`evict` sweep could free right now:
        index entries not protected whose only reference is the index
        itself."""
        protected = set(protect)
        return sum(1 for page in self._entries.values()
                   if page not in protected and pool.refcount[page] == 1)

    def drop_page(self, page: int, pool: PagePool) -> None:
        """Remove every entry pointing at ``page`` (used when a caller
        must reclaim a specific page, e.g. tests)."""
        for h, p in list(self._entries.items()):
            if p == page:
                del self._entries[h]
                self.evictions += 1
                pool.release(page)


def pages_for_tokens(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` KV rows."""
    return -(-int(n_tokens) // int(page_size))


def plan_admission(tokens: Sequence[int], budget: int, max_len: int,
                   page_size: int,
                   index: Optional[PrefixIndex], *,
                   touch: bool = False) -> Dict[str, object]:
    """The page plan for admitting ``tokens`` with ``budget`` new-token
    headroom: which prefix pages to share, whether the partial tail page
    is copy-on-write, and how many fresh pages to allocate. Pure
    function of the index state — both the admission *probe* (``touch``
    False) and the actual allocation (``touch`` True) use it, so they
    can never disagree about the page count.

    Note ``use = min(shared, len(tokens) - 1)``: at least the final
    prompt token is always re-run through prefill, because its logits
    seed the first sampled token — a fully-cached prompt caps its hit
    one token short, which is what makes the partial-tail COW case.
    """
    n = len(tokens)
    hits = index.lookup(tokens, touch=touch) if index is not None else []
    # clamp at 0: an empty prompt (n=0, legal for the planner) must plan
    # zero shared tokens, not use=-1 (whose tail-page remainder would
    # index hits[-1] on an empty hit list)
    use = max(0, min(len(hits) * page_size, n - 1))
    shared_pages = use // page_size
    cow_src = hits[shared_pages][1] if use % page_size else None
    total_tokens = min(n + max(int(budget), 1), max_len)
    total_pages = pages_for_tokens(total_tokens, page_size)
    new_pages = total_pages - shared_pages
    return {
        "hits": hits[:shared_pages + (1 if cow_src is not None else 0)],
        "use": use,                      # tokens served from the index
        "shared_pages": shared_pages,    # full read-only pages shared
        "cow_src": cow_src,              # page to copy for the tail, or None
        "total_pages": total_pages,      # final page-table row length
        "new_pages": new_pages,          # fresh allocations (incl. the COW)
        "tail": list(tokens[use:]),      # tokens prefill actually scans
    }
