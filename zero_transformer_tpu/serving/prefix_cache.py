"""Chunk-aligned token-prefix index over KV page ids (vLLM-style block
hashing).

Shared-prefix traffic — N personas behind one system prompt, retried
requests, agent loops replaying a conversation head — re-pays prefill for
token spans whose K/V the engine has already computed. This LRU lets a new
prompt skip straight to its first novel chunk:

- **Key scheme**: an entry covers ONE chunk of ``chunk_tokens`` tokens and
  is keyed by the ENTIRE token prefix up to and including that chunk
  (``tuple(prompt[:j * chunk])``), not by the chunk's own tokens — K/V at a
  position depends on every earlier token, so two prompts may share chunk
  *contents* but never chunk *K/V* unless the whole prefix matches. This is
  exactly vLLM's prefix/block hash. Exact tuple keys (not a digest) mean a
  hash collision can never serve wrong K/V.
- **Value**: the tuple of pool pages holding that chunk's K/V (int8 scale
  leaves ride the same pages), not a copy of the bytes. ``store_pages``
  records pages already refcount-bumped by ``PagedKVCache.bank`` — no
  device work; a hit hands them to ``PagedKVCache.share``, which maps them
  into the new slot's block table and bumps refcounts. Deterministic
  forward ⇒ reused pages are bit-identical to recomputation, so prefix
  hits preserve the engine's byte-identical parity contract.
- **Hit walk**: ``lookup`` extends the match one chunk at a time and stops
  strictly BEFORE the prompt's final token (``j * chunk < len(prompt)``):
  the last chunk is always recomputed, because the admission needs the
  logits at ``true_len - 1`` and pages store K/V only.
- **Eviction / flush** drop the index's reference through the pool: a page
  still mapped by a live slot survives until its last reference.
  ``reclaim(n)`` frees at least ``n`` pages for an allocation that found
  the pool exhausted — the page-fault path the engine counts.
- **Invalidation**: ``flush()`` on hot weight reload (new weights make
  every cached page stale); after a tick fault the engine rebuilds the
  index against its fresh pool.

Host-side bookkeeping only. Not thread-safe by itself — only the scheduler
tick thread touches it (admission and completion both run inside
``step()``).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Sequence, Tuple

Pages = Tuple[int, ...]


class PagedPrefixIndex:
    """LRU of chunk-aligned prefixes, each entry the pages of one chunk.

    ``capacity`` counts CHUNK ENTRIES (each worth ``chunk_tokens`` cache
    positions of K/V), so the pool pages the index pins are bounded at
    ``capacity * chunk_tokens`` positions regardless of how many distinct
    prompts pass through. ``pool`` is the ``PagePool`` whose refcounts the
    entries hold.
    """

    def __init__(self, chunk_tokens: int, capacity: int, pool):
        if chunk_tokens < 1:
            raise ValueError("chunk_tokens must be >= 1")
        if capacity < 1:
            raise ValueError("capacity must be >= 1 (0 disables at the engine)")
        self.chunk_tokens = chunk_tokens
        self.capacity = capacity
        self._pool = pool
        self._entries: "OrderedDict[Tuple[int, ...], Pages]" = OrderedDict()
        # cached DEEPER chunks per entry: an entry with live children is
        # never evicted (its children would become unreachable dead weight —
        # the hit walk stops at the first absent chunk), so eviction takes
        # the least-recent LEAF instead
        self._children: Dict[Tuple[int, ...], int] = {}
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def _key(self, prompt: Sequence[int], j: int) -> Tuple[int, ...]:
        return tuple(prompt[: j * self.chunk_tokens])

    def _parent(self, key: Tuple[int, ...]) -> Tuple[int, ...]:
        return key[: len(key) - self.chunk_tokens]

    def _link(self, key: Tuple[int, ...]) -> None:
        # counted whether or not the parent is RESIDENT: the map answers
        # "how many cached entries extend this key by one chunk", so a
        # parent stored out of order (re-cached after its deeper chunk)
        # arrives already pinned by its resident children — no scan needed
        parent = self._parent(key)
        if parent:
            self._children[parent] = self._children.get(parent, 0) + 1

    def _unlink(self, key: Tuple[int, ...]) -> None:
        parent = self._parent(key)
        if parent:
            n = self._children.get(parent, 1) - 1
            if n:
                self._children[parent] = n
            else:
                self._children.pop(parent, None)

    def _evict_one(self) -> None:
        """Drop the least-recently-used LEAF entry (no cached deeper chunk
        depends on it) and its page references. Evicting a mid-chain entry
        would orphan its descendants: still resident, never again reachable
        by the hit walk — the whole-prefix-eviction bug this ordering
        exists to fix."""
        victim = next(
            (k for k in self._entries if not self._children.get(k)),
            next(iter(self._entries)),  # cycle-free tree: always has a leaf
        )
        self._pool.decref(self._pop_entry(victim))

    def _pop_entry(self, victim: Tuple[int, ...]) -> Pages:
        pages = self._entries.pop(victim)
        self._unlink(victim)
        self.evictions += 1
        return pages

    def lookup(self, prompt: Sequence[int]) -> Tuple[int, List[Pages]]:
        """Longest chunk-aligned cached prefix of ``prompt``.

        Returns ``(tokens_covered, entries)`` where ``entries[i]`` is chunk
        ``i+1``'s pages; every covered chunk counts a hit and every
        remaining chunk-aligned chunk (still ending before the final token)
        counts a miss. The walk stops at the first absent chunk — a cached
        DEEPER chunk is unusable without its predecessors' K/V in the row.
        """
        C = self.chunk_tokens
        fill, entries = self.walk(prompt)
        for j in range(1, len(entries) + 1):
            self._entries.move_to_end(self._key(prompt, j))
        self.hits += len(entries)
        j = len(entries) + 1
        while j * C < len(prompt):
            self.misses += 1
            j += 1
        return fill, entries

    def walk(self, prompt: Sequence[int]) -> Tuple[int, List[Pages]]:
        """The hit walk WITHOUT stats or recency side effects — capacity
        planning (admission sizes its page reservation before committing
        to the hit, and must not count the same hit twice)."""
        C = self.chunk_tokens
        vals: List[Pages] = []
        j = 1
        while j * C < len(prompt):
            v = self._entries.get(self._key(prompt, j))
            if v is None:
                break
            vals.append(v)
            j += 1
        return len(vals) * C, vals

    def contains(self, prompt: Sequence[int], j: int) -> bool:
        return self._key(prompt, j) in self._entries

    def store_pages(self, prompt: Sequence[int], j: int, pages) -> None:
        """Insert chunk ``j`` (1-based) of ``prompt``'s prefix as ``pages``
        (already refcount-bumped by ``bank``); evicts LRU entries past
        capacity. A duplicate store refreshes the entry's recency and
        returns the extra references immediately (one index hold per page,
        ever)."""
        key = self._key(prompt, j)
        if key in self._entries:
            self._entries.move_to_end(key)
            self._pool.decref(pages)  # bank() bumped; the entry already holds
            return
        self._entries[key] = tuple(pages)
        self._link(key)
        self.stores += 1
        while len(self._entries) > self.capacity:
            self._evict_one()

    def reclaim(self, n_pages: int) -> int:
        """Evict entries until >= ``n_pages`` pages came FREE (refcount
        zero); returns pages freed. Only entries whose eviction actually
        frees something are touched — least-recent FREEABLE leaf first —
        and the walk stops when no leaf would free a page: evicting an
        entry whose pages a live slot still maps gains zero capacity, and
        wiping the hot shared-prefix set on a failed admission would turn
        one capacity miss into a hit-rate collapse."""
        freed = 0
        while freed < n_pages:
            victim = next(
                (
                    k
                    for k, pages in self._entries.items()
                    if not self._children.get(k)
                    and any(self._pool.refs[p] == 1 for p in pages)
                ),
                None,
            )
            if victim is None:
                break
            freed += self._pool.decref(self._pop_entry(victim))
        return freed

    def flush(self) -> int:
        """Drop every entry and its page references (hot reload); returns
        how many entries went."""
        for pages in self._entries.values():
            self._pool.decref(pages)
        n = len(self._entries)
        self._entries.clear()
        self._children.clear()
        return n

    def stats(self) -> Dict[str, float]:
        total = self.hits + self.misses
        return {
            "prefix_hits": self.hits,
            "prefix_misses": self.misses,
            "prefix_stores": self.stores,
            "prefix_evictions": self.evictions,
            "prefix_entries": len(self._entries),
            "prefix_hit_rate": (self.hits / total) if total else 0.0,
        }
