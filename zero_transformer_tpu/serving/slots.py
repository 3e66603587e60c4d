"""Paged KV-cache manager for continuous batching.

K/V lives in ONE global page pool (allocated through
``inference.init_cache`` on a ``kv_pages`` decode model — int8-KV aware,
optionally tensor-sharded); a SLOT is a row of the per-slot block table
that maps its logical positions to pool pages, and every scheduler tick
runs ONE fused decode step over all slots. The piece that makes rows
independent is the cache index: ``init_cache`` gives the scalar
``cache_index``/``decode_pos`` the single-request paths use, and
``vectorize_index`` widens it to a per-slot ``[n_slots]`` vector — the
model's decode path (``models.gpt.Attention``) sees a vector index and
switches every position-dependent computation (writes, validity mask, RoPE /
ALiBi / causal biases) to per-row form.

Jit-signature stability invariant: every device function here is traced for
ONE shape — the full pool with dynamic slot/length scalars (page spans:
one per power-of-two page count) — so admissions, retirements, and occupancy
changes never recompile.
"""
from __future__ import annotations

import functools
import json
import struct
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from zero_transformer_tpu.inference.generate import init_cache
from zero_transformer_tpu.models.gpt import kv_pool_wire_heads
from zero_transformer_tpu.models.mamba import STATE_LEAVES

# cache leaves that hold POSITIONS, not K/V data; widened per-slot.
# (cache_index: per-layer attention write position; decode_pos: the learned-
# position table offset at the Transformer level.)
INDEX_LEAVES = ("cache_index", "decode_pos")

# K/V byte-holding leaves of the PAGED cache: pools in the paged kernel's
# own layout (``models.gpt.kv_pool_leaves``) — K/V [n_pages, page, KVH * D],
# int8 scales [n_pages, page, KVH], and under the scanned stack ONE stacked
# [n_loops * n_layers, n_pages, page, ...] leaf per pool at the top of the
# cache tree (it rides the layer loops' carry; a looped model keeps an entry
# per (pass, layer), an unrolled one [n_loops, n_pages, ...] per layer). The
# page axis is ``ndim - 3`` either way, so a page — the unit that is
# allocated, shared, copied on write, banked by the prefix index and
# shipped in a span — carries every entry of its positions. The int32
# per-row page map is its own leaf and has no pass axis: a token sits in
# one page at one position whatever the pass. A latent-attention model keeps
# ONE pool instead, a latent row a position (``models.mla``): the same page
# axis, the same unit.
POOL_LEAVES = (
    "cached_key", "cached_value", "key_scale", "value_scale", "cached_latent",
)
TABLE_LEAF = "block_table"
_PAGE_AXIS_FROM_END = 3

# ``STATE_LEAVES`` (``models.mamba``: ``ssm_state``, ``conv_state``) are the
# third class: a mamba layer's RECURRENT state, addressed by SLOT and by
# nothing else: stacked ``[n_mamba_layers, n_slots, ...]`` at the top of the
# cache tree, no page axis, no position axis. A slot's state is right only
# at the position the slot has reached, so no page operation touches it
# (gather, scatter, copy-on-write, the prefix index and the wire format go
# by ``POOL_LEAVES``), a span of a model that has one is refused, and a
# released slot's is left as it is: the chunk-prefill program reads a
# request's first chunk's as zeros (``serving.engine``, which indexes the
# slot axis, 1).


def _leaf_name(path) -> str:
    last = path[-1]
    return str(last.key if hasattr(last, "key") else last)


def _cache_struct(model, batch: int):
    """Shape-only cache structure for a [batch, ...] run (no materialization)."""
    from zero_transformer_tpu.utils.jax_compat import clear_abstract_mesh

    with clear_abstract_mesh():
        return jax.eval_shape(
            lambda r: model.init(r, jnp.zeros((batch, 1), jnp.int32)),
            jax.random.PRNGKey(0),
        )["cache"]


def vectorize_index(cache: Any, n_slots: int) -> Any:
    """Widen scalar index leaves to per-slot vectors: shape ``s`` -> ``s + (n_slots,)``
    int32 zeros. K/V leaves pass through untouched (same buffers)."""

    def widen(path, leaf):
        if _leaf_name(path) in INDEX_LEAVES:
            return jnp.zeros(leaf.shape + (n_slots,), jnp.int32)
        return leaf

    return jax.tree_util.tree_map_with_path(widen, cache)


def _update_index(cache: Any, update, *args) -> Any:
    """Run the jitted ``update(index_leaves, *args)`` over the cache's
    ``INDEX_LEAVES`` and graft the result back into the tree. Every other
    leaf stays the SAME array: a program that does not need the K/V does
    not get it — a jit that takes the whole tree and hands it back writes
    a fresh copy of every leaf it was not donated, the pools included."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache)
    at = [i for i, (path, _) in enumerate(leaves) if _leaf_name(path) in INDEX_LEAVES]
    out = [leaf for _, leaf in leaves]
    for i, leaf in zip(at, update([out[i] for i in at], *args)):
        out[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, out)


@jax.jit
def _reset_index(index_leaves: List[jax.Array], keep: jax.Array) -> List[jax.Array]:
    """Zero the positions of retired slots (``keep`` [n_slots] bool). K/V
    rows are left in place — the validity mask (positions < index) already
    excludes them."""
    # keep broadcasts from the right
    return [jnp.where(keep, leaf, 0) for leaf in index_leaves]


# ---- paged KV cache (block tables over a global page pool) -----------------
#
# A fixed [n_slots, cache_len] cache would reserve n_slots * cache_len
# positions of K/V whatever the actual sequence lengths; the page pool
# reserves only the pages a sequence really fills (PagedAttention, Kwon et
# al. 2309.06180). Pages are REFCOUNTED: a slot mapping a page holds one
# reference and the prefix index holds another per cached chunk, so a prefix
# hit is a refcount bump into the new slot's block table — zero K/V bytes
# move — and nothing frees a page while any live slot or cached prefix still
# maps it.


# ---- transferable page spans (disaggregated prefill/decode + migration) ----
#
# A page span is the HOST-side image of one slot's leading pages: the raw
# K/V bytes (int8 scale leaves included) of every pool leaf plus the
# block-table fragment's geometry. It is the unit that moves between
# replicas — a prefill replica ships finished spans to a decode replica,
# and live migration ships a mid-stream slot's span to its new home. The
# gather/scatter programs are compiled per QUANTIZED page count (power of
# two) so diverse sequence lengths
# cannot compile-storm a long-lived replica; padding routes through the
# trash page (gather pads are sliced off host-side, scatter pads write
# garbage into page 0, which nothing ever reads).

_WIRE_MAGIC = b"ZTPG1"


def _wire_key(path) -> str:
    """A pool leaf's name in a page span: its path under the per-layer
    ``Attention`` module, as it has been since the format shipped. The
    scanned stack declares its stacked pools at the top of the cache tree
    (``models.gpt.Transformer``) but keeps that name on the wire."""
    key = jax.tree_util.keystr(path)
    return key if len(path) > 1 else "['blocks']['attn']" + key


def _dtype_token(dt) -> str:
    """Wire token for a numpy dtype. Extension dtypes (bfloat16, fp8s —
    numpy kind 'V') stringify to an OPAQUE void ('|V2') that the receiver
    cannot reconstruct; ship their NAME instead."""
    dt = np.dtype(dt)
    return dt.name if dt.kind == "V" else dt.str


def _dtype_from_token(token: str):
    try:
        return np.dtype(token)
    except TypeError:
        pass
    # extension dtype by name (bfloat16 etc.) — ml_dtypes ships with jax,
    # so this resolves wherever the pools themselves can exist. An unknown
    # token must surface as ValueError (the wire contract: torn/foreign
    # blobs become a clean 400, never a handler traceback).
    import ml_dtypes

    try:
        return np.dtype(getattr(ml_dtypes, str(token)))
    except (AttributeError, TypeError) as exc:
        raise ValueError(f"unknown dtype token {token!r}") from exc


@jax.jit
def _gather_pages_impl(cache, page_ids):
    """Pull pool pages out of every K/V pool leaf in ONE dispatch:
    {leaf path -> [len(page_ids), ...per-page]} with the page axis moved
    to the front so row ``i`` is page ``page_ids[i]`` whatever the pool
    layout (per-layer [n_pages, page, lanes] or stacked
    [L, n_pages, page, lanes]) — gathered first, and only the gathered
    rows transposed. The compile family is keyed by ``page_ids``'s
    (quantized) length — the caller pads to a power of two."""
    out: Dict[str, jax.Array] = {}

    def grab(path, leaf):
        if _leaf_name(path) not in POOL_LEAVES:
            return
        ax = leaf.ndim - _PAGE_AXIS_FROM_END
        rows = jnp.take(leaf, page_ids, axis=ax)
        out[jax.tree_util.keystr(path)] = jnp.moveaxis(rows, ax, 0)

    jax.tree_util.tree_map_with_path(grab, cache)
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_pages_impl(cache, page_ids, spans):
    """Inverse of ``_gather_pages_impl``: write span rows into the pool
    pages named by ``page_ids``, one dispatch across every pool leaf.
    Padding rows target the trash page (id 0) — harmless by design."""

    def put(path, leaf):
        key = jax.tree_util.keystr(path)
        if key not in spans:
            return leaf
        # rows back to the pool's axis order, then ONE in-place scatter on
        # the page axis — the (donated) pool itself is never transposed
        ax = leaf.ndim - _PAGE_AXIS_FROM_END
        rows = jnp.moveaxis(spans[key].astype(leaf.dtype), 0, ax)
        return leaf.at[(slice(None),) * ax + (page_ids,)].set(rows)

    return jax.tree_util.tree_map_with_path(put, cache)


@jax.jit
def _set_index_slot(
    index_leaves: List[jax.Array], slot: jax.Array, value: jax.Array
) -> List[jax.Array]:
    """Set ONE slot's fill cursor in every index leaf (migration import:
    the destination's cursor is host-known — prompt + emitted — and the
    imported pages already hold the K/V at [0, cursor))."""

    def upd(leaf):
        block = jnp.full(leaf.shape[:-1] + (1,), value, leaf.dtype)
        starts = (0,) * (leaf.ndim - 1) + (slot,)
        return jax.lax.dynamic_update_slice(leaf, block, starts)

    return [upd(leaf) for leaf in index_leaves]


def page_span_to_wire(payload: Dict[str, Any]) -> bytes:
    """Serialize a page-span payload (and any JSON-safe extras riding in
    it) to one self-describing byte string: magic + length-prefixed JSON
    header + the leaf buffers concatenated raw. No base64 inflation, no
    pickle — the format is readable by any stdlib-only peer."""
    leaves = payload.get("leaves", {})
    header = {
        k: v for k, v in payload.items() if k != "leaves"
    }
    header["leaves"] = []
    buffers: List[bytes] = []
    for key in sorted(leaves):
        arr = np.ascontiguousarray(leaves[key])
        header["leaves"].append({
            "key": key,
            "dtype": _dtype_token(arr.dtype),
            "shape": list(arr.shape),
            "nbytes": int(arr.nbytes),
        })
        buffers.append(arr.tobytes())
    head = json.dumps(header).encode()
    return b"".join(
        [_WIRE_MAGIC, struct.pack("<I", len(head)), head, *buffers]
    )


def page_span_from_wire(blob: bytes) -> Dict[str, Any]:
    """Parse ``page_span_to_wire`` output back into the payload dict.
    Raises ValueError on a torn or foreign blob — the ingest endpoint maps
    that to a clean 400, never a handler traceback."""
    if len(blob) < len(_WIRE_MAGIC) + 4 or not blob.startswith(_WIRE_MAGIC):
        raise ValueError("not a page-span wire blob")
    off = len(_WIRE_MAGIC)
    (head_len,) = struct.unpack_from("<I", blob, off)
    off += 4
    try:
        header = json.loads(blob[off : off + head_len])
    except ValueError as exc:
        raise ValueError(f"torn page-span header: {exc}") from exc
    off += head_len
    leaves: Dict[str, np.ndarray] = {}
    for meta in header.pop("leaves", []):
        n = int(meta["nbytes"])
        if off + n > len(blob):
            raise ValueError("page-span blob truncated mid-buffer")
        leaves[meta["key"]] = np.frombuffer(
            blob[off : off + n], dtype=_dtype_from_token(meta["dtype"])
        ).reshape(meta["shape"])
        off += n
    header["leaves"] = leaves
    return header


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page(cache: Any, src: jax.Array, dst: jax.Array) -> Any:
    """Copy pool page ``src`` onto ``dst`` in every K/V pool leaf, one
    dispatch — the copy-on-write primitive. The page axis sits at
    ``ndim - 3`` in every pool layout this repo produces (per-layer
    [n_pages, page, lanes], stacked [L, n_pages, page, lanes])."""

    def one(path, leaf):
        if _leaf_name(path) not in POOL_LEAVES:
            return leaf
        ax = leaf.ndim - _PAGE_AXIS_FROM_END
        row = jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=ax)
        return jax.lax.dynamic_update_slice_in_dim(leaf, row, dst, axis=ax)

    return jax.tree_util.tree_map_with_path(one, cache)


class PagePool:
    """Host-side page allocator: free list + per-page refcounts.

    Page 0 is the TRASH page — never allocated, always mapped by zeroed
    block-table rows, so parked/inactive rows in a fixed-shape dispatch
    write somewhere harmless (their reads are masked by validity anyway).

    ``reserved`` tracks pages PROMISED to admitted slots but not yet drawn:
    admission reserves a request's worst case (prompt + budget + draft
    headroom) up front, so a slot that was admitted can never hit a
    mid-decode out-of-pages fault — capacity pressure surfaces as requests
    WAITING in the queue, the honest backpressure signal the capacity sweep
    measures.
    """

    TRASH = 0

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError("n_pages must be >= 2 (trash + at least one real)")
        self.n_pages = n_pages
        self.refs = [0] * n_pages
        self._free: List[int] = list(range(1, n_pages))
        self.reserved = 0
        self.peak_in_use = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return (self.n_pages - 1) - len(self._free)

    @property
    def available(self) -> int:
        """Pages neither allocated nor promised to an admitted slot."""
        return len(self._free) - self.reserved

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        page = self._free.pop()
        self.refs[page] = 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return page

    def incref(self, pages) -> None:
        for p in pages:
            if p == self.TRASH or self.refs[p] < 1:
                raise ValueError(f"incref of unallocated page {p}")
            self.refs[p] += 1

    def decref(self, pages) -> int:
        """Drop one reference per page; pages reaching zero return to the
        free list. Returns how many were actually freed."""
        freed = 0
        for p in pages:
            if p == self.TRASH:
                continue
            if self.refs[p] < 1:
                raise ValueError(f"decref of free page {p}")
            self.refs[p] -= 1
            if self.refs[p] == 0:
                self._free.append(p)
                freed += 1
        return freed


class PagedKVCache:
    """Owns the engine's page pool + host-side slot and page bookkeeping:
    K/V lives in the model's page pool and each slot's rows are a block
    table (acquire / release / free_count for slots; reserve / ensure /
    share / bank / cow for pages).

    Device state: ``self.cache`` (pool leaves + ``block_table`` + vector
    index leaves). Host state: the authoritative block-table mirror
    (``self.table``), per-slot allocation/reservation counts, and the
    ``PagePool``. Only the engine's tick thread touches any of it.
    """

    def __init__(self, model, n_slots: int, mesh=None):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if model.kv_pages is None:
            raise ValueError("PagedKVCache needs a paged decode model (kv_pages)")
        self.model = model
        self.n_slots = n_slots
        self.mesh = mesh
        self.n_pages, self.page_size = model.kv_pages
        cap = model.cache_len or model.cfg.max_seq_len
        self.seq_capacity = cap
        self.n_blocks = cap // self.page_size
        self.pool = PagePool(self.n_pages)
        # host mirror of every row's block table; zeros = trash page
        self.table = np.zeros((n_slots, self.n_blocks), np.int32)
        # mapping changed since the last device push (mutators mark, the
        # engine flushes ONCE before any dispatch that reads device tables)
        self.tables_dirty = False
        self.alloc_blocks = [0] * n_slots  # leading blocks mapped, per slot
        self.reserved_blocks = [0] * n_slots  # admission promise, per slot
        self.cache = vectorize_index(
            init_cache(model, n_slots, mesh=mesh), n_slots
        )
        self._free: List[int] = list(range(n_slots))
        self.cow_copies = 0
        # bytes of recurrent state the cache holds beside its pages
        self.state_pool_bytes = sum(
            leaf.nbytes
            for path, leaf in jax.tree_util.tree_leaves_with_path(self.cache)
            if _leaf_name(path) in STATE_LEAVES
        )
        # page-span geometry: {wire key: (cache key, per-page wire shape,
        # dtype)}. On the wire a page is [(L,) page, KVH, D | 1] — the heads
        # split back out of the pool's merged lane axis, on HOST arrays
        # (free), so replicas keep exchanging the payloads they always have
        # (a latent row is one head of all its lanes)
        kvh = kv_pool_wire_heads(model.cfg)
        self.wire_leaves: Dict[str, Tuple[str, Tuple[int, ...], Any]] = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(self.cache):
            if _leaf_name(path) in POOL_LEAVES:
                ax = leaf.ndim - _PAGE_AXIS_FROM_END
                per_page = leaf.shape[:ax] + leaf.shape[ax + 1:-1] + (
                    kvh, leaf.shape[-1] // kvh
                )
                self.wire_leaves[_wire_key(path)] = (
                    jax.tree_util.keystr(path), per_page, leaf.dtype
                )

    # ---- device sync -----------------------------------------------------

    def sync_tables(self) -> None:
        """Push the host block-table mirror into every ``block_table`` leaf
        (per-layer copies under the scanned stack broadcast the same
        values). Tiny int32 traffic; ``flush_tables`` below batches the
        pushes to one per tick. Every leaf gets a buffer of its OWN: the
        decode step donates the cache, and unrolled layers' tables sharing
        one array would be one buffer donated twice."""

        def one(path, leaf):
            if _leaf_name(path) == TABLE_LEAF:
                return jnp.asarray(
                    np.broadcast_to(self.table, leaf.shape), leaf.dtype
                )
            return leaf

        self.cache = jax.tree_util.tree_map_with_path(one, self.cache)
        self.tables_dirty = False

    def flush_tables(self) -> None:
        """One device push for every mapping change since the last flush.
        MUST run before any dispatch that reads the device tables (the
        fused decode / spec step); the paged chunk program is exempt — it
        takes the host table as an argument and overwrites the device
        leaves itself. Batching matters: N slots crossing a page boundary
        on one tick would otherwise pay N separate pushes on the decode
        hot path."""
        if self.tables_dirty:
            self.sync_tables()

    # ---- allocation ------------------------------------------------------

    def blocks_for(self, tokens: int) -> int:
        return -(-tokens // self.page_size)  # ceil

    def can_admit(self, new_blocks: int) -> bool:
        return self.pool.available >= new_blocks

    def reserve(self, slot: int, total_tokens: int) -> None:
        """Promise pages covering ``total_tokens`` logical positions beyond
        what the slot already maps (shared prefix pages included in
        ``alloc_blocks`` by ``share``). Re-reserving replaces the slot's
        previous promise."""
        self._unreserve(slot)
        need = max(0, self.blocks_for(total_tokens) - self.alloc_blocks[slot])
        self.reserved_blocks[slot] = need
        self.pool.reserved += need

    def _unreserve(self, slot: int) -> None:
        self.pool.reserved -= self.reserved_blocks[slot]
        self.reserved_blocks[slot] = 0

    def ensure(self, slot: int, tokens: int) -> bool:
        """Map fresh pages so the slot's table covers positions
        ``[0, tokens)``; draws down the slot's reservation. Returns False
        when the pool is exhausted (the engine reclaims prefix-cache pages
        and retries, then preempts)."""
        need = self.blocks_for(tokens)
        while self.alloc_blocks[slot] < need:
            page = self.pool.alloc()
            if page is None:
                return False
            b = self.alloc_blocks[slot]
            self.table[slot, b] = page
            self.alloc_blocks[slot] = b + 1
            if self.reserved_blocks[slot] > 0:
                self.reserved_blocks[slot] -= 1
                self.pool.reserved -= 1
            self.tables_dirty = True
        return True

    def share(self, slot: int, pages: Sequence[int]) -> None:
        """Prefix hit: map ``pages`` as the slot's leading blocks and bump
        their refcounts — K/V reuse without moving a byte."""
        if not pages:
            return
        if self.alloc_blocks[slot] != 0:
            raise ValueError("share() must precede any allocation for the slot")
        self.pool.incref(pages)
        for b, p in enumerate(pages):
            self.table[slot, b] = p
        self.alloc_blocks[slot] = len(pages)
        self.tables_dirty = True

    def bank(self, slot: int, n_blocks: int) -> List[int]:
        """Page ids of the slot's first ``n_blocks`` blocks, refcounts
        bumped for the prefix index's hold (the caller stores them)."""
        pages = [int(p) for p in self.table[slot, :n_blocks]]
        self.pool.incref(pages)
        return pages

    def cow(self, slot: int, block: int) -> bool:
        """Copy-on-write guard: if the slot is about to WRITE into a shared
        page, give it a private copy first. Chunk-aligned sharing makes
        this unreachable in the steady state (divergence starts at a page
        boundary), but the guard keeps 'shared pages are never written with
        divergent data' a local invariant instead of a global proof."""
        if block >= self.alloc_blocks[slot]:
            return True
        page = int(self.table[slot, block])
        if page == PagePool.TRASH or self.pool.refs[page] <= 1:
            return True
        fresh = self.pool.alloc()
        if fresh is None:
            return False
        self.cache = _copy_page(
            self.cache, jnp.int32(page), jnp.int32(fresh)
        )
        self.table[slot, block] = fresh
        self.pool.decref([page])
        self.cow_copies += 1
        self.tables_dirty = True
        return True

    # ---- transferable page spans (export / import) -----------------------

    def _quantized_blocks(self, count: int) -> int:
        """Gather/scatter page counts are STATIC in the compiled transfer
        ops — quantize to the next power of two (capped at the per-slot
        block capacity) so the compile family stays ~log2(n_blocks)."""
        b = 1
        while b < count:
            b *= 2
        return min(b, max(1, self.n_blocks))

    # graftlint: hot-path
    def export_page_span(self, slot: int, n_tokens: int) -> Dict[str, Any]:
        """HOST-side image of the slot's leading pages covering positions
        ``[0, n_tokens)``: raw K/V bytes per pool leaf (int8 scales
        included) + the block-table fragment geometry. Read-only — the
        slot keeps its pages and refcounts are untouched, so an export
        followed by a failed ship leaves the source stream intact."""
        self._no_state("export_page_span")
        n_blocks = self.blocks_for(n_tokens)
        if n_blocks > self.alloc_blocks[slot]:
            raise ValueError(
                f"slot {slot} maps {self.alloc_blocks[slot]} blocks; "
                f"export of {n_blocks} requested"
            )
        pages = [int(p) for p in self.table[slot, :n_blocks]]
        padded = self._quantized_blocks(n_blocks)
        ids = pages + [PagePool.TRASH] * (padded - n_blocks)
        spans = _gather_pages_impl(self.cache, jnp.asarray(ids, jnp.int32))
        # graftlint: allow[host-sync-in-hot-path] reason=THE designed migration-send sync — one coalesced device_get of the whole span, off the engine lock, only when a stream actually migrates
        host = jax.device_get(spans)
        return {
            "page_size": self.page_size,
            "n_blocks": n_blocks,
            "n_tokens": int(n_tokens),
            "leaves": {
                wire: host[key][:n_blocks].reshape((n_blocks,) + per_page)
                for wire, (key, per_page, _) in self.wire_leaves.items()
            },
        }

    # graftlint: hot-path
    def import_page_span(self, slot: int, payload: Dict[str, Any]) -> bool:
        """Materialize an exported span as ``slot``'s leading blocks:
        allocate fresh pages, scatter the bytes in (ONE dispatch), and map
        them in the host table. Bit-exact by construction (raw bytes, same
        dtypes). Returns False when the pool cannot cover the span (the
        caller falls back or waits); raises ValueError on a structurally
        incompatible payload (page size / leaf geometry mismatch — that is
        a wrong-fleet bug, not a capacity condition).

        Imported pages are ordinary refcounted pool pages (ref 1, owned by
        the slot): bank/share them and the standard copy-on-write guard
        protects any post-import write to a shared page."""
        self._no_state("import_page_span")
        if self.alloc_blocks[slot] != 0:
            raise ValueError("import_page_span needs an empty slot")
        # graftlint: allow[host-sync-in-hot-path] reason=wire-payload fields are host ints (json header), never device values
        page_size, n_blocks = int(payload["page_size"]), int(payload["n_blocks"])
        if page_size != self.page_size:
            raise ValueError(
                f"page-span page_size {page_size} != pool "
                f"page_size {self.page_size}"
            )
        if n_blocks > self.n_blocks:
            raise ValueError(
                f"span of {n_blocks} blocks exceeds per-slot capacity "
                f"{self.n_blocks}"
            )
        leaves = payload["leaves"]
        if set(leaves) != set(self.wire_leaves):
            raise ValueError(
                f"page-span leaves {sorted(leaves)} != pool leaves "
                f"{sorted(self.wire_leaves)}"
            )
        for wire, arr in leaves.items():
            _, shape, dtype = self.wire_leaves[wire]
            if tuple(arr.shape) != (n_blocks,) + shape or np.dtype(
                arr.dtype
            ) != np.dtype(dtype):
                raise ValueError(
                    f"page-span leaf {wire} is {arr.dtype}{arr.shape}; "
                    f"pool expects {np.dtype(dtype).str}[{n_blocks}]+{shape}"
                )
        fresh: List[int] = []
        for _ in range(n_blocks):
            page = self.pool.alloc()
            if page is None:
                self.pool.decref(fresh)  # roll the partial allocation back
                return False
            fresh.append(page)
        padded = self._quantized_blocks(n_blocks)
        ids = fresh + [PagePool.TRASH] * (padded - n_blocks)
        spans = {}
        for wire, arr in leaves.items():
            # heads back into the pool's lane axis (a view of a host array)
            arr = arr.reshape(arr.shape[:-2] + (-1,))
            pad = np.zeros(
                (padded - n_blocks,) + arr.shape[1:], dtype=arr.dtype
            )
            spans[self.wire_leaves[wire][0]] = jnp.asarray(
                np.concatenate([arr, pad], axis=0)
            )
        self.cache = _scatter_pages_impl(
            self.cache, jnp.asarray(ids, jnp.int32), spans
        )
        for b, p in enumerate(fresh):
            self.table[slot, b] = p
        self.alloc_blocks[slot] = n_blocks
        self.tables_dirty = True
        return True

    def _no_state(self, what: str) -> None:
        if self.state_pool_bytes:
            raise ValueError(
                f"{what} is refused for a model with recurrent state: the "
                "wire format carries pages, and a span without the slot's "
                "state is half a request"
            )

    def set_cursor(self, slot: int, value: int) -> None:
        """Set the slot's fill cursor in every index leaf (import install:
        the host knows the migrated stream's exact position)."""
        self.cache = _update_index(
            self.cache, _set_index_slot, jnp.int32(slot), jnp.int32(value)
        )

    def reset_slot_pages(self, slot: int) -> None:
        """Drop every page the slot maps WITHOUT freeing the slot itself
        (hot-reload prefill restart: shared pre-reload pages must not be
        rewritten under new weights). The caller re-reserves."""
        n = self.alloc_blocks[slot]
        if not n:
            return
        self.pool.decref(int(p) for p in self.table[slot, :n])
        self.table[slot, :n] = 0
        self.alloc_blocks[slot] = 0
        self.tables_dirty = True

    # ---- slot bookkeeping -------------------------------------------------

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def active_count(self) -> int:
        return self.n_slots - len(self._free)

    @property
    def page_pool_util(self) -> float:
        real = self.n_pages - 1
        return self.pool.in_use / real if real else 0.0

    def acquire(self) -> Optional[int]:
        return self._free.pop(0) if self._free else None

    def release(self, slots: List[int]) -> None:
        """Retire slots: drop their page references (pages a cached prefix
        still holds survive), zero their table rows and index cursors."""
        if not slots:
            return
        for s in slots:
            if s in self._free:
                raise ValueError(f"slot {s} double-released")
            n = self.alloc_blocks[s]
            if n:
                self.pool.decref(int(p) for p in self.table[s, :n])
                self.table[s, :n] = 0
                self.alloc_blocks[s] = 0
                self.tables_dirty = True
            self._unreserve(s)
            self._free.append(s)
        keep = jnp.asarray(
            [s not in self._free for s in range(self.n_slots)], jnp.bool_
        )
        self.cache = _update_index(self.cache, _reset_index, keep)
