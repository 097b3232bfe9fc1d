"""KV/state cache management for the serving engine.

Two arenas, one scheduler-facing contract (alloc/free slots, write
prefill, account bytes):

* ``KVArena`` — the contiguous slot arena (paper-faithful: llama.cpp uses
  a contiguous KV arena managed by the host, Fig. 4 keeps "KV cache
  management" on the host side): one preallocated cache pytree sized
  (num_slots, max_seq), each slot hosting one live sequence for its whole
  lifetime. Simple, but every slot reserves ``max_seq`` tokens of cache
  regardless of actual sequence length.

* ``PagedKVArena`` — paged/block KV allocation. Cache storage becomes
  head-major (num_blocks, heads, block_size, feature) leaves managed by
  a ``BlockAllocator``
  free list; each sequence holds a growable **block table** (logical
  block -> physical block) instead of a contiguous stripe. KV bytes
  resident per sequence shrink from ``max_seq`` to
  ``ceil(len / block_size) * block_size`` tokens, so the same arena bytes
  absorb far more concurrent short sequences — the serving-density lever
  the hardware-accelerator surveys (Kachris 2024; Li et al. 2024) call
  out, applied to the paper's host-side cache-management finding.
  Constant-size states (SSM recurrent state, enc-dec cross KV) are not
  paged: they keep per-slot storage and a degenerate one-block table.

Decode steps read K/V *through* the block table inside the jitted step —
by default via the fused paged-attention Pallas kernel, which resolves
(slot, kv_block) -> physical page through scalar-prefetched tables (see
``PagedKVArena.page_layout`` for the layout contract) — so block
allocation mid-decode never changes a traced shape: continuous batching
and paging compose without re-jit.
"""
from __future__ import annotations

import functools
import hashlib
import heapq
import weakref
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import ModelAPI
from repro.models.attention import KV_QUANT_SCALE_DTYPE
from repro.parallel.sharding import (serving_cache_shardings, serving_degrees,
                                     slot_sharding)

#: Supported paged-KV storage quantization modes.
KV_QUANT_MODES = ("none", "int8")


def allocate(model: ModelAPI, batch: int, max_seq: int,
             dtype=jnp.bfloat16):
    """Zero-filled cache pytree sized for ``max_seq`` (the arena storage)."""
    shapes = model.cache_shapes(batch, max_seq)

    def mk(x):
        return jnp.zeros(x, dtype) if isinstance(x, tuple) else x
    return jax.tree.map(mk, shapes, is_leaf=lambda x: isinstance(x, tuple))


# Probe results keyed on the model by *weak* reference, then on the
# (shapes, dtype) signature. ServingEngine.reset() rebuilds arenas —
# without memoization every reset would re-trace the whole decode graph
# abstractly. The weak key means a dropped model's entries vanish with
# it: rebuilding engines in a loop cannot grow the cache without bound
# (the old id(model)-keyed version pinned every model ever probed).
_STEP_DTYPE_CACHE: "weakref.WeakKeyDictionary[ModelAPI, dict]" = \
    weakref.WeakKeyDictionary()


def step_leaf_dtypes(model: ModelAPI, batch: int, max_seq: int, dtype,
                     const_flags: Tuple[bool, ...],
                     mesh_key: Tuple[int, int] = (1, 1)) -> Tuple:
    """Per-leaf arena storage dtypes (flattened leaf order).

    Seq-indexed KV leaves store the requested cache ``dtype`` (the decode
    step casts its inserts to match). Constant-size *state* leaves (SSM
    recurrent/conv state, enc-dec cross KV) instead store whatever dtype
    the decode step **emits** at fixed point — probed with
    ``jax.eval_shape`` over abstract params, so no allocation or compile
    (memoized per (model, shapes, dtype, mesh): arena rebuilds don't
    re-trace). Without this, a bf16 arena hands the SSM recurrence a bf16
    state and gets an f32 one back: the second step sees new traced dtypes
    and recompiles (the ssm/hybrid "one extra step compile" ROADMAP item).
    Pure-attention models skip the probe entirely (no const leaves).
    ``mesh_key`` is the serving mesh fingerprint ``(dp, tp)`` — entries
    probed under different meshes must not collide, even though today's
    abstract probe is layout-blind (a sharded probe variant would not be)."""
    if not any(const_flags):
        return tuple(dtype for _ in const_flags)
    per_model = _STEP_DTYPE_CACHE.setdefault(model, {})
    key = (batch, max_seq, jnp.dtype(dtype).name, const_flags, mesh_key)
    hit = per_model.get(key)
    if hit is not None:
        return hit
    specs = model.cache_specs(batch, max_seq, dtype)
    params = model.abstract_params()
    token = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((batch,), jnp.int32)
    for _ in range(3):                     # tiny fixed-point iteration
        _, out = jax.eval_shape(model.decode_step, params, token, pos,
                                specs)
        emitted = tuple(x.dtype for x in jax.tree.leaves(out))
        leaves, treedef = jax.tree.flatten(specs)
        if emitted == tuple(x.dtype for x in leaves):
            break
        specs = treedef.unflatten(
            [jax.ShapeDtypeStruct(leaf.shape, dt)
             for leaf, dt in zip(leaves, emitted)])
    probed = tuple(x.dtype for x in jax.tree.leaves(specs))
    out = tuple(pd if const else jnp.dtype(dtype)
                for pd, const in zip(probed, const_flags))
    per_model[key] = out
    return out


class _FreeHeap:
    """Min-heap free list with O(log n) alloc/free and a membership set
    guarding double-frees (the old list-based free list re-sorted the
    whole list on every free — O(n log n) per release). ``remove`` takes
    a *specific* member out of the free list (prefix-cache resurrection
    of a freed-but-still-cached block) by lazy deletion: the heap entry
    stays behind and is skipped at pop when no longer in the set."""

    def __init__(self, n: int):
        self.n = n
        self._heap: List[int] = list(range(n))   # already heap-ordered
        self._free_set = set(self._heap)

    def __len__(self) -> int:
        return len(self._free_set)

    def __contains__(self, i: int) -> bool:
        return i in self._free_set

    def pop(self) -> Optional[int]:
        while self._heap:
            i = heapq.heappop(self._heap)
            if i in self._free_set:             # skip lazily-removed ids
                self._free_set.discard(i)
                return i
        return None

    def push(self, i: int) -> None:
        if i in self._free_set or not (0 <= i < self.n):
            raise ValueError(f"bad free: {i}")
        heapq.heappush(self._heap, i)
        self._free_set.add(i)

    def remove(self, i: int) -> None:
        if i not in self._free_set:
            raise ValueError(f"not free: {i}")
        self._free_set.discard(i)


class BlockAllocator:
    """Refcounted free-list allocator over ``num_blocks`` physical KV
    blocks of ``block_size`` tokens each. All-or-nothing multi-block
    allocation (an admission either gets its whole reservation or stays
    queued).

    Refcount lifecycle (prefix sharing): ``alloc`` hands out blocks at
    refcount 1; ``share`` takes an extra reference on a block another
    table already maps (or resurrects a refcount-0 block straight out of
    the free list — its page contents are still intact); ``free`` is a
    *decref* — a block returns to the free list only when its last
    reference drops. A refcount-0 block keeps its page contents until
    ``alloc`` reissues it, at which point the ``on_alloc`` hook fires so
    the prefix cache can drop the stale entry."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 1 or block_size < 1:
            raise ValueError(
                f"need num_blocks >= 1 and block_size >= 1, got "
                f"{num_blocks}/{block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free = _FreeHeap(num_blocks)
        self._ever_used: set = set()
        self.reissues = 0               # allocations of a previously-freed block
        self.refcounts: List[int] = [0] * num_blocks
        self.on_alloc = None            # callback(block) on (re)issue

    # -- queries ---------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        """Blocks currently on the free list (refcount 0)."""
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Blocks held by at least one reference."""
        return self.num_blocks - len(self._free)

    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to cover ``tokens`` cache positions."""
        return max(1, -(-int(tokens) // self.block_size))

    # -- lifecycle -------------------------------------------------------
    def alloc(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` blocks (lowest ids first) or None if < n are free."""
        if n < 0:
            raise ValueError(f"bad alloc count: {n}")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self.reissues += sum(1 for b in out if b in self._ever_used)
        self._ever_used.update(out)
        for b in out:
            self.refcounts[b] = 1
            if self.on_alloc is not None:
                self.on_alloc(b)
        return out

    def share(self, blocks: List[int]) -> None:
        """Take one extra reference per block. A live block (refcount
        >= 1) is simply increffed; a refcount-0 block still sitting in
        the free list (freed but cached, contents intact) is resurrected
        — pulled out of the free list with its page untouched."""
        for b in blocks:
            if not (0 <= b < self.num_blocks):
                raise ValueError(f"bad share: {b}")
            if self.refcounts[b] == 0:
                self._free.remove(b)
            self.refcounts[b] += 1

    def free(self, blocks: List[int]) -> None:
        """Drop one reference per block (decref). The block rejoins the
        free list only at refcount 0; its page contents are left intact
        so a prefix-cache entry can resurrect it until reissue."""
        for b in blocks:
            if not (0 <= b < self.num_blocks) or self.refcounts[b] <= 0:
                raise ValueError(f"bad free: {b}")
            self.refcounts[b] -= 1
            if self.refcounts[b] == 0:
                self._free.push(b)


class PrefixCache:
    """Host-side map from hashed token-block *chains* to physical pages.

    Key for chain block ``i`` is the running SHA-256 over all prompt
    tokens in blocks ``0..i`` — so a key identifies a full prefix, not a
    bag of tokens, and lookup is a walk from the root that stops at the
    first miss. Only **full** blocks are ever registered: the trailing
    partial block of a live sequence is written by ``paged_insert_token``
    every step and must stay exclusively owned.

    The cache holds no references of its own — an entry over a
    refcount-0 block is a *resurrection candidate*, not pinned memory.
    Eviction is implicit: when the allocator reissues a freed block, the
    ``invalidate_block`` hook drops its entry. Entries are 1:1 with
    blocks, so the cache can never exceed ``num_blocks`` entries."""

    def __init__(self, block_size: int):
        self.block_size = block_size
        self._by_key: Dict[bytes, int] = {}
        self._by_block: Dict[int, bytes] = {}
        self.hits = 0          # block-level lookup hits at admission
        self.misses = 0        # full prompt blocks that missed
        self.evictions = 0     # entries dropped on block reissue

    def __len__(self) -> int:
        return len(self._by_key)

    def keys_for(self, tokens, nfull: int) -> List[bytes]:
        """Chain digests for the first ``nfull`` full blocks of a prompt."""
        h = hashlib.sha256()
        keys: List[bytes] = []
        toks = np.asarray(tokens, np.int64)
        for i in range(nfull):
            h.update(toks[i * self.block_size:(i + 1) * self.block_size]
                     .tobytes())
            keys.append(h.digest())
        return keys

    def lookup(self, keys: List[bytes]) -> List[int]:
        """Longest cached chain prefix: physical blocks for consecutive
        key hits from the root, stopping at the first miss."""
        out: List[int] = []
        for k in keys:
            b = self._by_key.get(k)
            if b is None:
                break
            out.append(b)
        return out

    def is_cached(self, block: int) -> bool:
        """Whether ``block`` currently backs a published cache entry."""
        return block in self._by_block

    def register(self, key: bytes, block: int) -> bool:
        """Publish ``key -> block``; no-op if the key is already mapped
        (first writer wins — siblings sharing that entry already point at
        the published page). Returns True if a new entry was added."""
        if key in self._by_key:
            return False
        stale = self._by_block.pop(block, None)
        if stale is not None:               # block re-published under a new chain
            del self._by_key[stale]
        self._by_key[key] = block
        self._by_block[block] = key
        return True

    def invalidate_block(self, block: int) -> None:
        """Allocator reissued ``block`` — its cached contents are gone."""
        key = self._by_block.pop(block, None)
        if key is not None:
            del self._by_key[key]
            self.evictions += 1


class KVArena:
    """Fixed-size slot arena over the model's cache pytree.

    The arena owns the storage and the free list; the scheduler decides
    which request gets a freed slot. All decode steps run over the full
    (num_slots, ...) buffers with per-slot position/active masks, so slot
    turnover never changes a traced shape.
    """

    def __init__(self, model: ModelAPI, num_slots: int, max_seq: int,
                 dtype=jnp.bfloat16, mesh=None):
        self.model = model
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.dtype = dtype
        self.mesh = mesh
        self.dp, self.tp = serving_degrees(mesh)
        self._free = _FreeHeap(num_slots)
        # Leaves whose extent does NOT follow the sequence length (SSM
        # recurrent/conv state, enc-dec cross KV) carry *state*, not
        # masked history — chunked admission must zero them. Probe two
        # seq lengths and flag the leaves that did not move.
        is_shape = lambda x: isinstance(x, tuple)
        ta = jax.tree.leaves(model.cache_shapes(num_slots, 160),
                             is_leaf=is_shape)
        tb = jax.tree.leaves(model.cache_shapes(num_slots, 224),
                             is_leaf=is_shape)
        self._const_flags: Tuple[bool, ...] = tuple(
            a == b for a, b in zip(ta, tb))
        # Per-leaf storage dtypes: state leaves keep the dtype the decode
        # step emits (f32 SSM state), so step 1 never re-traces.
        self._leaf_dtypes = step_leaf_dtypes(model, num_slots, max_seq,
                                             dtype, self._const_flags,
                                             (self.dp, self.tp))
        shapes = model.cache_shapes(num_slots, max_seq)
        leaves, treedef = jax.tree.flatten(shapes, is_leaf=is_shape)
        self.buffers = treedef.unflatten(
            [jnp.zeros(s, dt) for s, dt in zip(leaves, self._leaf_dtypes)])
        self._shardings = None
        if mesh is not None:
            self._shardings = serving_cache_shardings(self.buffers, mesh)
            self.buffers = jax.device_put(self.buffers, self._shardings)

    def _repin(self) -> None:
        """Re-commit the buffers to their mesh shardings after an
        out-of-step jitted mutation (insert/reset/rollback helpers let
        GSPMD pick output layouts; a no-op device_put restores the
        committed placement so the serving step never re-jits)."""
        if self._shardings is not None:
            self.buffers = jax.device_put(self.buffers, self._shardings)

    # -- slot lifecycle -------------------------------------------------
    @property
    def free_slots(self) -> int:
        """Slots available for admission."""
        return len(self._free)

    @property
    def used_slots(self) -> int:
        """Slots hosting a live sequence."""
        return self.num_slots - len(self._free)

    def alloc(self) -> Optional[int]:
        """Claim a free slot (lowest index first) or None when full."""
        return self._free.pop()

    def free(self, slot: int) -> None:
        """Return ``slot`` to the free list (its storage is left stale —
        masked by kv_len and rewritten before reuse)."""
        self._free.push(slot)

    # -- storage --------------------------------------------------------
    def write_prefill(self, prefill_cache, slot: int) -> None:
        """Insert a B=1 prefill cache (seq <= max_seq) into ``slot``."""
        self.buffers = _arena_insert(self.buffers, prefill_cache,
                                     jnp.int32(slot))
        self._repin()

    def reset_slot(self, slot: int) -> None:
        """Zero ``slot``'s constant-size state leaves for a fresh chunked
        admission (no prefill write happens in chunked mode). Seq-indexed
        KV leaves are left alone — stale history is masked by kv_len and
        rewritten before use. No-op (zero device work) for pure-attention
        models."""
        if not any(self._const_flags):
            return
        leaves, treedef = jax.tree.flatten(self.buffers)
        new = _zero_const_leaves(leaves, jnp.int32(slot), self._const_flags)
        self.buffers = jax.tree.unflatten(treedef, new)
        self._repin()

    def nbytes(self) -> int:
        """Total device bytes of the arena's cache storage."""
        return cache_nbytes(self.buffers)

    def slot_bytes(self) -> float:
        """Bytes of arena backing one slot."""
        return self.nbytes() / self.num_slots

    def token_bytes(self) -> float:
        """Approximate cache bytes appended per generated token (exact for
        pure seq-indexed KV; SSM constant-size states amortized)."""
        return self.slot_bytes() / self.max_seq

    def resident_bytes(self) -> float:
        """Arena bytes reserved by live sequences. Whole-sequence slots
        pin a full max_seq stripe from admission to completion."""
        return self.used_slots * self.slot_bytes()

    # -- speculative rollback -------------------------------------------
    def rollback(self, slot: int, start: int, count: int,
                 width: int) -> None:
        """Erase cache positions ``[start, start + count)`` of ``slot``'s
        seq-indexed leaves — the KV a verification step inserted for
        *rejected* proposal tokens. Zeroing (rather than only rewinding
        the position) restores the arena bit-identical to never having
        inserted them: future reads are already kv_len-masked, but the
        rollback contract is checked differentially, not argued.
        ``width`` is the static window (the engine's chunk size), so one
        compilation covers every (slot, start, count)."""
        if count <= 0:
            return
        leaves, treedef = jax.tree.flatten(self.buffers)
        seq_flags = tuple(not c for c in self._const_flags)
        new = _zero_span(leaves, jnp.int32(slot), jnp.int32(start),
                         jnp.int32(count), width, seq_flags)
        self.buffers = jax.tree.unflatten(treedef, new)
        self._repin()


@functools.partial(jax.jit, donate_argnums=(0,))
def _arena_insert(arena, prefill_cache, slot):
    """Write a B=1 prefill cache into arena slot ``slot`` (traced scalar, so
    every slot shares one compilation per prefill-cache shape). Leaves are
    (L, B, S, ...): insert at (0, slot, 0, ...) — one in-place
    dynamic_update_slice per leaf, no fresh padded copy."""
    def w(a, c):
        start = (0, slot) + (0,) * (a.ndim - 2)
        return jax.lax.dynamic_update_slice(a, c.astype(a.dtype), start)
    return jax.tree.map(w, arena, prefill_cache)


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _zero_const_leaves(leaves, slot, const_flags):
    """Zero the constant-size (non-seq-indexed) leaves of one arena slot
    so a fresh admission never sees its predecessor's recurrent/cross
    state. ``slot`` is traced, so every slot shares one compilation."""
    out = []
    for a, is_const in zip(leaves, const_flags):
        if not is_const:
            out.append(a)
            continue
        zero = jnp.zeros((a.shape[0], 1) + a.shape[2:], a.dtype)
        start = (0, slot) + (0,) * (a.ndim - 2)
        out.append(jax.lax.dynamic_update_slice(a, zero, start))
    return out


@functools.partial(jax.jit, static_argnums=(4, 5), donate_argnums=(0,))
def _zero_span(leaves, slot, start, count, width, seq_flags):
    """Zero cache positions [start, start + count) of one slot across the
    seq-indexed leaves (L, B, S, ...). ``width`` is static (>= count) so
    every rollback shares one compilation; the window start is clamped to
    the leaf and the in-window mask re-aligned, so a span ending at S is
    handled without out-of-range slicing."""
    out = []
    for a, is_seq in zip(leaves, seq_flags):
        if not is_seq:
            out.append(a)
            continue
        s = a.shape[2]
        w = min(width, s)
        sc = jnp.clip(start, 0, s - w)          # clamped window start
        rel = start - sc                        # span offset inside window
        begin = (jnp.int32(0), slot, sc) + (jnp.int32(0),) * (a.ndim - 3)
        win = jax.lax.dynamic_slice(
            a, begin, (a.shape[0], 1, w) + a.shape[3:])
        mask = (jnp.arange(w) >= rel) & (jnp.arange(w) < rel + count)
        mask = mask.reshape((1, 1, w) + (1,) * (a.ndim - 3))
        win = jnp.where(mask, jnp.zeros((), a.dtype), win)
        out.append(jax.lax.dynamic_update_slice(a, win, begin))
    return out


@functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
def _zero_paged_positions(leaves, phys, offs, paged_flags):
    """Zero (physical page, in-page offset) pairs across the head-major
    paged leaves (L, NB, H, bs, ...), every head at once. Callers pad the
    pair list to a fixed width with null-block entries — the null page's
    contents are garbage by the layout contract, so zeroing it is free
    and keeps one compilation per pad width."""
    out = []
    for a, is_paged in zip(leaves, paged_flags):
        if not is_paged:
            out.append(a)
            continue
        out.append(a.at[:, phys, :, offs].set(jnp.zeros((), a.dtype)))
    return out


@functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
def _copy_pages(leaves, src, dst, paged_flags):
    """Copy physical pages ``src[i] -> dst[i]`` across the paged leaves
    (L, NB, H, bs, ...) — the copy-on-write split. Callers pad the pair
    list to a fixed width with null->null entries (the null page's
    contents are garbage by the layout contract, so self-copying it is
    free) to keep one compilation per pad width."""
    out = []
    for a, is_paged in zip(leaves, paged_flags):
        if not is_paged:
            out.append(a)
            continue
        out.append(a.at[:, dst].set(a[:, src]))
    return out


@functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0,))
def _paged_insert(buf_leaves, cache_leaves, phys, slot, paged_flags):
    """Scatter a B=1 prefill cache into an arena's physical blocks.

    ``buf_leaves``/``cache_leaves``: flattened leaf lists (same treedef).
    Paged leaves: buffer (L, NB, H, bs, F), cache (L, 1, P, [H,] F) — the
    P prefilled positions are re-blocked, made head-major (a cache
    without a head axis, MLA's latents, gets the unit one) and scattered
    to the ``phys`` block ids (padding past P is garbage-but-masked,
    exactly like the slot arena's bucket padding; positions past the
    reservation are dropped).
    Constant leaves: buffer (L, num_slots, ...), written at ``slot``.
    Static ``paged_flags`` keeps one compilation per (cache shape, block
    count) pair — prefill-cache shapes bound the compile count.
    """
    nbw = phys.shape[0]
    out = []
    for a, c, is_paged in zip(buf_leaves, cache_leaves, paged_flags):
        c = c.astype(a.dtype)
        if not is_paged:
            start = (0, slot) + (0,) * (a.ndim - 2)
            out.append(jax.lax.dynamic_update_slice(a, c, start))
            continue
        bs = a.shape[3]
        c2 = c[:, 0]                                 # (L, P, [H,] F)
        if c2.ndim == a.ndim - 2:
            c2 = c2[:, :, None]                      # (L, P, 1, F)
        want = nbw * bs
        P = c2.shape[1]
        if P < want:
            pad = [(0, 0), (0, want - P)] + [(0, 0)] * (c2.ndim - 2)
            c2 = jnp.pad(c2, pad)
        elif P > want:
            c2 = c2[:, :want]
        c2 = c2.reshape((c2.shape[0], nbw, bs) + c2.shape[2:])
        out.append(a.at[:, phys].set(jnp.swapaxes(c2, 2, 3)))
    return out


class PagedKVArena:
    """Block-table KV arena: storage is head-major (num_blocks, heads,
    block_size, feature) pages,
    each slot maps logical blocks to physical blocks through a growable
    table. One extra physical block (id ``num_blocks``) is the **null
    block**: unassigned table entries and inactive slots' writes land
    there, so the jitted step never needs a data-dependent guard.

    Lifecycle: ``alloc_slot(nblocks)`` admits a sequence (slot + initial
    reservation, all-or-nothing), ``ensure(slot, tokens)`` grows the table
    as decode crosses block boundaries (None on allocator exhaustion —
    the engine preempts), ``free_slot`` returns everything to the free
    lists.

    With ``prefix_cache=True`` blocks become refcounted and distinct
    slots MAY alias *full prompt blocks* (copy-on-write prefix sharing):
    ``alloc_slot_prefix`` maps a cached prompt prefix onto existing
    physical pages, ``register_prefix`` publishes a finished prefill's
    full blocks, and ``prepare_write`` splits any shared block before a
    write can land on it — so the per-step K/V scatter through the table
    remains collision-free by invariant: every position a step writes
    maps to an exclusively-owned (refcount-1) block.

    With ``kv_quant="int8"`` every paged leaf stores blocked int8 codes
    plus a float16 scale page (per-position, per-kv-head absmax scale,
    computed at insert time inside the jitted step); the fused kernel
    dequantizes during the block walk. Block/slot lifecycle, rollback,
    CoW and the prefix cache are representation-agnostic — they move or
    zero code and scale pages through the same leaf-wise jitted helpers.
    """

    def __init__(self, model: ModelAPI, num_slots: int, max_seq: int,
                 block_size: int, num_blocks: Optional[int] = None,
                 dtype=jnp.bfloat16, prefix_cache: bool = False,
                 kv_quant: str = "none", mesh=None):
        """Build the paged arena. See the class docstring for the model;
        ``kv_quant="int8"`` stores paged leaves as blocked int8 code
        pages plus float16 scale pages (quantize-on-insert, in-kernel
        dequant — see ``page_layout``). ``mesh`` commits the page storage
        to a ('data','model') serving mesh: pages shard over 'data', GQA
        kv-heads over 'model' (see ``parallel.sharding``)."""
        if not (1 <= block_size <= max_seq):
            raise ValueError(f"block_size {block_size} outside [1, {max_seq}]")
        if kv_quant not in KV_QUANT_MODES:
            raise ValueError(f"unknown kv_quant {kv_quant!r} "
                             f"(choose from {KV_QUANT_MODES})")
        self.model = model
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.block_size = block_size
        self.max_blocks = -(-max_seq // block_size)   # table width per slot
        if num_blocks is None:
            num_blocks = num_slots * self.max_blocks  # capacity parity
        self.num_blocks = num_blocks
        self.null_block = num_blocks                  # last physical page
        self.dtype = dtype
        self.kv_quant = kv_quant
        self.mesh = mesh
        self.dp, self.tp = serving_degrees(mesh)

        shapes, paged = model.paged_cache_shapes(num_slots, num_blocks + 1,
                                                 block_size)
        self._paged_flags: Tuple[bool, ...] = tuple(jax.tree.leaves(paged))
        # Per-leaf dtypes: non-paged state leaves store what the decode
        # step emits (f32 SSM state) — same one-compile guarantee as the
        # slot arena; paged page leaves store the requested cache dtype.
        self._leaf_dtypes = step_leaf_dtypes(
            model, num_slots, max_seq, dtype,
            tuple(not f for f in self._paged_flags),
            (self.dp, self.tp))
        is_shape = lambda x: isinstance(x, tuple)
        if kv_quant == "int8":
            if not any(self._paged_flags):
                raise ValueError(
                    "kv_quant requires paged (seq-indexed) KV leaves; "
                    "constant-size recurrent state has no per-position "
                    "rows to quantize independently")
            # Every paged leaf splits into {"q": int8 code pages, "s":
            # float16 scale pages} (scale shape = page shape minus the
            # quantized feature axis). The dict expands each paged leaf
            # into two flattened leaves ("q" sorts before "s"), both
            # paged, so every generic jitted helper — _paged_insert,
            # _copy_pages, _zero_paged_positions — applies to codes and
            # scales identically with zero special-casing.
            shapes = jax.tree.map(
                lambda s, f: {"q": s, "s": s[:-1]} if f else s,
                shapes, paged, is_leaf=is_shape)
            flags, dts = [], []
            for f, dt in zip(self._paged_flags, self._leaf_dtypes):
                if f:
                    flags += [True, True]
                    dts += [jnp.dtype(jnp.int8),
                            jnp.dtype(KV_QUANT_SCALE_DTYPE)]
                else:
                    flags.append(f)
                    dts.append(dt)
            self._paged_flags = tuple(flags)
            self._leaf_dtypes = tuple(dts)
        leaves, treedef = jax.tree.flatten(shapes, is_leaf=is_shape)
        self.buffers = treedef.unflatten(
            [jnp.zeros(s, dt) for s, dt in zip(leaves, self._leaf_dtypes)])
        self.has_paged = any(self._paged_flags)
        self._shardings = None
        self._table_sharding = None
        # Pages shard over 'data' only when the page count divides dp
        # (the rule in serving_cache_spec); remember the outcome so
        # page_layout can report the local shard's page count.
        self._pages_data_sharded = (
            mesh is not None and self.dp > 1 and self.has_paged
            and (num_blocks + 1) % self.dp == 0)
        if mesh is not None:
            self._shardings = serving_cache_shardings(
                self.buffers, mesh, self._paged_flags)
            self.buffers = jax.device_put(self.buffers, self._shardings)
            if self.dp > 1 and num_slots % self.dp == 0:
                self._table_sharding = slot_sharding(mesh, 2)
        # Shape-static byte quantities, precomputed once (resident_bytes
        # runs on the per-step hot path).
        self._nbytes = cache_nbytes(self.buffers)
        self._block_bytes = float(sum(
            x.size // x.shape[1] * x.dtype.itemsize
            for x, f in zip(jax.tree.leaves(self.buffers),
                            self._paged_flags) if f))
        self._const_bytes = self._nbytes \
            - self._block_bytes * (num_blocks + 1)

        self.allocator = BlockAllocator(num_blocks, block_size)
        self._free_slots = _FreeHeap(num_slots)
        self.tables = np.full((num_slots, self.max_blocks), self.null_block,
                              np.int32)
        self._slot_blocks: List[List[int]] = [[] for _ in range(num_slots)]
        self._dev_tables: Optional[jnp.ndarray] = None   # upload cache
        self.table_uploads = 0
        self.cow_splits = 0             # copy-on-write block splits
        self.prefix_cache: Optional[PrefixCache] = None
        if prefix_cache:
            if not self.has_paged:
                raise ValueError(
                    "prefix_cache requires paged (seq-indexed) KV leaves; "
                    "constant-size recurrent state is not addressable by "
                    "token-block chains")
            self.prefix_cache = PrefixCache(block_size)
            self.allocator.on_alloc = self.prefix_cache.invalidate_block

    def _repin(self) -> None:
        """Re-commit the page buffers to their mesh shardings after an
        out-of-step jitted mutation (CoW splits, rollback zeroing, prefill
        scatter let GSPMD pick output layouts; a no-op device_put restores
        the committed placement so the serving step never re-jits)."""
        if self._shardings is not None:
            self.buffers = jax.device_put(self.buffers, self._shardings)

    # -- queries ---------------------------------------------------------
    def page_layout(self) -> dict:
        """The page/table layout contract the fused paged-attention
        kernel (``kernels/paged_attention.py``) consumes:

        * paged leaves are head-major ``(L, num_pages, heads,
          block_size, feature)`` physical pages (GQA: kv-heads; MLA
          latents: a unit head axis) with ``num_pages == num_blocks +
          1`` — the trailing page (id ``null_block``) is the **null
          sentinel**. One ``(block_size, feature)`` tile per (page,
          head) is what the fused kernel DMAs, and its two trailing dims
          are the array's own, as TPU tiling requires;
        * every slot's block-table row is padded to ``max_blocks``
          entries; entries past the slot's allocation hold
          ``null_block``. Null-page contents are finite garbage (zeros,
          or stale inactive-slot writes) and always sit past ``kv_len``,
          so the kernel masks them before the softmax — no
          data-dependent guard needed inside the jitted step;
        * ``kv_quant == "int8"``: each paged leaf is a dict ``{"q", "s"}``
          — int8 code pages in the original page shape plus float16
          scale pages shaped like the pages minus the quantized feature
          axis, ``(L, num_pages, heads, block_size)`` (one scale per
          (kv-head, in-page position)). Codes and
          scales share the block table; the fused kernel dequantizes
          during the walk and zeroed pages dequantize to exactly zero,
          so the null/rollback/CoW contracts above apply unchanged.

        Under a data-parallel serving mesh ``num_pages`` stays the
        *global* (traced) page count — the kernel contract is unchanged —
        while ``local_pages`` reports the pages physically resident on
        one 'data' shard (``num_pages / dp`` when the page axis sharded,
        else the full count). Per-device byte accounting must scale by
        ``local_pages``, not ``num_pages``, so the fused-read
        arena-scaling gate stays exactly 1.0x under DP.

        See ``docs/kernel-contracts.md`` for the full written contract.
        """
        pages = self.num_blocks + 1
        local = pages // self.dp if self._pages_data_sharded else pages
        return {"block_size": self.block_size,
                "max_blocks": self.max_blocks,
                "num_pages": pages,
                "local_pages": local,
                "data_shards": self.dp,
                "null_block": self.null_block,
                "kv_quant": self.kv_quant}

    @property
    def free_slots(self) -> int:
        """Slots available for admission."""
        return len(self._free_slots)

    @property
    def used_slots(self) -> int:
        """Slots hosting a live sequence."""
        return self.num_slots - len(self._free_slots)

    def blocks_needed(self, tokens: int) -> int:
        """Blocks covering ``tokens`` cache positions (1 for models with
        no seq-indexed cache — the degenerate one-block table)."""
        if not self.has_paged:
            return 1
        return self.allocator.blocks_for(tokens)

    def slot_blocks(self, slot: int) -> List[int]:
        """Copy of ``slot``'s physical block list (logical order)."""
        return list(self._slot_blocks[slot])

    def device_tables(self) -> Tuple[jnp.ndarray, int]:
        """(device table array, bytes uploaded now). Tables mutate only at
        admission / block-boundary crossings / preemption, so the device
        copy is cached and re-uploaded only when dirty — steady-state
        decode steps move zero table bytes."""
        fresh = 0
        if self._dev_tables is None:
            if self._table_sharding is not None:
                self._dev_tables = jax.device_put(self.tables,
                                                  self._table_sharding)
            else:
                self._dev_tables = jnp.asarray(self.tables)
            fresh = self.tables.nbytes
            self.table_uploads += 1
        return self._dev_tables, fresh

    # -- slot/block lifecycle --------------------------------------------
    def alloc_slot(self, nblocks: int) -> Optional[int]:
        """Admit: claim a slot AND its initial ``nblocks`` reservation,
        all-or-nothing (ISSUE gate: admit when ceil(prompt/block) blocks
        are free). Returns the slot or None."""
        if self.free_slots == 0:
            return None
        blocks = self.allocator.alloc(nblocks)
        if blocks is None:
            return None
        slot = self._free_slots.pop()
        self._slot_blocks[slot] = blocks
        self.tables[slot, :len(blocks)] = blocks
        self._dev_tables = None
        return slot

    def alloc_slot_prefix(self, prompt_tokens,
                          chunk: int) -> Optional[Tuple[int, int, int]]:
        """Admission with prefix-cache matching: map the longest cached
        full-block chain of ``prompt_tokens`` onto existing physical
        pages (shared, increffed) and allocate fresh blocks only for the
        rest of the first feed. All-or-nothing like ``alloc_slot``.

        Returns ``(slot, hit_tokens, resident_growth_blocks)`` or None.
        ``hit_tokens`` prompt positions already hold valid KV — the
        engine skips feeding them. At least one prompt token is always
        re-fed (its logits seed sampling): when the *whole* prompt is
        cached, the last chain block is split copy-on-write at admission
        — position ``len - 1`` will be rewritten by that feed, and a
        shared page must never be written. ``resident_growth_blocks``
        counts blocks that newly became resident (fresh + resurrected;
        shares of live blocks are free)."""
        pc = self.prefix_cache
        if pc is None:
            raise ValueError("arena built without prefix_cache")
        if self.free_slots == 0:
            return None
        L = len(prompt_tokens)
        nfull = L // self.block_size
        keys = pc.keys_for(prompt_tokens, nfull)
        shared = pc.lookup(keys)
        cow_src: Optional[int] = None
        if shared and len(shared) * self.block_size >= L:
            cow_src = shared[-1]        # fully cached: split the last block
            shared = shared[:-1]
        hit_blocks = len(shared) + (cow_src is not None)
        h = (L - 1) if cow_src is not None else len(shared) * self.block_size
        take = shared + ([cow_src] if cow_src is not None else [])
        used0 = self.allocator.used_blocks
        self.allocator.share(take)      # hold refs while we allocate/copy
        need = self.blocks_needed(h + min(L - h, chunk))
        fresh = self.allocator.alloc(need - hit_blocks
                                     + (cow_src is not None))
        if fresh is None:
            self.allocator.free(take)   # roll the shares back
            return None
        if cow_src is not None:
            dst = fresh[0]
            leaves, treedef = jax.tree.flatten(self.buffers)
            new = _copy_pages(leaves, jnp.asarray([cow_src], jnp.int32),
                              jnp.asarray([dst], jnp.int32),
                              self._paged_flags)
            self.buffers = jax.tree.unflatten(treedef, new)
            self._repin()
            self.allocator.free([cow_src])
            self.cow_splits += 1
            blocks = shared + [dst] + fresh[1:]
        else:
            blocks = shared + fresh
        slot = self._free_slots.pop()
        self._slot_blocks[slot] = blocks
        self.tables[slot] = self.null_block
        self.tables[slot, :len(blocks)] = blocks
        self._dev_tables = None
        pc.hits += hit_blocks
        pc.misses += nfull - hit_blocks
        return slot, h, self.allocator.used_blocks - used0

    def register_prefix(self, slot: int, prompt_tokens) -> int:
        """Publish ``slot``'s full prompt blocks into the prefix cache
        (called when prefill completes — positions [0, prompt_len) are
        all written and decode writes land strictly past them). The
        trailing partial block is never registered: it keeps taking
        per-step writes and must stay exclusively owned. Idempotent —
        chains already published (by this sequence's own cache hit, or a
        sibling's earlier prefill) are skipped. Returns new entries."""
        pc = self.prefix_cache
        if pc is None or not self.has_paged:
            return 0
        nfull = len(prompt_tokens) // self.block_size
        owned = self._slot_blocks[slot]
        added = 0
        for i, key in enumerate(pc.keys_for(prompt_tokens, nfull)):
            if i >= len(owned):
                break
            added += pc.register(key, owned[i])
        return added

    def prepare_write(self, slot: int, start: int, count: int,
                      width: int) -> Optional[int]:
        """Copy-on-write barrier: make every block that positions
        ``[start, start + count)`` map to exclusively owned before the
        step writes there. Shared blocks (refcount > 1) are split —
        pages copied to fresh blocks inside the jitted path, the table
        remapped, the shared reference dropped. Returns the number of
        blocks split (0 when nothing in range is shared) or None on
        allocator exhaustion (the caller preempts a victim and retries).
        ``width`` is the static pad width, so every split shares one
        compilation per width."""
        if count <= 0 or self.prefix_cache is None or not self.has_paged:
            return 0
        bs = self.block_size
        owned = self._slot_blocks[slot]
        b0 = start // bs
        b1 = min((start + count - 1) // bs, len(owned) - 1)
        cow = [(i, owned[i]) for i in range(b0, b1 + 1)
               if self.allocator.refcounts[owned[i]] > 1]
        if not cow:
            return 0
        fresh = self.allocator.alloc(len(cow))
        if fresh is None:
            return None
        w = max(width, len(cow))
        src = np.full((w,), self.null_block, np.int32)
        dst = np.full((w,), self.null_block, np.int32)
        for j, (_, old) in enumerate(cow):
            src[j] = old
            dst[j] = fresh[j]
        leaves, treedef = jax.tree.flatten(self.buffers)
        new = _copy_pages(leaves, jnp.asarray(src), jnp.asarray(dst),
                          self._paged_flags)
        self.buffers = jax.tree.unflatten(treedef, new)
        self._repin()
        for j, (i, _) in enumerate(cow):
            owned[i] = fresh[j]
            self.tables[slot, i] = fresh[j]
        self.allocator.free([old for _, old in cow])   # drop shared refs
        self._dev_tables = None
        self.cow_splits += len(cow)
        return len(cow)

    def ensure(self, slot: int, tokens: int) -> Optional[int]:
        """Grow ``slot``'s table to cover ``tokens`` positions. Returns
        the number of newly allocated blocks, or None on exhaustion (the
        caller preempts a victim and retries)."""
        need = self.blocks_needed(tokens)
        have = len(self._slot_blocks[slot])
        if need <= have:
            return 0
        fresh = self.allocator.alloc(need - have)
        if fresh is None:
            return None
        self.tables[slot, have:need] = fresh
        self._slot_blocks[slot].extend(fresh)
        self._dev_tables = None
        return len(fresh)

    def free_slot(self, slot: int) -> None:
        """Release ``slot``: decref its blocks back to the allocator,
        reset its table row to the null sentinel, free the slot."""
        self.allocator.free(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self.tables[slot] = self.null_block
        self._dev_tables = None
        self._free_slots.push(slot)

    # ``KVArena``-compatible aliases so the scheduler's retire path is
    # arena-agnostic.
    def free(self, slot: int) -> None:
        """Alias for ``free_slot`` (the slot arena's retire name)."""
        self.free_slot(slot)

    def reset_slot(self, slot: int) -> None:
        """Zero ``slot``'s constant-size (non-paged) state leaves for a
        fresh chunked admission — SSM recurrent state and enc-dec cross KV
        carry state, not masked history. Paged leaves are left alone
        (stale pages are masked by kv_len and rewritten before use)."""
        if all(self._paged_flags):
            return
        leaves, treedef = jax.tree.flatten(self.buffers)
        const = tuple(not f for f in self._paged_flags)
        new = _zero_const_leaves(leaves, jnp.int32(slot), const)
        self.buffers = jax.tree.unflatten(treedef, new)
        self._repin()

    # -- storage ---------------------------------------------------------
    def write_prefill(self, prefill_cache, slot: int) -> None:
        """Scatter a B=1 prefill cache into ``slot``'s reserved blocks
        (serving uses this only for the enc-dec admission-time encoder
        pass; lockstep/eval callers may hand in padded prefill caches).
        A padded length P may overrun the reservation; the overrun is pad
        garbage and is routed to the null block — every dropped position
        is rewritten by the decode step before first use. The scatter
        width is always ``blocks_for(P)`` (real blocks first, null-block
        padding after), so the jit trace count tracks the prefill-cache
        shapes, not per-prompt reservation sizes."""
        if self.kv_quant != "none" and self.has_paged:
            raise NotImplementedError(
                "write_prefill cannot scatter an unquantized prefill "
                "cache into int8 pages; quantized serving feeds prompts "
                "through the chunked step (quantize-on-insert), and the "
                "engine refuses the families that need this path")
        leaves = jax.tree.leaves(prefill_cache)
        phys_ids = self._slot_blocks[slot][:1]
        if self.has_paged:
            P = next(c.shape[2] for c, f in zip(leaves, self._paged_flags)
                     if f)
            nbw = self.allocator.blocks_for(P)
            phys_ids = self._slot_blocks[slot][:nbw]
            phys_ids = phys_ids + [self.null_block] * (nbw - len(phys_ids))
        phys = jnp.asarray(phys_ids, jnp.int32)
        buf_leaves, treedef = jax.tree.flatten(self.buffers)
        new = _paged_insert(buf_leaves, leaves, phys, jnp.int32(slot),
                            self._paged_flags)
        self.buffers = jax.tree.unflatten(treedef, new)
        self._repin()

    # -- byte accounting --------------------------------------------------
    def nbytes(self) -> int:
        """Total device bytes of the arena's cache storage (precomputed
        — shape-static)."""
        return self._nbytes

    def block_bytes(self) -> float:
        """Bytes of paged storage backing one physical block."""
        return self._block_bytes

    def const_bytes(self) -> float:
        """Bytes of non-paged per-slot storage (SSM states, cross KV)."""
        return self._const_bytes

    def token_bytes(self) -> float:
        """Cache bytes appended per generated token (paged leaves only;
        constant-size states are admission-time, not per-token)."""
        if not self.has_paged:
            return self.const_bytes() / max(self.num_slots, 1) / self.max_seq
        return self.block_bytes() / self.block_size

    def resident_bytes(self) -> float:
        """Bytes pinned by live sequences right now: allocated blocks at
        block granularity + per-slot constant state for used slots."""
        const_slot = self.const_bytes() / max(self.num_slots, 1)
        return self.allocator.used_blocks * self.block_bytes() \
            + self.used_slots * const_slot

    # -- speculative rollback -------------------------------------------
    def rollback(self, slot: int, start: int, count: int,
                 width: int) -> int:
        """Erase cache positions ``[start, start + count)`` of ``slot``
        after a verification step rejected them: zero the page contents
        those positions map to through the (pre-trim) block table, then
        trim the table tail — blocks wholly past the surviving prefix are
        decreffed back to the allocator and their table entries reset to
        the null sentinel, so resident-bytes accounting tracks the
        *accepted* sequence length, not the speculated one. Returns the
        number of blocks dropped from the table. ``width`` is the static
        pad width (the engine's chunk size); unused pair lanes are routed
        to the null page, whose contents are garbage by contract.

        Prefix-sharing contract: zeroing is skipped for any position
        whose block is shared (refcount > 1) or published in the prefix
        cache — siblings (and future cache hits) still read those pages,
        and a cached page's contents *are* the KV of its token chain, so
        they stay valid regardless of this slot's rejection. The tail
        trim still decrefs such blocks; they are reclaimed only when the
        last reference drops."""
        if count <= 0 or not self.has_paged:
            return 0
        bs = self.block_size
        pos = np.arange(start, start + count)
        phys = np.full((width,), self.null_block, np.int32)
        offs = np.zeros((width,), np.int32)
        blk = self.tables[slot, pos // bs]
        pc = self.prefix_cache
        zeroable = np.asarray(
            [b != self.null_block
             and self.allocator.refcounts[b] == 1
             and (pc is None or not pc.is_cached(b)) for b in blk])
        phys[:count] = np.where(zeroable, blk, self.null_block)
        offs[:count] = pos % bs
        leaves, treedef = jax.tree.flatten(self.buffers)
        new = _zero_paged_positions(leaves, jnp.asarray(phys),
                                    jnp.asarray(offs), self._paged_flags)
        self.buffers = jax.tree.unflatten(treedef, new)
        self._repin()
        keep = self.blocks_needed(start) if start else 0
        owned = self._slot_blocks[slot]
        if len(owned) <= keep:
            return 0
        tail = owned[keep:]
        self.allocator.free(tail)
        del owned[keep:]
        self.tables[slot, keep:] = self.null_block
        self._dev_tables = None
        return len(tail)


def cache_nbytes(cache) -> int:
    """Total bytes across all leaves of a cache pytree."""
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache)))
