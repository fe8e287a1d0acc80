"""Block-paged KV caches: pool tensors, block tables, and the host allocator.

The contiguous caches in ``repro.core.kv_cache`` reserve a
``(B, max_len, H, d)`` slab per batch slot, so server capacity is bound by
the WORST-CASE prompt even when most requests are short — the fragmentation
problem paged attention solves.  Here KV lives in fixed-size *blocks* of
``block_size`` tokens inside a shared pool tensor ``(N, block_size, H, d)``;
a per-row *block table* maps logical block ``pos // block_size`` to a
physical block id.  Rows own only the blocks their tokens actually fill, and
identical prompt prefixes can map to the SAME physical blocks
(``repro.serve.prefix_cache``).

Two layers, deliberately separated:

  * ``BlockPool`` — the host-side allocator: free list, per-block refcounts
    (shared prefix blocks), copy-on-write ``ensure_owned``.  Pure Python;
    never traced.
  * ``PagedDenseKVCache`` / ``PagedWindowKVCache`` — fixed-shape device
    pytrees (jit/pjit friendly).  Their ``append`` / ``gather`` reproduce the
    contiguous ``DenseKVCache`` / ``WindowKVCache`` semantics bit-for-bit:
    ``gather()`` of a paged cache equals the contiguous cache's ``k``/``v``
    arrays at every valid position, so the decode math can be shared between
    the two layouts (``repro.core.attention``) and paged decode is
    numerically exact.

MoSA caches stay UNPAGED on purpose: they are already O(k) per head,
independent of context length — there is no quadratic slab to page (DESIGN
§7).  The same applies to SSM/xLSTM recurrent states (O(1)).

Writes to unallocated rows are dropped, not clobbered: block id ``< 0``
(no block) is remapped past the pool end and scattered with ``mode="drop"``,
so an inactive batch row or a right-padded prefill tail can never corrupt
another row's blocks.
"""

from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.obs.metrics import registry as _obs_registry


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    """Static paged-cache geometry.

    ``num_blocks == 0`` auto-sizes the pool to the contiguous worst case
    (``batch * ceil(max_len / block_size)`` for dense, ``batch * W /
    block_size`` for window caches) so ``paged=True`` is a drop-in; the
    serving win comes from passing a TIGHTER budget and letting the
    ``Scheduler`` admit block-granularly.
    """

    block_size: int = 16
    num_blocks: int = 0          # dense-group pool size (0 = worst case)
    num_window_blocks: int = 0   # window-group pool size (0 = worst case)


# --------------------------------------------------------------- allocator
class BlockPool:
    """Host-side free-list allocator with refcounted blocks.

    Refcounts implement prefix sharing: a block referenced by the prefix
    trie AND by live requests has ``ref > 1``; freeing decrements and the
    block returns to the free list only at zero.  ``ensure_owned`` is the
    copy-on-write primitive: a caller about to MUTATE a block (the window
    ring overwrites slots in place) gets a fresh private id back — plus a
    flag telling it to copy the payload — whenever the block is shared.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 name: str = "blocks"):
        assert num_blocks >= 0 and block_size > 0
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.name = name             # metrics namespace: pool.<name>.*
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._ref = [0] * num_blocks

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def live_blocks(self) -> int:
        return self.num_blocks - len(self._free)

    def refcount(self, bid: int) -> int:
        return self._ref[bid]

    def _publish(self) -> None:
        """Mirror occupancy into the obs registry (DESIGN §11): utilization
        gauges plus a live-blocks high-water mark.  One enabled check, then
        plain gauge sets — the allocator stays pure Python and untraced."""
        reg = _obs_registry()
        if not reg.enabled:
            return
        live = self.num_blocks - len(self._free)
        reg.set(f"pool.{self.name}.free_blocks", len(self._free))
        reg.set(f"pool.{self.name}.live_blocks", live)
        reg.set_max(f"pool.{self.name}.live_high_water", live)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` fresh blocks at ref 1, or None (all-or-nothing)."""
        if n < 0 or n > len(self._free):
            _obs_registry().inc(f"pool.{self.name}.alloc_failures")
            return None
        ids = [self._free.pop() for _ in range(n)]
        for b in ids:
            self._ref[b] = 1
        self._publish()
        return ids

    def incref(self, ids: Sequence[int]) -> None:
        hi = 0
        for b in ids:
            assert self._ref[b] > 0, f"incref of free block {b}"
            self._ref[b] += 1
            if self._ref[b] > hi:
                hi = self._ref[b]
        if ids:
            _obs_registry().set_max(
                f"pool.{self.name}.refcount_high_water", hi)

    def decref(self, ids: Sequence[int]) -> None:
        for b in ids:
            assert self._ref[b] > 0, f"double free of block {b}"
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(b)
        if ids:
            self._publish()

    def ensure_owned(self, bid: int) -> Optional[tuple]:
        """(owned_id, needs_copy).  Copy-on-write: shared blocks come back as
        a fresh allocation (caller copies ``bid`` -> ``owned_id`` on device
        and swaps its table entry); exclusive blocks come back unchanged.
        None if the pool is exhausted (caller preempts)."""
        assert self._ref[bid] > 0, f"ensure_owned of free block {bid}"
        if self._ref[bid] == 1:
            return bid, False
        got = self.alloc(1)
        if got is None:
            return None
        self.decref([bid])
        _obs_registry().inc(f"pool.{self.name}.cow_copies")
        return got[0], True


# ------------------------------------------------------------ device caches
def _blocks_for(tokens: int, block_size: int) -> int:
    return -(-tokens // block_size)


def _pool_scatter(pool, blk, off, vals):
    """Scatter ``vals`` at ``(blk, off)``; ``blk < 0`` (unallocated) drops.

    pool: (N, bs, ...); blk/off: (...idx) int32; vals: (...idx, ...).
    """
    n = pool.shape[0]
    blk = jnp.where(blk < 0, n, blk)   # out of bounds -> mode="drop"
    return pool.at[blk, off].set(vals.astype(pool.dtype), mode="drop")


class PagedDenseKVCache(NamedTuple):
    """Paged counterpart of ``DenseKVCache``.

    ``gather()`` reconstructs the contiguous ``(B, S, Hkv, d)`` layout
    (``S = max_blocks * block_size``); positions ``>= length`` hold stale or
    zero payload exactly like the contiguous cache's unwritten tail, and the
    decode math masks them identically — see ``repro.core.attention``.
    """

    k: jnp.ndarray            # (N, bs, Hkv, d) physical pool
    v: jnp.ndarray            # (N, bs, Hkv, d)
    block_table: jnp.ndarray  # (B, max_blocks) int32; -1 = unallocated
    length: jnp.ndarray       # (B,) int32 — tokens filled

    @classmethod
    def create(cls, batch, max_len, n_kv_heads, d_head, dtype=jnp.bfloat16,
               *, block_size: int = 16, num_blocks: int = 0,
               identity_tables: bool = False):
        nb = _blocks_for(max_len, block_size)
        n = num_blocks or batch * nb
        z = jnp.zeros((n, block_size, n_kv_heads, d_head), dtype)
        if identity_tables:
            # row r owns blocks [r*nb, (r+1)*nb) — the no-allocator layout
            # Server.generate uses for whole-batch prefill+decode.
            assert n >= batch * nb, (n, batch, nb)
            table = (jnp.arange(batch * nb, dtype=jnp.int32)
                     .reshape(batch, nb))
        else:
            table = jnp.full((batch, nb), -1, jnp.int32)
        return cls(z, z, table, jnp.zeros((batch,), jnp.int32))

    @property
    def block_size(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.block_table.shape[1] * self.k.shape[1]

    def append(self, k_new, v_new, n_valid=None):
        """k_new/v_new: (B, Tnew, Hkv, d); same semantics as
        ``DenseKVCache.append`` with per-row lengths throughout.

        ``n_valid`` (B,) — number of REAL tokens per row (right-padded
        prefill): writes past ``length + n_valid`` are dropped and ``length``
        advances by ``n_valid`` instead of ``Tnew``, so pad KV never lands in
        the pool (the masked-prefill fix, DESIGN §7).
        """
        B, Tnew = k_new.shape[:2]
        bs = self.block_size
        pos = self.length[:, None] + jnp.arange(Tnew, dtype=jnp.int32)  # (B,T)
        blk = jnp.take_along_axis(
            self.block_table, jnp.clip(pos // bs, 0,
                                       self.block_table.shape[1] - 1), axis=1)
        blk = jnp.where(pos // bs < self.block_table.shape[1], blk, -1)
        if n_valid is not None:
            nv = jnp.asarray(n_valid, jnp.int32)
            blk = jnp.where(jnp.arange(Tnew) < nv[:, None], blk, -1)
            adv = nv
        else:
            adv = jnp.full((B,), Tnew, jnp.int32)
        off = pos % bs
        k = _pool_scatter(self.k, blk, off, k_new)
        v = _pool_scatter(self.v, blk, off, v_new)
        return PagedDenseKVCache(k, v, self.block_table, self.length + adv)

    def append_packed(self, k_new, v_new, row_of_tok, pos_of_tok):
        """Packed-varlen append: scatter a flattened multi-row token stream.

        k_new/v_new: (total, Hkv, d); row_of_tok: (total,) int32 batch row
        per token (-1 = padding, dropped); pos_of_tok: (total,) int32 the
        token's absolute position in its row's KV space.  Each row's
        ``length`` advances by the number of its tokens in the stream —
        the packed counterpart of ``append`` with ``n_valid`` masking, and
        the write primitive of chunked prefill (DESIGN §9).
        """
        bs = self.block_size
        B, nbt = self.block_table.shape
        row = jnp.asarray(row_of_tok, jnp.int32)
        pos = jnp.asarray(pos_of_tok, jnp.int32)
        rowc = jnp.clip(row, 0, B - 1)
        blk = self.block_table[rowc, jnp.clip(pos // bs, 0, nbt - 1)]
        blk = jnp.where((row < 0) | (pos // bs >= nbt), -1, blk)
        off = pos % bs
        k = _pool_scatter(self.k, blk, off, k_new)
        v = _pool_scatter(self.v, blk, off, v_new)
        counts = jnp.zeros((B,), jnp.int32).at[
            jnp.where(row < 0, B, row)].add(1, mode="drop")
        return PagedDenseKVCache(k, v, self.block_table,
                                 self.length + counts)

    def gather(self):
        """(k, v) in the contiguous (B, S, Hkv, d) layout."""
        bt = jnp.clip(self.block_table, 0)    # -1 -> junk, masked by length
        B, nb = bt.shape
        bs = self.block_size

        def one(table):                        # vmap keeps B a batching dim
            kk = self.k[table].reshape(nb * bs, *self.k.shape[2:])
            vv = self.v[table].reshape(nb * bs, *self.v.shape[2:])
            return kk, vv

        return jax.vmap(one)(bt)


class PagedWindowKVCache(NamedTuple):
    """Paged counterpart of ``WindowKVCache`` (ring of the last W tokens).

    The ring arithmetic is IDENTICAL to the contiguous cache — token at
    position ``p`` lives at slot ``p % W``, physical location
    ``pool[table[b, slot // bs], slot % bs]`` — so ``gather()`` returns the
    exact ``(B, W, Hkv, d)`` ring layout ``WindowKVCache.k`` holds.
    ``W = positions.shape[1]`` must be a multiple of ``block_size``.

    Unlike dense blocks (append-only, immutable once full), ring blocks are
    OVERWRITTEN in place as the window slides — a row holding blocks shared
    through the prefix cache must ``BlockPool.ensure_owned`` them before its
    next append (the scheduler's copy-on-write step).
    """

    k: jnp.ndarray            # (N, bs, Hkv, d)
    v: jnp.ndarray
    block_table: jnp.ndarray  # (B, W // bs) int32
    positions: jnp.ndarray    # (B, W) int32 original positions (-1 = empty)
    length: jnp.ndarray       # (B,) total tokens seen

    @classmethod
    def create(cls, batch, window, n_kv_heads, d_head, dtype=jnp.bfloat16,
               *, block_size: int = 16, num_blocks: int = 0,
               identity_tables: bool = False):
        assert window % block_size == 0, (
            f"window {window} must be a multiple of block_size {block_size} "
            f"(ring slots map to blocks by slot // block_size)")
        wb = window // block_size
        n = num_blocks or batch * wb
        z = jnp.zeros((n, block_size, n_kv_heads, d_head), dtype)
        if identity_tables:
            assert n >= batch * wb, (n, batch, wb)
            table = (jnp.arange(batch * wb, dtype=jnp.int32)
                     .reshape(batch, wb))
        else:
            table = jnp.full((batch, wb), -1, jnp.int32)
        pos = jnp.full((batch, window), -1, jnp.int32)
        return cls(z, z, table, pos, jnp.zeros((batch,), jnp.int32))

    @property
    def block_size(self) -> int:
        return self.k.shape[1]

    @property
    def window(self) -> int:
        return self.positions.shape[1]

    def _write(self, k_vals, v_vals, pos, drop):
        """Scatter tokens at ring slots ``pos % W``; ``drop`` masks writes."""
        W, bs = self.window, self.block_size
        slot = pos % W
        blk = jnp.take_along_axis(self.block_table, slot // bs, axis=1)
        blk = jnp.where(drop, -1, blk)
        off = slot % bs
        k = _pool_scatter(self.k, blk, off, k_vals)
        v = _pool_scatter(self.v, blk, off, v_vals)
        positions = self.positions.at[
            jnp.arange(pos.shape[0])[:, None],
            jnp.where(drop, W, slot)].set(pos, mode="drop")
        return k, v, positions

    def append_one(self, k_new, v_new):
        """k_new/v_new: (B, Hkv, d) — single decode step, per-row slots."""
        pos = self.length[:, None].astype(jnp.int32)            # (B, 1)
        k, v, positions = self._write(k_new[:, None], v_new[:, None], pos,
                                      jnp.zeros_like(pos, bool))
        return PagedWindowKVCache(k, v, self.block_table, positions,
                                  self.length + 1)

    def append(self, k_new, v_new, n_valid=None):
        """Multi-token (prefill) append: keep the last ``min(W, n)`` real
        tokens per row, drop right-pad tails and tokens a later token in the
        SAME append would overwrite (duplicate ring slots must scatter
        uniquely).  k_new/v_new: (B, T, Hkv, d)."""
        B, T = k_new.shape[:2]
        nv = (jnp.full((B,), T, jnp.int32) if n_valid is None
              else jnp.asarray(n_valid, jnp.int32))
        t = jnp.arange(T, dtype=jnp.int32)[None, :]              # (1, T)
        pos = self.length[:, None] + t                           # (B, T)
        drop = (t >= nv[:, None]) | (t < nv[:, None] - self.window)
        k, v, positions = self._write(k_new, v_new, pos, drop)
        return PagedWindowKVCache(k, v, self.block_table, positions,
                                  self.length + nv)

    def append_packed(self, k_new, v_new, row_of_tok, pos_of_tok):
        """Packed-varlen ring append (see ``PagedDenseKVCache.append_packed``).

        Tokens scatter to ring slot ``pos % W``.  A token is dropped when a
        LATER token of the same row in this stream maps to the same slot
        (only the last W tokens per row survive — duplicate ring slots must
        scatter uniquely, as in ``append``).
        """
        W, bs = self.window, self.block_size
        B = self.block_table.shape[0]
        row = jnp.asarray(row_of_tok, jnp.int32)
        pos = jnp.asarray(pos_of_tok, jnp.int32)
        rowc = jnp.clip(row, 0, B - 1)
        rowd = jnp.where(row < 0, B, row)                 # drop index
        # per-row deepest position in THIS stream; tokens more than W-1
        # behind it would be overwritten within the same scatter -> drop
        deepest = jnp.full((B,), -1, jnp.int32).at[rowd].max(
            pos, mode="drop")
        drop = (row < 0) | (deepest[rowc] - pos >= W)
        slot = pos % W
        blk = self.block_table[rowc, slot // bs]
        blk = jnp.where(drop, -1, blk)
        k = _pool_scatter(self.k, blk, slot % bs, k_new)
        v = _pool_scatter(self.v, blk, slot % bs, v_new)
        positions = self.positions.at[
            jnp.where(drop, B, rowc), slot].set(pos, mode="drop")
        counts = jnp.zeros((B,), jnp.int32).at[rowd].add(1, mode="drop")
        return PagedWindowKVCache(k, v, self.block_table, positions,
                                  self.length + counts)

    def gather(self):
        """(k, v) in the contiguous ring (B, W, Hkv, d) layout."""
        bt = jnp.clip(self.block_table, 0)
        B, wb = bt.shape
        bs = self.block_size

        def one(table):
            kk = self.k[table].reshape(wb * bs, *self.k.shape[2:])
            vv = self.v[table].reshape(wb * bs, *self.v.shape[2:])
            return kk, vv

        return jax.vmap(one)(bt)


PAGED_CACHE_TYPES = (PagedDenseKVCache, PagedWindowKVCache)

# Sharding registration (CACHE_AXES, DESIGN §6/§7): pool dim 0 is the
# PHYSICAL BLOCK dim, shared by every batch row (any row's table may point
# anywhere in the pool), so it stays replicated over the data-parallel axes;
# the head dim head-shards over ``model`` exactly like the contiguous
# caches — gather and the paged kernel keep heads a batching dim, so a
# tp-sharded pool never relayouts during decode.  Block tables and
# positions are per-row metadata and follow the batch axes.
from repro.dist.sharding import register_cache_axes  # noqa: E402

register_cache_axes(PagedDenseKVCache, {
    "k": (None, None, "kv_heads", None),
    "v": (None, None, "kv_heads", None),
    "block_table": ("batch", None),
    "length": ("batch",),
})
register_cache_axes(PagedWindowKVCache, {
    "k": (None, None, "kv_heads", None),
    "v": (None, None, "kv_heads", None),
    "block_table": ("batch", None),
    "positions": ("batch", None),
    "length": ("batch",),
})

# Fields that live in POOL space (shared by every row) vs ROW space (one
# entry per batch row).  Row-granular ops — the slot write of continuous
# batching, snapshot/restore — must slice/update only the row fields and
# pass pools through whole.
POOL_FIELDS = {"k", "v"}


def copy_blocks(cache, src, dst):
    """Copy pool blocks ``src -> dst`` (both (n,) int32) in one paged cache —
    the device half of copy-on-write (``BlockPool.ensure_owned`` is the host
    half)."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return cache._replace(k=cache.k.at[dst].set(cache.k[src]),
                          v=cache.v.at[dst].set(cache.v[src]))
