"""Paged KV subsystem units: BlockPool invariants, paged-vs-contiguous
cache parity, the paged-attention kernel, and the prefix trie (PR 3).

Property-style allocator tests run twice: a deterministic stdlib-random
sweep that always runs, and a hypothesis version gated exactly like
``tests/test_property.py`` (the CI image may lack hypothesis).
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kv_cache import DenseKVCache, WindowKVCache
from repro.serve.paged_attention import (paged_attention_kernel,
                                         paged_attention_ref,
                                         paged_prefill_attention)
from repro.serve.paged_kv import (BlockPool, PagedDenseKVCache,
                                  PagedWindowKVCache, copy_blocks)
from repro.serve.prefix_cache import PrefixCache

# real hypothesis when installed, else the vendored fallback (see
# tests/_property_harness.py) — the sweep below always executes
from _property_harness import given, settings, st


# -------------------------------------------------------------- allocator
def test_block_pool_alloc_free_refcount():
    pool = BlockPool(8, 4)
    a = pool.alloc(3)
    b = pool.alloc(5)
    assert sorted(a + b) == list(range(8)) and pool.alloc(1) is None
    pool.incref(a)                       # shared (trie + row)
    pool.decref(a)
    assert pool.free_blocks == 0         # still referenced once
    pool.decref(a)
    assert pool.free_blocks == 3
    pool.decref(b)
    assert pool.free_blocks == 8
    with pytest.raises(AssertionError):  # double free caught
        pool.decref(b[:1])


def test_block_pool_ensure_owned_cow():
    pool = BlockPool(4, 4)
    (bid,) = pool.alloc(1)
    owned, copied = pool.ensure_owned(bid)
    assert owned == bid and not copied   # exclusive: no copy
    pool.incref([bid])                   # now shared
    owned, copied = pool.ensure_owned(bid)
    assert copied and owned != bid and pool.refcount(bid) == 1
    pool2 = BlockPool(1, 4)
    (only,) = pool2.alloc(1)
    pool2.incref([only])
    assert pool2.ensure_owned(only) is None   # exhausted -> caller preempts


def _run_alloc_trace(ops, num_blocks):
    """Replay an alloc/free/share trace; check the allocator invariants:
    no double-free, live+free partition the pool, exclusive live blocks
    never alias across owners."""
    pool = BlockPool(num_blocks, 4)
    owners = {}          # owner id -> list of block ids
    shared = []          # blocks holding an extra (trie-like) ref
    next_owner = 0
    for kind, arg in ops:
        if kind == "alloc":
            ids = pool.alloc(arg)
            if ids is not None:
                owners[next_owner] = ids
                next_owner += 1
        elif kind == "free" and owners:
            key = sorted(owners)[arg % len(owners)]
            pool.decref(owners.pop(key))
        elif kind == "share" and owners:
            key = sorted(owners)[arg % len(owners)]
            if owners[key]:
                bid = owners[key][0]
                pool.incref([bid])
                shared.append(bid)
        elif kind == "unshare" and shared:
            pool.decref([shared.pop()])
        # invariants after every op
        live = [b for ids in owners.values() for b in ids]
        assert len(live) == len(set(live)), "block aliased across live owners"
        for b in live:
            assert pool.refcount(b) >= 1
        assert pool.free_blocks + len(set(live + shared)) == num_blocks
    for ids in owners.values():
        pool.decref(ids)
    for b in shared:
        pool.decref([b])
    assert pool.free_blocks == num_blocks    # everything returns


def test_block_pool_trace_property_deterministic():
    for seed in range(20):
        rng = random.Random(seed)
        ops = [(rng.choice(["alloc", "free", "share", "unshare"]),
                rng.randrange(4)) for _ in range(60)]
        _run_alloc_trace([(k, a + 1 if k == "alloc" else a) for k, a in ops],
                         num_blocks=12)


@given(st.lists(st.tuples(
    st.sampled_from(["alloc", "free", "share", "unshare"]),
    st.integers(1, 5)), max_size=80),
    st.integers(4, 24))
@settings(max_examples=25, deadline=None)
def test_block_pool_trace_property(ops, num_blocks):
    _run_alloc_trace(ops, num_blocks)


# ---------------------------------------------------- paged cache parity
def test_paged_dense_matches_contiguous_bitwise():
    key = jax.random.PRNGKey(0)
    B, H, d, bs, ML = 2, 4, 8, 4, 32
    kv = jax.random.normal(key, (B, 10, H, d), jnp.float32)
    c = DenseKVCache.create(B, ML, H, d, jnp.float32).append(kv, kv)
    p = PagedDenseKVCache.create(B, ML, H, d, jnp.float32, block_size=bs,
                                 identity_tables=True).append(kv, kv)
    for t in range(6):
        one = jax.random.normal(jax.random.fold_in(key, t), (B, 1, H, d))
        c, p = c.append(one, one), p.append(one, one)
    gk, gv = p.gather()
    L = int(c.length[0])
    np.testing.assert_array_equal(np.asarray(c.k[:, :L]),
                                  np.asarray(gk[:, :L]))
    np.testing.assert_array_equal(np.asarray(c.v[:, :L]),
                                  np.asarray(gv[:, :L]))
    np.testing.assert_array_equal(np.asarray(c.length), np.asarray(p.length))


def test_paged_dense_n_valid_drops_pads():
    key = jax.random.PRNGKey(1)
    B, H, d = 2, 2, 4
    kv = jax.random.normal(key, (B, 8, H, d), jnp.float32)
    p = PagedDenseKVCache.create(B, 16, H, d, jnp.float32, block_size=4,
                                 identity_tables=True)
    p = p.append(kv, kv, n_valid=jnp.asarray([5, 8]))
    np.testing.assert_array_equal(np.asarray(p.length), [5, 8])
    gk, _ = p.gather()
    assert np.asarray(gk[0, 5:]).sum() == 0          # pad KV never written
    np.testing.assert_array_equal(np.asarray(gk[0, :5]),
                                  np.asarray(kv[0, :5]))


def test_paged_window_ring_matches_contiguous():
    key = jax.random.PRNGKey(2)
    B, H, d, W = 2, 2, 8, 8
    wc = WindowKVCache.create(B, W, H, d, jnp.float32)
    wp = PagedWindowKVCache.create(B, W, H, d, jnp.float32, block_size=4,
                                   identity_tables=True)
    for t in range(13):                              # wraps the ring
        one = jax.random.normal(jax.random.fold_in(key, t), (B, H, d))
        wc, wp = wc.append_one(one, one), wp.append_one(one, one)
    gk, gv = wp.gather()
    np.testing.assert_array_equal(np.asarray(wc.k), np.asarray(gk))
    np.testing.assert_array_equal(np.asarray(wc.positions),
                                  np.asarray(wp.positions))

    # multi-token (prefill) append == token-by-token ring arithmetic
    kvw = jax.random.normal(jax.random.fold_in(key, 99), (B, 13, H, d))
    wp2 = PagedWindowKVCache.create(B, W, H, d, jnp.float32, block_size=4,
                                    identity_tables=True).append(kvw, kvw)
    wc2 = WindowKVCache.create(B, W, H, d, jnp.float32)
    for t in range(13):
        wc2 = wc2.append_one(kvw[:, t], kvw[:, t])
    np.testing.assert_array_equal(np.asarray(wc2.k),
                                  np.asarray(wp2.gather()[0]))
    np.testing.assert_array_equal(np.asarray(wc2.positions),
                                  np.asarray(wp2.positions))


def test_unallocated_rows_never_corrupt_other_blocks():
    """Writes through a -1 block table are dropped, not clobbered."""
    B, H, d = 2, 2, 4
    p = PagedDenseKVCache.create(B, 16, H, d, jnp.float32, block_size=4,
                                 identity_tables=True)
    # row 1 has no blocks
    p = p._replace(block_table=p.block_table.at[1].set(-1))
    kv = jnp.ones((B, 6, H, d), jnp.float32)
    p = p.append(kv, kv)
    assert np.asarray(p.k[4:]).sum() == 0    # row-1 region untouched
    gk, _ = p.gather()
    np.testing.assert_array_equal(np.asarray(gk[0, :6]), np.asarray(kv[0]))


def test_copy_blocks_device_cow():
    p = PagedDenseKVCache.create(1, 16, 2, 4, jnp.float32, block_size=4,
                                 identity_tables=True)
    kv = jnp.arange(1 * 6 * 2 * 4, dtype=jnp.float32).reshape(1, 6, 2, 4)
    p = p.append(kv, kv)
    p2 = copy_blocks(p, jnp.asarray([0]), jnp.asarray([3]))
    np.testing.assert_array_equal(np.asarray(p2.k[3]), np.asarray(p2.k[0]))
    np.testing.assert_array_equal(np.asarray(p2.k[1]), np.asarray(p.k[1]))


# ------------------------------------------------------- paged attention
def test_paged_attention_ref_matches_contiguous_decode_math():
    """The gather reference reproduces the contiguous decode einsum."""
    key = jax.random.PRNGKey(3)
    B, Hq, Hkv, d, bs, ML = 2, 4, 2, 8, 4, 16
    kv = jax.random.normal(key, (B, 9, Hkv, d), jnp.float32)
    p = PagedDenseKVCache.create(B, ML, Hkv, d, jnp.float32, block_size=bs,
                                 identity_tables=True).append(kv, kv)
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, Hq, d))
    out = paged_attention_ref(q, p.k, p.v, p.block_table, p.length, d ** -0.5)

    # oracle: dense masked softmax over the first `length` positions
    kk = kv.transpose(0, 2, 1, 3)                      # (B, Hkv, T, d)
    qg = q.reshape(B, Hkv, Hq // Hkv, d)
    s = jnp.einsum("bgrd,bgkd->bgrk", qg, kk) * (d ** -0.5)
    pr = jax.nn.softmax(s, axis=-1)
    want = jnp.einsum("bgrk,bgkd->bgrd", pr, kv.transpose(0, 2, 1, 3))
    np.testing.assert_allclose(np.asarray(out).reshape(B, Hq, d),
                               np.asarray(want).reshape(B, Hq, d),
                               atol=1e-5, rtol=1e-5)


def test_paged_attention_kernel_matches_ref():
    """The Pallas kernel (interpret mode on CPU) == the gather reference,
    including rows at different lengths and unallocated -1 table tails."""
    key = jax.random.PRNGKey(4)
    B, Hq, Hkv, bs, nb = 2, 4, 2, 4, 4
    d = 128                                           # lane-aligned
    N = B * nb
    k_pool = jax.random.normal(key, (N, bs, Hkv, d), jnp.float32)
    v_pool = jax.random.normal(jax.random.fold_in(key, 1),
                               (N, bs, Hkv, d), jnp.float32)
    bt = jnp.arange(N, dtype=jnp.int32).reshape(B, nb)
    bt = bt.at[0, 2:].set(-1)                         # row 0: 2 blocks only
    lengths = jnp.asarray([6, 15], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, Hq, d),
                          jnp.float32)
    ref = paged_attention_ref(q, k_pool, v_pool, bt, lengths, d ** -0.5)
    ker = paged_attention_kernel(q, k_pool, v_pool, bt, lengths,
                                 scale=d ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               atol=1e-5, rtol=1e-5)


def test_paged_prefill_kernel_matches_ref():
    """The packed-prefill Pallas kernel (interpret mode on CPU) == the
    gather reference: GQA, segments continuing a cached past, a segment
    spanning several KV blocks, and an inactive (row -1) segment slot."""
    key = jax.random.PRNGKey(5)
    B, Hq, Hkv, bs, nb, d = 3, 4, 2, 4, 6, 16
    N = B * nb
    k_pool = jax.random.normal(key, (N, bs, Hkv, d), jnp.float32)
    v_pool = jax.random.normal(jax.random.fold_in(key, 1),
                               (N, bs, Hkv, d), jnp.float32)
    bt = jnp.asarray(np.random.default_rng(0).permutation(N).reshape(B, nb),
                     jnp.int32)
    cache = PagedDenseKVCache(k_pool, v_pool, bt,
                              jnp.zeros((B,), jnp.int32))
    # segments of 7, 3 and 9 tokens after 2, 13 and 0 cached tokens, then
    # an inactive slot; 19 real tokens and 5 of padding
    total = 24
    cu = jnp.asarray([0, 7, 10, 19, 19], jnp.int32)
    rows = jnp.asarray([2, 0, 1, -1], jnp.int32)
    past_lens = jnp.asarray([2, 13, 0, 0], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 2), (total, Hq, d),
                          jnp.float32)
    want = paged_prefill_attention(q, cache, cu, rows, past_lens,
                                   scale=d ** -0.5, impl="ref")
    got = paged_prefill_attention(q, cache, cu, rows, past_lens,
                                  scale=d ** -0.5, impl="kernel",
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------ prefix trie
def test_prefix_trie_insert_lookup_refcounts():
    pool = BlockPool(16, 4)
    trie = PrefixCache(4)
    toks = list(range(100, 112))                       # 3 full blocks
    ids = pool.alloc(3)
    chain, tip = trie.insert(toks, ids, pool)
    assert chain == ids and tip is not None
    assert all(pool.refcount(b) == 2 for b in ids)     # row + trie
    trie.attach_snapshot(tip, {"state": "s3"})

    # full-block prefix of a longer prompt matches; snapshot gating works
    node, depth = trie.lookup(toks + [7, 8], need_snapshot=True)
    assert node is tip and depth == 12
    node2, depth2 = trie.lookup(toks[:9] + [5], need_snapshot=False)
    assert depth2 == 8 and node2.snapshot is None
    assert trie.lookup(toks[:9] + [5], need_snapshot=True) == (None, 0)
    # the last token never matches (a hit must leave >= 1 token to prefill)
    assert trie.lookup(toks[:4], need_snapshot=False) == (None, 0)

    got = trie.acquire(node, pool)
    assert got == ids and all(pool.refcount(b) == 3 for b in ids)
    pool.decref(got)

    # shared insert: an identical prefix computed elsewhere keeps trie ids
    ids_b = pool.alloc(3)
    chain_b, _ = trie.insert(toks, ids_b, pool)
    assert chain_b == ids                              # trie authoritative
    pool.decref(ids_b)

    # release the row refs; LRU eviction drains leaf-first
    pool.decref(ids)
    free0 = pool.free_blocks
    assert trie.evict_lru(pool)                        # deepest leaf
    assert pool.free_blocks == free0 + 1
    while trie.evict_lru(pool):
        pass
    assert trie.n_nodes == 0 and pool.free_blocks == 16


@settings(max_examples=20, deadline=None)
@given(ops=st.lists(st.integers(0, 9), min_size=1, max_size=40),
       seed=st.integers(0, 2 ** 16))
def test_prefix_trie_node_count_matches_walk(ops, seed):
    """``n_nodes`` is a count kept by ``insert`` and ``evict_lru``: after
    any sequence of inserts (shared and fresh prefixes of random depth)
    and LRU evictions it equals a full walk of the trie, and the
    ``prefix.nodes`` gauge reads the same."""
    from repro import obs
    obs.registry().reset()
    rng = random.Random(seed)
    pool = BlockPool(64, 2)
    trie = PrefixCache(2)
    for op in ops:
        if op < 6:
            n = rng.randint(1, 4)
            toks = [rng.randint(0, 2) for _ in range(2 * n)]
            ids = pool.alloc(n)
            if ids is None:
                continue
            trie.insert(toks, ids, pool)
            pool.decref(ids)                # the row retires
        else:
            trie.evict_lru(pool)
        assert trie.n_nodes == sum(1 for _ in trie._iter_nodes())
        assert obs.registry().gauge("prefix.nodes").value == trie.n_nodes
