"""Paged-attention decode: block-table indirect loads + online softmax.

Decode attention over a ``PagedDenseKVCache``: queries are a single token per
row, keys/values live in pool blocks addressed through the row's block table.
Two implementations share the mask/scale conventions of
``repro.core.attention.MultiHeadAttention.decode_step`` (NEG_INF where-mask,
fp32 running max/denom), so both are numerically exact against the
contiguous decode path:

  * ``paged_attention_ref``    — gather the row's blocks back to the
    contiguous ``(B, S, Hkv, d)`` layout and run the identical einsum; the
    CPU/reference path and the oracle the kernel is tested against.
  * ``paged_attention_kernel`` — Pallas TPU kernel: grid ``(B, num_blocks)``,
    the block table and per-row lengths ride in scalar-prefetch SMEM so each
    grid step DMAs exactly one physical block ``pool[table[b, i]]`` into
    VMEM (the indirect load), with flash-style online softmax carried in
    VMEM scratch across the block-grid dimension.  No gather buffer is ever
    materialized.

``paged_attention_decode`` is the public dispatcher: the kernel where
Pallas lowers natively, the gather reference where kernels would only be
interpreted (``repro.kernels.interpret_default`` — the gather is faster than
an interpreted kernel and bit-identical to the contiguous decode path).  The
windowed ring cache always takes the gather path — its KV is bounded by W,
so there is no quadratic gather to avoid (DESIGN §7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default
from repro.serve.paged_kv import PagedDenseKVCache

NEG_INF = -1e30
LANE = 128


# ---------------------------------------------------------------- reference
def paged_attention_ref(q, k_pool, v_pool, block_table, lengths, scale):
    """q: (B, Hq, d); pools (N, bs, Hkv, d); block_table (B, nb);
    lengths (B,).  Returns (B, Hq, d) in q.dtype.

    Exactly ``MultiHeadAttention.decode_step``'s cache attention on the
    gathered layout: all positions ``< length`` attend (decode is causal by
    construction — every pooled token precedes the query)."""
    B, Hq, d = q.shape
    nb, bs = block_table.shape[1], k_pool.shape[1]
    Hkv = k_pool.shape[2]
    R = Hq // Hkv
    S = nb * bs

    bt = jnp.clip(block_table, 0)
    kk = jax.vmap(lambda t: k_pool[t].reshape(S, Hkv, d))(bt)   # (B,S,Hkv,d)
    vv = jax.vmap(lambda t: v_pool[t].reshape(S, Hkv, d))(bt)

    qg = q.reshape(B, Hkv, R, 1, d).astype(jnp.float32)
    s = jnp.einsum("bgrqd,bsgd->bgrqs", qg, kk.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    k_pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    ok = (k_pos < lengths[:, None])[:, None, None, None, :]
    s = jnp.where(ok, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    out = jnp.einsum("bgrqs,bsgd->bgrqd", p, vv.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(p.sum(-1), 1e-30)[..., None]
    return out.reshape(B, Hq, d).astype(q.dtype)


# ------------------------------------------------------------------- kernel
def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, bs: int, scale: float):
    """Grid (B, nb).  bt/len are scalar-prefetch SMEM; k/v blocks arrive
    already indirected by the index map (``pool[bt[b, i]]``)."""
    b, i = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    Hq, d = q_ref.shape[1], q_ref.shape[2]
    Hkv = k_ref.shape[2]
    R = Hq // Hkv
    length = len_ref[b]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * bs < length)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale                # (Hq, d)
        k = k_ref[0].astype(jnp.float32)                        # (bs, Hkv, d)
        v = v_ref[0].astype(jnp.float32)
        qg = q.reshape(Hkv, R, d)
        kg = k.transpose(1, 0, 2)                               # (Hkv, bs, d)
        vg = v.transpose(1, 0, 2)
        s = jax.lax.dot_general(qg, kg, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (Hkv, R, bs), 2)
        s = jnp.where(pos < length, s, NEG_INF)                 # (Hkv, R, bs)

        m_prev = m_ref[:, :1].reshape(Hkv, R)
        l_prev = l_ref[:, :1].reshape(Hkv, R)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(pos < length, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1)
        acc = acc_ref[...].reshape(Hkv, R, d)
        acc = acc * corr[..., None] + jax.lax.dot_general(
            p, vg, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(
            m_new.reshape(Hq, 1), m_ref.shape).astype(m_ref.dtype)
        l_ref[...] = jnp.broadcast_to(
            l_new.reshape(Hq, 1), l_ref.shape).astype(l_ref.dtype)
        acc_ref[...] = acc.reshape(Hq, d)

    @pl.when(i == nb - 1)
    def _finish():
        l = l_ref[:, :1]                                        # (Hq, 1)
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention_kernel(q, k_pool, v_pool, block_table, lengths, *,
                           scale: float, interpret: bool = False):
    """Pallas paged decode.  q: (B, Hq, d) with d a multiple of 128 (the
    wrapper pads); pools (N, bs, Hkv, d); block_table (B, nb); lengths (B,).
    """
    B, Hq, d = q.shape
    nb = block_table.shape[1]
    bs = k_pool.shape[1]
    Hkv = k_pool.shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # block_table, lengths
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, Hq, d), lambda b, i, bt, ln: (b, 0, 0)),
            # THE indirect load: the i-th logical block of row b is DMA'd
            # from physical block bt[b, i] (clamped; unallocated blocks are
            # masked out by `pos < length` in the kernel body).
            pl.BlockSpec((1, bs, Hkv, d),
                         lambda b, i, bt, ln: (jnp.maximum(bt[b, i], 0),
                                               0, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, d),
                         lambda b, i, bt, ln: (jnp.maximum(bt[b, i], 0),
                                               0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, d), lambda b, i, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, LANE), jnp.float32),   # running max (replicated)
            pltpu.VMEM((Hq, LANE), jnp.float32),   # running denom
            pltpu.VMEM((Hq, d), jnp.float32),      # output accumulator
        ],
    )
    kernel = functools.partial(_paged_kernel, bs=bs, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, d), q.dtype),
        interpret=interpret,
    )(block_table, lengths, q, k_pool, v_pool)


def _pad_lane(x):
    pad = (-x.shape[-1]) % LANE
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[-1] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------- dispatch
def paged_attention_decode(q, cache: PagedDenseKVCache, *, scale: float,
                           impl: str | None = None,
                           interpret: bool | None = None):
    """Decode attention of one token per row over a paged dense cache.

    q: (B, Hq, d).  ``impl``: ``"kernel"`` | ``"ref"`` | None (the kernel
    where Pallas lowers natively, the gather ref elsewhere — see the module
    docstring).
    """
    if impl is None:
        impl = "ref" if interpret_default() else "kernel"
    if impl == "ref":
        return paged_attention_ref(q, cache.k, cache.v, cache.block_table,
                                   cache.length, scale)
    interpret = interpret_default() if interpret is None else interpret
    d = q.shape[-1]
    out = paged_attention_kernel(
        _pad_lane(q), _pad_lane(cache.k), _pad_lane(cache.v),
        cache.block_table, cache.length, scale=scale, interpret=interpret)
    return out[..., :d]


# ------------------------------------------------- packed varlen prefill
def paged_prefill_attention_ref(q, k_pool, v_pool, block_table, row_of_tok,
                                pos_in_kv, scale):
    """Packed ragged prefill attention over paged pools (oracle + CPU path).

    q:          (total, Hq, d) — flattened chunk queries of N segments
    pools:      (P, bs, Hkv, d); block_table: (B, nb)
    row_of_tok: (total,) int32 — the batch row whose KV each token reads
                (-1 = padding token -> zero output)
    pos_in_kv:  (total,) int32 — the token's own absolute position in that
                row's KV space (past_len + local offset); it attends every
                key at position <= pos_in_kv (causal over past + chunk).
    Returns (total, Hq, d) in q.dtype.

    The chunk's own K/V must already be appended to the pools (the caller
    appends before attending, mirroring ``_prefill_dense_paged``).
    """
    total, Hq, d = q.shape
    nb, bs = block_table.shape[1], k_pool.shape[1]
    Hkv = k_pool.shape[2]
    R = Hq // Hkv
    S = nb * bs

    bt = jnp.clip(block_table, 0)
    kk = jax.vmap(lambda t: k_pool[t].reshape(S, Hkv, d))(bt)   # (B,S,Hkv,d)
    vv = jax.vmap(lambda t: v_pool[t].reshape(S, Hkv, d))(bt)
    row = jnp.maximum(row_of_tok, 0)
    kt = kk[row]                                                # (T,S,Hkv,d)
    vt = vv[row]

    qg = q.reshape(total, Hkv, R, d).astype(jnp.float32)
    s = jnp.einsum("tgrd,tsgd->tgrs", qg, kt.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(S)
    ok = (k_pos[None, :] <= pos_in_kv[:, None]) \
        & (row_of_tok >= 0)[:, None]                            # (T, S)
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(ok[:, None, None, :], p, 0.0)
    out = jnp.einsum("tgrs,tsgd->tgrd", p, vt.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(p.sum(-1), 1e-30)[..., None]
    return out.reshape(total, Hq, d).astype(q.dtype)


def _paged_prefill_kernel(row_ref, bt_ref, kvlen_ref, q_ref, pos_ref, k_ref,
                          v_ref, o_ref, m_ref, l_ref, acc_ref, *, bs: int,
                          scale: float):
    """Grid (N, nb) — one segment per n, all query heads at once, the row's
    paged KV streamed block-by-block along i with the online-softmax carry
    in VMEM scratch (same discipline as ``_paged_kernel``).

    row_ref / bt_ref / kvlen_ref ride in scalar-prefetch SMEM: the i-th KV
    block of segment n is DMA'd from physical block ``bt[row[n], i]`` by the
    index map before the body runs, and blocks at or past the segment's KV
    length ``kvlen[n]`` skip their compute.  Blocks: q / o (Hq, C, d);
    pos (C, 1) — the per-query absolute KV position (-1 = padding query);
    k / v (bs, Hkv, d).
    """
    n, i = pl.program_id(0), pl.program_id(1)
    Hq, C, d = q_ref.shape
    Hkv = k_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * bs < kvlen_ref[n])
    def _block():
        q = q_ref[...].astype(jnp.float32) * scale              # (Hq, C, d)
        k = k_ref[...].astype(jnp.float32).transpose(1, 0, 2)   # (Hkv,bs,d)
        v = v_ref[...].astype(jnp.float32).transpose(1, 0, 2)
        if Hq != Hkv:                                           # GQA
            k = jnp.repeat(k, Hq // Hkv, axis=0)
            v = jnp.repeat(v, Hq // Hkv, axis=0)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        k_pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        mask = (k_pos <= pos_ref[...])[None]                    # (1, C, bs)
        s = jnp.where(mask, s, NEG_INF)                         # (Hq, C, bs)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_attention_kernel(q_seg, pos_seg, k_pool, v_pool,
                                   block_table, row_of_seg, *, scale: float,
                                   interpret: bool = False):
    """Pallas packed prefill.  q_seg: (N, Hq, C, d) — the packed chunk
    unfolded to one right-padded row per segment, head-major (d a multiple
    of 128); pos_seg: (N, C) int32 absolute KV positions (-1 on padding);
    row_of_seg: (N,) int32 batch row per segment (clamped if -1).
    Returns (N, Hq, C, d)."""
    N, Hq, C, d = q_seg.shape
    nb = block_table.shape[1]
    bs = k_pool.shape[1]
    Hkv = k_pool.shape[2]
    kv_len = pos_seg.max(axis=1) + 1                            # (N,)

    def kv_index(n, i, row, bt, kvl):
        # blocks past the segment's KV length re-map to its last needed
        # block: the pipeline skips the repeated DMA, the body its compute
        i = jnp.minimum(i, jnp.maximum(kvl[n] - 1, 0) // bs)
        return (jnp.maximum(bt[jnp.maximum(row[n], 0), i], 0), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # row_of_seg, block_table, kv_len
        grid=(N, nb),
        in_specs=[
            pl.BlockSpec((None, Hq, C, d), lambda n, i, *_: (n, 0, 0, 0)),
            pl.BlockSpec((None, C, 1), lambda n, i, *_: (n, 0, 0)),
            pl.BlockSpec((None, bs, Hkv, d), kv_index),
            pl.BlockSpec((None, bs, Hkv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((None, Hq, C, d),
                               lambda n, i, *_: (n, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, C, 1), jnp.float32),   # running max
            pltpu.VMEM((Hq, C, 1), jnp.float32),   # running denom
            pltpu.VMEM((Hq, C, d), jnp.float32),   # output accumulator
        ],
    )
    kernel = functools.partial(_paged_prefill_kernel, bs=bs, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, Hq, C, d), q_seg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(row_of_seg, block_table, kv_len.astype(jnp.int32), q_seg,
      pos_seg[..., None], k_pool, v_pool)


def paged_prefill_attention(q, cache: PagedDenseKVCache, cu_seqlens,
                            row_of_seg, past_lens, *, scale: float,
                            impl: str | None = None,
                            interpret: bool | None = None):
    """Packed ragged prefill over a paged dense cache (public dispatcher).

    q: (total, Hq, d) — N segments flattened back to back; cu_seqlens:
    (N+1,) int32 offsets (cu[N] may be < total: the tail is padding);
    row_of_seg: (N,) int32 batch row per segment (-1 = inactive segment);
    past_lens: (N,) int32 tokens already in the row's cache BEFORE this
    chunk.  The chunk's K/V must already be appended (``append_packed``).
    ``impl`` as in ``paged_attention_decode``.
    """
    if impl is None:
        impl = "ref" if interpret_default() else "kernel"
    total, Hq, d = q.shape
    cu = jnp.asarray(cu_seqlens, jnp.int32)
    t = jnp.arange(total, dtype=jnp.int32)
    seg = jnp.searchsorted(cu[1:], t, side="right").astype(jnp.int32)
    seg = jnp.where(t < cu[-1], seg, -1)
    segc = jnp.maximum(seg, 0)
    local = t - cu[segc]
    row_of_tok = jnp.where(seg >= 0, row_of_seg[segc], -1)
    pos_in_kv = jnp.where(seg >= 0, past_lens[segc] + local, -1)

    if impl == "ref":
        return paged_prefill_attention_ref(
            q, cache.k, cache.v, cache.block_table, row_of_tok, pos_in_kv,
            scale)

    interpret = interpret_default() if interpret is None else interpret
    N = cu.shape[0] - 1
    C = total
    # unfold the packed stream to one right-padded row per segment
    tok_idx = cu[:-1, None] + jnp.arange(C)[None, :]            # (N, C)
    in_seg = jnp.arange(C)[None, :] < (cu[1:] - cu[:-1])[:, None]
    tok_c = jnp.clip(tok_idx, 0, total - 1)
    q_seg = jnp.where(in_seg[..., None, None], q[tok_c], 0)
    pos_seg = jnp.where(in_seg & (row_of_seg >= 0)[:, None],
                        past_lens[:, None] + jnp.arange(C)[None, :], -1)
    out_seg = paged_prefill_attention_kernel(
        _pad_lane(q_seg.transpose(0, 2, 1, 3)), pos_seg.astype(jnp.int32),
        _pad_lane(cache.k), _pad_lane(cache.v), cache.block_table,
        row_of_seg.astype(jnp.int32), scale=scale, interpret=interpret)
    out = out_seg[segc, :, local][..., :d]                      # (total,Hq,d)
    return jnp.where((seg >= 0)[:, None, None], out, 0).astype(q.dtype)
