"""Pallas TPU kernel: causal (optionally sliding-window) flash attention, GQA.

Serves the dense / local heads of the hybrid layer.  Standard flash-v2
streaming softmax with BlockSpec VMEM tiling: the grid's innermost
``arbitrary`` axis streams KV blocks of ``block_k`` through VMEM while the
fp32 running max / denominator / accumulator stay in VMEM scratch.  Blocks
outside a query block's causal (and sliding-window) range skip their
compute, and their index map is clamped into the range so the pipeline
issues no DMA for them: work per query block is O(min(q_end, window)).

GQA is expressed in the BlockSpec index_map: the KV block for query head h is
loaded from kv head h // (Hq // Hkv) — no materialized repeat.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_softmax_step(q, k_ref, v_ref, mask, m_ref, l_ref, acc_ref):
    """One KV block of flash-v2: fold ``softmax(q k^T)`` over the masked
    (bq, bk) tile into the running (m, l, acc) carry."""
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _scratch(block_q, d):
    return [pltpu.VMEM((block_q, 1), jnp.float32),      # running max
            pltpu.VMEM((block_q, 1), jnp.float32),      # running denom
            pltpu.VMEM((block_q, d), jnp.float32)]      # output accumulator


def _kv_range(q_start, block_q, block_k, window, lo_tok=0):
    """[lo, hi) KV blocks a query block starting at ``q_start`` can see."""
    hi = pl.cdiv(q_start + block_q, block_k)
    lo = lo_tok // block_k
    if window > 0:
        lo = jnp.maximum(lo, (q_start - window + 1) // block_k)
    return jnp.maximum(lo, 0), hi


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_k: int, scale: float, window: int, q_offset: int):
    """Grid (B, Hq, Tq // bq, Tk // bk).  Blocks: q/o (bq, d); k/v (bk, d)."""
    block_q = q_ref.shape[0]
    qi, kb = pl.program_id(2), pl.program_id(3)
    q_start = qi * block_q + q_offset          # absolute position of q row 0
    lo, hi = _kv_range(q_start, block_q, block_k, window)

    @pl.when(kb == 0)
    def _():
        _init(m_ref, l_ref, acc_ref)

    @pl.when((kb >= lo) & (kb < hi))
    def _():
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        q = q_ref[...].astype(jnp.float32) * scale
        _online_softmax_step(q, k_ref, v_ref, mask, m_ref, l_ref, acc_ref)

    @pl.when(kb == pl.num_programs(3) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "scale",
                                             "window", "q_offset", "interpret"))
def flash_attention_pallas(q, k, v, *, block_q: int = 128, block_k: int = 128,
                           scale: float | None = None, window: int = 0,
                           q_offset: int | None = None,
                           interpret: bool = False):
    """q: (B, Hq, Tq, d); k, v: (B, Hkv, Tk, d).  ``q_offset`` is the absolute
    position of q row 0 (default: Tk - Tq, i.e. q rows are the last Tq
    positions of the context — pass it explicitly when shapes are padded).
    Preconditions (ops.py): Tq % block_q == 0, Tk % block_k == 0, d a
    multiple of 128.
    """
    B, Hq, Tq, d = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    assert Tq % block_q == 0 and Tk % block_k == 0
    assert Hq % Hkv == 0
    n_rep = Hq // Hkv
    scale = scale if scale is not None else d ** -0.5
    if q_offset is None:
        q_offset = Tk - Tq
    n_kb = Tk // block_k

    def kv_index(b, h, i, j):
        lo, hi = _kv_range(i * block_q + q_offset, block_q, block_k, window)
        j = jnp.clip(j, lo, jnp.minimum(hi, n_kb) - 1)  # no DMA off-range
        return (b, h // n_rep, j, 0)

    kernel = functools.partial(_flash_kernel, block_k=block_k, scale=scale,
                               window=window, q_offset=q_offset)
    q_spec = pl.BlockSpec((None, None, block_q, d),
                          lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, d), kv_index)
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, Tq // block_q, n_kb),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tq, d), q.dtype),
        scratch_shapes=_scratch(block_q, d),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# --------------------------------------------------------------- packed varlen
def _flash_varlen_kernel(seg_smem_ref, cu_ref, segq_ref, segk_ref, q_ref,
                         k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                         block_k: int, scale: float, window: int):
    """Packed ragged self-attention over a flattened token stream.

    Grid: (Hq, Tp // block_q, Tp // block_k).  Scalar-prefetch (SMEM):
      seg_smem_ref: (Tp,)  — segment id per packed token (-1 = padding)
      cu_ref:       (N+1,) — cu_seqlens, segment s spans [cu[s], cu[s+1])
    VMEM blocks: segq (bq, 1) / segk (1, bk) — the same segment ids as a
    column and a row; q / o (bq, d); k / v (bk, d).

    The causal mask uses GLOBAL packed positions — within a segment global
    order equals local order, and the (seg_q == seg_k) term removes every
    cross-segment pair, so this is per-sequence causal attention with zero
    cross-contamination.  The KV block range is cut to
    [segment start of the block's first query, query block end), so work per
    query block is O(its own segment), not O(total).
    """
    block_q = q_ref.shape[0]
    qi, kb = pl.program_id(1), pl.program_id(2)
    q_start = qi * block_q
    lo, hi = _kv_range(q_start, block_q, block_k, window,
                       _segment_start(seg_smem_ref, cu_ref, q_start))

    @pl.when(kb == 0)
    def _():
        _init(m_ref, l_ref, acc_ref)

    @pl.when((kb >= lo) & (kb < hi))
    def _():
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = (segq_ref[...] == segk_ref[...]) & (q_pos >= k_pos)
        if window > 0:
            mask &= (q_pos - k_pos) < window
        q = q_ref[...].astype(jnp.float32) * scale
        _online_softmax_step(q, k_ref, v_ref, mask, m_ref, l_ref, acc_ref)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def _segment_start(seg_smem_ref, cu_ref, q_start):
    """First packed position of the segment holding token ``q_start`` — it
    bounds every key its query block can see (padding rows have seg = -1:
    clamp to 0 so the SMEM read stays in range)."""
    return cu_ref[jnp.maximum(seg_smem_ref[q_start], 0)]


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "scale",
                                             "window", "interpret"))
def flash_attention_varlen_pallas(q, k, v, seg, cu_seqlens, *,
                                  block_q: int = 128, block_k: int = 128,
                                  scale: float | None = None, window: int = 0,
                                  interpret: bool = False):
    """Packed ragged (cu_seqlens) causal attention, GQA.

    q: (Hq, Tp, d); k, v: (Hkv, Tp, d) — Tp = padded total token count of the
    flattened stream.  seg: (Tp,) int32 segment ids (-1 on padding rows);
    cu_seqlens: (N+1,) int32 cumulative offsets, prefetched to SMEM so the
    per-block KV range is cut before the DMA is issued.
    Preconditions (ops.py): Tp % block_q == 0 == Tp % block_k, d % 128 == 0.
    """
    Hq, Tp, d = q.shape
    Hkv = k.shape[0]
    assert Tp % block_q == 0 and Tp % block_k == 0
    assert Hq % Hkv == 0
    n_rep = Hq // Hkv
    scale = scale if scale is not None else d ** -0.5
    n_kb = Tp // block_k

    def kv_block(i, j, sg, cu):
        q_start = i * block_q
        lo, hi = _kv_range(q_start, block_q, block_k, window,
                           _segment_start(sg, cu, q_start))
        return jnp.clip(j, lo, jnp.minimum(hi, n_kb) - 1)

    kernel = functools.partial(_flash_varlen_kernel, block_k=block_k,
                               scale=scale, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Hq, Tp // block_q, n_kb),
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda h, i, j, sg, cu: (i, 0)),
            pl.BlockSpec((1, block_k),
                         lambda h, i, j, sg, cu: (0, kv_block(i, j, sg, cu))),
            pl.BlockSpec((None, block_q, d), lambda h, i, j, sg, cu: (h, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda h, i, j, sg, cu:
                         (h // n_rep, kv_block(i, j, sg, cu), 0)),
            pl.BlockSpec((None, block_k, d), lambda h, i, j, sg, cu:
                         (h // n_rep, kv_block(i, j, sg, cu), 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda h, i, j, sg, cu: (h, i, 0)),
        scratch_shapes=_scratch(block_q, d),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hq, Tp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(seg, cu_seqlens.astype(jnp.int32), seg.reshape(Tp, 1),
      seg.reshape(1, Tp), q, k, v)
