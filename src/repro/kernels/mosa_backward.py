"""Pallas TPU backward kernels for the fused MoSA inner attention.

Recompute-style (flash-attention bwd): neither kernel reads the O(S^2)
probability matrix from memory — scores are recomputed from Q/K and the
saved per-query log-sum-exp (``lse``), so the only extra residuals the
forward keeps are ``o_pre`` (B,H,S,d fp32) and ``lse`` (B,H,S fp32).

Math (S_ij = scale * q_i.k_j masked by I_q >= I_k and seg_q == seg_k;
P = softmax rows;
o_pre_i = sum_j P_ij v_j; out_i = r_i * o_pre_i; g = d out):

  dr_i   = g_i . o_pre_i                       (router-score gradient — the
                                                expert-choice learning path)
  g~_i   = r_i * g_i
  dV_j   = sum_i P_ij g~_i
  dS_ij  = P_ij * (g~_i . v_j - delta_i),  delta_i = g~_i . o_pre_i
  dQ_i   = scale * sum_j dS_ij k_j
  dK_j   = scale * sum_i dS_ij q_i

``delta`` and ``dr`` are O(S*d) elementwise reductions computed in plain jnp
by the wrapper (``mosa_vjp.py``); the two kernels here carry the O(S^2*d)
work with the forward's tiling (ids as columns/rows, see
``mosa_attention.py``) — one (batch*head) slice per outer grid index, the
opposite operand streamed along the innermost ``arbitrary`` axis with an
fp32 accumulator in VMEM scratch:

  _dq_kernel   grid (BH, S // block_q, S // block_k) -> dq block
  _dkv_kernel  grid (BH, S // block_k, S // block_q) -> dk, dv blocks

Masking note: rows ops.py padded (idx = +INT_MAX) see a garbage-but-finite
``lse``; their cotangent ``g~`` arrives as exact zeros (the output slice
pads cotangents with 0), so every term they touch vanishes — but ``P`` must
still be recomputed with the explicit mask, because exp(NEG_INF - lse) is
NOT ~0 when lse itself is ~NEG_INF (the empty-row case).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosa_attention import SEMANTICS, cols, pair_mask, rows


def _probs(q_ref, k_ref, idq_ref, sgq_ref, idk_ref, sgk_ref, lse_ref, scale):
    """Recomputed masked probabilities P (bq, bk) and the fp32 q / k tiles."""
    q = q_ref[0].astype(jnp.float32)                           # (bq, d)
    k = k_ref[0].astype(jnp.float32)                           # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = pair_mask(idq_ref[0], sgq_ref[0], idk_ref[0], sgk_ref[0])
    return jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0), q, k


def _dq_kernel(idq_ref, sgq_ref, idk_ref, sgk_ref, q_ref, k_ref, v_ref,
               gt_ref, lse_ref, delta_ref, dq_ref, acc_ref, *, scale: float):
    """Grid (BH, S // block_q, S // block_k); key blocks stream along j.
    gt (= r * g) is (1, bq, d) fp32; lse, delta are (1, bq, 1) columns."""
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p, _, k = _probs(q_ref, k_ref, idq_ref, sgq_ref, idk_ref, sgk_ref,
                     lse_ref, scale)
    v = v_ref[0].astype(jnp.float32)
    dp = jax.lax.dot_general(gt_ref[0], v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0])
    acc_ref[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(idq_ref, sgq_ref, idk_ref, sgk_ref, q_ref, k_ref, v_ref,
                gt_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale: float):
    """Grid (BH, S // block_k, S // block_q); query blocks stream along i."""
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    p, q, _ = _probs(q_ref, k_ref, idq_ref, sgq_ref, idk_ref, sgk_ref,
                     lse_ref, scale)
    gt = gt_ref[0]
    v = v_ref[0].astype(jnp.float32)
    dv_acc[...] += jax.lax.dot_general(
        p, gt, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(gt, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0])
    dk_acc[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qb == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "scale",
                                             "interpret"))
def mosa_attention_bwd_pallas(q, k, v, idx, seg, gt, lse, delta, *,
                              block_q: int = 128, block_k: int = 128,
                              scale: float | None = None,
                              interpret: bool = False):
    """Backward dispatch: two pallas_calls sharing one residual layout.

    q, k, v: (B, H, S, d) (padded, see ops.py); idx, seg: (B, H, S) int32;
    gt (= r * g): (B, H, S, d) fp32; lse, delta: (B, H, S) fp32.
    Returns (dq, dk, dv) in the dtypes of (q, k, v).
    """
    B, H, S, d = q.shape
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    scale = scale if scale is not None else d ** -0.5
    BH = B * H
    qf, kf, vf = (x.reshape(BH, S, d) for x in (q, k, v))
    gtf = gt.reshape(BH, S, d).astype(jnp.float32)
    idxf, segf = idx.reshape(BH, S), seg.reshape(BH, S)
    args = (cols(idxf), cols(segf), rows(idxf), rows(segf), qf, kf, vf, gtf,
            cols(lse.reshape(BH, S)), cols(delta.reshape(BH, S)))

    def specs(qi, ki):
        """BlockSpecs for ``args``; ``qi``/``ki`` pick the query / key block
        index out of the grid indices (i, j)."""
        col = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, qi(i, j), 0))
        row = pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, ki(i, j)))
        q_blk = pl.BlockSpec((1, block_q, d),
                             lambda b, i, j: (b, qi(i, j), 0))
        k_blk = pl.BlockSpec((1, block_k, d),
                             lambda b, i, j: (b, ki(i, j), 0))
        return [col, col, row, row, q_blk, k_blk, k_blk, q_blk, col, col]

    q_major = specs(lambda i, j: i, lambda i, j: j)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=(BH, S // block_q, S // block_k),
        in_specs=q_major,
        out_specs=q_major[4],
        out_shape=jax.ShapeDtypeStruct((BH, S, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=SEMANTICS,
        interpret=interpret,
    )(*args)

    k_major = specs(lambda i, j: j, lambda i, j: i)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale),
        grid=(BH, S // block_k, S // block_q),
        in_specs=k_major,
        out_specs=[k_major[5], k_major[6]],
        out_shape=[jax.ShapeDtypeStruct((BH, S, d), k.dtype),
                   jax.ShapeDtypeStruct((BH, S, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=SEMANTICS,
        interpret=interpret,
    )(*args)

    return (dq.reshape(B, H, S, d), dk.reshape(B, H, S, d),
            dv.reshape(B, H, S, d))
