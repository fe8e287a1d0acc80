"""Pallas TPU kernel: MoSA inner attention over expert-choice-selected tokens.

The hot spot the paper leaves to "future CUDA kernels": attention over the k
selected tokens of each head, with
  * the index-derived causal mask (I_q >= I_k) fused in,
  * an optional segment mask (seg_q == seg_k) for packed varlen streams, so
    selected tokens of different documents / requests sharing one flattened
    stream never attend across a sequence boundary,
  * the router scaling (diag(r) A) fused into the output,
  * flash-style streaming softmax (fp32 running max / denom),
  * BlockSpec VMEM tiling: grid ``(batch*head, S // block_q, S // block_k)``
    — one query block per (b*h, i) with the key blocks streamed along the
    innermost ``arbitrary`` axis and the softmax carry in VMEM scratch.

Shapes are MXU-friendly by construction: ops.py pads d_head to a multiple of
128 lanes and S (selected count) to a multiple of the block size; padded KV
slots carry idx = +INT_MAX and seg = -1 so the mask kills them, padded
queries are sliced off by the wrapper.  The dense (single-segment) path
passes seg = 0 everywhere, which makes the segment term a constant-true.

TPU tiling: per-token ids enter twice — as COLUMNS ``(BH, S, 1)`` for the
query side and as ROWS ``(BH, 1, S)`` for the key side — so the (bq, bk)
pair mask is a plain broadcast compare and every block's last two dims are
either (8k, 1) or (1, 128k | S): the layouts Mosaic tiles without
relayouts.  Per-query scalars (router score, lse, delta) ride as columns.

VMEM per grid step (bq = bk = 128, d = 128): q/k/v blocks 3 x 32 KiB (bf16)
+ scores 64 KiB + fp32 accumulator 64 KiB — far under the scoped limit.

Two entry points:
  * ``mosa_attention_pallas``      — inference forward (router scaling fused),
  * ``mosa_attention_fwd_res``     — training forward: emits the PRE-scale
    output ``o_pre`` (fp32) and the per-query log-sum-exp ``lse`` (fp32), the
    residuals the recompute-style backward kernels in ``mosa_backward.py``
    need.  ``mosa_vjp.py`` stitches the two into a ``jax.custom_vjp``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# grid axes: (batch*head, outer block, streamed block)
SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def cols(x):
    """(BH, S) -> (BH, S, 1): query-side ids / scalars, one per sublane."""
    return x[..., None]


def rows(x):
    """(BH, S) -> (BH, 1, S): key-side ids, one per lane."""
    return x[:, None, :]


def pair_mask(idx_q, seg_q, idx_k, seg_k):
    """Causal-by-original-position AND same-segment AND valid-key mask.

    ``idx_q``/``seg_q``: (bq, 1) columns; ``idx_k``/``seg_k``: (1, bk) rows.
    idx carries the token's ORIGINAL position (within its own sequence);
    seg carries the segment id of the packed stream (-1 = padding).
    """
    return (seg_q == seg_k) & (idx_q >= idx_k) & (idx_k >= 0)


def _fwd_kernel(*refs, scale: float, residuals: bool):
    """Grid (BH, S // block_q, S // block_k).  Blocks:

    idq, sgq: (1, bq, 1) query ids     idk, sgk: (1, 1, bk) key ids
    q: (1, bq, d)   k, v: (1, bk, d)   r: (1, bq, 1) router scores
    inference out:  o (1, bq, d) = r * softmax(...) v
    residual outs:  o_pre (1, bq, d) fp32, lse (1, bq, 1) fp32
    scratch: running max m, denom l (bq, 1); accumulator acc (bq, d).
    """
    if residuals:
        (idq_ref, sgq_ref, idk_ref, sgk_ref, q_ref, k_ref, v_ref,
         o_ref, lse_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (idq_ref, sgq_ref, idk_ref, sgk_ref, q_ref, k_ref, v_ref, r_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale                   # (bq, d)
    k = k_ref[0].astype(jnp.float32)                           # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    mask = pair_mask(idq_ref[0], sgq_ref[0], idk_ref[0], sgk_ref[0])
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        out = acc_ref[...] / l_safe
        if residuals:
            o_ref[0] = out
            lse_ref[0] = m_ref[...] + jnp.log(l_safe)
        else:
            o_ref[0] = (out * r_ref[0]).astype(o_ref.dtype)  # router scaling


def _fwd_call(q, k, v, idx, seg, r, *, block_q, block_k, scale, interpret,
              residuals):
    B, H, S, d = q.shape
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    BH = B * H
    qf, kf, vf = (x.reshape(BH, S, d) for x in (q, k, v))
    idxf, segf = idx.reshape(BH, S), seg.reshape(BH, S)

    col = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    row = pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, j))
    q_blk = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kv_blk = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    in_specs = [col, col, row, row, q_blk, kv_blk, kv_blk]
    args = [cols(idxf), cols(segf), rows(idxf), rows(segf), qf, kf, vf]
    if residuals:
        out_specs = [q_blk, col]
        out_shape = [jax.ShapeDtypeStruct((BH, S, d), jnp.float32),
                     jax.ShapeDtypeStruct((BH, S, 1), jnp.float32)]
    else:
        in_specs.append(col)
        args.append(cols(r.reshape(BH, S).astype(jnp.float32)))
        out_specs = q_blk
        out_shape = jax.ShapeDtypeStruct((BH, S, d), q.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, residuals=residuals),
        grid=(BH, S // block_q, S // block_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=SEMANTICS,
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "scale",
                                             "interpret"))
def mosa_attention_pallas(q, k, v, idx, seg, r, *, block_q: int = 128,
                          block_k: int = 128, scale: float | None = None,
                          interpret: bool = False):
    """q, k, v: (B, H, S, d); idx, seg: (B, H, S) int32; r: (B, H, S) fp32.

    Preconditions (ops.py guarantees them): S % block_q == 0,
    S % block_k == 0, d padded to 128 lanes.
    """
    B, H, S, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    out = _fwd_call(q, k, v, idx, seg, r, block_q=block_q, block_k=block_k,
                    scale=scale, interpret=interpret, residuals=False)
    return out.reshape(B, H, S, d)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "scale",
                                             "interpret"))
def mosa_attention_fwd_res(q, k, v, idx, seg, *, block_q: int = 128,
                           block_k: int = 128, scale: float | None = None,
                           interpret: bool = False):
    """Training-path forward.  Same preconditions as ``mosa_attention_pallas``
    (padded shapes from ops.py); returns ``(o_pre, lse)``:

      o_pre: (B, H, S, d) fp32 — softmax(QK^T masked) V, BEFORE router scaling
      lse:   (B, H, S)    fp32 — per-query log-sum-exp of the masked scores

    The caller applies ``out = o_pre * r`` (XLA fuses the scale into the
    kernel's consumer) and keeps both tensors as VJP residuals.
    """
    B, H, S, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    o_pre, lse = _fwd_call(q, k, v, idx, seg, None, block_q=block_q,
                           block_k=block_k, scale=scale, interpret=interpret,
                           residuals=True)
    return o_pre.reshape(B, H, S, d), lse.reshape(B, H, S)
