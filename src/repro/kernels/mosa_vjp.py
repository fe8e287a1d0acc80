"""``jax.custom_vjp`` around the fused MoSA attention kernels.

This is what makes the Pallas path TRAINABLE end-to-end: the primal call is
the inference kernel (``mosa_attention_pallas``, router scaling fused, zero
overhead when nobody differentiates), while under ``jax.grad`` the forward
switches to ``mosa_attention_fwd_res`` (which also emits the ``o_pre``/
``lse`` residuals) and the backward runs the recompute-style Pallas kernels
in ``mosa_backward.py``.

Gradients produced: dq, dk, dv AND dr — the router-score cotangent.  dr is
the gradient path that makes expert-choice selection learnable: upstream it
flows through ``take_along_axis`` into the selected tokens' sigmoid scores
and on into the router weights, exactly like autodiff of the einsum
reference (the parity oracle in tests/test_train_grad.py).

Static config (block sizes, scale, interpret) is closed over by a cached
factory instead of ``nondiff_argnums``, so each static combination builds
its ``custom_vjp`` once.

Wrapper-level math kept OUT of the kernels (cheap O(S*d) elementwise):

  g~    = r * g                 (router scaling of the cotangent)
  delta = rowsum(g~ * o_pre)    (the flash-bwd softmax correction term)
  dr    = rowsum(g  * o_pre)

``idx``/``seg`` are integer (non-differentiable): their cotangents are
``float0`` zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.mosa_attention import (mosa_attention_fwd_res,
                                          mosa_attention_pallas)
from repro.kernels.mosa_backward import mosa_attention_bwd_pallas


@functools.lru_cache(maxsize=None)
def _build(block_q: int, block_k: int, scale: float, interpret: bool):
    @jax.custom_vjp
    def fused(q, k, v, idx, seg, r):
        return mosa_attention_pallas(q, k, v, idx, seg, r, block_q=block_q,
                                     block_k=block_k, scale=scale,
                                     interpret=interpret)

    def fwd(q, k, v, idx, seg, r):
        o_pre, lse = mosa_attention_fwd_res(q, k, v, idx, seg,
                                            block_q=block_q, block_k=block_k,
                                            scale=scale, interpret=interpret)
        rf = r.astype(jnp.float32)
        out = (o_pre * rf[..., None]).astype(q.dtype)
        return out, (q, k, v, idx, seg, rf, o_pre, lse)

    def bwd(res, g):
        q, k, v, idx, seg, rf, o_pre, lse = res
        g32 = g.astype(jnp.float32)
        gt = g32 * rf[..., None]                       # (B,H,S,d) fp32
        dr = jnp.sum(g32 * o_pre, axis=-1)             # router-score grad
        delta = jnp.sum(gt * o_pre, axis=-1)
        dq, dk, dv = mosa_attention_bwd_pallas(
            q, k, v, idx, seg, gt, lse, delta, block_q=block_q,
            block_k=block_k, scale=scale, interpret=interpret)
        didx = np.zeros(idx.shape, jax.dtypes.float0)  # int input: no grad
        dseg = np.zeros(seg.shape, jax.dtypes.float0)
        return dq, dk, dv, didx, dseg, dr.astype(jnp.float32)

    fused.defvjp(fwd, bwd)
    return fused


def mosa_attention_trainable(q, k, v, idx, r, *, seg=None,
                             block_q: int = 128, block_k: int = 128,
                             scale: float | None = None,
                             interpret: bool = False):
    """Differentiable fused MoSA attention.  Same contract and preconditions
    as ``mosa_attention_pallas`` (ops.py handles padding); additionally
    supports ``jax.grad`` w.r.t. q, k, v and r.  ``seg`` (B, H, S) int32
    carries packed-varlen segment ids (None = single segment)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if seg is None:
        seg = jnp.zeros(idx.shape, jnp.int32)
    return _build(block_q, block_k, float(scale), bool(interpret))(
        q, k, v, idx, seg, r.astype(jnp.float32))
