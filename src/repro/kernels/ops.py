"""Jit'd public wrappers around the Pallas kernels.

Handles shape hygiene (padding S and d_head to MXU-aligned tiles, unpadding
outputs) and platform dispatch: ``interpret=None`` lowers natively on a TPU
backend and runs the Pallas interpreter elsewhere
(``repro.kernels.interpret_default``); pass ``interpret=False`` to compile
for a TPU explicitly.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.flash_attention import (flash_attention_pallas,
                                           flash_attention_varlen_pallas)
from repro.kernels.mosa_vjp import mosa_attention_trainable

LANE = 128


def _pad_to(x, axis, mult, value=0.0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def mosa_attention(q, k, v, idx, r, *, seg=None, block_q: int = 128,
                   block_k: int = 128, interpret: bool | None = None):
    """MoSA inner attention (see kernels/mosa_attention.py).

    q,k,v: (B,H,S,d); idx: (B,H,S) sorted ascending; r: (B,H,S) fp32.
    ``seg``: optional (B,H,S) int32 segment ids for packed-varlen streams —
    selected tokens only attend selected tokens of the SAME segment (None =
    one segment per row, the dense behaviour, bit-for-bit unchanged).
    Returns (B,H,S,d) in q.dtype.

    Differentiable: routed through the ``jax.custom_vjp`` in
    ``kernels/mosa_vjp.py`` — forward-only callers run the original fused
    kernel; under ``jax.grad`` the Pallas backward kernels produce
    dq/dk/dv/dr (pad/slice shape hygiene here differentiates transparently:
    cotangents of the output slice arrive zero-padded).

    Under an ambient mesh (``repro.dist.hints``) the kernel runs once per
    (batch, head) shard: the SPMD partitioner cannot split a Mosaic kernel,
    and every (b, h) slice is independent.
    """
    from repro.dist import hints

    interpret = interpret_default() if interpret is None else interpret
    kernel = functools.partial(_mosa_attention, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    return hints.per_shard(kernel, q, k, v, idx, r,
                           *(() if seg is None else (seg,)))


def _mosa_attention(q, k, v, idx, r, seg=None, *, block_q, block_k,
                    interpret):
    B, H, S, d = q.shape
    bq = min(block_q, max(8, 1 << (S - 1).bit_length()))
    bk = min(block_k, bq)
    scale = d ** -0.5  # scale on the TRUE head dim, before padding

    qp = _pad_to(_pad_to(q, 3, LANE), 2, bq)
    kp = _pad_to(_pad_to(k, 3, LANE), 2, bk)
    vp = _pad_to(_pad_to(v, 3, LANE), 2, bk)
    # pad idx with INT_MAX (mask kills padded keys), r with 0 (zero output)
    idxp = _pad_to(idx, 2, bq, value=jnp.iinfo(jnp.int32).max)
    rp = _pad_to(r, 2, bq, value=0.0)
    segp = None if seg is None else _pad_to(seg, 2, bq, value=-1)

    out = mosa_attention_trainable(qp, kp, vp, idxp, rp, seg=segp,
                                   block_q=bq, block_k=bk, scale=scale,
                                   interpret=interpret)
    return out[:, :, :S, :d]


def mosa_block_attention(q, k, v, bidx, rblk, *, sel_block_size: int,
                         T: int, seg=None, block_q: int = 128,
                         block_k: int = 128, interpret: bool | None = None):
    """Block-choice MoSA inner attention (DESIGN §10) on the token kernels.

    q,k,v: (B,H,S,d) block-major selected tokens, S = NB * sel_block_size;
    bidx: (B,H,NB) selected block indices sorted ascending (-1 = empty);
    rblk: (B,H,NB) fp32 per-block router scores; ``T`` the true sequence
    length (ragged tail of the last block is masked).  ``seg``: optional
    per-token (B,H,S) segment ids.  Returns (B,H,S,d) in q.dtype.

    Blocks expand to per-token positions (``bidx * bs + offset``); empty
    slots and ragged tails get position -1, which the token kernel's
    valid-key term masks out and which leaves their query rows with no key
    at all (zero output, zero gradient).  The block score broadcasts over
    its tokens, so autodiff sums the token kernel's router cotangent back
    PER BLOCK.  At ``sel_block_size=1`` the expansion is the identity and
    this is ``mosa_attention`` bit for bit (tests/test_block_choice.py).
    """
    bs = sel_block_size
    assert bs >= 1 and (bs & (bs - 1)) == 0 and bs <= LANE, (
        f"sel_block_size must be a power of two <= {LANE}, got {bs}")
    B, H, S, d = q.shape
    NB = bidx.shape[-1]
    assert S == NB * bs, (S, NB, bs)
    pos = bidx[..., None] * bs + jnp.arange(bs, dtype=jnp.int32)
    ok = (bidx[..., None] >= 0) & (pos < T)
    idx = jnp.where(ok, pos, -1).reshape(B, H, S).astype(jnp.int32)
    r = jnp.broadcast_to(rblk[..., None].astype(jnp.float32),
                         (B, H, NB, bs)).reshape(B, H, S)
    return mosa_attention(q, k, v, idx, r, seg=seg, block_q=block_q,
                          block_k=block_k, interpret=interpret)


def segments_from_cu_seqlens(cu_seqlens, total: int):
    """(seg, pos) per packed token from cumulative offsets.

    cu_seqlens: (N+1,) int32 with cu[0] == 0 and cu[N] <= total.  Tokens in
    [cu[s], cu[s+1]) get seg = s and pos = their LOCAL offset within the
    segment; tokens >= cu[N] (padding tail) get seg = -1, pos = 0.
    """
    cu = jnp.asarray(cu_seqlens, jnp.int32)
    t = jnp.arange(total, dtype=jnp.int32)
    seg = jnp.searchsorted(cu[1:], t, side="right").astype(jnp.int32)
    in_range = t < cu[-1]
    seg = jnp.where(in_range, seg, -1)
    pos = jnp.where(in_range, t - cu[jnp.maximum(seg, 0)], 0)
    return seg, pos


def flash_attention_varlen(q, k, v, cu_seqlens, *, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool | None = None):
    """Packed ragged (cu_seqlens) causal/windowed GQA flash attention.

    q: (total, Hq, d); k, v: (total, Hkv, d) — ONE flattened token stream
    holding N back-to-back sequences; cu_seqlens: (N+1,) int32 cumulative
    offsets (cu[0] = 0, cu[N] = total).  Attention is causal within each
    segment and never crosses a boundary.  Returns (total, Hq, d) in q.dtype.
    """
    interpret = interpret_default() if interpret is None else interpret
    total, Hq, d = q.shape
    bq = min(block_q, max(8, 1 << (total - 1).bit_length()))
    bk = min(block_k, bq)
    scale = d ** -0.5

    # head-major layout for the kernel: (H, total, d)
    qh = _pad_to(_pad_to(q.transpose(1, 0, 2), 2, LANE), 1, bq)
    kh = _pad_to(_pad_to(k.transpose(1, 0, 2), 2, LANE), 1, bk)
    vh = _pad_to(_pad_to(v.transpose(1, 0, 2), 2, LANE), 1, bk)
    Tp = qh.shape[1]
    seg, _ = segments_from_cu_seqlens(cu_seqlens, Tp)

    out = flash_attention_varlen_pallas(qh, kh, vh, seg,
                                        jnp.asarray(cu_seqlens, jnp.int32),
                                        block_q=bq, block_k=bk, scale=scale,
                                        window=window, interpret=interpret)
    return out[:, :total, :d].transpose(1, 0, 2)


def flash_attention(q, k, v, *, window: int = 0, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """Causal/windowed GQA flash attention.  q: (B,Hq,Tq,d), k/v (B,Hkv,Tk,d)."""
    interpret = interpret_default() if interpret is None else interpret
    B, Hq, Tq, d = q.shape
    Tk = k.shape[2]
    bq = min(block_q, max(8, 1 << (Tq - 1).bit_length()))
    bk = min(block_k, max(8, 1 << (Tk - 1).bit_length()))
    scale = d ** -0.5

    qp = _pad_to(_pad_to(q, 3, LANE), 2, bq)
    kp = _pad_to(_pad_to(k, 3, LANE), 2, bk)
    vp = _pad_to(_pad_to(v, 3, LANE), 2, bk)
    # NOTE: padded KV rows sit at positions >= Tk; causal masking with
    # absolute positions already excludes them for all real queries because
    # real q positions are < Tk.  Padded q rows are sliced off below.
    out = flash_attention_pallas(qp, kp, vp, block_q=bq, block_k=bk,
                                 scale=scale, window=window,
                                 q_offset=Tk - Tq, interpret=interpret)
    return out[:, :, :Tq, :d]
