"""Expert-choice token router — the heart of MoSA.

Each MoSA head owns one router vector ``W^r in R^h``.  Scores are the
*non-competitive* sigmoid ``r = sigmoid(X W^r)`` (sigma-MoE observation cited
by the paper), and each head independently selects its top-k tokens
(expert-choice: the head is the expert, so load balance is perfect by
construction — exactly k tokens per head, no auxiliary loss).

Selection is non-autoregressive (paper §5); the *scores* however are strictly
causal (token t's score depends only on token t).  ``streaming_topk_update``
implements the MoD-style autoregressive adaptation used by the serving path:
a running top-k set with evict-min updates.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.nn.module import logical
from repro.nn.layers import _trunc_normal

# ``jax.named_scope`` names of MoSA's compiled operations: they reach each
# operation's ``op_name`` metadata, so a device trace can attribute time to
# routing, to stored-set upkeep (top-k and the sorted insertion by a
# one-slot shift) and to attention over the stored set.
ROUTE, UPKEEP, ATTEND = "mosa.route", "mosa.upkeep", "mosa.attend"


@dataclasses.dataclass(frozen=True)
class ExpertChoiceRouter:
    d_model: int
    n_heads: int
    param_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        # Router kept in fp32: top-k boundary decisions are precision-sensitive.
        return {"w": _trunc_normal(key, (self.n_heads, self.d_model),
                                   self.d_model ** -0.5, jnp.float32)}

    def specs(self):
        return {"w": logical("mosa_heads", "embed")}

    def scores(self, params, x):
        """x: (B, T, h) -> sigmoid scores (B, H, T) in fp32."""
        with jax.named_scope(ROUTE):
            logits = jnp.einsum("bth,nh->bnt", x.astype(jnp.float32),
                                params["w"],
                                preferred_element_type=jnp.float32)
            return jax.nn.sigmoid(logits)


def select_topk(scores, k: int, force_first: bool = True):
    """Expert-choice selection.

    scores: (B, H, T) fp32.  Returns (r, idx), both (B, H, k), with ``idx``
    sorted ascending (so the index-derived causal mask is lower-triangular and
    the scatter back to the sequence is ordered) and ``r`` the corresponding
    router scores.

    ``force_first`` always includes token 0 (StreamingLLM attention-sink
    observation, used by the paper's IsoFLOP experiments): the head selects
    k-1 tokens from positions 1..T-1 plus token 0.  Token 0's output is still
    scaled by its *actual* router score.
    """
    B, H, T = scores.shape
    assert 0 < k <= T, f"k={k} out of range for T={T}"
    if force_first and k >= 2:
        _, idx_rest = jax.lax.top_k(scores[..., 1:], k - 1)      # (B, H, k-1)
        idx = jnp.concatenate(
            [jnp.zeros((B, H, 1), idx_rest.dtype), idx_rest + 1], axis=-1)
    else:
        _, idx = jax.lax.top_k(scores, k)
    idx = jnp.sort(idx, axis=-1)
    r = jnp.take_along_axis(scores, idx, axis=-1)
    return r, idx


def router_health_stats(r, idx, T: int):
    """Health metrics of one expert-choice selection (train-loop telemetry).

    r, idx: (B, H, k) — ``select_topk`` output for a (B, H, T) score tensor.

      * ``sel_entropy``   — entropy of the aggregate selection distribution
        over token positions, normalized by log T.  Low = the heads
        concentrate their k-budgets on few positions (router collapse —
        every head picking the same tokens); ~uniform coverage scores near
        the ceiling (the ceiling itself is (log B*H*k)/log T when
        B*H*k < T).
      * ``drop_rate``     — fraction of tokens selected by NO head; these
        positions get zero sparse-attention output AND zero router gradient
        this step (the paper's hybrid keeps dense heads partly for this).
      * ``head_util``     — mean router score over selected tokens: how
        strongly heads use their budget (scores sliding toward 0 = heads
        going dead; the sigmoid scale makes 0.5 the indifference point).
    """
    B, H, k = idx.shape
    sel = jax.nn.one_hot(idx, T, dtype=jnp.float32).sum(2)         # (B,H,T)
    counts = sel.sum(1)                                            # (B,T)
    drop_rate = (counts == 0).astype(jnp.float32).mean()
    p = sel.sum((0, 1)) / (B * H * k)                              # (T,)
    ent = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-20)), 0.0))
    return {"sel_entropy": ent / jnp.log(float(max(T, 2))),
            "drop_rate": drop_rate,
            "head_util": r.mean()}


def selection_mask(idx_q, idx_k):
    """Causal mask from original indices: allow iff I_q >= I_k.

    idx_q: (..., kq), idx_k: (..., kk) -> bool (..., kq, kk).
    """
    return idx_q[..., :, None] >= idx_k[..., None, :]


def block_pool_scores(scores, block_size: int):
    """Pool per-token router scores into per-block scores (block-choice MoSA).

    scores: (B, H, T) fp32 -> (B, H, NB) with NB = ceil(T / block_size).

    A block's score is the MEAN of its in-range token scores (the last block
    may cover fewer than ``block_size`` positions when ``block_size`` does
    not divide T; out-of-range slots are excluded from the mean).  At
    ``block_size=1`` this is the bitwise identity — the maintained
    token-choice equivalence (DESIGN §10) rests on it: sum over a size-1
    window then division by 1.0 reproduces every score exactly.
    """
    B, H, T = scores.shape
    bs = block_size
    nb = -(-T // bs)
    pad = nb * bs - T
    s = jnp.pad(scores, ((0, 0), (0, 0), (0, pad)))
    in_range = (jnp.arange(nb * bs) < T).reshape(nb, bs)            # (NB, bs)
    ssum = jnp.sum(jnp.where(in_range, s.reshape(B, H, nb, bs), 0.0), axis=-1)
    cnt = in_range.sum(-1).astype(scores.dtype)                     # (NB,) >= 1
    return ssum / cnt


def expand_block_index(bidx, block_size: int, T: int):
    """Per-block indices -> per-token positions (block-choice expansion).

    bidx: (..., NBsel) int32, -1 = empty slot.  Returns ``pos`` of shape
    (..., NBsel*block_size): ``bidx*bs + offset`` for real slots, and -1 for
    every token of an empty block or beyond ``T`` (the ragged tail of the
    last block).  The -1 sentinel keeps the downstream masks (``pos >= 0``)
    and scatters (positive sentinel + mode="drop") identical in shape to the
    token-choice path.
    """
    bs = block_size
    off = jnp.arange(bs, dtype=bidx.dtype)
    pos = bidx[..., None] * bs + off                                # (...,NB,bs)
    ok = (bidx[..., None] >= 0) & (pos < T)
    pos = jnp.where(ok, pos, -1)
    return pos.reshape(*bidx.shape[:-1], bidx.shape[-1] * bs)


def streaming_topk_update(cache_scores, cache_idx, new_score, new_pos, is_forced):
    """One step of the autoregressive (serving-time) top-k approximation.

    This is the evict-min policy behind ``repro.core.kv_cache.MoSAKVCache``
    (whose module docstring documents the ``-inf`` / ``-1`` empty-slot
    sentinels): the incoming token replaces the minimum-score slot iff its
    router score beats that minimum.  Empty slots score ``-inf``, so they
    always fill first.  The *storage* lives in the cache; the *policy* lives
    here — ``MoSAAttention.decode_step`` wires the two together.

    cache_scores: (..., k) current per-slot scores (-inf = empty slot)
    cache_idx:    (..., k) original positions of cached tokens (-1 = empty)
    new_score:    (...,)   router score of the incoming token
    new_pos:      scalar or broadcastable to new_score's shape — its position
    is_forced:    bool (broadcastable) — force insertion (token 0 /
                  attention sink)

    Returns (selected, slot, new_scores, new_idx):
      selected: (...,) bool — whether the token entered the set
      slot:     (...,) int  — which slot it replaced (valid where selected)
    """
    min_slot = jnp.argmin(cache_scores, axis=-1)                   # (...,)
    min_score = jnp.min(cache_scores, axis=-1)
    selected = jnp.logical_or(new_score > min_score, is_forced)
    slot = min_slot
    new_scores = jnp.where(
        jax.nn.one_hot(slot, cache_scores.shape[-1], dtype=bool) & selected[..., None],
        new_score[..., None], cache_scores)
    new_idx = jnp.where(
        jax.nn.one_hot(slot, cache_idx.shape[-1], dtype=bool) & selected[..., None],
        jnp.asarray(new_pos)[..., None].astype(cache_idx.dtype), cache_idx)
    return selected, slot, new_scores, new_idx
