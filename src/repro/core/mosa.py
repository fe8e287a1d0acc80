"""Mixture of Sparse Attention — the paper's core layer.

Per head: router scores r = sigmoid(X W^r); expert-choice top-k token
selection; Q/K/V/O computed *only* for the selected tokens; attention over the
k x k submatrix with the index-derived causal mask (I_i >= I_j) and RoPE at
the original positions; outputs scaled by the router score (this carries the
router's gradient) and scatter-added back to the full sequence.

Complexity per head: O(k^2 + T) versus O(T^2) dense.

Implementation notes (TPU adaptation — see DESIGN.md §3):
  * all shapes static (expert-choice: exactly k per head);
  * indices sorted ascending → the mask is effectively lower-triangular and
    the scatter-add back to the sequence touches memory in order;
  * heads are batched into single einsums over an explicit head axis, which
    shards over the `model` mesh axis (head-parallel TP);
  * the inner attention can run through the Pallas kernel (`impl="pallas"`)
    or the fused-XLA reference path (`impl="einsum"`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import ad_checkpoint

from repro.configs.base import MoSAConfig
from repro.core import rope as rope_lib
from repro.dist import hints
from repro.core.kv_cache import MoSABlockKVCache, MoSAKVCache
from repro.core.router import (ATTEND, UPKEEP, ExpertChoiceRouter,
                               block_pool_scores, expand_block_index,
                               select_topk, selection_mask,
                               streaming_topk_update)
from repro.nn.layers import _trunc_normal
from repro.nn.module import logical

NEG_INF = -1e30


def _per_head(x, w, cd):
    """Per-head projection of gathered rows: (B, H, n, a) x (H, a, c) ->
    (B, H, n, c) in ``cd`` with fp32 accumulation.  The head axis is the
    contraction's batch dimension and leads both operands — the canonical
    batched-matmul layout, which every backend runs for bf16 inputs with an
    fp32 result."""
    y = jnp.einsum("hbna,hac->hbnc", x.astype(cd).swapaxes(0, 1),
                   w.astype(cd), preferred_element_type=jnp.float32)
    return y.swapaxes(0, 1).astype(cd)


def insert_sorted(cache: MoSAKVCache, selected, slot, k_new, v_new, score,
                  pos):
    """Write one decoded token into each head's stored set, keeping the set
    sorted by position with empty slots last (DESIGN §5).

    ``selected`` / ``slot`` (B, H) are ``streaming_topk_update``'s verdict,
    ``k_new`` / ``v_new`` (B, H, d), ``score`` (B, H) and ``pos`` (B, H) the
    token's key, value, router score and position.  Returns the new
    ``(k, v, scores, idx)``.

    No sort is needed: the set is sorted with empties last, ``argmin`` takes
    the first empty slot while the set is not full, and ``pos`` is larger
    than every stored position.  So a set that is not full takes the token
    at ``slot`` and nothing moves; a full set drops ``slot``, shifts slots
    ``slot+1 .. k-1`` down by one and takes the token at ``k-1``.  Each array
    is read once and written once by an elementwise select between itself
    and its one-slot shift — the same values, in the same order, that a
    re-sort by position would give."""
    kc = cache.idx.shape[-1]
    j = jax.lax.broadcasted_iota(jnp.int32, cache.idx.shape, 2)  # (B,H,k)
    last = jnp.where(cache.idx[..., -1] >= 0, kc - 1, slot)[..., None]
    sel = selected[..., None]
    shift = sel & (slot[..., None] <= j) & (j < last)
    put = sel & (j == last)

    def move(a, new, shift, put):
        down = jnp.concatenate([a[:, :, 1:], a[:, :, -1:]], 2)
        return jnp.where(put, new[:, :, None], jnp.where(shift, down, a))

    return (move(cache.k, k_new, shift[..., None], put[..., None]),
            move(cache.v, v_new, shift[..., None], put[..., None]),
            move(cache.scores, score, shift, put),
            move(cache.idx, pos.astype(cache.idx.dtype), shift, put))


@dataclasses.dataclass(frozen=True)
class MoSAAttention:
    d_model: int
    cfg: MoSAConfig
    rope_theta: float = 10000.0
    rotary_frac: float = 0.5        # paper rotates half the dims
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32
    impl: str = "einsum"            # einsum | pallas

    @property
    def router(self):
        return ExpertChoiceRouter(self.d_model, self.cfg.n_mosa_heads,
                                  self.param_dtype)

    def init(self, key):
        c = self.cfg
        kr, kq, kk, kv, ko = jax.random.split(key, 5)
        H, h, d = c.n_mosa_heads, self.d_model, c.d_head
        std = h ** -0.5
        return {
            "router": self.router.init(kr),
            "wq": _trunc_normal(kq, (H, h, d), std, self.param_dtype),
            "wk": _trunc_normal(kk, (H, h, d), std, self.param_dtype),
            "wv": _trunc_normal(kv, (H, h, d), std, self.param_dtype),
            "wo": _trunc_normal(ko, (H, d, h), d ** -0.5, self.param_dtype),
        }

    def specs(self):
        return {
            "router": self.router.specs(),
            "wq": logical("mosa_heads", "embed", None),
            "wk": logical("mosa_heads", "embed", None),
            "wv": logical("mosa_heads", "embed", None),
            "wo": logical("mosa_heads", None, "embed"),
        }

    def k_for(self, T: int) -> int:
        """Paper §3.5: k = max(floor(T / rho), min_k), capped at T.
        With k_fixed > 0 (paper §3.4 long-sequence mode): constant k."""
        if self.cfg.k_fixed > 0:
            return min(self.cfg.k_fixed, T)
        return max(min(T // self.cfg.sparsity, T), min(self.cfg.min_k, T))

    def kb_for(self, T: int) -> int:
        """Block-choice selection width: ``ceil(k_for(T) / sel_block_size)``
        blocks, capped at the number of blocks in the sequence.  At
        ``sel_block_size=1`` this is exactly ``k_for(T)`` — the token-choice
        equivalence (DESIGN §10)."""
        bs = self.cfg.sel_block_size
        return min(-(-self.k_for(T) // bs), -(-T // bs))

    # ------------------------------------------------------------------ train
    def __call__(self, params, x, positions=None, valid=None, segments=None):
        """x: (B, T, h) -> (B, T, h).  Full MoSA layer (all heads).

        ``valid``: optional (B, T) bool marking right-pad tokens False
        (bucketed serving prefill, DESIGN §7).  Unlike causal dense
        attention, expert-choice selection is NOT causal — an attended pad
        would steal top-k slots from real tokens — so invalid tokens' router
        scores are masked below the sigmoid range (to -1.0, finite so no
        NaN can leak through the 0 * -inf corner), which keeps them out of
        every head's selection whenever k real candidates exist; selected
        overflow slots (k > real tokens) are scaled to zero contribution.

        ``segments``: optional (B, T) int32 document ids for PACKED training
        rows (data/pipeline.py packs multiple docs back to back).  The k x k
        attention additionally requires seg_q == seg_k, so no probability
        mass ever crosses a document boundary; expert-choice selection stays
        row-global (static k per head — the expert-choice budget is a row
        property, exactly like the non-causality of selection itself, see
        DESIGN §9).  Pass per-doc ``positions`` alongside so RoPE restarts
        at every boundary.  ``segments=None`` is bit-for-bit the old path.
        """
        if self.cfg.selection_granularity == "block":
            return self._call_block(params, x, positions, valid, segments)
        c, cd = self.cfg, self.compute_dtype
        B, T, h = x.shape
        H, d = c.n_mosa_heads, c.d_head
        k = self.k_for(T)

        scores = self.router.scores(params["router"], x)          # (B,H,T) fp32
        if valid is not None:
            scores = jnp.where(valid[:, None, :], scores, -1.0)
        r, idx = select_topk(scores, k, c.force_first_token)      # (B,H,k)
        if valid is not None:
            r = jnp.where(r > 0.0, r, 0.0)  # overflow pads: zero output

        if positions is None:
            pos_sel = idx
        else:
            base = positions if positions.ndim == 2 else positions[0]
            pos_sel = jnp.take_along_axis(base[:, None], idx, axis=-1)

        # Gather selected tokens: (B, H, k, h).  vmap over the batch keeps B
        # a scatter/gather *batching* dim for GSPMD — explicit batch indices
        # made it replicate B and all-reduce 16 GiB buffers per layer
        # (§Perf cell-2 it.8).
        xs = jax.vmap(lambda xb, ib: xb[ib])(x.astype(cd), idx)
        # checkpoint_name: under remat="mosa" (train/step.py) the gathered
        # activations and the selection are SAVED while projections and the
        # kxk attention recompute — the gather/scatter pair is the one part
        # of this layer whose recompute is memory-bound, not FLOP-bound.
        xs = ad_checkpoint.checkpoint_name(xs, "mosa_gather")
        r = ad_checkpoint.checkpoint_name(r, "mosa_router")

        q = _per_head(xs, params["wq"], cd)
        kk = _per_head(xs, params["wk"], cd)
        v = _per_head(xs, params["wv"], cd)
        q = rope_lib.apply_rope(q, pos_sel, self.rope_theta, self.rotary_frac)
        kk = rope_lib.apply_rope(kk, pos_sel, self.rope_theta, self.rotary_frac)

        seg_sel = None
        if segments is not None:
            seg_sel = jax.vmap(lambda sb, ib: sb[ib])(
                segments.astype(jnp.int32), idx)                  # (B,H,k)

        if self.impl == "pallas":
            from repro.kernels import ops as kops
            att = kops.mosa_attention(q, kk, v, idx, r.astype(jnp.float32),
                                      seg=seg_sel)
        else:
            att = self._einsum_attention(q, kk, v, idx, r, seg=seg_sel)

        # Per-head output projection, then scatter-add to original positions
        # (vmap'd over batch — see gather note above).
        y_heads = _per_head(att, params["wo"], cd)

        def scatter_one(yh, ib):
            return jnp.zeros((T, h), cd).at[ib.reshape(-1)].add(
                yh.reshape(-1, h))

        y = jax.vmap(scatter_one)(y_heads, idx)
        # partial head-contributions combine into the seq-sharded residual:
        # constraining here lets GSPMD emit a reduce-scatter, not all-reduce
        y = hints.constrain(y, ("dp", "tp", None))
        return y

    def _einsum_attention(self, q, k, v, idx, r, seg=None):
        """Reference attention over selected tokens.  All inputs (B,H,k,*).
        ``seg``: optional (B,H,k) segment ids of the selected tokens — packed
        rows additionally mask cross-segment pairs."""
        scale = self.cfg.d_head ** -0.5
        s = jnp.einsum("bnqd,bnkd->bnqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        mask = selection_mask(idx, idx)                            # (B,H,k,k)
        if seg is not None:
            mask &= seg[..., :, None] == seg[..., None, :]
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        att = jnp.einsum("bnqk,bnkd->bnqd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        # Router scaling — the router's gradient path.
        return att * r[..., None]

    # ----------------------------------------------------- block-choice train
    def _call_block(self, params, x, positions=None, valid=None,
                    segments=None):
        """Block-choice forward (DESIGN §10): expert-choice top-k over KV
        BLOCKS of ``sel_block_size`` tokens.  A block's router score is the
        mean of its token scores (``block_pool_scores``); the selected
        blocks' tokens are gathered as contiguous runs (the paged-allocator
        memory motion) and attend under the position-causal mask; outputs
        are scaled by the BLOCK score (the router's gradient path, summed
        over the block by the VJP).

        At ``sel_block_size=1`` every step below is the bitwise identity
        with ``__call__``'s token path — the maintained invariant
        ``tests/test_block_choice.py`` locks down.  ``force_first_token``
        generalizes to forcing block 0 (which contains token 0)."""
        c, cd = self.cfg, self.compute_dtype
        B, T, h = x.shape
        H, d = c.n_mosa_heads, c.d_head
        bs = c.sel_block_size
        kb = self.kb_for(T)

        scores = self.router.scores(params["router"], x)          # (B,H,T)
        if valid is not None:
            scores = jnp.where(valid[:, None, :], scores, -1.0)
        bsc = block_pool_scores(scores, bs)                       # (B,H,NBt)
        rblk, bidx = select_topk(bsc, kb, c.force_first_token)    # (B,H,kb)
        if valid is not None:
            rblk = jnp.where(rblk > 0.0, rblk, 0.0)  # all-pad blocks: zero

        pos = expand_block_index(bidx, bs, T)         # (B,H,kb*bs); -1 = pad
        posc = jnp.clip(pos, 0, T - 1)
        if positions is None:
            pos_rope = posc
        else:
            base = positions if positions.ndim == 2 else positions[0]
            pos_rope = jnp.take_along_axis(base[:, None], posc, axis=-1)

        xs = jax.vmap(lambda xb, ib: xb[ib])(x.astype(cd), posc)
        xs = ad_checkpoint.checkpoint_name(xs, "mosa_gather")
        rblk = ad_checkpoint.checkpoint_name(rblk, "mosa_router")

        q = _per_head(xs, params["wq"], cd)
        kk = _per_head(xs, params["wk"], cd)
        v = _per_head(xs, params["wv"], cd)
        q = rope_lib.apply_rope(q, pos_rope, self.rope_theta, self.rotary_frac)
        kk = rope_lib.apply_rope(kk, pos_rope, self.rope_theta,
                                 self.rotary_frac)

        seg_sel = None
        if segments is not None:
            seg_sel = jax.vmap(lambda sb, ib: sb[ib])(
                segments.astype(jnp.int32), posc)                 # (B,H,kb*bs)

        if self.impl == "pallas":
            from repro.kernels import ops as kops
            att = kops.mosa_block_attention(q, kk, v, bidx,
                                            rblk.astype(jnp.float32),
                                            sel_block_size=bs, T=T,
                                            seg=seg_sel)
        else:
            r_tok = jnp.broadcast_to(rblk[..., None],
                                     (B, H, kb, bs)).reshape(B, H, kb * bs)
            att = self._einsum_block_attention(q, kk, v, pos, r_tok,
                                               seg=seg_sel)

        y_heads = _per_head(att, params["wo"], cd)

        tgt = jnp.where(pos >= 0, pos, T)             # T -> dropped

        def scatter_one(yh, tb):
            return jnp.zeros((T, h), cd).at[tb.reshape(-1)].add(
                yh.reshape(-1, h), mode="drop")

        y = jax.vmap(scatter_one)(y_heads, tgt)
        y = hints.constrain(y, ("dp", "tp", None))
        return y

    def _einsum_block_attention(self, q, k, v, pos, r_tok, seg=None):
        """Reference attention over block-expanded tokens.  ``pos``: (B,H,S)
        expanded token positions (-1 = empty/ragged-tail row); ``r_tok``:
        (B,H,S) per-token copy of the BLOCK score.  Mirrors
        ``_einsum_attention`` exactly (same softmax form), plus the
        invalid-key mask and invalid-row zeroing the -1 sentinel needs —
        both bitwise no-ops at ``sel_block_size=1``."""
        scale = self.cfg.d_head ** -0.5
        ok = pos >= 0
        s = jnp.einsum("bnqd,bnkd->bnqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        mask = selection_mask(pos, pos) & ok[..., None, :]
        if seg is not None:
            mask &= seg[..., :, None] == seg[..., None, :]
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        att = jnp.einsum("bnqk,bnkd->bnqd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return att * r_tok[..., None] * ok[..., None]

    def routing_stats(self, params, x):
        """Diagnostics: score stats + head-overlap (for logging)."""
        B, T, _ = x.shape
        k = self.k_for(T)
        scores = self.router.scores(params["router"], x)
        r, idx = select_topk(scores, k, self.cfg.force_first_token)
        sel = jax.nn.one_hot(idx, T, dtype=jnp.float32).sum(2)      # (B,H,T)
        coverage = (sel.sum(1) > 0).mean()       # fraction of tokens any head picks
        load = sel.sum(1).mean() / k             # avg #heads per token / k
        return {"score_mean": scores.mean(), "score_std": scores.std(),
                "coverage": coverage, "load": load}

    def router_health(self, params, x):
        """Per-step router health for the train loop (see
        ``repro.core.router.router_health_stats``): selection entropy,
        token-drop rate, head utilization.

        Granularity-aware: block-choice layers (DESIGN §10) are scored in
        BLOCK space — the units the router actually ranks — so drop_rate is
        the fraction of pooled blocks no head selects and entropy is
        normalized by ``log NB``; token-space stats would report a spurious
        ``1 - 1/bs`` floor of "dropped" tokens inside selected blocks."""
        from repro.core.router import (block_pool_scores,
                                       router_health_stats)
        B, T, _ = x.shape
        scores = self.router.scores(params["router"], x)
        if self.cfg.selection_granularity == "block":
            bs = self.cfg.sel_block_size
            bsc = block_pool_scores(scores, bs)
            r, bidx = select_topk(bsc, self.kb_for(T),
                                  self.cfg.force_first_token)
            return router_health_stats(r, bidx, bsc.shape[-1])
        r, idx = select_topk(scores, self.k_for(T),
                             self.cfg.force_first_token)
        return router_health_stats(r, idx, T)

    # ---------------------------------------------------------------- serving
    def prefill(self, params, x, cache: MoSAKVCache, positions=None,
                valid=None):
        """Run the prompt through training-style selection and fill the cache
        with each head's top candidates (the prompt is fully known, so
        non-autoregressive selection is exact here).

        The cache is filled WIDE — ``min(capacity, T)`` candidates, not just
        the ``k_for(T)`` the output uses.  Width costs nothing (the slots
        exist either way) and is what makes chunked / continued prefill
        (``prefill_past``) EXACT under the growing ``k = T/rho`` schedule: a
        token in the final top-``k_for(T_total)`` has prefix rank at most
        ``k_for(T_total) <= capacity``, so a capacity-wide boundary never
        drops it (DESIGN §9).  Under a constant-k schedule capacity equals
        ``k_fixed`` and nothing changes.  The layer OUTPUT ``y`` still uses
        exactly the training-time ``k_for(T)`` selection.

        ``valid`` (B, T) bool masks right-pad tokens out of the selection
        (scores to -1.0, see ``__call__``); slots that still land on a pad
        (k exceeds the real token count) are stored as the empty-slot
        sentinels (``scores=-inf``, ``idx=-1``) — right-pads have the
        LARGEST indices, so after the ascending-idx sort they fall exactly
        where the empty-slots-last invariant wants them."""
        if self.cfg.selection_granularity == "block":
            return self._prefill_block(params, x, cache, positions, valid)
        c, cd = self.cfg, self.compute_dtype
        B, T, h = x.shape
        k_cache = cache.k.shape[2]
        k = min(k_cache, T)

        y = self(params, x, positions, valid)

        scores = self.router.scores(params["router"], x)
        if valid is not None:
            scores = jnp.where(valid[:, None, :], scores, -1.0)
        r, idx = select_topk(scores, k, c.force_first_token)
        xs = jax.vmap(lambda xb, ib: xb[ib])(x.astype(cd), idx)
        kk = _per_head(xs, params["wk"], cd)
        kk = rope_lib.apply_rope(kk, idx, self.rope_theta, self.rotary_frac)
        v = _per_head(xs, params["wv"], cd)
        if valid is not None:
            sel_ok = r > 0.0
            r = jnp.where(sel_ok, r, -jnp.inf)
            idx = jnp.where(sel_ok, idx, -1)
        pad = k_cache - k
        if pad:
            kk = jnp.pad(kk, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
            r = jnp.pad(r, ((0, 0), (0, 0), (0, pad)), constant_values=-jnp.inf)
            idx = jnp.pad(idx, ((0, 0), (0, 0), (0, pad)), constant_values=-1)
        nv = (jnp.full((B,), T, jnp.int32) if valid is None
              else valid.sum(-1).astype(jnp.int32))
        cache = MoSAKVCache(kk, v, r.astype(jnp.float32), idx,
                            cache.length + nv)
        return y, cache

    def _prefill_block(self, params, x, cache: MoSABlockKVCache,
                       positions=None, valid=None):
        """Block-choice prefill: training-style block selection fills the
        candidate set with the top ``CB`` COMPLETED blocks (their mean
        scores are final, so the stored state is exactly what streaming
        decode over the same prompt would converge to); the trailing
        partial block rides in the dedicated current slot with its running
        score sum (DESIGN §10).  Storage is capacity-wide for the same
        exactness-at-boundaries argument as the token path (``prefill``).
        """
        c, cd = self.cfg, self.compute_dtype
        B, T, h = x.shape
        H, d = c.n_mosa_heads, c.d_head
        bs = cache.block_size
        CB = cache.n_cand
        nbt = -(-T // bs)
        kcb = min(CB, nbt)
        INT_MAX = jnp.iinfo(jnp.int32).max

        y = self(params, x, positions, valid)

        scores = self.router.scores(params["router"], x)
        if valid is not None:
            scores = jnp.where(valid[:, None, :], scores, -1.0)
        nv = (jnp.full((B,), T, jnp.int32) if valid is None
              else valid.sum(-1).astype(jnp.int32))
        cbf = nv // bs                                    # completed blocks

        bsc = block_pool_scores(scores, bs)               # (B,H,NBt)
        done = jnp.arange(nbt)[None, None, :] < cbf[:, None, None]
        r, bidx = select_topk(jnp.where(done, bsc, -jnp.inf), kcb,
                              c.force_first_token)
        sel_ok = r > 0.0          # non-completed / forced-but-absent drop out
        r_st = jnp.where(sel_ok, r, -jnp.inf)
        b_st = jnp.where(sel_ok, bidx, -1)
        order = jnp.argsort(jnp.where(b_st < 0, INT_MAX, b_st), -1)
        b_st = jnp.take_along_axis(b_st, order, -1)
        r_st = jnp.take_along_axis(r_st, order, -1)
        if CB > kcb:
            pad = CB - kcb
            r_st = jnp.pad(r_st, ((0, 0), (0, 0), (0, pad)),
                           constant_values=-jnp.inf)
            b_st = jnp.pad(b_st, ((0, 0), (0, 0), (0, pad)),
                           constant_values=-1)

        # Whole-prompt K/V, roped at original positions (cf. ``prefill``).
        idx_all = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32),
                                   (B, H, T))
        k_all = jnp.einsum("bth,nhd->bntd", x.astype(cd),
                           params["wk"].astype(cd),
                           preferred_element_type=jnp.float32).astype(cd)
        v_all = jnp.einsum("bth,nhd->bntd", x.astype(cd),
                           params["wv"].astype(cd),
                           preferred_element_type=jnp.float32).astype(cd)
        k_all = rope_lib.apply_rope(k_all, idx_all, self.rope_theta,
                                    self.rotary_frac)

        off = jnp.arange(bs, dtype=jnp.int32)
        # candidate rows: contiguous runs of the selected completed blocks
        rows_pos = (b_st[..., None] * bs + off).reshape(B, H, CB * bs)
        row_ok = jnp.broadcast_to((b_st >= 0)[..., None],
                                  (B, H, CB, bs)).reshape(B, H, CB * bs)
        rowsc = jnp.clip(rows_pos, 0, T - 1)
        k_rows = jnp.take_along_axis(k_all, rowsc[..., None], axis=2)
        v_rows = jnp.take_along_axis(v_all, rowsc[..., None], axis=2)
        pos_rows = jnp.where(row_ok, rows_pos, -1)

        # current (partial) block: tokens [cbf*bs, nv)
        cur_pos = cbf[:, None] * bs + off                 # (B, bs)
        cur_ok = cur_pos < nv[:, None]
        cur_posb = jnp.broadcast_to(cur_pos[:, None], (B, H, bs))
        cur_posc = jnp.clip(cur_posb, 0, T - 1)
        cur_k = jnp.take_along_axis(k_all, cur_posc[..., None], axis=2)
        cur_v = jnp.take_along_axis(v_all, cur_posc[..., None], axis=2)
        cur_pos_st = jnp.where(cur_ok[:, None], cur_posb, -1)
        has_cur = (nv % bs) > 0                           # (B,)
        bidx_cur = jnp.broadcast_to(
            jnp.where(has_cur, cbf, -1)[:, None, None], (B, H, 1))
        t_ar = jnp.arange(T, dtype=jnp.int32)
        in_cur = ((t_ar[None] >= cbf[:, None] * bs) &
                  (t_ar[None] < nv[:, None]))             # (B, T)
        bsum = jnp.sum(jnp.where(in_cur[:, None], scores, 0.0), axis=-1)

        new = MoSABlockKVCache(
            jnp.concatenate([k_rows, cur_k], 2).astype(cache.k.dtype),
            jnp.concatenate([v_rows, cur_v], 2).astype(cache.v.dtype),
            jnp.concatenate([pos_rows, cur_pos_st], 2),
            jnp.concatenate([r_st.astype(jnp.float32),
                             jnp.full((B, H, 1), -jnp.inf, jnp.float32)], -1),
            jnp.concatenate([b_st, bidx_cur], -1),
            bsum.astype(jnp.float32),
            cache.length + nv)
        return y, new

    def prefill_past(self, params, x, cache: MoSAKVCache, positions=None,
                     valid=None):
        """Continued prefill: extend a restored prefix cache with a prompt
        suffix, reproducing training-style selection over the full prompt
        (DESIGN §7).

        Why this is EXACT — for every chunk split, every schedule: a token
        in the one-shot top-``k_for(T_total)`` has, within any prefix, rank
        at most ``k_for(T_total) <= capacity``; since ``prefill`` and this
        method both store the CAPACITY-wide top of their candidate union at
        every boundary, such a token is never dropped at a boundary, so the
        union of {cached entries} and {suffix tokens} is always a superset
        of the true selection.  (Scores, original-position RoPE, and K/V of
        cached entries are identical to what one-shot prefill computes, and
        the ascending-idx slot order makes top-k tie-breaking match too.)
        This covers the constant-k schedule (``k_fixed``, paper §3.4) AND
        the growing ``k = T / rho`` schedule — the former stored-width
        clamp to the chunk-local ``k_eff`` was the growing-k
        under-selection bug (DESIGN §9).  The output selection width
        matches one-shot prefill: ``min(k_for(L0 + T_valid), capacity)``,
        computed on traced lengths by rank-masking the union top-k (which
        ``lax.top_k`` already orders by score) in the suffix-output
        attention only.  Suffix-token outputs attend the final selection
        under the usual index-causal mask — identical math to ``__call__``
        restricted to suffix queries.  (The forced first token rides
        along: its cache entry gets a selection boost, its stored score
        stays real.)
        """
        if self.cfg.selection_granularity == "block":
            return self._prefill_past_block(params, x, cache, positions,
                                            valid)
        c, cd = self.cfg, self.compute_dtype
        B, T, h = x.shape
        H, d = c.n_mosa_heads, c.d_head
        kc = cache.k.shape[2]
        L0 = cache.length                                       # (B,)
        nv = (jnp.full((B,), T, jnp.int32) if valid is None
              else valid.sum(-1).astype(jnp.int32))

        if positions is None:
            base_pos = L0[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        else:
            base_pos = positions if positions.ndim == 2 else positions[0]
        idx_new = jnp.broadcast_to(base_pos[:, None], (B, H, T))

        scores_new = self.router.scores(params["router"], x)    # (B,H,T)
        if valid is not None:
            scores_new = jnp.where(valid[:, None, :], scores_new, -1.0)

        q_all = jnp.einsum("bth,nhd->bntd", x.astype(cd),
                           params["wq"].astype(cd),
                           preferred_element_type=jnp.float32).astype(cd)
        k_all = jnp.einsum("bth,nhd->bntd", x.astype(cd),
                           params["wk"].astype(cd),
                           preferred_element_type=jnp.float32).astype(cd)
        v_all = jnp.einsum("bth,nhd->bntd", x.astype(cd),
                           params["wv"].astype(cd),
                           preferred_element_type=jnp.float32).astype(cd)
        q_all = rope_lib.apply_rope(q_all, idx_new, self.rope_theta,
                                    self.rotary_frac)
        k_all = rope_lib.apply_rope(k_all, idx_new, self.rope_theta,
                                    self.rotary_frac)

        # Union candidates: cached prefix top-k (already roped at original
        # positions) + every suffix token.  Disjoint by construction
        # (cached idx < L0 <= suffix idx).
        scores_cat = jnp.concatenate([cache.scores, scores_new], axis=-1)
        idx_cat = jnp.concatenate([cache.idx, idx_new], axis=-1)
        k_cat = jnp.concatenate([cache.k.astype(cd), k_all], axis=2)
        v_cat = jnp.concatenate([cache.v.astype(cd), v_all], axis=2)

        sel_scores = scores_cat
        if c.force_first_token:
            sel_scores = jnp.where(idx_cat == 0, 2.0, sel_scores)  # boost
        with jax.named_scope(UPKEEP):
            _, j = jax.lax.top_k(sel_scores, kc)
            r_sel = jnp.take_along_axis(scores_cat, j, axis=-1)
            idx_sel = jnp.take_along_axis(idx_cat, j, axis=-1)
            k_sel = jnp.take_along_axis(k_cat, j[..., None], axis=2)
            v_sel = jnp.take_along_axis(v_cat, j[..., None], axis=2)

        sel_ok = r_sel > 0.0          # -inf empties / -1.0 pads drop out
        # One-shot selection width on traced lengths: top_k ordered the
        # union by (boosted) score, so rank == position.  The rank mask
        # gates ONLY the suffix-output attention (y must reproduce the
        # one-shot k_for(total) selection); STORAGE keeps the full
        # capacity-wide union — clobbering stored entries down to k_eff is
        # exactly the growing-k under-selection bug: a later chunk's larger
        # k_for(total') could legally re-admit a prefix token this chunk's
        # k_eff would have discarded.  Capacity-wide storage at every
        # boundary makes chunked == one-shot EXACT (see ``prefill``).
        total = L0 + nv
        if c.k_fixed > 0:
            k_eff = jnp.minimum(c.k_fixed, total)
        else:
            k_eff = jnp.maximum(jnp.minimum(total // c.sparsity, total),
                                jnp.minimum(c.min_k, total))
        k_eff = jnp.minimum(k_eff, kc)
        rank_ok = sel_ok & (jnp.arange(kc) < k_eff[:, None, None])
        r_st = jnp.where(sel_ok, r_sel, -jnp.inf)
        idx_st = jnp.where(sel_ok, idx_sel, -1)
        with jax.named_scope(UPKEEP):
            order = jnp.argsort(jnp.where(
                idx_st < 0, jnp.iinfo(jnp.int32).max, idx_st), -1)
            idx_st = jnp.take_along_axis(idx_st, order, -1)
            r_st = jnp.take_along_axis(r_st, order, -1)
            rank_ok = jnp.take_along_axis(rank_ok, order, -1)
            k_sel = jnp.take_along_axis(k_sel, order[..., None], 2)
            v_sel = jnp.take_along_axis(v_sel, order[..., None], 2)

        # Suffix-query outputs over the final selection (index-causal mask,
        # router-score scaling) — __call__ restricted to suffix queries.
        # Queries AND keys are rank-masked to the one-shot width.
        is_suffix = rank_ok & (idx_st >= L0[:, None, None]) & (idx_st >= 0)
        t_j = jnp.clip(idx_st - L0[:, None, None], 0, T - 1)
        with jax.named_scope(UPKEEP):
            q_sel = jnp.take_along_axis(q_all, t_j[..., None], axis=2)
        with jax.named_scope(ATTEND):
            s = jnp.einsum("bnqd,bnkd->bnqk", q_sel, k_sel,
                           preferred_element_type=jnp.float32) * (d ** -0.5)
            mask = (selection_mask(idx_st, idx_st)
                    & (idx_st >= 0)[:, :, None, :] & rank_ok[:, :, None, :])
            s = jnp.where(mask, s, NEG_INF)
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
            att = jnp.einsum("bnqk,bnkd->bnqd", p.astype(cd), v_sel,
                             preferred_element_type=jnp.float32)
        r_q = jnp.where(is_suffix, jnp.maximum(r_st, 0.0), 0.0)
        att = att * r_q[..., None]
        y_heads = _per_head(att, params["wo"], cd)

        tgt = jnp.where(is_suffix, t_j, T)          # T -> dropped

        def scatter_one(yh, tb):
            return jnp.zeros((T, h), cd).at[tb.reshape(-1)].add(
                yh.reshape(-1, h), mode="drop")

        y = jax.vmap(scatter_one)(y_heads, tgt)
        y = hints.constrain(y, ("dp", "tp", None))

        cache = MoSAKVCache(k_sel.astype(cache.k.dtype),
                            v_sel.astype(cache.v.dtype),
                            r_st.astype(jnp.float32), idx_st, L0 + nv)
        return y, cache

    def _prefill_past_block(self, params, x, cache: MoSABlockKVCache,
                            positions=None, valid=None):
        """Block-choice continued prefill (DESIGN §10).

        Selection state is block-granular, so at any BLOCK-ALIGNED boundary
        the cache state is exactly what a longer one-shot prefill would
        hold for the same prefix: candidate blocks are completed (their
        mean scores final and immutable — a suffix can never change them)
        and the current slot is empty.  This is what makes paged MoSA
        prefix hits exact — the prefix-cache trie snapshots at block
        multiples (``sel_block_size`` defaults to the paged block size),
        closing the token path's chunk-causal gap.

        Union exactness mirrors the token path: a block in the final
        top-``kb_for(total)`` has prefix rank <= ``kb_for(total) <= CB``,
        so capacity-wide candidate storage at every boundary never drops
        it.  The suffix may straddle the cache's partial current block:
        its running ``bsum`` carries the head of the straddled block, and
        the old current rows are stitched in front of the suffix K/V when
        the block finally completes.

        The suffix-token OUTPUTS reproduce one-shot ``__call__`` over the
        whole prompt restricted to suffix queries: block scores of the
        union pool (old candidates + every suffix-touched block, with the
        trailing partial block at its one-shot partial mean), force boost
        on block 0, rank-masked to the traced one-shot width
        ``kb_for(L0 + nv)``.
        """
        c, cd = self.cfg, self.compute_dtype
        B, T, h = x.shape
        H, d = c.n_mosa_heads, c.d_head
        bs = cache.block_size
        CB = cache.n_cand
        NSB = (T + bs - 1) // bs + 1  # suffix can straddle this many blocks
        INT_MAX = jnp.iinfo(jnp.int32).max
        off = jnp.arange(bs, dtype=jnp.int32)
        L0 = cache.length                                       # (B,)
        nv = (jnp.full((B,), T, jnp.int32) if valid is None
              else valid.sum(-1).astype(jnp.int32))
        total = L0 + nv
        base0 = L0 // bs                                        # (B,)

        if positions is None:
            base_pos = L0[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        else:
            base_pos = positions if positions.ndim == 2 else positions[0]
        idx_new = jnp.broadcast_to(base_pos[:, None], (B, H, T))

        scores_new = self.router.scores(params["router"], x)    # (B,H,T)
        vmask = (jnp.ones((B, T), bool) if valid is None else valid)

        q_all = jnp.einsum("bth,nhd->bntd", x.astype(cd),
                           params["wq"].astype(cd),
                           preferred_element_type=jnp.float32).astype(cd)
        k_all = jnp.einsum("bth,nhd->bntd", x.astype(cd),
                           params["wk"].astype(cd),
                           preferred_element_type=jnp.float32).astype(cd)
        v_all = jnp.einsum("bth,nhd->bntd", x.astype(cd),
                           params["wv"].astype(cd),
                           preferred_element_type=jnp.float32).astype(cd)
        q_all = rope_lib.apply_rope(q_all, idx_new, self.rope_theta,
                                    self.rotary_frac)
        k_all = rope_lib.apply_rope(k_all, idx_new, self.rope_theta,
                                    self.rotary_frac)

        # --- per-relative-block score sums/counts over the suffix tokens
        rel = base_pos // bs - base0[:, None]                   # (B,T)
        oh = (jax.nn.one_hot(rel, NSB, dtype=jnp.float32)
              * vmask[..., None])                               # (B,T,NSB)
        sums = jnp.einsum("bnt,btj->bnj", scores_new, oh)       # (B,H,NSB)
        cnts = oh.sum(1)                                        # (B,NSB)
        carry = (L0 % bs).astype(jnp.float32)                   # (B,)
        is0 = (jnp.arange(NSB) == 0).astype(jnp.float32)        # (NSB,)
        tot_cnt = cnts + is0[None] * carry[:, None]             # (B,NSB)
        tot_sum = sums + is0[None, None] * cache.bsum[..., None]
        blk_end = (base0[:, None] + jnp.arange(NSB) + 1) * bs   # (B,NSB)
        done_new = blk_end <= total[:, None]                    # (B,NSB)
        # one-shot mean: final for completed, partial for the current block
        out_new = jnp.where(tot_cnt[:, None] > 0,
                            tot_sum / jnp.maximum(tot_cnt[:, None], 1.0),
                            -jnp.inf)                           # (B,H,NSB)
        cand_new = jnp.where(done_new[:, None], out_new, -jnp.inf)

        # --- K/V rows of the suffix-touched blocks.  Row (j, o) holds
        # absolute position p = (base0+j)*bs + o: before L0 it comes from
        # the cache's old current slot (the straddled block head), else
        # from the suffix projections.
        p_new = ((base0[:, None, None] + jnp.arange(NSB)[None, :, None]) * bs
                 + off[None, None]).reshape(B, NSB * bs)        # (B,NSB*bs)
        filled = p_new < total[:, None]
        from_old = p_new < L0[:, None]
        t_suf = jnp.clip(p_new - L0[:, None], 0, T - 1)         # (B,NSB*bs)
        old_cur_k = cache.k[:, :, CB * bs:].astype(cd)          # (B,H,bs,d)
        old_cur_v = cache.v[:, :, CB * bs:].astype(cd)
        o_pat = jnp.tile(off, NSB)                              # (NSB*bs,)
        t_sufb = jnp.broadcast_to(t_suf[:, None], (B, H, NSB * bs))
        k_new_rows = jnp.where(
            from_old[:, None, :, None],
            old_cur_k[:, :, o_pat], jnp.take_along_axis(
                k_all, t_sufb[..., None], axis=2))
        v_new_rows = jnp.where(
            from_old[:, None, :, None],
            old_cur_v[:, :, o_pat], jnp.take_along_axis(
                v_all, t_sufb[..., None], axis=2))
        pos_new_rows = jnp.broadcast_to(
            jnp.where(filled, p_new, -1)[:, None], (B, H, NSB * bs))

        # --- union pool: old candidates + suffix-touched blocks (disjoint
        # and ascending in block index by construction)
        P = CB + NSB
        pool_sc = jnp.concatenate([cache.bscore[..., :CB], out_new], -1)
        pool_bi = jnp.concatenate(
            [cache.bidx[..., :CB],
             jnp.broadcast_to((base0[:, None] + jnp.arange(NSB))[:, None],
                              (B, H, NSB)).astype(jnp.int32)], -1)
        pool_k = jnp.concatenate([cache.k[:, :, :CB * bs].astype(cd),
                                  k_new_rows], 2)
        pool_v = jnp.concatenate([cache.v[:, :, :CB * bs].astype(cd),
                                  v_new_rows], 2)
        pool_pos = jnp.concatenate([cache.pos[:, :, :CB * bs],
                                    pos_new_rows], 2)

        # --- candidate STORAGE: capacity-wide top-CB over completed blocks
        stor_sc = jnp.concatenate([cache.bscore[..., :CB], cand_new], -1)
        stor_sel = stor_sc
        if c.force_first_token:
            stor_sel = jnp.where(pool_bi == 0, 2.0, stor_sel)
        _, jst = jax.lax.top_k(stor_sel, CB)
        r_stor = jnp.take_along_axis(stor_sc, jst, -1)
        b_stor = jnp.take_along_axis(pool_bi, jst, -1)
        sel_ok = r_stor > 0.0
        r_stor = jnp.where(sel_ok, r_stor, -jnp.inf)
        b_stor = jnp.where(sel_ok, b_stor, -1)
        order = jnp.argsort(jnp.where(b_stor < 0, INT_MAX, b_stor), -1)
        b_stor = jnp.take_along_axis(b_stor, order, -1)
        r_stor = jnp.take_along_axis(r_stor, order, -1)
        jso = jnp.take_along_axis(jst, order, -1)               # (B,H,CB)
        rows_st = (jso[..., None] * bs + off).reshape(B, H, CB * bs)
        ck = jnp.take_along_axis(pool_k, rows_st[..., None], axis=2)
        cv = jnp.take_along_axis(pool_v, rows_st[..., None], axis=2)
        cp = jnp.take_along_axis(pool_pos, rows_st, -1)
        cp = jnp.where(jnp.broadcast_to((b_stor >= 0)[..., None],
                                        (B, H, CB, bs)).reshape(B, H, CB * bs),
                       cp, -1)

        # --- new current slot: the (possibly still partial) block at total
        cbn = total // bs                                       # (B,)
        jcur = (cbn - base0)[:, None]                           # (B,1)
        rows_cur = jnp.broadcast_to(
            (jcur * bs + off[None])[:, None], (B, H, bs))       # (B,H,bs)
        cur_k = jnp.take_along_axis(k_new_rows, rows_cur[..., None], axis=2)
        cur_v = jnp.take_along_axis(v_new_rows, rows_cur[..., None], axis=2)
        cur_pos = jnp.take_along_axis(pos_new_rows, rows_cur, -1)
        has_cur = (total % bs) > 0                              # (B,)
        bsum_new = jnp.where(
            has_cur[:, None],
            jnp.take_along_axis(
                tot_sum, jnp.broadcast_to(jcur[..., None], (B, H, 1)),
                -1)[..., 0],
            0.0)
        bidx_cur = jnp.broadcast_to(
            jnp.where(has_cur, cbn, -1)[:, None, None], (B, H, 1))

        # --- suffix-query outputs over the rank-masked one-shot selection
        out_sel = pool_sc
        if c.force_first_token:
            out_sel = jnp.where(pool_bi == 0, 2.0, out_sel)
        _, jo = jax.lax.top_k(out_sel, P)                       # full order
        r_o = jnp.take_along_axis(pool_sc, jo, -1)
        b_o = jnp.take_along_axis(pool_bi, jo, -1)
        if c.k_fixed > 0:
            k_eff = jnp.minimum(c.k_fixed, total)
        else:
            k_eff = jnp.maximum(jnp.minimum(total // c.sparsity, total),
                                jnp.minimum(c.min_k, total))
        kb_eff = jnp.minimum((k_eff + bs - 1) // bs, (total + bs - 1) // bs)
        rank_ok = (r_o > 0.0) & (jnp.arange(P) < kb_eff[:, None, None])
        rows_o = (jo[..., None] * bs + off).reshape(B, H, P * bs)
        kk_o = jnp.take_along_axis(pool_k, rows_o[..., None], axis=2)
        vv_o = jnp.take_along_axis(pool_v, rows_o[..., None], axis=2)
        pos_o = jnp.take_along_axis(pool_pos, rows_o, -1)
        ok_row = (jnp.broadcast_to(rank_ok[..., None],
                                   (B, H, P, bs)).reshape(B, H, P * bs)
                  & (pos_o >= 0))
        is_suffix = ok_row & (pos_o >= L0[:, None, None])
        t_j = jnp.clip(pos_o - L0[:, None, None], 0, T - 1)
        q_sel = jnp.take_along_axis(q_all, t_j[..., None], axis=2)
        s = jnp.einsum("bnqd,bnkd->bnqk", q_sel, kk_o,
                       preferred_element_type=jnp.float32) * (d ** -0.5)
        mask = (selection_mask(pos_o, pos_o) & ok_row[:, :, None, :])
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        att = jnp.einsum("bnqk,bnkd->bnqd", p.astype(cd), vv_o,
                         preferred_element_type=jnp.float32)
        r_tok = jnp.broadcast_to(r_o[..., None],
                                 (B, H, P, bs)).reshape(B, H, P * bs)
        r_q = jnp.where(is_suffix, jnp.maximum(r_tok, 0.0), 0.0)
        att = att * r_q[..., None]
        y_heads = _per_head(att, params["wo"], cd)
        tgt = jnp.where(is_suffix, t_j, T)                      # T -> dropped

        def scatter_one(yh, tb):
            return jnp.zeros((T, h), cd).at[tb.reshape(-1)].add(
                yh.reshape(-1, h), mode="drop")

        y = jax.vmap(scatter_one)(y_heads, tgt)
        y = hints.constrain(y, ("dp", "tp", None))

        new = MoSABlockKVCache(
            jnp.concatenate([ck, cur_k], 2).astype(cache.k.dtype),
            jnp.concatenate([cv, cur_v], 2).astype(cache.v.dtype),
            jnp.concatenate([cp, cur_pos], 2),
            jnp.concatenate([r_stor.astype(jnp.float32),
                             jnp.full((B, H, 1), -jnp.inf, jnp.float32)], -1),
            jnp.concatenate([b_stor, bidx_cur], -1),
            bsum_new.astype(jnp.float32),
            total)
        return y, new

    def prefill_packed(self, params, x, cache: MoSAKVCache, meta):
        """Packed multi-segment chunked prefill (DESIGN §9).

        ``x``: (1, C, h) — a flattened chunk of N prompt segments, each
        continuing a different batch row's cache.  ``meta`` is the packed
        layout built by ``TransformerLM.prefill_packed``: ``rows`` (N,)
        batch row per segment (-1 = inactive), ``tok_idx``/``in_seg``
        (N, C) the unpack gather, ``seg_of_tok``/``local_of_tok``/
        ``row_of_tok`` (C,) the scatter-back.

        Expert-choice selection is PER SEGMENT — the chunk is unpacked to a
        (N, C) right-padded batch and run through ``prefill_past`` (whose
        per-row traced ``L0 = cache.length`` and ``valid`` masking already
        express exactly the continued-chunk semantics), then the updated
        rows scatter back into the full B-row cache.  A row may appear at
        most ONCE per chunk (the scheduler guarantees it; duplicate rows
        would race in the write-back).  The MoSA projections run on the
        (N, C) unpacked view — an O(N·C) overhead on an O(k²) side, paid
        for keeping the exact-union selection math in one place.

        Cache-type agnostic: every leaf of ``MoSAKVCache`` AND the
        block-choice ``MoSABlockKVCache`` is batch-major, so the row
        gather / write-back is a ``tree.map``; ``prefill_past`` dispatches
        on the selection granularity internally.
        """
        B = cache.k.shape[0]
        rows = meta["rows"]
        rowc = jnp.clip(rows, 0, B - 1)
        rowd = jnp.where(rows < 0, B, rows)               # drop index
        gc = jax.tree.map(lambda a: a[rowc], cache)
        xs = x[0][meta["tok_idx"]] * meta["in_seg"][..., None].astype(x.dtype)
        y_seg, gc2 = self.prefill_past(params, xs, gc, None, meta["in_seg"])

        cache = jax.tree.map(
            lambda old, new: old.at[rowd].set(new.astype(old.dtype),
                                              mode="drop"), cache, gc2)
        segc = jnp.maximum(meta["seg_of_tok"], 0)
        y = y_seg[segc, meta["local_of_tok"]]             # (C, h)
        y = jnp.where((meta["row_of_tok"] >= 0)[:, None], y, 0.0)
        return y[None].astype(y_seg.dtype), cache

    def _decode_block(self, params, x, cache: MoSABlockKVCache,
                      positions=None):
        """Streaming BLOCK-choice decode (DESIGN §10).

        Sequencing per step (before attention, so the new token can attend
        itself — the ``decode_step`` convention):

          1. write the token's K/V into current-slot row ``t % bs`` and add
             its router score to the running ``bsum``;
          2. if that COMPLETES the block (``(t+1) % bs == 0``): its mean
             score is now final — run ``streaming_topk_update`` over the
             candidate blocks, copy the current rows into the evicted
             slot where selected, re-sort candidates by block index
             (empties last), and reset the current slot;
          3. attend over every valid row (``pos >= 0``) — candidates plus
             the in-progress block;
          4. scale the output by the query block's mean score — final mean
             x selected-flag on completion, the running partial mean
             otherwise (the current block always participates while it is
             being built; at ``sel_block_size=1`` every step completes, so
             this reduces exactly to token-choice's score x selected).
        """
        c, cd = self.cfg, self.compute_dtype
        B, _, h = x.shape
        H, d = c.n_mosa_heads, c.d_head
        bs = cache.block_size
        CB = cache.n_cand
        R = (CB + 1) * bs
        INT_MAX = jnp.iinfo(jnp.int32).max
        t = cache.length if positions is None else positions[:, 0]   # (B,)

        x0 = x[:, 0]
        score = self.router.scores(params["router"], x)[..., 0]      # (B,H)

        q = jnp.einsum("bh,nhd->bnd", x0.astype(cd), params["wq"].astype(cd),
                       preferred_element_type=jnp.float32).astype(cd)
        kk = jnp.einsum("bh,nhd->bnd", x0.astype(cd), params["wk"].astype(cd),
                        preferred_element_type=jnp.float32).astype(cd)
        v = jnp.einsum("bh,nhd->bnd", x0.astype(cd), params["wv"].astype(cd),
                       preferred_element_type=jnp.float32).astype(cd)
        pos_t = jnp.broadcast_to(t[:, None, None], (B, H, 1)).astype(jnp.int32)
        q = rope_lib.apply_rope(q[:, :, None], pos_t, self.rope_theta,
                                self.rotary_frac)[:, :, 0]
        kk = rope_lib.apply_rope(kk[:, :, None], pos_t, self.rope_theta,
                                 self.rotary_frac)[:, :, 0]

        # 1. write into current-slot row t % bs (masked elementwise update —
        #    see DenseKVCache.append for why not dynamic-update-slice)
        row = (CB * bs + t % bs)[:, None]                            # (B,1)
        hit = jax.lax.broadcasted_iota(jnp.int32, (B, R), 1) == row  # (B,R)
        m = hit[:, None, :, None]
        k2 = jnp.where(m, kk[:, :, None].astype(cache.k.dtype), cache.k)
        v2 = jnp.where(m, v[:, :, None].astype(cache.v.dtype), cache.v)
        pos2 = jnp.where(hit[:, None], t[:, None, None].astype(jnp.int32),
                         cache.pos)
        bsum2 = cache.bsum + score                                   # (B,H)
        cur_blk = (t // bs).astype(jnp.int32)                        # (B,)

        # 2. completion: the mean is final — run the block through the
        #    evict-min streaming policy shared with token-choice.
        completed = (t + 1) % bs == 0                                # (B,)
        final = bsum2 / bs                                           # (B,H)
        is_forced = (jnp.asarray(c.force_first_token)
                     & (cur_blk == 0) & completed)[:, None]          # (B,1)
        selected, slot, nbs_, nbi_ = streaming_topk_update(
            cache.bscore[..., :CB], cache.bidx[..., :CB], final,
            jnp.broadcast_to(cur_blk[:, None], (B, H)), is_forced)
        sel_flag = selected & completed[:, None]                     # (B,H)
        cand_sc = jnp.where(completed[:, None, None], nbs_,
                            cache.bscore[..., :CB])
        cand_bi = jnp.where(completed[:, None, None], nbi_,
                            cache.bidx[..., :CB])

        # copy current rows into the evicted slot where the block made it
        cur_k = k2[:, :, CB * bs:]                                   # (B,H,bs,d)
        cur_v = v2[:, :, CB * bs:]
        cur_pos = pos2[:, :, CB * bs:]
        hit_slot = ((jax.lax.broadcasted_iota(jnp.int32, (B, H, CB), 2)
                     == slot[..., None]) & sel_flag[..., None])      # (B,H,CB)
        ck = jnp.where(hit_slot[..., None, None],
                       cur_k[:, :, None], k2[:, :, :CB * bs].reshape(
                           B, H, CB, bs, d))
        cv = jnp.where(hit_slot[..., None, None],
                       cur_v[:, :, None], v2[:, :, :CB * bs].reshape(
                           B, H, CB, bs, d))
        cp = jnp.where(hit_slot[..., None],
                       cur_pos[:, :, None], pos2[:, :, :CB * bs].reshape(
                           B, H, CB, bs))

        # re-sort candidates by block index (empties last)
        order = jnp.argsort(jnp.where(cand_bi < 0, INT_MAX, cand_bi), -1)
        cand_bi = jnp.take_along_axis(cand_bi, order, -1)
        cand_sc = jnp.take_along_axis(cand_sc, order, -1)
        row_perm = (order[..., None] * bs +
                    jnp.arange(bs, dtype=jnp.int32)).reshape(B, H, CB * bs)
        ck = jnp.take_along_axis(ck.reshape(B, H, CB * bs, d),
                                 row_perm[..., None], axis=2)
        cv = jnp.take_along_axis(cv.reshape(B, H, CB * bs, d),
                                 row_perm[..., None], axis=2)
        cp = jnp.take_along_axis(cp.reshape(B, H, CB * bs), row_perm, -1)

        # 4'. query-block scale BEFORE the current slot resets
        cnt = (t % bs).astype(jnp.float32) + 1.0                     # (B,)
        r_q = jnp.where(completed[:, None],
                        final * sel_flag.astype(jnp.float32),
                        bsum2 / cnt[:, None])                        # (B,H)

        # reset the current slot where the block completed
        cur_pos = jnp.where(completed[:, None, None], -1, cur_pos)
        bsum3 = jnp.where(completed[:, None], 0.0, bsum2)
        bidx_cur = jnp.where(completed, -1, cur_blk)[:, None, None]  # (B,1,1)
        bidx_cur = jnp.broadcast_to(bidx_cur, (B, H, 1))

        # 3. attention over all valid rows
        k_full = jnp.concatenate([ck, cur_k], 2)
        v_full = jnp.concatenate([cv, cur_v], 2)
        pos_full = jnp.concatenate([cp, cur_pos], 2)
        ok = pos_full >= 0                                           # (B,H,R)
        s = jnp.einsum("bnd,bnkd->bnk", q, k_full.astype(cd),
                       preferred_element_type=jnp.float32) * (d ** -0.5)
        s = jnp.where(ok, s, NEG_INF)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        att = jnp.einsum("bnk,bnkd->bnd", p.astype(cd), v_full.astype(cd),
                         preferred_element_type=jnp.float32)
        att = att * r_q[..., None]
        y = jnp.einsum("bnd,ndh->bh", att.astype(cd), params["wo"].astype(cd),
                       preferred_element_type=jnp.float32).astype(cd)

        new = MoSABlockKVCache(
            k_full.astype(cache.k.dtype), v_full.astype(cache.v.dtype),
            pos_full,
            jnp.concatenate([cand_sc,
                             jnp.full((B, H, 1), -jnp.inf, jnp.float32)], -1),
            jnp.concatenate([cand_bi, bidx_cur], -1),
            bsum3, cache.length + 1)
        return y[:, None], new

    def decode_step(self, params, x, cache: MoSAKVCache, positions=None):
        """Streaming expert-choice decode (MoD-style adaptation, DESIGN §5).

        x: (B, 1, h).  The new token enters a head's top-k set iff its router
        score beats the current minimum (or it is the forced first token);
        only then does that head compute its output for this position.
        KV memory stays at k entries per head forever.

        Positions are per-row (``cache.length``): under continuous batching
        rows sit at different sequence offsets.  ``insert_sorted`` places the
        token so the slots stay sorted by original position (empty slots
        last): ``cache.idx`` keeps the ascending-index invariant that
        training-time ``select_topk`` establishes — the layout stays
        deterministic and any index-derived causal mask stays
        lower-triangular (DESIGN §5).
        """
        if self.cfg.selection_granularity == "block":
            return self._decode_block(params, x, cache, positions)
        c, cd = self.cfg, self.compute_dtype
        B, _, h = x.shape
        H, d = c.n_mosa_heads, c.d_head
        t = cache.length if positions is None else positions[:, 0]   # (B,)

        x0 = x[:, 0]                                              # (B, h)
        score = self.router.scores(params["router"], x)[..., 0]   # (B, H)
        is_forced = jnp.logical_and(jnp.asarray(c.force_first_token),
                                    t == 0)[:, None]              # (B, 1)

        q = jnp.einsum("bh,nhd->bnd", x0.astype(cd), params["wq"].astype(cd),
                       preferred_element_type=jnp.float32).astype(cd)
        kk = jnp.einsum("bh,nhd->bnd", x0.astype(cd), params["wk"].astype(cd),
                        preferred_element_type=jnp.float32).astype(cd)
        v = jnp.einsum("bh,nhd->bnd", x0.astype(cd), params["wv"].astype(cd),
                       preferred_element_type=jnp.float32).astype(cd)
        pos_t = jnp.broadcast_to(t[:, None, None], (B, H, 1)).astype(jnp.int32)
        q = rope_lib.apply_rope(q[:, :, None], pos_t, self.rope_theta,
                                self.rotary_frac)[:, :, 0]
        kk = rope_lib.apply_rope(kk[:, :, None], pos_t, self.rope_theta,
                                 self.rotary_frac)[:, :, 0]

        with jax.named_scope(UPKEEP):
            pos = jnp.broadcast_to(t[:, None], (B, H))
            selected, slot, _, _ = streaming_topk_update(
                cache.scores, cache.idx, score, pos, is_forced)
            # The barrier keeps the insertion out of the attention's fusion:
            # the attention then reads the stored set from memory, as it
            # read the output of a re-sort, and its compiled sums keep their
            # order.  Fused, they summed in another order on a TPU v5e and
            # greedy tokens drifted from a re-sort's.
            new_k, new_v, new_scores, new_idx = jax.lax.optimization_barrier(
                insert_sorted(cache, selected, slot, kk, v, score, pos))

        # Attention of the (possibly inserted) query over the cached set.
        with jax.named_scope(ATTEND):
            valid = new_idx >= 0                                   # (B,H,k)
            s = jnp.einsum("bnd,bnkd->bnk", q, new_k,
                           preferred_element_type=jnp.float32) * (d ** -0.5)
            s = jnp.where(valid, s, NEG_INF)
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
            att = jnp.einsum("bnk,bnkd->bnd", p.astype(cd), new_v,
                             preferred_element_type=jnp.float32)
        att = att * (score * selected.astype(jnp.float32))[..., None]
        y = jnp.einsum("bnd,ndh->bh", att.astype(cd), params["wo"].astype(cd),
                       preferred_element_type=jnp.float32).astype(cd)

        cache = MoSAKVCache(new_k, new_v, new_scores, new_idx, cache.length + 1)
        return y[:, None], cache
