"""Plain reference of the mosa-paper language model, as it is served.

Written from the paper (arXiv 2505.00315, Mixture of Sparse Attention) and
imports nothing of the program under test.  It reads weights laid out as the
program takes them (the benchmark makes those weights itself, from the seed;
``bench/weights.py``) and computes in float32 with every matrix product at
``Precision.HIGHEST``, or, for the control, with every matrix operand
rounded to float8 (e4m3) first.

One request at a time (``jax.vmap`` batches several):

  * ``prefill_chunk`` appends one chunk of the prompt.  Dense heads: causal
    attention over the whole past.  MoSA heads (expert-choice routing):
    each head scores every token with ``sigmoid(x . w_r)``; the candidates
    are the head's stored set (the best ``capacity`` tokens of the earlier
    chunks) plus every token of this chunk; the head keeps the best
    ``capacity`` of them (token 0 always first, the attention sink), and the
    chunk's outputs use the best ``k = max(total // sparsity, min_k)`` of
    them, with the index-causal mask, scaled by the router score.
  * ``decode_steps`` feeds served tokens one by one.  A MoSA head admits the
    new token when its score beats the lowest stored score (evict-min; empty
    slots score -inf), attends over every stored token, and scales its output
    by score x admitted.

Departures from the training forward, which are the serving path's own
semantics and are followed here on purpose:

  1. Selection over a chunked prompt is chunk-causal: a chunk sees the best
     ``capacity`` tokens of the chunks before it, not the whole prompt.  The
     chunk boundaries are those the run used.
  2. Decoding is the streaming evict-min approximation of expert choice, and
     a decode query attends over the whole stored set, not over the best
     ``k_for(t)`` of it.
  3. GELU is the tanh approximation.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Shape numbers of one mosa-paper configuration."""

    n_layers: int
    d_model: int
    vocab: int
    d_head: int
    n_dense: int            # dense heads per layer
    n_mosa: int             # MoSA heads per layer (0: the dense baseline)
    rotary_dense: float     # share of d_head that RoPE rotates, dense heads
    sparsity: int = 8
    min_k: int = 2
    capacity: int = 128     # stored MoSA tokens per head
    max_len: int = 1024
    force_first: bool = True
    rope_theta: float = 10000.0
    eps: float = 1e-6


class State(NamedTuple):
    """Per-request serving state (layer axis first)."""

    length: jnp.ndarray      # () tokens so far
    dk: jnp.ndarray          # (L, S, Hd, d) dense keys, roped
    dv: jnp.ndarray          # (L, S, Hd, d)
    ms: jnp.ndarray          # (L, Hm, cap) stored router scores, -inf empty
    mi: jnp.ndarray          # (L, Hm, cap) stored positions, -1 empty
    mk: jnp.ndarray          # (L, Hm, cap, d) stored keys, roped
    mv: jnp.ndarray          # (L, Hm, cap, d)


def empty_state(g: Geometry) -> State:
    L, S, d = g.n_layers, g.max_len, g.d_head
    Hd, Hm, cap = g.n_dense, max(g.n_mosa, 1), g.capacity
    return State(jnp.zeros((), jnp.int32),
                 jnp.zeros((L, S, Hd, d), jnp.float32),
                 jnp.zeros((L, S, Hd, d), jnp.float32),
                 jnp.full((L, Hm, cap), -jnp.inf, jnp.float32),
                 jnp.full((L, Hm, cap), -1, jnp.int32),
                 jnp.zeros((L, Hm, cap, d), jnp.float32),
                 jnp.zeros((L, Hm, cap, d), jnp.float32))


def _q(x, fp8: bool):
    x = x.astype(jnp.float32)
    if not fp8:
        return x
    x = jnp.clip(x, -FP8_MAX, FP8_MAX)
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _mm(spec, a, b, fp8):
    return jnp.einsum(spec, _q(a, fp8), _q(b, fp8), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, frac, theta):
    """x (..., n, d) at integer positions pos (..., n): rotate the first
    ``frac`` of the dims, half-split pairing."""
    d = x.shape[-1]
    r = int(d * frac)
    r -= r % 2
    if r == 0:
        return x
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    xr, xp = x[..., :r], x[..., r:]
    x1, x2 = xr[..., :r // 2], xr[..., r // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return jnp.concatenate([xr * cos + rot * sin, xp], -1)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                      * (x + 0.044715 * x ** 3)))


def layer_stack(params):
    """The per-layer weights, stacked on a leading layer axis."""
    layers = params["layers"]
    scan = layers.get("scan", {})
    if len(scan) == 1 and "tail" not in layers:
        return scan["pos0"]
    raise ValueError("reference expects one scanned period of layers")


def _k_for(total, g: Geometry):
    k = jnp.maximum(jnp.minimum(total // g.sparsity, total),
                    jnp.minimum(g.min_k, total))
    return jnp.minimum(k, g.capacity)


def _attn_weights(lp, g: Geometry):
    """(dense, mosa) weight dicts of one layer; dense-only models keep their
    attention weights at the top of the mixer."""
    mixer = lp["mixer"]
    if g.n_mosa == 0:
        return mixer, None
    return mixer.get("dense"), mixer["sparse"]


def _dense_chunk(w, x, pos, valid, dk, dv, total, g, fp8):
    """Dense heads over a chunk: x (C, h) at positions pos (C,)."""
    C = x.shape[0]
    Hd, d, S = g.n_dense, g.d_head, g.max_len
    q = _mm("ch,hn->cn", x, w["wq"], fp8).reshape(C, Hd, d)
    k = _mm("ch,hn->cn", x, w["wk"], fp8).reshape(C, Hd, d)
    v = _mm("ch,hn->cn", x, w["wv"], fp8).reshape(C, Hd, d)
    q = _rope(q.swapaxes(0, 1), pos[None], g.rotary_dense,
              g.rope_theta).swapaxes(0, 1)
    k = _rope(k.swapaxes(0, 1), pos[None], g.rotary_dense,
              g.rope_theta).swapaxes(0, 1)
    tgt = jnp.where(valid, pos, S)
    dk = dk.at[tgt].set(k, mode="drop")
    dv = dv.at[tgt].set(v, mode="drop")
    s = _mm("chd,shd->hcs", q, dk, fp8) * d ** -0.5
    kpos = jnp.arange(S)
    ok = (kpos[None, :] <= pos[:, None]) & (kpos[None, :] < total)
    s = jnp.where(ok[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("hcs,shd->chd", p, dv, fp8).reshape(C, Hd * d)
    return _mm("cn,nh->ch", o, w["wo"], fp8), dk, dv


def _mosa_chunk(w, x, pos, valid, st, total, g, fp8):
    """MoSA heads over a chunk (union selection, see module docstring)."""
    ms, mi, mk, mv = st
    C = x.shape[0]
    Hm, d, cap = g.n_mosa, g.d_head, g.capacity
    sc = jax.nn.sigmoid(_mm("ch,nh->nc", x, w["router"]["w"], fp8))
    sc = jnp.where(valid[None], sc, -jnp.inf)                    # (Hm, C)
    q = _rope(_mm("ch,nhd->ncd", x, w["wq"], fp8), pos[None], 0.5,
              g.rope_theta)
    k = _rope(_mm("ch,nhd->ncd", x, w["wk"], fp8), pos[None], 0.5,
              g.rope_theta)
    v = _mm("ch,nhd->ncd", x, w["wv"], fp8)
    pos_b = jnp.broadcast_to(pos[None], (Hm, C))
    u_s = jnp.concatenate([ms, sc], -1)                          # (Hm, U)
    u_i = jnp.concatenate([mi, jnp.where(valid[None], pos_b, -1)], -1)
    u_k = jnp.concatenate([mk, k], 1)
    u_v = jnp.concatenate([mv, v], 1)
    boost = u_s
    if g.force_first:
        boost = jnp.where(u_i == 0, 2.0, u_s)
    _, order = jax.lax.top_k(boost, cap)                         # (Hm, cap)
    rank = jnp.full(u_s.shape, cap, jnp.int32)
    rank = jax.vmap(lambda r, o: r.at[o].set(jnp.arange(cap)))(rank, order)
    live = u_i >= 0
    out_sel = live & (rank < _k_for(total, g))                   # (Hm, U)

    # outputs of the chunk's own selected tokens
    n_old = ms.shape[-1]
    qsel = out_sel[:, n_old:]                                    # (Hm, C)
    s = _mm("ncd,nud->ncu", q, u_k, fp8) * d ** -0.5
    ok = out_sel[:, None, :] & (u_i[:, None, :] <= pos_b[:, :, None])
    s = jnp.where(ok, s, -jnp.inf)
    p = jax.nn.softmax(jnp.where(qsel[..., None], s, 0.0), axis=-1)
    att = _mm("ncu,nud->ncd", p, u_v, fp8)
    att = att * jnp.where(qsel, sc, 0.0)[..., None]
    y = _mm("ncd,ndh->ch", att, w["wo"], fp8)

    # new stored set: the best ``cap`` candidates, sorted by position
    keep_s = jnp.take_along_axis(u_s, order, -1)
    keep_i = jnp.take_along_axis(u_i, order, -1)
    ok_keep = keep_i >= 0
    keep_s = jnp.where(ok_keep, keep_s, -jnp.inf)
    keep_i = jnp.where(ok_keep, keep_i, -1)
    srt = jnp.argsort(jnp.where(keep_i < 0, jnp.iinfo(jnp.int32).max,
                                keep_i), -1)
    order = jnp.take_along_axis(order, srt, -1)
    ms2 = jnp.take_along_axis(keep_s, srt, -1)
    mi2 = jnp.take_along_axis(keep_i, srt, -1)
    mk2 = jnp.take_along_axis(u_k, order[..., None], 1)
    mv2 = jnp.take_along_axis(u_v, order[..., None], 1)
    return y, (ms2, mi2, mk2, mv2)


def _ffn(lp, x, g, fp8):
    h = _gelu(_mm("ch,hf->cf", x, lp["ffn"]["w_in"], fp8))
    return _mm("cf,fh->ch", h, lp["ffn"]["w_out"], fp8)


def _logits(params, x, g, fp8):
    x = _rms(x, params["final_norm"]["scale"], g.eps)
    return _mm("ch,hv->cv", x, params["unembed"]["w"], fp8)


def prefill_chunk(params, state: State, toks, n_valid, g: Geometry,
                  fp8: bool = False):
    """Append ``toks[:n_valid]`` (padded to a fixed chunk) to ``state``.
    Returns (logits at the last valid token (V,), new state)."""
    C = toks.shape[0]
    L0 = state.length
    pos = L0 + jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(C) < n_valid
    total = L0 + n_valid
    x = params["embed"]["table"][toks].astype(jnp.float32)

    def layer(x, xs):
        lp, dk, dv, ms, mi, mk, mv = xs
        wd, wm = _attn_weights(lp, g)
        xin = _rms(x, lp["norm1"]["scale"], g.eps)
        y = jnp.zeros_like(x)
        if g.n_dense:
            yd, dk, dv = _dense_chunk(wd, xin, pos, valid, dk, dv, total, g,
                                      fp8)
            y = y + yd
        if g.n_mosa:
            ym, (ms, mi, mk, mv) = _mosa_chunk(wm, xin, pos, valid,
                                               (ms, mi, mk, mv), total, g,
                                               fp8)
            y = y + ym
        x = x + y
        x = x + _ffn(lp, _rms(x, lp["norm2"]["scale"], g.eps), g, fp8)
        return x, (dk, dv, ms, mi, mk, mv)

    lp = layer_stack(params)
    x, (dk, dv, ms, mi, mk, mv) = jax.lax.scan(
        layer, x, (lp, state.dk, state.dv, state.ms, state.mi, state.mk,
                   state.mv))
    last = jnp.maximum(n_valid - 1, 0)
    logits = _logits(params, x[last][None], g, fp8)[0]
    new = State(total, dk, dv, ms, mi, mk, mv)
    keep = n_valid > 0
    new = jax.tree.map(lambda a, b: jnp.where(keep, a, b), new, state)
    return logits, new


def _decode_layer(x, xs, t, g, fp8):
    lp, dk, dv, ms, mi, mk, mv = xs
    wd, wm = _attn_weights(lp, g)
    xin = _rms(x, lp["norm1"]["scale"], g.eps)                   # (1, h)
    pos = t[None]
    y = jnp.zeros_like(x)
    if g.n_dense:
        yd, dk, dv = _dense_chunk(wd, xin, pos, jnp.ones((1,), bool), dk, dv,
                                  t + 1, g, fp8)
        y = y + yd
    if g.n_mosa:
        d = g.d_head
        s = jax.nn.sigmoid(_mm("ch,nh->n", xin, wm["router"]["w"], fp8))
        q = _rope(_mm("ch,nhd->ncd", xin, wm["wq"], fp8), pos[None], 0.5,
                  g.rope_theta)[:, 0]
        k = _rope(_mm("ch,nhd->ncd", xin, wm["wk"], fp8), pos[None], 0.5,
                  g.rope_theta)[:, 0]
        v = _mm("ch,nhd->ncd", xin, wm["wv"], fp8)[:, 0]
        slot = jnp.argmin(ms, -1)                                # (Hm,)
        low = jnp.take_along_axis(ms, slot[:, None], -1)[:, 0]
        adm = (s > low) | (g.force_first & (t == 0))
        hit = (jnp.arange(ms.shape[-1])[None] == slot[:, None]) & adm[:, None]
        ms = jnp.where(hit, s[:, None], ms)
        mi = jnp.where(hit, t, mi)
        mk = jnp.where(hit[..., None], k[:, None], mk)
        mv = jnp.where(hit[..., None], v[:, None], mv)
        srt = jnp.argsort(jnp.where(mi < 0, jnp.iinfo(jnp.int32).max, mi),
                          -1)
        ms = jnp.take_along_axis(ms, srt, -1)
        mi = jnp.take_along_axis(mi, srt, -1)
        mk = jnp.take_along_axis(mk, srt[..., None], 1)
        mv = jnp.take_along_axis(mv, srt[..., None], 1)
        sc = _mm("nd,nud->nu", q, mk, fp8) * d ** -0.5
        sc = jnp.where(mi >= 0, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        att = _mm("nu,nud->nd", p, mv, fp8) * (s * adm)[:, None]
        y = y + _mm("nd,ndh->h", att, wm["wo"], fp8)[None]
    x = x + y
    x = x + _ffn(lp, _rms(x, lp["norm2"]["scale"], g.eps), g, fp8)
    return x, (dk, dv, ms, mi, mk, mv)


def decode_steps(params, state: State, toks, n_valid, g: Geometry,
                 fp8: bool = False):
    """Feed ``toks[:n_valid]`` one at a time.  Returns the logits after
    each fed token (N, V) (rows past ``n_valid`` are junk) and the state."""
    lp = layer_stack(params)

    def step(st, inp):
        tok, j = inp
        t = st.length
        x = params["embed"]["table"][tok][None].astype(jnp.float32)
        x, (dk, dv, ms, mi, mk, mv) = jax.lax.scan(
            lambda x, xs: _decode_layer(x, xs, t, g, fp8), x,
            (lp, st.dk, st.dv, st.ms, st.mi, st.mk, st.mv))
        logits = _logits(params, x, g, fp8)[0]
        new = State(t + 1, dk, dv, ms, mi, mk, mv)
        new = jax.tree.map(lambda a, b: jnp.where(j < n_valid, a, b), new, st)
        return new, logits

    n = toks.shape[0]
    state, logits = jax.lax.scan(step, state,
                                 (toks, jnp.arange(n, dtype=jnp.int32)))
    return logits, state


def serve_logits(params, g: Geometry, chunks, n_chunks, served, n_served,
                 fp8: bool = False):
    """Logits of every served token of one request.

    ``chunks``: (M, C) prompt chunks as the run prefilled them, padded;
    ``n_chunks``: (M,) valid tokens per chunk (0 = no chunk);
    ``served``: (N,) served tokens, ``n_served`` of them valid.
    Returns (N, V): row j holds the logits the j-th served token was drawn
    from."""
    state = empty_state(g)
    first = jnp.zeros((g.vocab,), jnp.float32)
    for m in range(chunks.shape[0]):
        lg, state = prefill_chunk(params, state, chunks[m], n_chunks[m], g,
                                  fp8)
        first = jnp.where(n_chunks[m] > 0, lg, first)
    rest, _ = decode_steps(params, state, served[:-1], n_served - 1, g, fp8)
    return jnp.concatenate([first[None], rest], 0)
