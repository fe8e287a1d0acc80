"""Fused decode path, MoSA streaming invariants, cache sharding specs,
continuous batching, and DESIGN.md reference integrity (PR 2)."""

import re
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoSAConfig, get_config
from repro.core.kv_cache import DenseKVCache, MoSAKVCache, WindowKVCache
import repro.core.mosa as mosa_mod
from repro.core.mosa import MoSAAttention, insert_sorted
from repro.core.router import streaming_topk_update
from repro.dist import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.nn.transformer import TransformerLM

REPO = Path(__file__).resolve().parent.parent


# ------------------------------------------------------------ fused decode
def _fused(model):
    return jax.jit(model.decode_many,
                   static_argnames=("n", "temperature", "top_k",
                                    "return_logits"))


def test_fused_decode_logits_match_full_forward():
    """Prefill + N fused decode steps == one full forward (dense caches)."""
    cfg = get_config("qwen2-1.5b", preset="smoke")
    model = TransformerLM(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    B, P, G = 2, 10, 6
    prompts = jax.random.randint(key, (B, P), 2, cfg.vocab)

    caches = model.init_cache(B, P + G, jnp.float32)
    lp, caches = model.prefill(params, prompts, caches)
    tok0 = jnp.argmax(lp[:, -1], -1).astype(jnp.int32)[:, None]
    toks, logits, _ = _fused(model)(params, tok0, caches, None, n=G,
                                    return_logits=True)
    assert toks.shape == (B, G) and logits.shape[:2] == (B, G)

    # Teacher-force the full forward with the prompt + the tokens the fused
    # decoder actually consumed; step j's logits live at position P-1+j+1.
    full_in = jnp.concatenate([prompts, tok0, toks[:, :-1]], axis=1)
    logits_full, _ = model(params, full_in)
    for j in range(G):
        np.testing.assert_allclose(
            np.asarray(logits[:, j], np.float32),
            np.asarray(logits_full[:, P + j], np.float32),
            atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("arch,kw", [("qwen2-1.5b", {}),
                                     ("mosa-paper", {"variant": "mosa"}),
                                     ("jamba-v0.1-52b", {})])
def test_fused_decode_matches_stepwise(arch, kw):
    """The scan-fused chunk emits exactly the per-token loop's tokens."""
    cfg = get_config(arch, preset="smoke", **kw)
    model = TransformerLM(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    B, P, G = 2, 8, 5
    prompts = jax.random.randint(key, (B, P), 2, cfg.vocab)

    caches = model.init_cache(B, 32, jnp.float32)
    lp, c0 = model.prefill(params, prompts, caches)
    tok0 = jnp.argmax(lp[:, -1], -1).astype(jnp.int32)[:, None]

    tok, cs, step = tok0, c0, []
    for _ in range(G):
        lg, cs = model.decode_step(params, tok, cs)
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
        step.append(tok)
    caches = model.init_cache(B, 32, jnp.float32)
    _, c0 = model.prefill(params, prompts, caches)
    fused, _ = _fused(model)(params, tok0, c0, None, n=G)
    np.testing.assert_array_equal(np.asarray(jnp.concatenate(step, 1)),
                                  np.asarray(fused))


# --------------------------------------------------- MoSA streaming decode
def _mosa_layer(k_fixed=0, sparsity=4):
    cfg = MoSAConfig(n_mosa_heads=3, sparsity=sparsity, n_dense_heads=0,
                     d_head=8, k_fixed=k_fixed)
    return MoSAAttention(64, cfg), cfg


def test_mosa_streaming_cache_invariants():
    """kv_entries constant in T; idx entries valid, unique, sorted."""
    layer, c = _mosa_layer(k_fixed=6)
    key = jax.random.PRNGKey(0)
    params = layer.init(key)
    B, P, G = 2, 12, 10
    x = jax.random.normal(key, (B, P + G, 64), jnp.float32)

    cache = MoSAKVCache.create(B, c.n_mosa_heads, 6, c.d_head, jnp.float32)
    entries0 = cache.kv_entries
    _, cache = layer.prefill(params, x[:, :P], cache)
    for t in range(P, P + G):
        _, cache = layer.decode_step(params, x[:, t:t + 1], cache)
        assert cache.kv_entries == entries0          # O(k), never grows
        idx = np.asarray(cache.idx)
        assert idx.shape == (B, c.n_mosa_heads, 6)
        for b in range(B):
            for h in range(c.n_mosa_heads):
                row = idx[b, h]
                valid = row[row >= 0]
                assert (valid <= t).all()                    # positions seen
                assert len(np.unique(valid)) == len(valid)   # no duplicates
                assert (np.diff(valid) > 0).all()            # sorted ascending
                # empty slots (-1) only after the valid prefix
                assert (row[len(valid):] == -1).all()
    assert int(cache.length[0]) == P + G


def test_mosa_streaming_k_equals_T_matches_training():
    """With k = T nothing is ever evicted: streaming decode reproduces the
    training-style (non-autoregressive) selection exactly."""
    T = 10
    layer, c = _mosa_layer(k_fixed=T)
    key = jax.random.PRNGKey(1)
    params = layer.init(key)
    B, P = 2, 4
    x = jax.random.normal(key, (B, T, 64), jnp.float32)

    y_train = layer(params, x)                       # (B, T, 64)
    cache = MoSAKVCache.create(B, c.n_mosa_heads, T, c.d_head, jnp.float32)
    _, cache = layer.prefill(params, x[:, :P], cache)
    for t in range(P, T):
        y_t, cache = layer.decode_step(params, x[:, t:t + 1], cache)
        np.testing.assert_allclose(np.asarray(y_t[:, 0], np.float32),
                                   np.asarray(y_train[:, t], np.float32),
                                   atol=2e-4, rtol=2e-4)
    # every position ended up cached, in order
    np.testing.assert_array_equal(
        np.asarray(cache.idx),
        np.broadcast_to(np.arange(T), (B, c.n_mosa_heads, T)))


def test_mosa_decode_per_row_positions():
    """Rows at different sequence offsets decode with their own positions
    (continuous batching): a row's result is independent of its batchmates."""
    layer, c = _mosa_layer(k_fixed=5)
    key = jax.random.PRNGKey(2)
    params = layer.init(key)
    x = jax.random.normal(key, (2, 9, 64), jnp.float32)

    # batch of two rows prefilled at different lengths
    ca = MoSAKVCache.create(1, c.n_mosa_heads, 5, c.d_head, jnp.float32)
    cb = MoSAKVCache.create(1, c.n_mosa_heads, 5, c.d_head, jnp.float32)
    _, ca = layer.prefill(params, x[:1, :8], ca)
    _, cb = layer.prefill(params, x[1:, :3], cb)
    joint = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), ca, cb)
    y_joint, joint2 = layer.decode_step(params, x[:, 8:9], joint)
    y_solo, ca2 = layer.decode_step(params, x[:1, 8:9], ca)
    np.testing.assert_allclose(np.asarray(y_joint[:1], np.float32),
                               np.asarray(y_solo, np.float32),
                               atol=1e-5, rtol=1e-5)
    assert int(joint2.length[0]) == 9 and int(joint2.length[1]) == 4


# Sort-and-gather oracle of the stored-set upkeep: evict-min with the minimum
# read by a gather, a one-hot blend of the new K/V, then a re-sort of every
# array by position (empties last).  ``insert_sorted`` must match it bit for
# bit while running no sort and no gather.
def _oracle_topk_update(cache_scores, cache_idx, new_score, new_pos,
                        is_forced):
    min_slot = jnp.argmin(cache_scores, axis=-1)
    min_score = jnp.take_along_axis(cache_scores, min_slot[..., None],
                                    -1)[..., 0]
    selected = jnp.logical_or(new_score > min_score, is_forced)
    hit = (jax.nn.one_hot(min_slot, cache_scores.shape[-1], dtype=bool)
           & selected[..., None])
    new_scores = jnp.where(hit, new_score[..., None], cache_scores)
    new_idx = jnp.where(
        hit, jnp.asarray(new_pos)[..., None].astype(cache_idx.dtype),
        cache_idx)
    return selected, min_slot, new_scores, new_idx


def _oracle_insert(cache, selected, slot, k_new, v_new, score, pos):
    # ``slot`` is the oracle's argmin; the evict-min writes of scores and
    # positions are ``_oracle_topk_update``'s, repeated on the old set.
    cd = k_new.dtype
    hit = (jax.nn.one_hot(slot, cache.idx.shape[-1], dtype=bool)
           & selected[..., None])
    new_scores = jnp.where(hit, score[..., None], cache.scores)
    new_idx = jnp.where(hit, pos[..., None].astype(cache.idx.dtype),
                        cache.idx)
    onehot = jax.nn.one_hot(slot, cache.k.shape[2], dtype=cd)
    upd = (onehot * selected[..., None].astype(cd))[..., None]
    new_k = cache.k * (1 - upd) + upd * k_new[:, :, None]
    new_v = cache.v * (1 - upd) + upd * v_new[:, :, None]
    order = jnp.argsort(jnp.where(
        new_idx < 0, jnp.iinfo(jnp.int32).max, new_idx), -1)
    return (jnp.take_along_axis(new_k, order[..., None], 2),
            jnp.take_along_axis(new_v, order[..., None], 2),
            jnp.take_along_axis(new_scores, order, -1),
            jnp.take_along_axis(new_idx, order, -1))


def _sorted_state(rng, B, H, K, d, n_valid, t, dtype):
    """A stored set as decode finds it: per (row, head) ``n_valid`` distinct
    positions below ``t`` in ascending order, then empty slots."""
    idx = np.full((B, H, K), -1, np.int32)
    scores = np.full((B, H, K), -np.inf, np.float32)
    for b in range(B):
        for h in range(H):
            n = int(n_valid[b, h])
            idx[b, h, :n] = np.sort(rng.choice(t, n, replace=False))
            scores[b, h, :n] = rng.uniform(0.05, 0.95, n)
    k = rng.standard_normal((B, H, K, d)).astype(np.float32)
    v = rng.standard_normal((B, H, K, d)).astype(np.float32)
    return MoSAKVCache(jnp.asarray(k, dtype), jnp.asarray(v, dtype),
                       jnp.asarray(scores), jnp.asarray(idx),
                       jnp.full((B,), t, jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("case", ["empty", "forced_t0", "partial", "full",
                                  "not_selected", "tied_min", "mixed"])
def test_mosa_insert_sorted_matches_sort_gather_oracle(case, dtype):
    """The one-slot-shift insertion gives the oracle's slots, values and
    order: empty, partly filled and full sets, the forced first token,
    rejected tokens and tied minimum scores (argmin takes the first)."""
    seed = zlib.crc32(f"{case}-{dtype.__name__}".encode())
    rng = np.random.default_rng(seed)
    B, H, K, d = 4, 6, 16, 8
    t = 0 if case == "forced_t0" else 200
    n_valid = {"empty": 0, "forced_t0": 0, "full": K, "not_selected": K,
               "tied_min": K}.get(case)
    if n_valid is None:
        hi = K if case == "partial" else K + 1
        n_valid = rng.integers(0 if case == "mixed" else 1, hi, (B, H))
    n_valid = np.broadcast_to(n_valid, (B, H))
    cache = _sorted_state(rng, B, H, K, d, n_valid, max(t, K), dtype)
    score = rng.uniform(0.05, 0.95, (B, H)).astype(np.float32)
    if case == "not_selected":
        score = np.asarray(cache.scores).min(-1) * 0.5
    if case == "tied_min":
        s = np.asarray(cache.scores).copy()
        for b in range(B):
            for h in range(H):
                s[b, h, rng.choice(K, 3, replace=False)] = 0.01
        cache = cache._replace(scores=jnp.asarray(s))
    if case == "mixed":
        score = np.where(rng.random((B, H)) < 0.3, 0.0, score)
    score = jnp.asarray(score, jnp.float32)
    pos = jnp.full((B, H), t, jnp.int32)
    forced = jnp.asarray(case == "forced_t0")
    k_new = jnp.asarray(rng.standard_normal((B, H, d)), dtype)
    v_new = jnp.asarray(rng.standard_normal((B, H, d)), dtype)

    sel, slot, _, _ = streaming_topk_update(cache.scores, cache.idx, score,
                                            pos, forced)
    got = insert_sorted(cache, sel, slot, k_new, v_new, score, pos)
    osel, oslot, _, _ = _oracle_topk_update(cache.scores, cache.idx, score,
                                            pos, forced)
    want = _oracle_insert(cache, osel, oslot, k_new, v_new, score, pos)
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(osel))
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(oslot))
    for name, g, w in zip(("k", "v", "scores", "idx"), got, want):
        assert g.dtype == w.dtype, name
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32), err_msg=name)
    if case == "not_selected":
        assert not np.asarray(sel).any()
    if case == "tied_min":
        assert (np.asarray(np.asarray(cache.scores)[
            np.arange(B)[:, None], np.arange(H), np.asarray(slot)])
            == 0.01).all()
    if case == "forced_t0":
        assert np.asarray(sel).all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("P", [0, 3])
def test_mosa_decode_step_matches_sort_gather_oracle(P, dtype, monkeypatch):
    """48 decode steps with k_fixed < T, from an empty set (the forced first
    token, then filling) or after a short prefill: every output and every
    stored set equals the sort-and-gather step's, and the sets stay valid,
    unique, ascending and empties-last."""
    K, G = 6, 48
    cfg = MoSAConfig(n_mosa_heads=3, sparsity=4, n_dense_heads=0, d_head=8,
                     k_fixed=K)
    layer = MoSAAttention(64, cfg, compute_dtype=dtype)
    key = jax.random.PRNGKey(3)
    params = layer.init(key)
    B = 2
    x = jax.random.normal(key, (B, P + G, 64), jnp.float32)
    cache = MoSAKVCache.create(B, cfg.n_mosa_heads, K, cfg.d_head, dtype)
    if P:
        _, cache = layer.prefill(params, x[:, :P], cache)

    def run(c0):
        # A fresh function per run: jit traces whichever upkeep is patched in.
        step = jax.jit(lambda p, xt, c: layer.decode_step(p, xt, c))
        c, ys, cs = c0, [], []
        for t in range(P, P + G):
            y, c = step(params, x[:, t:t + 1], c)
            ys.append(y)
            cs.append(c)
        return ys, cs

    ys, cs = run(cache)
    with monkeypatch.context() as m:
        m.setattr(mosa_mod, "streaming_topk_update", _oracle_topk_update)
        m.setattr(mosa_mod, "insert_sorted", _oracle_insert)
        oys, ocs = run(cache)
    for t, (y, oy, c, oc) in enumerate(zip(ys, oys, cs, ocs), P):
        np.testing.assert_array_equal(np.asarray(y, np.float32),
                                      np.asarray(oy, np.float32),
                                      err_msg=f"y at t={t}")
        for name in ("k", "v", "scores", "idx"):
            np.testing.assert_array_equal(
                np.asarray(getattr(c, name), np.float32),
                np.asarray(getattr(oc, name), np.float32),
                err_msg=f"{name} at t={t}")
        idx = np.asarray(c.idx)
        for row in idx.reshape(-1, K):
            valid = row[row >= 0]
            assert (valid <= t).all() and (np.diff(valid) > 0).all()
            assert (row[len(valid):] == -1).all()
    assert (np.asarray(cs[-1].idx) >= 0).all()      # the sets filled


def test_mosa_decode_upkeep_has_no_sort_or_gather():
    """Token-choice decode keeps its stored set sorted without a sort
    primitive and without a gather over the (B, H, k, d) K/V or the
    (B, H, k) scores and positions."""
    B, H, K, d = 2, 3, 5, 8
    cfg = MoSAConfig(n_mosa_heads=H, sparsity=4, n_dense_heads=0, d_head=d,
                     k_fixed=K)
    layer = MoSAAttention(64, cfg)
    params = layer.init(jax.random.PRNGKey(0))
    cache = MoSAKVCache.create(B, H, K, d, jnp.float32)
    x = jnp.ones((B, 1, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(layer.decode_step)(params, x, cache).jaxpr

    def eqns(jx):
        for e in jx.eqns:
            yield e
            for p in e.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        yield from eqns(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        yield from eqns(sub)

    prims = [(e.primitive.name,
              tuple(e.invars[0].aval.shape) if e.invars else ())
             for e in eqns(jaxpr)]
    assert any(name == "argmin" for name, _ in prims)   # the walk sees upkeep
    # the stored set reaches the attention through a barrier (see decode_step)
    assert any(name == "optimization_barrier" for name, _ in prims)
    assert not [p for p in prims if p[0] == "sort"]
    stored = {(B, H, K, d), (B, H, K)}
    assert not [p for p in prims if p[0] == "gather" and p[1] in stored]


def test_window_decode_parity_past_window():
    """Prompt longer than the window: prefill's slot layout must match
    append_one's ring arithmetic (slot = position % W) so decode evicts the
    oldest token and matches the full windowed forward at every step."""
    from repro.configs.base import AttentionConfig
    from repro.core.attention import MultiHeadAttention
    W = 4
    acfg = AttentionConfig(kind="gqa", n_heads=2, n_kv_heads=2, d_head=8,
                           window=W)
    mha = MultiHeadAttention(32, acfg, impl="naive")
    key = jax.random.PRNGKey(5)
    params = mha.init(key)
    B, P, T = 2, 6, 11
    x = jax.random.normal(key, (B, T, 32), jnp.float32)
    y_full = mha(params, x)

    cache = WindowKVCache.create(B, W, 2, 8, jnp.float32)
    y_pre, cache = mha.prefill(params, x[:, :P], cache)
    np.testing.assert_allclose(np.asarray(y_pre), np.asarray(y_full[:, :P]),
                               atol=1e-4, rtol=1e-4)
    for t in range(P, T):
        y_t, cache = mha.decode_step(params, x[:, t:t + 1], cache)
        np.testing.assert_allclose(np.asarray(y_t[:, 0]),
                                   np.asarray(y_full[:, t]),
                                   atol=1e-4, rtol=1e-4, err_msg=f"step {t}")
        pos = np.asarray(cache.positions)
        for b in range(B):   # ring holds exactly the last W positions
            assert sorted(pos[b]) == list(range(t - W + 1, t + 1)), (t, pos[b])


def test_window_cache_per_row_slots():
    """Ring-buffer slots are per-row (length % W row by row)."""
    cache = WindowKVCache.create(2, 4, 1, 8, jnp.float32)
    cache = cache._replace(length=jnp.asarray([5, 0], jnp.int32))
    k = jnp.ones((2, 1, 8), jnp.float32)
    cache = cache.append_one(k, k)
    pos = np.asarray(cache.positions)
    assert pos[0, 5 % 4] == 5 and pos[1, 0] == 0
    np.testing.assert_array_equal(np.asarray(cache.length), [6, 1])


# ------------------------------------------------------- cache sharding
def test_mosa_cache_head_dim_shards_over_model():
    """Acceptance: under the ``tp`` rule set the MoSA cache head dim maps to
    the ``model`` mesh axis (head-parallel decode, DESIGN §6)."""
    mesh = make_host_mesh(tp=1)
    cache = jax.eval_shape(
        lambda: MoSAKVCache.create(2, 4, 8, 16, jnp.float32))
    spec = shd.cache_spec(cache, mesh, "tp")
    assert spec.k[1] == "model" and spec.v[1] == "model"
    assert spec.scores[1] == "model" and spec.idx[1] == "model"
    # and through the full tree path, stacked caches shift by the layer axis
    stacked = jax.eval_shape(lambda: jax.tree.map(
        lambda t: jnp.zeros((3,) + t.shape, t.dtype), cache))
    sh = shd.cache_shardings({"scan": {"pos0": stacked}}, mesh, "tp")
    assert sh["scan"]["pos0"].k.spec[2] == "model"


def test_dense_cache_spec_seq_vs_heads():
    mesh = make_host_mesh(tp=1)
    cache = jax.eval_shape(
        lambda: DenseKVCache.create(2, 32, 4, 16, jnp.float32))
    spec = shd.cache_spec(cache, mesh, "tp")
    assert len(spec.k) >= 3 and spec.k[2] == "model"   # kv_heads -> model
    seq = shd.cache_spec(cache, mesh, "tp", seq_sharded=True)
    assert seq.k[1] == "model"                         # seq wins...
    assert len(seq.k) < 3 or seq.k[2] is None          # ...heads replicate


def test_cache_shardings_cover_every_arch():
    mesh = make_host_mesh(tp=1)
    for arch in ("gemma3-4b", "deepseek-v2-lite-16b", "jamba-v0.1-52b",
                 "xlstm-125m"):
        cfg = get_config(arch, preset="smoke")
        model = TransformerLM(cfg)
        shapes = jax.eval_shape(lambda: model.init_cache(2, 32, jnp.float32))
        sh = shd.cache_shardings(shapes, mesh, "tp")
        assert jax.tree.structure(shapes) == jax.tree.structure(
            jax.tree.map(lambda x: 0, sh)), arch


# --------------------------------------------------- continuous batching
def test_request_pool_honors_eos_and_max_steps():
    from repro.launch.serve import RequestPool, Server
    cfg = get_config("qwen2-1.5b", preset="smoke")
    server = Server(cfg, batch=2, max_len=32)
    key = jax.random.PRNGKey(3)
    prompts = [jax.random.randint(jax.random.fold_in(key, i), (6,), 2,
                                  cfg.vocab) for i in range(3)]

    # discover a token greedy decode emits, then use it as EOS
    probe = RequestPool(server)
    for pr in prompts:
        probe.submit(pr, max_new=8)
    ref = probe.run()
    assert all(len(v) == 8 for v in ref.values())      # eos<0: no early stop
    eos = int(ref[0][2])

    pool = RequestPool(server, eos=eos)
    for pr in prompts:
        pool.submit(pr, max_new=8)
    out = pool.run()
    assert set(out) == {0, 1, 2}
    assert len(out[0]) <= 8 and int(out[0][-1]) == eos
    for rid, toks in out.items():                       # eos at most once, last
        t = np.asarray(toks)
        assert (t[:-1] != eos).all()

    # max_steps caps total decode work but still returns partial results
    pool2 = RequestPool(server, chunk=2)
    for pr in prompts[:2]:
        pool2.submit(pr, max_new=12)
    partial = pool2.run(max_steps=3)
    assert all(1 <= len(v) <= 4 for v in partial.values())


def test_request_pool_mixed_lengths_refill():
    """More requests than slots, different prompt lengths: everything is
    served to its own max_new."""
    from repro.launch.serve import RequestPool, Server
    cfg = get_config("mosa-paper", preset="smoke", variant="mosa")
    server = Server(cfg, batch=2, max_len=64)
    pool = RequestPool(server, chunk=4)
    key = jax.random.PRNGKey(4)
    want = {}
    for i in range(4):
        n = 3 + i
        rid = pool.submit(jax.random.randint(jax.random.fold_in(key, i),
                                             (5 + 2 * i,), 2, cfg.vocab),
                          max_new=n)
        want[rid] = n
    out = pool.run()
    assert {k: len(v) for k, v in out.items()} == want


# ------------------------------------------------------------ docs
def test_design_references_resolve():
    """Every ``DESIGN §N`` / ``DESIGN.md §N`` citation in src/ names a real
    section of DESIGN.md."""
    design = (REPO / "DESIGN.md").read_text()
    sections = set(re.findall(r"^#+\s*§([\w-]+)", design, re.M))
    assert sections, "DESIGN.md has no §-numbered sections"
    refs = []
    for py in (REPO / "src").rglob("*.py"):
        for m in re.finditer(r"DESIGN(?:\.md)?\s*§([\w-]+)", py.read_text()):
            refs.append((py.name, m.group(1)))
    assert refs, "no DESIGN references found in src/ (regex broken?)"
    missing = [(f, s) for f, s in refs if s not in sections]
    assert not missing, f"unresolved DESIGN references: {missing}"


def test_bench_serve_artifact_tracks_acceptance():
    """BENCH_serve.json exists and records the PR's acceptance numbers."""
    import json
    path = REPO / "BENCH_serve.json"
    assert path.exists(), "run `make bench-smoke`"
    res = json.loads(path.read_text())
    assert res["config"]["max_len"] >= 256
    v = res["variants"]
    assert v["mosa"]["cache_bytes"] < v["dense"]["cache_bytes"]
    # The PR-2 artifact records 2.9-4.3x; the regression gate is looser
    # because the exact ratio is hardware-dependent (dispatch overhead vs
    # the shrunken model's compute varies across CI machines).
    for r in v.values():
        assert r["fused_speedup"] >= 1.5, r
