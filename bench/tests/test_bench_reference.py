"""The float32 reference against the program's einsum path at a tiny
mosa-paper size: packed chunked prefill, then decoding through the paged
Scheduler, with the chunk boundaries the run used, compared on logits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import serving, traffic
from bench.reference import serve_logits
from bench.tests import smoke


@pytest.mark.parametrize("variant", ["mosa", "dense"])
def test_reference_matches_served_logits(variant, monkeypatch):
    smoke.skip_chip_look(monkeypatch)
    spec = smoke.config(variant)
    mix = smoke.OPEN
    server, cfg, params = serving.build(11, spec)
    assert cfg.mosa is None or cfg.mosa.impl == "einsum"

    reqs = traffic.requests(mix, 11, 1.0, cfg.vocab)
    sched, src, log, _ = serving.serve_window(server, spec, mix, reqs, 1.0,
                                              closed=False)
    calls = log.dispatches()
    chunks, bad = serving.map_requests(
        [s for _, ss in calls for s in ss], reqs, src.rid)
    assert not bad
    geo = serving.geometry(spec)
    C, N = spec["serve"]["chunk_tokens"], mix["output"]["max"]
    ref_fn = jax.jit(serve_logits, static_argnums=(1,))
    checked = 0
    for i, rid in src.rid.items():
        got = np.asarray(sched.results[rid])
        p = reqs[i].prompt
        ch = np.zeros((serving.MAX_CHUNKS, C), np.int32)
        nch = np.zeros((serving.MAX_CHUNKS,), np.int32)
        for m, (past, n) in enumerate(chunks[i]):
            ch[m, :n] = p[past:past + n]
            nch[m] = n
        sv = np.zeros((N,), np.int32)
        sv[:len(got)] = got
        ref = np.asarray(ref_fn(params, geo, jnp.asarray(ch),
                                jnp.asarray(nch), jnp.asarray(sv),
                                len(got)))[:len(got)]
        # the reference's greedy choice is what was served
        assert np.array_equal(ref.argmax(-1), got)
        checked += len(got)
    assert checked >= 40


def test_reference_logits_match_prefill_and_decode(monkeypatch):
    """One prompt prefilled in two chunks, then four decode steps: the
    program's logits against the reference's, value for value."""
    smoke.skip_chip_look(monkeypatch)
    spec = smoke.config("mosa")
    server, cfg, params = serving.build(4, spec)
    caches = server.new_cache()
    # the scheduler's admission: give row 2 its dense blocks
    nb = spec["serve"]["max_len"] // spec["serve"]["block_size"]
    caches = server.grow_tables(caches, jnp.arange(nb, dtype=jnp.int32),
                                jnp.int32(2))
    prompt = traffic.corpus_tokens(4, 0, 50, cfg.vocab)
    outs = []
    for past, n in ((0, 32), (32, 18)):
        buf = np.zeros((1, 64), np.int32)
        buf[0, :n] = prompt[past:past + n]
        logits, caches = server.prefill_packed(
            server.params, jnp.asarray(buf), caches,
            jnp.asarray([0, n, n, n, n], jnp.int32),
            jnp.asarray([2, -1, -1, -1], jnp.int32),
            jnp.asarray([past, 0, 0, 0], jnp.int32))
        outs.append(np.asarray(logits[0]))
    t0 = int(outs[-1].argmax())
    cur = jnp.zeros((server.batch, 1), jnp.int32).at[2, 0].set(t0)
    decode = jax.jit(server.model.decode_many, static_argnums=(4, 6, 7))
    with server.mesh:
        toks, dec, caches = decode(server.params, cur, caches,
                                   jax.random.PRNGKey(0), 4,
                                   jnp.float32(0.0), 0, True)
    served = np.concatenate([[t0], np.asarray(toks[2])]).astype(np.int32)
    got = np.concatenate([outs[-1][None], np.asarray(dec[2])[:4]])
    geo = serving.geometry(spec)
    ch = np.zeros((serving.MAX_CHUNKS, 64), np.int32)
    ch[0, :32], ch[1, :18] = prompt[:32], prompt[32:]
    nch = np.array([32, 18, 0, 0], np.int32)
    ref = np.asarray(serve_logits(params, geo, jnp.asarray(ch),
                                  jnp.asarray(nch), jnp.asarray(served), 5))
    scale = ref.std(-1, keepdims=True)
    assert np.abs(got - ref).max() / scale.min() < 1e-3
