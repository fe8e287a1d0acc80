"""Batched serving driver.

Prefill + decode with per-layer caches; the MoSA layers realize the paper's
KV-cache reduction at serve time (streaming top-k cache, DESIGN §5).  The
decode hot path is the scan-fused chunk decoder of DESIGN §6: one jit
dispatch per *chunk* of tokens instead of several dispatches per token,
sampling on-device, caches donated, and (under the ``tp`` rule sets) the
MoSA KV caches head-sharded over the ``model`` mesh axis.

Library entry points:
  * ``Server`` — holds jit'd ``prefill`` / ``decode_step`` /
    ``decode_many`` with per-cache-type shardings; ``generate`` runs
    greedy / temperature / top-k decoding for a batch in one fused program;
    ``generate_stepwise`` keeps the legacy one-dispatch-per-token loop (the
    benchmark baseline).  ``Server(paged=PagedConfig(...))`` switches the
    dense/window KV caches to the block-paged pools of
    ``repro.serve.paged_kv`` and exposes the per-row ops
    (``prefill_row`` / ``snapshot_row`` / ``restore_row`` /
    ``grow_tables``) the paged scheduler drives (DESIGN §7).
  * ``RequestPool`` — contiguous-slab continuous batching: requests occupy
    batch slots; finished slots are refilled between fused decode chunks
    (single-row masked prefill written into the batched caches) and EOS is
    honored.  This is the NON-PAGED fallback; the paged path is
    ``repro.serve.Scheduler`` (block-granular admission, prefix cache,
    preempt-to-recompute), re-exported here as ``Scheduler``.

CLI (smoke-scale):
  PYTHONPATH=src python -m repro.launch.serve --arch mosa-paper \\
      --preset smoke --variant mosa --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import get_config
from repro.dist import sharding as shd
from repro.dist import hints
from repro.dist.fault_tolerance import elastic_plan
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import use_compile_cache
from repro.nn.module import init_shapes
from repro.nn.transformer import TransformerLM, sample_logits
from repro.serve.paged_kv import (PAGED_CACHE_TYPES, POOL_FIELDS,
                                  PagedConfig, PagedDenseKVCache,
                                  PagedWindowKVCache)
from repro.serve.scheduler import Scheduler  # noqa: F401  (re-export)


# ---------------------------------------------------- batch-row cache ops
# The serving caches are one pytree holding B rows; continuous batching
# needs to prefill / snapshot / restore ONE row without touching the
# others.  For contiguous caches a row is just index b of every leaf; for
# paged caches the POOL fields (see ``paged_kv.POOL_FIELDS``) are shared by
# all rows and pass through whole, while tables / positions / lengths are
# per-row.  Layer-stacked ``scan`` caches shift the batch dim right by the
# layer axis (DESIGN §2), handled here by vmapping the per-type op over the
# layer axis.

def _is_stacked(path) -> bool:
    return any(getattr(e, "key", None) == "scan" for e in path)


def _is_paged(x) -> bool:
    return isinstance(x, PAGED_CACHE_TYPES)


def row_slice(caches, b):
    """A batch-of-1 view of row ``b``: row fields sliced, pools shared —
    ``model.prefill`` on the view writes through to the shared pools."""
    def one(path, leaf):
        ax = 1 if _is_stacked(path) else 0
        if _is_paged(leaf):
            return type(leaf)(*(
                arr if name in POOL_FIELDS
                else jax.lax.dynamic_slice_in_dim(arr, b, 1, ax)
                for name, arr in zip(leaf._fields, leaf)))
        return jax.lax.dynamic_slice_in_dim(leaf, b, 1, ax)
    return jax.tree_util.tree_map_with_path(one, caches, is_leaf=_is_paged)


def row_write(caches, row, b):
    """Write a batch-of-1 row view back at row ``b``.  Paged pools REPLACE
    the batched pools (the view's writes only touched this row's blocks);
    row fields update in place."""
    def one(path, dst, src):
        ax = 1 if _is_stacked(path) else 0
        if _is_paged(dst):
            return type(dst)(*(
                s if name in POOL_FIELDS
                else jax.lax.dynamic_update_slice_in_dim(
                    d, s.astype(d.dtype), b, ax)
                for name, d, s in zip(dst._fields, dst, src)))
        return jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), b, ax)
    return jax.tree_util.tree_map_with_path(one, caches, row,
                                            is_leaf=_is_paged)


def _snap_paged(leaf, b):
    """Row snapshot of one UNSTACKED paged cache: per-row metadata plus —
    for the window ring, whose blocks are mutated in place and therefore
    never shared — the gathered ring CONTENT (bounded by W)."""
    if isinstance(leaf, PagedDenseKVCache):
        return {"block_table": leaf.block_table[b], "length": leaf.length[b]}
    bt = jnp.clip(leaf.block_table[b], 0)
    k = leaf.k[bt].reshape(leaf.window, *leaf.k.shape[2:])
    v = leaf.v[bt].reshape(leaf.window, *leaf.v.shape[2:])
    return {"block_table": leaf.block_table[b], "k": k, "v": v,
            "positions": leaf.positions[b], "length": leaf.length[b]}


def _restore_paged(leaf, snap, b):
    """Inverse of ``_snap_paged`` at row ``b``.  The caller (Scheduler) has
    rewritten ``snap["block_table"]`` to freshly owned / incref'd block ids;
    window ring content is scattered into the NEW blocks."""
    if isinstance(leaf, PagedDenseKVCache):
        return leaf._replace(
            block_table=leaf.block_table.at[b].set(snap["block_table"]),
            length=leaf.length.at[b].set(snap["length"]))
    W, bs = leaf.window, leaf.block_size
    slots = jnp.arange(W, dtype=jnp.int32)
    blk = snap["block_table"][slots // bs]
    blk = jnp.where(blk < 0, leaf.k.shape[0], blk)
    off = slots % bs
    return leaf._replace(
        k=leaf.k.at[blk, off].set(snap["k"].astype(leaf.k.dtype),
                                  mode="drop"),
        v=leaf.v.at[blk, off].set(snap["v"].astype(leaf.v.dtype),
                                  mode="drop"),
        block_table=leaf.block_table.at[b].set(snap["block_table"]),
        positions=leaf.positions.at[b].set(snap["positions"]),
        length=leaf.length.at[b].set(snap["length"]))


def row_snapshot(caches, b):
    """Host-restorable state of row ``b``: paged metadata + ring content,
    full rows of every unpaged leaf (MoSA caches, SSM states).  Everything
    bounded — the quadratic dense KV stays behind block ids."""
    def one(path, leaf):
        if _is_paged(leaf):
            if _is_stacked(path):
                return jax.vmap(_snap_paged, in_axes=(0, None))(leaf, b)
            return _snap_paged(leaf, b)
        ax = 1 if _is_stacked(path) else 0
        return jax.lax.dynamic_slice_in_dim(leaf, b, 1, ax)
    return jax.tree_util.tree_map_with_path(one, caches, is_leaf=_is_paged)


def row_restore(caches, snap, b):
    """Write a ``row_snapshot`` back at row ``b`` (admission reset, prefix
    restore, preempt-resume)."""
    def one(path, dst, s):
        if _is_paged(dst):
            if _is_stacked(path):
                return jax.vmap(_restore_paged, in_axes=(0, 0, None))(
                    dst, s, b)
            return _restore_paged(dst, s, b)
        ax = 1 if _is_stacked(path) else 0
        return jax.lax.dynamic_update_slice_in_dim(
            dst, s.astype(dst.dtype), b, ax)
    return jax.tree_util.tree_map_with_path(one, caches, snap,
                                            is_leaf=_is_paged)


def set_dense_tables(caches, dense_row, b):
    """Point row ``b``'s dense block tables (every paged dense layer shares
    one logical chain) at ``dense_row`` — the decode-time growth write."""
    def one(path, leaf):
        if not isinstance(leaf, PagedDenseKVCache):
            return leaf
        if _is_stacked(path):
            bt = leaf.block_table.at[:, b].set(dense_row[None])
        else:
            bt = leaf.block_table.at[b].set(dense_row)
        return leaf._replace(block_table=bt)
    return jax.tree_util.tree_map_with_path(one, caches, is_leaf=_is_paged)


def set_window_tables(caches, window_row, b):
    """Point row ``b``'s window-ring block tables at ``window_row`` — the
    lazy-ring growth write (rings allocate blocks on first write, not at
    admission; -1 tail entries mean 'not yet written this far')."""
    def one(path, leaf):
        if not isinstance(leaf, PagedWindowKVCache):
            return leaf
        if _is_stacked(path):
            bt = leaf.block_table.at[:, b].set(window_row[None])
        else:
            bt = leaf.block_table.at[b].set(window_row)
        return leaf._replace(block_table=bt)
    return jax.tree_util.tree_map_with_path(one, caches, is_leaf=_is_paged)


class Server:
    def __init__(self, model_cfg, mesh=None, rule_set: str = "tp",
                 max_len: int = 256, batch: int = 4, params=None,
                 seq_sharded: bool = False,
                 paged: Optional[PagedConfig] = None):
        """``paged``: switch the dense/window KV caches to the block-paged
        pools of ``repro.serve.paged_kv`` (DESIGN §7).  With the default
        auto-sized pools (``num_blocks == 0``) every row owns a worst-case
        identity chain and ``generate`` works unchanged; with explicit
        budgets the block tables start unallocated and admission is the
        ``repro.serve.Scheduler``'s job."""
        self.model_cfg = model_cfg
        self.model = TransformerLM(model_cfg)
        if mesh is None:
            plan = elastic_plan(len(jax.devices()), tp=1)
            mesh = mesh_lib.make_mesh(plan["shape"], plan["axes"])
        self.mesh = mesh
        self.max_len = max_len
        self.batch = batch
        self.paged = paged

        shapes = init_shapes(self.model)
        self.param_sh = shd.param_shardings(self.model, mesh, rule_set, shapes)
        cache_shapes = jax.eval_shape(
            lambda: self.model.init_cache(batch, max_len, paged=paged))
        self.cache_sh = shd.cache_shardings(cache_shapes, mesh, rule_set,
                                            seq_sharded=seq_sharded)
        tok_sh = shd.batch_sharding(mesh, rule_set, batch=batch)

        self.prefill = jax.jit(
            self.model.prefill,
            in_shardings=(self.param_sh, tok_sh, self.cache_sh),
            out_shardings=(None, self.cache_sh))
        # decode-time ``tok`` inherits its sharding (see _decode_many below).
        self.decode_step = jax.jit(
            self.model.decode_step,
            in_shardings=(self.param_sh, None, self.cache_sh),
            out_shardings=(None, self.cache_sh),
            donate_argnums=(2,))
        # The fused chunk decoder: n decode steps + on-device sampling in one
        # program; caches donated so XLA updates them in place.
        # static_argnums + positional calls: jit rejects kwargs outright when
        # in_shardings is given, so (n, top_k, return_logits) travel
        # positionally.  ``temperature`` stays TRACED so sweeping it
        # never recompiles the n-step program.  ``tok`` inherits its incoming
        # sharding (None): it is a committed on-device array sampled from the
        # previous chunk's (replicated) logits, and pinning it to the batch
        # sharding makes pjit reject the replicated layout outright.
        self._decode_many = jax.jit(
            self.model.decode_many,
            static_argnums=(4, 6, 7),
            in_shardings=(self.param_sh, None, self.cache_sh, None, None),
            out_shardings=(None, self.cache_sh),
            donate_argnums=(2,))
        self.sample = jax.jit(sample_logits, static_argnames=("top_k",))

        def decode_many(params, tok, caches, key, n, temperature=0.0,
                        top_k=0):
            reg = obs.registry()
            if reg.enabled:       # dispatch counters only — no device sync
                reg.inc("server.decode_dispatches")
                reg.inc("server.decode_steps", n)
                reg.set("server.decode_batch", tok.shape[0])
            return self._decode_many(params, tok, caches, key, n,
                                     jnp.float32(temperature), top_k, False)
        self.decode_many = decode_many

        # Single-row prefill + slot write: continuous batching refills one
        # finished slot without touching the other rows' caches.  The
        # prompt arrives RIGHT-padded to its bucket with a ``valid`` mask
        # and per-row ``last_pos`` — causality keeps pads out of real
        # tokens' attention, MoSA masks them out of selection, and cache
        # lengths advance by real tokens only (the masked-prefill fix;
        # DESIGN §7).
        cache_shapes1 = jax.eval_shape(
            lambda: self.model.init_cache(1, max_len))
        self.cache_sh1 = shd.cache_shardings(cache_shapes1, mesh, rule_set,
                                             seq_sharded=seq_sharded)

        def _prefill_one(params, tokens, caches, valid, last_pos):
            return self.model.prefill(params, tokens, caches, None, None,
                                      valid, last_pos)

        self.prefill_one = jax.jit(
            _prefill_one,
            in_shardings=(self.param_sh, None, self.cache_sh1, None, None),
            out_shardings=(None, self.cache_sh1))

        def _write_slot(batched, row, b):
            return row_write(batched, row, b)

        self.write_slot = jax.jit(_write_slot, donate_argnums=(0,),
                                  out_shardings=self.cache_sh)

        # Paged row ops (Scheduler path, DESIGN §7): prefill one row IN
        # PLACE of the batched caches — the row view shares the pools, so
        # appended KV lands directly in this row's allocated blocks —
        # plus snapshot / restore / table-growth writes.
        def _prefill_row(params, prompt, caches, b, valid, last_pos,
                         continued):
            row = row_slice(caches, b)
            logits, row = self.model.prefill(params, prompt, row, None, None,
                                             valid, last_pos, continued)
            return logits, row_write(caches, row, b)

        self.prefill_row = jax.jit(
            _prefill_row, static_argnums=(6,),
            in_shardings=(self.param_sh, None, self.cache_sh, None, None,
                          None),
            out_shardings=(None, self.cache_sh), donate_argnums=(2,))

        # Packed multi-segment chunked prefill (DESIGN §9): ONE program for
        # every chunk of every prompt mix — (C, N) are static, raggedness
        # lives in cu_seqlens/rows/past_lens data.  This replaces the
        # Scheduler's former pow2-bucket prefill ladder (log2(max_len)
        # compiles) with a single compile.
        def _prefill_packed(params, tokens, caches, cu, rows, past_lens):
            return self.model.prefill_packed(params, tokens, caches, cu,
                                             rows, past_lens)

        self._prefill_packed_jit = jax.jit(
            _prefill_packed,
            in_shardings=(self.param_sh, None, self.cache_sh, None, None,
                          None),
            out_shardings=(None, self.cache_sh), donate_argnums=(2,))

        def prefill_packed(params, tokens, caches, cu, rows, past_lens):
            reg = obs.registry()
            if reg.enabled:
                reg.inc("server.prefill_dispatches")
                reg.set("server.prefill_tokens", tokens.shape[-1])
            return self._prefill_packed_jit(params, tokens, caches, cu, rows,
                                            past_lens)
        self.prefill_packed = prefill_packed
        self.snapshot_row = jax.jit(row_snapshot)
        self.restore_row = jax.jit(row_restore, donate_argnums=(0,),
                                   out_shardings=self.cache_sh)
        self.grow_tables = jax.jit(set_dense_tables, donate_argnums=(0,),
                                   out_shardings=self.cache_sh)
        self.grow_window_tables = jax.jit(set_window_tables,
                                          donate_argnums=(0,),
                                          out_shardings=self.cache_sh)

        if params is None:
            with mesh:
                params = jax.jit(self.model.init,
                                 out_shardings=self.param_sh)(
                    jax.random.PRNGKey(0))
        self.params = params

    def new_cache(self, batch: Optional[int] = None):
        batch = self.batch if batch is None else batch
        sh = self.cache_sh if batch == self.batch else self.cache_sh1
        paged = self.paged if batch == self.batch else None
        with self.mesh:
            return jax.jit(
                lambda: self.model.init_cache(batch, self.max_len,
                                              paged=paged),
                out_shardings=sh)()

    def generate(self, prompts: jnp.ndarray, gen_len: int,
                 temperature: float = 0.0, key=None, top_k: int = 0):
        """prompts: (B, P) int32 -> ((B, gen_len) int32, caches).

        One prefill dispatch + ONE fused decode dispatch for the whole
        completion; greedy when ``temperature == 0``.
        """
        B, P = prompts.shape
        assert B == self.batch
        assert self.paged is None or (self.paged.num_blocks == 0 and
                                      self.paged.num_window_blocks == 0), (
            "generate needs auto-sized paged pools (identity block tables);"
            " budgeted pools are managed by repro.serve.Scheduler")
        assert P + gen_len - 1 <= self.max_len, (
            f"prompt ({P}) + {gen_len - 1} decode steps exceeds max_len "
            f"{self.max_len}: appends past the cache end are silently "
            f"dropped (masked update never matches)")
        caches = self.new_cache()
        if key is None:
            key = jax.random.PRNGKey(0)
        k0, kd = jax.random.split(key)
        with self.mesh, hints.sharding_hints(mesh=self.mesh):
            logits, caches = self.prefill(self.params, prompts, caches)
            tok0 = self.sample(logits[:, -1], k0, jnp.float32(temperature),
                               top_k=top_k)
            toks, caches = self.decode_many(
                self.params, tok0[:, None], caches, kd, gen_len - 1,
                temperature, top_k)
        return jnp.concatenate([tok0[:, None], toks], axis=1), caches

    def generate_stepwise(self, prompts: jnp.ndarray, gen_len: int,
                          temperature: float = 0.0, key=None, top_k: int = 0):
        """Legacy per-token loop (one jit dispatch + eagerly dispatched
        sampling ops per token; jax's async dispatch means the host blocks
        only at the end, so the fused path's win over this baseline is
        per-token dispatch overhead, not removed host syncs).

        Kept as the benchmark baseline for the fused path — see
        ``benchmarks/serve_bench.py`` and DESIGN §6.  Sampling goes through
        the same jitted ``sample_logits`` as the fused path.
        """
        B, P = prompts.shape
        assert B == self.batch
        assert P + gen_len - 1 <= self.max_len, (
            f"prompt ({P}) + {gen_len - 1} decode steps exceeds max_len "
            f"{self.max_len}")
        caches = self.new_cache()
        if key is None:
            key = jax.random.PRNGKey(0)
        temp = jnp.float32(temperature)
        with self.mesh, hints.sharding_hints(mesh=self.mesh):
            logits, caches = self.prefill(self.params, prompts, caches)
            key, sub = jax.random.split(key)
            tok = self.sample(logits[:, -1], sub, temp, top_k=top_k)[:, None]
            out = [tok]
            for i in range(gen_len - 1):
                logits, caches = self.decode_step(self.params, tok, caches)
                key, sub = jax.random.split(key)
                tok = self.sample(logits[:, -1], sub, temp,
                                  top_k=top_k)[:, None]
                out.append(tok)
        return jnp.concatenate(out, axis=1), caches


@dataclasses.dataclass
class Request:
    rid: int
    prompt: jnp.ndarray
    max_new: int
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False


class RequestPool:
    """Continuous batching: fixed B slots over one batched cache.

    Decode runs in fused chunks (``Server.decode_many``).  Between chunks,
    requests that finished (EOS, per-request ``max_new``, or the global
    ``max_steps`` budget) free their slot, and queued requests take it over:
    the new prompt is prefilled batch-of-one and written into that row of
    the batched caches (every cache keeps per-row ``length``, so rows at
    different sequence positions coexist).  Prompts are left-padded to a
    fixed bucket so the single-row prefill compiles once.

    Length policy (clamps, not errors): each request prefills at its own
    power-of-two bucket (``prefill_len`` pins a fixed bucket instead; both
    capped at the server's ``max_len``), so a request's output never
    depends on what else is queued and at most log2(max_len) prefill
    programs compile.  Prompts longer than the bucket are LEFT-truncated to
    their most recent tokens; shorter prompts are RIGHT-padded with a
    ``valid`` mask and per-row ``last_pos`` — causality keeps pads out of
    every real token's attention, MoSA masks them out of expert-choice
    selection, and cache lengths advance by real tokens only, so decode
    overwrites the pad tail in place (masked prefill, DESIGN §7; the
    former LEFT-pad scheme attended pads and is gone).  ``max_new`` is
    clamped so prompt + completion fits ``max_len`` — against the REAL
    prompt length, so padding no longer costs cache capacity.

    This pool is the NON-PAGED fallback: slots reserve worst-case
    contiguous slabs and the pow2 bucket doubles as the admission
    granularity.  With ``Server(paged=...)`` use ``repro.serve.Scheduler``
    instead — admission there is block-granular (the bucket only caps how
    many prefill programs compile) and exhaustion preempts-to-recompute
    rather than queueing forever.

    ``eos``: token id that ends a request (included in its output); ``< 0``
    disables EOS stopping.
    """

    def __init__(self, server: Server, eos: int = -1, chunk: int = 8,
                 prefill_len: Optional[int] = None):
        assert server.paged is None, (
            "RequestPool is the contiguous-slab fallback; a paged Server "
            "is driven by repro.serve.Scheduler instead")
        self.server = server
        self.eos = eos
        self.chunk = chunk
        self.prefill_len = prefill_len
        self.queue: list = []

    def submit(self, prompt, max_new: int):
        rid = len(self.queue)
        self.queue.append(Request(rid, jnp.asarray(prompt, jnp.int32), max_new))
        return rid

    def _bucket(self, prompt_len: int) -> int:
        """Pow2 prefill bucket — kept ONLY for this non-paged pool, where
        the bucket doubles as the slot's cache reservation.  The paged
        ``repro.serve.Scheduler`` admits block-granularly and buckets only
        to bound how many prefill programs compile."""
        if self.prefill_len:
            return min(self.prefill_len, self.server.max_len)
        b = 1
        while b < max(prompt_len, 1):
            b *= 2
        return min(b, self.server.max_len)

    def run(self, max_steps: int = 1000):
        """Serve every queued request; returns {rid: generated tokens}.

        ``max_steps`` caps the total number of decode steps across the whole
        pool — when the budget runs out, in-flight requests return whatever
        they generated so far and the remaining queue is left unserved.
        """
        srv = self.server
        B = srv.batch
        results: dict = {}
        slots: list = [None] * B
        caches = srv.new_cache()
        cur = jnp.zeros((B, 1), jnp.int32)
        key = jax.random.PRNGKey(0)
        steps = 0

        def finish(b):
            r = slots[b]
            r.done = True
            results[r.rid] = jnp.asarray(r.generated, jnp.int32)
            slots[b] = None

        with srv.mesh, hints.sharding_hints(mesh=srv.mesh):
            while self.queue or any(s is not None for s in slots):
                # Refill free slots: single-row prefill -> write into row b.
                for b in range(B):
                    if slots[b] is None and self.queue and steps < max_steps:
                        r = self.queue.pop(0)
                        bucket = self._bucket(len(r.prompt))
                        prompt = r.prompt[-bucket:]
                        P = len(prompt)
                        # clamp so the completion fits the cache: positions
                        # P..max_len-1 hold the decoded tokens' KV (pads
                        # cost nothing — decode overwrites them)
                        r.max_new = min(r.max_new, srv.max_len - P + 1)
                        prompt = jnp.pad(prompt, (0, bucket - P))
                        valid = (jnp.arange(bucket) < P)[None]
                        row = srv.new_cache(batch=1)
                        logits, row = srv.prefill_one(
                            srv.params, prompt[None], row, valid,
                            jnp.full((1,), P - 1, jnp.int32))
                        caches = srv.write_slot(caches, row, b)
                        tok0 = srv.sample(logits[:, -1], key)
                        cur = cur.at[b, 0].set(tok0[0])
                        slots[b] = r
                        r.generated.append(int(tok0[0]))
                        if r.max_new <= 1 or int(tok0[0]) == self.eos:
                            finish(b)
                if not any(s is not None for s in slots):
                    if steps >= max_steps:
                        break
                    continue
                if steps >= max_steps:
                    for b in range(B):
                        if slots[b] is not None:
                            finish(b)
                    break

                # One fused decode chunk for all live rows.  Chunk length is
                # clamped to the longest remaining request so a nearly-done
                # cohort doesn't burn a full chunk (n stays in [1, chunk], so
                # at most `chunk` distinct programs ever compile).
                need = max(r.max_new - len(r.generated)
                           for r in slots if r is not None)
                n = max(min(self.chunk, max_steps - steps, need), 1)
                key, sub = jax.random.split(key)
                toks, caches = srv.decode_many(srv.params, cur, caches, sub, n)
                steps += n
                host = jax.device_get(toks)
                cur = toks[:, -1:]
                for b in range(B):
                    r = slots[b]
                    if r is None:
                        continue
                    for t in host[b]:
                        r.generated.append(int(t))
                        if int(t) == self.eos or len(r.generated) >= r.max_new:
                            finish(b)
                            break
        return results


def _load_run(cfg, args):
    """``--load-rate``: seeded open-loop traffic through the timed paged
    Scheduler, SLO/goodput summary on stdout (DESIGN §12)."""
    import json

    from repro.obs.slo import SLOSpec, evaluate
    from repro.serve.loadgen import (OpenLoopSource, bursty_workload,
                                     poisson_workload)

    nb = -(-args.max_len // 16)
    server = Server(cfg, batch=args.batch, max_len=args.max_len,
                    paged=PagedConfig(block_size=16,
                                      num_blocks=args.batch * nb,
                                      num_window_blocks=4 * args.batch))
    build = bursty_workload if args.bursty else poisson_workload
    wl = build(args.load_rate, args.load_n, args.load_seed, cfg.vocab)
    sched = Scheduler(server, max_queue=args.max_queue or None,
                      metrics_path=args.metrics_path,
                      trace_path=args.trace_path)
    t0 = time.perf_counter()
    sched.run(max_steps=100_000, source=OpenLoopSource(wl))
    dt = time.perf_counter() - t0
    spec = SLOSpec(ttft_s=args.ttft_slo, tpot_s=args.tpot_slo or None)
    ev = evaluate(list(sched.records.values()), spec)
    ev["offered_req_s"] = args.load_rate
    ev["duration_s"] = round(dt, 3)
    print(json.dumps(ev, indent=2, sort_keys=True))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="mosa-paper")
    p.add_argument("--preset", default="smoke")
    p.add_argument("--variant", default=None)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=16)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--stepwise", action="store_true",
                   help="use the legacy per-token loop instead of the "
                        "fused chunk decoder")
    p.add_argument("--metrics-path", default=None,
                   help="write an obs metrics snapshot here on exit "
                        "(.jsonl appends; DESIGN §11)")
    p.add_argument("--trace-path", default=None,
                   help="write a Chrome-trace JSON of the run here on exit")
    p.add_argument("--load-rate", type=float, default=0.0,
                   help="instead of one batch generate, drive the timed "
                        "Scheduler with a seeded open-loop arrival stream "
                        "at this rate (req/s) and print the SLO/goodput "
                        "summary (DESIGN §12)")
    p.add_argument("--load-n", type=int, default=32,
                   help="requests in the load run")
    p.add_argument("--load-seed", type=int, default=0)
    p.add_argument("--bursty", action="store_true",
                   help="Gamma (CV=3) interarrivals instead of Poisson")
    p.add_argument("--max-queue", type=int, default=0,
                   help="shed arrivals past this queue depth "
                        "(0 = never shed)")
    p.add_argument("--ttft-slo", type=float, default=0.5,
                   help="TTFT SLO in seconds for the load-run goodput")
    p.add_argument("--tpot-slo", type=float, default=0.0,
                   help="TPOT SLO in seconds (0 = no TPOT obligation)")
    args = p.parse_args(argv)
    use_compile_cache()

    akw = {"variant": args.variant} if args.variant else {}
    cfg = get_config(args.arch, preset=args.preset, **akw)
    if args.load_rate > 0:
        return _load_run(cfg, args)
    server = Server(cfg, batch=args.batch, max_len=args.max_len)
    key = jax.random.PRNGKey(0)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 2,
                                 cfg.vocab)
    gen = server.generate_stepwise if args.stepwise else server.generate
    toks, caches = gen(prompts, args.gen, temperature=args.temperature,
                       key=key, top_k=args.top_k)
    jax.block_until_ready(toks)   # warm (compile) outside the timing
    t0 = time.perf_counter()
    toks, caches = gen(prompts, args.gen, temperature=args.temperature,
                       key=key, top_k=args.top_k)
    jax.block_until_ready(toks)
    dt = time.perf_counter() - t0
    print(f"generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s, "
          f"{'stepwise' if args.stepwise else 'fused'})")
    print(toks[0])
    # report the paper's KV metric if the model has MoSA layers
    if cfg.mosa is not None:
        from repro.core.hybrid import HybridAttention
        hy = HybridAttention(cfg.d_model, cfg.mosa)
        print(f"KV entries per MoSA layer: {hy.kv_total(args.max_len)} "
              f"(dense equivalent: "
              f"{args.max_len * (cfg.mosa.n_dense_heads + cfg.mosa.n_mosa_heads)})")
    obs.dump(args.metrics_path, args.trace_path, tag="serve-cli")


if __name__ == "__main__":
    main()
