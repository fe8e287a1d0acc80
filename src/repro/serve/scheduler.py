"""Block-granular continuous batching over a paged ``Server`` (DESIGN §7).

Replaces ``launch.serve.RequestPool``'s pow2-bucket slot logic for the paged
path.  Where the pool reserves a worst-case contiguous slab per slot (so
capacity is ``HBM / slab``, no matter how short requests actually are), the
scheduler admits requests while FREE BLOCKS suffice and grows each row's
dense block chain one block at a time as decode proceeds:

  * **Admission** — a request needs ``ceil(P / bs)`` dense blocks for its
    prompt (minus any prefix-cache hit) plus ring blocks covering the ring
    slots the prompt actually WRITES (``ceil(min(P, W) / bs)`` — lazy ring
    allocation; a short prompt on a large window holds a sliver of the
    ring, not all of it); if the pools cannot cover that after LRU-evicting
    unused prefix-cache entries, the request waits in the queue.
  * **Decode growth** — before each fused chunk, rows crossing a dense
    block boundary get a fresh block (``Server.grow_tables``), and rows
    whose next ``n`` tokens reach unallocated ring slots get those ring
    blocks (``Server.grow_window_tables``); once a row has seen ``W``
    tokens its ring is complete and never grows again.  The allocate-
    before-write discipline is the safety invariant: a window write
    through a ``-1`` table entry would drop the KV but still record the
    slot's position, making decode read junk — guarded by
    tests/test_paged_kv.py's lazy-ring invariant test.
  * **Preempt-to-recompute** — when growth cannot be satisfied, the
    latest-admitted victim releases all its blocks and re-enters the queue
    with ``prompt + generated`` as its new prompt (recompute, not swap:
    MoSA's O(k) caches make recompute cheap relative to reserving swap
    space), so the oldest requests always run to completion — no livelock.
    For dense/window models preemption is token-invisible (recompute is
    exact; asserted in tests); on MoSA hybrids the recomputed prefill
    replaces the streamed selection — the same approximation family as
    decode itself.
  * **Prefix cache** — prompts are matched against the block trie
    (``repro.serve.prefix_cache``); a hit increfs the shared dense blocks,
    restores the boundary snapshot (MoSA caches, window ring content), and
    prefills ONLY the unshared suffix (``continued=True`` — the exact union
    selection of ``MoSAAttention.prefill_past``).  On a miss the prefill is
    split at the shareable boundary so the inserted snapshot is a function
    of the prefix tokens alone — the causality prefix reuse requires.
    Chunk-causal note: for TOKEN-choice MoSA layers this split is the same
    approximation family as streaming decode (training-style expert choice
    is non-causal and therefore CANNOT be prefix-cached); for dense/window
    models the split is exact.  BLOCK-choice MoSA (DESIGN §10) closes the
    gap: snapshots land on ``sel_block_size`` boundaries, where the
    ``MoSABlockKVCache`` holds only completed-block means — a pure function
    of the prefix tokens — so a prefix hit reproduces the cold path
    exactly.  ``prefix_cache=False`` restores one-shot training-style
    prefill.

  * **Chunked packed prefill** (DESIGN §9) — prompts are streamed through
    ``Server.prefill_packed`` in fixed ``chunk_tokens``-sized packed
    chunks: up to ``max_prefill_segs`` pending rows' next segments are
    flattened back to back into ONE fused program per chunk, with
    ``cu_seqlens``/``rows``/``past_lens`` carrying the raggedness as data.
    Exactly one prefill program compiles — this replaces the former pow2
    bucket ladder (log2(max_len) programs, up to 2x padding waste), and a
    long prompt can no longer stall TTFT: decode chunks of live rows
    interleave between its prefill chunks (mid-prefill rows are paused —
    snapshot + empty tables so the decode dispatch's writes drop — and
    resumed before their next chunk).  Chunking is EXACT, for every chunk
    split: attention is past-aware through the paged pools and MoSA's
    capacity-wide union selection (``prefill_past``) reproduces one-shot
    selection bit-for-bit; selection width is ``k_for`` of each row's REAL
    prompt length — per segment, never per padded row.

No imports from ``repro.launch`` (the server arrives duck-typed), so the
launch layer can re-export this scheduler without a cycle.
"""

from __future__ import annotations

import copy
import dataclasses
import functools
import time
from collections import OrderedDict
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.kv_cache import MoSABlockKVCache, MoSAKVCache
from repro.dist import hints
from repro.serve.paged_kv import (BlockPool, PagedDenseKVCache,
                                  PagedWindowKVCache)
from repro.serve.prefix_cache import PrefixCache

# Bounded retention for the deprecated per-rid TTFT map (DESIGN §11): the
# histogram is the real record; this keeps only the most recent rids.
TTFT_KEEP = 4096

# Bounded retention for per-request SLO records (DESIGN §12): goodput is
# computed over a load run's worth of requests, not unbounded history.
RECORDS_KEEP = 8192


@dataclasses.dataclass
class _Request:
    rid: int
    prompt: jnp.ndarray
    max_new: int
    generated: list = dataclasses.field(default_factory=list)
    t_submit: float = 0.0        # tracer clock at submit/requeue
    t_arrival: float = 0.0       # tracer clock at ORIGINAL submit — unlike
    tenant: str = ""             # t_submit it survives preemption, so TTFT
    ttft: Optional[float] = None  # stays arrival-based (§12)
    prefill_s: Optional[float] = None  # admission to first token


def _sched_span(name: str):
    """Record every call of the decorated method as span ``name`` on the
    ``sched`` track (DESIGN §11)."""
    def wrap(fn):
        @functools.wraps(fn)
        def spanned(self, *args, **kwargs):
            with obs.tracer().span(name, track="sched"):
                return fn(self, *args, **kwargs)
        return spanned
    return wrap


def _cache_leaves(caches):
    is_leaf = (lambda x: isinstance(x, (PagedDenseKVCache,
                                        PagedWindowKVCache, MoSAKVCache,
                                        MoSABlockKVCache)))
    return jax.tree_util.tree_leaves(caches, is_leaf=is_leaf)


def _paged_entries(snap):
    """The paged-cache dicts inside a host row snapshot (they are the only
    dicts carrying a ``block_table`` key — see ``launch.serve.row_snapshot``
    for the structure)."""
    out = []

    def walk(x):
        if isinstance(x, dict):
            if "block_table" in x:
                out.append(x)
                return
            for v in x.values():
                walk(v)
        elif hasattr(x, "_fields"):
            for v in x:
                walk(v)

    walk(snap)
    return out


def _set_snapshot_tables(snap, dense_row, window_row):
    """Point a host snapshot's block tables at ``dense_row`` /
    ``window_row`` (np int32, -1 padded).  Window entries are the ones
    carrying ring content (``"k"``); stacked tables broadcast the row over
    the layer axis."""
    for e in _paged_entries(snap):
        row = window_row if "k" in e else dense_row
        bt = e["block_table"]
        if bt.ndim == row.ndim + 1:          # layer-stacked (scan) cache
            e["block_table"] = np.broadcast_to(
                row, bt.shape).astype(np.int32).copy()
        else:
            e["block_table"] = row.astype(np.int32).copy()


def _table_row(ids: List[int], width: int) -> np.ndarray:
    row = np.full((width,), -1, np.int32)
    row[:len(ids)] = ids
    return row


# ----------------------------------------------- serve-time router health
def _selection_health(pos, wt, n_slots: int) -> dict:
    """Host-side analog of ``repro.core.router.router_health_stats`` over a
    flat list of selected slot indices ``pos`` with selection weights
    ``wt``: entropy of the weight mass over ``n_slots`` (normalized by
    ``log n_slots``), the fraction of slots selected by no head, and the
    mean selection weight."""
    n = max(int(n_slots), 2)
    keep = (pos >= 0) & (pos < n)
    pos, wt = pos[keep], wt[keep]
    counts = np.bincount(pos, minlength=n)
    mass = np.bincount(pos, weights=np.maximum(wt, 0.0), minlength=n)
    tot = mass.sum()
    p = (mass / tot) if tot > 0 else np.full(n, 1.0 / n)
    ent = float(-(p * np.log(np.maximum(p, 1e-12))).sum() / np.log(n))
    return {"sel_entropy": ent,
            "drop_rate": float((counts == 0).mean()),
            "head_util": float(wt.mean()) if wt.size else 0.0}


def _router_health_from_snapshot(snap, P: int) -> dict:
    """MoSA router health for one request, computed from the HOST row
    snapshot its prefill just produced (DESIGN §11) — numpy on data already
    fetched for snapshotting, no extra device work beyond the row gather.

    Token-choice caches score ``min(capacity, P)`` kept tokens over the
    ``P`` prompt positions; block-choice caches score their COMPLETED
    blocks (slot CB, the partial block, excluded) over ``ceil(P / bs)``
    pool blocks.  Stats are averaged across every routed layer instance
    (stacked scan layers contribute one sample per layer)."""
    samples: List[dict] = []

    def walk(x):
        if isinstance(x, MoSAKVCache):
            s = np.asarray(x.scores, np.float64)
            ix = np.asarray(x.idx, np.int64)
            s2 = s.reshape(-1, s.shape[-2] * s.shape[-1])
            i2 = ix.reshape(-1, ix.shape[-2] * ix.shape[-1])
            for l in range(s2.shape[0]):
                valid = np.isfinite(s2[l]) & (i2[l] >= 0)
                samples.append(_selection_health(
                    i2[l][valid], s2[l][valid], P))
            return
        if isinstance(x, MoSABlockKVCache):
            bsc = np.asarray(x.bscore, np.float64)[..., :-1]
            bix = np.asarray(x.bidx, np.int64)[..., :-1]
            bs = x.k.shape[-2] // x.bscore.shape[-1]
            nb = -(-int(P) // max(bs, 1))
            cb = bsc.shape[-1]
            s2 = bsc.reshape(-1, bsc.shape[-2] * cb)
            i2 = bix.reshape(-1, bix.shape[-2] * cb)
            for l in range(s2.shape[0]):
                valid = np.isfinite(s2[l]) & (i2[l] >= 0)
                samples.append(_selection_health(
                    i2[l][valid], s2[l][valid], nb))
            return
        if isinstance(x, dict):
            for v in x.values():
                walk(v)
        elif hasattr(x, "_fields"):
            for v in x:
                walk(v)

    walk(snap)
    if not samples:
        return {}
    return {k: float(np.mean([s[k] for s in samples]))
            for k in ("sel_entropy", "drop_rate", "head_util")}


class Scheduler:
    """Continuous batching with block-granular admission.

    ``server``: a ``launch.serve.Server`` built with
    ``paged=PagedConfig(num_blocks=..., num_window_blocks=...)`` — explicit
    budgets; the worst-case auto sizing would make admission vacuous.
    """

    def __init__(self, server, eos: int = -1, chunk: int = 8,
                 chunk_tokens: int = 64, max_prefill_segs: int = 4,
                 prefix_cache: bool = True,
                 metrics_path: Optional[str] = None,
                 trace_path: Optional[str] = None,
                 router_health_every: int = 4,
                 max_queue: Optional[int] = None):
        """``chunk``: decode tokens per fused decode dispatch.
        ``chunk_tokens``: the packed prefill chunk budget C — every prefill
        dispatch processes exactly C token slots (ONE compiled program);
        ``max_prefill_segs``: max pending rows packed per chunk (N).

        Observability (DESIGN §11): metrics/spans go to the global
        ``repro.obs`` registry/tracer.  ``metrics_path`` (``.jsonl``
        appends a snapshot line) and ``trace_path`` (Chrome-trace JSON)
        are written when ``run()`` drains.  ``router_health_every``: every
        Nth completed prompt on a MoSA model gets its router health
        (sel_entropy / drop_rate / head_util) sampled from the prefill's
        row snapshot — 0 disables the sampling.

        ``max_queue`` (DESIGN §12): admission-control depth — a submit
        arriving with ``max_queue`` requests already waiting is SHED
        (empty result, ``serve.shed`` counter, ``outcome="shed"`` record)
        instead of queued.  Shedding is what keeps goodput for admitted
        work through overload: without it every queued request's TTFT
        degrades together.  ``None`` (default) never sheds."""
        paged = server.paged
        assert paged is not None and paged.num_blocks > 0, (
            "Scheduler needs Server(paged=PagedConfig(num_blocks=...)) with "
            "an explicit dense-block budget")
        self.server = server
        self.eos = eos
        self.chunk = chunk
        self.chunk_tokens = min(chunk_tokens, server.max_len)
        self.max_segs = max(1, max_prefill_segs)
        self.bs = paged.block_size
        self.queue: List[_Request] = []
        self.results: dict = {}

        self.caches = server.new_cache()
        leaves = _cache_leaves(self.caches)
        dense = [x for x in leaves if isinstance(x, PagedDenseKVCache)]
        window = [x for x in leaves if isinstance(x, PagedWindowKVCache)]
        assert dense, "paged scheduler needs at least one paged dense layer"
        self.nb_max = dense[0].block_table.shape[-1]
        self.has_window = bool(window)
        self.wb = window[0].block_table.shape[-1] if window else 0
        if self.has_window:
            assert paged.num_window_blocks > 0, (
                "model has window layers: pass num_window_blocks")
        # A hit must restore per-row state beyond dense blocks (MoSA top-k
        # sets, window rings, SSM states) -> only snapshot nodes usable.
        self.need_snapshot = any(
            not isinstance(x, PagedDenseKVCache) for x in leaves)

        self.dense_pool = BlockPool(paged.num_blocks, self.bs, name="dense")
        self.window_pool = (BlockPool(paged.num_window_blocks, self.bs,
                                      name="window")
                            if self.has_window else None)
        self.prefix = PrefixCache(self.bs) if prefix_cache else None
        self._empty_row = jax.device_get(server.snapshot_row(self.caches, 0))
        # prefill_chunks * chunk_tokens is the slot count every dispatch
        # pays; prefilled_tokens / prefill_chunk_slots is the packed-token
        # efficiency the pow2 buckets never reached (BENCH_serve metric).
        self.stats = {"prefix_hits": 0, "prefix_hit_tokens": 0,
                      "prefilled_tokens": 0, "prefill_chunks": 0,
                      "prefill_chunk_slots": 0, "preemptions": 0,
                      "max_concurrent": 0}
        # rid -> TTFT seconds, bounded to the TTFT_KEEP newest rids; the
        # obs histogram serve.ttft_s is the unbounded-safe record.
        self._ttft: OrderedDict = OrderedDict()
        # rid -> per-request SLO record (obs.slo schema), bounded; written
        # at finish/shed time, consumed by obs.slo.evaluate.
        self.records: OrderedDict = OrderedDict()
        self.max_queue = max_queue
        self.metrics_path = metrics_path
        self.trace_path = trace_path
        self.router_health_every = router_health_every
        self._has_mosa = any(isinstance(x, (MoSAKVCache, MoSABlockKVCache))
                             for x in leaves)
        self._health_seen = 0

        B = server.batch
        self._slots: List[Optional[dict]] = [None] * B
        self._admit_seq = 0

    @property
    def ttft(self) -> OrderedDict:
        """Deprecated: per-rid TTFT map, now bounded to the ``TTFT_KEEP``
        most recent requests.  Read ``obs.registry()``'s ``serve.ttft_s``
        histogram (p50/p90/p99) instead."""
        return self._ttft

    def _record_ttft(self, r: _Request, dt: float) -> None:
        r.ttft = dt
        self._ttft[r.rid] = dt
        while len(self._ttft) > TTFT_KEEP:
            self._ttft.popitem(last=False)
        reg = obs.registry()
        reg.observe("serve.ttft_s", dt)
        if r.tenant:
            reg.observe("serve.ttft_s", dt, tenant=r.tenant)

    def _record(self, r: _Request, outcome: str,
                queue_delay: float = 0.0, tpot=None) -> None:
        """Append ``r``'s SLO record (obs.slo schema — parity with
        ``records_from_spans`` is tested)."""
        self.records[r.rid] = {
            "rid": r.rid, "tenant": r.tenant, "outcome": outcome,
            "t_arrival": r.t_arrival, "queue_delay_s": queue_delay,
            "ttft_s": r.ttft, "tpot_s": tpot, "prefill_s": r.prefill_s,
            "new_tokens": len(r.generated)}
        while len(self.records) > RECORDS_KEEP:
            self.records.popitem(last=False)

    def _in_flight(self) -> int:
        return sum(s is not None for s in self._slots)

    # ----------------------------------------------------------- interface
    def submit(self, prompt, max_new: int, tenant: str = "") -> int:
        rid = len(self.results) + len(self.queue) + \
            sum(s is not None for s in self._slots)
        now = obs.tracer().now()
        reg = obs.registry()
        reg.inc("serve.submitted")
        if tenant:
            reg.inc("serve.submitted", tenant=tenant)
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            # Admission control: shed rather than queue past the depth cap
            # (the queued request's TTFT would already be forfeit — see
            # __init__ docstring).  The caller still gets a result (empty).
            r = _Request(rid, jnp.zeros((0,), jnp.int32), 0,
                         t_submit=now, t_arrival=now, tenant=tenant)
            self.results[rid] = jnp.zeros((0,), jnp.int32)
            self._record(r, "shed")
            reg.inc("serve.shed")
            if tenant:
                reg.inc("serve.shed", tenant=tenant)
            obs.tracer().instant("shed", track=f"req{rid}", tenant=tenant)
            return rid
        self.queue.append(_Request(rid, jnp.asarray(prompt, jnp.int32),
                                   max_new, t_submit=now, t_arrival=now,
                                   tenant=tenant))
        reg.set("serve.queue_depth", len(self.queue))
        return rid

    # ------------------------------------------------------------- helpers
    def _alloc_dense(self, n: int):
        """All-or-nothing dense alloc, LRU-evicting prefix entries until it
        fits (one ``evict`` span per allocation that had to evict)."""
        ids = self.dense_pool.alloc(n)
        if ids is not None or self.prefix is None:
            return ids
        with obs.tracer().span("evict", track="sched") as span:
            leaves = 0
            while ids is None and self.prefix.evict_lru(self.dense_pool):
                leaves += 1
                ids = self.dense_pool.alloc(n)
            span["leaves"] = leaves
        return ids

    def _window_blocks_for(self, tokens: int) -> int:
        """Ring blocks needed once ``tokens`` tokens have been written:
        positions ``0..tokens-1`` land on ring slots ``0..min(tokens,W)-1``
        (monotone fill until the ring wraps), so coverage is a PREFIX of the
        block table — lazy allocation extends it, never punches holes."""
        W = self.wb * self.bs
        return -(-min(tokens, W) // self.bs)

    def _free_slot(self, b):
        """Release row ``b``'s blocks AND clear its device state.  The
        clear is not hygiene theater: ``decode_many`` keeps stepping every
        row, so a stale block table would scatter the dead row's KV into
        freed blocks the allocator may already have handed to a live
        request — silent cross-request corruption.  Restoring the empty
        template (-1 tables, zero lengths) makes the dead row's writes
        drop instead."""
        s = self._slots[b]
        self.dense_pool.decref(s["dense_ids"])
        if self.window_pool is not None:
            self.window_pool.decref(s["window_ids"])
        self._slots[b] = None
        self.caches = self.server.restore_row(
            self.caches, copy.deepcopy(self._empty_row), jnp.int32(b))

    @_sched_span("finish")
    def _finish(self, b):
        s = self._slots[b]
        r = s["req"]
        self.results[r.rid] = jnp.asarray(r.generated, jnp.int32)
        reg, tr = obs.registry(), obs.tracer()
        now = tr.now()
        tpot = None
        if s.get("t_first") is not None:
            tr.add("decode", s["t_first"], now, track=f"req{r.rid}",
                   tokens=len(r.generated))
            if len(r.generated) >= 2:
                # per-token decode latency over the post-first-token run
                tpot = (now - s["t_first"]) / (len(r.generated) - 1)
                reg.observe("serve.tpot_s", tpot)
                if r.tenant:
                    reg.observe("serve.tpot_s", tpot, tenant=r.tenant)
        tr.instant("finish", track=f"req{r.rid}", tokens=len(r.generated),
                   tenant=r.tenant)
        reg.inc("serve.finished")
        if r.tenant:
            reg.inc("serve.finished", tenant=r.tenant)
        reg.inc("serve.generated_tokens", len(r.generated))
        self._record(r, "finished", queue_delay=s.get("queue_delay", 0.0),
                     tpot=tpot)
        self._free_slot(b)
        reg.set("serve.in_flight", self._in_flight())

    def _preempt(self, b):
        """Preempt-to-recompute: release every block, requeue with
        prompt + generated as the new prompt."""
        s = self._slots[b]
        r = s["req"]
        if r.generated:
            r.prompt = jnp.concatenate(
                [r.prompt, jnp.asarray(r.generated, jnp.int32)])
        reg, tr = obs.registry(), obs.tracer()
        now = tr.now()
        phase_t0 = s["t_first"] if s.get("t_first") is not None \
            else s.get("t_admit", now)
        tr.add(s["phase"], phase_t0, now, track=f"req{r.rid}",
               preempted=True)
        tr.instant("preempt", track=f"req{r.rid}")
        self._free_slot(b)
        self.queue.insert(0, r)
        r.t_submit = now                 # requeue restarts the queue wait
        self.stats["preemptions"] += 1
        reg.inc("serve.preempted")
        if r.tenant:
            reg.inc("serve.preempted", tenant=r.tenant)
        reg.set("serve.in_flight", self._in_flight())

    def _pending_same_prefix(self, prompt_np, P) -> bool:
        """True when a live mid-prefill row will shortly trie-insert a
        shareable prefix of ``prompt_np`` (its forced boundary not yet
        reached) — admitting now would recompute those shared blocks."""
        n_share = ((P - 1) // self.bs) * self.bs
        for s in self._slots:
            if s is None or s["phase"] != "prefill":
                continue
            ins = s["insert_at"]
            if ins is None:
                continue
            if self.need_snapshot:
                # Hits land only on snapshot-carrying tips: useful iff our
                # prompt contains the row's FULL pending prefix.
                d = ins if ins <= n_share else 0
            else:
                # Snapshot-free (pure paged-dense): any block-aligned
                # common depth along the pending chain is a future hit.
                d = min(ins, n_share)
            if d >= self.bs and np.array_equal(s["prompt_np"][:d],
                                               prompt_np[:d]):
                return True
        return False

    # ------------------------------------------------------------ admission
    @_sched_span("admit")
    def _admit(self, b, r: _Request) -> Optional[bool]:
        """Admit ``r`` into row ``b``: allocate its blocks, restore its
        snapshot/tables, and park it in ``phase="prefill"`` — the prompt
        itself is streamed by ``_advance_prefills``.  Returns True, or None
        when the block pools cannot cover the prompt."""
        srv = self.server
        prompt_np = np.asarray(r.prompt)
        P = min(len(prompt_np), srv.max_len)
        prompt_np = prompt_np[-P:]
        remaining_cap = srv.max_len - P + 1
        r.max_new = min(r.max_new, len(r.generated) + remaining_cap)

        node, depth, chain_ids = None, 0, []
        if self.prefix is not None:
            node, depth = self.prefix.lookup(prompt_np, self.need_snapshot)
            if node is None and self._pending_same_prefix(prompt_np, P):
                # Cache-aware admission: a live mid-prefill row is about to
                # insert this very prefix (admission is no longer
                # synchronous with prefill, so the miss is transient).
                # Wait one round rather than recompute the shared blocks;
                # if that row is preempted instead, the next attempt
                # proceeds as a plain miss — no deadlock.
                return None
        n_prompt_blocks = -(-P // self.bs)
        n_new_blocks = n_prompt_blocks - depth // self.bs

        if node is not None:
            chain_ids = self.prefix.acquire(node, self.dense_pool)
        suffix_ids = self._alloc_dense(n_new_blocks)
        if suffix_ids is None:
            if chain_ids:
                self.dense_pool.decref(chain_ids)
            return None
        window_ids: List[int] = []
        if self.window_pool is not None:
            # Lazy ring: only the blocks the prompt's P tokens will write.
            window_ids = self.window_pool.alloc(self._window_blocks_for(P))
            if window_ids is None:
                self.dense_pool.decref(chain_ids + suffix_ids)
                return None
        dense_ids = chain_ids + suffix_ids

        insert_at = None
        if node is not None:
            if node.snapshot is not None:
                snap = copy.deepcopy(node.snapshot)
            else:
                # snapshot-free hit (pure paged-dense model, any depth):
                # the only per-row state is the table + length
                snap = copy.deepcopy(self._empty_row)
                for e in _paged_entries(snap):
                    if "k" not in e:
                        e["length"] = np.full_like(e["length"], depth)
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += depth
        else:
            snap = copy.deepcopy(self._empty_row)
            if self.prefix is not None and (P - 1) // self.bs > 0:
                # Miss: force a chunk boundary at the shareable depth so
                # the snapshot inserted there depends on the prefix tokens
                # alone (see module docstring).
                insert_at = ((P - 1) // self.bs) * self.bs
        _set_snapshot_tables(snap, _table_row(dense_ids, self.nb_max),
                             _table_row(window_ids, max(self.wb, 1)))
        self.caches = srv.restore_row(self.caches, snap, jnp.int32(b))

        reg, tr = obs.registry(), obs.tracer()
        now = tr.now()
        tr.add("queued", r.t_submit, now, track=f"req{r.rid}")
        reg.inc("serve.admitted")
        # Queue delay of THIS admission (t_submit restarts on requeue) —
        # the wait component §12 separates from service time.
        queue_delay = now - r.t_submit
        reg.observe("serve.queue_delay_s", queue_delay)
        if r.tenant:
            reg.observe("serve.queue_delay_s", queue_delay, tenant=r.tenant)
        if node is not None:
            reg.observe("serve.prefix_hit_frac", depth / max(P, 1),
                        bounds=obs.UNIT_BOUNDS)
        self._slots[b] = {"req": r, "dense_ids": dense_ids,
                          "window_ids": window_ids, "length": P,
                          "seq": self._admit_seq, "phase": "prefill",
                          "prompt_np": prompt_np, "done": depth,
                          "insert_at": insert_at, "paused_snap": None,
                          "t_admit": now, "t_first": None,
                          "queue_delay": queue_delay}
        self._admit_seq += 1
        self.stats["max_concurrent"] = max(
            self.stats["max_concurrent"],
            sum(s is not None for s in self._slots))
        reg.set("serve.in_flight", self._in_flight())
        reg.set_max("serve.max_concurrent", self._in_flight())
        return True

    # ------------------------------------------------------ chunked prefill
    def _advance_prefills(self, key, cur):
        """One packed prefill chunk: pack the next segments of up to
        ``max_segs`` pending rows (oldest first) into ``chunk_tokens``
        slots, dispatch ONE ``Server.prefill_packed`` program, then advance
        each row — snapshot-insert at a forced prefix boundary, or sample
        the first token and flip to decode when its prompt completes."""
        srv = self.server
        pending = sorted(
            (b for b in range(len(self._slots))
             if self._slots[b] is not None
             and self._slots[b]["phase"] == "prefill"),
            key=lambda x: self._slots[x]["seq"])
        C = self.chunk_tokens
        segs = []                            # (row, start, take)
        budget = C
        for b in pending:
            if budget == 0 or len(segs) == self.max_segs:
                break
            s = self._slots[b]
            take = min(len(s["prompt_np"]) - s["done"], budget)
            ins = s["insert_at"]
            if ins is not None and s["done"] < ins < s["done"] + take:
                take = ins - s["done"]       # stop AT the boundary
            segs.append((b, s["done"], take))
            budget -= take

        reg, tr = obs.registry(), obs.tracer()
        paused = [b for b, _, _ in segs
                  if self._slots[b]["paused_snap"] is not None]
        if paused:
            with tr.span("resume", track="sched", rows=len(paused)):
                for b in paused:
                    s = self._slots[b]
                    self.caches = srv.restore_row(
                        self.caches, s["paused_snap"], jnp.int32(b))
                    s["paused_snap"] = None
                    reg.inc("serve.resumes")

        N = self.max_segs
        buf = np.zeros((C,), np.int32)
        cu = np.zeros((N + 1,), np.int32)
        rows = np.full((N,), -1, np.int32)
        past = np.zeros((N,), np.int32)
        off = 0
        for i, (b, start, take) in enumerate(segs):
            buf[off:off + take] = self._slots[b]["prompt_np"][start:start +
                                                              take]
            rows[i] = b
            past[i] = start
            off += take
            cu[i + 1] = off
        cu[len(segs) + 1:] = off
        with tr.span("prefill_chunk", track="sched", segs=len(segs),
                     tokens=off, segs_rows_past_take=list(segs)):
            logits, self.caches = srv.prefill_packed(
                srv.params, jnp.asarray(buf)[None], self.caches,
                jnp.asarray(cu), jnp.asarray(rows), jnp.asarray(past))
        self.stats["prefilled_tokens"] += off
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_chunk_slots"] += C
        reg.observe("serve.chunk_packed_efficiency", off / C,
                    bounds=obs.UNIT_BOUNDS)

        for i, (b, start, take) in enumerate(segs):
            s = self._slots[b]
            s["done"] += take
            if s["insert_at"] is not None and s["done"] == s["insert_at"]:
                self._insert_prefix(b)
            if s["done"] != len(s["prompt_np"]):
                continue
            with tr.span("first_token", track="sched", row=b):
                s["phase"] = "decode"
                s["length"] = len(s["prompt_np"])
                key, sub = jax.random.split(key)
                tok0 = srv.sample(logits[i:i + 1], sub)
                r = s["req"]
                r.generated.append(int(tok0[0]))
                now = tr.now()
                # resumed=True marks a post-preemption re-prefill (the
                # request already produced its first token in an earlier
                # life) — records_from_spans must not read TTFT off it.
                tr.add("prefill", s["t_admit"], now, track=f"req{r.rid}",
                       prompt=len(s["prompt_np"]),
                       resumed=r.ttft is not None)
                s["t_first"] = now
                if r.ttft is None:
                    # Arrival-based TTFT (§12): first token minus submit
                    # time, queue wait included — under load the queue IS
                    # the latency.  Survives preemption via t_arrival.
                    self._record_ttft(r, now - r.t_arrival)
                    r.prefill_s = now - s["t_admit"]
                self._sample_router_health(b)
                cur = cur.at[b, 0].set(int(tok0[0]))
                if len(r.generated) >= r.max_new or int(tok0[0]) == self.eos:
                    self._finish(b)
        return key, cur

    def _sample_router_health(self, b) -> None:
        """Every ``router_health_every``-th completed prompt on a MoSA
        model: fetch the row snapshot its prefill just wrote and publish
        sel_entropy / drop_rate / head_util into the registry — the serve-
        side twin of the train loop's in-step router health (DESIGN §11)."""
        if not self._has_mosa or not self.router_health_every:
            return
        reg = obs.registry()
        if not reg.enabled:
            return
        self._health_seen += 1
        if (self._health_seen - 1) % self.router_health_every:
            return
        with obs.tracer().span("router_health", track="sched"):
            s = self._slots[b]
            snap = jax.device_get(
                self.server.snapshot_row(self.caches, jnp.int32(b)))
            stats = _router_health_from_snapshot(snap, len(s["prompt_np"]))
            for k, v in stats.items():
                reg.observe(f"serve.router.{k}", v, bounds=obs.UNIT_BOUNDS)

    @_sched_span("prefix_insert")
    def _insert_prefix(self, b):
        """Insert row ``b``'s shareable prefix into the trie.  Called when
        ``done`` hits the forced boundary: the row's device state is then
        exactly the one-shot prefill of ``prompt[:insert_at]`` (packed
        chunking is exact), i.e. a function of the prefix tokens alone."""
        srv = self.server
        s = self._slots[b]
        n_share = s["insert_at"]
        snap1 = jax.device_get(srv.snapshot_row(self.caches, jnp.int32(b)))
        chain, tip = self.prefix.insert(
            s["prompt_np"][:n_share], s["dense_ids"][:n_share // self.bs],
            self.dense_pool)
        _set_snapshot_tables(snap1, _table_row(chain, self.nb_max),
                             _table_row([], max(self.wb, 1)))
        self.prefix.attach_snapshot(tip, snap1)
        s["insert_at"] = None

    def _pause_prefills(self):
        """Park every mid-prefill row before a decode dispatch:
        ``decode_many`` steps ALL rows, so without this its writes would
        advance the row's lengths and corrupt its MoSA selection.  The host
        snapshot preserves the row; the empty template (-1 tables, zero
        lengths) makes the decode writes drop.  ``_advance_prefills``
        restores the snapshot before the row's next chunk."""
        srv = self.server
        rows = [b for b, s in enumerate(self._slots)
                if s is not None and s["phase"] == "prefill"
                and s["paused_snap"] is None]
        if not rows:
            return
        reg = obs.registry()
        with obs.tracer().span("pause_prefills", track="sched", rows=rows):
            for b in rows:
                self._slots[b]["paused_snap"] = jax.device_get(
                    srv.snapshot_row(self.caches, jnp.int32(b)))
                self.caches = srv.restore_row(
                    self.caches, copy.deepcopy(self._empty_row),
                    jnp.int32(b))
                reg.inc("serve.pauses")

    # ------------------------------------------------------------- growth
    def _alloc_or_preempt(self, alloc_fn, n: int, b: int, live):
        """``alloc_fn(n)``, preempting latest-admitted victims on failure.
        Latest-admitted only: preempting a row OLDER than ``b`` would break
        the monotone-progress guarantee (the oldest request must never lose
        its blocks to a newer one); when nothing newer than ``b`` exists,
        the caller preempts ``b`` itself."""
        ids = alloc_fn(n)
        while ids is None:
            s = self._slots[b]
            victims = [x for x in live
                       if self._slots[x] is not None and x != b
                       and self._slots[x]["seq"] > s["seq"]]
            if not victims:
                return None
            victim = max(victims, key=lambda x: self._slots[x]["seq"])
            self._preempt(victim)
            ids = alloc_fn(n)
        return ids

    def _grow_row(self, b: int, n: int, live) -> bool:
        """Cover the next ``n`` decode tokens of row ``b``: dense chain
        blocks plus the window ring blocks those tokens' ring slots need
        (lazy-ring invariant: allocation always precedes the write).
        Returns False iff ``b`` itself had to be preempted."""
        srv = self.server
        s = self._slots[b]
        needed = min(-(-(s["length"] + n) // self.bs), self.nb_max)
        extra = needed - len(s["dense_ids"])
        if extra > 0:
            ids = self._alloc_or_preempt(self._alloc_dense, extra, b, live)
            if ids is None:
                self._preempt(b)
                return False
            s["dense_ids"].extend(ids)
            self.caches = srv.grow_tables(
                self.caches,
                jnp.asarray(_table_row(s["dense_ids"], self.nb_max)),
                jnp.int32(b))
        if self.window_pool is not None:
            extra_w = self._window_blocks_for(s["length"] + n) \
                - len(s["window_ids"])
            if extra_w > 0:
                ids = self._alloc_or_preempt(self.window_pool.alloc,
                                             extra_w, b, live)
                if ids is None:
                    self._preempt(b)
                    return False
                s["window_ids"].extend(ids)
                self.caches = srv.grow_window_tables(
                    self.caches,
                    jnp.asarray(_table_row(s["window_ids"],
                                           max(self.wb, 1))),
                    jnp.int32(b))
        return True

    # ---------------------------------------------------------------- run
    def run(self, max_steps: int = 1000, source=None):
        """Serve every queued request; returns {rid: generated tokens}.
        Semantics mirror ``RequestPool.run`` (EOS, per-request ``max_new``,
        global ``max_steps`` decode budget).

        **Timed mode** (DESIGN §12): ``source`` is a duck-typed arrival
        stream (``repro.serve.loadgen`` builds them) that SUBMITS requests
        at their arrival times instead of the caller pre-queueing
        everything — the closed-loop/open-loop traffic the SLO bench
        drives.  Protocol: ``pump(sched, now)`` submits every request due
        by ``now`` (seconds since ``run()`` started), ``exhausted()`` says
        no more arrivals will ever come, ``next_arrival_in(now)`` is the
        wait until the next one (None for "when in-flight work completes").
        The loop runs until the source is exhausted AND the system drains;
        while idle between arrivals it sleeps (≤50 ms slices) rather than
        spinning."""
        srv = self.server
        B = srv.batch
        cur = jnp.zeros((B, 1), jnp.int32)
        key = jax.random.PRNGKey(0)
        steps = 0
        timer = obs.registry().timer("serve.run_s")
        timer.__enter__()
        t_run0 = obs.tracer().now()

        def by_phase(phase):
            return [b for b in range(B) if self._slots[b] is not None
                    and self._slots[b]["phase"] == phase]

        with srv.mesh, hints.sharding_hints(mesh=srv.mesh):
            while True:
                if source is not None:
                    source.pump(self, obs.tracer().now() - t_run0)
                if not self.queue and \
                        all(s is None for s in self._slots):
                    if source is None or source.exhausted():
                        break
                    wait = source.next_arrival_in(
                        obs.tracer().now() - t_run0)
                    if wait is not None and wait > 0:
                        with obs.tracer().span("wait_arrival",
                                               track="sched"):
                            time.sleep(min(wait, 0.05))
                    continue
                for b in range(B):
                    if self._slots[b] is None and self.queue \
                            and steps < max_steps:
                        if self._admit(b, self.queue[0]) is None:
                            break               # blocks exhausted: wait
                        self.queue.pop(0)
                        obs.registry().set("serve.queue_depth",
                                           len(self.queue))
                live_pre, live_dec = by_phase("prefill"), by_phase("decode")
                if not live_pre and not live_dec:
                    if steps >= max_steps:
                        break
                    if self.queue and not any(self._slots):
                        # nothing live and head-of-queue cannot be admitted
                        raise RuntimeError(
                            "request needs more blocks than the pool has: "
                            f"free={self.dense_pool.free_blocks} of "
                            f"{self.dense_pool.num_blocks}")
                    continue
                if steps >= max_steps:
                    # Decode budget spent: wind down, but rows caught
                    # mid-prefill still stream to completion so every
                    # admitted request yields its first token.
                    for b in live_dec:
                        self._finish(b)
                    if not live_pre:
                        break

                if live_pre:
                    # One packed prefill chunk, then (at most) one decode
                    # chunk — long prompts interleave with live decodes
                    # instead of stalling them.
                    key, cur = self._advance_prefills(key, cur)
                    live_dec = by_phase("decode")
                if not live_dec or steps >= max_steps:
                    continue

                need = max(self._slots[b]["req"].max_new -
                           len(self._slots[b]["req"].generated)
                           for b in live_dec)
                n = max(min(self.chunk, max_steps - steps, need), 1)

                # Grow dense chains and (lazily) window rings of the decode
                # rows to cover the next n appended tokens; preempt
                # latest-admitted rows (mid-prefill ones included — their
                # full prompt just requeues) when a pool runs dry.
                self._pause_prefills()
                reg, tr = obs.registry(), obs.tracer()
                with tr.span("grow_rows", track="sched"):
                    live = [b for b in range(B)
                            if self._slots[b] is not None]
                    for b in sorted(live_dec,
                                    key=lambda x: self._slots[x]["seq"]):
                        if self._slots[b] is None:
                            continue
                        self._grow_row(b, n, live)
                live_dec = by_phase("decode")
                if not live_dec:
                    continue

                key, sub = jax.random.split(key)
                with tr.span("decode_chunk", track="sched",
                             rows=len(live_dec), n=n,
                             lengths=[self._slots[b]["length"]
                                      for b in live_dec]):
                    toks, self.caches = srv.decode_many(srv.params, cur,
                                                        self.caches, sub, n)
                    steps += n
                    host = jax.device_get(toks)
                reg.inc("serve.decode_tokens", n * len(live_dec))
                cur = toks[:, -1:]
                with tr.span("emit", track="sched"):
                    for b in live_dec:
                        s = self._slots[b]
                        if s is None:
                            continue
                        r = s["req"]
                        for t in host[b]:
                            r.generated.append(int(t))
                            s["length"] += 1
                            if int(t) == self.eos or \
                                    len(r.generated) >= r.max_new:
                                self._finish(b)
                                break
        timer.__exit__(None, None, None)
        reg = obs.registry()
        if reg.enabled:
            # timer.dt is measured even with obs off (only the histogram
            # write is gated) — the registry.timer contract.
            reg.set("serve.tokens_per_s",
                    reg.counter("serve.generated_tokens").value /
                    max(timer.dt, 1e-9))
        obs.dump(self.metrics_path, self.trace_path, tag="scheduler")
        return dict(self.results)
