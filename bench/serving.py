"""The serving cells' run: set-up, the timed window through
``Scheduler.run(source=...)``, the traced part of the window, and the
comparison of what was served with the plain reference.

The program under test is ``repro.launch.serve.Server`` (paged caches) and
``repro.serve.Scheduler``, at the geometry the configuration file gives.
Only its public interface is used: ``submit`` through the source protocol,
``results``, ``records`` and ``ttft`` for what each request got and when,
the registry's counters, the tracer's spans, and ``Server.prefill_packed``
observed (not altered) to learn how each prompt was chunked.
"""

from __future__ import annotations

import gc
import math
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import harness, traffic
from bench import trace_reduce

# Requests compared with the reference per run, and chunks per prompt the
# reference program is built for.
SAMPLE = 16
MAX_CHUNKS = 4
# A served token "far below" the reference's best: more than this many
# reference logit standard deviations below it.
FAR_STD = 2.0
# Where the traced part starts (a share of the window) and its length in
# seconds.  Starting the profiler stalls the host for seconds, so the traced
# part sits late in the window and the queue wait is read over the requests
# admitted before it started.
TRACE_AT, TRACE_LEN = 0.7, 3.0


def model_config(spec: dict):
    from repro.configs.base import get_config
    return get_config(spec["arch"], **spec["get_config"])


def geometry(spec: dict):
    """The reference's shape numbers, from the configuration file."""
    from bench.reference import Geometry
    g = spec["shape"]
    return Geometry(n_layers=g["n_layers"], d_model=g["d_model"],
                    vocab=g["vocab"], d_head=g["d_head"],
                    n_dense=g["n_dense_heads"], n_mosa=g["n_mosa_heads"],
                    rotary_dense=g["rotary_dense"], sparsity=g["sparsity"],
                    min_k=g["min_k"], capacity=g["mosa_capacity"],
                    max_len=spec["serve"]["max_len"])


class PrefillLog:
    """Keeps the arguments of every ``prefill_packed`` dispatch (device
    arrays; read only after the window) to recover each prompt's chunks."""

    def __init__(self, server):
        from repro import obs
        self.calls = []
        self.times = []
        self._now = obs.tracer().now
        self._inner = server.prefill_packed
        server.prefill_packed = self

    def __call__(self, params, tokens, caches, cu, rows, past):
        self.times.append(self._now())
        self.calls.append((tokens, cu, rows, past))
        return self._inner(params, tokens, caches, cu, rows, past)

    def detach(self, server):
        server.prefill_packed = self._inner

    def dispatches(self):
        """[(tracer time, [(row, past, tokens)])] in dispatch order."""
        import jax
        host = jax.device_get(self.calls)
        out = []
        for t, (tokens, cu, rows, past) in zip(self.times, host):
            segs = []
            for i in range(len(rows)):
                if rows[i] < 0 or cu[i + 1] <= cu[i]:
                    continue
                segs.append((int(rows[i]), int(past[i]),
                             np.asarray(tokens[0, cu[i]:cu[i + 1]])))
            out.append((t, segs))
        return out


class Source:
    """The open- or closed-loop arrival stream, in the Scheduler's source
    protocol, which also opens and closes the traced part of the window.

    Open loop: request ``i`` is due at ``t_open + reqs[i].t``; the window
    opens when ``run()`` starts.  Closed loop: ``clients`` requests are kept
    outstanding; the window opens once the first ``clients`` have their
    first token (a full batch), and closes ``seconds`` later, after which
    nothing more is submitted and the run drains."""

    def __init__(self, reqs, seconds, closed_clients=0, trace_dir=None):
        self.reqs = reqs
        self.seconds = seconds
        self.clients = closed_clients
        self.trace_dir = trace_dir
        self.rid = {}             # request index -> rid
        self.t_submit = {}        # request index -> tracer time submitted
        self.t0 = None            # tracer time of run() start
        self.t_open = None        # tracer time the window opened
        self.trace_span = None    # tracer times the profiler ran
        self.profile_called = None  # tracer time the profiler was asked for
        self._i = 0
        self._tracing = None
        self.queue_at_close = None

    def _now(self):
        from repro import obs
        return obs.tracer().now()

    def _submit(self, sched, i):
        r = self.reqs[i]
        self.rid[i] = sched.submit(r.prompt, r.max_new)
        self.t_submit[i] = self._now()

    def _profile(self, t):
        if self.trace_dir is None or self.t_open is None:
            return
        rel = t - self.t_open
        if self._tracing is None and rel >= TRACE_AT * self.seconds:
            from repro import obs
            self.counters0 = counters()
            self.profile_called = t
            obs.start_profiler(str(self.trace_dir))
            self._tracing = (self._now(), None)
            self.profile_took = {"start": self._tracing[0] - t}
        elif (self._tracing is not None and self._tracing[1] is None
              and rel >= TRACE_AT * self.seconds + TRACE_LEN):
            self.stop_profile()

    def stop_profile(self):
        if self._tracing is not None and self._tracing[1] is None:
            from repro import obs
            t = self._now()
            obs.stop_profiler()
            self._tracing = (self._tracing[0], self._now())
            self.profile_took["stop"] = self._tracing[1] - t
            self.counters1 = counters()
            self.trace_span = self._tracing

    def pump(self, sched, now):
        t = self._now()
        if self.t0 is None:
            self.t0 = t - now
            if not self.clients:
                self.t_open = self.t0
        if self.clients:
            self._pump_closed(sched, t)
        else:
            while self._i < len(self.reqs) and \
                    self.reqs[self._i].t <= t - self.t_open:
                self._submit(sched, self._i)
                self._i += 1
        self._profile(t)
        if self.queue_at_close is None and self.t_open is not None \
                and t - self.t_open >= self.seconds:
            self.queue_at_close = len(sched.queue)

    def _pump_closed(self, sched, t):
        if self.t_open is None and len(sched.ttft) >= self.clients:
            self.t_open = t
        if self.t_open is not None and t - self.t_open >= self.seconds:
            self._i = len(self.reqs)          # window closed: drain
            return
        done = sum(1 for i in self.rid if self.rid[i] in sched.results)
        while self._i < len(self.reqs) and len(self.rid) - done < self.clients:
            self._submit(sched, self._i)
            self._i += 1

    def exhausted(self):
        return self._i >= len(self.reqs)

    def next_arrival_in(self, now):
        if self.exhausted():
            return None
        if self.clients:
            return 0.0
        return max(self.reqs[self._i].t - (now - (self.t_open - self.t0)),
                   0.0)


def counters() -> dict:
    from repro import obs
    reg = obs.registry()
    return {n: reg.counter(n).value
            for n in ("serve.decode_tokens", "server.decode_steps")}


def build(seed, spec):
    """Server + params from the seed; returns (server, cfg, params)."""
    import jax
    from repro.dist import sharding as shd
    from repro.launch.serve import Server
    from repro.nn.module import init_shapes
    from repro.nn.transformer import TransformerLM
    from repro.serve.paged_kv import PagedConfig
    from bench.weights import make_params

    cfg = model_config(spec)
    sv = spec["serve"]
    params = make_params(init_shapes(TransformerLM(cfg)), seed)
    mesh = shd.make_mesh((1, 1), ("data", "model"))
    server = Server(cfg, mesh=mesh, batch=sv["batch"], max_len=sv["max_len"],
                    params=params,
                    paged=PagedConfig(block_size=sv["block_size"],
                                      num_blocks=sv["num_blocks"]))
    jax.block_until_ready(params)
    return server, cfg, params


def scheduler(server, spec):
    from repro.serve import Scheduler
    sv = spec["serve"]
    return Scheduler(server, chunk=sv["decode_chunk"],
                     chunk_tokens=sv["chunk_tokens"],
                     max_prefill_segs=sv["max_prefill_segs"])


def warm_up(server, spec, mix, reqs, seed, vocab) -> dict:
    """Compile and run once every program the window uses; returns the
    seconds each part took.

    ``decode_many`` is one program per chunk length ``n`` in 1..chunk and
    per layout of its input token (the token a completed prompt's sample
    writes, or the last column of the previous chunk), so each of those is
    called once directly, on a scheduler's caches that are then dropped.
    Then a handful of requests at once take every scheduler path
    (multi-segment packed prefill, prefix insert, pause/resume, table
    growth, sampling, finish).  ``Scheduler._finish`` converts a request's
    token list on the device, one program per output length, so each
    output length of this run's requests is converted once here."""
    import jax
    import jax.numpy as jnp
    from repro.dist import hints
    sv = spec["serve"]
    chunk = sv["decode_chunk"]
    extra = chunk * 2 + 3
    took = {}
    t = time.perf_counter()
    with server.mesh:             # the scheduler converts under its mesh
        for n in sorted({r.max_new for r in reqs} | {extra}):
            jnp.asarray(list(range(n)), jnp.int32)
    took["convert"] = time.perf_counter() - t
    t = time.perf_counter()
    sched = scheduler(server, spec)
    key = jax.random.PRNGKey(0)
    with server.mesh, hints.sharding_hints(mesh=server.mesh):
        cur = jnp.zeros((server.batch, 1), jnp.int32).at[0, 0].set(1)
        for n in range(1, chunk + 1):
            toks, sched.caches = server.decode_many(server.params, cur,
                                                    sched.caches, key, n)
            toks, sched.caches = server.decode_many(
                server.params, toks[:, -1:], sched.caches, key, n)
        jax.block_until_ready(toks)
    del sched, toks
    took["decode_many"] = time.perf_counter() - t
    t = time.perf_counter()
    sched = scheduler(server, spec)
    lo = int(mix["prompt"]["min"])
    hi = min(int(mix["prompt"]["max"]), sv["chunk_tokens"] + 64)
    for k, n in enumerate([lo, hi, (lo + hi) // 2, lo + 17, hi - 5, lo + 1]):
        sched.submit(traffic.corpus_tokens(seed + 1, k, n, vocab), extra)
    sched.run(max_steps=10 ** 9)
    del sched
    took["requests"] = time.perf_counter() - t
    return took


def settle() -> None:
    """Collect the set-up's garbage and move what survives out of the
    collector's reach, so that a full collection inside the window scans
    only what the window allocates."""
    gc.collect()
    gc.freeze()


def map_requests(segs, reqs, rids):
    """Assign each prefill segment ``(row, past, tokens)`` to its request.
    A segment at ``past == 0`` starts a prompt on its row: it belongs to
    the earliest-submitted request not yet started whose prompt begins with
    its tokens (admission is first come, first served); later segments on
    the row continue that request.  A request whose segments do not tile
    its prompt exactly once (a preempted request prefills again) is left
    out of the comparison."""
    order = sorted(rids, key=lambda i: rids[i])
    key_len = 4
    waiting = {}
    for i in order:
        waiting.setdefault(tuple(reqs[i].prompt[:key_len]), []).append(i)
    started = set()
    row_req = {}
    chunks = {i: [] for i in rids}
    bad = set()
    for row, past, toks in segs:
        if past == 0:
            row_req.pop(row, None)
            if len(toks) >= key_len:
                cands = waiting.get(tuple(toks[:key_len]), [])
            else:
                cands = [i for i in order if i not in started]
            hit = next((i for i in cands if i not in started and
                        np.array_equal(reqs[i].prompt[:len(toks)], toks)),
                       None)
            if hit is None:
                again = next((i for i in started if np.array_equal(
                    reqs[i].prompt[:len(toks)], toks)), None)
                if again is not None:
                    bad.add(again)
                continue
            started.add(hit)
            row_req[row] = hit
        i = row_req.get(row)
        if i is None:
            continue
        p = reqs[i].prompt
        if not np.array_equal(p[past:past + len(toks)], toks):
            bad.add(i)
            continue
        chunks[i].append((past, len(toks)))
    for i, ch in chunks.items():
        pos = 0
        for past, n in ch:
            if past != pos:
                bad.add(i)
            pos = past + n
        if pos != len(reqs[i].prompt):
            bad.add(i)
    return chunks, bad


def compare(params, geo, reqs, chunks, served, sample, C, N,
            control=False):
    """Reference logits of every served token of ``sample`` (request
    indices).  Returns, per request, the gap of each served token: how far
    its reference logit lies below the reference's best, in reference
    logit standard deviations; with ``control`` also the control's gaps:
    those of the token the float8 reference puts first, at the same
    positions."""
    import jax
    import jax.numpy as jnp
    from bench.reference import serve_logits
    R = len(sample)
    ch = np.zeros((R, MAX_CHUNKS, C), np.int32)
    nch = np.zeros((R, MAX_CHUNKS), np.int32)
    sv = np.zeros((R, N), np.int32)
    nsv = np.zeros((R,), np.int32)
    for r, i in enumerate(sample):
        p = reqs[i].prompt
        for m, (past, n) in enumerate(chunks[i]):
            ch[r, m, :n] = p[past:past + n]
            nch[r, m] = n
        s = served[i]
        sv[r, :len(s)] = s
        nsv[r] = len(s)

    def one(params, chunks, n_chunks, served, n_served, fp8):
        return serve_logits(params, geo, chunks, n_chunks, served, n_served,
                            fp8)

    # weights are an argument, never a constant baked into the program
    f = jax.jit(jax.vmap(one, in_axes=(None, 0, 0, 0, 0, None)),
                static_argnums=(5,))

    args = (params, jnp.asarray(ch), jnp.asarray(nch), jnp.asarray(sv),
            jnp.asarray(nsv))

    def logits(fp8):
        t = time.perf_counter()
        out = np.asarray(f(*args, fp8))
        harness.say(f"bench: reference{' (control)' if fp8 else ''} "
                    f"took {time.perf_counter() - t:.2f} s")
        return out

    ref = logits(False)

    def gaps(pick):
        out = []
        for r in range(R):
            lg = ref[r, :nsv[r]]
            got = np.take_along_axis(lg, pick[r, :nsv[r], None], -1)[:, 0]
            out.append((lg.max(-1) - got) / lg.std(-1))
        return out

    if not control:
        return gaps(sv), None
    return gaps(sv), gaps(logits(True).argmax(-1))


def numbers(gaps) -> dict:
    """The compared numbers from per-token gaps (reference logit standard
    deviations below the reference's best): the widest gap, and the mean
    over tokens of how far a gap reaches past ``FAR_STD``."""
    g = np.concatenate(gaps)
    return {"served_gap_std": float(g.max()),
            "served_excess_far": float(np.maximum(g - FAR_STD, 0.0).mean())}


def check_served(params, spec, mix, reqs, rids, served, dispatches, seed,
                 control=False) -> dict:
    """Compare a sample of what was served with the reference.  Returns
    ``program`` (and with ``control`` also ``control``): the compared
    numbers; ``gaps`` / ``control_gaps``: per request, per served token;
    ``requests``, ``tokens``: the sample's size."""
    segs = [seg for _, ss in dispatches for seg in ss]
    chunks, bad = map_requests(segs, reqs, rids)
    ok = [i for i in served if i not in bad and 0 < len(chunks[i])
          <= MAX_CHUNKS and len(served[i]) >= 1]
    if bad:
        harness.say(f"bench: {len(bad)} requests whose chunks could not be "
                    f"matched are left out of the comparison")
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 11]))
    sample = pick_sample(rng, ok, reqs, served)
    if not sample:
        inf = {"served_gap_std": math.inf, "served_excess_far": math.inf}
        return {"program": inf, "control": inf if control else None,
                "gaps": [], "control_gaps": None, "requests": 0, "tokens": 0}
    prog, ctl = compare(params, geometry(spec), reqs, chunks, served, sample,
                        spec["serve"]["chunk_tokens"],
                        int(mix["output"]["max"]), control)
    return {"program": numbers(prog),
            "control": numbers(ctl) if control else None,
            "gaps": prog, "control_gaps": ctl, "requests": len(sample),
            "tokens": sum(len(served[i]) for i in sample)}


def pick_sample(rng, ok, reqs, served):
    """``SAMPLE`` finished requests drawn from the seed, with the longest
    (prompt + served tokens) among them."""
    ok = sorted(ok)
    if not ok:
        return []
    longest = max(ok, key=lambda i: (len(reqs[i].prompt) + len(served[i]),
                                     -i))
    rest = [i for i in ok if i != longest]
    take = list(rng.choice(len(rest), size=min(SAMPLE - 1, len(rest)),
                           replace=False)) if rest else []
    return [longest] + [rest[j] for j in take]


def serve_window(server, spec, mix, reqs, seconds, closed, trace_dir=None):
    """Serve ``reqs`` through a fresh Scheduler: the window and the drain.
    Returns (scheduler, source, prefill log, programs lowered inside)."""
    import jax
    sched = scheduler(server, spec)
    log = PrefillLog(server)
    src = Source(reqs, seconds, int(mix.get("clients", 0)) if closed else 0,
                 trace_dir)
    lowered = []

    def on_event(event, secs, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(kw.get("fun_name", event))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        sched.run(max_steps=10 ** 12, source=src)
    finally:
        src.stop_profile()
        jax.monitoring.unregister_event_duration_listener(on_event)
        log.detach(server)
    return sched, src, log, lowered


def summarize(sched, src, reqs, seconds, closed) -> dict:
    """End-to-end numbers of one window from the scheduler's records.

    Open loop: every request that arrived in the window; TTFT from its
    scheduled arrival to its first token, TPOT over its later tokens; a
    request with no finished record counts as infinitely late.  Closed
    loop: the requests that finished inside the window; output tokens are
    those delivered inside it, each request's tokens spread evenly from its
    first token to its last."""
    recs = sched.records
    t_open, t_close = src.t_open, src.t_open + seconds
    ttft, tpot, qwait, late, timeline = [], [], [], [], []
    admitted = []                 # tracer time each request was admitted
    attempted = failed = tokens = 0
    for i, rid in src.rid.items():
        rec = recs.get(rid)
        ok = rec is not None and rec["outcome"] == "finished" \
            and rec["ttft_s"] is not None
        if ok:
            timeline.append((len(reqs[i].prompt),
                             rec["t_arrival"] + rec["ttft_s"],
                             rec["tpot_s"] or 0.0, rec["new_tokens"]))
        if closed:
            if not ok:
                if src.t_submit[i] <= t_close:
                    attempted += 1
                    failed += 1
                    tpot.append(math.inf)
                continue
            t_first = rec["t_arrival"] + rec["ttft_s"]
            times = t_first + (rec["tpot_s"] or 0.0) * np.arange(
                rec["new_tokens"])
            tokens += int(((times >= t_open) & (times <= t_close)).sum())
            if t_open <= times[-1] <= t_close:
                attempted += 1
                if rec["tpot_s"] is not None:
                    tpot.append(rec["tpot_s"])
            continue
        attempted += 1
        due = t_open + reqs[i].t
        if not ok:
            failed += 1
            ttft.append(math.inf)
            tpot.append(math.inf)
            qwait.append(math.inf)
            admitted.append(-math.inf)
            continue
        ttft.append(rec["t_arrival"] + rec["ttft_s"] - due)
        if rec["tpot_s"] is not None:
            tpot.append(rec["tpot_s"])
        late.append(rec["t_arrival"] - due)
        qwait.append(rec["queue_delay_s"] + rec["t_arrival"] - due)
        admitted.append(rec["t_arrival"] + rec["queue_delay_s"])
    out = {"attempted": attempted, "failed": failed, "qwait": qwait,
           "admitted": admitted,
           "late": late, "timeline": timeline,
           "tpot_p95_ms": 1e3 * traffic.percentile(tpot, 95),
           "finished_in_window": sum(
               1 for _, tf, dt, n in timeline
               if t_open <= tf + dt * (n - 1) <= t_close),
           "queue_at_close": src.queue_at_close}
    if closed:
        out["output_tokens_per_s"] = tokens / seconds
    else:
        out["ttft_p95_ms"] = 1e3 * traffic.percentile(ttft, 95)
    return out


def run(ctx, closed: bool):
    from repro import obs

    spec = ctx.cell.config
    mix = ctx.cell.traffic
    t = time.perf_counter()
    took = {"start": t - ctx.data["t_start"]}
    server, cfg, params = build(ctx.seed, spec)
    took["build"] = time.perf_counter() - t
    reqs = traffic.requests(mix, ctx.seed, ctx.seconds, cfg.vocab)
    took.update(warm_up(server, spec, mix, reqs, ctx.seed, cfg.vocab))
    trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) \
        if ctx.trace else None
    settle()
    ctx.data["setup_s"] = time.perf_counter() - ctx.data["t_start"]
    harness.say("bench: set-up " + ", ".join(
        f"{k} {v:.2f} s" for k, v in took.items()))
    sched, src, log, lowered = serve_window(server, spec, mix, reqs,
                                            ctx.seconds, closed, trace_dir)
    t_end = obs.tracer().now()
    if closed:
        # setup_s runs to the window's opening: filling the batch is set-up
        # the traffic needs.
        ctx.data["setup_s"] += src.t_open - src.t0
    harness.say(f"bench: programs lowered inside the window: "
                f"{len(lowered)} {sorted(set(lowered))}")
    ctx.data["memory_peak_bytes"] = harness.memory_peak_bytes(1)

    sm = summarize(sched, src, reqs, ctx.seconds, closed)
    metrics = {"setup_s": ctx.data["setup_s"],
               "tpot_p95_ms": sm["tpot_p95_ms"]}
    if closed:
        metrics["output_tokens_per_s"] = sm["output_tokens_per_s"]
    else:
        metrics["ttft_p95_ms"] = sm["ttft_p95_ms"]
        late = sm["late"]
        harness.say(f"bench: generator lateness p50 "
                    f"{1e3 * traffic.percentile(late, 50):.3f} ms, max "
                    f"{1e3 * max(late, default=0.0):.3f} ms")
    harness.say(f"bench: {sm['attempted']} requests attempted, "
                f"{sm['failed']} failed, preemptions "
                f"{sched.stats['preemptions']}, prefix hits "
                f"{sched.stats['prefix_hits']}; drained "
                f"{t_end - src.t_open:.2f} s after the window opened")
    served = {i: np.asarray(sched.results[rid])
              for i, rid in src.rid.items() if rid in sched.results}
    qwait = sm["qwait"]
    if ctx.trace and src.profile_called is not None:
        qwait = [q for q, t in zip(qwait, sm["admitted"])
                 if t < src.profile_called]
    ctx.data.update(qwait=qwait, timeline=sm["timeline"],
                    geometry=spec["shape"], serve=spec["serve"])

    breakdown = None
    device_extra = {}
    if ctx.trace:
        t0, t1 = src.trace_span
        ctx.data["trace_span"] = (t0, t1)
        ctx.data["spans"] = [
            s for s in obs.tracer().spans()
            if s.name in ("prefill_chunk", "decode_chunk")
            and t0 <= s.t0 <= t1]
        ctx.data["counters"] = (src.counters0, src.counters1)
        t = time.perf_counter()
        red = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        src.profile_took["reduce"] = time.perf_counter() - t
        harness.say("bench: profiler " + ", ".join(
            f"{k} {v:.2f} s" for k, v in src.profile_took.items()))
        ctx.data["trace"] = red
        device_extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        breakdown = red["breakdown"]

    # ---- the comparison with the plain reference, once the program's
    # state is freed
    calls = log.dispatches()
    ctx.data["prefill_calls"] = [(t, [(past, len(tk)) for _, past, tk in ss])
                                 for t, ss in calls]
    rids = dict(src.rid)
    del sched, log, src, server
    gc.collect()
    t_ref = time.perf_counter()
    res = check_served(params, spec, mix, reqs, rids, served, calls,
                       ctx.seed)
    harness.say(f"bench: reference over {res['requests']} requests, "
                f"{res['tokens']} served tokens, took "
                f"{time.perf_counter() - t_ref:.1f} s; {res['program']}")
    if res["gaps"]:
        g = np.concatenate(res["gaps"])
        harness.say("bench: served tokens more than c std below the "
                    "reference's best, share and mean excess: " + ", ".join(
                        f"c={c} {float((g > c).mean()):.5f} "
                        f"{float(np.maximum(g - c, 0).mean()):.5f}"
                        for c in (1.5, 2.0, 2.5, 3.0))
                    + f"; mean gap {float(g.mean()):.5f}")
    checks = {k: (res["program"][k], lim)
              for k, lim in spec["limits"].items()}
    return harness.Outcome(metrics=metrics, attempted=sm["attempted"],
                           failed=sm["failed"], checks=checks,
                           device_extra=device_extra, breakdown=breakdown)


def decode_census(ctx):
    """The decode dispatches of the traced part of the window, each as
    ``(n steps, [position of each live row at the first step])``.  Live
    rows and their positions are estimated from the requests' records: a
    request decodes from its first token to its last, one position per
    ``tpot``."""
    out = []
    tl = ctx.data.get("timeline", [])
    for sp in ctx.data.get("spans", []):
        if sp.name != "decode_chunk":
            continue
        n = int(sp.args.get("n", 1))
        lengths = []
        for P, t_first, tpot, ntok in tl:
            t_last = t_first + tpot * (ntok - 1)
            if ntok >= 2 and t_first <= sp.t0 < t_last:
                lengths.append(int(P + (sp.t0 - t_first) / max(tpot, 1e-9)))
        out.append((n, lengths))
    return out


def prefill_in_trace(ctx):
    """Segments ``(past, take)`` of each prefill dispatch in the traced part
    of the window."""
    span = ctx.data.get("trace_span")
    if not span:
        return []
    return [segs for t, segs in ctx.data.get("prefill_calls", [])
            if span[0] <= t <= span[1]]
