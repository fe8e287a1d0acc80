"""Observability suite (ISSUE 8, DESIGN §11): registry/histogram units,
exporter formats, tracer + Chrome-trace validity, the scheduler
counter-consistency property (admitted == finished + preempted after a
drain), device-metrics parity under jit + donated buffers, the obs-off
zero-write guarantee, and the Scheduler/Trainer artifact dump paths."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import BlockSpec, get_config
from repro.launch.serve import Scheduler, Server
from repro.obs.export import prometheus_text
from repro.obs.metrics import (DEFAULT_BOUNDS, UNIT_BOUNDS, Histogram,
                               Registry, publish)
from repro.obs.slo import records_from_spans
from repro.obs.tracing import Tracer
from repro.serve.paged_kv import PagedConfig
from tests._property_harness import given, settings, st


@pytest.fixture(autouse=True)
def fresh_obs():
    """Every test starts (and leaves) the process-global registry/tracer
    enabled and empty — the obs state is deliberately global, so tests
    must not leak series into each other."""
    obs.set_enabled(True)
    obs.registry().reset()
    obs.tracer().reset()
    yield
    obs.set_enabled(True)
    obs.registry().reset()
    obs.tracer().reset()


# ---------------------------------------------------------------- registry
def test_counter_gauge_semantics():
    reg = Registry()
    reg.inc("c")
    reg.inc("c", 2.5)
    reg.set("g", 7.0)
    reg.set("g", 3.0)                    # last value wins
    reg.set_max("hw", 3.0)
    reg.set_max("hw", 1.0)               # high-water keeps the max
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.5
    assert snap["gauges"]["g"] == 3.0
    assert snap["gauges"]["hw"] == 3.0


def test_registry_type_collision_asserts():
    reg = Registry()
    reg.inc("x")
    with pytest.raises(AssertionError):
        reg.observe("x", 1.0)


def test_registry_disabled_zero_writes():
    """The ISSUE 8 guarantee: a disabled registry records NOTHING — the
    convenience calls fast-exit and the factories hand back a shared no-op
    never stored in the map."""
    reg = Registry(enabled=False)
    reg.inc("a")
    reg.set("b", 1.0)
    reg.observe("c", 0.5)
    h = reg.histogram("d")
    h.observe(1.0)
    assert h.summary() == {}
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "gauges_meta": {}, "histograms": {}}
    assert reg._metrics == {}
    assert publish({"x": 1.0}, "p.", reg=reg) == {}
    # labeled calls are just as write-free
    reg.inc("a", tenant="x")
    reg.observe("c", 0.5, tenant="x")
    with reg.timer("t", tenant="x") as t:
        pass
    assert t.dt >= 0.0                      # the clock still ran
    assert reg._metrics == {}


# --------------------------------------------------------------- histogram
def test_histogram_single_observation_is_exact():
    h = Histogram("t", bounds=UNIT_BOUNDS)
    h.observe(0.37)
    for q in (0.0, 0.5, 0.9, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(0.37)
    s = h.summary()
    assert s["count"] == 1 and s["min"] == s["max"] == 0.37


def test_histogram_uniform_quantiles():
    """Unit-width buckets, one sample per bucket: interpolated quantiles
    are exact at every bucket edge."""
    h = Histogram("t", bounds=tuple(float(i) for i in range(101)))
    for v in range(1, 101):
        h.observe(float(v))
    assert h.quantile(0.50) == pytest.approx(50.0)
    assert h.quantile(0.90) == pytest.approx(90.0)
    assert h.quantile(0.99) == pytest.approx(99.0)
    p = h.percentiles()
    assert set(p) == {"p50", "p90", "p99"}


def test_histogram_interpolates_within_bucket():
    """100 samples of 0.42 land in one UNIT bucket (0.40, 0.45]; min/max
    clamping must report 0.42 for every quantile, not the bucket edges."""
    h = Histogram("t", bounds=UNIT_BOUNDS)
    for _ in range(100):
        h.observe(0.42)
    assert h.quantile(0.5) == pytest.approx(0.42)
    assert h.quantile(0.99) == pytest.approx(0.42)


def test_histogram_overflow_and_bounds():
    h = Histogram("t", bounds=(1.0, 2.0))
    for v in (0.5, 1.5, 100.0):
        h.observe(v)
    assert h.counts == [1, 1, 1]          # under, mid, overflow
    assert h.quantile(1.0) == pytest.approx(100.0)
    assert DEFAULT_BOUNDS[0] == pytest.approx(1e-6)
    assert DEFAULT_BOUNDS[-1] >= 1e3 * 0.99
    assert all(a < b for a, b in zip(DEFAULT_BOUNDS, DEFAULT_BOUNDS[1:]))
    assert UNIT_BOUNDS[0] == 0.0 and UNIT_BOUNDS[-1] == 1.0
    with pytest.raises(AssertionError):
        Histogram("bad", bounds=(1.0, 1.0))


def test_publish_kinds():
    reg = Registry()
    publish({"a": 1.0}, "g.", reg=reg)
    publish({"a": 0.5}, "h.", reg=reg, kind="histogram")
    snap = reg.snapshot()
    assert snap["gauges"]["g.a"] == 1.0
    assert snap["histograms"]["h.a"]["count"] == 1


# --------------------------------------------------------------- exporters
def test_prometheus_text_format():
    reg = Registry()
    reg.inc("serve.admitted", 3)
    reg.set("pool.dense.free_blocks", 7)
    reg.observe("serve.ttft-s", 1.5, bounds=(1.0, 2.0))
    txt = prometheus_text(reg)
    assert "# TYPE serve_admitted counter\nserve_admitted 3" in txt
    assert "# TYPE pool_dense_free_blocks gauge" in txt
    # cumulative buckets + +Inf == count
    assert 'serve_ttft_s_bucket{le="1"} 0' in txt
    assert 'serve_ttft_s_bucket{le="2"} 1' in txt
    assert 'serve_ttft_s_bucket{le="+Inf"} 1' in txt
    assert "serve_ttft_s_count 1" in txt


def test_dump_json_and_prom(tmp_path):
    reg = obs.registry()
    reg.inc("a")
    reg.observe("b", 0.5)
    mpath, ppath = tmp_path / "m.json", tmp_path / "m.prom"
    obs.dump(metrics_path=str(mpath), prom_path=str(ppath))
    snap = json.loads(mpath.read_text())
    assert snap["counters"]["a"] == 1.0
    assert "# TYPE b histogram" in ppath.read_text()
    # .jsonl suffix appends lines instead of overwriting
    jl = tmp_path / "m.jsonl"
    obs.dump(metrics_path=str(jl), tag="t1")
    obs.dump(metrics_path=str(jl), tag="t2")
    lines = [json.loads(x) for x in jl.read_text().splitlines()]
    assert [x["tag"] for x in lines] == ["t1", "t2"]
    assert all("time" in x for x in lines)


# ------------------------------------------------------------------ tracer
def test_tracer_chrome_trace_valid(tmp_path):
    tr = Tracer()
    with tr.span("outer", track="a", n=1):
        pass
    t0 = tr.now()
    tr.add("phase", t0, t0 + 0.5, track="b")
    tr.instant("marker", track="a")
    doc = tr.chrome_trace()
    evs = doc["traceEvents"]
    meta = {e["args"]["name"]: e["tid"] for e in evs if e["ph"] == "M"}
    assert set(meta) == {"a", "b"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"outer", "phase", "marker"}
    phase = next(e for e in xs if e["name"] == "phase")
    assert phase["tid"] == meta["b"]
    assert phase["dur"] == pytest.approx(5e5, rel=1e-3)   # 0.5 s in µs
    path = tmp_path / "t.json"
    tr.export_chrome(str(path))
    assert json.loads(path.read_text())["traceEvents"]
    jl = tmp_path / "t.jsonl"
    tr.export_jsonl(str(jl))
    assert len(jl.read_text().splitlines()) == 3


def test_tracer_disabled_and_ring():
    tr = Tracer(capacity=4, enabled=False)
    with tr.span("x"):
        pass
    tr.add("y", 0.0, 1.0)
    tr.instant("z")
    assert len(tr) == 0
    tr.enabled = True
    for i in range(10):
        tr.instant(f"s{i}")
    assert len(tr) == 4                       # ring keeps the newest
    assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]


def test_set_enabled_toggles_both():
    obs.set_enabled(False)
    assert not obs.registry().enabled and not obs.tracer().enabled
    assert not obs.enabled()
    obs.set_enabled(True)
    assert obs.enabled()


def test_profiler_failure_raises():
    """A profiler bracket that cannot trace raises instead of leaving an
    empty trace window behind, and span annotation ends with it."""
    from repro.obs.tracing import TRACER, stop_profiler
    TRACER.annotate = True
    with pytest.raises(RuntimeError):
        stop_profiler()                   # no trace was started
    assert not TRACER.annotate


# ------------------------------------------------- scheduler integration
def _hybrid_cfg():
    """3-layer dense + window + MoSA stack (the paged-serving acceptance
    config) — exercises pool gauges, prefix counters, AND serve-time
    router health in one scheduler run."""
    cfg = get_config("mosa-paper", preset="smoke", variant="mosa",
                     sparsity=4)
    return dataclasses.replace(
        cfg, n_layers=3,
        attention=dataclasses.replace(cfg.attention, window=16),
        pattern=(BlockSpec("attn", "dense"), BlockSpec("attn_local", "dense"),
                 BlockSpec("mosa", "dense")))


def _dense_window_cfg():
    cfg = get_config("mosa-paper", preset="smoke", variant="dense")
    return dataclasses.replace(
        cfg, n_layers=2,
        attention=dataclasses.replace(cfg.attention, window=16),
        pattern=(BlockSpec("attn", "dense"),
                 BlockSpec("attn_local", "dense")))


_SMALL_SERVER = None


def small_server():
    """One dense+window server shared by the drain tests (cached — compile
    once); the paged pool is small enough that long request mixes preempt.
    A plain helper, not a fixture: the vendored property harness binds
    ``given`` strategies by parameter position, so property tests cannot
    take fixture arguments."""
    global _SMALL_SERVER
    if _SMALL_SERVER is None:
        cfg = _dense_window_cfg()
        _SMALL_SERVER = Server(cfg, batch=2, max_len=64,
                               paged=PagedConfig(block_size=8,
                                                 num_blocks=14,
                                                 num_window_blocks=4))
    return _SMALL_SERVER


def _run_mix(server, lens, max_new, prefix_cache=False, **kw):
    sched = Scheduler(server, chunk=4, prefix_cache=prefix_cache, **kw)
    rids = [sched.submit(
        jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(7), i),
                           (n,), 2, 64), max_new=max_new)
        for i, n in enumerate(lens)]
    out = sched.run()
    assert all(len(out[r]) == max_new for r in rids)
    return sched, rids


@settings(max_examples=5, deadline=None)
@given(lens=st.lists(st.integers(1, 24), min_size=1, max_size=5),
       max_new=st.integers(1, 6))
def test_scheduler_counter_consistency_property(lens, max_new):
    """Drain invariant (ISSUE 8): after every request completes,
    admitted == finished + preempted (each preemption costs one re-admit),
    submitted == finished, and the in-flight gauge reads zero — across
    random length mixes including pool-exhausting ones."""
    server = small_server()
    reg = obs.registry()
    reg.reset()
    sched, _ = _run_mix(server, lens, max_new)
    snap = reg.snapshot()
    c = snap["counters"]
    assert c["serve.admitted"] == \
        c["serve.finished"] + c.get("serve.preempted", 0)
    assert c["serve.submitted"] == len(lens)
    assert c["serve.finished"] == len(lens)
    assert snap["gauges"]["serve.in_flight"] == 0
    assert snap["gauges"]["serve.queue_depth"] == 0
    assert c["serve.generated_tokens"] == len(lens) * max_new
    assert sched.stats["preemptions"] == c.get("serve.preempted", 0)


def test_scheduler_obs_off_noop():
    """obs disabled: the scheduler still serves correctly (including the
    bounded ttft compat property) and the registry/tracer record nothing."""
    server = small_server()
    obs.set_enabled(False)
    sched, rids = _run_mix(server, [5, 9], 3)
    assert obs.registry().snapshot() == \
        {"counters": {}, "gauges": {}, "gauges_meta": {}, "histograms": {}}
    assert len(obs.tracer()) == 0
    assert all(r in sched.ttft for r in rids)     # ttft survives obs-off


def test_scheduler_artifacts_and_lifecycle(tmp_path):
    """End-to-end artifact dump on the MoSA hybrid: the Chrome trace holds
    queued -> prefill -> decode for every request, the metrics snapshot
    carries TTFT/TPOT histograms, pool gauges, prefix counters, and the
    serve-time router-health series (same registry as training)."""
    cfg = _hybrid_cfg()
    server = Server(cfg, batch=2, max_len=64,
                    paged=PagedConfig(block_size=8, num_blocks=24,
                                      num_window_blocks=4))
    mpath = tmp_path / "metrics.jsonl"
    tpath = tmp_path / "trace.json"
    sched = Scheduler(server, chunk=4, prefix_cache=True,
                      metrics_path=str(mpath), trace_path=str(tpath),
                      router_health_every=1)
    rids = [sched.submit(
        jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(8), i),
                           (n,), 2, cfg.vocab), max_new=4)
        for i, n in enumerate((5, 11, 7))]
    out = sched.run()
    assert all(len(out[r]) == 4 for r in rids)

    doc = json.loads(tpath.read_text())
    tid_name = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
                if e.get("ph") == "M"}
    by_track = {}
    for e in doc["traceEvents"]:
        if e.get("ph") == "X":
            by_track.setdefault(tid_name[e["tid"]], set()).add(e["name"])
    for r in rids:
        assert {"queued", "prefill", "decode"} <= by_track[f"req{r}"], \
            f"req{r}: {by_track.get(f'req{r}')}"
    assert "prefill_chunk" in by_track["sched"]
    assert "decode_chunk" in by_track["sched"]

    snap = json.loads(mpath.read_text().splitlines()[-1])
    assert snap["tag"] == "scheduler"
    h = snap["histograms"]
    assert h["serve.ttft_s"]["count"] == len(rids)
    assert h["serve.tpot_s"]["count"] == len(rids)
    assert 0 < h["serve.chunk_packed_efficiency"]["max"] <= 1.0
    assert "serve.router.sel_entropy" in h        # MoSA health, serve side
    assert 0.0 <= h["serve.router.drop_rate"]["max"] <= 1.0
    g = snap["gauges"]
    # drained up to the prefix trie's retained blocks (one per node)
    assert g["pool.dense.live_blocks"] == g.get("prefix.nodes", 0)
    assert g["pool.dense.live_high_water"] > 0
    assert any(k.startswith("prefix.") for k in snap["counters"])
    assert g["serve.tokens_per_s"] > 0


SCHED_SPANS = {"admit", "evict", "resume", "first_token", "prefix_insert",
               "router_health", "pause_prefills", "grow_rows", "emit",
               "finish", "wait_arrival", "prefill_chunk", "decode_chunk"}


class _Later:
    """A timed source: three requests at once, the rest once the
    scheduler has drained them and slept for ``pause`` seconds."""

    def __init__(self, prompts, max_new, pause=0.05):
        self.first, self.rest = prompts[:3], prompts[3:]
        self.max_new, self.pause, self.due = max_new, pause, None

    def pump(self, sched, now):
        for p in self.first:
            sched.submit(p, self.max_new)
        self.first = []
        if self.due is not None and now >= self.due:
            for p in self.rest:
                sched.submit(p, self.max_new)
            self.rest = []

    def exhausted(self):
        return not self.first and not self.rest

    def next_arrival_in(self, now):
        if self.due is None:
            self.due = now + self.pause
        return max(self.due - now, 0.0)


def _span_run(enabled):
    """The hybrid on a pool that the prefix trie fills, prompts longer
    than a prefill chunk beside decoding rows, and a pause between
    arrivals: every scheduler span has a reason to open.  Records the
    scheduler's own decode lengths and packed segments at dispatch."""
    cfg = _hybrid_cfg()
    server = Server(cfg, batch=2, max_len=64,
                    paged=PagedConfig(block_size=8, num_blocks=12,
                                      num_window_blocks=4))
    sched = Scheduler(server, chunk=4, chunk_tokens=16, prefix_cache=True,
                      router_health_every=1)
    decode_lengths, prefill_segs = [], []
    inner_decode, inner_prefill = server.decode_many, server.prefill_packed

    def decode_many(params, tok, caches, key, n):
        decode_lengths.append([s["length"] for s in sched._slots
                               if s is not None and s["phase"] == "decode"])
        return inner_decode(params, tok, caches, key, n)

    def prefill_packed(params, tokens, caches, cu, rows, past):
        cu, rows, past = (np.asarray(a) for a in (cu, rows, past))
        prefill_segs.append([(int(rows[i]), int(past[i]),
                              int(cu[i + 1] - cu[i]))
                             for i in range(len(rows))
                             if rows[i] >= 0 and cu[i + 1] > cu[i]])
        return inner_prefill(params, tokens, caches, cu, rows, past)

    server.decode_many, server.prefill_packed = decode_many, prefill_packed
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, cfg.vocab, size=(n,)).astype(np.int32)
               for n in (5, 40, 33, 21, 38, 27)]
    obs.set_enabled(enabled)
    sched.run(source=_Later(prompts, max_new=6))
    return sched, decode_lengths, prefill_segs


@pytest.mark.parametrize("enabled", [True, False])
def test_scheduler_spans_args_and_prefill_s(enabled):
    """Every scheduler span lands on the ``sched`` track under its name;
    ``decode_chunk``'s ``lengths`` and ``prefill_chunk``'s
    ``segs_rows_past_take`` equal the scheduler's own state at dispatch;
    every finished record carries ``prefill_s`` (admission to first
    token, inside TTFT).  With obs off the ring and the registry stay
    empty and the records are still written."""
    sched, decode_lengths, prefill_segs = _span_run(enabled)
    recs = list(sched.records.values())
    assert len(recs) == 6
    for r in recs:
        assert r["outcome"] == "finished"
        assert 0 < r["prefill_s"] <= r["ttft_s"] - r["queue_delay_s"] + 1e-9
    if not enabled:
        assert len(obs.tracer()) == 0
        assert obs.registry().snapshot() == \
            {"counters": {}, "gauges": {}, "gauges_meta": {},
             "histograms": {}}
        return
    spans = [s for s in obs.tracer().spans() if s.track == "sched"]
    assert {s.name for s in spans} == SCHED_SPANS
    dec = [s for s in spans if s.name == "decode_chunk"]
    assert [s.args["lengths"] for s in dec] == decode_lengths
    assert all(s.args["rows"] == len(s.args["lengths"]) for s in dec)
    pre = [s for s in spans if s.name == "prefill_chunk"]
    assert [[tuple(x) for x in s.args["segs_rows_past_take"]]
            for s in pre] == prefill_segs
    assert all(s.args["leaves"] > 0 for s in spans if s.name == "evict")
    assert all(s.args["rows"] for s in spans if s.name == "pause_prefills")
    by_rid = {r["rid"]: r for r in records_from_spans(obs.tracer().spans())}
    for rid, r in sched.records.items():
        assert by_rid[rid]["prefill_s"] == r["prefill_s"]


def _decode_many_hlo(server, caches):
    tok = jnp.zeros((server.batch, 1), jnp.int32)
    return server._decode_many.lower(
        server.params, tok, caches, jax.random.PRNGKey(0), 4,
        jnp.float32(0.0), 0, False).compile().as_text()


def test_mosa_scopes_reach_compiled_decode_op_names(monkeypatch):
    """The MoSA stored-set upkeep (the selects of its one-slot-shift
    insertion) carries ``mosa.upkeep`` in its ``op_name`` inside the
    compiled ``decode_many`` (a ``lax.scan`` body) and holds no sort or
    gather, routing carries ``mosa.route``; and the scopes change nothing
    but metadata: with ``jax.named_scope`` made inert the compiled program
    is the same text once metadata is stripped."""
    import contextlib
    import re
    cfg = dataclasses.replace(
        get_config("mosa-paper", preset="smoke", variant="mosa", sparsity=4),
        n_layers=2, pattern=(BlockSpec("attn", "dense"),
                             BlockSpec("mosa", "dense")))
    server = Server(cfg, batch=2, max_len=64,
                    paged=PagedConfig(block_size=8, num_blocks=16))
    caches = Scheduler(server).caches
    text = _decode_many_hlo(server, caches)

    def op_names(kind):
        return [m.group(1) for m in re.finditer(
            rf' {kind}\(.*op_name="([^"]*)"', text)]

    sorts, gathers = op_names("sort"), op_names("gather")
    assert not [n for n in sorts + gathers if "/mosa.upkeep/" in n]
    selects = [n for n in op_names("select") if "/mosa.upkeep/" in n]
    assert len(selects) >= 4, selects
    assert all("/while/body/" in n for n in selects)
    assert re.search(r'op_name="[^"]*/mosa\.route/', text)
    assert re.search(r'op_name="[^"]*/mosa\.attend/', text)

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()                    # trace again, without scopes
    plain = _decode_many_hlo(server, caches)
    jax.clear_caches()

    def canon(t):
        # the computations alone (not the source-location tables), less
        # each instruction's metadata, with instructions renamed in order
        # of appearance (the numbering of names may shift)
        t = re.sub(r", metadata=\{[^}]*\}", "", t[t.index("\n%"):])
        names = {}
        return re.sub(r"%[\w.\-]+", lambda m: names.setdefault(
            m.group(0), f"%v{len(names)}"), t)
    assert "mosa.upkeep" not in plain
    assert canon(plain) == canon(text)


# ------------------------------------------- device-metrics / train side
def _tiny_mosa_cfg():
    cfg = get_config("mosa-paper", preset="smoke", variant="mosa",
                     sparsity=4)
    return dataclasses.replace(cfg, n_layers=2, pattern=(
        BlockSpec("attn", "dense"), BlockSpec("mosa", "dense")))


def test_health_in_step_parity_jit_donated():
    """Device-metrics pattern (DESIGN §11): router-health stats computed
    in-step (riding the jitted, donated train step's metrics) match the
    standalone ``router_health`` forward on the same params/batch."""
    from repro.nn.transformer import TransformerLM
    from repro.optim import schedules
    from repro.optim.optimizer import adamw
    from repro.train.step import make_train_step

    cfg = _tiny_mosa_cfg()
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 2, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    want = jax.jit(model.router_health)(params, tokens)

    opt = adamw(schedules.linear_warmup(1e-3, 10), clip_norm=1.0)
    opt_state = opt.init(params)
    fn = jax.jit(make_train_step(model, opt, health=True),
                 donate_argnums=(0, 1))
    _, _, _, metrics = fn(params, opt_state, jnp.zeros((), jnp.int32), batch)
    for k in ("sel_entropy", "drop_rate", "head_util"):
        np.testing.assert_allclose(float(metrics[k]), float(want[k]),
                                   rtol=1e-6, err_msg=k)
        assert 0.0 <= float(metrics[k]) <= 1.0


def test_health_in_step_microbatch_accumulates():
    """Health keys survive the scan-based microbatch accumulator (shapes
    come from eval_shape, values are means over microbatches)."""
    from repro.nn.transformer import TransformerLM
    from repro.optim import schedules
    from repro.optim.optimizer import adamw
    from repro.train.step import make_train_step

    cfg = _tiny_mosa_cfg()
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 2, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    fn = jax.jit(make_train_step(model, opt := adamw(
        schedules.linear_warmup(1e-3, 10), clip_norm=1.0),
        microbatches=2, health=True))
    _, _, _, m = fn(params, opt.init(params), jnp.zeros((), jnp.int32),
                    batch)
    for k in ("sel_entropy", "drop_rate", "head_util"):
        assert 0.0 <= float(m[k]) <= 1.0, k


def test_trainer_registry_and_dump(tmp_path):
    """Trainer routes step telemetry through the registry and dumps the
    configured artifacts on exit; health_in_step=False falls back to the
    standalone forward at log intervals (flag parity satellite)."""
    from repro.launch.train import TrainConfig, Trainer

    mpath = tmp_path / "train.json"
    tpath = tmp_path / "train.trace.json"
    cfg = TrainConfig(arch="mosa-paper", preset="smoke",
                      arch_kwargs={"variant": "mosa"}, seq_len=32,
                      global_batch=2, steps=3, lr=1e-3, warmup=2,
                      log_every=1, metrics_path=str(mpath),
                      trace_path=str(tpath))
    tr = Trainer(cfg)
    assert tr._health_in_step
    _, _, hist = tr.run(install_signals=False)
    snap = json.loads(mpath.read_text())
    assert snap["gauges"]["train.step"] == 2
    assert snap["histograms"]["train.step_time_s"]["count"] == 3
    assert snap["gauges"]["train.tokens_per_s"] > 0
    assert snap["histograms"]["train.router.sel_entropy"]["count"] == 3
    assert snap["gauges"]["train.loss"] > 0.0
    doc = json.loads(tpath.read_text())
    steps = [e for e in doc["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "train_step"]
    assert len(steps) == 3
    # in-step health rode the metrics into the history at every log step
    assert all("sel_entropy" in h for h in hist)

    # fallback path: same telemetry via the standalone forward
    obs.registry().reset()
    cfg2 = dataclasses.replace(cfg, health_in_step=False, metrics_path=None,
                               trace_path=None)
    tr2 = Trainer(cfg2)
    assert not tr2._health_in_step
    _, _, hist2 = tr2.run(install_signals=False)
    assert all("sel_entropy" in h for h in hist2)
    snap2 = obs.registry().snapshot()
    assert snap2["histograms"]["train.router.sel_entropy"]["count"] >= 1
