"""Traffic/SLO layer suite (ISSUE 9, DESIGN §12): labeled metric series,
cross-process snapshot merging (unit + property parity vs one shared
registry, including JSONL round-trips), the ``registry.timer`` helper,
tracer ring-drop accounting, labeled Prometheus rendering, seeded load
generation, the Scheduler's timed source mode (open loop, closed loop,
shedding), and SLO/goodput evaluation with scheduler-records vs
span-derived-records parity."""

import dataclasses
import itertools
import json

import numpy as np
import pytest

from repro import obs
from repro.configs.base import BlockSpec, get_config
from repro.launch.serve import Scheduler, Server
from repro.obs.export import (merge_snapshot_files, prometheus_text,
                              write_metrics_jsonl)
from repro.obs.metrics import Registry, merge_snapshots, series_key
from repro.obs.slo import SLOSpec, evaluate, records_from_spans
from repro.obs.tracing import Tracer
from repro.serve.loadgen import (ClosedLoopSource, OpenLoopSource,
                                 TenantSpec, bursty_workload,
                                 closed_workload, poisson_workload)
from repro.serve.paged_kv import PagedConfig
from tests._property_harness import given, settings, st


@pytest.fixture(autouse=True)
def fresh_obs():
    obs.set_enabled(True)
    obs.registry().reset()
    obs.tracer().reset()
    yield
    obs.set_enabled(True)
    obs.registry().reset()
    obs.tracer().reset()


# ------------------------------------------------------------------ labels
def test_series_key_rendering():
    assert series_key("a.b", None) == "a.b"
    assert series_key("a.b", {}) == "a.b"
    assert series_key("a.b", {"t": "x"}) == 'a.b{t="x"}'
    # sorted keys -> process-independent snapshot keys
    assert series_key("m", {"b": "2", "a": "1"}) == 'm{a="1",b="2"}'
    # exposition-format escapes
    assert series_key("m", {"v": 'a"b\\c\nd'}) == \
        'm{v="a\\"b\\\\c\\nd"}'


def test_labeled_series_are_distinct():
    reg = Registry()
    reg.inc("serve.finished")
    reg.inc("serve.finished", tenant="a")
    reg.inc("serve.finished", tenant="a")
    reg.inc("serve.finished", tenant="b")
    snap = reg.snapshot()
    assert snap["counters"]["serve.finished"] == 1
    assert snap["counters"]['serve.finished{tenant="a"}'] == 2
    assert snap["counters"]['serve.finished{tenant="b"}'] == 1
    assert reg.get("serve.finished", tenant="a").value == 2
    assert reg.get("serve.finished", tenant="a").labels == {"tenant": "a"}
    # same name, different label sets, same family — and histograms too
    reg.observe("h", 0.5, tenant="a")
    reg.observe("h", 0.7)
    assert reg.get("h", tenant="a").count == 1
    assert reg.get("h").count == 1


# ------------------------------------------------------------------- timer
def test_timer_observes_and_measures():
    reg = Registry()
    with reg.timer("op.time_s") as t:
        pass
    assert t.dt >= 0.0
    h = reg.get("op.time_s")
    assert h.count == 1 and h.sum == t.dt
    with reg.timer("op.time_s", tenant="a") as t2:
        pass
    assert reg.get("op.time_s", tenant="a").count == 1
    assert t2.dt >= 0.0
    # disabled: clock runs, nothing recorded
    off = Registry(enabled=False)
    with off.timer("op.time_s") as t3:
        pass
    assert t3.dt >= 0.0 and off._metrics == {}


# ----------------------------------------------------- tracer ring drops
def test_tracer_ring_drop_accounting():
    tr = Tracer(capacity=4)
    for i in range(4):
        tr.instant(f"s{i}")
    assert tr.dropped_spans == 0
    for i in range(3):
        tr.instant(f"x{i}")
    assert tr.dropped_spans == 3              # 3 oldest overwritten
    assert len(tr) == 4
    assert tr.chrome_trace()["otherData"]["dropped_spans"] == 3
    tr.reset()
    assert tr.dropped_spans == 0
    assert tr.chrome_trace()["otherData"]["dropped_spans"] == 0
    # a disabled tracer never drops (it never records)
    tr.enabled = False
    for i in range(10):
        tr.instant(f"y{i}")
    assert tr.dropped_spans == 0


def test_dump_publishes_dropped_spans(tmp_path):
    tr = Tracer(capacity=2)
    for i in range(5):
        tr.instant(f"s{i}")
    mpath = tmp_path / "m.json"
    obs.dump(metrics_path=str(mpath), tr=tr)
    snap = json.loads(mpath.read_text())
    assert snap["gauges"]["tracer.dropped_spans"] == 3


# ----------------------------------------------------- prometheus format
def _parse_prom(txt):
    """Minimal exposition-format reader (samples attach to the family
    whose HELP/TYPE header precedes them): {family: {"type", "help",
    "samples": {sample_line_name_with_labels: value}}}."""
    fams, cur = {}, None
    for line in txt.splitlines():
        if line.startswith(("# HELP ", "# TYPE ")):
            _, field, name, rest = line.split(" ", 3)
            cur = fams.setdefault(name, {"samples": {}})
            cur[field.lower()] = rest
        elif line:
            key, val = line.rsplit(" ", 1)
            cur["samples"][key] = float(val)
    return fams


def test_prometheus_labeled_round_trip():
    reg = Registry()
    reg.inc("serve.finished", 3)
    reg.inc("serve.finished", tenant="a")
    reg.set("pool.free", 7)
    reg.observe("serve.ttft_s", 1.5, bounds=(1.0, 2.0), tenant='q"t')
    txt = prometheus_text(reg)
    fams = _parse_prom(txt)
    f = fams["serve_finished"]
    assert f["type"] == "counter" and f["help"] == "serve.finished"
    assert f["samples"]["serve_finished"] == 3
    assert f["samples"]['serve_finished{tenant="a"}'] == 1
    assert fams["pool_free"]["type"] == "gauge"
    h = fams["serve_ttft_s"]
    assert h["type"] == "histogram"
    # labeled buckets carry BOTH le and the series labels, escaped
    assert h["samples"]['serve_ttft_s_bucket{le="2",tenant="q\\"t"}'] == 1
    assert h["samples"]['serve_ttft_s_bucket{le="+Inf",tenant="q\\"t"}'] == 1
    assert h["samples"]['serve_ttft_s_count{tenant="q\\"t"}'] == 1
    # one HELP/TYPE header per family even with multiple series
    assert txt.count("# TYPE serve_finished counter") == 1


# ----------------------------------------------------------------- merge
def _strip_meta(snap):
    return {k: v for k, v in snap.items() if k != "gauges_meta"}


def test_merge_semantics_unit():
    a, b = Registry(), Registry()
    a.inc("c", 2)
    b.inc("c", 3)
    a.inc("only_a")
    b.set_max("hw", 5.0)
    a.set_max("hw", 7.0)
    a.set("last", 1.0)
    b.set("last", 2.0)                  # newer stamp wins
    a.observe("h", 0.5)
    b.observe("h", 1.5)
    b.observe("h", 2.5)
    m = merge_snapshots([a.snapshot(), b.snapshot()])
    assert m["counters"]["c"] == 5
    assert m["counters"]["only_a"] == 1
    assert m["gauges"]["hw"] == 7.0
    assert m["gauges"]["last"] == 2.0
    h = m["histograms"]["h"]
    assert h["count"] == 3 and h["sum"] == 4.5
    assert h["min"] == 0.5 and h["max"] == 2.5
    assert h["p50"] > 0


def test_merge_order_independent():
    regs = [Registry() for _ in range(3)]
    for i, r in enumerate(regs):
        r.inc("c", i + 1)
        r.set("g", float(i))
        r.set_max("m", float(10 - i))
        for v in range(i + 2):
            r.observe("h", v / 4.0)
    snaps = [r.snapshot() for r in regs]
    first = merge_snapshots(snaps)
    for perm in itertools.permutations(snaps):
        assert merge_snapshots(list(perm)) == first
    # associativity: merging a merged snapshot with the third matches
    two = merge_snapshots(snaps[:2])
    assert _strip_meta(merge_snapshots([two, snaps[2]])) == \
        _strip_meta(first)


_OPS = ("inc", "set", "set_max", "observe")


@settings(max_examples=20, deadline=None)
@given(ops=st.lists(
    st.tuples(st.sampled_from(_OPS), st.integers(0, 2),
              st.sampled_from(("m.a", "m.b", "m.c")),
              st.sampled_from(("", "a", "b")),
              st.integers(1, 100)),
    min_size=1, max_size=40))
def test_merge_parity_property(ops):
    """The §12 aggregation contract: K per-process registries fed a random
    interleaving of ops merge to EXACTLY what one shared registry fed the
    same global sequence reports — counters, gauges (both kinds),
    histograms, labels included.  Values are quarter-integers so float
    addition is exact regardless of grouping."""
    procs = [Registry() for _ in range(3)]
    shared = Registry()
    for kind, p, base, tenant, v in ops:
        name = base + "." + kind            # one metric type per name
        labels = {"tenant": tenant} if tenant else {}
        val = v / 4.0
        for reg in (procs[p], shared):
            getattr(reg, kind)(name, val, **labels)
    merged = merge_snapshots([r.snapshot() for r in procs])
    assert _strip_meta(merged) == _strip_meta(shared.snapshot())
    # order-independence on the same draw
    rev = merge_snapshots([r.snapshot() for r in reversed(procs)])
    assert _strip_meta(rev) == _strip_meta(merged)


def test_merge_jsonl_files_parity(tmp_path):
    """Replica aggregation end-to-end: N processes dump JSONL snapshots,
    ``merge_snapshot_files`` reads the last line of each and reproduces
    the shared-registry view."""
    shared = Registry()
    paths = []
    for i in range(3):
        r = Registry()
        for reg in (r, shared):
            reg.inc("req.count", i + 1, tenant=f"t{i}")
            reg.inc("req.count", 2)
            reg.observe("lat.s", (i + 1) / 4.0)
            reg.set_max("hw", float(i))
        p = tmp_path / f"replica{i}.jsonl"
        write_metrics_jsonl(str(p), r, tag=f"r{i}")     # stale line...
        r.inc("req.count", 1)
        shared.inc("req.count", 1)
        write_metrics_jsonl(str(p), r, tag=f"r{i}")     # ...then final
        paths.append(str(p))
    merged = merge_snapshot_files(paths)
    want = shared.snapshot()
    assert merged["counters"] == want["counters"]
    assert merged["gauges"] == want["gauges"]
    for k, h in want["histograms"].items():
        got = merged["histograms"][k]
        for field in ("count", "sum", "counts", "bounds", "min", "max",
                      "p50", "p90", "p99"):
            assert got[field] == h[field], (k, field)


# --------------------------------------------------------------- loadgen
def test_workload_determinism_and_mix():
    tenants = (TenantSpec("a", weight=3.0, prompt_len=(4, 8),
                          max_new=(2, 4)),
               TenantSpec("b", weight=1.0, prompt_len=(16, 24),
                          max_new=(5, 6)))
    w1 = poisson_workload(rate=10.0, n=200, seed=7, vocab=64,
                          tenants=tenants)
    w2 = poisson_workload(rate=10.0, n=200, seed=7, vocab=64,
                          tenants=tenants)
    assert len(w1) == 200
    for x, y in zip(w1, w2):
        assert x.t == y.t and x.tenant == y.tenant and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)
    assert poisson_workload(10.0, 200, 8, 64, tenants)[0].t != w1[0].t
    # arrival times sorted, mean interarrival ~ 1/rate
    ts = [a.t for a in w1]
    assert ts == sorted(ts)
    assert ts[-1] / 200 == pytest.approx(0.1, rel=0.5)
    # tenant mix respects weights; lengths respect per-tenant ranges
    frac_a = sum(a.tenant == "a" for a in w1) / 200
    assert 0.55 <= frac_a <= 0.95
    for a in w1:
        lo, hi = (4, 8) if a.tenant == "a" else (16, 24)
        assert lo <= len(a.prompt) <= hi
        assert (a.prompt >= 0).all() and (a.prompt < 64).all()


def test_bursty_workload_is_burstier():
    po = poisson_workload(10.0, 500, 3, 64)
    bu = bursty_workload(10.0, 500, 3, 64, cv=3.0)

    def cv(arr):
        gaps = np.diff([0.0] + [a.t for a in arr])
        return gaps.std() / gaps.mean()

    assert cv(bu) > 1.5 * cv(po)
    assert cv(po) == pytest.approx(1.0, rel=0.3)        # Poisson CV = 1


def test_closed_workload_and_sources():
    w = closed_workload(5, 1, 64)
    assert all(a.t == 0.0 for a in w)

    class FakeSched:
        def __init__(self):
            self.results = {}
            self.subs = []

        def submit(self, prompt, max_new, tenant=""):
            rid = len(self.subs)
            self.subs.append((len(prompt), max_new, tenant))
            return rid

    # open loop: submits exactly the due arrivals
    arr = poisson_workload(5.0, 10, 2, 64)
    src = OpenLoopSource(arr)
    fake = FakeSched()
    src.pump(fake, arr[2].t)
    assert len(fake.subs) == 3
    assert src.next_arrival_in(arr[2].t) == \
        pytest.approx(arr[3].t - arr[2].t)
    src.pump(fake, arr[-1].t)
    assert src.exhausted() and src.next_arrival_in(1e9) is None
    # closed loop: holds `concurrency` outstanding
    csrc = ClosedLoopSource(w, concurrency=2)
    fake2 = FakeSched()
    csrc.pump(fake2, 0.0)
    assert len(fake2.subs) == 2
    csrc.pump(fake2, 1.0)
    assert len(fake2.subs) == 2               # nothing finished yet
    fake2.results[0] = "done"
    csrc.pump(fake2, 2.0)
    assert len(fake2.subs) == 3
    fake2.results.update({1: "d", 2: "d"})
    csrc.pump(fake2, 3.0)
    assert len(fake2.subs) == 5 and csrc.exhausted()


# ------------------------------------------------------------ slo.evaluate
def _rec(rid, tenant, outcome, ttft, tpot, toks=8, qd=0.0):
    return {"rid": rid, "tenant": tenant, "outcome": outcome,
            "t_arrival": 0.0, "queue_delay_s": qd, "ttft_s": ttft,
            "tpot_s": tpot, "new_tokens": toks}


def test_evaluate_goodput_and_tenants():
    spec = SLOSpec(ttft_s=0.1, tpot_s=0.02, name="interactive")
    recs = [
        _rec(0, "a", "finished", 0.05, 0.01),
        _rec(1, "a", "finished", 0.50, 0.01),      # TTFT miss
        _rec(2, "b", "finished", 0.05, 0.05),      # TPOT miss
        _rec(3, "b", "finished", 0.05, None, toks=1),  # no TPOT obligation
        _rec(4, "b", "shed", None, None, toks=0),
    ]
    ev = evaluate(recs, spec)
    assert ev["total"] == 5 and ev["finished"] == 4 and ev["shed"] == 1
    assert ev["slo_met"] == 2
    assert ev["goodput"] == pytest.approx(2 / 5)
    assert ev["served_goodput"] == pytest.approx(2 / 4)
    assert ev["spec"]["name"] == "interactive"
    assert ev["ttft"]["count"] == 4
    assert ev["ttft"]["p50"] == pytest.approx(0.05)
    per = ev["per_tenant"]
    assert set(per) == {"a", "b"}
    assert per["a"]["goodput"] == pytest.approx(1 / 2)
    assert per["b"]["goodput"] == pytest.approx(1 / 3)
    assert per["b"]["shed"] == 1
    # empty record set
    empty = evaluate([], spec)
    assert empty["goodput"] == 0.0 and empty["ttft"] == {"count": 0}


def test_records_from_spans_synthetic():
    tr = Tracer()
    t0 = 1.0
    tr.add("queued", t0, t0 + 0.2, track="req5")
    tr.add("prefill", t0 + 0.2, t0 + 0.5, track="req5", prompt=16)
    tr.add("decode", t0 + 0.5, t0 + 1.5, track="req5", tokens=11)
    tr.instant("finish", track="req5", tokens=11, tenant="a")
    tr.instant("shed", track="req7", tenant="b")
    tr.add("queued", t0, t0 + 9.9, track="req9")       # never finished
    recs = {r["rid"]: r for r in records_from_spans(tr.spans())}
    r5 = recs[5]
    assert r5["outcome"] == "finished" and r5["tenant"] == "a"
    assert r5["t_arrival"] == pytest.approx(t0)
    assert r5["queue_delay_s"] == pytest.approx(0.2)
    assert r5["ttft_s"] == pytest.approx(0.5)
    assert r5["tpot_s"] == pytest.approx(1.0 / 10)
    assert r5["new_tokens"] == 11
    assert recs[7]["outcome"] == "shed" and recs[7]["tenant"] == "b"
    assert recs[9]["outcome"] == "incomplete"


# ----------------------------------------------- scheduler timed mode
def _dense_window_cfg():
    cfg = get_config("mosa-paper", preset="smoke", variant="dense")
    return dataclasses.replace(
        cfg, n_layers=2,
        attention=dataclasses.replace(cfg.attention, window=16),
        pattern=(BlockSpec("attn", "dense"),
                 BlockSpec("attn_local", "dense")))


_SERVER = None


def small_server():
    """Cached dense+window server (compile once across this module); the
    small pool makes long mixes preempt — same pattern as test_obs."""
    global _SERVER
    if _SERVER is None:
        _SERVER = Server(_dense_window_cfg(), batch=2, max_len=64,
                         paged=PagedConfig(block_size=8, num_blocks=14,
                                           num_window_blocks=4))
    return _SERVER


_TENANTS = (TenantSpec("gold", weight=1.0, prompt_len=(4, 12),
                       max_new=(2, 4)),
            TenantSpec("free", weight=1.0, prompt_len=(4, 12),
                       max_new=(2, 4)))


def test_timed_open_loop_serves_all():
    server = small_server()
    sched = Scheduler(server, chunk=4, prefix_cache=False)
    arrivals = poisson_workload(rate=200.0, n=6, seed=11, vocab=64,
                                tenants=_TENANTS)
    src = OpenLoopSource(arrivals)
    out = sched.run(source=src)
    assert len(src.submitted_rids) == 6
    for a, rid in zip(src.arrivals, src.submitted_rids):
        assert len(out[rid]) == a.max_new
    recs = list(sched.records.values())
    assert len(recs) == 6
    assert all(r["outcome"] == "finished" for r in recs)
    assert {r["tenant"] for r in recs} <= {"gold", "free"}
    snap = obs.registry().snapshot()
    for r in recs:
        assert r["ttft_s"] is not None and r["ttft_s"] > 0
        assert r["queue_delay_s"] >= 0
        if not snap["counters"].get("serve.preempted", 0):
            # arrival-based TTFT includes the queue wait (a preempted
            # request's final queue delay restarts, so only assert on
            # preemption-free runs)
            assert r["ttft_s"] >= r["queue_delay_s"] * (1 - 1e-9)
    assert snap["counters"]['serve.finished{tenant="gold"}'] + \
        snap["counters"]['serve.finished{tenant="free"}'] == 6
    assert snap["histograms"]["serve.queue_delay_s"]["count"] >= 6
    assert snap["histograms"]["serve.run_s"]["count"] == 1


def test_timed_closed_loop_bounds_concurrency():
    server = small_server()
    sched = Scheduler(server, chunk=4, prefix_cache=False)
    reqs = closed_workload(5, 13, 64, tenants=_TENANTS[:1])
    src = ClosedLoopSource(reqs, concurrency=1)
    out = sched.run(source=src)
    assert len(out) == 5
    for a, rid in zip(reqs, src.submitted_rids):
        assert len(out[rid]) == a.max_new
    snap = obs.registry().snapshot()
    # concurrency cap binds BELOW the batch size (2): the closed loop is
    # doing the limiting, not the server
    assert snap["gauges"]["serve.max_concurrent"] == 1
    assert snap["counters"]["serve.finished"] == 5


def test_shedding_under_max_queue():
    server = small_server()
    sched = Scheduler(server, chunk=4, prefix_cache=False, max_queue=1)
    rids = [sched.submit(np.full((6,), 3, np.int32), max_new=2,
                         tenant="gold") for _ in range(4)]
    out = sched.run()
    # first fills the queue; the rest shed at submit time
    assert len(out[rids[0]]) == 2
    for r in rids[1:]:
        assert len(out[r]) == 0
    recs = sched.records
    assert recs[rids[0]]["outcome"] == "finished"
    assert all(recs[r]["outcome"] == "shed" for r in rids[1:])
    snap = obs.registry().snapshot()
    assert snap["counters"]["serve.shed"] == 3
    assert snap["counters"]['serve.shed{tenant="gold"}'] == 3
    assert snap["counters"]["serve.submitted"] == 4
    assert snap["counters"]["serve.finished"] == 1
    # goodput accounting sees the sheds
    ev = evaluate(list(recs.values()), SLOSpec(ttft_s=1e9))
    assert ev["total"] == 4 and ev["shed"] == 3
    assert ev["goodput"] == pytest.approx(1 / 4)
    assert ev["served_goodput"] == pytest.approx(1.0)


def test_scheduler_records_match_span_records():
    """Parity (§12): the offline span-derived records equal the live
    scheduler records — across a mix long enough to trigger preemption
    and re-prefill on the small pool."""
    server = small_server()
    sched = Scheduler(server, chunk=4, prefix_cache=False)
    rng = np.random.default_rng(5)
    # P=50 rows admit at 7 dense blocks each (pool: 14, so both fit
    # exactly), then decode growth to 51+ tokens wants an 8th each — the
    # newer row MUST be preempted: preemption + re-prefill (resumed span)
    # are exercised by construction.
    for i, n in enumerate((50, 50, 12)):
        sched.submit(rng.integers(2, 64, size=(n,)).astype(np.int32),
                     max_new=10, tenant="gold" if i % 2 else "free")
    out = sched.run()
    assert len(out) == 3
    assert obs.registry().snapshot()["counters"].get(
        "serve.preempted", 0) > 0, "mix was meant to preempt"
    live = sched.records
    derived = {r["rid"]: r for r in records_from_spans(obs.tracer().spans())}
    assert set(derived) == set(live)
    for rid, want in live.items():
        got = derived[rid]
        # ttft is float-reassembled from span endpoints (t0 + dur): approx;
        # every other field is computed from the same floats — exact.
        assert got["ttft_s"] == pytest.approx(want["ttft_s"], rel=1e-9)
        for k in ("tenant", "outcome", "t_arrival", "queue_delay_s",
                  "tpot_s", "prefill_s", "new_tokens"):
            assert got[k] == want[k], (rid, k, got[k], want[k])
    ev = evaluate(list(live.values()), SLOSpec(ttft_s=1e9))
    assert ev["finished"] == 3 and "per_tenant" in ev
