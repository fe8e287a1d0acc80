"""Idle gaps by scheduler span and device time by MoSA scope
(``bench/trace_scopes.py``) on hand-made traces, the prefill-service
reader on hand-made spans, and the accepted readers' values on the recorded
chip trace, which no later change may move."""

import gzip
import json
from pathlib import Path

import pytest

from bench import harness
from bench import trace_reduce as tr
from bench import trace_scopes as ts

DATA = Path(__file__).resolve().parent / "data"


def _trace(ops, host, modules=()):
    def line(name, evs):
        return {"name": name, "events": [list(e) for e in evs]}
    return tr.from_dict({"planes": [
        {"name": "/device:TPU:0", "lines": [
            line("XLA Modules", [(n, s, d, {}) for n, s, d in modules]),
            line("XLA Ops", ops)]},
        {"name": "/host:CPU", "lines": [line("python", host)]}]})


PATH = "jit(decode_many)/while/body/closed_call"
# the decode program's instructions: result type and op_name
NAMES = {"fusion.1": ("f32[16,54,128]", f"{PATH}/mosa.upkeep/sort"),
         "fusion.2": ("bf16[16,54,128,64]", f"{PATH}/mosa.attend/dot"),
         "fusion.3": ("f32[16,54]", f"{PATH}/mosa.route/logistic"),
         "fusion.4": ("bf16[8]", f"{PATH}/add"),
         "fusion.5": ("f32[16,54,128]", f"{PATH}/mosa.upkeep/gather"),
         "fusion.6": ("bf16[8,128]", f"{PATH}/mosa.upkeep/sort")}
OPS = [("%fusion.1 = f32[16,54,128] fusion(%a)", 1000, 1000, {}),
       ("%fusion.2 = bf16[16,54,128,64] fusion(%b)", 2000, 500, {}),
       ("%fusion.3 = f32[16,54] fusion(%c)", 2500, 500, {}),
       ("%while.7 = (s32[]) while(%t)", 1000, 2000, {}),    # a container
       ("%fusion.4 = bf16[8] fusion(%d)", 3300, 100, {}),
       ("%fusion.4 = bf16[8] fusion(%d)", 4800, 100, {}),
       # another program's instruction of the same name: no scope
       ("%fusion.6 = bf16[8,128] fusion(%e)", 6000, 1000, {}),
       ("%fusion.5 = f32[16,54,128] fusion(%f)", 9000, 500, {})]
HOST = [("$profiler.py:101 start_trace", 0, 500, {}),
        ("decode_chunk", 900, 2200, {}),             # holds no gap
        ("grow_rows", 3100, 200, {}),                # gap 3000-3300
        ("emit", 3300, 1500, {}),                    # gap 3400-4800 ...
        ("finish", 3500, 1000, {}),                  # ... inner at 4100
        ("wait_arrival", 4800, 1200, {}),            # gap 4900-6000
        ("admit", 7000, 1500, {}),                   # gap 7000-9000 ...
        ("evict", 7200, 800, {}),                    # ... inner at 8000
        ("$profiler.py:120 stop_trace", 10000, 10, {})]
MODS = [("jit_decode_many(3)", 1000, 2000), ("jit_other(4)", 6000, 1000),
        ("jit_decode_many(3)", 9000, 500)]


def test_gaps_take_the_innermost_scheduler_span():
    r = ts.reduce(_trace(OPS, HOST, MODS), NAMES)
    assert r["window_s"] == pytest.approx(9500e-9)
    assert r["idle_s"] == pytest.approx(9500e-9 - 3700e-9)
    assert r["idle_by_span"] == {
        "grow_rows": pytest.approx(300e-9),
        "finish": pytest.approx(1400e-9),
        "wait_arrival": pytest.approx(1100e-9),
        "evict": pytest.approx(2000e-9),
        tr.OUTSIDE: pytest.approx(500e-9 + 500e-9)}
    # host work: everything but the dispatches and waiting for traffic
    assert r["host_idle_s"] == pytest.approx((300 + 1400 + 2000) * 1e-9)
    assert r["idle_gaps"][0] == ["evict", pytest.approx(2000e-9)]


def test_scope_time_inside_decode_programs_only():
    r = ts.reduce(_trace(OPS, HOST, MODS), NAMES)
    assert r["decode_s"] == pytest.approx(2500e-9)
    assert r["scope_s"] == {"mosa.upkeep": pytest.approx(1500e-9),
                            "mosa.attend": pytest.approx(500e-9),
                            "mosa.route": pytest.approx(500e-9)}
    top = {n: (v, s) for n, v, s in r["device_ops"]}
    assert top["fusion.1 f32[16,54,128]"] == (pytest.approx(1000e-9),
                                              "mosa.upkeep")
    assert top["fusion.4 bf16[8]"] == (pytest.approx(200e-9), None)
    assert not any("while" in n for n in top)
    assert top["fusion.6 bf16[8,128]"][1] is None     # not in decode
    # an instruction whose result type differs is not the program's
    other = dict(NAMES, **{"fusion.1": ("f32[8]", NAMES["fusion.1"][1])})
    r = ts.reduce(_trace(OPS, HOST, MODS), other)
    assert r["scope_s"]["mosa.upkeep"] == pytest.approx(500e-9)
    assert ts.reduce(_trace(OPS, HOST, MODS))["scope_s"] == \
        dict.fromkeys(ts.SCOPES, 0.0)


def test_op_names_from_compiled_hlo_text():
    """Each instruction of a compiled program with its result type and
    op_name, a named scope inside a scan body included."""
    import jax
    import jax.numpy as jnp

    def f(x):
        def body(c, _):
            with jax.named_scope("mosa.upkeep"):
                c = jnp.take_along_axis(c, jnp.argsort(c, -1), -1)
            return c * 1.5, None
        return jax.lax.scan(body, x, None, length=3)[0]
    text = jax.jit(f).lower(jnp.ones((4, 8))).compile().as_text()
    names = ts.op_names(text)
    # the sort instructions (not their comparators' scalar parameters)
    sorts = [(k, v) for k, v in names.items()
             if k.startswith("sort") and "[4,8]" in v[0]]
    assert sorts and all(ts.scope_of(v[1]) == "mosa.upkeep"
                         for _, v in sorts)
    k, (ty, _) = sorts[0]
    assert ts.op_name(f"%{k} = {ty} sort(...)", names) == sorts[0][1][1]
    assert ts.op_name(f"%{k} = f32[1] sort(...)", names) == ""


def test_scope_of_matches_whole_path_components():
    assert ts.scope_of("jit(f)/mosa.upkeep/jit(argsort)/sort") == \
        "mosa.upkeep"
    assert ts.scope_of("jit(f)/mosa.upkeeper/sort") is None
    assert ts.scope_of("") is None


def test_cut_keeps_op_names_and_scheduler_spans():
    pd = _trace(OPS, HOST + [("$scheduler.py:9 _admit", 7000, 10, {})],
                MODS)
    d = ts.cut(pd, 2400, 7500, NAMES)
    names = {e[0].split(" fusion(")[0] for p in d["planes"]
             for ln in p["lines"] for e in ln["events"]}
    assert "$scheduler.py:9 _admit" not in names
    assert {"emit", "admit", "evict", "%fusion.2 = bf16[16,54,128,64]",
            "jit_decode_many(3)"} <= names
    assert "%fusion.1 = f32[16,54,128]" not in names    # before the cut
    assert "%fusion.5 = f32[16,54,128]" not in names    # after the cut
    assert set(d["op_names"]) == {"fusion.2", "fusion.3", "fusion.4",
                                  "fusion.6"}
    d = json.loads(json.dumps(d))
    back = ts.reduce(tr.from_dict(d), d["op_names"])
    assert back["scope_s"]["mosa.attend"] == pytest.approx(500e-9)
    assert back["window_s"] == pytest.approx(9500e-9)    # markers kept


def test_no_device_plane_is_refused():
    with pytest.raises(ValueError, match="no TPU device plane"):
        ts.reduce(tr.from_dict({"planes": [
            {"name": "/host:CPU", "lines": []}]}))


class _Span:
    def __init__(self, name, t0, dur, track, **args):
        self.name, self.t0, self.dur = name, t0, dur
        self.track, self.args = track, args


def test_prefill_service_reads_first_lives_before_the_profiler(monkeypatch):
    from repro import obs
    mod = harness.load_module(harness.ROOT / "bench" / "layer_metrics" /
                              "prefill_service_p95_ms.py")
    spans = [_Span("prefill", 0.0, 0.5, "req0"),           # set-up
             _Span("prefill_chunk", 10.0, 0.1, "sched")]
    # the window: 20 requests, first tokens at 10.2 .. 12.1
    spans += [_Span("prefill", 10.0 + 0.1 * i, 0.2 + 0.01 * i, f"req{i}")
              for i in range(20)]
    spans += [_Span("prefill", 10.0, 9.0, "req3", resumed=True),
              _Span("prefill", 10.0, 8.0, "req4", preempted=True),
              _Span("prefill", 20.0, 7.0, "req21")]        # after start
    timeline = [(64, 10.2 + 0.11 * i, 0.1, 8) for i in range(20)]
    monkeypatch.setattr(obs.tracer(), "spans", lambda: spans)
    ctx = harness.Context(cell=None, seed=1, seconds=1.0, trace=True,
                          peaks={})
    assert mod.read(ctx) is None
    ctx.data.update(trace_span=(15.0, 18.0), timeline=timeline)
    # nearest rank: the 19th of 20 service times, 0.2 + 0.18 s
    assert mod.read(ctx) == pytest.approx(380.0)
    ctx.data["trace_span"] = (10.0, 13.0)      # nothing finished before
    assert mod.read(ctx) is None


def _accepted_readers(fixture):
    """The five readers of the accepted benchmark on a recorded chip trace
    and fixed records."""
    red = tr.reduce_profile(tr.from_dict(fixture), tr.program_files())
    spec = harness.load_json(harness.ROOT / "bench" / "configs" /
                             "mosa-paper-medium-mosa8.json")
    ctx = harness.Context(cell=None, seed=1, seconds=51.0, trace=True,
                          peaks=harness.peaks("TPU v5 lite"))
    ctx.data.update(
        trace=red, trace_span=(100.0, 103.0), geometry=spec["shape"],
        serve=spec["serve"], qwait=[0.01 * i for i in range(1, 41)],
        prefill_calls=[(99.0, [(0, 512)]),
                       (101.0, [(0, 200), (128, 300), (512, 12)])],
        timeline=[(300, 95.0, 0.15, 64), (120, 100.5, 0.15, 32),
                  (700, 98.0, 0.15, 100)],
        spans=[_Span("decode_chunk", 101.0, 0.7, "sched", n=8, rows=3),
               _Span("decode_chunk", 102.0, 0.7, "sched", n=4, rows=3)],
        counters=({"serve.decode_tokens": 0.0, "server.decode_steps": 0.0},
                  {"serve.decode_tokens": 36.0,
                   "server.decode_steps": 12.0}))
    return {name: harness.load_module(
        harness.ROOT / "bench" / "layer_metrics" / f"{name}.py").read(ctx)
        for name in ("queue_wait_p95_ms", "step_mfu.prefill",
                     "step_mfu.decode", "paged_decode_roofline",
                     "idle_share.serve")}


def _approx(**values):
    return {k: None if v is None else pytest.approx(v, rel=1e-12)
            for k, v in values.items()}


def test_accepted_readers_on_the_recorded_prefill_trace():
    """The accepted readers' values on the recorded prefill trace, as the
    accepted benchmark computes them: no change may move them."""
    fixture = json.loads((DATA / "trace_mosa8_prefill.json").read_text())
    assert _accepted_readers(fixture) == _approx(**{
        "queue_wait_p95_ms": 380.0,
        "step_mfu.prefill": 0.8953840412218994,
        "step_mfu.decode": None,                 # no decode program
        "paged_decode_roofline": None,           # no paged decode kernel
        "idle_share.serve": 22.19050364541132})


def _decode_fixture():
    return json.loads(gzip.decompress(
        (DATA / "trace_mosa8_decode.json.gz").read_bytes()))


def test_accepted_readers_on_the_recorded_decode_trace():
    assert _accepted_readers(_decode_fixture()) == _approx(**{
        "queue_wait_p95_ms": 380.0,
        "step_mfu.prefill": None,                # no prefill program
        "step_mfu.decode": 0.9782859952090325,
        "paged_decode_roofline": 1.0654175917092532,
        "idle_share.serve": 77.26296380051271})  # one execution of 3.3 s


def test_recorded_decode_trace_upkeep_scope():
    """One 8-step ``decode_many`` of the mosa8 hybrid at 16 rows on a TPU
    v5e, cut with ``cut``, with the ``op_names`` of the same program's HLO
    text.  The four largest operations, over 16 rows x 54 heads x 128
    stored slots, are the stored set's re-sort and gathers: all under
    ``mosa.upkeep``, four fifths of the program's device time."""
    d = _decode_fixture()
    r = ts.reduce(tr.from_dict(d), d["op_names"])
    assert r["decode_s"] == pytest.approx(0.739997487, rel=1e-9)
    top4 = r["device_ops"][:4]
    assert all("110592" in n and s == "mosa.upkeep" for n, _, s in top4)
    assert r["scope_s"]["mosa.upkeep"] / r["decode_s"] == \
        pytest.approx(0.8076254372469296, rel=1e-9)
    assert r["scope_s"]["mosa.attend"] > 0 and r["scope_s"]["mosa.route"] > 0
    # the map names the instructions that carry an op_name: 93% of the time
    assert r["named_s"] / r["decode_s"] > 0.9
