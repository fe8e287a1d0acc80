"""A whole run of a serving cell on the CPU at a tiny size, with the look
for a chip skipped: the harness finds everything by name, the comparison
with the reference passes, and fails when the timed path is broken."""

import json
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, serving
from bench.tests import smoke

@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return smoke.make_tree(tmp_path_factory.mktemp("bench"), [
        ("tiny-open", smoke.config(), smoke.OPEN),
        ("tiny-closed", smoke.config(), smoke.CLOSED),
        ("tiny-dense", smoke.config("dense", dtype="bfloat16"),
         dict(smoke.OPEN, output={"dist": "uniform", "min": 24, "max": 40}))])


def test_open_loop_cell_runs_and_is_correct(tree, monkeypatch):
    line = smoke.run_cell(tree, "tiny-open", monkeypatch, seed=2 ** 33 + 1)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert line["attempted"] == 12 and line["failed"] == 0
    assert set(line["checks"]) == set(smoke.LIMITS)
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"] == smoke.LIMITS[name]
    assert list(line)[-1] == "checks"


def test_closed_loop_cell_reports_throughput(tree, monkeypatch):
    line = smoke.run_cell(tree, "tiny-closed", monkeypatch, seconds=2.0)
    assert line["correct"] is True
    assert line["metrics"]["output_tokens_per_s"]["value"] > 0
    assert line["metrics"]["output_tokens_per_s"]["unit"] == "tokens/s"


def test_altered_token_is_not_correct(tree, monkeypatch):
    """A token altered where it is produced (the sampler) fails the
    comparison."""
    import jax.numpy as jnp
    import repro.launch.serve as ls
    import repro.nn.transformer as tr
    orig = tr.sample_logits

    def off_by_one(logits, key, temperature=0.0, top_k=0):
        tok = orig(logits, key, temperature, top_k)
        return ((tok + 1) % logits.shape[-1]).astype(jnp.int32)

    monkeypatch.setattr(tr, "sample_logits", off_by_one)
    monkeypatch.setattr(ls, "sample_logits", off_by_one)
    line = smoke.run_cell(tree, "tiny-open", monkeypatch)
    assert line["correct"] is False
    for name, c in line["checks"].items():
        assert c["value"] > c["limit"], name


def test_decode_state_left_unchanged_is_not_correct(tree, monkeypatch):
    """A decode step that returns its caches unchanged fails the
    comparison."""
    import jax
    import jax.numpy as jnp
    build = serving.build

    def frozen_build(seed, spec):
        server, cfg, params = build(seed, spec)
        step = server.decode_many

        def decode_many(params, tok, caches, *args, **kw):
            kept = jax.tree.map(jnp.copy, caches)
            toks, _ = step(params, tok, caches, *args, **kw)
            return toks, kept

        server.decode_many = decode_many
        return server, cfg, params

    monkeypatch.setattr(serving, "build", frozen_build)
    line = smoke.run_cell(tree, "tiny-open", monkeypatch)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_control_reads_above_the_limit(tree, monkeypatch):
    """The program in bfloat16 against the reference in float8 put in its
    place: at the same positions the control reads above the limit the
    program keeps (the dense baseline's widest gap)."""
    smoke.skip_chip_look(monkeypatch)
    cell = harness.cell("tiny-dense", tree)
    spec, mix = cell.config, cell.traffic
    server, cfg, params = serving.build(3, spec)
    from bench import traffic
    reqs = traffic.requests(mix, 3, 1.0, cfg.vocab)
    sched, src, log, _ = serving.serve_window(server, spec, mix, reqs, 1.0,
                                              closed=False)
    served = {i: np.asarray(sched.results[r])
              for i, r in src.rid.items() if r in sched.results}
    res = serving.check_served(params, spec, mix, reqs, dict(src.rid),
                               served, log.dispatches(), 3, control=True)
    assert res["requests"] == 12
    lim = spec["limits"]["served_gap_std"]
    prog, ctl = res["program"]["served_gap_std"], \
        res["control"]["served_gap_std"]
    assert prog <= lim < ctl, (prog, lim, ctl)


def test_cell_traffic_and_metric_from_new_files_only(tree):
    """A later PR adds a cell by adding files: nothing existing changes."""
    bm = json.loads((tree / "BENCHMARK.json").read_text())
    (tree / "bench" / "traffic" / "tiny-burst.json").write_text(
        json.dumps(dict(smoke.OPEN, rate_rps=30.0)))
    (tree / "bench" / "layer_metrics" / "requests_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx.data['qwait']))\n")
    bm["workloads"].append({"name": "tiny-burst", "config": "tiny-open",
                            "traffic": "tiny-burst", "chips": 1,
                            "why": "test"})
    bm["per_layer"].append({"name": "requests_seen", "unit": "requests",
                            "better": "higher", "source": "program_span",
                            "layer": "scheduler", "moves": "ttft_p95_ms",
                            "workloads": ["tiny-burst"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = harness.cell("tiny-burst", tree)
    assert cell.traffic["rate_rps"] == 30.0
    assert [m["name"] for m in cell.per_layer] == ["requests_seen"]
    assert "tiny-burst" not in [
        m["name"] for m in harness.cell("tiny-open", tree).per_layer]
    ctx = harness.Context(cell=cell, seed=1, seconds=1.0, trace=True,
                          peaks={}, data={"qwait": [0.1, 0.2, 0.3]})
    assert harness.read_layer_metrics(ctx, tree) == {
        "requests_seen": {"value": 3.0, "unit": "requests"}}


def test_no_tpu_exits_nonzero_without_a_result():
    r = subprocess.run(
        [sys.executable, str(harness.ROOT / "bench" / "run.py"),
         "--workload", "mosa8-serve-mixed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError, match="not in bench/peaks.json"):
        harness.peaks("TPU v99")
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_layer_metric_readers_on_recorded_data():
    """Readers read what the driver stored, and return nothing where there
    is nothing to read."""
    root = harness.ROOT
    mods = {n: harness.load_module(root / "bench" / "layer_metrics" /
                                   f"{n}.py")
            for n in ("queue_wait_p95_ms", "idle_share.serve",
                      "step_mfu.decode")}
    ctx = harness.Context(cell=None, seed=1, seconds=1.0, trace=True,
                          peaks={"bf16_flops": 197e12,
                                 "hbm_bytes_per_s": 819e9})
    assert all(m.read(ctx) is None for m in mods.values())
    c0 = {"serve.decode_tokens": 100.0, "server.decode_steps": 10.0}
    c1 = {"serve.decode_tokens": 420.0, "server.decode_steps": 30.0}
    ctx.data.update(counters=(c0, c1), qwait=[0.001 * i for i in range(100)],
                    trace={"busy_s": 3.0, "window_s": 4.0})
    assert mods["queue_wait_p95_ms"].read(ctx) == pytest.approx(94.0)
    assert mods["idle_share.serve"].read(ctx) == pytest.approx(25.0)


def test_traced_run_reports_per_layer_metrics(tmp_path, monkeypatch):
    """A ``--trace 1`` run end to end, with the CPU's trace replaced by the
    reduction of the recorded chip trace: the cell's per-layer metrics,
    ``busy_s``/``window_s`` and the breakdown reach the result line."""
    from bench import trace_reduce
    per_layer = [dict(m, workloads=["tiny-open"]) for m in json.loads(
        (harness.ROOT / "BENCHMARK.json").read_text())["per_layer"]]
    root = smoke.make_tree(tmp_path, [("tiny-open", smoke.config(),
                                       smoke.OPEN)], per_layer)
    fixture = json.loads((harness.ROOT / "bench" / "tests" / "data" /
                          "trace_mosa8_prefill.json").read_text())
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda d: (
        trace_reduce.reduce_profile(trace_reduce.from_dict(fixture))))
    line = smoke.run_cell(root, "tiny-open", monkeypatch, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    assert {"queue_wait_p95_ms", "idle_share.serve"} <= set(m)
    assert not {"ttft_p95_ms", "tpot_p95_ms", "setup_s"} & set(m)
    assert 0 < m["idle_share.serve"]["value"] < 100
    assert line["device"]["busy_s"] <= line["device"]["window_s"]
    assert len(line["breakdown"]["device_ops"]) == 10
    assert line["breakdown"]["idle_gaps"]
