"""The trace reduction: hand-made traces, and a short trace recorded on a
TPU v5e serving the mosa8 hybrid (``data/trace_mosa8_decode.json``, cut to
a fraction of a second with ``trace_reduce.to_dict``)."""

import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def _trace(ops, host, modules=()):
    def line(name, evs):
        return {"name": name, "events": [[n, s, d, st] for n, s, d, st in evs]}
    return tr.from_dict({"planes": [
        {"name": "/device:TPU:0", "lines": [
            line("XLA Modules", [(n, s, d, {}) for n, s, d in modules]),
            line("XLA Ops", ops)]},
        {"name": "/host:CPU", "lines": [line("python", host)]}]})


def test_busy_is_the_union_and_gaps_take_the_span_around_them():
    ops = [("fusion.1", 1000, 1000, {}),            # 1000-2000
           ("fusion.2", 1500, 1000, {}),            # overlaps: 1000-2500
           ("%paged_attention_kernel.3 = bf16[8,4,128] custom-call(), "
            'custom_call_target="tpu_custom_call"', 4000, 500, {}),
           ("%while.7 = (s32[]) while(%t)", 1000, 8500, {}),  # a container
           ("fusion.4", 9000, 500, {})]             # 9000-9500
    host = [("$profiler.py:101 start_trace", 0, 500, {}),
            ("decode_chunk", 2600, 1300, {}),       # holds gap 2500-4000
            ("$profiler.py:120 stop_trace", 10000, 10, {})]
    mods = [("jit_decode_many(3)", 1000, 3500),
            ("jit_decode_many(3)", 9000, 500)]
    r = tr.reduce_profile(_trace(ops, host, mods))
    assert r["window_s"] == pytest.approx(9500e-9)
    assert r["busy_s"] == pytest.approx(8500e-9)
    assert r["programs"] == {"jit_decode_many": pytest.approx(4000e-9)}
    assert r["program_runs"] == {"jit_decode_many": 2}
    assert r["kernels"] == {"paged_attention_kernel": pytest.approx(500e-9)}
    assert r["kernel_calls"] == {"paged_attention_kernel": 1}
    # the while op spans 1000-9500: only 0-1000 and 9500-9500 stay idle
    assert r["idle_by_span"] == {"outside any span": pytest.approx(1000e-9)}
    ops = [n for n, _ in r["breakdown"]["device_ops"]]
    assert ops[0] == "fusion.1" and not any("while" in n for n in ops)
    assert "paged_attention_kernel.3 bf16[8,4,128]" in ops


def test_gaps_take_the_span_or_the_program_function_around_them():
    ops = [("fusion.1", 1000, 1000, {}), ("fusion.2", 4000, 500, {}),
           ("fusion.3", 9000, 500, {})]
    host = [("$profiler.py:101 start_trace", 0, 500, {}),
            ("decode_chunk", 2100, 1800, {}),      # holds gap 2000-4000
            ("$scheduler.py:9 _admit", 4400, 5000, {}),
            ("$scheduler.py:7 run", 0, 10000, {}),  # outer: not chosen
            ("$profiler.py:120 stop_trace", 10000, 10, {})]
    r = tr.reduce_profile(_trace(ops, host), frozenset({"scheduler.py"}))
    by = r["idle_by_span"]
    assert by["decode_chunk"] == pytest.approx(2000e-9)
    assert by["fn scheduler.py:9 _admit"] == pytest.approx(4500e-9)
    assert by["fn scheduler.py:7 run"] == pytest.approx(1000e-9)


def test_kernels_are_tpu_custom_calls_named_by_instruction():
    assert tr._kernel_of("%fusion.2 = f32[4] fusion(%a)") is None
    assert tr._kernel_of('%mosa_attention_pallas.12 = bf16[2] custom-call(), '
                         'custom_call_target="tpu_custom_call"') == \
        "mosa_attention_pallas"


def test_a_trace_with_no_device_is_refused():
    with pytest.raises(ValueError, match="no TPU device plane"):
        tr.reduce_profile(tr.from_dict({"planes": [
            {"name": "/host:CPU", "lines": []}]}))


def test_recorded_chip_trace():
    """35 ms before to 10 ms after one packed prefill of the mosa8 hybrid
    at 64 rows (TPU v5e), reduced as a run reduces its trace."""
    d = json.loads((DATA / "trace_mosa8_prefill.json").read_text())
    r = tr.reduce_profile(tr.from_dict(d), tr.program_files())
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["window_s"] == pytest.approx(0.1552, abs=1e-3)
    assert r["program_runs"]["jit__prefill_packed"] == 1
    assert r["programs"]["jit__prefill_packed"] == pytest.approx(0.1202,
                                                                 abs=1e-3)
    # one paged prefill kernel call per layer
    assert r["kernel_calls"] == {"paged_prefill_attention_kernel": 18}
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert len(names) == 10 and not any("while" in n for n in names)
    labels = [lab for lab, _ in r["breakdown"]["idle_gaps"]]
    assert any("_admit" in lab for lab in labels)
    assert any(lab.startswith("prefill_chunk") for lab in labels)
    idle = sum(r["idle_by_span"].values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
