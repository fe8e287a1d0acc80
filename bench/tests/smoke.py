"""A benchmark tree at a size the CPU can run, for the tests: the harness's
files copied beside a ``BENCHMARK.json`` whose cells use the tiny
mosa-paper preset, with the look for a chip skipped."""

from __future__ import annotations

import contextlib
import io
import json
import shutil
from pathlib import Path

from bench import harness

BENCH = Path(__file__).resolve().parent.parent

SHAPE = {"n_layers": 2, "d_model": 512, "d_ff": 2048, "vocab": 512,
         "d_head": 64, "n_dense_heads": 4, "n_mosa_heads": 6,
         "rotary_dense": 0.5, "sparsity": 8, "min_k": 2, "mosa_capacity": 16,
         "param_bytes": 4}
SERVE = {"batch": 4, "max_len": 128, "block_size": 16, "num_blocks": 40,
         "chunk_tokens": 64, "max_prefill_segs": 4, "decode_chunk": 2}
OPEN = {"driver": "serve_open", "rate_rps": 12.0,
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                   "min": 8, "max": 60},
        "output": {"dist": "uniform", "min": 2, "max": 8}}
CLOSED = {"driver": "serve_closed", "clients": 4, "pool": 64,
          "prompt": {"dist": "uniform", "min": 8, "max": 24},
          "output": {"dist": "uniform", "min": 6, "max": 10}}


def config(variant: str = "mosa", limits=None, dtype="float32") -> dict:
    kw = {"preset": "smoke", "variant": variant, "dtype": dtype}
    shape = dict(SHAPE)
    if variant == "mosa":
        kw["n_mosa_heads"] = SHAPE["n_mosa_heads"]
    else:
        shape.update(n_dense_heads=9, n_mosa_heads=0, rotary_dense=1.0)
    shape["n_params"] = 0
    return {"arch": "mosa-paper", "get_config": kw, "shape": shape,
            "serve": SERVE, "limits": dict(limits or LIMITS),
            "reduced": []}


def _limits():
    out = {}
    for name in ("mosa-paper-medium-mosa8", "mosa-paper-medium-dense"):
        out.update(harness.load_json(BENCH / "configs" / f"{name}.json")
                   ["limits"])
    return out


# every number the cells compare, at the cells' own limits
LIMITS = _limits()


def make_tree(tmp: Path, cells, per_layer=()) -> Path:
    """A root holding BENCHMARK.json and a copy of ``bench/``.  ``cells``:
    [(name, config dict, traffic dict)]."""
    root = Path(tmp)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bm = {"command": ["python3", "bench/run.py"], "paths": ["bench"],
          "run_seconds": 1, "configs": [], "workloads": [],
          "end_to_end": [
              {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
               "bound": 0.25, "source": "host_clock"},
              {"name": "tpot_p95_ms", "unit": "ms", "better": "lower",
               "bound": 0.25, "source": "host_clock"},
              {"name": "output_tokens_per_s", "unit": "tokens/s",
               "better": "higher", "bound": 0.25, "source": "host_clock"},
              {"name": "setup_s", "unit": "s", "better": "lower",
               "bound": 0.25, "source": "host_clock"}],
          "per_layer": list(per_layer)}
    for name, cfg, mix in cells:
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
        bm["configs"].append({"name": name, "source": "test",
                              "file": f"bench/configs/{name}.json",
                              "reduced": [], "why": "test"})
        bm["workloads"].append({"name": name, "config": name,
                                "traffic": name, "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def skip_chip_look(monkeypatch):
    """Let a run proceed on the CPU: no device check, no compile cache."""
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(harness, "peaks", lambda kind, root=None: {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(cc, "use_compile_cache", lambda: "")


def run_cell(root: Path, name: str, monkeypatch, seed: int = 5,
             seconds: float = 1.0, trace: int = 0) -> dict:
    """Drive ``bench/run.py`` on the CPU; returns its result line."""
    from bench import run
    skip_chip_look(monkeypatch)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", name, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
