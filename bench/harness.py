"""What every cell shares: finding its files by name, the device check, the
table of peaks, per-layer metric readers and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

    bench/configs/<config>.json        sizes and geometry of a configuration
    bench/traffic/<traffic>.json       a mix: its driver kind and parameters
    bench/drivers/<kind>.py            ``run(ctx) -> Outcome``
    bench/layer_metrics/<metric>.py    ``read(ctx) -> value | None``
    bench/costs/<name>.py              operations and bytes from shapes
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Any, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """A run that cannot produce a result (no chip, unknown device, bad
    files): the process exits non-zero and prints no result line."""


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    return json.loads(path.read_text())


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def load_module(path: Path):
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cost(name: str, root: Path = ROOT):
    """The cost module ``bench/costs/<name>.py``."""
    return load_module(root / "bench" / "costs" / f"{name}.py")


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list


def cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bm = benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bm["configs"]}
    c = cfgs[w["config"]]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bm["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"]
             if applies(m) and m["moves"] in names]
    return Cell(name, c["name"], load_json(root / c["file"]),
                w["traffic"],
                load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
                int(w["chips"]), e2e, layer)


def peaks(kind: str, root: Path = ROOT) -> dict:
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def device_info(chips: int) -> dict:
    """The accelerator this run uses; a host with no TPU, or fewer chips
    than the cell asks for, is an error (there is no CPU fallback)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, found {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"cell needs {chips} chips, found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peak = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak.append(int(stats["peak_bytes_in_use"]))
    return max(peak) if peak else None


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader sees.  ``data`` is filled by the
    driver: records, counters, the reduced trace, the model's shape."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    peaks: dict
    data: dict = dataclasses.field(default_factory=dict)
    root: Path = ROOT


@dataclasses.dataclass
class Outcome:
    """A driver's result: end-to-end metric values by name, work counts,
    and the comparison that decides ``correct`` (name -> (value, limit);
    a value above its limit is not correct)."""

    metrics: dict
    attempted: int
    failed: int
    checks: dict
    device_extra: dict = dataclasses.field(default_factory=dict)
    breakdown: Optional[dict] = None


def read_layer_metrics(ctx: Context, root: Path = ROOT) -> dict:
    out = {}
    for m in ctx.cell.per_layer:
        mod = load_module(root / "bench" / "layer_metrics" / f"{m['name']}.py")
        v = mod.read(ctx)
        if v is None or not math.isfinite(v):
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def correct(checks: dict) -> bool:
    return bool(checks) and all(
        v is not None and lim is not None and math.isfinite(v) and v <= lim
        for v, lim in checks.values())


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def result_line(outcome: Outcome, metrics: dict, device: dict) -> str:
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in outcome.checks.items()}
    line: dict[str, Any] = {
        "correct": correct(outcome.checks),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": metrics,
        "device": device,
    }
    if outcome.breakdown is not None:
        line["breakdown"] = outcome.breakdown
    line["checks"] = checks
    return json.dumps(line)
