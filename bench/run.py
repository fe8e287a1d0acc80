"""Run one benchmark cell once, on the machine this process starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, builds it (weights from the
seed, every program warmed up: that is ``setup_s``), measures for
``--seconds`` seconds, checks what the timed path produced against the
plain reference, and prints one JSON object as the last line of standard
output.  ``--trace 1`` reports the cell's per-layer metrics from a device
trace of part of the window instead of its end-to-end metrics.

Exits non-zero and prints no result when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from ``bench/peaks.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None, root: Path = ROOT) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from bench import harness
    # the TPU runtime would otherwise log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        cell = harness.cell(args.workload, root)
        device = harness.device_info(cell.chips)
        peaks = harness.peaks(device["kind"], root)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    if cache_dir:
        # every program of the cell, small ones included, goes to the cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    harness.say(f"bench: {cell.name} seed {args.seed} seconds {args.seconds}"
                f" trace {args.trace}; compile cache {cache_dir}")

    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), peaks=peaks,
                          data={"t_start": T_START}, root=root)
    driver = harness.load_module(
        root / "bench" / "drivers" / f"{cell.traffic['driver']}.py")
    try:
        outcome = driver.run(ctx)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    dev = dict(device)
    dev["memory_peak_bytes"] = ctx.data.get("memory_peak_bytes")
    dev.update(outcome.device_extra)
    if args.trace:
        metrics = harness.read_layer_metrics(ctx, root)
    else:
        metrics = {m["name"]: {"value": float(outcome.metrics[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in outcome.metrics}
    for k, (v, lim) in outcome.checks.items():
        harness.say(f"check {k}: {v!r} (limit {lim!r})")
    print(harness.result_line(outcome, metrics, dev), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
