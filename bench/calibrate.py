"""Readings that set a serving cell's limits: for each seed, the numbers
the cell compares, read from the program and from the control, at the
cell's own load.

    python bench/calibrate.py --workload mosa8-serve-mixed \\
        --seeds 101,102,... --seconds 51 [--fault frozen] [--out cal.jsonl]

One process: set-up once, then per seed new weights from that seed, a
window of ``--seconds`` at the cell's traffic, and the comparison of a
sample of what was served with the float32 reference (the program's
reading) and of the float8 reference's first choices at the same
positions (the control's reading: the reference in the precision below
the configuration's bfloat16).  The lower reading of the limit is the
largest program reading, the upper the smallest control reading.  Each row
also says whether the harness's own comparison finds the control correct.

``--fault frozen`` plants a fault in the timed path instead: every decode
step returns its caches unchanged.  The program's reading is then the
fault's, and ``correct`` says whether the harness lets it through.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=51.0)
    p.add_argument("--fault", choices=("", "frozen"), default="")
    p.add_argument("--out", default="")
    args = p.parse_args(argv)

    from bench import harness, serving, traffic
    from bench.weights import make_params
    from repro.launch.compile_cache import use_compile_cache
    from repro.nn.module import init_shapes
    cell = harness.cell(args.workload)
    harness.device_info(cell.chips)
    use_compile_cache()
    spec, mix = cell.config, cell.traffic
    closed = mix["driver"] == "serve_closed"
    seeds = [int(s) for s in args.seeds.split(",")]
    server, cfg, params = serving.build(seeds[0], spec)
    reqs = traffic.requests(mix, seeds[0], args.seconds, cfg.vocab)
    serving.warm_up(server, spec, mix, reqs, seeds[0], cfg.vocab)
    if args.fault == "frozen":
        frozen(server)
    shapes = init_shapes(server.model)
    for seed in seeds:
        if seed != seeds[0]:
            server.params = params = None
            gc.collect()
            server.params = params = make_params(shapes, seed)
        reqs = traffic.requests(mix, seed, args.seconds, cfg.vocab)
        serving.settle()
        sched, src, log, lowered = serving.serve_window(
            server, spec, mix, reqs, args.seconds, closed)
        sm = serving.summarize(sched, src, reqs, args.seconds, closed)
        served = {i: np.asarray(sched.results[rid])
                  for i, rid in src.rid.items() if rid in sched.results}
        calls = log.dispatches()
        rids = dict(src.rid)
        del sched, src, log
        gc.collect()
        t0 = time.perf_counter()
        res = serving.check_served(params, spec, mix, reqs, rids, served,
                                   calls, seed, control=True)
        limits = spec["limits"]
        row = {"workload": args.workload, "seed": seed, "fault": args.fault,
               "program": res["program"], "control": res["control"],
               "correct": harness.correct(
                   {k: (res["program"][k], v) for k, v in limits.items()}),
               "control_correct": harness.correct(
                   {k: (res["control"][k], v) for k, v in limits.items()}),
               "requests": res["requests"], "served_tokens": res["tokens"],
               "ref_s": time.perf_counter() - t0,
               "tpot_p95_ms": sm["tpot_p95_ms"],
               "ttft_p95_ms": sm.get("ttft_p95_ms"),
               "output_tokens_per_s": sm.get("output_tokens_per_s"),
               "program_by_position": [[round(float(x), 4) for x in g]
                                       for g in res["gaps"]],
               "control_by_position": [[round(float(x), 4) for x in g]
                                       for g in res["control_gaps"]]}
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


def frozen(server):
    """Every decode step returns its caches unchanged (copied first, since
    the program donates them)."""
    import jax
    import jax.numpy as jnp
    step = server.decode_many

    def decode_many(params, tok, caches, *args, **kw):
        kept = jax.tree.map(jnp.copy, caches)
        toks, _ = step(params, tok, caches, *args, **kw)
        return toks, kept

    server.decode_many = decode_many


if __name__ == "__main__":
    sys.exit(main())
