"""Find the highest arrival rate a serving cell sustains: one process,
several offered rates, one window each.

    python bench/sweep.py --workload mosa8-serve-mixed --rates 5,10,20 \\
        --seconds 20 [--seed 1] [--out sweep.jsonl]

For each rate it prints (and appends to ``--out``) one JSON line: offered
rate, completed rate (requests finished inside the window per second),
queue depth when the window closed, TTFT and TPOT p95 in ms, and how long
the drain took.  The knee is the highest rate whose completed rate keeps up
with the offered one and whose queue does not grow; the cell's rate is
0.8 x the knee, written into its traffic file by hand.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--out", default="")
    args = p.parse_args(argv)

    from bench import harness, serving, traffic
    cell = harness.cell(args.workload)
    harness.device_info(cell.chips)
    from repro import obs
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    spec, mix = cell.config, cell.traffic
    server, cfg, params = serving.build(args.seed, spec)
    rates = [float(r) for r in args.rates.split(",")]
    every = [r for k, rate in enumerate(rates) for r in traffic.requests(
        mix, args.seed + k, args.seconds, cfg.vocab, rate_rps=rate)]
    serving.warm_up(server, spec, mix, every, args.seed, cfg.vocab)
    del every
    serving.settle()
    harness.say(f"sweep: set-up {time.perf_counter() - T_START:.1f} s")
    for k, rate in enumerate(rates):
        reqs = traffic.requests(mix, args.seed + k, args.seconds, cfg.vocab,
                                rate_rps=rate)
        sched, src, log, lowered = serving.serve_window(
            server, spec, mix, reqs, args.seconds, closed=False)
        sm = serving.summarize(sched, src, reqs, args.seconds, closed=False)
        row = {"workload": args.workload, "offered_rps": rate,
               "completed_rps": sm["finished_in_window"] / args.seconds,
               "queue_at_close": sm["queue_at_close"],
               "ttft_p95_ms": sm["ttft_p95_ms"],
               "tpot_p95_ms": sm["tpot_p95_ms"],
               "attempted": sm["attempted"], "failed": sm["failed"],
               "drain_s": obs.tracer().now() - src.t_open - args.seconds,
               "preemptions": sched.stats["preemptions"],
               "lowered_in_window": len(lowered)}
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        del sched, src, log
    return 0


if __name__ == "__main__":
    sys.exit(main())
