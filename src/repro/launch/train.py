"""CLI face of the training subsystem (``repro.train``, DESIGN §8).

The driver itself — resumable loop, donated train step, microbatch
accumulation, mixed precision, router telemetry — lives in
``repro.train.loop`` / ``repro.train.step``; this module parses flags,
builds a ``TrainConfig``, and runs it.  ``TrainConfig`` / ``Trainer`` /
``make_train_step`` are re-exported here for compatibility (they moved in
the PR that introduced ``repro.train``).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \\
      --preset smoke --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/run1

  # the paper's IsoFLOP smoke sweep (dense vs MoSA at one matched budget):
  PYTHONPATH=src python -m repro.launch.train --isoflop --steps 20 \\
      --batch 4 --seq 64
"""

from __future__ import annotations

import argparse
import json

from repro.launch.compile_cache import use_compile_cache
from repro.train.loop import TrainConfig, Trainer          # noqa: F401
from repro.train.step import make_train_step               # noqa: F401


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="mosa-paper")
    p.add_argument("--preset", default="smoke")
    p.add_argument("--variant", default=None,
                   help="mosa-paper variant: dense|mosa|fixed|routing|pure")
    p.add_argument("--sparsity", type=int, default=None)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=2.5e-4)
    p.add_argument("--warmup", type=int, default=100)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=200)
    p.add_argument("--rule-set", default="tp")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--microbatch", type=int, default=1,
                   help="gradient-accumulation splits per step")
    p.add_argument("--compute", default=None, choices=[None, "bfloat16",
                                                       "float32"],
                   help="bfloat16 = bf16-compute/fp32-master")
    p.add_argument("--remat", default=None,
                   choices=[None, "none", "full", "dots_saveable", "mosa"])
    p.add_argument("--mosa-impl", default=None,
                   choices=[None, "einsum", "pallas"],
                   help="pallas = fused fwd + custom-VJP bwd kernels")
    p.add_argument("--isoflop", action="store_true",
                   help="run the FLOP-matched dense-vs-MoSA sweep instead "
                        "of a single config")
    p.add_argument("--metrics-path", default=None,
                   help="write an obs metrics snapshot here on exit "
                        "(.jsonl appends; DESIGN §11)")
    p.add_argument("--trace-path", default=None,
                   help="write a Chrome-trace JSON of the run here on exit")
    p.add_argument("--no-health-in-step", action="store_true",
                   help="router health via a standalone forward at log "
                        "time instead of in-step aux outputs")
    args = p.parse_args(argv)
    use_compile_cache()

    if args.isoflop:
        from repro.train.isoflop import isoflop_sweep, run_isoflop
        points = isoflop_sweep(
            preset=args.preset, T=args.seq,
            sparsities=(args.sparsity,) if args.sparsity else (8,))
        results = run_isoflop(
            points, steps=args.steps, seq_len=args.seq,
            global_batch=args.batch, ckpt_root=args.ckpt_dir,
            train_kw={"lr": args.lr, "warmup": args.warmup,
                      "rule_set": args.rule_set,
                      "log_every": args.log_every,
                      "microbatch": args.microbatch,
                      "compute": args.compute, "remat": args.remat,
                      "mosa_impl": args.mosa_impl})
        print(json.dumps(results, indent=2, default=float))
        return

    akw = {}
    if args.variant is not None:
        akw["variant"] = args.variant
    if args.sparsity is not None:
        akw["sparsity"] = args.sparsity
    cfg = TrainConfig(arch=args.arch, preset=args.preset, steps=args.steps,
                      global_batch=args.batch, seq_len=args.seq, lr=args.lr,
                      warmup=args.warmup, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every, rule_set=args.rule_set,
                      log_every=args.log_every, arch_kwargs=akw,
                      microbatch=args.microbatch, compute=args.compute,
                      remat=args.remat, mosa_impl=args.mosa_impl,
                      health_in_step=not args.no_health_in_step,
                      metrics_path=args.metrics_path,
                      trace_path=args.trace_path)
    trainer = Trainer(cfg)
    _, _, history = trainer.run()
    print(json.dumps({"final": history[-1] if history else None,
                      "straggler": trainer.monitor.summary()}))


if __name__ == "__main__":
    main()
