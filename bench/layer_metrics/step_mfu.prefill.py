"""Model step (``Server.prefill_packed``): model operations of the prompt
tokens prefilled in the traced window over (the prefill program's device
time x peak bf16 FLOP/s), in %.  Operations per segment are the paper's
accounting (``bench/costs/model_step.py``); the per-dispatch mean of the
operations is set against the per-execution mean of the program's time, so
a dispatch cut at the trace's edge does not skew it."""

from bench import harness, serving


def read(ctx):
    t = ctx.data.get("trace")
    calls = serving.prefill_in_trace(ctx)
    if not t or not calls:
        return None
    prog = [(n, v) for n, v in t["programs"].items() if "prefill_packed" in n]
    runs = sum(t["program_runs"].get(n, 0) for n, _ in prog)
    dev = sum(v for _, v in prog)
    if runs == 0 or dev <= 0:
        return None
    ms = harness.cost("model_step", ctx.root)
    s = ctx.data["geometry"]
    flops = sum(ms.prefill_flops(s, past, take)
                for segs in calls for past, take in segs) / len(calls)
    return 100.0 * flops / ((dev / runs) * ctx.peaks["bf16_flops"])
