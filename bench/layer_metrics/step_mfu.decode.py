"""Model step (``decode_many`` program): least time of the decode steps in
the traced window over that program's device time, in %.  Decode is judged
against both peaks: a step's least time is the larger of its model
operations over peak bf16 FLOP/s and the bytes it must read (every weight
once, each live row's dense keys and values and its MoSA heads' stored set)
over HBM bandwidth (``bench/costs/model_step.py``).  Live rows and their
positions come from the requests' records (``serving.decode_census``); the
per-dispatch mean is set against the per-execution mean of the program."""

from bench import harness, serving


def read(ctx):
    t = ctx.data.get("trace")
    census = serving.decode_census(ctx)
    if not t or not census:
        return None
    prog = [(n, v) for n, v in t["programs"].items() if "decode_many" in n]
    runs = sum(t["program_runs"].get(n, 0) for n, _ in prog)
    dev = sum(v for _, v in prog)
    if runs == 0 or dev <= 0:
        return None
    ms = harness.cost("model_step", ctx.root)
    s = ctx.data["geometry"]
    least = 0.0
    for n, lengths in census:
        for j in range(n):
            least += ms.decode_least_s(s, [x + j for x in lengths],
                                       ctx.peaks)
    least /= len(census)
    return 100.0 * least / (dev / runs)
