"""Scheduler: 95th percentile of a request's prefill service, from its
admission to its first token (packed prefill chunks taking turns with
decode chunks), in ms: the part of TTFT after the queue.  Read from the
program's own ``prefill`` span on each request's track, which the
scheduler records from admission to first token (its duration is the
record's ``prefill_s``), for the life that produced the first token (not a
re-prefill after preemption).  Over the window's requests whose first token
came before the profiler started: its start stalls the host for seconds,
as for ``queue_wait_p95_ms``.  A request with no first token has no span
and is counted by ``ttft_p95_ms``."""

from bench import traffic


def read(ctx):
    from repro import obs
    span = ctx.data.get("trace_span")
    timeline = ctx.data.get("timeline")
    if not span or not timeline:
        return None
    # the window's first first token: every earlier span is set-up's
    first = min(t_first for _, t_first, _, _ in timeline) - 1e-6
    service = [s.dur for s in obs.tracer().spans()
               if s.name == "prefill" and s.track.startswith("req")
               and not s.args.get("resumed") and not s.args.get("preempted")
               and first <= s.t0 + s.dur < span[0]]
    if not service:
        return None
    return 1e3 * traffic.percentile(service, 95)
