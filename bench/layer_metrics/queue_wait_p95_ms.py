"""Scheduler: 95th percentile of the time a request waited before its
prompt began, in ms: the scheduler's recorded queue delay plus how late the
generator submitted it.  A failed request counts as infinitely late.
Read from ``Scheduler.records`` (open-loop cells only), over the requests
admitted before the profiler started: its start stalls the host for
seconds, and a request admitted after that waited through the stall."""

from bench import traffic


def read(ctx):
    q = ctx.data.get("qwait")
    if not q:
        return None
    return 1e3 * traffic.percentile(q, 95)
