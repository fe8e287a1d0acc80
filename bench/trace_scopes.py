"""What the program's own names say about a traced window, beyond what
``trace_reduce`` reads:

  * idle gaps of the device labelled by the innermost scheduler span over
    each gap's midpoint (every span ``repro.serve.scheduler`` records on its
    ``sched`` track, which ``Tracer.annotate`` puts on the profiler's host
    plane), and the idle time under the spans of host work between
    dispatches;
  * device time of the operations under each of MoSA's named scopes
    (``repro.core.router``: ``mosa.route``, ``mosa.upkeep``,
    ``mosa.attend``) inside the ``decode_many`` program's executions;
  * the ten operations with most device time, each with its scope.

A TPU trace's operation events carry the instruction (``%fusion.346 =
f32[110592]... fusion(...)``) but not its ``op_name``, so the scopes come
from the compiled ``decode_many`` program's HLO text (``op_names``: each
instruction's ``op_name`` metadata), matched by instruction name and result
type.  ``cut`` keeps a short stretch of a trace as plain data that
``trace_reduce.from_dict`` reads back, with the ``op_names`` it needs.
"""

from __future__ import annotations

import re

from bench import trace_reduce as tr

SCHED_SPANS = ("admit", "evict", "resume", "first_token", "prefix_insert",
               "router_health", "pause_prefills", "grow_rows", "emit",
               "finish", "wait_arrival", "prefill_chunk", "decode_chunk")
# Spans that are not host work between dispatches: the two dispatches
# themselves and waiting for traffic.
NOT_HOST_WORK = ("prefill_chunk", "decode_chunk", "wait_arrival")
SCOPES = ("mosa.route", "mosa.upkeep", "mosa.attend")
DECODE = "decode_many"
_HLO_LINE = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = (.+?) [a-z][\w\-]*\(.*'
                       r'metadata=\{[^}]*op_name="([^"]*)"')


def op_names(hlo_text: str) -> dict:
    """Instruction name -> (result type, op_name) of a compiled program's
    HLO text (``Compiled.as_text()``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = (m.group(2), m.group(3))
    return out


def op_name(event_name: str, names: dict) -> str:
    """The ``op_name`` of an operation event, where the program's
    instruction of that name has the event's result type, else ''."""
    if " = " not in event_name:
        return ""
    got = names.get(tr._instr(event_name))
    if got is None or not event_name.split(" = ", 1)[1].startswith(got[0]):
        return ""
    return got[1]


def scope_of(name: str):
    """The MoSA scope an ``op_name`` lies under, else None."""
    return next((s for s in SCOPES if f"/{s}/" in f"/{name}/"), None)


def _window(pd):
    """(start, stop) of the traced window in the trace's nanoseconds."""
    starts, stops = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for name, s, d, _ in tr._events(line):
                    if name.endswith("start_trace"):
                        starts.append(s + d)
                    elif name.endswith("stop_trace"):
                        stops.append(s)
    return (max(starts) if starts else None, min(stops) if stops else None)


def reduce(pd, names=None) -> dict:
    """``names``: ``op_names`` of the ``decode_many`` program, for the
    scopes (none without it)."""
    names = names or {}
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")
              and p.name[len("/device:TPU:"):].isdigit()]
    if not planes:
        raise ValueError("trace holds no TPU device plane")
    dev = planes[0]
    spans = [(s, s + d, name)
             for plane in pd.planes if plane.name.startswith("/host:CPU")
             for line in plane.lines
             for name, s, d, _ in tr._events(line) if name in SCHED_SPANS]
    decode, ops = [], []
    for line in dev.lines:
        if line.name == "XLA Modules":
            decode += [(s, s + d) for name, s, d, _ in tr._events(line)
                       if DECODE in name]
        elif line.name == "XLA Ops":
            ops += list(tr._events(line))
    busy = tr.union([(s, s + d) for _, s, d, _ in ops])
    w0, w1 = _window(pd)
    if w0 is None:
        w0 = min(s for s, _ in busy)
    if w1 is None:
        w1 = max(e for _, e in busy)

    gaps, t = [], w0
    for s, e in busy:
        if e <= w0 or s >= w1:
            continue
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < w1:
        gaps.append((t, w1))
    labels = tr._innermost(spans, [0.5 * (a + b) for a, b in gaps])
    idle_by, longest = {}, []
    for (a, b), label in zip(gaps, labels):
        label = label or tr.OUTSIDE
        idle_by[label] = idle_by.get(label, 0.0) + (b - a) * 1e-9
        longest.append(((b - a) * 1e-9, label))
    longest.sort(reverse=True)

    decode = tr.union(decode)
    scope_s = {s: 0.0 for s in SCOPES}
    op_s, op_scope = {}, {}
    named = 0.0            # decode time of operations whose op_name is known
    i = 0
    for name, s, d, _ in sorted(ops, key=lambda o: o[1]):
        if any(c in name for c in tr.CONTAINERS):
            continue
        while i < len(decode) and decode[i][1] <= s:
            i += 1
        in_decode = i < len(decode) and decode[i][0] <= s
        known = op_name(name, names) if in_decode else ""
        named += d * 1e-9 if known else 0.0
        scope = scope_of(known)
        short = tr._short(name)
        op_s[short] = op_s.get(short, 0.0) + d * 1e-9
        if scope or short not in op_scope:
            op_scope[short] = scope
        if scope:
            scope_s[scope] += d * 1e-9
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "idle_s": sum(idle_by.values()),
        "idle_by_span": idle_by,
        "host_idle_s": sum(v for k, v in idle_by.items()
                           if k in SCHED_SPANS and k not in NOT_HOST_WORK),
        "idle_gaps": [[lab, v] for v, lab in longest[:10]],
        "decode_s": sum(e - s for s, e in decode) * 1e-9,
        "scope_s": scope_s,
        "named_s": named,
        "device_ops": [[n, v, op_scope[n]] for n, v in top],
    }


def cut(pd, start_ns, stop_ns, names=None) -> dict:
    """``[start_ns, stop_ns]`` of a trace as ``trace_reduce.from_dict``
    data: the first device's ``XLA Modules`` and ``XLA Ops`` lines, the
    scheduler spans and the markers of the traced window, and under
    ``"op_names"`` the entries of ``names`` its operations use."""
    planes, used, device_seen = [], {}, False
    names = names or {}
    for plane in pd.planes:
        dev = plane.name.startswith("/device:TPU:")
        if (dev and device_seen) or not (
                dev or plane.name.startswith("/host:CPU")):
            continue
        device_seen |= dev
        lines = []
        for line in plane.lines:
            if dev and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            evs = []
            for name, s, d, _ in tr._events(line):
                marker = name.endswith(("start_trace", "stop_trace"))
                if not (dev or marker or name in SCHED_SPANS):
                    continue
                if not marker and (s + d < start_ns or s > stop_ns):
                    continue
                evs.append([name, s, d, {}])
                if op_name(name, names):
                    used[tr._instr(name)] = names[tr._instr(name)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "op_names": used}
