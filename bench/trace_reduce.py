"""Reduce a profiler trace (``.xplane.pb``) of a traced window to what the
per-layer metrics read.

  * busy intervals: the union of the intervals in which an operation ran on
    a device (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), and
    ``busy_s`` averaged over the devices;
  * ``window_s``: from the end of ``start_trace`` to the start of
    ``stop_trace`` on the host, as the trace records them;
  * per-program device time (line ``XLA Modules``), per-operation and
    per-kernel device time; a Pallas kernel is a ``tpu_custom_call``
    operation, named by its HLO instruction, which takes the name of the
    jitted function around the ``pallas_call`` (``KERNELS`` lists the
    program's);
  * idle gaps (window minus busy), each labelled with the program's host
    span around its midpoint (``prefill_chunk``, ``decode_chunk``,
    ``train_step``), else with the innermost function of the program that
    the profiler's Python tracer shows running (``fn <file>:<line> <name>``),
    else ``outside any span``.

Only JAX's own reader (``jax.profiler.ProfileData``) is used.
"""

from __future__ import annotations

import heapq
import re
from pathlib import Path

# The program's Pallas kernels by the instruction name a trace shows them
# under (the jitted function around each ``pallas_call``).
KERNELS = {
    "paged_attention_kernel": "paged decode (serve/paged_attention.py)",
    "paged_prefill_attention_kernel": "paged packed prefill (same file)",
    "mosa_attention_pallas": "MoSA forward (kernels/mosa_attention.py)",
    "mosa_attention_fwd_res": "MoSA forward with residuals (same file)",
    "mosa_attention_bwd_pallas": "MoSA dq and dkv (kernels/mosa_backward.py)",
    "flash_attention_pallas": "flash (kernels/flash_attention.py)",
    "flash_attention_varlen_pallas": "flash varlen (same file)",
}
CONTAINERS = (" while(", " conditional(", " call(")
HOST_SPANS = ("prefill_chunk", "decode_chunk", "train_step")
OUTSIDE = "outside any span"


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns), e


def _instr(name: str) -> str:
    """``%fusion.342 = bf16[...] fusion(...)`` -> ``fusion.342``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def _kernel_of(name: str):
    """The base instruction name of a Pallas kernel call, else None."""
    if "tpu_custom_call" not in name:
        return None
    return re.sub(r"\.\d+$", "", _instr(name))


def _short(name: str) -> str:
    """An operation's instruction name and result type, for a breakdown."""
    if " = " not in name:
        return name[:120]
    rest = name.split(" = ", 1)[1]
    return f"{_instr(name)} {rest.split(' ', 1)[0][:80]}"


def _program_name(name: str) -> str:
    """``jit__prefill_packed(123)`` -> ``jit__prefill_packed``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost(intervals, points):
    """For each of ``points`` (ascending), the name of the shortest of
    ``intervals`` ``(start, end, name)`` that holds it, else None: one
    sweep, not a search per point."""
    order = sorted(intervals)
    active = []                       # heap of (end, length, name)
    out = []
    i = 0
    for p in points:
        while i < len(order) and order[i][0] <= p:
            s, e, name = order[i]
            heapq.heappush(active, (e, e - s, name))
            i += 1
        while active and active[0][0] < p:
            heapq.heappop(active)
        best = min(((d, n) for _, d, n in active), default=None)
        out.append(best[1] if best else None)
    return out


def reduce_profile(pd, program_files=frozenset()) -> dict:
    """``program_files``: base names of the program's source files, whose
    functions may label an idle gap."""
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            devices.append(plane)
        elif plane.name.startswith("/host:CPU"):
            host.append(plane)
    if not devices:
        raise ValueError("trace holds no TPU device plane")

    starts, stops, spans, funcs = [], [], [], []
    for plane in host:
        for line in plane.lines:
            for name, s, d, _ in _events(line):
                if name.endswith("start_trace"):
                    starts.append(s + d)
                elif name.endswith("stop_trace"):
                    stops.append(s)
                elif name in HOST_SPANS:
                    spans.append((s, s + d, name))
                elif name.startswith("$") and \
                        name[1:].split(":", 1)[0] in program_files:
                    funcs.append((s, s + d, "fn " + name[1:]))

    busy_total = 0.0
    programs, runs, ops, kernels = {}, {}, {}, {}
    kernel_calls = {}
    first_busy = None
    all_busy = []
    ext = []
    for plane in devices:
        iv = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for name, s, d, _ in _events(line):
                    p = _program_name(name)
                    programs[p] = programs.get(p, 0.0) + d * 1e-9
                    runs[p] = runs.get(p, 0) + 1
                    ext.append((s, s + d))
            elif line.name == "XLA Ops":
                for name, s, d, e in _events(line):
                    iv.append((s, s + d))
                    if not any(c in name for c in CONTAINERS):
                        op = _short(name)
                        ops[op] = ops.get(op, 0.0) + d * 1e-9
                    k = _kernel_of(name)
                    if k is not None:
                        kernels[k] = kernels.get(k, 0.0) + d * 1e-9
                        kernel_calls[k] = kernel_calls.get(k, 0) + 1
        u = union(iv)
        if first_busy is None:
            first_busy = u
        all_busy.append(u)
    ext_iv = [x for u in all_busy for x in u] + ext
    w0 = max(starts) if starts else min(s for s, _ in ext_iv)
    w1 = min(stops) if stops else max(e for _, e in ext_iv)
    window_ns = max(w1 - w0, 1.0)
    for u in all_busy:
        busy_total += sum(max(0.0, min(e, w1) - max(s, w0)) for s, e in u)
    busy_s = busy_total / len(devices) * 1e-9

    # idle gaps of the first device, labelled by the host span around them
    gaps = []
    t = w0
    for s, e in first_busy or []:
        if e <= w0 or s >= w1:
            continue
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < w1:
        gaps.append((t, w1))
    idle_by = {}
    longest = []
    mids = [0.5 * (a + b) for a, b in gaps]
    for (a, b), span, fn in zip(gaps, _innermost(spans, mids),
                                _innermost(funcs, mids)):
        label = span or fn or OUTSIDE
        idle_by[label] = idle_by.get(label, 0.0) + (b - a) * 1e-9
        longest.append(((b - a) * 1e-9, label))
    longest.sort(reverse=True)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_s,
        "window_s": window_ns * 1e-9,
        "programs": programs,
        "program_runs": runs,
        "kernels": kernels,
        "kernel_calls": kernel_calls,
        "idle_by_span": idle_by,
        "breakdown": {
            "device_ops": [[n, v] for n, v in top_ops],
            "idle_gaps": [[f"{lab} (gap {i + 1})", v]
                          for i, (v, lab) in enumerate(longest[:10])],
        },
    }


def find_xplane(directory) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise ValueError(f"no .xplane.pb under {directory}")
    return found[-1]


def program_files() -> frozenset:
    """Base names of the program's source files (``src/repro``), less those
    that JAX's own modules share (the Python tracer shows base names)."""
    import jax
    import repro
    ours = {p.name for d in repro.__path__ for p in Path(d).rglob("*.py")}
    theirs = {p.name for p in Path(jax.__file__).parent.rglob("*.py")}
    return frozenset(ours - theirs)


def reduce_file(path) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)), program_files())


def reduce_dir(directory) -> dict:
    return reduce_file(find_xplane(directory))


class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def to_dict(pd, start_ns=None, stop_ns=None, max_events=None,
            files=frozenset()) -> dict:
    """The parts of a trace the reduction reads, as plain data: device
    planes' ``XLA Modules`` and ``XLA Ops`` lines, and the host events that
    mark the traced window and the program's spans, cut to
    ``[start_ns, stop_ns]`` (for keeping a small recorded trace)."""
    planes = []
    for plane in pd.planes:
        dev = plane.name.startswith("/device:TPU:")
        if not dev and not plane.name.startswith("/host:CPU"):
            continue
        lines = []
        for line in plane.lines:
            if dev and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            evs = []
            for name, s, d, e in _events(line):
                marker = name.endswith(("start_trace", "stop_trace"))
                keep = dev or marker or name in HOST_SPANS or (
                    name.startswith("$") and
                    name[1:].split(":", 1)[0] in files)
                if not keep:
                    continue
                if not marker and start_ns is not None and (
                        s + d < start_ns or s > stop_ns):
                    continue
                evs.append([name, s, d, {}])
                if max_events and len(evs) >= max_events:
                    break
            if evs:
                lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def from_dict(d: dict):
    """``to_dict``'s data as objects ``reduce_profile`` reads."""
    planes = []
    for p in d["planes"]:
        lines = []
        for ln in p["lines"]:
            evs = [_Obj(name=n, start_ns=s, duration_ns=du,
                        stats=list(st.items()))
                   for n, s, du, st in ln["events"]]
            lines.append(_Obj(name=ln["name"], events=evs))
        planes.append(_Obj(name=p["name"], lines=lines))
    return _Obj(planes=planes)
