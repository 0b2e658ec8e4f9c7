"""Reduction of a JAX profiler trace to the intervals the metrics read.

A traced run writes an ``.xplane.pb``; :func:`load_xplane` keeps only
what the per-layer metrics need, as plain lists that JSON can hold:

    {"device": [{"plane": "/device:TPU:0",
                 "modules": [[name, start_ns, end_ns], ...],
                 "ops":     [[name, start_ns, end_ns, module], ...]}, ...],
     "host":   [[name, start_ns, end_ns], ...]}

``modules`` are the executions of compiled programs (the "XLA Modules"
line of a TPU plane), ``ops`` the operations inside them ("XLA Ops"),
named by their HLO instruction (``l2r_gemm_pallas_stacked_planes.237``;
the trace gives the whole instruction text), ``host`` the benchmark's
own ``TraceAnnotation`` spans, whose names start with ``bench.``.  Ops
nest: a ``while`` op spans the ops of its body.  Device and host events
share the profiler's clock.  The functions below take that dict; tests
feed them a small recorded one.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_MODULE_LINES = ("XLA Modules",)
_OP_LINES = ("XLA Ops",)


def load_xplane(trace_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            modules, ops = [], []
            for line in plane.lines:
                if line.name in _MODULE_LINES:
                    modules += [[e.name, e.start_ns, e.end_ns]
                                for e in line.events]
                elif line.name in _OP_LINES:
                    ops += [[instruction(e.name), e.start_ns, e.end_ns]
                            for e in line.events]
            modules.sort(key=lambda m: m[1])
            ops.sort(key=lambda o: o[1])
            _tag_ops(ops, modules)
            device.append({"plane": plane.name, "modules": modules,
                           "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.end_ns] for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    device.sort(key=lambda d: d["plane"])
    host.sort(key=lambda h: h[1])
    return {"device": device, "host": host}


def instruction(text: str) -> str:
    """``%fusion.3 = f32[8] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def module_name(name: str) -> str:
    """``jit_decode(1234)`` -> ``jit_decode``: the program's name without
    the run id the profiler appends."""
    return name.split("(", 1)[0].strip()


def op_kind(name: str) -> str:
    """``fusion.123`` -> ``fusion``: an operation's name without the
    instruction number, so repeated instructions aggregate."""
    return re.sub(r"\.\d+$", "", name)


def _tag_ops(ops: list, modules: list) -> None:
    """Append to each op the name of the module execution that encloses
    it (``""`` where none does).  Both lists are sorted by start."""
    starts = [m[1] for m in modules]
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        enclosing = ""
        if i >= 0 and modules[i][1] <= op[1] and op[2] <= modules[i][2]:
            enclosing = module_name(modules[i][0])
        op.append(enclosing)


def window(trace: dict) -> tuple[int, int]:
    """The traced window: the benchmark's ``bench.window`` span, or where
    it is missing the extent of the device events."""
    spans = [h for h in trace["host"] if h[0] == WINDOW_SPAN]
    if spans:
        return spans[0][1], spans[0][2]
    evs = [e for d in trace["device"] for e in d["ops"] + d["modules"]]
    if not evs:
        raise ValueError("trace holds no device event")
    return min(e[1] for e in evs), max(e[2] for e in evs)


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: list[list[int]] = []
    for _, s, e, *_ in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi] in which some operation ran."""
    return sum(e - s for s, e in union(ops, lo, hi))


def gaps(ops, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle intervals of [lo, hi]: no operation running."""
    out, t = [], lo
    for s, e in union(ops, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def runs(device: dict, names, lo: int, hi: int) -> list[list]:
    """Executions of the modules named in ``names`` (exact names, without
    the run id) that lie wholly inside [lo, hi]."""
    names = set(names)
    return [m for m in device["modules"]
            if module_name(m[0]) in names and lo <= m[1] and m[2] <= hi]


def ops_in_runs(device: dict, runs_: list) -> list[list]:
    """The ops that lie inside one of ``runs_`` (module executions)."""
    if not runs_:
        return []
    spans = sorted((r[1], r[2]) for r in runs_)
    starts = [s for s, _ in spans]
    out = []
    for op in device["ops"]:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[2] <= spans[i][1]:
            out.append(op)
    return out


def matching(ops, patterns) -> list:
    """The ops whose name contains one of ``patterns``."""
    return [o for o in ops if any(p in o[0] for p in patterns)]


def duration_ns(events) -> int:
    return sum(e[2] - e[1] for e in events)


def self_ns(ops, lo: int, hi: int) -> list[tuple[str, int]]:
    """(op, its time in [lo, hi] not covered by the ops nested in it),
    so that a ``while`` op does not count its body's time again."""
    out, stack = [], []  # stack: [index into out, end]
    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        s, e = max(op[1], lo), min(op[2], hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:  # nested: the enclosing op loses this span
            parent = out[stack[-1][0]]
            parent[1] -= min(e, stack[-1][1]) - s
        out.append([op, e - s])
        stack.append([len(out) - 1, e])
    return [(op, t) for op, t in out]


def _span_at(host: list, t: int) -> str:
    """The innermost benchmark span open at ``t`` (other than the window
    itself), or ``"outside any benchmark span"``."""
    best = None
    for name, s, e in host:
        if s <= t <= e and name != WINDOW_SPAN:
            if best is None or e - s < best[2] - best[1]:
                best = (name, s, e)
    return best[0] if best else "outside any benchmark span"


def breakdown(trace: dict, lo: int, hi: int, top: int = 10) -> dict:
    """The device operations that took most time (self time) on the first
    device, by ``module:op_kind``, and the longest idle gaps labelled by
    what the benchmark was doing on the host, in seconds."""
    dev = trace["device"][0]
    total: dict[str, int] = {}
    for (name, _, _, mod), t in self_ns(dev["ops"], lo, hi):
        key = f"{mod or '-'}:{op_kind(name)}"
        total[key] = total.get(key, 0) + t
    ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(dev["ops"], lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[_span_at(trace["host"], (s + e) // 2),
                           (e - s) / 1e9] for s, e in idle]}
