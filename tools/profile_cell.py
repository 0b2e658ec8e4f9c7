#!/usr/bin/env python
"""Profile one benchmark cell and put its device time on the program's
named scopes and its idle gaps on the gateway's host spans.

    python tools/profile_cell.py --workload smollm-135m-chat --seed 7 \\
        --seconds 51 [--out profile.json]

Runs the cell as ``bench/run.py --trace 1`` does (set-up, a window of
``--seconds`` with ``bench.run.TRACE_SECONDS`` profiled from its
middle), on a TPU, and reduces the trace with ``bench/trace.py`` plus
what that reduction leaves out:

* each device op's scope path, read from the ``op_name`` metadata of its
  instruction in the compiled programs (the trace names an op by its
  instruction only): ``attention/kv_cache_update``, ``conv1_1``;
* the gateway's ``gateway.*`` host spans with their thread, so that an
  idle gap is labelled by the innermost one open on the device loop's
  thread, and by the benchmark's ``bench.*`` span where none is;
* for a gateway cell, p50/p95 of each request's queue wait
  (``t_admit - t_arrival``) and first-token lag (``t_first_token -
  t_admit``) over the requests due before the profile started (stopping
  the profiler stalls the loop for seconds), a prefill group's wait
  behind the emit queue (its ``gateway.admit`` joined to its
  ``gateway.emit`` by uids), and ``stats()["decode_in_flight_mean"]``;
* the cost of tracing: the rate of the cell's progress (decode steps,
  or batches landed) inside the profiled slice and in the slices of the
  same length just before and after it; and what else slows the window:
  its longest stall between two marks, and the compiles inside it.

Tables go to standard error; everything, with the raw spans, request
stamps and decode dispatches, to the JSON file.  The reducing functions
take plain lists, so tests feed them small ones.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

PROGRAM_PREFIX = "gateway."
EMIT_SPAN = "gateway.emit"  # the only span of the gateway's emit thread
# op_name components that are structure, not a scope the program named
_STRUCTURE = {"while", "body", "cond", "closed_call", "checkpoint",
              "remat", "branch", "pallas_call"}
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"')


# ---------------------------------------------------------------- scopes
def scope_path(op_name: str, kind: str = "") -> str:
    """The named scopes of an ``op_name``, outermost first:
    ``jit(decode)/while/body/closed_call/attention/kv_cache_update/
    dynamic_update_slice`` -> ``attention/kv_cache_update``.  Drops
    transformations (``jit(..)``, ``vmap()``), loop structure, the
    primitive (the last component) and a kernel's own name ``kind``."""
    parts = op_name.split("/")[:-1]
    return "/".join(p for p in parts
                    if "(" not in p and p not in _STRUCTURE and p != kind)


def hlo_scopes(hlo_text: str) -> tuple[str, dict[str, str]]:
    """(module name, {instruction: op_name}) of a compiled module's
    text."""
    first = hlo_text.split("\n", 1)[0]
    module = first.split()[1].rstrip(",") if first.startswith("HloModule") \
        else ""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return module, out


def op_scopes(ops: list, op_names: dict[str, dict[str, str]]) -> list[str]:
    """The scope path of each of ``ops`` ([name, start, end, module]),
    from ``op_names`` = {module: {instruction: op_name}}."""
    from bench.trace import op_kind

    return [scope_path(op_names.get(mod, {}).get(name, ""), op_kind(name))
            for name, _, _, mod in ops]


def scope_table(ops: list, scopes: list[str], lo: int, hi: int,
                key=lambda mod, kind, path: (mod, path.split("/")[0])
                ) -> list[list]:
    """Device self time in [lo, hi] (seconds) by ``key(module, op kind,
    scope path)``, largest first; by module and top scope by default."""
    from bench.trace import op_kind, self_ns

    where = {id(op): s for op, s in zip(ops, scopes)}
    total: dict[tuple, int] = {}
    for op, t in self_ns(ops, lo, hi):
        k = key(op[3] or "-", op_kind(op[0]), where[id(op)])
        total[k] = total.get(k, 0) + t
    return [[*k, v / 1e9] for k, v in sorted(total.items(),
                                             key=lambda kv: -kv[1])]


# ------------------------------------------------------------ host spans
def program_spans(trace_dir: str) -> list[list]:
    """[[name, start_ns, end_ns, thread, args], ...] of the ``gateway.*``
    host spans of the newest ``.xplane.pb`` under ``trace_dir``, sorted
    by start; ``thread`` tells the host threads apart (the profiler names
    every Python thread alike), ``args`` holds the span's arguments."""
    import glob

    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [[e.name, e.start_ns, e.end_ns, f"{line.name}#{i}",
                     {k: str(v) for k, v in e.stats}]
                    for e in line.events if e.name.startswith(PROGRAM_PREFIX)]
    return sorted(out, key=lambda p: p[1])


def _innermost(spans, t: int) -> str | None:
    best = None
    for name, s, e, *_ in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else None


def label_gaps(trace: dict, program: list, lo: int, hi: int,
               top: int = 10) -> list[list]:
    """The ``top`` longest idle gaps of the first device in [lo, hi], as
    [label, seconds]: the innermost ``gateway.*`` span open at the gap's
    midpoint on the device loop's thread (the threads of every span but
    ``gateway.emit``), else ``bench.trace.breakdown``'s label."""
    from bench.trace import WINDOW_SPAN, gaps

    loop = {p[3] for p in program if p[0] != EMIT_SPAN}
    mine = [p for p in program if p[3] in loop]
    bench = [h for h in trace["host"] if h[0] != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps(trace["device"][0]["ops"], lo, hi),
                       key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) // 2
        out.append([_innermost(mine, mid) or _innermost(bench, mid)
                    or "outside any benchmark span", (e - s) / 1e9])
    return out


def prefill_lag_parts(program: list) -> dict:
    """Where a traced prefill group's first-token lag goes, joined by its
    uids from ``gateway.admit`` to its ``gateway.emit``: p50/p95 (ms) of
    the wait for the emit thread (admit's end to emit's start, behind
    the items dispatched before it) and of the emit itself (mostly the
    wait for the device's result)."""
    from bench.reduce import nearest_rank

    admits = {p[4].get("uids"): p for p in program
              if p[0] == "gateway.admit"}
    queued, emit = [], []
    for p in program:
        a = admits.get(p[4].get("uids")) if p[0] == EMIT_SPAN else None
        if a is not None and p[4].get("kind") == "prefill":
            queued.append((p[1] - a[2]) / 1e6)
            emit.append((p[2] - p[1]) / 1e6)
    out = {"groups": len(emit)}
    for name, xs in (("emit_queue", queued), ("emit", emit)):
        if xs:
            out[f"{name}_p50_ms"] = nearest_rank(xs, 50)
            out[f"{name}_p95_ms"] = nearest_rank(xs, 95)
    return out


# ----------------------------------------------------------- the gateway
def request_split(reqs) -> dict:
    """p50/p95 (nearest rank, ms) of queue wait and first-token lag over
    the admitted ones of ``reqs``."""
    from bench.reduce import nearest_rank

    adm = [r for r in reqs if getattr(r, "t_admit", None) is not None]
    first = [r for r in adm if r.t_first_token is not None]
    out = {"requests": len(reqs), "admitted": len(adm)}
    for name, xs in (
            ("queue_wait", [r.t_admit - r.t_arrival for r in adm]),
            ("first_token_lag", [r.t_first_token - r.t_admit for r in first]),
            ("ttft", [r.t_first_token - r.t_arrival for r in first])):
        if xs:
            out[f"{name}_p50_ms"] = 1e3 * nearest_rank(xs, 50)
            out[f"{name}_p95_ms"] = 1e3 * nearest_rank(xs, 95)
    return out


def rates(marks: list, t0: float, t1: float) -> dict:
    """Progress per second inside [t0, t1] and in the slices of the same
    length before and after it, from ``marks`` = [(time, count, ...),
    ...]."""
    def count_at(t):
        done = [m[1] for m in marks if m[0] <= t]
        return done[-1] if done else marks[0][1]

    d = t1 - t0
    out = {}
    for name, a in (("before", t0 - d), ("traced", t0), ("after", t1)):
        if marks[0][0] <= a and a + d <= marks[-1][0]:
            out[name] = (count_at(a + d) - count_at(a)) / d
    return out


# --------------------------------------------------------------- the run
def _executable_texts(sut) -> list[str]:
    """The compiled programs the cell's timed path runs."""
    import jax

    if hasattr(sut, "gw"):  # bench/systems/gateway.py
        exes = [sut.gw._decode_exe, *sut.gw._prefill_exe.values()]
        return [e.as_text() for e in exes if e is not None]
    return [sut.fwd.lower(sut.w, jax.device_put(sut.pool[0]),
                          sut.wq).compile().as_text()]


def profile(cell, seed: int, seconds: float) -> dict:
    """Set up ``cell`` (a ``bench.run.Cell``), run its window with the
    middle profiled, and reduce the trace."""
    import jax

    from bench import trace as tr
    from bench.run import Tracer

    sut = cell.system.System(cell.config, cell.mix, seed, cell.model)
    sut.setup()

    # progress marks: every decode dispatch of a gateway, else every
    # boundary the system ticks (a landed batch)
    marks = []
    gw = getattr(sut, "gw", None)
    if gw is not None:
        dispatch = gw._decode_step

        def marked_decode_step():
            in_flight = gw.steps - gw._decodes_landed
            dispatch()
            marks.append((time.perf_counter(), gw.steps, in_flight))

        gw._decode_step = marked_decode_step
    compiles = []  # (time, event, seconds) of the compiles in the window
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(
            (time.perf_counter(), event, secs)) if "compile" in event
        else None)

    class Marking(Tracer):
        def tick(self):
            if gw is None:
                marks.append((time.perf_counter(), len(marks)))
            super().tick()

    tracer = Marking(True)
    try:
        counts = sut.window(seconds, tracer)
        tracer.stop()
        trace = tr.load_xplane(tracer.dir)
        program = program_spans(tracer.dir)
    finally:
        tracer.cleanup()
    lo, hi = tr.window(trace)
    names = dict(hlo_scopes(t) for t in _executable_texts(sut))
    dev = trace["device"][0]
    scopes = op_scopes(dev["ops"], names)
    out = {"workload": cell.name, "seed": seed,
           "device": jax.devices()[0].device_kind,
           "window_s": (hi - lo) / 1e9,
           "busy_s": tr.busy_ns(dev["ops"], lo, hi) / 1e9,
           "counts": {k: v for k, v in counts.items()
                      if not isinstance(v, list)},
           "by_scope": scope_table(dev["ops"], scopes, lo, hi),
           "by_op_and_scope": scope_table(
               dev["ops"], scopes, lo, hi,
               key=lambda mod, kind, path: (mod, kind, path))[:40],
           "idle_gaps": label_gaps(trace, program, lo, hi),
           "program_spans": [[n, (a - lo) / 1e6, (b - lo) / 1e6, th, args]
                             for n, a, b, th, args in program],
           "prefill_lag_parts": prefill_lag_parts(program),
           "rates_per_s": rates(marks, tracer.t0, tracer.t1),
           "longest_stall_s": max(
               ([b[0] - a[0], a[0] - tracer.t_arm]
                for a, b in zip(marks, marks[1:])), default=None),
           "compiles_in_window": [[t - tracer.t_arm, ev, secs]
                                  for t, ev, secs in compiles
                                  if t >= tracer.t_arm],
           "profile_ms": [1e3 * (tracer.t0 - tracer.t_arm),
                          1e3 * (tracer.t1 - tracer.t_arm)],
           "marks_ms": [[1e3 * (m[0] - tracer.t_arm), *m[1:]]
                        for m in marks]}
    if gw is not None:
        out["decode_in_flight_mean"] = gw.stats(
            latency=False)["decode_in_flight_mean"]
        # the profiler's stop stalls the loop for seconds: the requests
        # due before the profile started show the split without it
        out["requests"] = request_split(
            [r for r in sut.touched if r.t_arrival < tracer.t0])
        out["requests_all"] = request_split(sut.touched)
        out["stamps_ms"] = [
            [r.uid] + [None if t is None else 1e3 * (t - tracer.t_arm)
                       for t in (r.t_arrival, getattr(r, "t_admit", None),
                                 r.t_first_token, r.t_complete)]
            for r in sut.touched]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None, help="JSON file to write")
    args = ap.parse_args(argv)

    from bench.run import ROOT, Cell, check_device, enable_compile_cache

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = Cell(json.load(f), args.workload)
    _, err = check_device(cell.spec["chips"])
    if err:
        print(f"profile_cell: {err}", file=sys.stderr)
        return 3
    enable_compile_cache()
    out = profile(cell, args.seed, args.seconds)
    for k in ("by_scope", "idle_gaps"):
        print(f"== {k}", file=sys.stderr)
        for row in out[k][:25]:
            print("  ".join(str(x) for x in row), file=sys.stderr)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    small = {k: v for k, v in out.items()
             if k not in ("by_scope", "by_op_and_scope", "idle_gaps",
                          "program_spans", "stamps_ms", "marks_ms")}
    print(json.dumps(small), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
