"""Benchmark entry: one run of one cell, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's
root: the cell's configuration file (``bench/configs/<config>.json``,
whose ``system`` names ``bench/systems/<system>.py`` and whose plain
reference is ``bench/configs/<config>.py``), its traffic mix
(``bench/traffic/<mix>.json``) and one reader per metric
(``bench/metrics/<metric>.py``, ``read(record) -> float | None``).

A run sets up (weights from the seed on the device, the program's
executables from the compile cache, every shape of the cell warmed up),
measures for ``--seconds``, then checks what the timed path produced
against the plain reference.  ``--trace 1`` profiles a few seconds
from the middle of the window and reports the per-layer metrics instead of the
end-to-end ones.  The last line of standard output is the result:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown"], "checks"}

and the last lines of standard error repeat each compared number beside
its limit.  Without a TPU, or with fewer chips than the cell asks for,
the run prints no result and exits 3.

``--sweep R1,R2,...`` (open-loop cells) serves the cell's traffic at each
rate in turn in one process and prints one line per rate: how the knee
of the cell's configuration is found.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

# the compiled programs go to one fixed directory inside the checkout
# (the path is part of the cache key), unless the environment names one
CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
    ROOT, ".jax_cache")
TRACE_SECONDS = 4.0  # profiled part of a --trace 1 window


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, bench: dict, name: str, bench_dir: str = BENCH_DIR,
                 config: dict | None = None, mix: dict | None = None):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.spec = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        entry = configs[self.spec["config"]]
        if config is None:
            with open(os.path.join(os.path.dirname(bench_dir),
                                   entry["file"])) as f:
                config = json.load(f)
        self.config = config
        self.model = load_module(
            os.path.join(bench_dir, "configs", entry["name"] + ".py"),
            "bench_model_" + entry["name"].replace("-", "_"))
        from bench import traffic

        self.mix = mix or traffic.load_mix(self.spec["traffic"],
                                           os.path.join(bench_dir, "traffic"))
        self.system = load_module(
            os.path.join(bench_dir, "systems", self.config["system"] + ".py"),
            "bench_system_" + self.config["system"])

        def mine(m):
            return name in m.get("workloads", [name])

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [])]
        self.readers = {m["name"]: load_module(
            os.path.join(bench_dir, "metrics", m["name"] + ".py"),
            "bench_metric_" + m["name"].replace(".", "_"))
            for m in self.end_to_end + self.per_layer}


class Tracer:
    """Profiles ``seconds`` from the middle of the window when enabled.
    The system calls :meth:`start` as the window opens and :meth:`tick`
    at its own boundaries; the profiler starts at the first tick past
    the middle less half of ``seconds`` and stops at the first tick
    ``seconds`` later, or at :meth:`stop`."""

    def __init__(self, enabled: bool, seconds: float = TRACE_SECONDS):
        self.enabled, self.seconds = enabled, seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if enabled \
            else None
        self.t_arm = self.t0 = self.t1 = None
        self.delay = 0.0
        self._span = None

    def start(self, window_s: float):
        if self.enabled and self.t_arm is None:
            self.t_arm = time.perf_counter()
            self.delay = max(0.0, (window_s - self.seconds) / 2)
            self.tick()

    def tick(self):
        if self.t_arm is None or self.t1 is not None:
            return
        now = time.perf_counter()
        if self.t0 is None and now - self.t_arm >= self.delay:
            import jax

            jax.profiler.start_trace(self.dir)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.t0 = time.perf_counter()
        elif self.t0 is not None and now - self.t0 >= self.seconds:
            self.stop()

    def stop(self):
        if self.t0 is None or self.t1 is not None:
            return
        import jax

        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def span(self, name: str):
        """A host span of the benchmark's own, on the trace's clock."""
        import contextlib

        if self.t0 is None or self.t1 is not None:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)

    def cleanup(self):
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def fail(msg: str, code: int = 3) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def check_device(chips: int):
    """(devices, peaks) of this machine, or an error message."""
    import jax

    from bench.peaks import peaks_for

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, f"needs a TPU, but JAX found {devices[0].platform!r}"
    if len(devices) < chips:
        return None, f"the cell needs {chips} chips, JAX found {len(devices)}"
    try:
        return (devices, peaks_for(devices[0].device_kind)), None
    except KeyError as e:
        return None, str(e)


def enable_compile_cache():
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Counts backend compiles, so that a compile in the window shows."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices=None, peaks: dict | None = None,
             t_start: float = T_START) -> dict:
    """Set up, measure and check one run; returns the result record.
    ``devices``/``peaks`` are None only where a test drives the run on
    the CPU."""
    import jax

    from bench import trace as tr

    compiles = CompileCounter()
    sut = cell.system.System(cell.config, cell.mix, seed, cell.model)
    sut.setup()
    setup_s = time.perf_counter() - t_start
    tracer = Tracer(trace)
    n_compiles = compiles.n
    try:
        counts = sut.window(seconds, tracer)
        tracer.stop()
        counts["compiles_in_window"] = compiles.n - n_compiles
        scalars = {k: v for k, v in counts.items()
                   if not isinstance(v, list)}
        print(f"bench: set-up {setup_s!r} s, window {scalars}",
              file=sys.stderr)
        dev = (devices or jax.devices())[0]
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        checks = sut.check()
        rec = {"counts": counts, "work": sut.work(), "peaks": peaks,
               "setup_s": setup_s, "seconds": seconds}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices or jax.devices()),
                  "memory_peak_bytes": int(mem)}
        out_breakdown = None
        if trace:
            rec["trace"] = tr.load_xplane(tracer.dir)
            lo, hi = tr.window(rec["trace"])
            rec["window_ns"] = (lo, hi)
            busy = [tr.busy_ns(d["ops"], lo, hi) for d in rec["trace"]["device"]]
            device["busy_s"] = sum(busy) / max(len(busy), 1) / 1e9
            device["window_s"] = (hi - lo) / 1e9
            out_breakdown = tr.breakdown(rec["trace"], lo, hi)
        wanted = cell.per_layer if trace else cell.end_to_end
        metrics = {}
        for m in wanted:
            v = cell.readers[m["name"]].read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        missing = [m["name"] for m in cell.end_to_end
                   if not trace and m["name"] not in metrics]
        if missing:
            raise RuntimeError(f"end-to-end metrics not measured: {missing}")
    finally:
        tracer.cleanup()
    out = {"correct": all(c["ok"] for c in checks),
           "attempted": counts["attempted"], "failed": counts["failed"],
           "metrics": metrics, "device": device}
    if out_breakdown is not None:
        out["breakdown"] = out_breakdown
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated request rates (open-loop cells)")
    args = ap.parse_args(argv)

    path = os.path.join(ROOT, "BENCHMARK.json")
    try:
        with open(path) as f:
            bench = json.load(f)
        cell = Cell(bench, args.workload)
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot load the cell: {e!r}", 2)
    ok, err = check_device(cell.spec["chips"])
    if err:
        return fail(err)
    devices, peaks = ok
    enable_compile_cache()
    if args.sweep:
        rates = [float(r) for r in args.sweep.split(",")]
        sut = cell.system.System(cell.config, cell.mix, args.seed,
                                 cell.model)
        sut.setup()
        for rate in rates:
            print(json.dumps(sut.sweep_point(rate, args.seconds)), flush=True)
        return 0
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices, peaks)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
