"""Reductions of a run's record to metric values: percentiles, and the
shares of the chip's peaks from the trace and the system's work counts.

The work is the model's own (multiply-adds of its shapes, least bytes of
its matmuls), never what an implementation computes (plane products,
padding), so a change of radix, tiling or kernel leaves it true.  The
time is the device's, from the trace.
"""

from __future__ import annotations

import math

from bench import trace
from bench.peaks import roofline_s


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank: a value that occurred."""
    xs = sorted(values)
    return xs[max(0, math.ceil(q / 100 * len(xs)) - 1)]


def step_runs(rec: dict, step: str):
    """(the executions of ``step``'s module in the traced window, its
    work record), or None where there is nothing to read."""
    tr, work = rec.get("trace"), rec.get("work", {}).get(step)
    if not tr or not work or not work.get("executions"):
        return None
    lo, hi = rec["window_ns"]
    runs = trace.runs(tr["device"][0], [work["module"]], lo, hi)
    return (runs, work) if runs else None


def step_mfu(rec: dict, step: str) -> float | None:
    """Least compute time of the mean execution's model work (int8 work
    at the int8 peak, float work at the bf16 peak) over the mean device
    time of one execution, in %."""
    found = step_runs(rec, step)
    if found is None:
        return None
    runs, w = found
    p, n = rec["peaks"], w["executions"]
    least = (w["int8_ops"] / n / p["int8_ops_per_s"]
             + w["bf16_flops"] / n / p["bf16_flops_per_s"])
    return 100.0 * least / (trace.duration_ns(runs) / len(runs) / 1e9)


def step_l2r_roofline(rec: dict, step: str, kernels) -> float | None:
    """Roofline time of the mean execution's layer matmuls, at the rows
    it carried, over the mean device time of the L2R kernel events
    inside one execution, in %."""
    found = step_runs(rec, step)
    if found is None:
        return None
    runs, w = found
    ops = trace.matching(trace.ops_in_runs(rec["trace"]["device"][0], runs),
                         kernels)
    kernel_s = trace.duration_ns(ops) / len(runs) / 1e9
    if kernel_s <= 0:
        return None
    rows = w["rows"] / w["executions"]
    ob = rec["work"]["out_bytes"]
    least = sum(roofline_s(2.0 * rows * k * n, rows * k + k * n + rows * n * ob,
                           rec["peaks"])
                for k, n in rec["work"]["layer_gemms"])
    return 100.0 * least / kernel_s


def idle_share(rec: dict) -> float | None:
    """Share of the traced window in which no operation ran, in %."""
    tr = rec.get("trace")
    if not tr or not tr["device"]:
        return None
    lo, hi = rec["window_ns"]
    busy = trace.busy_ns(tr["device"][0]["ops"], lo, hi)
    return 100.0 * (1.0 - busy / (hi - lo))
