"""Roofline time of VGG-16's conv and fc matmuls (the larger of int8
operations over the int8 peak and least bytes over HBM bandwidth, per
layer), summed over the forwards that ran wholly inside the traced
window, over the device time of the L2R kernel events inside them."""

from bench import trace
from bench.peaks import roofline_s

# the HLO instruction names of the L2R Pallas kernels in a TPU trace
# (l2r_gemm_pallas_stacked_planes.237, ...)
L2R_KERNELS = ("l2r_gemm_pallas",)


def read(rec):
    tr, w = rec.get("trace"), rec["work"]
    if not tr or "forward_module" not in w:
        return None
    lo, hi = rec["window_ns"]
    dev = tr["device"][0]
    runs = trace.runs(dev, [w["forward_module"]], lo, hi)
    kernel_s = trace.duration_ns(trace.matching(
        trace.ops_in_runs(dev, runs), L2R_KERNELS)) / 1e9
    if not runs or kernel_s <= 0:
        return None
    least = sum(roofline_s(ops, b, rec["peaks"]) for ops, b in w["gemms"])
    return 100.0 * len(runs) * least / kernel_s
