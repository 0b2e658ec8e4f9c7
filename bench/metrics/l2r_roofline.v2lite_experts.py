"""Roofline time of the held experts' GEMMs in the mean decode
execution, at the rows routed to them (from the program's counters,
spread evenly over the MoE layers and the held experts; per GEMM the
larger of int8 operations over the int8 peak and least bytes over HBM
bandwidth), over the mean device time of the experts' L2R kernel events
(``l2r_gemm_pallas_stacked_planes_experts``) inside one decode
execution, in %.  A trace whose kernels carry no such name reads
nothing."""

from bench import trace
from bench.peaks import roofline_s
from bench.reduce import step_runs

EXPERT_KERNELS = ("l2r_gemm_pallas_stacked_planes_experts",)


def read(rec):
    found = step_runs(rec, "decode")
    if found is None or "expert_gemms" not in rec["work"]:
        return None
    runs, w = found
    ops = trace.matching(trace.ops_in_runs(rec["trace"]["device"][0], runs),
                         EXPERT_KERNELS)
    kernel_s = trace.duration_ns(ops) / len(runs) / 1e9
    if kernel_s <= 0:
        return None
    work, ob = rec["work"], rec["work"]["out_bytes"]
    n_exp = work["experts_held"] * work["moe_layers"]
    rows = w["expert_rows"] / w["executions"] / n_exp  # one expert, layer
    least = n_exp * sum(
        roofline_s(2.0 * rows * k * n, rows * k + k * n + rows * n * ob,
                   rec["peaks"])
        for k, n in work["expert_gemms"])
    return 100.0 * least / kernel_s
