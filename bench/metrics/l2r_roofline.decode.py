"""Roofline time of the layers' weight matmuls of the mean decode
execution, at the rows it carried (the larger of int8 operations over
the int8 peak and least bytes over HBM bandwidth, per matmul), over the
mean device time of the L2R kernel events inside one decode execution."""

from bench.reduce import step_l2r_roofline

# the HLO instruction names of the L2R Pallas kernels in a TPU trace
# (l2r_gemm_pallas_stacked_planes.237, ...)
L2R_KERNELS = ("l2r_gemm_pallas",)


def read(rec):
    return step_l2r_roofline(rec, "decode", L2R_KERNELS)
