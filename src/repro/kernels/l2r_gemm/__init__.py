"""L2R digit-plane GEMM: Pallas TPU kernels + backend dispatch + oracles."""
from .kernel import (l2r_gemm_pallas, l2r_gemm_pallas_stacked,
                     l2r_gemm_pallas_stacked_planes,
                     l2r_gemm_pallas_streaming,
                     l2r_gemm_pallas_streaming_planes, stacked_schedule,
                     stacked_tiles, streaming_schedule)
from .ops import (BACKENDS, BACKEND_ENV_VAR, SCHEDULES, PlaneOperands,
                  l2r_conv2d, l2r_conv2d_int, l2r_conv2d_progressive,
                  l2r_conv2d_progressive_while, l2r_gemm,
                  l2r_gemm_progressive, l2r_matmul_f, pad_to,
                  resolve_backend)
from .ref import int_gemm_ref, l2r_gemm_ref, l2r_gemm_ref_stacked

__all__ = [
    "l2r_gemm_pallas", "l2r_gemm_pallas_stacked",
    "l2r_gemm_pallas_stacked_planes", "l2r_gemm_pallas_streaming",
    "l2r_gemm_pallas_streaming_planes",
    "stacked_schedule", "stacked_tiles", "streaming_schedule",
    "PlaneOperands",
    "l2r_gemm", "l2r_gemm_progressive", "l2r_matmul_f", "l2r_conv2d",
    "l2r_conv2d_int", "l2r_conv2d_progressive",
    "l2r_conv2d_progressive_while", "pad_to",
    "resolve_backend", "BACKENDS", "BACKEND_ENV_VAR", "SCHEDULES",
    "l2r_gemm_ref", "l2r_gemm_ref_stacked", "int_gemm_ref",
]
