"""Pallas TPU kernels (compiled by default; ``interpret=True`` runs them on
the CPU, and tests/test_tpu_compile.py compiles them for a described v5e):

  l2r_gemm        — MSDF digit-plane int8 GEMM (the composite IPU on the
                    MXU; the paper's primary compute hot-spot);
  flash_attention — roofline-driven beyond-paper kernel (score blocks in
                    VMEM; §Perf hillclimb A);
  msdf_ipu        — register-level PE-array simulation of the CIPU
                    (design-space sweeps + hardware regression oracle).
"""
