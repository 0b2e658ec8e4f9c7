"""Jit wrapper for the PE-array CIPU simulator."""

from .kernel import cipu_array_pallas
from .ref import cipu_array_ref, int_sop_ref

__all__ = ["simulate_pe_array", "cipu_array_ref", "int_sop_ref"]


def simulate_pe_array(a, b, n_bits: int = 8, use_pallas: bool = True,
                      interpret: bool = False):
    """Simulate M independent CIPU PEs.  a, b: (M, k) unsigned."""
    if not use_pallas:
        return cipu_array_ref(a, b, n_bits)
    return cipu_array_pallas(a, b, n_bits, interpret=interpret)
