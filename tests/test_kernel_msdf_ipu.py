"""PE-array CIPU Pallas kernel vs the scalar golden model + integer SOP."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.msdf_ipu import cipu_array_pallas, cipu_array_ref, int_sop_ref


@pytest.mark.parametrize("m,k,n_bits", [(64, 72, 8), (100, 9, 8), (256, 16, 6),
                                        (8, 72, 8)])
def test_pe_array_exact(m, k, n_bits):
    rng = np.random.default_rng(m + k)
    hi = 1 << n_bits
    a = jnp.asarray(rng.integers(0, hi, (m, k)), jnp.int32)
    b = jnp.asarray(rng.integers(0, hi, (m, k)), jnp.int32)
    out = cipu_array_pallas(a, b, n_bits, bm=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(int_sop_ref(a, b)))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(cipu_array_ref(a, b, n_bits)))
