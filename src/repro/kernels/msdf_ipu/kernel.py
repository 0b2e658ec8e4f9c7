"""Pallas TPU kernel: PE-array simulation of the composite IPU.

The paper's accelerator is a T_r x T_c array of PEs, each streaming one
SOP through the carry-save CIPU datapath (core/ipu.py is the scalar
golden model).  This kernel runs the *cycle-accurate register-level
simulation itself* data-parallel on the vector unit: one grid cell
simulates a (bm,)-batch of PEs, the n^2-cycle loop lives in VMEM
registers (PPR/residual carry-save pairs as vectors).

Use cases: RTL-free design-space sweeps of the unit (n, k, radix) at
millions of SOPs/s, and regression oracles for the hardware team — the
outputs are bit-identical to core/ipu.py (tested).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["cipu_array_pallas"]


def _kernel(a_ref, b_ref, out_ref, *, n_bits: int, k: int):
    n = n_bits
    a = a_ref[...].astype(jnp.int32)  # (bm, k)
    b = b_ref[...].astype(jnp.int32)
    bm = a.shape[0]

    def csa(x, y, z):
        return x ^ y ^ z, ((x & y) | (x & z) | (y & z)) << 1

    def cycle(c, state):
        ppr_s, ppr_c, res_s, res_c = state
        i = c // n + 1
        j = c % n + 1
        a_bits = (a >> (n - i)) & 1
        b_bits = (b >> (n - j)) & 1
        cnt = jnp.sum(a_bits & b_bits, axis=-1)  # counter circuit, (bm,)
        wrap = j == n
        res_in_s = jnp.where(wrap, res_s << 1, 0)
        res_in_c = jnp.where(wrap, res_c << 1, 0)
        s0, c0 = csa(ppr_s << 1, ppr_c << 1, cnt)
        s1, c1 = csa(res_in_s, res_in_c, jnp.zeros_like(cnt))
        s2, c2 = csa(s0, c0, s1)
        s3, c3 = csa(s2, c1, c2)
        new_ppr_s = jnp.where(wrap, 0, s3)
        new_ppr_c = jnp.where(wrap, 0, c3)
        new_res_s = jnp.where(wrap, s3, res_s)
        new_res_c = jnp.where(wrap, c3, res_c)
        return new_ppr_s, new_ppr_c, new_res_s, new_res_c

    zeros = jnp.zeros((bm,), jnp.int32)
    state = (zeros, zeros, zeros, zeros)
    state = jax.lax.fori_loop(0, n * n, cycle, state)
    out_ref[...] = state[2] + state[3]


@functools.partial(jax.jit, static_argnames=("n_bits", "bm", "interpret"))
def cipu_array_pallas(a: jax.Array, b: jax.Array, n_bits: int = 8,
                      bm: int = 256, interpret: bool = False) -> jax.Array:
    """a, b: (M, k) unsigned operands -> (M,) exact SOPs, simulated at
    the register level.  M must divide into bm-sized PE batches (padded
    here)."""
    m, k = a.shape
    pad = (-m) % bm
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
    kernel = functools.partial(_kernel, n_bits=n_bits, k=k)
    out = pl.pallas_call(
        kernel,
        grid=((m + pad) // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                  pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((m + pad,), jnp.int32),
        interpret=interpret,
    )(a.astype(jnp.int32), b.astype(jnp.int32))
    return out[:m]
