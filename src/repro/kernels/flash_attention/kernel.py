"""Pallas TPU kernel: flash attention (online-softmax, VMEM-resident).

Motivated directly by the baseline roofline (EXPERIMENTS.md §Roofline):
every long-sequence cell is dominated by HBM traffic of materialized
attention score blocks (e.g. phi3 prefill_32k: 44.8 s memory term vs
7.7 s compute).  Keeping the (bq, bkv) score tile in VMEM with online
max/denominator carries — the same carry-free-accumulate discipline as
the paper's PPR/residual registers, one level up the hierarchy — removes
that traffic entirely: HBM touches only Q, K, V, O.

Grid: (batch*q_heads, n_q_blocks, n_kv_blocks), KV innermost so the
(acc, m, l) scratch carries across KV iterations.  GQA is handled in the
index map (kv head = q head // group); causal/window blocks outside the
band are predicated off with pl.when (no MXU work on TPU).

VMEM at (bq, bkv, dh) = (512, 512, 128): q/k/v tiles 128+128+128 KiB,
f32 score tile 1 MiB, acc 256 KiB — ~1.7 MiB << 16 MiB.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.online import msdf_level_slices
from repro.core.quant import (QuantConfig, plane_count, stack_planes_lhs,
                              stack_planes_rhs)

__all__ = ["flash_attention_pallas", "flash_attention_l2r_pallas"]

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, bq, bkv, n_kv, causal, window, scale, kv_len):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    kv_start = kj * bkv
    # static-shape mask positions
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)

    # band test: does this (q, kv) block intersect the visible region?
    live = kv_start < kv_len
    if causal:
        live &= kv_start <= q_start + bq - 1
    if window is not None:
        live &= kv_start + bkv > q_start - window + 1

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, dh)
        k = k_ref[0].astype(jnp.float32)  # (bkv, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = kv_pos < kv_len
        if causal:
            mask &= kv_pos <= q_pos
        if window is not None:
            mask &= kv_pos > q_pos - window
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(kj == n_kv - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "bq", "bkv", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, Sq, H, dh)
    k: jax.Array,  # (B, Skv, Kv, dh)
    v: jax.Array,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    bq: int = 512,
    bkv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, dh = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    bq = min(bq, sq)
    bkv = min(bkv, skv)

    # pad sequence dims to block multiples (masked out in-kernel)
    pq = (-sq) % bq
    pkv = (-skv) % bkv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))

    # (B, S, H, dh) -> (B*H, S, dh) program-major layout
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq + pq, dh)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv + pkv, dh)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv + pkv, dh)

    n_q = (sq + pq) // bq
    n_kv = (skv + pkv) // bkv

    kernel = functools.partial(
        _kernel, bq=bq, bkv=bkv, n_kv=n_kv, causal=causal, window=window,
        scale=scale, kv_len=skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, qi, kj: (bh, qi, 0)),
            # GQA: kv head = q head // g
            pl.BlockSpec((1, bkv, dh),
                         lambda bh, qi, kj, g=g, kvh=kvh:
                         ((bh // g // kvh) * kvh + (bh // g) % kvh, kj, 0)),
            pl.BlockSpec((1, bkv, dh),
                         lambda bh, qi, kj, g=g, kvh=kvh:
                         ((bh // g // kvh) * kvh + (bh // g) % kvh, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda bh, qi, kj: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq + pq, dh), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out.reshape(b, h, sq + pq, dh).transpose(0, 2, 1, 3)
    return out[:, :sq]


# -------------------------------------------------- flash-fused L2R scores
def _l2r_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref,
                acc_ref, m_ref, l_ref,
                *, bq, bkv, n_kv, causal, window, scale, kv_len,
                slices, dh):
    """Flash attention with the MSDF level walk fused into the score tile.

    Identical online-softmax structure to :func:`_kernel`; the one change
    is the score dot: instead of a float QK^T pass, the (bq, bkv) tile is
    accumulated by a STATIC walk over significance levels — each level
    one int MXU pass over a contiguous plane-slice pair of the
    pre-shifted stacks (the level-stacked schedule of
    kernels/l2r_gemm, nested inside the KV-block walk).  ``slices`` is
    the host-enumerated ``msdf_level_slices`` prefix, so a truncated
    ``levels`` processes exactly the MSDF pair set of the truncated
    stacked schedule while the softmax/PV stream stays float — the
    progressive score prefix rides inside the flash fusion instead of
    materializing (L, Q, S) snapshots in HBM.
    """
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    kv_start = kj * bkv
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)

    live = kv_start < kv_len
    if causal:
        live &= kv_start <= q_start + bq - 1
    if window is not None:
        live &= kv_start + bkv > q_start - window + 1

    @pl.when(live)
    def _compute():
        qst = q_ref[0]  # (bq, D*dh) ascending pre-shifted planes
        kst = k_ref[0]  # (bkv, D*dh) descending pre-shifted planes
        d = qst.shape[-1] // dh  # plane count implicit in the stack width
        s_int = jnp.zeros((bq, bkv), jnp.int32)
        for (lvl, i_lo, i_hi) in slices:
            a_l = qst[:, i_lo * dh:(i_hi + 1) * dh]
            r0 = (d - 1 - lvl + i_lo) * dh
            b_l = kst[:, r0:r0 + (i_hi - i_lo + 1) * dh]
            # pre-shifted planes are bit-fields of the int operand: every
            # product already carries its final significance — one int
            # pass per level, no shifts (same body as the stacked GEMM)
            s_int += jax.lax.dot_general(
                a_l, b_l, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
        # per-query-row x per-key-slot dequantization, then the usual
        # softmax scale — scales commute with the head-dim contraction
        s = (s_int.astype(jnp.float32) * qs_ref[0]
             * ks_ref[0].reshape(1, bkv) * scale)
        mask = kv_pos < kv_len
        if causal:
            mask &= kv_pos <= q_pos
        if window is not None:
            mask &= kv_pos > q_pos - window
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(kj == n_kv - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "log2_radix", "levels", "causal", "window",
                     "scale", "bq", "bkv", "interpret"),
)
def flash_attention_l2r_pallas(
    q: jax.Array,  # (B, Sq, H, dh)
    k: jax.Array,  # (B, Skv, Kv, dh)
    v: jax.Array,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    bq: int = 256,
    bkv: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention whose QK^T is the digit-serial level walk.

    The streaming-level-walk fusion: q and k are quantized per vector
    (one scale per query row / key slot — the scales that commute with
    the head-dim contraction AND with KV blocking, core/l2r_attention.py),
    their pre-shifted plane stacks stream through the online-softmax
    KV-block walk, and each (bq, bkv) score tile is built by the static
    MSDF level schedule in VMEM.  ``levels`` truncates that schedule —
    the fused analogue of ``l2r_attn_scores(..., levels=...)``: the score
    matrix the softmax sees is the dequantized truncated prefix, with no
    per-level HBM snapshots.  Softmax statistics, PV, and the output stay
    float; v is untouched.

    VMEM at (bq, bkv, dh, D) = (256, 256, 128, 4): q/k plane tiles
    128 + 128 KiB int8, v 64 KiB, f32 score tile 256 KiB, acc 128 KiB —
    well under budget.  Off the TPU, pass ``interpret=True`` (the
    default is the compiled kernel); parity vs the jnp quantized path is numerical
    (online softmax reassociates), vs ``attention_ref`` it adds the
    quantization error of W8A8 scores.
    """
    b, sq, h, dh = q.shape
    _, skv, kvh, _ = k.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    d = plane_count(n_bits, log2_radix)
    cfg = QuantConfig(n_bits=n_bits, log2_radix=log2_radix)

    from repro.core.l2r_attention import quantize_per_vector
    qq, qs = quantize_per_vector(q, cfg)   # scales (B, Sq, H, 1)
    kq, ks = quantize_per_vector(k, cfg)   # scales (B, Skv, Kv, 1)
    q_stack = stack_planes_lhs(qq, n_bits, log2_radix)            # ascending
    k_stack = stack_planes_rhs(kq, n_bits, log2_radix, axis=-1)   # descending

    pq = (-sq) % bq
    pkv = (-skv) % bkv
    if pq:
        q_stack = jnp.pad(q_stack, ((0, 0), (0, pq), (0, 0), (0, 0)))
        qs = jnp.pad(qs, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pkv:
        k_stack = jnp.pad(k_stack, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        ks = jnp.pad(ks, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))

    qt = q_stack.transpose(0, 2, 1, 3).reshape(b * h, sq + pq, d * dh)
    kt = k_stack.transpose(0, 2, 1, 3).reshape(b * kvh, skv + pkv, d * dh)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv + pkv, dh)
    qst = qs.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        b * h, sq + pq, 1)
    kst = ks.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        b * kvh, skv + pkv, 1)

    n_q = (sq + pq) // bq
    n_kv = (skv + pkv) // bkv
    g = h // kvh

    kernel = functools.partial(
        _l2r_kernel, bq=bq, bkv=bkv, n_kv=n_kv, causal=causal,
        window=window, scale=scale, kv_len=skv,
        slices=tuple(msdf_level_slices(d, levels)), dh=dh,
    )
    kv_map = (lambda bh, qi, kj, g=g, kvh=kvh:
              ((bh // g // kvh) * kvh + (bh // g) % kvh, kj, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d * dh), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, bkv, d * dh), kv_map),
            pl.BlockSpec((1, bkv, dh), kv_map),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, bkv, 1), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda bh, qi, kj: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq + pq, dh), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, qst, kst)
    out = out.reshape(b, h, sq + pq, dh).transpose(0, 2, 1, 3)
    return out[:, :sq]
