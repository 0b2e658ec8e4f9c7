"""Public L2R GEMM/conv ops: backend dispatch, padding, quant/dequant.

This is the production entry point for the model stack (models/cnn.py,
models/common.py:dense, serve/engine.py).  Three backends:

  * ``jnp``             — the level-stacked pure-jnp schedule
                          (core/l2r_gemm.py); fastest off-TPU, no padding;
  * ``pallas-interpret``— the Pallas kernel body interpreted on CPU
                          (validation only — slow, but exercises the real
                          kernel dataflow);
  * ``pallas-tpu``      — the compiled Pallas kernel (requires a TPU).

Selection: explicit ``backend=`` argument > ``REPRO_L2R_BACKEND`` env var
> platform default (``pallas-tpu`` on TPU hosts, ``jnp`` elsewhere).
``schedule`` picks ``stacked`` (production, 2D-1 level matmuls),
``streaming`` (the same level walk emitted as a per-level prefix stream —
scan-based, progressive-precision consumers fold over it; bit-identical
to ``stacked`` at every truncation depth) or ``pairs`` (the D²-pass
baseline, kept for regression benchmarks).  ``l2r_gemm_progressive`` /
``l2r_conv2d_progressive`` expose the per-level snapshots + tail bounds
(core/progressive.py) behind the same backend dispatch.

The fused ``l2r_conv2d`` performs implicit im2col: the kh*kw taps of the
window stream through the digit-plane GEMM as shifted views of the
feature map, so the (B*H*W, cin*kh*kw) patch matrix is never
materialized in HBM.

**Pre-stacked plane operands** (``PlaneOperands``, core/quant.py): the
digit-plane stacks — not the raw int tensors — are the real operands of
every schedule, so the stacks are a first-class API.  ``l2r_gemm`` (and
the streaming consumers in core/progressive.py) accept a
``PlaneOperands`` in place of either raw operand on every backend;
``l2r_conv2d`` / ``l2r_conv2d_progressive*`` consume the
``QuantizedWeights.planes`` load-time weight-stack cache (built by
``quantize_weights(..., prestack=True)``).  The operand story:

  * activations: plane extraction is hoisted ONCE per feature map on
    EVERY backend — the jnp conv stacks raw digits (f32 BLAS fast path),
    the Pallas conv stacks pre-shifted bit-fields and each tap feeds a
    shifted view straight into the pre-stacked kernel entries
    (kernel.py:l2r_gemm_pallas_stacked_planes / _streaming_planes), so
    the kh*kw taps share one extraction instead of paying one each;
  * weights: ``QuantizedWeights`` caches the reversed RHS stack at model
    load (raw-digit layout — converts to the pre-shifted Pallas layout
    with exact chunk shifts) — weight planes are extracted exactly once
    per process instead of once per call/decode step;
  * all prestacked paths are bit-identical to inline extraction (the
    inline paths build the very same stacks; swept in
    tests/test_prestacked.py).
"""

from __future__ import annotations

import functools
import os
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.l2r_attention import (attn_scores_stacked,
                                      attn_scores_streaming_scan,
                                      attn_scores_streaming_while)
from repro.core.l2r_gemm import (l2r_matmul_int_stacked, stacked_gemm_planes)
from repro.core.progressive import (ProgressiveResult, l2r_matmul_int_streaming,
                                    level_bounds, progressive_matmul)
from repro.core.quant import (PlaneOperands, QuantConfig, QuantizedWeights,
                              plane_count, quantize, quantize_weights,
                              stack_planes_lhs, stack_planes_rhs)

from .kernel import (l2r_gemm_pallas, l2r_gemm_pallas_stacked,
                     l2r_gemm_pallas_stacked_planes,
                     l2r_gemm_pallas_streaming,
                     l2r_gemm_pallas_streaming_planes, stacked_tiles)
from .ref import l2r_gemm_ref

__all__ = ["l2r_gemm", "l2r_gemm_progressive", "l2r_attn_scores",
           "l2r_matmul_f", "l2r_conv2d", "l2r_conv2d_int",
           "l2r_conv2d_progressive", "l2r_conv2d_progressive_while",
           "pad_to", "resolve_backend", "PlaneOperands",
           "BACKENDS", "BACKEND_ENV_VAR", "SCHEDULES"]

SCHEDULES = ("stacked", "pairs", "streaming")

BACKENDS = ("jnp", "pallas-interpret", "pallas-tpu")
BACKEND_ENV_VAR = "REPRO_L2R_BACKEND"


def resolve_backend(backend: str | None = None,
                    n_bits: int | None = None) -> str:
    """Dispatch rule: explicit arg > $REPRO_L2R_BACKEND > platform default.

    The platform default is ``pallas-tpu`` when jax runs on TPU and the
    ``jnp`` level-stacked schedule everywhere else (interpret-mode Pallas
    is a validation tool, never a production default).

    An explicit ``pallas-tpu`` on a host whose jax platform is not TPU is
    rejected HERE, with a clear message — previously the mismatch
    surfaced as an opaque Mosaic lowering error deep inside the first
    ``pallas_call``.  A typo'd ``$REPRO_L2R_BACKEND`` is rejected here
    too, naming the env var and the valid backends — resolve time is the
    ONE place a bad env value can fail early instead of surfacing as an
    arbitrary downstream error.

    ``n_bits`` is the digit config of the call.  The compiled kernels
    feed int8 digit-plane tiles to the MXU, so a config wider than 8
    bits (int16 plane stacks, which Mosaic refuses) is rejected here on
    ``pallas-tpu``, naming the config.
    """
    chosen = _resolve(backend)
    if chosen == "pallas-tpu" and n_bits is not None and n_bits > 8:
        raise ValueError(
            f"the pallas-tpu L2R kernels take int8 digit planes, but this "
            f"call's digit config has n_bits={n_bits} (int16 planes); use "
            f"an n_bits <= 8 QuantConfig, or backend='jnp'")
    return chosen


def _resolve(backend: str | None) -> str:
    source = "backend argument"
    chosen = backend
    if not chosen:
        env = os.environ.get(BACKEND_ENV_VAR, "").strip()
        if env:
            chosen, source = env, f"${BACKEND_ENV_VAR} env var"
    chosen = chosen or "auto"
    if chosen == "auto":
        return "pallas-tpu" if jax.default_backend() == "tpu" else "jnp"
    if chosen not in BACKENDS:
        raise ValueError(
            f"unknown L2R backend {chosen!r} (from the {source}); valid "
            f"backends: {', '.join(BACKENDS)}, or 'auto' for the platform "
            f"default")
    if chosen == "pallas-tpu" and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"backend='pallas-tpu' requires a TPU host, but jax is running "
            f"on {jax.default_backend()!r}.  Use backend='pallas-interpret' "
            f"to validate the kernel dataflow on this host (slow, "
            f"correctness only), backend='jnp' for the production CPU/GPU "
            f"path, or unset ${BACKEND_ENV_VAR} for the platform default.")
    return chosen


def pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    """Zero-pad every dim of ``x`` up to a multiple of ``mults`` (exact for
    matmul operands).  ``mults`` must name every dim: a shorter (or
    longer) tuple used to be silently zip-truncated, leaving trailing
    dims unpadded with no error — now a ValueError.
    """
    if len(mults) != x.ndim:
        raise ValueError(
            f"pad_to: mults {mults!r} has rank {len(mults)} but x has rank "
            f"{x.ndim} (shape {x.shape}); every dim needs a multiple — "
            f"pass 1 for dims that should stay unpadded")
    pads = []
    for dim, mult in zip(x.shape, mults):
        rem = (-dim) % mult
        pads.append((0, rem))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def _lhs_stack_blocked(a, n_bits: int, log2_radix: int, bm: int, bk: int):
    """Pre-shifted LHS plane stack block-padded for the Pallas kernels.

    ``a`` is a raw (M, K) operand (padded then stacked — identical to
    stacking the padded operand) or a :class:`PlaneOperands` (its core
    stack is chunk-padded: zero digits of zero values, exact).  Returns
    ``(stack (Mp, D*Kp), m)``.
    """
    d = plane_count(n_bits, log2_radix)
    if isinstance(a, PlaneOperands):
        st = a.core_stack(shifted=True)
        m, k = st.shape[-2], a.k
        r = st.reshape(m, d, k)
        r = jnp.pad(r, (((0, (-m) % bm), (0, 0), (0, (-k) % bk))))
        return r.reshape(r.shape[0], -1), m
    m = a.shape[0]
    return stack_planes_lhs(pad_to(a, (bm, bk)), n_bits, log2_radix), m


def _rhs_stack_blocked(b, n_bits: int, log2_radix: int, bk: int, bn: int):
    """Pre-shifted (descending) RHS plane stack block-padded per chunk.
    Returns ``(stack (D*Kp, Np), n)``; accepts raw (K, N) or a 2-D
    :class:`PlaneOperands`."""
    d = plane_count(n_bits, log2_radix)
    if isinstance(b, PlaneOperands):
        st = b.core_stack(shifted=True)
        k, n = b.k, st.shape[-1]
        r = st.reshape(d, k, n)
        r = jnp.pad(r, ((0, 0), (0, (-k) % bk), (0, (-n) % bn)))
        return r.reshape(-1, r.shape[-1]), n
    n = b.shape[1]
    return stack_planes_rhs(pad_to(b, (bk, bn)), n_bits, log2_radix), n


def _gemm_mk(a) -> tuple[int, int]:
    if isinstance(a, PlaneOperands):
        return a.stack.shape[-2], a.k
    return a.shape


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "log2_radix", "levels", "bm", "bk", "bn",
                     "schedule", "backend", "early_exit", "name"),
)
def _l2r_gemm_backend(
    aq: jax.Array,
    bq: jax.Array,
    n_bits: int,
    log2_radix: int,
    levels: int | None,
    bm: int,
    bk: int,
    bn: int,
    schedule: str,
    backend: str,
    early_exit: bool = False,
    name: str | None = None,
) -> jax.Array:
    """Backend-resolved integer GEMM (backend is a static, already-resolved
    string here so the trace cache keys on it).  Either operand may be a
    pre-stacked :class:`PlaneOperands` (schedule "stacked"/"streaming")."""
    a_pre = isinstance(aq, PlaneOperands)
    b_pre = isinstance(bq, PlaneOperands)
    if backend == "jnp":
        if schedule == "stacked":
            if not (a_pre or b_pre):
                return l2r_matmul_int_stacked(aq, bq, n_bits, log2_radix,
                                              levels)
            # raw-digit layout whenever every operand allows it (the f32
            # BLAS fast path); a pre-shifted cache pulls both sides to
            # the shift-free int-dot layout instead of being unshifted
            shifted = (a_pre and aq.shifted) or (b_pre and bq.shifted)
            a_st = aq.core_stack(shifted) if a_pre else stack_planes_lhs(
                aq, n_bits, log2_radix, shifted=shifted)
            b_st = bq.core_stack(shifted) if b_pre else stack_planes_rhs(
                bq, n_bits, log2_radix, shifted=shifted)
            k = aq.k if a_pre else aq.shape[-1]
            return stacked_gemm_planes(a_st, b_st, k, n_bits, log2_radix,
                                       levels, shifted=shifted)
        if schedule == "streaming":
            return l2r_matmul_int_streaming(aq, bq, n_bits, log2_radix,
                                            levels, early_exit)
        return l2r_gemm_ref(aq, bq, n_bits, log2_radix, levels)
    interpret = backend == "pallas-interpret"
    m, _ = _gemm_mk(aq)
    if schedule == "pairs":  # raw-only baseline (validated in l2r_gemm)
        n = bq.shape[1]
        out = l2r_gemm_pallas(pad_to(aq, (bm, bk)), pad_to(bq, (bk, bn)),
                              n_bits, log2_radix, levels, bm, bk, bn,
                              interpret=interpret)
        return out[:m, :n]
    # schedule="streaming" asks only for the FINAL prefix: the stacked
    # kernel walks the identical (level, k-block) schedule, so it IS that
    # prefix — writing the (L, M, N) snapshot planes
    # (l2r_gemm_pallas_streaming, used by l2r_gemm_progressive) would
    # spend L x the output HBM on a bit-identical result.
    a_stack, m = _lhs_stack_blocked(aq, n_bits, log2_radix, bm, bk)
    b_rev, n = _rhs_stack_blocked(bq, n_bits, log2_radix, bk, bn)
    out = l2r_gemm_pallas_stacked_planes(a_stack, b_rev, n_bits, log2_radix,
                                         levels, bm, bk, bn,
                                         interpret=interpret, name=name)
    return out[:m, :n]


def _describe_operand(x) -> str:
    if isinstance(x, PlaneOperands):
        return x.describe()
    return f"array(shape={tuple(x.shape)}, dtype={x.dtype})"


def _check_plane_operand(x, side: str, n_bits: int, log2_radix: int,
                         other=None) -> None:
    if not isinstance(x, PlaneOperands):
        return
    paired = "" if other is None \
        else f" (other operand: {_describe_operand(other)})"
    if x.side != side:
        raise ValueError(
            f"{x.describe()} prepared as {x.side!r} passed as the {side} "
            f"operand (LHS stacks ascend, RHS stacks descend — they are "
            f"not interchangeable){paired}")
    if (x.n_bits, x.log2_radix) != (n_bits, log2_radix):
        raise ValueError(
            f"{x.describe()} does not match the call "
            f"(n_bits={n_bits}, log2_radix={log2_radix}){paired}; "
            f"re-prepare the stack for this config")


def l2r_gemm(
    aq: jax.Array,
    bq: jax.Array,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
    bm: int = 128,
    bk: int = 256,
    bn: int = 128,
    schedule: str = "stacked",
    backend: str | None = None,
    early_exit: bool = False,
    name: str | None = None,
) -> jax.Array:
    """Integer MSDF GEMM with backend dispatch. (M,K)x(K,N) -> int32.

    Any shape is accepted (Pallas backends zero-pad to blocks — exact for
    matmul).  Bit-identical across backends and schedules, including
    truncated ``levels``.

    Either operand may be a pre-stacked
    :class:`~repro.core.quant.PlaneOperands` (``PlaneOperands.prepare_lhs``
    / ``prepare_rhs``, or the ``QuantizedWeights.planes`` load-time
    cache) on every backend — plane extraction then happens exactly once
    where the operand was prepared, not once per call, with bit-identical
    results.  The ``pairs`` baseline schedule consumes raw int tensors
    only.

    ``early_exit`` (``schedule="streaming"``, jnp backend) runs the level
    walk as the ``lax.while_loop`` emitter instead of the fixed scan —
    bit-identical result here (with no consumer fold every level runs; it
    is the control flow early-exit consumers terminate inside, see
    core/progressive.py).  Schedules/backends that cannot honor the flag
    REJECT it: the pairs/stacked schedules have no level loop to stop,
    and the Pallas grids cannot shrink at runtime — their analogue is the
    streaming kernel's dynamic ``level_count`` scalar
    (kernel.py:l2r_gemm_pallas_streaming).

    ``name`` tags the stacked Pallas kernel in device traces
    (kernel.py:l2r_gemm_pallas_stacked_planes); results never depend
    on it.
    """
    assert schedule in SCHEDULES, schedule
    if early_exit and schedule != "streaming":
        raise ValueError(
            f"early_exit is a streaming-schedule control flow; "
            f"schedule={schedule!r} has no level loop to stop short "
            f"(it would be silently dropped)")
    resolved = resolve_backend(backend, n_bits)
    if early_exit and resolved != "jnp":
        raise ValueError(
            f"early_exit=True is the jnp while-loop emitter; the "
            f"{resolved!r} backend cannot shrink its grid at runtime and "
            f"would silently drop the flag — use the streaming kernel's "
            f"dynamic level_count scalar "
            f"(l2r_gemm_pallas_streaming(level_count=...)) for grid-level "
            f"stop-short on Pallas")
    _check_plane_operand(aq, "lhs", n_bits, log2_radix, other=bq)
    _check_plane_operand(bq, "rhs", n_bits, log2_radix, other=aq)
    if schedule == "pairs" and (isinstance(aq, PlaneOperands)
                                or isinstance(bq, PlaneOperands)):
        raise TypeError(
            "schedule='pairs' (the D²-pass baseline) consumes raw int "
            "operands; pre-stacked PlaneOperands are a stacked/streaming-"
            "schedule format")
    # trace-time int32 soundness certificate (analysis/overflow.py):
    # K is static here, so unsound digit configs are caught before any
    # tensor flows.  Deferred import: analysis pulls in core modules.
    from repro.analysis.overflow import check_or_raise as _certify
    k = aq.k if isinstance(aq, PlaneOperands) else (
        bq.k if isinstance(bq, PlaneOperands) else int(aq.shape[-1]))
    _certify(n_bits, log2_radix, int(k), levels=levels, where="l2r_gemm")
    return _l2r_gemm_backend(aq, bq, n_bits, log2_radix, levels,
                             bm, bk, bn, schedule, resolved,
                             early_exit, name)


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "log2_radix", "levels", "bm", "bk", "bn",
                     "backend"),
)
def _l2r_gemm_progressive_backend(aq, bq, n_bits, log2_radix, levels,
                                  bm, bk, bn, backend):
    if backend == "jnp":
        return progressive_matmul(aq, bq, n_bits, log2_radix, levels)
    m, k = _gemm_mk(aq)
    a_stack, m = _lhs_stack_blocked(aq, n_bits, log2_radix, bm, bk)
    b_rev, n = _rhs_stack_blocked(bq, n_bits, log2_radix, bk, bn)
    stream = l2r_gemm_pallas_streaming_planes(
        a_stack, b_rev, n_bits, log2_radix, levels, bm, bk, bn,
        interpret=(backend == "pallas-interpret"))
    bounds = level_bounds(plane_count(n_bits, log2_radix), log2_radix, k,
                          levels)
    return ProgressiveResult(partial=stream[:, :m, :n], tail_bound=bounds.f32,
                             bound_i32=bounds.i32, decidable=bounds.decidable)


def l2r_gemm_progressive(
    aq: jax.Array,
    bq: jax.Array,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
    bm: int = 128,
    bk: int = 256,
    bn: int = 128,
    backend: str | None = None,
) -> ProgressiveResult:
    """Per-level MSDF snapshot stream with backend dispatch.

    Level l of ``result.partial`` is bit-identical to
    ``l2r_gemm(..., levels=l+1, schedule="stacked")`` on every backend;
    bounds come with the int32 exactness guard (core/progressive.py).
    Either operand may be a pre-stacked :class:`PlaneOperands` (as in
    :func:`l2r_gemm`).  Consumers that only need a fold over the stream
    (early-exit serving) should use
    ``core.progressive.streaming_matmul_scan`` instead — this entry
    materializes the ``(L, M, N)`` stack it returns.
    """
    _check_plane_operand(aq, "lhs", n_bits, log2_radix, other=bq)
    _check_plane_operand(bq, "rhs", n_bits, log2_radix, other=aq)
    return _l2r_gemm_progressive_backend(aq, bq, n_bits, log2_radix, levels,
                                         bm, bk, bn,
                                         resolve_backend(backend, n_bits))


def _attn_pallas_scores(q_po: PlaneOperands, k_po: PlaneOperands,
                        n_bits: int, log2_radix: int, levels: int | None,
                        interpret: bool) -> jax.Array:
    """Attention scores through the pre-stacked Pallas GEMM kernel.

    The score walk is a batch of independent (Q*G, dh) x (dh, S) GEMMs —
    one per (batch, kv-head) — and each one IS the level-stacked kernel's
    problem, so the route is an unrolled loop of
    ``l2r_gemm_pallas_stacked_planes`` calls over pre-shifted slices of
    the SAME stacks the jnp schedule consumes (the cache's descending
    head-dim blocks transpose to the kernel's (D*K, N) layout exactly —
    plane-major descending either way).  Validation-oriented: the batch
    loop is python-unrolled, so this is for parity runs and small decode
    shapes, not the production serving path (which is jnp off-TPU).
    """
    d = plane_count(n_bits, log2_radix)
    dh = q_po.k
    qs = q_po.core_stack(shifted=True)   # (B, Q, Kv, G, D*dh) ascending
    ks = k_po.core_stack(shifted=True)   # (B, S, Kv, D*dh) descending
    b_, q_, kv, g = qs.shape[:4]
    s_ = ks.shape[1]
    bk = min(256, -(-dh // 128) * 128)
    dhp = dh + (-dh) % bk
    m0 = q_ * g
    rows = []
    for bi in range(b_):
        cols = []
        for kvi in range(kv):
            a = qs[bi, :, kvi].reshape(m0, d, dh)
            a = jnp.pad(a, (((0, (-m0) % 128), (0, 0), (0, dhp - dh))))
            kb = ks[bi, :, kvi].reshape(s_, d, dh).transpose(1, 2, 0)
            kb = jnp.pad(kb, ((0, 0), (0, dhp - dh), (0, (-s_) % 128)))
            t = l2r_gemm_pallas_stacked_planes(
                a.reshape(a.shape[0], -1), kb.reshape(-1, kb.shape[-1]),
                n_bits, log2_radix, levels, 128, bk, 128,
                interpret=interpret)
            cols.append(t[:m0, :s_].reshape(q_, g, s_).transpose(1, 0, 2))
        rows.append(jnp.stack(cols, axis=0))
    return jnp.stack(rows, axis=0)  # (B, Kv, G, Q, S)


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "log2_radix", "levels", "schedule", "backend",
                     "early_exit"),
)
def _l2r_attn_scores_backend(qq, kq, n_bits, log2_radix, levels, schedule,
                             backend, early_exit):
    if backend == "jnp":
        if schedule == "streaming":
            if early_exit:
                acc, _, _ = attn_scores_streaming_while(
                    qq, kq, n_bits=n_bits, log2_radix=log2_radix,
                    levels=levels)
            else:
                acc, _, _ = attn_scores_streaming_scan(
                    qq, kq, n_bits=n_bits, log2_radix=log2_radix,
                    levels=levels)
            return acc
        return attn_scores_stacked(qq, kq, n_bits, log2_radix, levels)
    # schedule="streaming" asks only for the FINAL prefix here, and the
    # stacked kernel walks the identical (level, k-block) schedule — same
    # argument as _l2r_gemm_backend's streaming-on-Pallas route.
    q_po = qq if isinstance(qq, PlaneOperands) \
        else PlaneOperands.prepare_lhs(qq, n_bits, log2_radix)
    k_po = kq if isinstance(kq, PlaneOperands) \
        else PlaneOperands.prepare_rhs(kq, n_bits, log2_radix, axis=-1)
    return _attn_pallas_scores(q_po, k_po, n_bits, log2_radix, levels,
                               backend == "pallas-interpret")


def l2r_attn_scores(
    qq,
    kq,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
    schedule: str = "stacked",
    backend: str | None = None,
    early_exit: bool = False,
) -> jax.Array:
    """Digit-serial QK^T scores with backend dispatch: int32 (B,Kv,G,Q,S).

    ``qq`` is the grouped query block (B, Q, Kv, G, dh) as signed ints or
    a prepared LHS :class:`PlaneOperands`; ``kq`` the cached keys
    (B, S, Kv, dh) as signed ints or the KV cache's incrementally
    stacked RHS operand (models/attention.py:kv_plane_operands — plane
    extraction then happened at append time, not per decode step).
    Bit-identical across backends and schedules at every ``levels``
    truncation, by the same contract as :func:`l2r_gemm`; softmax and PV
    stay float outside this entry (core/l2r_attention.py).

    ``schedule="streaming"`` runs the level walk as the per-level prefix
    emitter (jnp; on Pallas the stacked kernel IS the final prefix);
    ``early_exit`` additionally swaps in the ``lax.while_loop`` emitter —
    control-flow-only here (no consumer fold, every level runs), rejected
    off the jnp streaming path exactly as in :func:`l2r_gemm`.  Consumers
    that fold the stream (margin-bounded progressive decode) use
    ``core.l2r_attention.attn_scores_streaming_while`` directly.
    """
    if schedule not in ("stacked", "streaming"):
        raise ValueError(
            f"l2r_attn_scores schedule must be 'stacked' or 'streaming', "
            f"got {schedule!r} (the pairs baseline is a GEMM-only "
            f"regression schedule)")
    if early_exit and schedule != "streaming":
        raise ValueError(
            f"early_exit is a streaming-schedule control flow; "
            f"schedule={schedule!r} has no level loop to stop short "
            f"(it would be silently dropped)")
    resolved = resolve_backend(backend, n_bits)
    if early_exit and resolved != "jnp":
        raise ValueError(
            f"early_exit=True is the jnp while-loop emitter; the "
            f"{resolved!r} backend cannot shrink its grid at runtime and "
            f"would silently drop the flag")
    _check_plane_operand(qq, "lhs", n_bits, log2_radix, other=kq)
    _check_plane_operand(kq, "rhs", n_bits, log2_radix, other=qq)
    from repro.analysis.overflow import check_or_raise as _certify
    dh = qq.k if isinstance(qq, PlaneOperands) else (
        kq.k if isinstance(kq, PlaneOperands) else int(qq.shape[-1]))
    _certify(n_bits, log2_radix, int(dh), levels=levels,
             where="l2r_attn_scores")
    return _l2r_attn_scores_backend(qq, kq, n_bits, log2_radix, levels,
                                    schedule, resolved, early_exit)


def l2r_matmul_f(
    x: jax.Array,
    w: jax.Array | None,
    cfg: QuantConfig = QuantConfig(),
    levels: int | None = None,
    w_q: QuantizedWeights | tuple[jax.Array, jax.Array] | None = None,
    backend: str | None = None,
    schedule: str = "stacked",
    name: str | None = None,
) -> jax.Array:
    """Float -> quantize -> dispatched MSDF GEMM -> dequantized float.

    ``w_q`` (core/quant.py:QuantizedWeights, built once at load) skips
    the per-forward weight quantization; ``w`` may then be None.  When
    the cache also carries its pre-stacked RHS plane stack
    (``quantize_weights(..., prestack=True)``) and the layout matches
    this call's config, the GEMM consumes the stack directly — weight
    plane extraction then happened exactly once at load time.  ``name``
    tags the kernel in device traces (:func:`l2r_gemm`).
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    # per-row (per-token) activation scales commute with the K-contraction
    xq, xs = quantize(x2, cfg, axis=0 if cfg.per_channel else None)
    w_in = None
    if w_q is None:
        wq, ws = quantize(w, cfg, axis=-1)  # per-out-channel: (1, N)
    elif isinstance(w_q, QuantizedWeights):
        wq, ws = w_q.q, w_q.scale
        p = w_q.planes
        if (p is not None and schedule != "pairs"
                and p.matches(cfg.n_bits, cfg.log2_radix, ndim=2,
                              side="rhs")):
            w_in = p
    else:
        wq, ws = w_q
    out = l2r_gemm(xq, wq if w_in is None else w_in, cfg.n_bits,
                   cfg.log2_radix, levels, schedule=schedule, backend=backend,
                   name=name)
    out = out.astype(jnp.float32) * xs * ws.reshape(1, -1)
    return out.astype(x.dtype).reshape(*lead, wq.shape[-1])


def _conv_same_geometry(h: int, w_: int, kh: int, kw: int,
                        stride: tuple[int, int], dilation: tuple[int, int]):
    """Output size + per-edge padding of a "SAME" conv (XLA/TF convention:
    total pad = max((out-1)*stride + eff_k - in, 0), low edge gets the
    floor half — matches lax.conv_general_dilated("SAME"))."""
    sh, sw = stride
    dh, dw = dilation
    oh, ow = -(-h // sh), -(-w_ // sw)
    eff_kh, eff_kw = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    ph = max((oh - 1) * sh + eff_kh - h, 0)
    pw = max((ow - 1) * sw + eff_kw - w_, 0)
    return oh, ow, (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2)


def _tap_view(xp: jax.Array, dy: int, dx: int, oh: int, ow: int,
              stride: tuple[int, int], dilation: tuple[int, int]) -> jax.Array:
    """Shifted (strided) view of the padded map feeding tap (dy, dx):
    out[y, x] consumes xp[y*sh + dy*dh, x*sw + dx*dw]."""
    sh, sw = stride
    dh, dw = dilation
    return xp[:, dy * dh:dy * dh + (oh - 1) * sh + 1:sh,
              dx * dw:dx * dw + (ow - 1) * sw + 1:sw]


def _conv_w_geom(w_in) -> tuple[int, int, int, int]:
    """(kh, kw, cin, cout) of a raw conv weight or its PlaneOperands cache."""
    if isinstance(w_in, PlaneOperands):
        kh, kw = w_in.stack.shape[0], w_in.stack.shape[1]
        return kh, kw, w_in.k, w_in.stack.shape[-1]
    return w_in.shape


def _conv_wrev(w_in, n_bits: int, log2_radix: int, shifted: bool) -> jax.Array:
    """Reversed RHS plane stack (kh, kw, D*cin, cout) of the conv weight —
    from the load-time cache when present (exact layout conversion),
    extracted here otherwise."""
    if isinstance(w_in, PlaneOperands):
        return w_in.core_stack(shifted)
    return stack_planes_rhs(w_in, n_bits, log2_radix, axis=-2,
                            shifted=shifted)


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "log2_radix", "levels", "backend", "stride",
                     "dilation", "name"),
)
def _l2r_conv2d_int(
    xq: jax.Array,
    w_in,
    n_bits: int,
    log2_radix: int,
    levels: int | None,
    backend: str,
    stride: tuple[int, int] = (1, 1),
    dilation: tuple[int, int] = (1, 1),
    name: str | None = None,
) -> jax.Array:
    """Integer core of the fused conv: implicit im2col over kh*kw taps.

    xq: (B, H, W, cin) small ints; ``w_in``: (kh, kw, cin, cout) small
    ints OR the pre-stacked :class:`PlaneOperands` weight cache;
    "SAME" padding, arbitrary stride/dilation (each tap reads a
    step-sliced shifted view — no patch matrix for any geometry).
    Bit-identical to quantized im2col + l2r_matmul_int on the same
    operands: the contraction over (kh, kw, cin) splits into kh*kw
    independent cin-contractions, and per-significance-level partial
    sums add across taps exactly.

    Activation plane extraction is hoisted out of the tap loop on EVERY
    backend — one stack per feature map (raw digits on jnp for the f32
    BLAS fast path, pre-shifted bit-fields feeding the pre-stacked
    Pallas kernel entry) — and the weight stack comes from the load-time
    cache when provided, so a cached 3x3 layer performs exactly one
    activation extraction and zero weight extractions per call.
    """
    bsz, h, w_, cin = xq.shape
    kh, kw, _, cout = _conv_w_geom(w_in)
    oh, ow, (ph_lo, ph_hi), (pw_lo, pw_hi) = _conv_same_geometry(
        h, w_, kh, kw, stride, dilation)
    xp = jnp.pad(xq, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
    acc = jnp.zeros((bsz, oh, ow, cout), jnp.int32)
    d = plane_count(n_bits, log2_radix)
    if backend == "jnp":
        # hoist plane extraction out of the tap loop: one LHS stack for
        # the whole feature map, one reversed RHS stack for all taps
        # (raw digits -> the guarded f32 BLAS fast path)
        xsp = stack_planes_lhs(xp, n_bits, log2_radix, shifted=False)
        wrev = _conv_wrev(w_in, n_bits, log2_radix, shifted=False)
        for dy in range(kh):
            for dx in range(kw):
                a = _tap_view(xsp, dy, dx, oh, ow, stride, dilation)
                acc = acc + stacked_gemm_planes(
                    a, wrev[dy, dx], cin, n_bits, log2_radix, levels,
                    shifted=False)
        return acc
    # Pallas: the same per-feature-map hoist, in the kernels' pre-shifted
    # layout — each tap view of the stacked map feeds the pre-stacked
    # kernel entry directly (channels-last stacking commutes with the
    # spatial tap slicing), instead of re-extracting planes per tap.
    # Every tap is the same (B*OH*OW, D*cin) x (D*cin, cout) GEMM: pad
    # rows, the per-plane K chunk and cout to 128-multiples only, and take
    # the tiles from that shape (kernel.py:stacked_tiles) so a large map
    # walks few, large grid steps.
    m0 = bsz * oh * ow
    mp, ckp, coutp = (-(-v // 128) * 128 for v in (m0, cin, cout))
    xsp = stack_planes_lhs(xp, n_bits, log2_radix)  # (B, H', W', D*cin)
    bm, bk, bn = stacked_tiles(mp, ckp, coutp)
    wrev = _conv_wrev(w_in, n_bits, log2_radix, shifted=True)
    wrev = jnp.pad(wrev.reshape(kh, kw, d, cin, cout),
                   ((0, 0), (0, 0), (0, 0), (0, ckp - cin),
                    (0, coutp - cout)))
    wrev = wrev.reshape(kh, kw, d * ckp, coutp)
    interpret = backend == "pallas-interpret"
    for dy in range(kh):
        for dx in range(kw):
            a = _tap_view(xsp, dy, dx, oh, ow, stride, dilation)
            a2 = jnp.pad(a.reshape(m0, d, cin),
                         ((0, mp - m0), (0, 0), (0, ckp - cin)))
            t = l2r_gemm_pallas_stacked_planes(
                a2.reshape(mp, d * ckp), wrev[dy, dx], n_bits,
                log2_radix, levels, bm, bk, bn, interpret=interpret,
                name=name)
            acc = acc + t[:m0, :cout].reshape(bsz, oh, ow, cout)
    return acc


def l2r_conv2d(
    x: jax.Array,
    w: jax.Array | None,
    b: jax.Array | None = None,
    cfg: QuantConfig = QuantConfig(),
    levels: int | None = None,
    w_q: QuantizedWeights | None = None,
    backend: str | None = None,
    stride: int | tuple[int, int] = 1,
    dilation: int | tuple[int, int] = 1,
    name: str | None = None,
) -> jax.Array:
    """Fused L2R conv2d, NHWC/HWIO, "SAME" padding, any stride/dilation.

    The composite-IPU conv without the HBM patch matrix: activations are
    quantized per image (scales commute with the window contraction),
    digit planes are extracted once per feature map on every backend,
    and each kernel tap streams a shifted (stride-stepped,
    dilation-spaced) view of the feature map through the level-stacked
    GEMM.  ``w_q`` reuses a load-time weight cache — when it carries the
    pre-stacked plane stack (``quantize_weights(..., prestack=True,
    plane_axis=-2)``) the conv consumes that stack directly and performs
    no weight plane extraction at all; otherwise ``w`` (kh, kw, cin,
    cout) is quantized per output channel here.  ``name`` tags the tap
    kernels in device traces (:func:`l2r_gemm`).
    """
    if w_q is None:
        w_q = quantize_weights(w, cfg)  # (kh,kw,cin,cout), scale (1,1,1,cout)
    xq, xs = quantize(x, cfg, axis=0)  # per-image scales (B,1,1,1)
    out = l2r_conv2d_int(xq, w_q, cfg, levels, backend, stride, dilation,
                         name)
    out = out.astype(jnp.float32) * xs * w_q.scale.reshape(1, 1, 1, -1)
    out = out.astype(x.dtype)
    if b is not None:
        out = out + b.astype(out.dtype)
    return out


def l2r_conv2d_int(
    xq: jax.Array,
    w_q: QuantizedWeights,
    cfg: QuantConfig = QuantConfig(),
    levels: int | None = None,
    backend: str | None = None,
    stride: int | tuple[int, int] = 1,
    dilation: int | tuple[int, int] = 1,
    name: str | None = None,
) -> jax.Array:
    """Integer core of :func:`l2r_conv2d`: quantized activations ``xq``
    (B, H, W, cin) against the weight cache ``w_q`` -> int32 (B, OH, OW,
    cout), before dequantization.  Bit-identical across backends."""
    from repro.analysis.overflow import check_or_raise as _certify
    kh, kw, cin, _ = w_q.q.shape
    _certify(cfg.n_bits, cfg.log2_radix, int(cin), levels=levels,
             taps=int(kh * kw), where="l2r_conv2d")
    return _l2r_conv2d_int(xq, _conv_w_in(w_q, cfg), cfg.n_bits,
                           cfg.log2_radix, levels,
                           resolve_backend(backend, cfg.n_bits),
                           _pair(stride), _pair(dilation), name)


def _pair(v: int | tuple[int, int]) -> tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


def _conv_w_in(w_q: QuantizedWeights, cfg: QuantConfig):
    """The conv weight operand: the cached plane stack when its layout
    matches this call's config (contraction axis -2), the raw int weight
    otherwise (inline extraction — bit-identical)."""
    p = w_q.planes
    if p is not None and p.matches(cfg.n_bits, cfg.log2_radix, ndim=4,
                                   side="rhs", contract_axis=2):
        return p
    return w_q.q


# ------------------------------------------------------- progressive conv
def _conv_level_term(xq, w_in, n_bits, log2_radix, stride, dilation):
    """Per-level term of the progressive conv's jnp paths: hoisted
    zero-padded plane stacks + a ``term(ao, bo)`` closure summing the tap
    contributions of one significance level.  Shared by the fixed scan
    AND the early-exit while loop — identical ops in identical order is
    what keeps the two control flows bit-identical.  ``w_in`` may be the
    pre-stacked weight cache (its window stack IS the padded ``wrev``
    built here — zero extraction, bit-identical stream)."""
    from repro.core.l2r_gemm import _f32_dot_exact

    bsz, h, w_, cin = xq.shape
    kh, kw, _, cout = _conv_w_geom(w_in)
    d = n_bits // log2_radix
    oh, ow, (ph_lo, ph_hi), (pw_lo, pw_hi) = _conv_same_geometry(
        h, w_, kh, kw, stride, dilation)
    xp = jnp.pad(xq, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
    xsp = stack_planes_lhs(xp, n_bits, log2_radix, shifted=False)
    pad = (d - 1) * cin
    xsp = jnp.pad(xsp, ((0, 0), (0, 0), (0, 0), (0, pad)))
    if isinstance(w_in, PlaneOperands):
        wrev = w_in.window_stack()
    else:
        wrev = stack_planes_rhs(w_in, n_bits, log2_radix, axis=-2,
                                shifted=False)
        wrev = jnp.pad(wrev, ((0, 0), (0, 0), (0, pad), (0, 0)))
    use_f32 = _f32_dot_exact(cin, d, log2_radix)
    if use_f32:
        xsp = xsp.astype(jnp.float32)
        wrev = wrev.astype(jnp.float32)
    width = d * cin

    def term(ao, bo):
        t_sum = jnp.zeros((bsz, oh, ow, cout), jnp.int32)
        for dy in range(kh):
            for dx in range(kw):
                a = _tap_view(xsp, dy, dx, oh, ow, stride, dilation)
                a_l = jax.lax.dynamic_slice_in_dim(a, ao * cin, width,
                                                   axis=a.ndim - 1)
                b_l = jax.lax.dynamic_slice_in_dim(wrev[dy, dx], bo * cin,
                                                   width, axis=0)
                t = jax.lax.dot_general(
                    a_l, b_l,
                    ((((a_l.ndim - 1),), ((0,))), ((), ())),
                    preferred_element_type=jnp.float32 if use_f32
                    else jnp.int32,
                    precision=jax.lax.Precision.HIGHEST if use_f32 else None,
                )
                t_sum = t_sum + t.astype(jnp.int32)
        return t_sum

    return term, (bsz, oh, ow, cout)


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "log2_radix", "levels", "backend", "stride",
                     "dilation"),
)
def _l2r_conv2d_progressive_int(
    xq: jax.Array,
    w_in,
    n_bits: int,
    log2_radix: int,
    levels: int | None,
    backend: str,
    stride: tuple[int, int] = (1, 1),
    dilation: tuple[int, int] = (1, 1),
) -> jax.Array:
    """Per-level prefix stream of the fused conv: (L, B, OH, OW, cout).

    Level l is bit-identical to ``_l2r_conv2d_int(..., levels=l+1)``: the
    taps share each significance level, so the per-level conv term is the
    tap sum of per-level GEMM terms.  The jnp path is the streaming scan
    of core/progressive.py with the tap loop inside the level step;
    Pallas backends sum the per-tap snapshot streams of the streaming
    kernel.  Activation planes are hoisted once per feature map on every
    backend, and ``w_in`` may be the pre-stacked weight cache (zero
    weight extraction).
    """
    from repro.core.progressive import _level_walk

    bsz, h, w_, cin = xq.shape
    kh, kw, _, cout = _conv_w_geom(w_in)
    d = n_bits // log2_radix
    oh, ow, (ph_lo, ph_hi), (pw_lo, pw_hi) = _conv_same_geometry(
        h, w_, kh, kw, stride, dilation)
    a_off, b_off, svals = _level_walk(d, levels)
    n_steps = int(svals.shape[0])
    if n_steps == 0:
        return jnp.zeros((0, bsz, oh, ow, cout), jnp.int32)
    if backend != "jnp":
        xp = jnp.pad(xq, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
        bk = min(256, -(-cin // 128) * 128)
        ckp = cin + (-cin) % bk
        xsp = stack_planes_lhs(xp, n_bits, log2_radix)  # once per map
        wrev = _conv_wrev(w_in, n_bits, log2_radix, shifted=True)
        wrev = jnp.pad(wrev.reshape(kh, kw, d, cin, cout),
                       ((0, 0), (0, 0), (0, 0), (0, ckp - cin),
                        (0, (-cout) % 128)))
        wrev = wrev.reshape(kh, kw, d * ckp, -1)
        acc = jnp.zeros((n_steps, bsz, oh, ow, cout), jnp.int32)
        for dy in range(kh):
            for dx in range(kw):
                a = _tap_view(xsp, dy, dx, oh, ow, stride, dilation)
                a2 = a.reshape(-1, d, cin)
                m0 = a2.shape[0]
                a2 = jnp.pad(a2,
                             (((0, (-m0) % 128), (0, 0), (0, ckp - cin))))
                t = l2r_gemm_pallas_streaming_planes(
                    a2.reshape(a2.shape[0], -1), wrev[dy, dx], n_bits,
                    log2_radix, levels, 128, bk, 128,
                    interpret=(backend == "pallas-interpret"))
                t = t[:, :m0, :cout]
                acc = acc + t.reshape(n_steps, bsz, oh, ow, cout)
        return acc

    term, out_shape = _conv_level_term(xq, w_in, n_bits, log2_radix, stride,
                                       dilation)

    def step(acc, xs):
        ao, bo, s = xs
        acc = acc + (term(ao, bo) << (log2_radix * s))
        return acc, acc

    acc0 = jnp.zeros(out_shape, jnp.int32)
    xs = (jnp.asarray(a_off), jnp.asarray(b_off), jnp.asarray(svals))
    _, stack = jax.lax.scan(step, acc0, xs)
    return stack


def l2r_conv2d_progressive_while(
    x: jax.Array,
    w: jax.Array | None = None,
    cfg: QuantConfig = QuantConfig(),
    fold: Callable | None = None,
    init=None,
    done_fn: Callable | None = None,
    levels: int | None = None,
    w_q: QuantizedWeights | None = None,
    backend: str | None = None,
    stride: int | tuple[int, int] = 1,
    dilation: int | tuple[int, int] = 1,
):
    """Early-exit fused conv stream: the progressive conv's level loop run
    as a ``lax.while_loop`` carrying the consumer's fold state.

    The per-level arithmetic is the SAME tap-summed term the fixed scan
    of :func:`l2r_conv2d_progressive` executes (shared closure), so after
    ``levels_run`` iterations the integer prefix is bit-identical to
    ``result.partial[levels_run - 1]`` of the scan path.  ``fold(carry,
    partial, level_index) -> carry`` consumes each integer prefix;
    ``done_fn(fold_carry) -> scalar bool`` stops the loop (``None`` runs
    every level — control-flow-only).  jnp backend only: the grid-level
    analogue on Pallas is the streaming kernel's ``level_count`` scalar.

    Returns ``(prefix (B, OH, OW, cout) int32, fold_carry, levels_run
    () int32, scale (B, 1, 1, cout))`` — ``prefix * scale`` is the float
    feature-map prefix at the exit level.
    """
    assert resolve_backend(backend) == "jnp", (
        "l2r_conv2d_progressive_while: jnp backend only (use the streaming "
        "kernel's level_count scalar for grid-level shortening)")
    if w_q is None:
        w_q = quantize_weights(w, cfg)
    xq, xs = quantize(x, cfg, axis=0)  # per-image scales (B,1,1,1)
    from repro.core.progressive import _level_walk, _while_emitter

    a_off, b_off, svals = _level_walk(cfg.planes, levels)
    scale = xs * w_q.scale.reshape(1, 1, 1, -1)
    term, out_shape = _conv_level_term(xq, _conv_w_in(w_q, cfg), cfg.n_bits,
                                       cfg.log2_radix,
                                       _pair(stride), _pair(dilation))
    acc0 = jnp.zeros(out_shape, jnp.int32)
    if int(svals.shape[0]) == 0:
        return acc0, init, jnp.int32(0), scale
    t, acc, fold_c = _while_emitter(term, a_off, b_off, svals,
                                    cfg.log2_radix, acc0, fold, init,
                                    done_fn)
    return acc, fold_c, t, scale


def l2r_conv2d_progressive(
    x: jax.Array,
    w: jax.Array | None = None,
    cfg: QuantConfig = QuantConfig(),
    levels: int | None = None,
    w_q: QuantizedWeights | None = None,
    backend: str | None = None,
    stride: int | tuple[int, int] = 1,
    dilation: int | tuple[int, int] = 1,
):
    """Progressive-precision fused conv: per-level snapshots + tail bounds.

    Returns ``(result, scale)``: ``result`` is a
    :class:`~repro.core.progressive.ProgressiveResult` whose
    ``partial[l]`` is the integer conv truncated after l+1 MSDF levels
    (bit-identical to ``l2r_conv2d``'s core at ``levels=l+1``), with tail
    bounds for the conv's effective contraction K = kh*kw*cin; ``scale``
    is the (B, 1, 1, cout) dequantization factor (per-image activation
    scale x per-channel weight scale) — ``partial[l] * scale`` is the
    float feature map prefix, and ``tail_bound[l] * scale`` bounds its
    distance from the exact W8A8 conv.
    """
    if w_q is None:
        w_q = quantize_weights(w, cfg)
    xq, xs = quantize(x, cfg, axis=0)  # per-image scales (B,1,1,1)
    kh, kw, cin, _ = w_q.q.shape
    stack = _l2r_conv2d_progressive_int(
        xq, _conv_w_in(w_q, cfg), cfg.n_bits, cfg.log2_radix, levels,
        resolve_backend(backend, cfg.n_bits), _pair(stride),
        _pair(dilation))
    bounds = level_bounds(cfg.planes, cfg.log2_radix, kh * kw * cin, levels)
    result = ProgressiveResult(partial=stack, tail_bound=bounds.f32,
                               bound_i32=bounds.i32,
                               decidable=bounds.decidable)
    return result, xs * w_q.scale.reshape(1, 1, 1, -1)
