"""Streaming progressive-precision subsystem (online early output).

The hardware's defining property is that most-significant output digits
are available after the online delay, long before the computation
finishes.  This module is the tensor-level realization of that property
**on the level-stacked schedule** (core/l2r_gemm.py): a single
``lax.scan`` walks the significance levels s = 2D-2 .. 0 most significant
first, carrying only the running ``(…, M, N)`` accumulator, and after
every level the prefix sum is *bit-identical* to the stacked schedule
truncated at that depth (`l2r_matmul_int_stacked(..., levels=t+1)`).

Mechanics: both operands keep the pre-stacked digit-plane layout the
dispatcher uses (quant.py:stack_planes_lhs/rhs) and are zero-padded by
D-1 extra plane blocks.  Every level then reads a *fixed-width* window of
D plane blocks — LHS at block ``i_lo(s)``, RHS at block ``d-1-s+i_lo`` —
and the pairs outside the level's true range land on zero blocks on
exactly one side, contributing nothing.  A fixed window makes the level
loop a scan (one fused contraction per step), which is what lets
consumers *fold* over the stream (`streaming_matmul_scan`) without ever
materializing the ``(L, …, M, N)`` snapshot stack: early-exit consumers
(VGG classify heads, progressive decode) carry only their decision state.

Two control flows share that per-level step: the fixed-length ``lax.scan``
(`streaming_matmul_scan` — the oracle, always runs every level) and the
``lax.while_loop`` early-exit emitter (`streaming_matmul_while`), which
carries the consumer's fold/decision state and STOPS once every row in
the tile has decided — turning saved levels into saved wall-clock inside
one fused computation instead of merely skipped follow-up passes.

Decision machinery: `level_bounds` gives per-level hard bounds on the
unseen tail (core/online.py:tail_bound) in three forms — a conservatively
up-rounded float32 (for scaled-domain decisions), an int32 bound with an
explicit exactness guard (`decidable`; levels whose true bound exceeds
the int32 clip are simply never decidable — conservative, never wrong),
and the raw Python ints.  `earliest_decision_level` compares margins and
bounds in a single dtype (int32) under that guard.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .l2r_gemm import _f32_dot_exact
from .online import msdf_levels, tail_bound
# decision_state moved to core/policy.py (the one decision fold of every
# streaming walk); re-exported here for existing importers
from .policy import LevelPolicy, decision_state, head_walk_machinery
from .quant import (PlaneOperands, plane_count, stack_planes_lhs,
                    stack_planes_rhs)

__all__ = [
    "ProgressiveResult",
    "LevelBounds",
    "level_bounds",
    "progressive_matmul",
    "streaming_matmul_scan",
    "streaming_matmul_while",
    "l2r_matmul_int_streaming",
    "streaming_argmax",
    "sharded_walk_axes",
    "decision_state",
    "earliest_decision_level",
    "CONSENSUS_WALK_SCOPE",
]

#: named scope wrapping the shard_mapped consensus walk body — every
#: collective the walk declares carries this prefix in its HLO
#: ``metadata op_name`` (see analysis/sharding.py)
CONSENSUS_WALK_SCOPE = "l2r_consensus_walk"

# int32 decision clip: bounds above this cannot be compared exactly in
# int32 (2*bound must not overflow), so those levels are marked
# undecidable instead of comparing in a lossy dtype.
_BOUND_CLIP = (2**31 - 1) // 2


class ProgressiveResult(NamedTuple):
    """Stacked per-level prefix results of the MSDF stream.

    partial:    (L, ..., M, N) int32 prefix sums, level l includes the
                top (l+1) significance levels — bit-identical to the
                stacked schedule truncated at levels=l+1.
    tail_bound: (L,) float32 — hard bound on |exact - partial[l]|,
                conservatively rounded toward +inf.
    bound_i32:  (L,) int32 — the same bound where it fits the int32
                decision range (clipped otherwise).
    decidable:  (L,) bool — True iff bound_i32 is the exact bound, i.e.
                int32 margin comparisons at this level are sound.
    """

    partial: jax.Array
    tail_bound: jax.Array
    bound_i32: jax.Array
    decidable: jax.Array


class LevelBounds(NamedTuple):
    """Per-level tail bounds in the three dtypes consumers need."""

    f32: jax.Array        # (L,) float32, rounded toward +inf
    i32: jax.Array        # (L,) int32, clipped at the decision range
    decidable: jax.Array  # (L,) bool, True iff i32 is exact
    exact: tuple          # Python ints (host-side reporting)


def _f32_up(b: int) -> np.float32:
    """Smallest float32 >= the exact integer bound (inf if out of range)."""
    v = np.float32(b)
    if np.isinf(v):
        return v
    # float32 -> exact int comparison in unbounded Python ints
    if int(v) < b:
        v = np.nextafter(v, np.float32(np.inf))
    return v


def level_bounds(d: int, log2_radix: int, k: int,
                 levels: int | None = None) -> LevelBounds:
    """Hard tail bounds after each of the first `levels` MSDF levels."""
    n_levels = len(msdf_levels(d)[:levels])
    exact = tuple(tail_bound(d, t + 1, log2_radix, k)
                  for t in range(n_levels))
    f32 = np.asarray([_f32_up(b) for b in exact], np.float32)
    fits = np.asarray([b <= _BOUND_CLIP for b in exact], bool)
    i32 = np.asarray([b if f else _BOUND_CLIP for b, f in zip(exact, fits)],
                     np.int32)
    return LevelBounds(jnp.asarray(f32), jnp.asarray(i32),
                       jnp.asarray(fits), exact)


# ------------------------------------------------------- streaming emitter
def _contract_k(x) -> int:
    """Contraction length of a raw operand or a pre-stacked PlaneOperands."""
    return x.k if isinstance(x, PlaneOperands) else x.shape[-1]


def _lhs_lead(aq) -> tuple[int, ...]:
    """Leading (…, M) output shape contributed by the LHS operand."""
    return aq.stack.shape[:-1] if isinstance(aq, PlaneOperands) \
        else aq.shape[:-1]


def _rhs_n(bq) -> int:
    return bq.stack.shape[-1] if isinstance(bq, PlaneOperands) \
        else bq.shape[-1]


def _streaming_operands(aq, bq, n_bits, log2_radix):
    """Zero-padded raw-digit plane stacks for the fixed-width level scan.

    Either operand may already be a :class:`~repro.core.quant.PlaneOperands`
    (e.g. the load-time weight-stack cache): its window stack is consumed
    directly — bit-identical to inline extraction, which produces the
    very same stack — so per-step streaming does no plane extraction at
    all for pre-stacked sides.  A stack built for a different digit
    config would walk the level schedule wrong, so mismatches raise
    rather than silently mis-slice.
    """
    d = plane_count(n_bits, log2_radix)
    for op, want, other in ((aq, "lhs", bq), (bq, "rhs", aq)):
        if isinstance(op, PlaneOperands) \
                and not op.matches(n_bits, log2_radix, side=want):
            other_desc = other.describe() if isinstance(other, PlaneOperands) \
                else f"array(shape={tuple(other.shape)}, dtype={other.dtype})"
            raise ValueError(
                f"{op.describe()} cannot feed the {want} slot "
                f"of a streaming walk with n_bits={n_bits}, "
                f"log2_radix={log2_radix} (other operand: {other_desc}); "
                f"re-prepare the stack for this config")
    if isinstance(aq, PlaneOperands):
        a_pad = aq.window_stack()
    else:
        k = aq.shape[-1]
        a_stack = stack_planes_lhs(aq, n_bits, log2_radix, shifted=False)
        a_pad = jnp.pad(a_stack,
                        [(0, 0)] * (a_stack.ndim - 1) + [(0, (d - 1) * k)])
    if isinstance(bq, PlaneOperands):
        b_pad = bq.window_stack()
    else:
        k = bq.shape[0]
        b_rev = stack_planes_rhs(bq, n_bits, log2_radix, shifted=False)
        b_pad = jnp.pad(b_rev,
                        [(0, (d - 1) * k)] + [(0, 0)] * (b_rev.ndim - 1))
    return a_pad, b_pad


def _level_walk(d: int, levels: int | None):
    """Per-step (a_off, b_off, s) block offsets of the fixed-width window.

    Level s reads LHS blocks [i_lo, i_lo+D) and RHS (reversed) blocks
    [d-1-s+i_lo, d-1-s+i_lo+D); the window positions past the level's
    true pair range hit zero padding on exactly one side.
    """
    svals = msdf_levels(d)[:levels]
    a_off = np.asarray([max(0, s - d + 1) for s in svals], np.int32)
    b_off = np.asarray([d - 1 - s + a for s, a in zip(svals, a_off)],
                       np.int32)
    return a_off, b_off, np.asarray(svals, np.int32)


def _stream_setup(aq, bq, n_bits, log2_radix):
    """Shared operand prep of the scan and while emitters: zero-padded
    plane stacks, the f32 fast-path decision, and the per-level term
    function.  BOTH control flows call the identical ``term(ao, bo)`` —
    same slices, same dot, same dtypes — which is what makes the
    while-loop path bit-identical to the scan oracle."""
    d = plane_count(n_bits, log2_radix)
    k = _contract_k(aq)
    a_pad, b_pad = _streaming_operands(aq, bq, n_bits, log2_radix)
    # the fixed window spans up to D real pairs -> the f32 exactness guard
    # must hold for a depth-D*K contraction of raw digits
    use_f32 = _f32_dot_exact(k, d, log2_radix)
    if use_f32:
        a_pad = a_pad.astype(jnp.float32)
        b_pad = b_pad.astype(jnp.float32)
    w = d * k

    def term(ao, bo):
        a_l = jax.lax.dynamic_slice_in_dim(a_pad, ao * k, w,
                                           axis=a_pad.ndim - 1)
        b_l = jax.lax.dynamic_slice_in_dim(b_pad, bo * k, w, axis=0)
        t = jax.lax.dot_general(
            a_l, b_l,
            ((((a_l.ndim - 1),), ((0,))), ((), ())),
            preferred_element_type=jnp.float32 if use_f32 else jnp.int32,
            precision=jax.lax.Precision.HIGHEST if use_f32 else None,
        )
        return t.astype(jnp.int32)

    return term


def streaming_matmul_scan(
    aq: jax.Array,
    bq: jax.Array,
    fold: Callable | None = None,
    init=None,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
    emit: bool = False,
):
    """Scan the per-level MSDF prefix stream; never stacks levels itself.

    ``fold(carry, partial, level_index) -> carry`` consumes each prefix
    as it is emitted (the software analogue of a downstream online unit
    reading digits before the producer finishes); the scan carries only
    the ``(…, M, N)`` accumulator plus the fold's own state.  With
    ``emit=True`` the per-level prefixes are also returned stacked
    (``(L, …, M, N)`` — only for consumers that genuinely need the full
    snapshot history, e.g. `progressive_matmul`).

    Returns ``(final_partial, final_fold_carry, stack_or_None)``.  Each
    prefix is bit-identical to ``l2r_matmul_int_stacked(..., levels=t+1)``.

    This fixed-length scan is the ORACLE of the streaming subsystem: it
    always executes every requested level.  :func:`streaming_matmul_while`
    runs the same walk as a ``lax.while_loop`` that stops once the fold's
    decision state says no more digits are needed.

    Either operand may be a pre-stacked
    :class:`~repro.core.quant.PlaneOperands` (raw-digit layout) — the
    stream is bit-identical to inline extraction.
    """
    d = plane_count(n_bits, log2_radix)
    a_off, b_off, svals = _level_walk(d, levels)
    n_steps = int(svals.shape[0])
    acc0 = jnp.zeros((*_lhs_lead(aq), _rhs_n(bq)), jnp.int32)
    if n_steps == 0:  # levels=0: empty MSDF prefix
        empty = jnp.zeros((0, *acc0.shape), jnp.int32) if emit else None
        return acc0, init, empty

    term = _stream_setup(aq, bq, n_bits, log2_radix)

    def step(carry, xs):
        acc, fold_c = carry
        ao, bo, s, idx = xs
        acc = acc + (term(ao, bo) << (log2_radix * s))
        if fold is not None:
            fold_c = fold(fold_c, acc, idx)
        return (acc, fold_c), (acc if emit else None)

    xs = (jnp.asarray(a_off), jnp.asarray(b_off), jnp.asarray(svals),
          jnp.arange(n_steps, dtype=jnp.int32))
    (acc, fold_c), ys = jax.lax.scan(step, (acc0, init), xs)
    return acc, fold_c, ys


def _while_emitter(term, a_off, b_off, svals, log2_radix, acc0,
                   fold, init, done_fn):
    """Shared ``lax.while_loop`` harness of the early-exit emitters (GEMM
    and fused conv): one significance level per iteration — ``term(ao,
    bo)`` shifted to its level and accumulated, the fold applied, the
    done predicate polled in the loop condition.  Returns ``(levels_run,
    acc, fold_carry)``."""
    n_steps = int(svals.shape[0])
    a_off = jnp.asarray(a_off)
    b_off = jnp.asarray(b_off)
    svals = jnp.asarray(svals)

    def cond(state):
        t, _, fold_c = state
        running = t < n_steps
        if done_fn is not None:
            running = running & ~done_fn(fold_c)
        return running

    def body(state):
        t, acc, fold_c = state
        acc = acc + (term(a_off[t], b_off[t]) << (log2_radix * svals[t]))
        if fold is not None:
            fold_c = fold(fold_c, acc, t)
        return t + 1, acc, fold_c

    return jax.lax.while_loop(cond, body, (jnp.int32(0), acc0, init))


def streaming_matmul_while(
    aq: jax.Array,
    bq: jax.Array,
    fold: Callable | None = None,
    init=None,
    done_fn: Callable | None = None,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
):
    """Early-exit streaming emitter: the SAME level walk as
    :func:`streaming_matmul_scan`, run as a ``lax.while_loop`` that stops
    as soon as ``done_fn(fold_carry)`` (a scalar bool — typically "every
    row in the tile has decided") becomes True, so saved levels are saved
    wall-clock *inside* the fused computation, not just skipped follow-up
    passes.

    The loop body is the identical per-level arithmetic of the scan (same
    slices, same dot, same order), so after ``levels_run`` iterations the
    accumulator is bit-identical to the scan's prefix at that depth — and
    since ``done_fn`` only reads the fold state the scan would have
    produced, the exit level itself is bit-identical too.  With
    ``done_fn=None`` the loop runs every level (control-flow-only change;
    final result bit-identical to the scan and the stacked schedule).

    Returns ``(partial, fold_carry, levels_run)``: ``partial`` is the
    prefix after ``levels_run`` levels (== the full result iff the stream
    was exhausted), ``levels_run`` the number of levels actually executed.
    """
    d = plane_count(n_bits, log2_radix)
    a_off, b_off, svals = _level_walk(d, levels)
    n_steps = int(svals.shape[0])
    acc0 = jnp.zeros((*_lhs_lead(aq), _rhs_n(bq)), jnp.int32)
    if n_steps == 0:  # levels=0: empty MSDF prefix
        return acc0, init, jnp.int32(0)

    term = _stream_setup(aq, bq, n_bits, log2_radix)
    t, acc, fold_c = _while_emitter(term, a_off, b_off, svals, log2_radix,
                                    acc0, fold, init, done_fn)
    return acc, fold_c, t


@partial(jax.jit,
         static_argnames=("n_bits", "log2_radix", "levels", "early_exit"))
def l2r_matmul_int_streaming(
    aq: jax.Array,
    bq: jax.Array,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
    early_exit: bool = False,
) -> jax.Array:
    """Final (or `levels`-truncated) result via the streaming schedule.

    Bit-identical to `l2r_matmul_int_stacked`; carries only the running
    accumulator — the dispatcher's ``schedule="streaming"`` jnp entry.
    ``early_exit=True`` runs the while-loop emitter instead of the fixed
    scan: with no consumer decision state it still executes every level
    (control-flow-only — the mode consumers with a fold terminate early).
    """
    if early_exit:
        acc, _, _ = streaming_matmul_while(aq, bq, None, None, None,
                                           n_bits, log2_radix, levels)
        return acc
    acc, _, _ = streaming_matmul_scan(aq, bq, None, None, n_bits,
                                      log2_radix, levels)
    return acc


@partial(jax.jit, static_argnames=("n_bits", "log2_radix", "levels"))
def progressive_matmul(
    aq: jax.Array,
    bq: jax.Array,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
) -> ProgressiveResult:
    """Full per-level snapshot stack of the MSDF stream.

    Built on the same streaming scan the serving consumers fold over;
    the ``(L, …, M, N)`` stack exists only because this API returns it
    (tests/benchmarks) — early-exit consumers use
    :func:`streaming_matmul_scan` / :func:`streaming_argmax` instead.
    """
    bounds = level_bounds(plane_count(n_bits, log2_radix), log2_radix,
                          _contract_k(aq), levels)
    _, _, stack = streaming_matmul_scan(aq, bq, None, None, n_bits,
                                        log2_radix, levels, emit=True)
    return ProgressiveResult(partial=stack, tail_bound=bounds.f32,
                             bound_i32=bounds.i32, decidable=bounds.decidable)


# ------------------------------------------------------ decision machinery
def streaming_argmax(
    xq: jax.Array,
    wq: jax.Array,
    xs: jax.Array,
    ws: jax.Array,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
    bias: jax.Array | None = None,
    out_dtype=jnp.float32,
    safety: float = 1e-5,
    early_exit: bool = False,
    mesh=None,
    policy: LevelPolicy | None = None,
):
    """Stream a quantized classifier/LM-head matmul, committing the argmax
    of the *dequantized* scores at the earliest sound level.

    xq (M, K) int row activations with per-row scales xs (M, 1); wq (K, N)
    int weights with per-out-channel scales ws (1, N) — either side may
    instead be a pre-stacked :class:`~repro.core.quant.PlaneOperands`
    (the ``QuantizedWeights.planes`` load-time cache for wq), which skips
    per-call plane extraction with a bit-identical stream.  ``levels``
    truncates the stream exactly like every other `levels` in the stack
    (the final prefix then equals the truncated one-shot matmul).

    The decision runs in the scaled domain — per-entry bound
    ``tail * xs * ws`` (per-channel weight scales mean a scalar int
    margin test would be unsound) — widened by two float32 slack terms:
    a relative ``safety`` on the bound itself, and a per-row absolute
    term of a few ulps of the LARGEST score magnitude, because the
    rounding error of ``int32 partial -> f32 * scales`` scales with the
    score, not with the (possibly much smaller) tail bound.  Rows never
    decided early fall back to the final argmax, so the committed index
    ALWAYS equals the full-precision (or `levels`-truncated) argmax.

    ``early_exit=True`` runs the while-loop emitter: the level loop STOPS
    once every row has decided, so the committed tokens and exit levels
    (bit-identical to the scan path) come with actual wall-clock savings
    inside the fused computation.  The returned ``logits`` are then the
    dequantized prefix at the exit level — every committed row's argmax
    equals the full argmax (that is the decision guarantee), but the logit
    VALUES carry the undigested tail; consumers that need full-depth logit
    values keep ``early_exit=False``.

    Returns ``(logits (M, N) out_dtype, tok (M,) int32, exit_level (M,)
    int32)`` where exit_level counts levels actually needed (L-1 = full
    stream).  With ``early_exit=False`` the ``logits`` reproduce
    kernels/l2r_gemm ``l2r_matmul_f`` dequantization bit-for-bit (same op
    order), so downstream argmaxes agree with the non-streaming path.

    **Sharded walk.**  When a mesh is installed (``sharding.ctx``, or the
    explicit ``mesh=`` override) whose ``model`` axis divides N and/or
    whose data axes divide M, the walk runs as the ``shard_map``ped
    consensus emitter (:func:`_streaming_argmax_sharded`): the RHS plane
    stack is vocab-sharded, the LHS stack batch-sharded, every level's
    decision is reached from per-shard (max, first-index, runner-up)
    triples reduced across ``model``, and the early-exit ``done_fn``
    reaches global consensus via a ``psum`` of per-row decided flags —
    the loop stops at the fleet-wide slowest row.  Prefixes, committed
    decisions, and exit levels are bit-identical to this single-device
    path (the sharded accumulator is integer-exact per vocab shard, the
    decision floats are elementwise, and every cross-shard reduction is
    an exact max/min/sum of the same values).

    **Per-row policy.**  ``policy`` (core/policy.py:LevelPolicy, one row
    per M) replaces the batch-global decision with per-row precision
    classes: ``bounded(0)`` rows reproduce this walk bit for bit,
    ``budget(L)`` rows force-commit at level L with the token a
    ``levels=L`` run would commit, ``exact`` rows never early-commit
    (full-depth fallback).  Rows are decision-independent, so a mixed
    batch commits each row exactly as a single-class batch would;
    ``early_exit`` still picks the while-loop emitter, which stops at
    the slowest row (an exact row keeps the loop running full depth).
    """
    axes = sharded_walk_axes(_lhs_lead(xq), _rhs_n(wq), mesh)
    if axes is not None:
        return _streaming_argmax_sharded(
            xq, wq, xs, ws, n_bits, log2_radix, levels, bias, out_dtype,
            safety, early_exit, policy, *axes)
    d = plane_count(n_bits, log2_radix)
    bounds = level_bounds(d, log2_radix, _contract_k(xq), levels)
    n_levels = int(bounds.f32.shape[0])
    wsr = ws.reshape(1, -1).astype(jnp.float32)
    xsf = xs.astype(jnp.float32)
    m = _lhs_lead(xq)[-1]
    if policy is not None:
        assert policy.mode.shape == (m,), \
            f"policy rows {policy.mode.shape} != batch rows ({m},)"
    fold, init, done_fn, finalize = head_walk_machinery(
        bounds.f32, xsf, wsr, bias, out_dtype, safety=safety,
        n_levels=n_levels, m_global=m, n_total=_rhs_n(wq),
        policy=policy, early_exit=early_exit)
    if early_exit:
        acc, carry, _ = streaming_matmul_while(
            xq, wq, fold, init, done_fn, n_bits, log2_radix, levels)
    else:
        acc, carry, _ = streaming_matmul_scan(
            xq, wq, fold, init, n_bits, log2_radix, levels)
    return finalize(acc, carry)


# ------------------------------------------------- sharded streaming walk
def sharded_walk_axes(lead: tuple[int, ...], n: int, mesh=None):
    """Mesh routing of the streaming walk: ``(mesh, dp_axes, model_axis)``
    when the sharded consensus emitter applies, ``None`` otherwise.

    ``mesh`` defaults to the installed context mesh (sharding/ctx.py).
    The walk shards the batch (M) over the data-parallel axes and the
    vocab (N) over ``model``; an axis that does not divide its dim is
    dropped (that side replicates — still correct, the other side still
    shards), and when neither axis is usable (or the mesh is trivial)
    the caller takes the plain single-device path.  Only 2-D tiles
    stream sharded (the serving consumers all reshape to (M, K)).
    """
    from repro.sharding import ctx

    mesh = mesh if mesh is not None else ctx.get_mesh()
    if mesh is None or len(lead) != 1:
        return None
    m = lead[0]
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = ctx.mesh_axis_size(mesh, dp) if dp else 1
    if dp_size <= 1 or m % dp_size:
        dp = ()
    model = "model" if "model" in mesh.axis_names else None
    if model is not None and (mesh.shape["model"] <= 1
                              or n % mesh.shape["model"]):
        model = None
    if not dp and model is None:
        return None
    return mesh, dp, model


def _streaming_argmax_sharded(xq, wq, xs, ws, n_bits, log2_radix, levels,
                              bias, out_dtype, safety, early_exit, policy,
                              mesh, dp, model_ax):
    """The ``shard_map``ped consensus level walk behind
    :func:`streaming_argmax` (see its docstring for routing).

    Layout: the LHS activation stack is batch-sharded over the ``dp``
    axes, the RHS weight stack (raw or the ``QuantizedWeights.planes``
    cache) vocab-sharded over ``model``; K — the contraction — is never
    sharded, so each device's accumulator tile is the integer-exact
    column/row slice of the single-device accumulator at every level
    (the f32 fast path is guarded exact, the int32 path is exact
    arithmetic — neither depends on reduction order).

    Per-level global decision, from per-shard triples reduced over
    ``model`` (every reduction an exact max/min of identical floats, so
    decided/argmax/exit-level are bit-identical to the oracle):

      * global top = ``pmax`` of local maxima; first-occurrence index =
        ``pmin`` over shards of (local first-achiever index, or N);
      * the top's lower confidence bound comes from the one shard that
        owns the winning column (``pmax`` of the owner's value, -inf
        elsewhere); the runner-up upper bound is the ``pmax`` of each
        shard's max-excluding-the-winner;
      * decided rows then update tok/lv exactly as the local fold does.

    Early-exit consensus: the fold ``psum``s the per-row decided flags
    over the data axes (rows are replicated across ``model``; the
    decision scalars already agree there) and the while loop's
    ``done_fn`` reads that scalar — every device stops at the SAME
    level, the fleet-wide slowest row's, which is exactly where the
    single-device while loop stops for the full batch.

    The decision fold itself is core/policy.py:head_walk_machinery —
    the SAME fold as the local walk, with the cross-shard reductions
    (pmax/pmin over ``model``, the consensus psum over ``dp``) switched
    on by the axis names.  Per-row policies shard their rows over the
    data axes like every other per-row carry.
    """
    from jax.sharding import PartitionSpec as P

    d = plane_count(n_bits, log2_radix)
    bounds = level_bounds(d, log2_radix, _contract_k(xq), levels)
    n_levels = int(bounds.f32.shape[0])
    m = _lhs_lead(xq)[-1]
    n_total = _rhs_n(wq)
    wsr = ws.reshape(1, -1).astype(jnp.float32)
    xsf = xs.astype(jnp.float32)
    has_bias = bias is not None
    b_arr = bias.reshape(-1) if has_bias else jnp.zeros((n_total,), jnp.float32)
    dp_spec = dp if dp else None
    if policy is not None:
        assert policy.mode.shape == (m,), \
            f"policy rows {policy.mode.shape} != batch rows ({m},)"

    def walk(bf32, xq_s, wq_s, xsf_s, wsr_s, bias_s, *maybe_policy):
        # the walk-level named scope prefixes every op_name inside the
        # trace (incl. head_walk_machinery's l2r_coll_* reduction tags),
        # so the sharding auditor can attribute each collective of the
        # partitioned module to this declared consensus schedule
        with jax.named_scope(CONSENSUS_WALK_SCOPE):
            policy_s = maybe_policy[0] if maybe_policy else None
            fold, init, done_fn, finalize = head_walk_machinery(
                bf32, xsf_s, wsr_s, bias_s if has_bias else None, out_dtype,
                safety=safety, n_levels=n_levels, m_global=m, n_total=n_total,
                policy=policy_s, early_exit=early_exit,
                model_ax=model_ax, dp=dp)
            if early_exit:
                acc, carry, _ = streaming_matmul_while(
                    xq_s, wq_s, fold, init, done_fn,
                    n_bits, log2_radix, levels)
            else:
                acc, carry, _ = streaming_matmul_scan(
                    xq_s, wq_s, fold, init, n_bits, log2_radix, levels)
            # dequantize + fallback exactly as the single-device path:
            # the out_dtype round-trip must match bit for bit
            return finalize(acc, carry)

    args = [bounds.f32, xq, wq, xsf, wsr, b_arr]
    in_specs = [P(None), P(dp_spec, None), P(None, model_ax),
                P(dp_spec, None), P(None, model_ax), P(model_ax)]
    if policy is not None:
        args.append(policy)
        in_specs.append(LevelPolicy(P(dp_spec), P(dp_spec), P(dp_spec)))
    fn = jax.shard_map(
        walk, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(dp_spec, model_ax), P(dp_spec), P(dp_spec)),
        check_vma=False)
    return fn(*args)


def earliest_decision_level(result: ProgressiveResult) -> jax.Array:
    """Earliest MSDF level at which greedy argmax over the last axis is
    already decided (top-1 margin exceeds twice the tail bound).

    The margin and the bound are compared in ONE dtype (int32); levels
    whose exact bound does not fit the int32 decision range carry
    ``decidable=False`` and are skipped (conservative — a lossy float
    comparison could declare an unsound early exit).  Returns (...,)
    int32 per row; value L-1 means "needed the full stream".
    """
    partial = result.partial  # (L, ..., N)
    extra = (1,) * (partial.ndim - 2)
    b32 = result.bound_i32.reshape((-1,) + extra)       # (L, 1, ..., 1)
    ok = result.decidable.reshape((-1,) + extra)
    top2 = jax.lax.top_k(partial, 2)[0]  # (L, ..., 2)
    margin = top2[..., 0] - top2[..., 1]  # int32, exact
    decided = ok & (margin > 2 * b32)  # 2*b32 <= 2^31-2: no overflow
    lv = jnp.argmax(decided, axis=0)  # first True (0 if none True!)
    any_decided = jnp.any(decided, axis=0)
    return jnp.where(any_decided, lv, partial.shape[0] - 1).astype(jnp.int32)
