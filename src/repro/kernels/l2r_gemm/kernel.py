"""Pallas TPU kernels: L2R digit-plane GEMM (the composite IPU on the MXU).

Two schedules are provided:

``l2r_gemm_pallas`` — the original pair-loop schedule (one small MXU pass
per digit-plane pair, D² passes per K-step, planes re-extracted in VMEM
every step).  Kept as the comparison baseline and a second oracle.

``l2r_gemm_pallas_stacked`` — the production **significance-level plane
stacking** schedule.  Hardware mapping:

  * digit planes are extracted ONCE, outside the grid, and pre-shifted to
    their significance (``A'_i = A_i << b*i``, ``B'_j = B_j << b*j`` —
    each shifted plane is a bit-field of the operand, so it stays in the
    operand's n-bit dtype).  The planes are stacked along the contraction
    axis: ``A_stack (M, D*K)`` ascending, ``B_rev (D*K, N)`` descending;
  * the paper's composite counter circuit -> ONE K-stacked MXU
    contraction per significance level ``s = i + j``: the level's pair
    set {(i, s-i)} is a contiguous column slice of ``A_stack`` against a
    contiguous row slice of ``B_rev``, so the D² pair matmuls collapse to
    2D-1 level matmuls and the kernel inner loop is a single
    ``acc += A_blk @ B_blk`` per grid step — no plane extraction, no
    shifts (the pre-shift makes every product land at its final weight);
  * the MSDF schedule -> a static (level, k-block) walk enumerated
    host-side and fed through **scalar prefetch**: two int32 index
    vectors give each grid step its block coordinates into the stacked
    operands, and the BlockSpec index maps read them (this is the
    block-sparse / grouped-matmul Pallas idiom);
  * PPR/residual carry-save pair -> the int32 VMEM accumulator (carry-
    free at matmul granularity);
  * progressive precision (``levels``) -> truncating the schedule vector
    to the top levels; the processed pair set is identical to
    ``online.msdf_pairs(d, levels)``, so truncated results are
    bit-identical to the pair loop (validated against
    ``core/online.py:tail_bound`` semantics in the tests).

VMEM budget, stacked schedule: the default (bm, bk, bn) = (128, 256, 128)
  holds an A block of 32 KiB (int8), a B block of 32 KiB and an int32
  accumulator of 64 KiB (~256 KiB double-buffered) << 16 MiB/core — 3x
  leaner than the pair-loop kernel, which additionally held 2 x D int32
  plane workspaces (256 KiB at radix 4).  The fused conv chooses its tiles
  from each tap GEMM's shape instead (:func:`stacked_tiles`): the whole
  plane chunk as ``bk``, the padded output width as ``bn`` (each capped at
  512), and the largest row tile dividing the padded rows whose blocks fit
  the scoped VMEM, so a large feature map walks a few thousand grid steps
  rather than hundreds of thousands of 128-row ones.  A kernel whose
  blocks (:func:`stacked_vmem_bytes`) take over half the scoped VMEM asks
  Mosaic for twice their size.  M/N tiles are MXU-aligned (multiples of
  128); the int8 K block is a multiple of 32 lanes.  HBM traffic: the
  stacked operands are D x the int8 payload, but each block is read
  exactly once per output tile — the same per-pair traffic the pair loop
  paid, now amortized over MXU passes that are D x deeper on average.

Backend selection (jnp / pallas-interpret / pallas-tpu) lives in ops.py.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.online import msdf_level_slices, msdf_pairs
from repro.core.quant import stack_planes_lhs, stack_planes_rhs

__all__ = ["l2r_gemm_pallas", "l2r_gemm_pallas_stacked",
           "l2r_gemm_pallas_stacked_planes", "l2r_gemm_pallas_streaming",
           "l2r_gemm_pallas_streaming_planes", "stacked_schedule",
           "stacked_tiles", "stacked_vmem_bytes", "streaming_schedule"]

# A TPU v5e core's VMEM: the scoped default a kernel gets without asking,
# and all of it, the most a kernel may ask for.
SCOPED_VMEM_BYTES = 16 * 1024 * 1024
VMEM_BYTES = 128 * 1024 * 1024
# stacked_tiles keeps the blocks within the scoped default; the kernel
# then asks for twice their size, leaving the compiler room for the dot's
# int32 result and its own scratch.
TILE_VMEM_BUDGET = SCOPED_VMEM_BYTES
# Caps on the chosen tiles.  Rows: on a v5e, a conv1 tap at bm 8192 runs
# within 2% of bm 12544 and 11% under bm 4096 (sweep in PERF.md §5).
TILE_BM_CAP = 8192
TILE_KN_CAP = 512


# --------------------------------------------------------------- pair loop
def _plane(x: jax.Array, i: int, n_planes: int, log2_radix: int) -> jax.Array:
    """Digit plane i of an int8 tile, as int8 for the MXU.

    The shifts run in an int32 workspace (exact for 2's complement); a
    digit is at most ``log2_radix`` bits plus sign, so it narrows back
    to int8 losslessly.  Mosaic refuses int32 operands to the MXU dot.
    """
    xi = x.astype(jnp.int32)
    if i == n_planes - 1:
        p = xi >> (log2_radix * i)  # signed top digit
    else:
        p = (xi >> (log2_radix * i)) & ((1 << log2_radix) - 1)
    return p.astype(jnp.int8)


def _l2r_gemm_kernel(
    a_ref, b_ref, o_ref, acc_ref,
    *, pairs: Sequence[tuple[int, int]], log2_radix: int, n_planes: int,
    k_steps: int,
):
    """One (bm, bn) output tile; grid = (M/bm, N/bn, K/bk), K innermost."""
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]  # (bm, bk) int8
    b = b_ref[...]  # (bk, bn) int8

    # MSDF-ordered composite accumulation: one MXU pass per plane pair.
    acc = acc_ref[...]
    for (i, j) in pairs:
        ai = _plane(a, i, n_planes, log2_radix)
        bj = _plane(b, j, n_planes, log2_radix)
        term = jax.lax.dot_general(
            ai, bj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        acc = acc + (term << (log2_radix * (i + j)))
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "log2_radix", "levels", "bm", "bk", "bn", "interpret"),
)
def l2r_gemm_pallas(
    aq: jax.Array,
    bq: jax.Array,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
    bm: int = 128,
    bk: int = 256,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Pair-loop MSDF GEMM (baseline). aq: (M, K) int8, bq: (K, N) -> int32.

    Shapes must be multiples of the block sizes (ops.py pads — zero
    padding is exact for matmul).  `interpret=True` runs the kernel body
    on the CPU for validation.  Operands wider than 8 bits are refused
    at dispatch (ops.py:resolve_backend): their digit planes would not
    be int8 tiles.
    """
    m, k = aq.shape
    k2, n = bq.shape
    assert k == k2, (aq.shape, bq.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{k})x({k2},{n}) not padded to blocks ({bm},{bk},{bn})"
    )
    d = n_bits // log2_radix
    pairs = tuple(msdf_pairs(d, levels))
    k_steps = k // bk

    kernel = functools.partial(
        _l2r_gemm_kernel,
        pairs=pairs, log2_radix=log2_radix, n_planes=d, k_steps=k_steps,
    )
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(aq, bq)


# ------------------------------------------------------ level-stacked
def stacked_schedule(
    d: int, k_blocks: int, levels: int | None
) -> tuple[np.ndarray, np.ndarray]:
    """Static (level, k-block) walk of the stacked operands, MSDF order.

    Returns two int32 vectors of length T = n_pairs(levels) * k_blocks:
    ``a_blocks[t]`` is the block-column into A_stack (plane i, k-chunk c
    -> i * k_blocks + c) and ``b_blocks[t]`` the block-row into B_rev
    (plane j = s - i lives at reversed offset (d-1-j) * k_blocks).
    Consumed via scalar prefetch by the stacked kernel's index maps.
    """
    a_blocks: list[int] = []
    b_blocks: list[int] = []
    for (s, i_lo, i_hi) in msdf_level_slices(d, levels):
        for i in range(i_lo, i_hi + 1):
            for c in range(k_blocks):
                a_blocks.append(i * k_blocks + c)
                b_blocks.append((d - 1 - s + i) * k_blocks + c)
    return (np.asarray(a_blocks, np.int32), np.asarray(b_blocks, np.int32))


def stacked_vmem_bytes(bm: int, bk: int, bn: int) -> int:
    """VMEM the stacked kernel's blocks take: the double-buffered int8 A
    (bm, bk) and B (bk, bn) operand blocks, the double-buffered int32
    (bm, bn) output block and the int32 accumulator."""
    return 2 * (bm * bk + bk * bn) + 3 * bm * bn * 4


def _largest_tile(dim: int, cap: int, fits=lambda t: True) -> int:
    """Largest multiple of 128 that divides ``dim``, is at most ``cap``
    and ``fits``; 128 where none larger does."""
    return max((t for t in range(128, min(dim, cap) + 1, 128)
                if dim % t == 0 and fits(t)), default=128)


def stacked_tiles(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(bm, bk, bn) of the stacked kernel for an (m, D*k) x (D*k, n) GEMM.

    ``m``, ``k`` (one plane chunk) and ``n`` are padded to multiples of
    128.  ``bk`` and ``bn`` take the whole chunk and the whole width where
    they fit the cap, so a tap walks one k-block per plane pair; ``bm`` is
    the largest row tile that divides ``m`` (no row is padded past 128)
    and keeps :func:`stacked_vmem_bytes` within the VMEM budget.  A pure
    function of shape: every tile divides its dimension.
    """
    assert m % 128 == 0 and k % 128 == 0 and n % 128 == 0, (m, k, n)
    bk = _largest_tile(k, TILE_KN_CAP)
    bn = _largest_tile(n, TILE_KN_CAP)
    bm = _largest_tile(m, TILE_BM_CAP, lambda t: stacked_vmem_bytes(
        t, bk, bn) <= TILE_VMEM_BUDGET)
    return bm, bk, bn


def _l2r_stacked_kernel(a_idx_ref, b_idx_ref, a_ref, b_ref, o_ref, acc_ref,
                        *, t_steps: int):
    """One (bm, bn) output tile; grid = (M/bm, N/bn, T), schedule innermost.

    The whole MSDF structure lives in the prefetched index vectors: the
    body is a single int8 MXU pass per step — ``acc += A_blk @ B_blk`` —
    with no plane extraction and no shifts (operands are pre-shifted).
    """
    del a_idx_ref, b_idx_ref  # consumed by the BlockSpec index maps

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == t_steps - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "log2_radix", "levels", "bm", "bk", "bn",
                     "interpret", "name"),
)
def l2r_gemm_pallas_stacked_planes(
    a_stack: jax.Array,
    b_rev: jax.Array,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
    bm: int = 128,
    bk: int = 256,
    bn: int = 128,
    interpret: bool = False,
    name: str | None = None,
) -> jax.Array:
    """Level-stacked MSDF GEMM over PRE-STACKED plane operands.

    The pre-stacked kernel entry: operands are the already-extracted,
    PRE-SHIFTED plane stacks — ``a_stack (M, D*K)`` ascending
    (quant.py:stack_planes_lhs), ``b_rev (D*K, N)`` descending
    (stack_planes_rhs) — exactly D plane chunks each (no streaming
    window padding), every chunk's K a multiple of ``bk`` and M/N
    multiples of ``bm``/``bn`` (ops.py block-pads per chunk).  Callers
    that feed one tensor through many GEMMs (the fused conv's kh*kw
    taps, per-decode-step weight matmuls) extract planes once and call
    this entry per GEMM — the hoist the jnp backend already performs,
    now available to the TPU kernel (ROADMAP follow-up).

    ``name`` tags the kernel in device traces: the call's HLO
    instruction becomes ``l2r_gemm_pallas_stacked_planes_<name>`` (the
    caller's layer), where it is otherwise this function's name.
    """
    m, dk = a_stack.shape
    dk2, n = b_rev.shape
    d = n_bits // log2_radix
    assert dk == dk2 and dk % d == 0, (a_stack.shape, b_rev.shape, d)
    k = dk // d
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"plane stacks ({m},{d}x{k})x({d}x{k},{n}) not padded to blocks "
        f"({bm},{bk},{bn})"
    )
    k_blocks = k // bk
    a_idx, b_idx = stacked_schedule(d, k_blocks, levels)
    t_steps = int(a_idx.shape[0])
    if t_steps == 0:  # levels=0: empty MSDF prefix
        return jnp.zeros((m, n), jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // bm, n // bn, t_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t, ai, bi: (i, ai[t])),
            pl.BlockSpec((bk, bn), lambda i, j, t, ai, bi: (bi[t], j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t, ai, bi: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    # blocks over half the scoped default ask for twice their size
    vmem = 2 * stacked_vmem_bytes(bm, bk, bn)
    params = (pltpu.CompilerParams(vmem_limit_bytes=min(vmem, VMEM_BYTES))
              if vmem > SCOPED_VMEM_BYTES else None)
    return pl.pallas_call(
        functools.partial(_l2r_stacked_kernel, t_steps=t_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        compiler_params=params,
        interpret=interpret,
        name=f"l2r_gemm_pallas_stacked_planes_{name}" if name else None,
    )(jnp.asarray(a_idx), jnp.asarray(b_idx), a_stack, b_rev)


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "log2_radix", "levels", "bm", "bk", "bn", "interpret"),
)
def l2r_gemm_pallas_stacked(
    aq: jax.Array,
    bq: jax.Array,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
    bm: int = 128,
    bk: int = 256,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Level-stacked MSDF GEMM. aq: (M, K), bq: (K, N) small ints -> int32.

    Bit-identical to ``core.l2r_gemm.l2r_matmul_int`` for exact and
    truncated ``levels``.  Shapes must be multiples of the block sizes
    (ops.py pads; zero padding is exact).  Plane extraction happens here,
    once, outside the grid, and the stacks feed the pre-stacked entry
    (:func:`l2r_gemm_pallas_stacked_planes`) — the kernel streams
    pre-shifted plane blocks.
    """
    m, k = aq.shape
    k2, n = bq.shape
    assert k == k2, (aq.shape, bq.shape)
    a_stack = stack_planes_lhs(aq, n_bits, log2_radix)  # (M, D*K)
    b_rev = stack_planes_rhs(bq, n_bits, log2_radix)    # (D*K, N)
    return l2r_gemm_pallas_stacked_planes(
        a_stack, b_rev, n_bits, log2_radix, levels, bm, bk, bn,
        interpret=interpret)


# ------------------------------------------------------------- streaming
def streaming_schedule(
    d: int, k_blocks: int, levels: int | None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The stacked (level, k-block) walk plus each step's level index.

    The block walk IS :func:`stacked_schedule` (same arrays — that is
    what makes per-level prefixes bit-identical to stacked truncation);
    the third vector routes every step's output write to its level's
    snapshot plane."""
    a_blocks, b_blocks = stacked_schedule(d, k_blocks, levels)
    steps_per_level = [(i_hi - i_lo + 1) * k_blocks
                       for (_, i_lo, i_hi) in msdf_level_slices(d, levels)]
    lv_idx = np.repeat(np.arange(len(steps_per_level), dtype=np.int32),
                       steps_per_level)
    return a_blocks, b_blocks, np.asarray(lv_idx, np.int32)


def _l2r_streaming_kernel(a_idx_ref, b_idx_ref, lv_idx_ref, cnt_ref,
                          a_ref, b_ref, o_ref, acc_ref):
    """One (bm, bn) tile of the per-level snapshot stream.

    Same single-MXU-pass body as the stacked kernel; the running
    accumulator is additionally written to the current level's output
    plane every step — when the walk crosses a level boundary the block
    index map moves to the next plane and the last write left behind IS
    that level's prefix snapshot (the revisit-then-advance output idiom:
    per output tile the level index is non-decreasing in t, never
    revisited).

    ``cnt_ref`` is the dynamic level-count scalar: grid steps whose level
    index is >= the count skip BOTH the MXU pass and the output write —
    the grid-level analogue of the jnp while-loop's early exit (the grid
    itself still iterates; a Mosaic grid cannot shrink at runtime, but
    skipped steps cost a scalar compare instead of an MXU pass + HBM
    write)."""
    del a_idx_ref, b_idx_ref  # consumed by the index maps

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(lv_idx_ref[pl.program_id(2)] < cnt_ref[0])
    def _work():
        acc_ref[...] += jax.lax.dot_general(
            a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        o_ref[0] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "log2_radix", "levels", "bm", "bk", "bn",
                     "interpret"),
)
def l2r_gemm_pallas_streaming_planes(
    a_stack: jax.Array,
    b_rev: jax.Array,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
    bm: int = 128,
    bk: int = 256,
    bn: int = 128,
    interpret: bool = False,
    level_count: jax.Array | int | None = None,
) -> jax.Array:
    """Per-level snapshot stream over PRE-STACKED plane operands.

    The streaming analogue of :func:`l2r_gemm_pallas_stacked_planes`:
    operands are the already-extracted PRE-SHIFTED stacks (``a_stack
    (M, D*K)`` ascending, ``b_rev (D*K, N)`` descending, exactly D
    chunks, chunk K padded to ``bk`` and M/N to ``bm``/``bn``), the
    output the ``(L, M, N)`` snapshot stream.  ``level_count`` semantics
    as in :func:`l2r_gemm_pallas_streaming`.
    """
    m, dk = a_stack.shape
    dk2, n = b_rev.shape
    d = n_bits // log2_radix
    assert dk == dk2 and dk % d == 0, (a_stack.shape, b_rev.shape, d)
    k = dk // d
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"plane stacks ({m},{d}x{k})x({d}x{k},{n}) not padded to blocks "
        f"({bm},{bk},{bn})"
    )
    a_idx, b_idx, lv_idx = streaming_schedule(d, k // bk, levels)
    t_steps = int(a_idx.shape[0])
    n_levels = int(lv_idx[-1]) + 1 if t_steps else 0
    if t_steps == 0:  # levels=0: empty MSDF prefix
        return jnp.zeros((0, m, n), jnp.int32)
    if level_count is None:
        level_count = n_levels
    cnt = jnp.asarray(level_count, jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(m // bm, n // bn, t_steps),
        in_specs=[
            pl.BlockSpec((bm, bk),
                         lambda i, j, t, ai, bi, li, ct: (i, ai[t])),
            pl.BlockSpec((bk, bn),
                         lambda i, j, t, ai, bi, li, ct: (bi[t], j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda i, j, t, ai, bi, li, ct: (li[t], i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    return pl.pallas_call(
        _l2r_streaming_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_levels, m, n), jnp.int32),
        interpret=interpret,
    )(jnp.asarray(a_idx), jnp.asarray(b_idx), jnp.asarray(lv_idx), cnt,
      a_stack, b_rev)


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "log2_radix", "levels", "bm", "bk", "bn",
                     "interpret"),
)
def l2r_gemm_pallas_streaming(
    aq: jax.Array,
    bq: jax.Array,
    n_bits: int = 8,
    log2_radix: int = 2,
    levels: int | None = None,
    bm: int = 128,
    bk: int = 256,
    bn: int = 128,
    interpret: bool = False,
    level_count: jax.Array | int | None = None,
) -> jax.Array:
    """Per-level snapshot stream of the stacked MSDF GEMM: (L, M, N) int32.

    Level l of the output is bit-identical to the stacked schedule
    truncated at ``levels=l+1`` — the Pallas realization of the streaming
    emitter (core/progressive.py) for on-TPU progressive serving.  Shapes
    must be multiples of the block sizes (ops.py pads).  Plane extraction
    happens once here and feeds the pre-stacked entry
    (:func:`l2r_gemm_pallas_streaming_planes`).

    ``level_count`` is a DYNAMIC int32 scalar (no recompilation when it
    changes, unlike the static ``levels``): grid steps at levels >= the
    count skip their MXU pass and output write, so a consumer that has
    already decided (e.g. the while-loop early exit on the jnp backend)
    can stop the snapshot stream short at runtime.  Output planes at
    levels >= ``level_count`` are left unwritten (unspecified); planes
    below it are bit-identical to the full run.  ``None`` processes every
    scheduled level."""
    m, k = aq.shape
    k2, n = bq.shape
    assert k == k2, (aq.shape, bq.shape)
    a_stack = stack_planes_lhs(aq, n_bits, log2_radix)  # (M, D*K)
    b_rev = stack_planes_rhs(bq, n_bits, log2_radix)    # (D*K, N)
    return l2r_gemm_pallas_streaming_planes(
        a_stack, b_rev, n_bits, log2_radix, levels, bm, bk, bn,
        interpret=interpret, level_count=level_count)
