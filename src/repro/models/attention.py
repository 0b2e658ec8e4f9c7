"""Attention math: RoPE / M-RoPE, GQA, chunked (flash-style) attention,
full and ring (sliding-window) KV caches.

Memory discipline: train/prefill attention never materializes the full
(S, S) score matrix — a static python loop over query chunks (exact
static KV ranges: no wasted FLOPs on causal/local masks) wraps an inner
lax.scan over KV chunks with online-softmax accumulation.  Decode (q=1)
attends directly against the cache.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.l2r_attention import (attn_scores_stacked,
                                      attn_scores_streaming_while,
                                      quantize_per_vector)
from repro.core.policy import LevelPolicy, attn_walk_machinery
from repro.core.progressive import level_bounds
from repro.core.quant import PlaneOperands, QuantConfig, _symmetric_quant

__all__ = [
    "apply_rope",
    "chunked_attention",
    "decode_attention",
    "attn_exit_tap",
    "KVCache",
    "init_kv_cache",
    "update_kv_cache",
    "write_slots",
    "cache_layer",
    "kv_plane_operands",
]


# ------------------------------------------------- progressive exit-level tap
_EXIT_TAP: list | None = None


@contextlib.contextmanager
def attn_exit_tap():
    """Collect per-call decode-attention exit levels (EAGER calls only).

    Yields a list; every eager ``decode_attention(..., early_exit=True)``
    call inside the context appends its levels-run scalar (int).  The
    tap is a demo/diagnostic hook (examples/progressive_attention.py),
    not an aux output channel — exit levels under ``jit`` are tracers
    with no runtime value, so a TRACED call inside an active tap raises
    ``RuntimeError`` instead of silently recording nothing (run the
    tapped call eagerly, e.g. under ``jax.disable_jit()``).  Call order
    is evaluation order, i.e. layer order for a single decode step.
    """
    global _EXIT_TAP
    prev, records = _EXIT_TAP, []
    _EXIT_TAP = records
    try:
        yield records
    finally:
        _EXIT_TAP = prev


# ----------------------------------------------------------------- RoPE
def _rope_angles(positions: jax.Array, head_dim: int, theta: float) -> jax.Array:
    """positions (...,) -> angles (..., head_dim//2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    return positions.astype(jnp.float32)[..., None] * freqs


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float = 10_000.0,
    mode: str = "standard",
    sections: tuple[int, int, int] = (16, 24, 24),
) -> jax.Array:
    """Rotary embedding.

    x: (B, S, H, dh).  positions: (B, S) for standard RoPE, or (3, B, S)
    for M-RoPE (qwen2-vl: temporal/height/width position streams, each
    rotating its own slice of the frequency spectrum).
    """
    if mode == "none":
        return x
    b, s, h, dh = x.shape
    half = dh // 2
    if mode == "mrope":
        assert positions.shape[0] == 3, "mrope expects (3, B, S) positions"
        angles = _rope_angles(positions, dh, theta)  # (3, B, S, half)
        sec = jnp.cumsum(jnp.asarray(sections))
        idx = jnp.searchsorted(sec, jnp.arange(half), side="right")  # 0/1/2
        angles = jnp.take_along_axis(
            jnp.moveaxis(angles, 0, -1),  # (B, S, half, 3)
            idx[None, None, :, None].astype(jnp.int32),
            axis=-1,
        )[..., 0]  # (B, S, half)
    else:
        angles = _rope_angles(positions, dh, theta)  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)  # (B, S, 1, half)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ------------------------------------------------------ chunked attention
def _block_scores(q, k, scale, softcap, score_dtype=jnp.float32):
    """q (B, qc, Kv, G, dh), k (B, kc, Kv, dh) -> (B, Kv, G, qc, kc).

    score_dtype=bf16 keeps MXU f32 accumulation but stores score blocks
    (the dominant HBM tensor at long S) in bf16; softmax statistics stay
    f32 downstream."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k, preferred_element_type=score_dtype)
    s = s * jnp.asarray(scale, score_dtype)
    if softcap is not None:
        s = (jnp.tanh(s / softcap) * softcap).astype(score_dtype)
    return s


def default_chunks(sq: int) -> tuple[int, int]:
    """(q_chunk, kv_chunk) balancing HLO size (unrolled q chunks) against
    live score-block memory: ~8 query chunks, 2k KV blocks."""
    q = max(1024, sq // 8)
    kv = max(1024, min(2048, sq // 8))
    return q, kv


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    softcap: float | None = None,
    q_chunk: int | None = None,
    kv_chunk: int | None = None,
    q_offset: int = 0,
    score_dtype=jnp.float32,
    head_shard: bool = False,
    l2r: QuantConfig | None = None,
    levels: int | None = None,
) -> jax.Array:
    """GQA flash-style attention.

    q: (B, Sq, H, dh); k, v: (B, Skv, Kv, dh) with H % Kv == 0.
    Static query-chunk loop -> exact static KV ranges (no masked-out
    FLOPs beyond boundary chunks); inner lax.scan with online softmax.
    ``q_offset``: absolute position of q[0] relative to k[0] (prefill
    continuation); causal masks compare absolute positions.

    ``l2r`` routes the QK^T contraction through the digit-serial score
    walk (core/l2r_attention.py): q rows and k slots quantize with
    per-vector scales (chunking-independent — prefill scores agree with
    any decode-step recomputation of the same tokens), planes are
    extracted ONCE per call, and ``levels`` truncates the MSDF stream
    (None = exact W8A8 scores).  Softmax and PV stay float (the exact
    first cut); quantized scores accumulate in f32 regardless of
    ``score_dtype``.
    """
    b, sq, h, dh = q.shape
    _, skv, kv_heads, _ = k.shape
    g = h // kv_heads
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    dq, dkv = default_chunks(sq)
    q_chunk = min(q_chunk or dq, sq)
    kv_chunk = min(kv_chunk or dkv, skv)
    n_q = (sq + q_chunk - 1) // q_chunk

    # pad KV to a chunk multiple so dynamic_slice never clamps (clamped
    # starts would silently misalign data vs. the position mask).
    pad_kv = (-skv) % kv_chunk
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))

    q = q.reshape(b, sq, kv_heads, g, dh)
    if head_shard:
        # shard attention math on the KV-head dim (uneven counts padded
        # by GSPMD): per-chip score traffic drops by ~n_kv/axis_size and
        # the softmax stays chip-local (§Perf hillclimb A).
        from repro.sharding.ctx import hint_uneven
        q = hint_uneven(q, None, None, "model", None, None)
        k = hint_uneven(k, None, None, "model", None)
        v = hint_uneven(v, None, None, "model", None)
    neg = jnp.float32(-1e30)  # finite sentinel: -inf breeds NaNs in
    #                           fully-masked boundary blocks
    q_po = k_po = qs = ks_t = None
    if l2r is not None:
        # per-vector quantization + ONE plane extraction per call; the
        # seq axes slice through both stacks (plane blocks live on the
        # head dim), so chunk slicing below never re-extracts
        qq, qs = quantize_per_vector(q, l2r)
        kq, ks = quantize_per_vector(k, l2r)
        q_po = PlaneOperands.prepare_lhs(qq, l2r.n_bits, l2r.log2_radix)
        k_po = PlaneOperands.prepare_rhs(kq, l2r.n_bits, l2r.log2_radix,
                                         axis=-1)
        ks_t = ks[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    outs = []
    for qi in range(n_q):
        q_start = qi * q_chunk
        qc = min(q_chunk, sq - q_start)
        q_blk = jax.lax.slice_in_dim(q, q_start, q_start + qc, axis=1)
        if l2r is not None:
            q_blk_po = dataclasses.replace(
                q_po, stack=jax.lax.slice_in_dim(
                    q_po.stack, q_start, q_start + qc, axis=1))
            qs_t = jax.lax.slice_in_dim(
                qs, q_start, q_start + qc, axis=1).transpose(0, 2, 3, 1, 4)
        q_abs_end = q_offset + q_start + qc - 1  # last query position
        # static KV range for this query chunk
        hi = min(skv, q_abs_end + 1) if causal else skv
        lo = 0
        if window is not None:
            lo = max(0, q_offset + q_start - window + 1)
        lo_c, hi_c = lo // kv_chunk, (hi + kv_chunk - 1) // kv_chunk
        kv_idx = jnp.arange(lo_c, hi_c)

        q_pos = q_offset + q_start + jnp.arange(qc)  # (qc,)

        def body(carry, kc_i):
            acc, m, l = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, kc_i * kv_chunk, kv_chunk, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, kc_i * kv_chunk, kv_chunk, axis=1)
            if l2r is None:
                s = _block_scores(q_blk, k_blk, scale, softcap, score_dtype)
            else:
                k_blk_po = dataclasses.replace(
                    k_po, stack=jax.lax.dynamic_slice_in_dim(
                        k_po.stack, kc_i * kv_chunk, kv_chunk, axis=1))
                ks_blk = jax.lax.dynamic_slice_in_dim(
                    ks_t, kc_i * kv_chunk, kv_chunk, axis=ks_t.ndim - 1)
                s_int = attn_scores_stacked(q_blk_po, k_blk_po, l2r.n_bits,
                                            l2r.log2_radix, levels)
                s = s_int.astype(jnp.float32) * qs_t * ks_blk \
                    * jnp.float32(scale)
                if softcap is not None:
                    s = jnp.tanh(s / softcap) * softcap
            kv_pos = kc_i * kv_chunk + jnp.arange(kv_chunk)
            mask = jnp.ones((qc, kv_chunk), bool)
            if causal:
                mask &= kv_pos[None, :] <= q_pos[:, None]
            if window is not None:
                mask &= kv_pos[None, :] > q_pos[:, None] - window
            mask &= kv_pos[None, :] < skv  # tail padding guard
            s = jnp.where(mask[None, None, None], s, jnp.asarray(neg, s.dtype))
            # softmax statistics in f32 regardless of score storage dtype
            m_new = jnp.maximum(m, s.max(-1).astype(jnp.float32))
            p = jnp.exp(s.astype(jnp.float32) - m_new[..., None])
            # zero contributions where the whole row was masked (p == 1
            # only when s == m_new == sentinel; real blocks zero it via
            # alpha, but kill it eagerly to keep l exact):
            p = jnp.where(mask[None, None, None], p, 0.0)
            # materialize p once, in the value dtype (the exp fusion emits
            # it directly); the row-sum accumulates in f32 from that copy
            p = p.astype(v.dtype)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, -1, dtype=jnp.float32)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p, v_blk,
                            preferred_element_type=jnp.float32)
            acc_new = acc * alpha[..., None] + pv
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, kv_heads, g, qc, dh), jnp.float32)
        m0 = jnp.full((b, kv_heads, g, qc), neg, jnp.float32)
        l0 = jnp.zeros((b, kv_heads, g, qc), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), kv_idx)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        outs.append(jnp.moveaxis(out, 3, 1))  # (B, qc, Kv, G, dh)
    o = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return o.reshape(b, sq, h, dh).astype(v.dtype)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    kv_positions: jax.Array,
    q_position: jax.Array,
    *,
    window: int | None = None,
    scale: float | None = None,
    softcap: float | None = None,
    l2r: QuantConfig | None = None,
    levels: int | None = None,
    early_exit: bool = False,
    exit_tol: float = 1e-4,
    k_planes: jax.Array | PlaneOperands | None = None,
    k_scale: jax.Array | None = None,
    policy: LevelPolicy | None = None,
) -> jax.Array:
    """Single-token attention against a (possibly ring) cache.

    q: (B, 1, H, dh); caches: (B, L, Kv, dh); kv_positions: (B, L) int32
    absolute positions (-1 = empty slot); q_position: (B,) int32.

    ``l2r`` routes QK^T through the digit-serial score walk
    (core/l2r_attention.py) with exact softmax + float PV; ``levels``
    truncates the MSDF stream.  ``k_planes``/``k_scale`` feed the
    incrementally plane-stacked KV cache (:func:`update_kv_cache` with a
    quant config): the per-slot key planes/scales are then consumed
    directly — NO per-step plane re-extraction over the history —
    bit-identical to quantizing ``k_cache`` here (both quantize the
    stored cache values with the same per-vector formula).

    ``early_exit=True`` runs the margin-bounded progressive walk: a
    ``lax.while_loop`` over significance levels that stops once every
    (batch, kv-head, group) score row has BOTH its running max decided
    (the argmax margin beats the scaled tail bound —
    core/policy.py:decision_state) and its normalizer pinned (every
    unmasked score known to within ``exit_tol``, so softmax weights are
    stable at the tolerance).  Rows that never decide consume the whole
    stream, making the output exactly the full-depth quantized result;
    decided rows return softmax over the exit-level prefix.  Incompatible
    with ``softcap``.

    ``policy`` (core/policy.py:LevelPolicy, one row per BATCH entry)
    runs the walk with per-row precision classes instead of the
    batch-global knobs: ``bounded(tol)`` rows use their own normalizer
    tolerance (``bounded(exit_tol)`` == the legacy early-exit walk bit
    for bit), ``budget(L)`` rows SNAPSHOT their int32 score prefix at
    level L — their softmax sees exactly the ``levels=L`` scores, bit-
    identical to a truncated run, even when batch-mates stream deeper —
    and ``exact`` rows never early-commit (the loop runs full depth for
    them, output == the full stacked schedule).  Bounded rows keep the
    batch-coupled legacy semantics: their softmax runs over the prefix
    at the GLOBAL stop level, so non-argmax weights can move within the
    tolerance relative to a solo run (the decision, not the score bits,
    is the guarantee).  Implies the progressive walk; requires ``l2r``.
    """
    b, _, h, dh = q.shape
    kv_heads = k_cache.shape[2]
    g = h // kv_heads
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    qg = q.reshape(b, 1, kv_heads, g, dh)
    valid = (kv_positions >= 0) & (kv_positions <= q_position[:, None])
    if window is not None:
        valid &= kv_positions > (q_position[:, None] - window)
    valid_b = valid[:, None, None, None, :]  # (B, 1, 1, 1, L)

    if l2r is None:
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache,
                       preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid_b, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, 1, h, dh).astype(v_cache.dtype)

    # ---- digit-serial QK^T -------------------------------------------
    qq, qs = quantize_per_vector(qg, l2r)
    qs_t = qs.transpose(0, 2, 3, 1, 4)  # (B, Kv, G, 1, 1)
    if k_planes is not None:
        assert k_scale is not None, \
            "plane-stacked cache: k_planes and k_scale travel together"
        k_op = k_planes if isinstance(k_planes, PlaneOperands) else \
            PlaneOperands(k_planes, "rhs", l2r.n_bits, l2r.log2_radix,
                          dh, -1, False, l2r.planes - 1)
        ks = k_scale
    else:
        kq, ks3 = quantize_per_vector(k_cache, l2r)
        k_op, ks = kq, ks3[..., 0]
    ks_t = ks.transpose(0, 2, 1)[:, :, None, None, :]  # (B, Kv, 1, 1, L)
    sf = jnp.float32(scale)

    def dequant(acc):
        return acc.astype(jnp.float32) * qs_t * ks_t * sf

    if not early_exit and policy is None:
        s_int = attn_scores_stacked(qq, k_op, l2r.n_bits, l2r.log2_radix,
                                    levels)
        s = dequant(s_int)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid_b, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, 1, h, dh).astype(v_cache.dtype)

    # ---- margin-bounded progressive walk -----------------------------
    if softcap is not None:
        raise ValueError("progressive attention (early_exit/policy) does "
                         "not compose with softcap: tanh re-scales the "
                         "score margins the tail bounds are stated in")
    bounds = level_bounds(l2r.planes, l2r.log2_radix, dh, levels)
    n_levels = int(bounds.f32.shape[0])
    fold, init, done_fn = attn_walk_machinery(
        bounds.f32, dequant, valid_b,
        qs_t[:, :, :, 0, :] * ks_t[:, :, :, 0, :] * sf,
        rows_shape=(b, kv_heads, g), n_levels=n_levels,
        exit_tol=exit_tol, policy=policy,
        score_shape=(b, kv_heads, g, 1, k_cache.shape[1]))
    acc, carry, levels_run = attn_scores_streaming_while(
        qq, k_op, fold, init, done_fn,
        l2r.n_bits, l2r.log2_radix, levels)
    if policy is None:
        _done, lv = carry
        s_int = acc
    else:
        _done, lv, forced_any, s_commit = carry
        # budget rows committed at their clamp level: serve THEIR softmax
        # from the snapshotted prefix so mixed batches stay bit-identical
        # to a solo levels=L run even when batch-mates stream deeper.
        s_int = jnp.where(forced_any[..., None, None], s_commit, acc)
    if _EXIT_TAP is not None:
        if isinstance(levels_run, jax.core.Tracer):
            raise RuntimeError(
                "attn_exit_tap() cannot record under jit: levels_run is a "
                "tracer, so the tap would silently capture nothing. Run the "
                "tapped call eagerly (e.g. under jax.disable_jit()) or drop "
                "the tap around traced code.")
        _EXIT_TAP.append({"levels_run": int(levels_run),
                          "exit_levels": np.asarray(lv)})
    s = jnp.where(valid_b, dequant(s_int), -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, h, dh).astype(v_cache.dtype)


# ------------------------------------------------------------- KV caches
class KVCache(NamedTuple):
    """Full or ring KV cache. ``length`` is the allocated size (the
    window for ring caches); positions tracks absolute token positions.

    ``k_planes``/``k_scale`` (present iff the cache was built with a
    quant config) are the **incrementally plane-stacked** key cache:
    every update also quantizes the new keys per slot and writes their
    raw-digit descending plane stack — window-padded to 2D-1 blocks, the
    ``PlaneOperands.prepare_rhs(axis=-1, window_pad=True)`` layout — so
    decode-step digit-serial QK^T consumes a ready operand instead of
    re-extracting planes over the whole history each step (the attention
    analogue of the window-padded LM-head weight cache).  ``None``
    fields are empty pytree nodes: existing cache trees are unchanged.
    """

    k: jax.Array  # (B, L, Kv, dh)
    v: jax.Array  # (B, L, Kv, dh)
    positions: jax.Array  # (B, L) int32, -1 = empty
    k_planes: jax.Array | None = None  # (B, L, Kv, (2D-1)*dh) int8
    k_scale: jax.Array | None = None   # (B, L, Kv) f32 per-slot scales


def init_kv_cache(batch: int, length: int, kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16,
                  quant: QuantConfig | None = None) -> KVCache:
    k_planes = k_scale = None
    if quant is not None:
        d = quant.planes
        k_planes = jnp.zeros(
            (batch, length, kv_heads, (2 * d - 1) * head_dim), jnp.int8)
        # empty slots carry the scale a zero key vector quantizes to, so
        # the whole stacked cache — used slots or not — is bit-identical
        # to re-extracting planes from the (zero-initialized) float cache
        _, s0 = _symmetric_quant(jnp.zeros((), jnp.float32),
                                 jnp.zeros((), jnp.float32), quant)
        k_scale = jnp.full((batch, length, kv_heads), s0, jnp.float32)
    return KVCache(
        k=jnp.zeros((batch, length, kv_heads, head_dim), dtype),
        v=jnp.zeros((batch, length, kv_heads, head_dim), dtype),
        positions=jnp.full((batch, length), -1, jnp.int32),
        k_planes=k_planes,
        k_scale=k_scale,
    )


def write_slots(buf: jax.Array, slots: jax.Array, new: jax.Array,
                layer: jax.Array | None = None) -> jax.Array:
    """``buf`` (B, L, ...) with ``new`` (B, S, ...) written at ``slots``
    (B, S); a slot outside [0, L) is dropped.

    With ``layer``, ``buf`` is a leaf of a layer-stacked cache (layers,
    B, L, ...) riding a scan carry, and ``new`` holds one position a row
    (decode).  The layer's slab takes each row's entry by a select
    against the slot and goes back where it came from, one fusion that
    XLA performs in place in whatever layout it keeps the stack, and
    that GSPMD keeps on each shard.  A scatter would not do: on a TPU it
    first relayouts a stack whose position axis lies minor-most
    (SmolLM's 3 x 64 heads) and copies it whole around the scan."""
    if layer is None:
        return jax.vmap(lambda b, s, n: b.at[s].set(n))(buf, slots, new)
    assert slots.shape[1] == 1, "in place: one new position a row"
    slab = jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)
    hit = jnp.arange(slab.shape[1]) == slots  # (B, L)
    hit = hit.reshape(hit.shape + (1,) * (slab.ndim - 2))
    return jax.lax.dynamic_update_index_in_dim(
        buf, jnp.where(hit, new, slab), layer, 0)


def cache_layer(cache, layer: jax.Array | None):
    """Layer ``layer``'s slab of a layer-stacked cache (any NamedTuple
    of stacked leaves), sliced where it lies for its consumer to read;
    the cache itself where ``layer`` is None."""
    if layer is None:
        return cache
    return jax.tree.map(
        lambda buf: jax.lax.dynamic_index_in_dim(buf, layer, 0,
                                                 keepdims=False), cache)


def update_kv_cache(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                    positions: jax.Array,
                    quant: QuantConfig | None = None,
                    layer: jax.Array | None = None) -> KVCache:
    """Insert S new entries at slots positions % L (ring semantics; for a
    full-length cache L >= max position this is plain indexed write).

    k_new/v_new: (B, S, Kv, dh); positions: (B, S) absolute.  ``layer``
    marks a layer-stacked cache (every leaf with a leading layers axis):
    the entries go into that layer in place (:func:`write_slots`).

    A plane-stacked cache (``init_kv_cache(..., quant=...)``) must pass
    the same ``quant`` here: the new keys' digit planes append into the
    pre-allocated stack incrementally.  The quantized value is the key
    AS STORED in the float cache (after the cache-dtype cast), so the
    incremental stack is bit-identical to re-extracting planes from the
    full float cache at any later step.
    """
    length = cache.positions.shape[-1]
    slots = positions % length  # (B, S)
    def write(buf, new):
        return write_slots(buf, slots, new, layer)
    k_planes, k_scale = cache.k_planes, cache.k_scale
    if k_planes is not None:
        assert quant is not None, \
            "plane-stacked KV cache: pass the QuantConfig that built it"
        from repro.core.quant import stack_planes_rhs
        d = quant.planes
        dh = cache.k.shape[-1]
        kq, ks = quantize_per_vector(k_new.astype(cache.k.dtype), quant)
        new_stack = stack_planes_rhs(kq, quant.n_bits, quant.log2_radix,
                                     axis=-1, shifted=False)
        new_stack = jnp.pad(
            new_stack, [(0, 0)] * (new_stack.ndim - 1) + [(0, (d - 1) * dh)])
        k_planes = write(k_planes, new_stack)
        k_scale = write(k_scale, ks[..., 0])
    return KVCache(
        k=write(cache.k, k_new.astype(cache.k.dtype)),
        v=write(cache.v, v_new.astype(cache.v.dtype)),
        positions=write(cache.positions, positions),
        k_planes=k_planes,
        k_scale=k_scale,
    )


def kv_plane_operands(cache: KVCache, quant: QuantConfig) -> PlaneOperands:
    """View the cache's incremental plane stack as the RHS operand the
    score walks consume (raw digits, descending on the head dim,
    window-padded — zero per-step operand prep)."""
    assert cache.k_planes is not None, \
        "cache has no plane stack: init_kv_cache(..., quant=...)"
    return PlaneOperands(cache.k_planes, "rhs", quant.n_bits,
                         quant.log2_radix, cache.k.shape[-1], -1, False,
                         quant.planes - 1)
