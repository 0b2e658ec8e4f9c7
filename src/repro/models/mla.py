"""Multi-head latent attention (MLA, DeepSeek-V2, arXiv:2405.04434 §2.1)
with YaRN rope, and its latent cache.

Per layer and token, with no query compression:

    q              = W_q h                      (heads x (nope + rope))
    [c_kv, k_pe]   = W_kva h,  c_kv <- RMSNorm(c_kv)
    [k_nope, v]    = W_kvb c_kv                 (heads x (nope + value))
    q_pe, k_pe     rotated by YaRN rope; k_pe is one vector shared by
                   every head

The cache holds, per position, the normed ``c_kv`` and the roped
``k_pe`` (``kv_lora_rank + qk_rope_dim`` values) instead of every head's
K and V.  Prefill decompresses ``c_kv`` through ``W_kvb`` into per-head
K and V.  Decode uses the absorbed form and never decompresses the
cache: ``q_nope . W_UK`` moves each head's query into the latent, scores
are taken against ``c_kv`` and ``k_pe``, and the latent output goes
through ``W_UV``.  ``W_kvb`` runs in the compute dtype's float
arithmetic in both paths: absorption reorders its product, which
quantizing its input to int8 would not commute with.  The other
projections go through :func:`~repro.models.common.dense`, the L2R path
when the config carries one.

Rope rotates the pairs (2i, 2i+1) of the rope slice, as the published
code does by permuting them into halves before a rotate-half rotation.
YaRN scales cos and sin by ``mscale / mscale_all_dim``, which is 1 for
the configurations served here (the two are equal; the benchmark's
system refuses others), so only ``yarn_mscale_all_dim`` is kept: it
scales the softmax.

Named scopes: ``mla`` (the layer), ``mla_cache_update`` (the cache
write) and ``mla_cache_read`` (decode's scores and weighted sum over the
cache).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .common import Param, dense, rms_norm
from .config import ModelConfig

__all__ = ["MLACache", "init_mla_cache", "update_mla_cache", "mla_build",
           "mla_apply", "yarn_inv_freq", "yarn_get_mscale",
           "mla_softmax_scale"]

_NEG = -1e30  # finite mask sentinel: -inf breeds NaNs in empty rows


class MLACache(NamedTuple):
    """Latent cache of one MLA layer: per position the normed latent
    ``c_kv`` and the roped shared rope key ``k_pe``; ``positions`` holds
    each slot's absolute position, -1 where empty (as in ``KVCache``)."""

    c_kv: jax.Array  # (B, L, kv_lora_rank)
    k_pe: jax.Array  # (B, L, qk_rope_dim)
    positions: jax.Array  # (B, L) int32, -1 = empty


def init_mla_cache(batch: int, length: int, rank: int, rope_dim: int,
                   dtype=jnp.bfloat16) -> MLACache:
    return MLACache(c_kv=jnp.zeros((batch, length, rank), dtype),
                    k_pe=jnp.zeros((batch, length, rope_dim), dtype),
                    positions=jnp.full((batch, length), -1, jnp.int32))


def update_mla_cache(cache: MLACache, c_kv: jax.Array, k_pe: jax.Array,
                     positions: jax.Array) -> MLACache:
    """Write S new entries at slots ``positions`` (B, S)."""
    def write(buf, new):
        return jax.vmap(lambda b, s, n: b.at[s].set(n))(
            buf, positions, new.astype(buf.dtype))
    return MLACache(c_kv=write(cache.c_kv, c_kv),
                    k_pe=write(cache.k_pe, k_pe),
                    positions=write(cache.positions, positions))


# ------------------------------------------------------------------ YaRN
def yarn_get_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention factor: 0.1 m ln(s) + 1 (1 at s <= 1)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """Rope inverse frequencies of the ``qk_rope_dim`` slice (dim/2,):
    interpolated by ``yarn_factor`` at low frequencies, kept at high
    ones, with a linear ramp between the correction dims of
    ``yarn_beta_fast`` and ``yarn_beta_slow`` rotations."""
    dim, base, f = cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn_factor
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if f <= 1:
        return extra.astype(np.float32)
    inter = extra / f

    def corr(rot):  # the dim index that turns ``rot`` times in the window
        return dim * math.log(cfg.yarn_original_len / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # 1 where the frequency is extrapolated
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-1/2, times YaRN's mscale squared."""
    s = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn_mscale_all_dim:
        m = yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        s *= m * m
    return s


def _rope_pairs(x: jax.Array, positions: jax.Array, cfg: ModelConfig):
    """Rotate the pairs (2i, 2i+1) of ``x`` (B, S, heads, dr) at
    ``positions`` (B, S); the result is in the published code's
    permuted layout (evens, then odds), for queries and keys alike."""
    dr = x.shape[-1]
    half = dr // 2
    x = x.reshape(*x.shape[:-1], half, 2).swapaxes(-1, -2).reshape(x.shape)
    ang = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
    cos = jnp.cos(ang)[:, :, None].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# --------------------------------------------------------------- the layer
def mla_build(cfg: ModelConfig) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wq": Param((d, h * (dn + dr)), ("embed", "qkv")),
        "wkv_a": Param((d, r + dr), ("embed", None)),
        "kv_norm": Param((r,), (None,), init="zeros"),
        # "latent": kept in float (models/common.py:_quantizable)
        "wkv_b": Param((r, h * (dn + dv)), ("latent", "qkv")),
        "wo": Param((h * dv, d), ("qkv", "embed")),
    }


@jax.named_scope("mla")
def mla_apply(cfg: ModelConfig, p: dict, x: jax.Array, *, mode: str,
              rope_positions: jax.Array, positions: jax.Array,
              cache: MLACache | None):
    """One MLA layer on x (B, S, d).  Returns (out, new_cache)."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    f32 = jnp.float32

    q = dense(x, p["wq"], cfg.l2r, cfg.l2r_levels).reshape(b, s, h, dn + dr)
    q_nope = q[..., :dn]
    q_pe = _rope_pairs(q[..., dn:], rope_positions, cfg)
    kv_a = dense(x, p["wkv_a"], cfg.l2r, cfg.l2r_levels)
    c_kv = rms_norm(kv_a[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = _rope_pairs(kv_a[..., None, r:], rope_positions, cfg)[:, :, 0]
    w_kvb = p["wkv_b"].astype(x.dtype).reshape(r, h, dn + dv)
    scale = mla_softmax_scale(cfg)

    if mode == "decode":
        with jax.named_scope("mla_cache_update"):
            cache = update_mla_cache(cache, c_kv, k_pe, positions)
        # absorbed: each head's query moves into the latent
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_kvb[..., :dn])
        with jax.named_scope("mla_cache_read"):
            c = cache.c_kv.astype(x.dtype)
            sc = (jnp.einsum("bshr,btr->bhst", q_lat, c,
                             preferred_element_type=f32)
                  + jnp.einsum("bshd,btd->bhst", q_pe,
                               cache.k_pe.astype(x.dtype),
                               preferred_element_type=f32)) * scale
            kp = cache.positions[:, None, None, :]
            valid = (kp >= 0) & (kp <= positions[:, None, :, None])
            w = jax.nn.softmax(jnp.where(valid, sc, _NEG), axis=-1)
            o_lat = jnp.einsum("bhst,btr->bshr", w.astype(x.dtype), c)
        o = jnp.einsum("bshr,rhv->bshv", o_lat, w_kvb[..., dn:])
    else:
        if mode == "prefill":
            with jax.named_scope("mla_cache_update"):
                cache = update_mla_cache(cache, c_kv, k_pe, positions)
        kv = jnp.einsum("bsr,rhk->bshk", c_kv, w_kvb)
        sc = (jnp.einsum("bshn,bthn->bhst", q_nope, kv[..., :dn],
                         preferred_element_type=f32)
              + jnp.einsum("bshd,btd->bhst", q_pe, k_pe,
                           preferred_element_type=f32)) * scale
        causal = positions[:, None, None, :] <= positions[:, None, :, None]
        w = jax.nn.softmax(jnp.where(causal, sc, _NEG), axis=-1)
        o = jnp.einsum("bhst,bthv->bshv", w.astype(x.dtype), kv[..., dn:])
    out = dense(o.reshape(b, s, h * dv), p["wo"], cfg.l2r, cfg.l2r_levels)
    return out, cache
