"""Decoder-LM assembly: pattern-based layers, scan-grouped blocks, caches.

An architecture is a per-layer sequence of (mixer, ffn) kinds
(ModelConfig.layer_kinds): mixers are 'global' / 'local' attention,
'mla' (latent attention), 'ssd' (Mamba-2), 'rec' (RG-LRU); ffns are
'mlp' / 'moe'.  Layers are
grouped into the smallest repeating unit and executed under lax.scan
(one traced copy per unit — compile time and HLO size stay bounded for
62-layer models), with aperiodic prefix/suffix layers unrolled.

Three modes:
  train   — full sequence, no cache, remat per scanned block;
  prefill — full sequence, writes caches/states;
  decode  — one token against caches/states (O(1) state for ssd/rec/local).

Prefill and decode serve: their MoE layers are dropless
(models/moe.py:moe_serve), and the serving state of an MoE model
carries the MoE counters (``LMState.moe_counts``), which decode steps
accumulate.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .attention import (
    KVCache,
    apply_rope,
    chunked_attention,
    decode_attention,
    init_kv_cache,
    update_kv_cache,
)
from .common import Param, dense, rms_norm, layer_norm
from .config import ModelConfig
from .mla import init_mla_cache, mla_apply, mla_build
from .mlp import mlp_build, mlp_apply
from .moe import moe_apply, moe_build, moe_serve
from .rglru import init_rglru_state, rglru_apply, rglru_build, rglru_decode
from .ssm import init_ssm_state, ssm_apply, ssm_build, ssm_decode

__all__ = [
    "lm_build",
    "lm_forward",
    "logits_from_hidden",
    "init_lm_state",
    "LMState",
]


# --------------------------------------------------------------- attention
def attn_build(cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    p = {
        "wq": Param((d, h * dh), ("embed", "qkv")),
        "wk": Param((d, kv * dh), ("embed", "qkv")),
        "wv": Param((d, kv * dh), ("embed", "qkv")),
        "wo": Param((h * dh, d), ("qkv", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = Param((h * dh,), ("qkv",), init="zeros")
        p["bk"] = Param((kv * dh,), ("qkv",), init="zeros")
        p["bv"] = Param((kv * dh,), ("qkv",), init="zeros")
    return p


@jax.named_scope("attention")
def attn_apply(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    *,
    mode: str,
    rope_positions: jax.Array,
    positions: jax.Array,
    cache: KVCache | None,
    window: int | None,
    cross_kv: tuple[jax.Array, jax.Array] | None = None,
):
    """Self- or cross-attention layer.  Returns (out, new_cache).  Runs
    under the ``attention`` named scope, its cache write under
    ``kv_cache_update``."""
    b, s, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv, cfg.head_dim

    from repro.sharding.ctx import hint

    q = dense(x, p["wq"], cfg.l2r, cfg.l2r_levels)
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
    q = hint(q, None, None, "model")  # keep TP over the fused head dim
    q = q.reshape(b, s, h, dh)

    if cross_kv is not None:  # cross-attention: kv precomputed from encoder
        k_all, v_all = cross_kv
        out = chunked_attention(
            q, k_all, v_all, causal=False, scale=cfg.attn_scale,
            softcap=cfg.logit_softcap,
        )
        return dense(out.reshape(b, s, h * dh), p["wo"], cfg.l2r, cfg.l2r_levels), cache

    k = dense(x, p["wk"], cfg.l2r, cfg.l2r_levels)
    v = dense(x, p["wv"], cfg.l2r, cfg.l2r_levels)
    if "bk" in p:
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    k = k.reshape(b, s, kv, dh)
    v = v.reshape(b, s, kv, dh)

    q = apply_rope(q, rope_positions, cfg.rope_theta, cfg.rope_mode, cfg.mrope_sections)
    k = apply_rope(k, rope_positions, cfg.rope_theta, cfg.rope_mode, cfg.mrope_sections)

    if mode == "decode":
        with jax.named_scope("kv_cache_update"):
            cache = update_kv_cache(cache, k, v, positions,
                                    quant=cfg.attn_l2r)
        out = decode_attention(
            q, cache.k, cache.v, cache.positions, positions[:, 0],
            window=window, scale=cfg.attn_scale, softcap=cfg.logit_softcap,
            l2r=cfg.attn_l2r, levels=cfg.attn_levels,
            early_exit=cfg.attn_early_exit, exit_tol=cfg.attn_exit_tol,
            k_planes=cache.k_planes, k_scale=cache.k_scale,
        ).astype(q.dtype)  # a wider cache must not widen the residual
    else:
        if mode == "prefill":
            # a plane-stacked cache fills incrementally here too: decode
            # steps after this prefill consume a ready operand
            with jax.named_scope("kv_cache_update"):
                cache = update_kv_cache(cache, k, v, positions,
                                        quant=cfg.attn_l2r)
        out = chunked_attention(
            q, k, v, causal=True, window=window, scale=cfg.attn_scale,
            softcap=cfg.logit_softcap,
            score_dtype=jnp.dtype(cfg.attn_score_dtype),
            head_shard=cfg.attn_head_shard,
            l2r=cfg.attn_l2r, levels=cfg.attn_levels,
        )
    out = hint(out.reshape(b, s, h * dh), None, None, "model")
    return dense(out, p["wo"], cfg.l2r, cfg.l2r_levels), cache


# ------------------------------------------------------------ layer dispatch
def _mixer_build(cfg: ModelConfig, kind: str) -> dict:
    if kind in ("global", "local"):
        return attn_build(cfg)
    if kind == "mla":
        return mla_build(cfg)
    if kind == "ssd":
        return ssm_build(cfg)
    if kind == "rec":
        return rglru_build(cfg)
    raise ValueError(kind)


def _ffn_build(cfg: ModelConfig, kind: str, layer_idx: int) -> dict:
    if kind == "moe":
        return moe_build(cfg)
    # deepseek-style MoE models use a wider hidden on their dense layers
    if cfg.n_experts and cfg.dense_d_ff:
        return mlp_build(cfg, d_ff=cfg.dense_d_ff)
    return mlp_build(cfg)


def layer_build(cfg: ModelConfig, kinds: tuple[str, str], layer_idx: int) -> dict:
    mixer, ffn = kinds
    out = {
        "mixer_norm": Param((cfg.d_model,), ("embed",), init="zeros"),
        "mixer": _mixer_build(cfg, mixer),
    }
    if ffn != "none":
        out["ffn_norm"] = Param((cfg.d_model,), ("embed",), init="zeros")
        out["ffn"] = _ffn_build(cfg, ffn, layer_idx)
    return out


def _mixer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype):
    if kind == "global":
        return init_kv_cache(batch, max_len, cfg.n_kv, cfg.head_dim, dtype,
                             quant=cfg.attn_l2r)
    if kind == "local":
        return init_kv_cache(batch, min(cfg.window, max_len), cfg.n_kv,
                             cfg.head_dim, dtype, quant=cfg.attn_l2r)
    if kind == "mla":
        return init_mla_cache(batch, max_len, cfg.kv_lora_rank,
                              cfg.qk_rope_dim, dtype)
    if kind == "ssd":
        return init_ssm_state(cfg, batch)
    if kind == "rec":
        return init_rglru_state(cfg, batch)
    raise ValueError(kind)


def layer_apply(
    cfg: ModelConfig,
    params: dict,
    kinds: tuple[str, str],
    x: jax.Array,
    *,
    mode: str,
    rope_positions,
    positions,
    cache,
    valid=None,
):
    """One (mixer + ffn) residual layer.  Returns (x, new_cache, aux,
    counts): ``counts`` is a serving MoE layer's int32 counters
    (models/moe.py:MOE_COUNTS), else None.  ``valid`` (B, S) marks the
    rows whose output is used (a serving MoE routes the others
    nowhere)."""
    mixer_kind, ffn_kind = kinds
    norm = layer_norm_fn(cfg)
    h = norm(x, params["mixer_norm"])
    if mixer_kind in ("global", "local"):
        window = cfg.window if mixer_kind == "local" else None
        mixed, new_cache = attn_apply(
            cfg, params["mixer"], h, mode=mode, rope_positions=rope_positions,
            positions=positions, cache=cache, window=window,
        )
    elif mixer_kind == "mla":
        mixed, new_cache = mla_apply(
            cfg, params["mixer"], h, mode=mode,
            rope_positions=rope_positions, positions=positions, cache=cache)
    elif mixer_kind == "ssd":
        if mode == "decode":
            mixed, new_cache = ssm_decode(cfg, params["mixer"], h, cache)
        else:
            mixed, new_cache = ssm_apply(cfg, params["mixer"], h,
                                         cache if mode == "prefill" else None)
    elif mixer_kind == "rec":
        if mode == "decode":
            mixed, new_cache = rglru_decode(cfg, params["mixer"], h, cache)
        else:
            mixed, new_cache = rglru_apply(cfg, params["mixer"], h,
                                           cache if mode == "prefill" else None)
    else:
        raise ValueError(mixer_kind)
    x = x + mixed

    aux = jnp.zeros((), jnp.float32)
    counts = None
    if ffn_kind != "none":
        h = norm(x, params["ffn_norm"])
        if ffn_kind == "moe":
            with jax.named_scope("moe"):
                if mode == "train":
                    out, aux = moe_apply(cfg, params["ffn"], h)
                else:
                    out, counts = moe_serve(cfg, params["ffn"], h, valid)
        else:
            with jax.named_scope("mlp"):
                out = mlp_apply(cfg, params["ffn"], h)
        x = x + out
    return x, new_cache, aux, counts


def _add_counts(a, b):
    """Fold two MoE counter vectors: sums of rows, max of the per-expert
    maximum (either may be None)."""
    if a is None or b is None:
        return b if a is None else a
    return jnp.stack([a[0] + b[0], a[1] + b[1], jnp.maximum(a[2], b[2])])


def layer_norm_fn(cfg: ModelConfig) -> Callable:
    if cfg.use_layer_norm:
        # beta folded to zero-init gamma pair is overkill; whisper uses LN
        # with both; we store a single gamma and zero beta for simplicity.
        return lambda x, g: layer_norm(x, 1.0 + g, jnp.zeros_like(g), cfg.norm_eps)
    return lambda x, g: rms_norm(x, g, cfg.norm_eps)


# --------------------------------------------------------------- LM assembly
def lm_build(cfg: ModelConfig) -> dict:
    prefix, repeats, unit, suffix = cfg.block_grouping()
    params: dict[str, Any] = {
        "embed": Param((cfg.vocab, cfg.d_model), ("vocab", "embed"), init="embed"),
        "final_norm": Param((cfg.d_model,), ("embed",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        params["head"] = Param((cfg.d_model, cfg.vocab), ("embed", "vocab"), scale=0.02)

    li = 0
    pre = []
    for kk in prefix:
        pre.append(layer_build(cfg, kk, li))
        li += 1
    params["prefix"] = pre

    if repeats:
        unit_params = []
        for u_idx, kk in enumerate(unit):
            unit_params.append(layer_build(cfg, kk, li + u_idx))
        # stack: every leaf gets a leading "layers" axis of size `repeats`
        def stack_param(p: Param) -> Param:
            return Param((repeats, *p.shape), ("layers", *p.axes),
                         init=p.init, scale=p.scale, dtype=p.dtype)
        params["stack"] = jax.tree.map(
            stack_param, unit_params,
            is_leaf=lambda x: isinstance(x, Param),
        )
        li += repeats * len(unit)

    suf = []
    for kk in suffix:
        suf.append(layer_build(cfg, kk, li))
        li += 1
    params["suffix"] = suf
    return params


@dataclasses.dataclass
class LMState:
    """Serving state: caches grouped like the params + next position."""

    prefix: list
    stack: Any  # leaves have leading (repeats,) axis
    suffix: list
    pos: jax.Array  # (B,) next position to write
    # MoE models: int32 (3,) counters (models/moe.py:MOE_COUNTS) that
    # decode steps accumulate; batch-independent, never spliced per slot
    moe_counts: jax.Array | None = None


jax.tree_util.register_dataclass(
    LMState, data_fields=["prefix", "stack", "suffix", "pos", "moe_counts"],
    meta_fields=[]
)


def init_lm_state(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> LMState:
    prefix, repeats, unit, suffix = cfg.block_grouping()
    mk = lambda kk: _mixer_cache(cfg, kk[0], batch, max_len, dtype)
    stack = None
    if repeats:
        unit_caches = [mk(kk) for kk in unit]
        stack = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *([unit_caches] * repeats),
        ) if repeats > 1 else jax.tree.map(lambda x: x[None], unit_caches)
    return LMState(
        prefix=[mk(kk) for kk in prefix],
        stack=stack,
        suffix=[mk(kk) for kk in suffix],
        pos=jnp.zeros((batch,), jnp.int32),
        moe_counts=(jnp.zeros((3,), jnp.int32)
                    if "moe" in cfg.ffn_kinds() else None),
    )


def lm_forward(
    cfg: ModelConfig,
    params: dict,
    *,
    tokens: jax.Array | None = None,
    embeds: jax.Array | None = None,
    rope_positions: jax.Array | None = None,
    mode: str = "train",
    state: LMState | None = None,
    resid_shard: Callable[[jax.Array], jax.Array] = lambda x: x,
    remat: bool = False,
    valid: jax.Array | None = None,
):
    """Backbone forward.

    Returns (hidden (B,S,d), new_state, aux_loss).  `tokens` xor `embeds`
    (modality-stub archs feed embeddings per the assignment).  ``valid``
    (B, S) marks the positions whose output is used, where a bucket
    prefill pads (serving MoE layers skip the others).
    """
    prefix_k, repeats, unit, suffix_k = cfg.block_grouping()
    compute_dtype = jnp.dtype(cfg.compute_dtype)

    if embeds is None:
        x = params["embed"].astype(compute_dtype)[tokens]
    else:
        x = embeds.astype(compute_dtype)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, compute_dtype)

    b, s = x.shape[:2]
    if state is not None:
        positions = state.pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if rope_positions is None:
        rope_positions = positions

    aux_total = jnp.zeros((), jnp.float32)
    counts = None

    def run_layer(x, lp, kinds, cache):
        return layer_apply(
            cfg, lp, kinds, x, mode=mode, rope_positions=rope_positions,
            positions=positions, cache=cache, valid=valid,
        )

    new_prefix = []
    for i, kk in enumerate(prefix_k):
        c = state.prefix[i] if state is not None else None
        x, c2, aux, cnt = run_layer(x, params["prefix"][i], kk, c)
        x = resid_shard(x)
        new_prefix.append(c2)
        aux_total += aux
        counts = _add_counts(counts, cnt)

    new_stack = None
    if repeats:
        # Caches ride the scan CARRY and are updated in place with
        # dynamic_update_index_in_dim: XLA aliases while-loop carries, so
        # decode/prefill never copies the full stacked KV cache at once
        # (the xs/ys formulation materialized a whole-cache copy per
        # step — 42% of baseline decode HBM traffic; EXPERIMENTS.md
        # §Perf).  Each block still copies its layer's cache out of the
        # carry and back: the ``kv_cache_read`` / ``kv_cache_writeback``
        # scopes, 46% of decode device time at 64 slots x 1024 on a TPU
        # v5e (PERF.md §5).
        def block(carry, lp):
            x, aux_acc, caches_all, blk_i, cnt_acc = carry
            if caches_all is not None:
                with jax.named_scope("kv_cache_read"):
                    caches = jax.tree.map(
                        lambda buf: jax.lax.dynamic_index_in_dim(
                            buf, blk_i, 0, keepdims=False),
                        caches_all)
            new_caches = []
            for u_idx, kk in enumerate(unit):
                x, c2, aux, cnt = run_layer(
                    x, lp[u_idx], kk,
                    caches[u_idx] if caches_all is not None else None)
                new_caches.append(c2)
                aux_acc = aux_acc + aux
                cnt_acc = _add_counts(cnt_acc, cnt)
            x = resid_shard(x)
            if caches_all is not None:
                with jax.named_scope("kv_cache_writeback"):
                    caches_all = jax.tree.map(
                        lambda buf, new: jax.lax.dynamic_update_index_in_dim(
                            buf, new.astype(buf.dtype), blk_i, 0),
                        caches_all, new_caches)
            return (x, aux_acc, caches_all, blk_i + 1, cnt_acc), None

        block_fn = jax.checkpoint(block) if remat else block
        caches_in = state.stack if state is not None else None
        # the scan carries the unit's MoE counters when it serves any
        cnt0 = (jnp.zeros((3,), jnp.int32)
                if mode != "train" and any(k[1] == "moe" for k in unit)
                else None)
        (x, aux_total, new_stack, _, cnt), _ = jax.lax.scan(
            block_fn,
            (x, aux_total, caches_in, jnp.zeros((), jnp.int32), cnt0),
            params["stack"],
        )
        counts = _add_counts(counts, cnt)

    new_suffix = []
    for i, kk in enumerate(suffix_k):
        c = state.suffix[i] if state is not None else None
        x, c2, aux, cnt = run_layer(x, params["suffix"][i], kk, c)
        x = resid_shard(x)
        new_suffix.append(c2)
        aux_total += aux
        counts = _add_counts(counts, cnt)

    x = layer_norm_fn(cfg)(x, params["final_norm"])

    new_state = None
    if state is not None:
        new_state = LMState(
            prefix=new_prefix, stack=new_stack, suffix=new_suffix,
            pos=state.pos + s,
            moe_counts=_add_counts(state.moe_counts, counts),
        )
    return x, new_state, aux_total


@jax.named_scope("head")
def logits_from_hidden(cfg: ModelConfig, params: dict, hidden: jax.Array) -> jax.Array:
    """LM head.  With an L2R config the head matmul runs through the
    digit-plane pipeline like every other matmul — which also makes it
    streamable level-by-level (serve/engine.py progressive decode commits
    tokens bit-identically to this full evaluation).  A ``head_q`` cache
    entry (serve/engine.py:prepare_params) skips the per-step head-weight
    quantization on serving paths.  Runs under the ``head`` named
    scope."""
    if cfg.l2r is not None and "head_q" in params:
        return dense(hidden, params["head_q"], cfg.l2r, cfg.l2r_levels)
    if cfg.tie_embeddings:
        w = params["embed"].T
    else:
        w = params["head"]
    return dense(hidden, w.astype(hidden.dtype), cfg.l2r, cfg.l2r_levels)
