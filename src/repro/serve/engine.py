"""Serving engine: prefill / decode step factories, cache shardings,
batched greedy decoding, progressive-precision mode.

Cache sharding policy (per DESIGN.md §5): batch over DP axes when it
divides; on the "model" axis shard kv-heads when they divide 16,
otherwise head_dim (every assigned arch divides one of the two); SSM /
RG-LRU states shard their channel dim.  `long_500k` (batch=1) replicates
batch and relies on the model-axis sharding to fit.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.policy import LevelPolicy
from repro.core.progressive import streaming_argmax
from repro.core.quant import QuantConfig, QuantizedWeights, quantize
from repro.models.attention import KVCache
from repro.models.config import ModelConfig
from repro.models.mla import MLACache
from repro.models.encdec import (EncDecState, encdec_forward,
                                 init_encdec_state)
from repro.models.transformer import (LMState, init_lm_state, lm_forward,
                                      logits_from_hidden)
from repro.sharding.axes import dp_axes

__all__ = ["prepare_params", "make_prefill_step", "make_decode_step",
           "make_bucket_prefill_step", "prefill_buckets", "bucket_for",
           "supports_bucketed_prefill",
           "progressive_logits_from_hidden", "state_specs", "abstract_state",
           "greedy_generate", "SERVE_COMPILER_OPTIONS"]


# XLA may keep a bf16 intermediate in f32 inside a fusion ("excess
# precision"), and which values it keeps depends on the fusions it picks
# for a shape.  On the TPU that made a request's prefill logits and KV
# cache differ in the last bits between a one-row and a packed four-row
# bucket, so its tokens depended on what it was batched with.  Serving
# executables round every value where the program says: on the TPU a
# bf16 row's result is then the same in any batch, as the gateway/
# batcher parity requires (float32 rows still are not; see PERF.md).
# The step factories below return their step jitted with these options.
SERVE_COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


def _serve_jit(fn: Callable, jit_kwargs: dict) -> Callable:
    return jax.jit(fn, compiler_options=SERVE_COMPILER_OPTIONS, **jit_kwargs)


# ------------------------------------------------------- weight preparation
def prepare_params(cfg: ModelConfig, params, desc=None, prestack: bool = True,
                   mesh: Mesh | None = None):
    """Load-time serving weights: build the L2R weight cache ONCE.

    When ``cfg.l2r`` is set, every eligible matmul weight is converted to
    a :class:`~repro.core.quant.QuantizedWeights` record (int8 + per-
    out-channel scale) exactly once, here — the prefill/decode traces
    then stream activations through the dispatched level-stacked
    digit-plane kernel with NO per-step weight quantization.  Without an
    L2R config this is the identity (bf16/f32 serving).

    ``prestack=True`` (default) also caches every record's reversed RHS
    digit-plane stack (core/quant.py:PlaneOperands), so the decode/
    prefill traces carry no weight plane extraction either — planes are
    extracted exactly once per process.  The head cache is additionally
    built with the streaming window padding: the progressive head stream
    (``progressive_logits_from_hidden``, every decode step) consumes the
    cached stack with zero per-step operand preparation.  Costs D x (the
    head 2D-1 x) the int8 weight bytes; pass False for the
    extract-per-call layout.

    ``mesh`` (default: the installed ``sharding.ctx`` mesh) pins the
    head cache's sharding at build time: the (K, V) int8 head, its
    scales, and the window-padded plane stack are partitioned over the
    ``model`` axis on the vocab dim — the layout the ``shard_map``ped
    consensus head stream (core/progressive.py) consumes without any
    per-step resharding.  Backbone weights stay replicated (activations
    are batch-sharded instead; the head is the one vocab-axis matmul of
    every decode step).  Sharding never changes values.

    Routed-expert stacks quantize per expert (models/common.py:
    quantize_tree); MoE routers and MLA's latent up-projection stay
    float (models/common.py:_quantizable).

    ``desc`` is the Param descriptor tree (for eligibility); defaults to
    rebuilding it from ``cfg`` for LM families.
    """
    if cfg.l2r is None:
        return params
    from repro.core.quant import quantize_weights
    from repro.models.common import quantize_tree
    from repro.sharding import ctx

    if mesh is None:
        mesh = ctx.get_mesh()
    if desc is None:
        assert cfg.family != "encdec", "pass the encdec desc tree explicitly"
        from repro.models.transformer import lm_build

        desc = lm_build(cfg)
    out = quantize_tree(desc, params, cfg.l2r, prestack=prestack)
    # the LM head (vocab-axis, excluded from quantize_tree so embedding
    # lookups keep the f32 table) is the LARGEST matmul of every decode
    # step — cache its int8 form too so logits_from_hidden and the
    # progressive head stream skip per-step weight quantization
    head = (out["embed"].T if cfg.tie_embeddings else out.get("head")) \
        if isinstance(out, dict) else None
    if head is not None and not isinstance(head, QuantizedWeights):
        out = {**out, "head_q": quantize_weights(
            head, cfg.l2r, prestack=prestack, window_pad=prestack,
            shard=(None, "model") if mesh is not None else None, mesh=mesh)}
    return out


# ------------------------------------------------------------- shardings
def _model_axis_for_cache(cfg: ModelConfig, mesh: Mesh) -> tuple:
    """(kv_heads_axis, head_dim_axis) for KV caches."""
    m = mesh.shape.get("model", 1)
    if cfg.n_kv % m == 0:
        return ("model", None)
    if cfg.head_dim % m == 0:
        return (None, "model")
    return (None, None)


def _bspec(mesh: Mesh, batch: int):
    axes = dp_axes(mesh)
    import math
    size = math.prod(mesh.shape[a] for a in axes)
    if batch % size == 0 and size > 1:
        return axes
    if batch % mesh.shape.get("data", 1) == 0:
        return "data"
    return None


def state_specs(cfg: ModelConfig, mesh: Mesh, batch: int, max_len: int,
                kv_shard: str = "heads"):
    """PartitionSpec tree matching init_lm_state/init_encdec_state.

    kv_shard="heads": model axis on kv-heads (or head_dim) — baseline.
    kv_shard="seq":   model axis on the cache sequence dim — decode
    attention then reduces over a sharded axis and GSPMD emits tiny
    softmax-stat all-reduces instead of gathering the whole cache
    (§Perf hillclimb C: 79 GB/step of KV all-gather eliminated).
    """
    b = _bspec(mesh, batch)
    kvh, hd = _model_axis_for_cache(cfg, mesh)
    m = mesh.shape.get("model", 1)

    def kv_spec():
        # the incrementally plane-stacked key cache (cfg.attn_l2r) adds
        # k_planes/k_scale leaves; their specs mirror the float cache
        # (None fields stay empty pytree nodes when the knob is off).
        # The plane axis is (2D-1)*dh — never sharded (head_dim shards
        # would split plane blocks); the scale has no head_dim axis.
        planes = cfg.attn_l2r is not None
        if kv_shard == "seq":
            seq_ax = "model"
            return KVCache(
                k=P(b, seq_ax, None, None),
                v=P(b, seq_ax, None, None),
                positions=P(b, seq_ax),
                k_planes=P(b, seq_ax, None, None) if planes else None,
                k_scale=P(b, seq_ax, None) if planes else None)
        return KVCache(
            k=P(b, None, kvh, hd), v=P(b, None, kvh, hd),
            positions=P(b, None),
            k_planes=P(b, None, kvh, None) if planes else None,
            k_scale=P(b, None, kvh) if planes else None)

    def mixer_spec(kind: str):
        if kind in ("global", "local"):
            return kv_spec()
        if kind == "mla":  # one latent per position: shards with batch
            return MLACache(c_kv=P(b, None, None), k_pe=P(b, None, None),
                            positions=P(b, None))
        if kind == "ssd":
            d_inner = cfg.ssm_expand * cfg.d_model
            conv_dim = d_inner + 2 * cfg.ssm_state
            heads = d_inner // cfg.ssm_head_dim
            return {
                "ssd": P(b, "model" if heads % m == 0 else None, None, None),
                "conv": P(b, None, "model" if conv_dim % m == 0 else None),
            }
        if kind == "rec":
            w = cfg.lru_width or cfg.d_model
            wa = "model" if w % m == 0 else None
            return {"h": P(b, wa), "conv": P(b, None, wa)}
        raise ValueError(kind)

    if cfg.family == "encdec":
        c = kv_spec()
        return EncDecState(
            self_cache=KVCache(k=P(None, *c.k), v=P(None, *c.v),
                               positions=P(None, *c.positions)),
            cross_k=P(None, b, None, kvh, hd),
            cross_v=P(None, b, None, kvh, hd),
            pos=P(b),
        )

    prefix, repeats, unit, suffix = cfg.block_grouping()
    add_layer = lambda spec: jax.tree.map(
        lambda s: P(None, *s), spec, is_leaf=lambda x: isinstance(x, P))
    stack = None
    if repeats:
        stack = [add_layer(mixer_spec(kk[0])) for kk in unit]
    return LMState(
        prefix=[mixer_spec(kk[0]) for kk in prefix],
        stack=stack,
        suffix=[mixer_spec(kk[0]) for kk in suffix],
        pos=P(b),
        moe_counts=P() if "moe" in cfg.ffn_kinds() else None,
    )


def abstract_state(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    """ShapeDtypeStruct state (dry-run input without allocation)."""
    init = (init_encdec_state if cfg.family == "encdec" else init_lm_state)
    return jax.eval_shape(lambda: init(cfg, batch, max_len, dtype))


# ------------------------------------------------------------ step factories
def _replicated_backbone(fn: Callable, mesh: Mesh | None,
                         backbone_hints: bool) -> Callable:
    """``fn`` (a backbone forward) for the replicated-backbone mesh
    setting: one program per device on replicated operands.

    With ``backbone_hints=False`` on a multi-device mesh (``mesh``, or
    the installed context mesh), every device computes the whole
    backbone, as GSPMD would for replicated operands.  Inside
    ``shard_map`` no partitioner touches it: the compiled Pallas kernels
    lower (Mosaic kernels cannot be partitioned automatically), and each
    device runs the unmeshed trace, bit for bit.  Otherwise ``fn`` is
    returned as is.  Call with :func:`_backbone_params`, so the
    vocab-sharded head cache is not gathered into the backbone."""
    from repro.sharding import ctx

    mesh = mesh if mesh is not None else ctx.get_mesh()
    if backbone_hints or mesh is None or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def _backbone_params(params):
    """The parameters the backbone reads: all but the LM-head cache."""
    if isinstance(params, dict) and "head_q" in params:
        return {k: v for k, v in params.items() if k != "head_q"}
    return params


def _check_step_flags(progressive: bool, early_exit: bool,
                      policy: LevelPolicy | None = None) -> None:
    """Reject contradictory step-factory flag combinations.

    ``early_exit``/``levels`` knobs are kept as shims over the
    :class:`~repro.core.policy.LevelPolicy` path, but both shim and
    policy ride the progressive head stream — asking for either with
    ``progressive=False`` is a contradiction, not a silent no-op."""
    if early_exit and not progressive:
        raise ValueError(
            "contradictory arguments: early_exit=True requires "
            "progressive=True — early_exit stops the streamed head's "
            "level loop, which only exists on the progressive path "
            "(got progressive=False, early_exit=True)")
    if policy is not None and not progressive:
        raise ValueError(
            "contradictory arguments: policy requires progressive=True — "
            "LevelPolicy rows steer the streamed head's level walk, which "
            "only exists on the progressive path "
            "(got progressive=False with policy set)")


def make_prefill_step(cfg: ModelConfig, max_len: int,
                      cache_dtype=jnp.bfloat16,
                      progressive: bool = False,
                      early_exit: bool = False,
                      backbone_hints: bool = True,
                      mesh: Mesh | None = None,
                      policy: LevelPolicy | None = None,
                      **jit_kwargs) -> Callable:
    """(params, batch) -> (state, last_token_logits), jitted.

    ``progressive=True`` (LM families, requires ``cfg.l2r``) is
    batch-level progressive prefill: the backbone runs exactly over the
    whole prompt, and the LM head streams for the LAST prompt token ONLY
    — the other positions are never argmaxed by anyone, so they take the
    exact one-shot path (here: they are simply never fed to the head,
    the same ``hidden[:, -1:]`` slice the one-shot prefill uses).  The
    step then returns ``(state, logits, first_tok (B, 1) int32,
    exit_level (B, 1) int32)``; ``first_tok`` always equals
    ``argmax(logits_from_hidden(...))`` of the one-shot prefill.
    ``early_exit`` stops the head's level loop once every sequence in the
    prefill batch has decided (see make_decode_step).

    ``backbone_hints=False`` traces the step with the interior sharding
    hints scoped off (sharding/ctx.py:hints_disabled): the right setting
    whenever the backbone state is REPLICATED on the mesh — the hints
    would pin interior tensors of a replicated computation onto model
    axes, making GSPMD repartition (and float-reassociate) backbone
    contractions.  The streamed head still routes through the sharded
    consensus walk; with the hints off the whole step is bit-identical
    to the unmeshed trace.  ``mesh`` overrides the installed context
    mesh for the head stream (callers holding an explicit mesh — the
    batcher — must not depend on the module global being set).

    ``policy`` (factory default, overridable per call as a trailing
    step argument) routes the head stream through per-row
    :class:`~repro.core.policy.LevelPolicy` precision classes — one row
    per batch entry; ``early_exit`` stays as the batch-global shim.

    Every step factory returns its step as ``jax.jit(step,
    compiler_options=SERVE_COMPILER_OPTIONS, **jit_kwargs)`` (donation,
    shardings), so every serving program is compiled the same way.
    Call, ``.lower()`` or audit the returned step; JAX refuses it inside
    another ``jax.jit``.
    """
    _check_step_flags(progressive, early_exit, policy)
    default_policy = policy
    if progressive:
        assert cfg.family != "encdec", "progressive prefill: LM families only"
        assert cfg.l2r is not None, \
            "progressive prefill streams the quantized head: set cfg.l2r"

    def prefill(params, batch, policy=None):
        from contextlib import ExitStack

        from repro.sharding import ctx

        with ExitStack() as stack:
            if not backbone_hints:
                stack.enter_context(ctx.hints_disabled())
            return _prefill_body(params, batch, policy)

    def _prefill_body(params, batch, policy=None):
        if cfg.family == "encdec":
            state = init_encdec_state(cfg, batch["tokens"].shape[0], max_len,
                                      cache_dtype)
            hidden, state, _ = encdec_forward(
                cfg, params, tokens=batch["tokens"], frames=batch["frames"],
                mode="prefill", state=state)
        else:
            tokens = batch.get("tokens")
            embeds = batch.get("embeds")
            bsz = (tokens if tokens is not None else embeds).shape[0]
            state = init_lm_state(cfg, bsz, max_len, cache_dtype)
            fwd = _replicated_backbone(
                lambda p, s, t, e, rp: lm_forward(
                    cfg, p, tokens=t, embeds=e, rope_positions=rp,
                    mode="prefill", state=s), mesh, backbone_hints)
            hidden, state, _ = fwd(_backbone_params(params), state, tokens,
                                   embeds, batch.get("rope_positions"))
        if progressive:
            logits, tok, lv = progressive_logits_from_hidden(
                cfg, params, hidden[:, -1:], early_exit=early_exit,
                mesh=mesh,
                policy=policy if policy is not None else default_policy)
            return state, logits, tok.astype(jnp.int32), lv
        logits = logits_from_hidden(cfg, params, hidden[:, -1:])
        return state, logits

    return _serve_jit(prefill, jit_kwargs)


# ------------------------------------------------------- bucketed prefill
def prefill_buckets(max_len: int, min_bucket: int = 8) -> tuple[int, ...]:
    """Power-of-2 prompt-length buckets, capped at ``max_len``.

    Prompts pad to the smallest covering bucket, so prefill traces (and
    AOT executables) exist per BUCKET instead of per unique prompt
    length.  The last bucket is ``max_len`` itself (the cache bound),
    whether or not it is a power of two.
    """
    assert max_len >= 1
    out: list[int] = []
    b = min(min_bucket, max_len)
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


def bucket_for(length: int, buckets: tuple[int, ...]) -> int:
    """Smallest bucket covering ``length`` (buckets ascending)."""
    for b in buckets:
        if length <= b:
            return b
    raise ValueError(f"prompt length {length} exceeds largest bucket "
                     f"{buckets[-1]} (the cache bound)")


def supports_bucketed_prefill(cfg: ModelConfig) -> bool:
    """Bucketed (right-padded) prefill is exact only for attention
    mixers (``global``, ``local``, ``mla``): causal masking makes pad
    positions invisible to every real position, and the pad cache
    entries can be marked empty afterwards.  Recurrent mixers (ssd /
    rec) carry the state at the LAST position — pad tokens would
    contaminate it — so those families keep the exact-length prefill
    path."""
    return cfg.family != "encdec" and all(
        k in ("global", "local", "mla") for k, _ in cfg.layer_kinds())


def _mask_bucket_state(state: LMState, true_len: jax.Array) -> LMState:
    """Post-prefill fixup for a right-padded prompt: per-row ``pos``
    becomes the TRUE length and every KV-cache entry written by a pad
    position is marked empty (-1), so decode attention never sees pad
    keys and the first decoded token lands at position ``true_len`` —
    overwriting the stale pad k/v slot by slot as decoding proceeds.
    Bit-exact: masked entries contribute exact zeros to the softmax, and
    cache contents at slots < true_len are untouched."""
    tl = true_len.astype(jnp.int32).reshape(-1, 1)  # (B, 1): broadcasts
    #   against (B, L) and stacked (layers, B, L) position leaves alike

    def fix(c):
        if not isinstance(c, (KVCache, MLACache)):
            return c
        return c._replace(
            positions=jnp.where(c.positions < tl, c.positions, -1))

    is_kv = lambda x: isinstance(x, (KVCache, MLACache))
    return LMState(
        prefix=jax.tree.map(fix, state.prefix, is_leaf=is_kv),
        stack=jax.tree.map(fix, state.stack, is_leaf=is_kv),
        suffix=jax.tree.map(fix, state.suffix, is_leaf=is_kv),
        pos=true_len.astype(jnp.int32),
        moe_counts=state.moe_counts,
    )


def make_bucket_prefill_step(cfg: ModelConfig, max_len: int,
                             cache_dtype=jnp.bfloat16,
                             progressive: bool = False,
                             early_exit: bool = False,
                             backbone_hints: bool = True,
                             mesh: Mesh | None = None,
                             policy: LevelPolicy | None = None,
                             **jit_kwargs) -> Callable:
    """(params, tokens (B, Lb), true_len (B,)) -> make_prefill_step returns.

    The bucketed form of :func:`make_prefill_step`: ``tokens`` is a
    whole BUCKET of right-padded prompts (one traced/compiled program
    per (B, bucket) shape, not per unique prompt length) and ``true_len``
    carries each row's real prompt length.  The head consumes the hidden
    state at ``true_len - 1`` per row (not the pad tail), the returned
    state's ``pos`` is the true length, and pad-written cache entries
    are marked empty — decode from this state is bit-identical to an
    unpadded prefill of the same prompt (tests/test_gateway.py).

    Rows are independent, so multiple queued prompts PACK into one
    dispatch: pad the batch with dummy rows (``true_len = 1``) and
    ignore their outputs.  Attention families only (see
    :func:`supports_bucketed_prefill`); local (ring) windows require
    the bucket to fit the window, asserted at trace time.

    ``policy`` works as in :func:`make_prefill_step`: factory default,
    per-call trailing override (the gateway lowers the policy
    positionally into each bucket's AOT executable).
    """
    _check_step_flags(progressive, early_exit, policy)
    assert supports_bucketed_prefill(cfg), \
        "bucketed prefill: attention-mixer LM families only"
    default_policy = policy
    if progressive:
        assert cfg.l2r is not None, \
            "progressive prefill streams the quantized head: set cfg.l2r"
    local = any(k == "local" for k, _ in cfg.layer_kinds())

    def prefill(params, tokens, true_len, policy=None):
        from contextlib import ExitStack

        from repro.sharding import ctx

        with ExitStack() as stack:
            if not backbone_hints:
                stack.enter_context(ctx.hints_disabled())
            return _body(params, tokens, true_len, policy)

    def _body(params, tokens, true_len, policy=None):
        bsz, lb = tokens.shape
        if local:
            assert lb <= cfg.window, (
                f"bucket {lb} exceeds the local attention window "
                f"{cfg.window}: the ring cache would wrap over real "
                f"prompt entries")
        state = init_lm_state(cfg, bsz, max_len, cache_dtype)
        # an MoE layer routes the pad positions nowhere
        valid = (jnp.arange(lb)[None] < true_len[:, None]
                 if "moe" in cfg.ffn_kinds() else None)
        fwd = _replicated_backbone(
            lambda p, s, t, v: lm_forward(cfg, p, tokens=t, mode="prefill",
                                          state=s, valid=v),
            mesh, backbone_hints)
        hidden, state, _ = fwd(_backbone_params(params), state, tokens,
                               valid)
        idx = (true_len.astype(jnp.int32) - 1)[:, None, None]
        h_last = jnp.take_along_axis(hidden, idx, axis=1)  # (B, 1, d)
        state = _mask_bucket_state(state, true_len)
        if progressive:
            logits, tok, lv = progressive_logits_from_hidden(
                cfg, params, h_last, early_exit=early_exit, mesh=mesh,
                policy=policy if policy is not None else default_policy)
            return state, logits, tok.astype(jnp.int32), lv
        return state, logits_from_hidden(cfg, params, h_last)

    return _serve_jit(prefill, jit_kwargs)


@jax.named_scope("head")
def progressive_logits_from_hidden(cfg: ModelConfig, params, hidden,
                                   early_exit: bool = False,
                                   mesh: Mesh | None = None,
                                   policy: LevelPolicy | None = None):
    """Stream the LM head level-by-level, committing each row's token at
    its earliest sound MSDF level.

    The quantization recipe is exactly `logits_from_hidden`'s L2R path
    (dense -> l2r_matmul_f), so the returned logits are bit-identical to
    the full head evaluation and the committed tokens ALWAYS equal
    ``argmax(logits_from_hidden(...))`` — rows that never reach a sound
    early margin simply consume the whole stream.  ``early_exit=True``
    runs the head stream as the while-loop emitter that STOPS once every
    row has decided: tokens and exit levels stay bit-identical, but the
    returned logits are then the dequantized prefix at the exit level
    (core/progressive.py:streaming_argmax).  Returns
    ``(logits (..., V), tok (...,) int32, exit_level (...,) int32)``.

    When a mesh is installed (sharding/ctx.py), the stream runs as the
    ``shard_map``ped consensus walk — batch rows over the data axes,
    vocab shards over ``model``, early exit at the fleet-wide slowest
    row — with bit-identical logits, tokens, and exit levels
    (core/progressive.py:streaming_argmax, sharded walk).

    ``policy`` carries per-row :class:`~repro.core.policy.LevelPolicy`
    precision classes — one row per FLATTENED lead entry of ``hidden``
    (decode: one per batch slot) — threaded straight into the shared
    decision fold; ``exact`` rows roundtrip the full stream, ``budget``
    rows clamp at their level, ``bounded`` rows early-commit at their
    own tolerance.  Runs under the ``head`` named scope.
    """
    qcfg = cfg.l2r or QuantConfig()
    if "head_q" in params:  # the prepare_params load-time head cache
        wq, ws = params["head_q"].q, params["head_q"].scale
        p = params["head_q"].planes
        if p is not None and p.matches(qcfg.n_bits, qcfg.log2_radix,
                                       ndim=2, side="rhs"):
            wq = p  # cached plane stack: zero per-step operand prep
    else:
        if cfg.tie_embeddings:
            w = params["embed"].T
        else:
            w = params["head"]
        wq, ws = quantize(w.astype(hidden.dtype), qcfg, axis=-1)
    lead = hidden.shape[:-1]
    x2 = hidden.reshape(-1, hidden.shape[-1])
    xq, xs = quantize(x2, qcfg, axis=0 if qcfg.per_channel else None)
    if policy is not None:
        policy = policy.reshape((x2.shape[0],))
    logits, tok, lv = streaming_argmax(xq, wq, xs, ws, qcfg.n_bits,
                                       qcfg.log2_radix,
                                       levels=cfg.l2r_levels,
                                       out_dtype=hidden.dtype,
                                       early_exit=early_exit, mesh=mesh,
                                       policy=policy)
    return (logits.reshape(*lead, -1), tok.reshape(lead), lv.reshape(lead))


def make_decode_step(cfg: ModelConfig, progressive: bool = False,
                     early_exit: bool = False,
                     backbone_hints: bool = True,
                     mesh: Mesh | None = None,
                     policy: LevelPolicy | None = None,
                     **jit_kwargs) -> Callable:
    """(params, state, tokens (B,1)) -> (state, next_tokens (B,1), logits),
    jitted.

    ``progressive=True`` (LM families, requires ``cfg.l2r``) streams the
    final head matmul most-significant-level first and commits each
    token at its earliest decision level; the step then also returns the
    per-row exit levels: ``(state, next_tokens, logits, exit_level
    (B,1))``.  Tokens are bit-identical to the non-progressive step —
    the exit levels are what a digit-serial deployment would NOT compute.
    ``early_exit=True`` additionally stops the head's level loop once
    every slot in the batch has decided (the while-loop emitter): the
    skipped levels become skipped wall-clock on this host, not just an
    accounting entry, at the price of exit-level logit values for the
    non-argmax entries (tokens and exit levels are unchanged).
    ``backbone_hints=False`` scopes the interior sharding hints off
    during tracing — the replicated-backbone mesh setting — and ``mesh``
    overrides the context mesh for the head stream; see
    :func:`make_prefill_step`.

    ``policy`` (factory default, overridable per call as the trailing
    step argument — ``decode(params, state, tokens, rope_positions,
    policy)``) streams the head under per-slot
    :class:`~repro.core.policy.LevelPolicy` precision classes; the
    batcher/gateway splice admitted requests' classes into the slot
    rows so one fused while loop serves heterogeneous SLAs.
    """
    _check_step_flags(progressive, early_exit, policy)
    default_policy = policy
    if progressive:
        assert cfg.family != "encdec", "progressive decode: LM families only"
        assert cfg.l2r is not None, \
            "progressive decode streams the quantized head: set cfg.l2r"

    def decode(params, state, tokens, rope_positions=None, policy=None):
        from contextlib import ExitStack

        from repro.sharding import ctx

        with ExitStack() as stack:
            if not backbone_hints:
                stack.enter_context(ctx.hints_disabled())
            return _decode_body(params, state, tokens, rope_positions,
                                policy)

    def _decode_body(params, state, tokens, rope_positions=None,
                     policy=None):
        if cfg.family == "encdec":
            hidden, state, _ = encdec_forward(
                cfg, params, tokens=tokens, mode="decode", state=state)
        else:
            fwd = _replicated_backbone(
                lambda p, s, t, rp: lm_forward(
                    cfg, p, tokens=t, rope_positions=rp, mode="decode",
                    state=s), mesh, backbone_hints)
            hidden, state, _ = fwd(_backbone_params(params), state, tokens,
                                   rope_positions)
        if progressive:
            logits, tok, lv = progressive_logits_from_hidden(
                cfg, params, hidden, early_exit=early_exit, mesh=mesh,
                policy=policy if policy is not None else default_policy)
            return state, tok.astype(jnp.int32), logits, lv
        logits = logits_from_hidden(cfg, params, hidden)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return state, next_tok, logits

    return _serve_jit(decode, jit_kwargs)


def greedy_generate(cfg: ModelConfig, params, prompt: jax.Array, steps: int,
                    max_len: int | None = None, cache_dtype=jnp.float32):
    """Batched greedy decoding loop (host-driven; example/serving path)."""
    b, s = prompt.shape
    max_len = max_len or (s + steps)
    prefill = make_prefill_step(cfg, max_len, cache_dtype)
    decode = make_decode_step(cfg)
    state, logits = prefill(params, {"tokens": prompt})
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [tok]
    for _ in range(steps - 1):
        state, tok, _ = decode(params, state, tok)
        out.append(tok)
    return jnp.concatenate(out, axis=1)
