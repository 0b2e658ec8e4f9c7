"""Mixture-of-Experts FFN: top-k routing with capacity, scatter dispatch,
shared experts (DeepSeekMoE) and top-1 routed + shared (Llama-4 style).

Training dispatch (:func:`moe_apply`) is scatter/gather based — token
t's i-th choice of expert e gets slot p = (number of earlier assignments
to e); assignments beyond the static capacity C are dropped (standard
capacity dropping).  This avoids the (tokens, experts, capacity) one-hot
einsum blow-up and maps onto an all-to-all when experts are sharded over
the "model" mesh axis.

Serving (:func:`moe_serve`) never drops: capacity dropping would make a
request's output depend on the requests batched with it.  Each held
expert computes the rows routed to it in a buffer of
``capacity_factor`` times their expected count; a step in which one
expert is routed more computes further rounds of such buffers until
every routed row has been computed.

**Held experts.** The router always scores all ``n_experts`` and picks
its top-k over all of them.  A layer told that it holds
``n_experts_held`` experts from ``expert_offset`` on (one chip's share
under expert parallelism) has only those experts' weights and adds only
their part of the result; the choices of experts held elsewhere add
nothing here.  On one chip there is no exchange.

Routing runs in float32 (the router's logits decide discrete choices).
Gates are the softmax probabilities of the chosen experts, renormalised
to sum 1 when ``moe_norm_topk``, otherwise kept raw (DeepSeek-V2's gate
with its published ``routed_scaling_factor`` of 1).  Named scopes:
``moe_route``, ``moe_experts`` and ``moe_shared``; the expert GEMMs'
L2R kernels are named ``l2r_gemm_pallas_stacked_planes_experts``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.quant import QuantizedWeights
from repro.kernels.l2r_gemm.kernel import DENSE_BM

from .common import Param, dense
from .config import ModelConfig

__all__ = ["moe_build", "moe_apply", "moe_serve", "moe_capacity",
           "moe_serve_capacity", "MOE_COUNTS"]

# the serving counters moe_serve returns, in order (int32 (3,)): rows
# routed to held experts, expert rows computed (padding included), and
# the most rows one held expert was routed
MOE_COUNTS = ("rows_routed", "rows_computed", "rows_max_expert")


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    ideal = cfg.experts_per_token * n_tokens / max(cfg.n_experts, 1)
    cap = int(math.ceil(ideal * cfg.capacity_factor))
    return max(8, min(cap, n_tokens))


def moe_build(cfg: ModelConfig) -> dict:
    e, d, f = cfg.experts_held, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    glu = cfg.ffn_kind in ("swiglu", "geglu")
    params = {
        "router": Param((d, cfg.n_experts), ("embed", "router"), scale=0.02),
        "wi": Param((e, d, 2, f) if glu else (e, d, f),
                    ("experts", "embed", None, "ffn") if glu else ("experts", "embed", "ffn")),
        "wo": Param((e, f, d), ("experts", "ffn", "embed")),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        params["shared_wi"] = Param(
            (d, 2, fs) if glu else (d, fs),
            ("embed", None, "ffn") if glu else ("embed", "ffn"),
        )
        params["shared_wo"] = Param((fs, d), ("ffn", "embed"))
    return params


def _expert_ffn(cfg: ModelConfig, wi, wo, xb: jax.Array) -> jax.Array:
    """xb: (E, C, d) -> (E, C, d); per-expert GLU/GELU FFN.

    With the L2R switch on, or with weights quantized per expert
    (``prepare_params``' :class:`~repro.core.quant.QuantizedWeights`, or
    the ``{"q", "scale"}`` records of ``quantize_desc``), the expert
    matmuls run through :func:`~repro.models.common.dense` vmapped over
    experts: the backend-dispatched level-stacked kernel, named
    ``experts`` in device traces, with per-row activation scales and
    per-expert weight scales."""
    glu = cfg.ffn_kind in ("swiglu", "geglu")
    l2r = cfg.l2r is not None or isinstance(wi, (QuantizedWeights, dict))

    def mm(xs, ws):
        return jax.vmap(lambda xe, we: dense(xe, we, cfg.l2r, cfg.l2r_levels,
                                             name="experts"))(xs, ws)
    if l2r:
        h = mm(xb, wi)
    else:
        h = jnp.einsum("ecd,ed...f->ec...f", xb, wi.astype(xb.dtype))
    if glu:
        gate, up = h[..., 0, :], h[..., 1, :]
        act = jax.nn.silu(gate) if cfg.ffn_kind == "swiglu" else jax.nn.gelu(gate)
        h = act * up
    else:
        h = jax.nn.gelu(h)
    if l2r:
        return mm(h, wo)
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(xb.dtype))


def _route(cfg: ModelConfig, params: dict, xt: jax.Array):
    """Float32 top-k routing of tokens xt (T, d) over all ``n_experts``.
    Returns (probs (T, E), gates (T, k), local expert index (T, k),
    held (T, k)): a choice is held where its expert is one of this
    layer's ``experts_held`` from ``expert_offset``; ``local`` is its
    index among them."""
    with jax.named_scope("moe_route"):
        logits = jnp.dot(xt.astype(jnp.float32),
                         params["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
        if cfg.moe_norm_topk:
            gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        local = idx - cfg.expert_offset
        held = (local >= 0) & (local < cfg.experts_held)
        return probs, gates, local, held


def _shared_ffn(cfg: ModelConfig, params: dict, xt: jax.Array) -> jax.Array:
    with jax.named_scope("moe_shared"):
        glu = cfg.ffn_kind in ("swiglu", "geglu")
        h = dense(xt, params["shared_wi"], cfg.l2r, cfg.l2r_levels)
        if glu:
            g_, u_ = h[..., 0, :], h[..., 1, :]
            h = (jax.nn.silu(g_) if cfg.ffn_kind == "swiglu" else jax.nn.gelu(g_)) * u_
        else:
            h = jax.nn.gelu(h)
        return dense(h, params["shared_wo"], cfg.l2r, cfg.l2r_levels)


def moe_serve_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    """Rows per held expert in one round of serving's dispatch:
    ``capacity_factor`` times the expected share of ``n_tokens``, rounded
    up to the L2R kernel's row tile (fewer rows would be padded to it
    anyway), at most ``n_tokens``."""
    want = cfg.capacity_factor * n_tokens * cfg.experts_per_token \
        / max(cfg.n_experts, 1)
    return min(n_tokens, DENSE_BM * math.ceil(want / DENSE_BM))


def moe_serve(cfg: ModelConfig, params: dict, x: jax.Array,
              valid: jax.Array | None = None):
    """Serving MoE on x (B, S, d), dropless.  Returns (out, counts) with
    ``counts`` the int32 :data:`MOE_COUNTS` of this call.

    Each held expert takes the rows routed to it, in token order, into a
    buffer of :func:`moe_serve_capacity` rows.  Where one expert is
    routed more rows than that, its rows beyond the buffer go through
    further rounds of the same buffers (a ``while`` loop that runs only
    then), so nothing is dropped and the buffers never grow.  Rows
    are independent (per-row activation scales, exact integer GEMMs)
    and a token sums its choices in one fixed order, so its output does
    not depend on the round that computed it or on the rows beside it.
    ``valid`` (B, S) marks the rows whose output is used; the others
    (bucket padding, a packed prefill's dummy rows) are routed nowhere
    and add only the shared experts.  Under a mesh the tokens stay
    data-parallel and the expert buffers lie on the "model" axis, as in
    :func:`moe_apply`."""
    from repro.sharding.ctx import hint, hint_dp

    b, s, d = x.shape
    t, eh, k = b * s, cfg.experts_held, cfg.experts_per_token
    cap = moe_serve_capacity(cfg, t)
    xt = hint_dp(x.reshape(t, d))  # tokens stay DP-sharded through routing
    _, gates, local, held = _route(cfg, params, xt)
    if valid is not None:
        held = held & valid.reshape(t, 1)
    with jax.named_scope("moe_experts"):
        expert = jnp.where(held, local, 0)  # (T, k)
        onehot = jax.nn.one_hot(jnp.where(held, local, -1), eh,
                                dtype=jnp.int32)  # (T, k, E_h)
        per_expert = onehot.sum((0, 1))
        # slot of each held choice among its expert's rows, in token order
        before = jnp.cumsum(onehot.reshape(t * k, eh), 0).reshape(t, k, eh) \
            - onehot
        slot = jnp.take_along_axis(before, expert[..., None], -1)[..., 0]
        rounds = (per_expert.max() + cap - 1) // cap
        ffn = functools.partial(_expert_ffn, cfg, params["wi"], params["wo"])
        # each choice's row, flat (T k, d): a scatter of these stays on
        # the data axis of a mesh, where a broadcast (T, k, d) update did
        # not (several times the temporaries in a 16x16 prefill dry run)
        rows = hint_dp(xt[jnp.repeat(jnp.arange(t), k)])

        def one_round(r):
            """The held choices in slots [r cap, (r + 1) cap) of their
            expert's rows, and their experts' outputs (T, k, d)."""
            here = held & (slot // cap == r)
            buf = jnp.zeros((eh, cap, d), x.dtype).at[
                jnp.where(here, expert, eh).reshape(-1),
                (slot % cap).reshape(-1)].add(rows, mode="drop")
            yb = hint(ffn(hint(buf, "model")), "model")  # (E_h, C, d)
            return here, yb[expert, slot % cap]

        v = one_round(0)[1]
        if cap < t:  # an expert may be routed more rows than one round

            def further(r, v):
                here, vr = one_round(r)
                return jnp.where(here[..., None], vr, v)

            v = jax.lax.fori_loop(1, rounds, further, v)
        w = jnp.where(held, gates, 0.0)
        y = w[:, 0, None] * v[:, 0].astype(jnp.float32)
        for j in range(1, k):  # one fixed order of the choices' sum
            y = y + w[:, j, None] * v[:, j].astype(jnp.float32)
        out = hint_dp(y).astype(x.dtype)
    if cfg.n_shared_experts:
        out = out + _shared_ffn(cfg, params, xt)
    computed = (jnp.maximum(rounds, 1) * eh * cap).astype(jnp.int32)
    counts = jnp.stack([per_expert.sum(), computed, per_expert.max()])
    return out.reshape(b, s, d), counts


def _dp_groups(t: int) -> int:
    """Number of shard-local dispatch groups = total device count (the
    flat token dim is sharded over dp x model); 1 without a mesh."""
    from repro.sharding.ctx import get_mesh

    mesh = get_mesh()
    if mesh is None:
        return 1
    n = mesh.size
    return n if n > 1 and t % n == 0 else 1


def moe_apply(cfg: ModelConfig, params: dict, x: jax.Array):
    """x: (B, S, d) -> (out, aux_loss).  Routed top-k + optional shared."""
    if cfg.moe_dp_local and _dp_groups(x.shape[0] * x.shape[1]) > 1:
        return moe_apply_dp_local(cfg, params, x)
    b, s, d = x.shape
    t = b * s
    k = cfg.experts_per_token
    cap = moe_capacity(cfg, t)
    xt = x.reshape(t, d)

    from repro.sharding.ctx import hint, hint_dp

    xt = hint_dp(xt)  # tokens stay DP-sharded through routing
    probs, gate_vals, local, held = _route(cfg, params, xt)  # (T, E), (T, k)
    probs = hint_dp(probs)

    # slot assignment: position of each held (t, i) among assignments to
    # its expert, in token order (cumsum of one-hot counts); choices of
    # experts held elsewhere take no slot.
    e = cfg.experts_held
    flat_e = jnp.where(held, local, 0).reshape(-1)  # (T*k,)
    onehot = jax.nn.one_hot(jnp.where(held, local, -1).reshape(-1), e,
                            dtype=jnp.int32)  # (T*k, E_h)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot  # exclusive prefix count
    slot = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]  # (T*k,)
    keep = (slot < cap) & held.reshape(-1)
    gates = gate_vals.reshape(-1) * keep

    # dispatch: (E, C, d) expert buffers (all-to-all under expert sharding)
    buf = jnp.zeros((e, cap, d), x.dtype)
    safe_slot = jnp.where(keep, slot, cap - 1)
    src = jnp.repeat(jnp.arange(t), k)
    contrib = hint_dp(jnp.where(keep[:, None], xt[src], 0))
    buf = buf.at[flat_e, safe_slot].add(contrib, mode="drop")
    buf = hint(buf, "model")  # experts live on the model axis

    yb = _expert_ffn(cfg, params["wi"], params["wo"], buf)  # (E, C, d)
    yb = hint(yb, "model")

    # combine: gather each kept assignment back, weighted by its gate
    y_tok = yb[flat_e, safe_slot]  # (T*k, d)
    y = jnp.zeros((t, d), jnp.float32).at[src].add(
        y_tok.astype(jnp.float32) * gates[:, None]
    )
    out = hint_dp(y).astype(x.dtype)

    if cfg.n_shared_experts:
        out = out + _shared_ffn(cfg, params, xt)

    # Switch-style load-balance aux loss (over the held experts)
    lo = cfg.expert_offset
    me = probs[:, lo:lo + e].mean(0)  # (E_h,) mean router prob
    ce = jnp.zeros((e,), jnp.float32).at[flat_e].add(keep.astype(jnp.float32)) / max(t * k, 1)
    aux = e * jnp.sum(me * ce) * cfg.router_aux_weight
    return out.reshape(b, s, d), aux


def moe_apply_dp_local(cfg: ModelConfig, params: dict, x: jax.Array):
    """DP-local-capacity MoE (§Perf hillclimb B).

    Tokens are DP-major (the batch dim is sharded over ("pod","data")),
    so reshaping to (DP, T_local) aligns group g with data shard g.  Slot
    assignment and the dispatch scatter then happen *inside* each shard
    (zero communication); the single cross-device movement is the
    (DP, E, C_local, d) -> (E, DP*C_local, d) transpose, which GSPMD
    lowers to the canonical MoE all-to-all.  Capacity is per shard
    (C_local = ceil(k*T_local/E * factor)): dropping is shard-local,
    the standard behavior of production MoE systems.
    """
    from repro.sharding.ctx import hint, hint_dp

    assert cfg.experts_held == cfg.n_experts, \
        "DP-local dispatch shards every expert over the mesh"
    b, s, d = x.shape
    t = b * s
    e = cfg.n_experts
    k = cfg.experts_per_token
    dp = _dp_groups(t)
    t_local = t // dp
    cap = moe_capacity(cfg, t_local)

    # flattened rows are (batch x seq)-major: batch is DP-sharded AND the
    # sequence is model-sharded between blocks (Megatron-SP), so the flat
    # token dim must be pinned over BOTH axes — dropping this constraint
    # (hillclimb B5) regressed 14.9s -> 18.2s: GSPMD then gathers rows
    # over "model" for the router/shared-expert matmuls.
    all_axes = ("pod", "data", "model")
    xt = hint(x.reshape(t, d), all_axes)
    probs, gate_vals, expert_idx, _ = _route(cfg, params, xt)  # (T, k)
    probs = hint(probs, all_axes)

    xg = xt.reshape(dp, t_local, d)
    eg = expert_idx.reshape(dp, t_local, k)
    gg = gate_vals.reshape(dp, t_local, k)

    def dispatch_one(x_l, e_l, g_l):
        flat_e = e_l.reshape(-1)  # (T_l*k,)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        keep = slot < cap
        gates = g_l.reshape(-1) * keep
        safe_slot = jnp.where(keep, slot, cap - 1)
        src = jnp.repeat(jnp.arange(t_local), k)
        contrib = jnp.where(keep[:, None], x_l[src], 0)
        buf = jnp.zeros((e, cap, d), x_l.dtype).at[flat_e, safe_slot].add(
            contrib, mode="drop")
        return buf, flat_e, safe_slot, gates, src, keep

    bufs, flat_e, safe_slot, gates, src, keep = jax.vmap(dispatch_one)(
        xg, eg, gg)  # bufs: (G, E, C, d), one group per device
    # dispatch is device-local: group dim pinned over ALL mesh axes
    bufs = hint(bufs, all_axes)
    # the all-to-all happens HERE: regroup so each chip holds its dp-row's
    # groups for its "model"-axis expert slice; the expert FFN is vmapped
    # over the group dim — no reshape/transpose of sharded dims, so GSPMD
    # never materializes a gathered copy.
    bufs = hint(bufs, ("pod", "data"), "model")
    yb = jax.vmap(
        lambda b_: _expert_ffn(cfg, params["wi"], params["wo"], b_))(bufs)
    yb = hint(yb, ("pod", "data"), "model")
    # all-to-all back: groups return to their owning device for combine
    ybg = hint(yb, all_axes)  # (G, E, C, d)

    def combine_one(y_l, fe, ss, g_l, src_l):
        y_tok = y_l[fe, ss]  # (T_l*k, d)
        out = jnp.zeros((t_local, d), jnp.float32).at[src_l].add(
            y_tok.astype(jnp.float32) * g_l[:, None])
        return out

    y = jax.vmap(combine_one)(ybg, flat_e, safe_slot, gates, src)
    out = hint(y.reshape(t, d), all_axes).astype(x.dtype)

    if cfg.n_shared_experts:
        out = out + _shared_ffn(cfg, params, xt)

    me = probs.mean(0)
    ce = jnp.zeros((e,), jnp.float32).at[flat_e.reshape(-1)].add(
        keep.reshape(-1).astype(jnp.float32)) / max(t * k, 1)
    aux = e * jnp.sum(me * ce) * cfg.router_aux_weight
    return out.reshape(b, s, d), aux
