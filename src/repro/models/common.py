"""Parameter descriptors, initialization, norms and the dense primitive.

Models are written as pairs of pure functions:

    build(cfg)  -> pytree of Param descriptors (shape/dtype/logical axes)
    apply(cfg, params, ...) -> activations

The descriptor tree is materialized three ways:
  * materialize(tree, rng)      -> real arrays (training / CPU smoke tests)
  * abstract(tree)              -> jax.ShapeDtypeStruct (multi-pod dry-run:
                                   no allocation of 400B-parameter models)
  * partition_specs(tree,rules) -> PartitionSpec tree for pjit shardings.

Every matmul in the stack goes through :func:`dense`, which dispatches to
the paper's L2R digit-plane pipeline when the config carries a
QuantConfig — making the technique a first-class switch on all
architectures.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.l2r_gemm import l2r_dense
from repro.core.quant import QuantConfig, QuantizedWeights, quantize_weights
from repro.kernels.l2r_gemm.ops import l2r_matmul_f

__all__ = [
    "Param",
    "materialize",
    "abstract",
    "partition_specs",
    "dense",
    "quantize_tree",
    "rms_norm",
    "layer_norm",
    "count_params",
]


@dataclasses.dataclass(frozen=True)
class Param:
    """Declarative parameter: shape, logical axes, init recipe."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"  # normal | zeros | ones | embed
    scale: float | None = None  # stddev override; default fan-in
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_param(x) -> bool:
    return isinstance(x, Param)


def materialize(tree, rng: jax.Array, param_dtype=jnp.float32):
    """Instantiate real arrays for a descriptor tree."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_param)
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, p in zip(keys, leaves):
        dtype = param_dtype if p.dtype == jnp.float32 else p.dtype
        if p.init == "zeros":
            out.append(jnp.zeros(p.shape, dtype))
        elif p.init == "ones":
            out.append(jnp.ones(p.shape, dtype))
        else:
            fan_in = p.shape[0] if len(p.shape) >= 2 else max(p.shape[-1], 1)
            if p.init == "embed":
                std = p.scale if p.scale is not None else 0.02
            else:
                std = p.scale if p.scale is not None else 1.0 / math.sqrt(fan_in)
            out.append((jax.random.normal(key, p.shape, jnp.float32) * std).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def abstract(tree, param_dtype=jnp.float32):
    """ShapeDtypeStruct stand-ins (dry-run: no device allocation)."""
    def f(p: Param):
        dtype = param_dtype if p.dtype == jnp.float32 else p.dtype
        return jax.ShapeDtypeStruct(p.shape, dtype)
    return jax.tree.map(f, tree, is_leaf=_is_param)


def partition_specs(tree, rules: dict[str, Any]):
    """Map logical axes -> mesh axes.  rules values: str | tuple | None."""
    def f(p: Param):
        return P(*(rules.get(a, None) if a is not None else None for a in p.axes))
    return jax.tree.map(f, tree, is_leaf=_is_param)


def count_params(tree) -> int:
    leaves = jax.tree.leaves(tree, is_leaf=_is_param)
    return sum(math.prod(p.shape) for p in leaves)


def dense(
    x: jax.Array,
    w,
    l2r: QuantConfig | None = None,
    l2r_levels: int | None = None,
    name: str | None = None,
) -> jax.Array:
    """x @ w with optional L2R digit-plane arithmetic (the paper's unit).

    w may have >2 dims (e.g. fused qkv (d, 3, h*dh)); trailing dims are
    flattened for the contraction and restored after.

    w may also be pre-quantized (built ONCE at model load):
      * :class:`~repro.core.quant.QuantizedWeights` (quantize_tree /
        serve.engine.prepare_params) — the L2R weight cache.  With an
        ``l2r`` config the activations stream through the dispatched
        level-stacked digit-plane kernel against the cached int8 weights
        (no per-forward weight quantization in the trace); without one it
        is plain W8A8 integer dense.
      * a legacy {"q": int8, "scale"} record (quantize_desc/
        quantize_params, the checkpoint codec): W8A8 serving arithmetic.
    Weights stored in int8 halve the HBM weight traffic that dominates
    decode; the integer product is exactly what the L2R composite IPU
    computes digit-serially (bit equality proven in
    tests/test_kernel_l2r_gemm.py).

    ``name`` tags the L2R kernel in device traces
    (kernels/l2r_gemm/ops.py:l2r_matmul_f); results never depend on it.
    """
    if isinstance(w, QuantizedWeights):
        trail = w.q.shape[1:]
        wq = w.q.reshape(w.q.shape[0], -1) if w.q.ndim > 2 else w.q
        ws = jnp.broadcast_to(w.scale, (1, *trail)).reshape(1, -1)
        if l2r is not None:
            planes = w.planes
            if planes is not None and planes.stack.ndim > 2:
                # flatten trailing output dims of the cached RHS stack the
                # same way as q (the contraction axis is leading, so the
                # plane layout is untouched)
                planes = dataclasses.replace(
                    planes, stack=planes.stack.reshape(
                        planes.stack.shape[0], -1), axis=-2)
            out = l2r_matmul_f(x, None, l2r, l2r_levels,
                               w_q=QuantizedWeights(wq, ws, planes),
                               name=name)
            return out.reshape(*x.shape[:-1], *trail)
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        from repro.core.quant import quantize

        xq, xs = quantize(x2, QuantConfig(), axis=0)  # per-row act scales
        out = jax.lax.dot_general(
            xq, wq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        out = out.astype(jnp.float32) * xs * ws
        return out.astype(x.dtype).reshape(*lead, *trail)
    if isinstance(w, dict) and "q" in w:
        wq, scale = w["q"], w["scale"]
        trail = wq.shape[1:]
        if wq.ndim > 2:
            wq = wq.reshape(wq.shape[0], -1)
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        from repro.core.quant import quantize

        xq, xs = quantize(x2, QuantConfig(), axis=0)  # per-row act scales
        out = jax.lax.dot_general(
            xq, wq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        out = out.astype(jnp.float32) * xs * scale.reshape(()).astype(jnp.float32)
        return out.astype(x.dtype).reshape(*lead, *trail)
    if w.ndim > 2:
        out = dense(x, w.reshape(w.shape[0], -1), l2r, l2r_levels, name)
        return out.reshape(*x.shape[:-1], *w.shape[1:])
    if l2r is not None:
        # production L2R path: the backend-dispatched level-stacked kernel
        # (kernels/l2r_gemm), not the pure-jnp core pair loop
        return l2r_matmul_f(x, w, l2r, l2r_levels, name=name)
    return l2r_dense(x, w, l2r, l2r_levels)


def _quantizable(p: Param) -> bool:
    """Matmul weights eligible for int8 storage: 2D+ normal-init params
    that are not embedding/vocab tables (lookup + tied logits stay f32),
    not MoE routers (routing runs in float32), and not MLA's latent
    up-projection (its absorbed decode reorders the product, which int8
    activations would not commute with).  Routed-expert stacks
    (:func:`_expert_stack`) are eligible and keep a scale per expert."""
    return (p.init == "normal" and len(p.shape) >= 2
            and not {"vocab", "router", "latent"} & set(p.axes))


def _expert_stack(p: Param) -> bool:
    """A routed-expert weight stack ((layers,) experts, K, ..., N)."""
    return p.init == "normal" and "experts" in p.axes and len(p.shape) >= 3


def _scale_lead(p: Param) -> int:
    """How many leading axes of an eligible weight keep their own
    {"q", "scale"} scale: the stacked layer axis, and an expert stack's
    expert axis."""
    if _expert_stack(p):
        return p.axes.index("experts") + 1
    return 1 if p.axes and p.axes[0] == "layers" else 0


def quantize_desc(desc_tree):
    """Descriptor transform: eligible Param -> {"q": int8, "scale": f32}.

    One scale per (stacked layer x) (expert x) tensor; dense() dispatches
    on the record.  This is the serving-time storage format of the L2R
    pipeline: the Pallas kernel consumes exactly these int8 operands and
    streams their digit planes MSDF in VMEM.
    """
    def f(p: Param):
        if not _quantizable(p):
            return p
        n, rest = _scale_lead(p), len(p.shape) - _scale_lead(p)
        return {
            "q": Param(p.shape, p.axes, init=p.init, scale=p.scale,
                       dtype=jnp.int8),
            "scale": Param(p.shape[:n] + (1,) * rest,
                           p.axes[:n] + (None,) * rest, init="ones"),
        }
    return jax.tree.map(f, desc_tree, is_leaf=_is_param)


def quantize_params(desc_tree, params):
    """Materialized f32 params -> int8 records matching quantize_desc."""
    from repro.core.quant import QuantConfig, quantize

    def f(p: Param, w):
        if not _quantizable(p):
            return w
        wf = w.astype(jnp.float32)
        amax = jnp.max(jnp.abs(wf), axis=tuple(range(_scale_lead(p), wf.ndim)),
                       keepdims=True)
        scale = jnp.maximum(amax, 1e-30) / 127.0
        q = jnp.clip(jnp.round(wf / scale), -127, 127)
        return {"q": q.astype(jnp.int8), "scale": scale}
    return jax.tree.map(f, desc_tree, params, is_leaf=_is_param)


def quantize_tree(desc_tree, params, cfg: QuantConfig = QuantConfig(),
                  prestack: bool = False):
    """Materialized f32 params -> :class:`QuantizedWeights` leaves.

    The load-time L2R weight cache for full model trees: every eligible
    matmul weight (same eligibility as quantize_desc) is quantized ONCE,
    per out-channel (and per stacked layer and expert), so serving traces
    carry no weight quantization ops.  dense() consumes the records
    directly.

    ``prestack=True`` additionally caches each weight's reversed RHS
    digit-plane stack (core/quant.py:PlaneOperands, contraction axis 0 —
    axis 1 for stacked-layer weights, whose leading layer axis the
    forward scan strips) so the serving traces carry no weight plane
    extraction either: D x the int8 weight bytes buys
    extract-once-per-process operands.

    Routed-expert stacks quantize per (layer,) expert and out-channel,
    their contraction axis the one after the expert axis.
    """
    def f(p: Param, w):
        if _expert_stack(p):
            e = p.axes.index("experts")
            return quantize_weights(w, cfg, channel_axes=(*range(e + 1), -1),
                                    prestack=prestack, plane_axis=e + 1)
        if not _quantizable(p):
            return w
        stacked = p.axes and p.axes[0] == "layers"
        axes = (0, -1) if stacked else (-1,)
        return quantize_weights(w, cfg, channel_axes=axes, prestack=prestack,
                                plane_axis=1 if stacked else 0)
    return jax.tree.map(f, desc_tree, params, is_leaf=_is_param)


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + gamma.astype(jnp.float32))
    return out.astype(dtype)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return out.astype(dtype)
