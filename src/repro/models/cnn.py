"""VGG-16 — the paper's evaluation network, with the L2R conv path.

Convolutions run either as plain float (lax.conv) or through the paper's
composite inner-product pipeline via the **fused** conv op
(kernels/l2r_gemm/ops.py:l2r_conv2d): digit planes are extracted once per
feature map and each kernel tap streams a shifted view through the
level-stacked MSDF GEMM — no (B*H*W, cin*kh*kw) patch matrix in HBM.
The backend (jnp / pallas-interpret / pallas-tpu) is chosen by the
dispatcher (ops.py:resolve_backend).  With all significance levels the
L2R path is exact W8A8 integer conv; with fewer levels it is the
progressive-precision (online early output) mode.

Weights quantize ONCE per model load: build the cache with
:func:`vgg16_quantize_weights` and pass it to :func:`vgg16_apply` —
per-forward weight quantization then disappears from the traces.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.cycle_model import VGG16_CONV_LAYERS
from repro.core.progressive import streaming_argmax
from repro.core.quant import (QuantConfig, QuantizedWeights, quantize,
                              quantize_weights)
from repro.kernels.l2r_gemm.ops import l2r_conv2d, l2r_matmul_f

from .common import Param

__all__ = ["vgg16_build", "vgg16_apply", "vgg16_classify_progressive",
           "vgg16_quantize_weights", "VGG16_CONV_LAYERS"]


def vgg16_build(n_classes: int = 1000, in_channels: int = 3) -> dict:
    params: dict = {}
    c_in = in_channels
    for layer in VGG16_CONV_LAYERS:
        params[layer.name] = {
            "w": Param((layer.k, layer.k, c_in, layer.M), (None, None, None, "ffn")),
            "b": Param((layer.M,), ("ffn",), init="zeros"),
        }
        c_in = layer.M
    params["fc6"] = {"w": Param((512 * 7 * 7, 4096), (None, "ffn")),
                     "b": Param((4096,), ("ffn",), init="zeros")}
    params["fc7"] = {"w": Param((4096, 4096), ("ffn", "ffn")),
                     "b": Param((4096,), ("ffn",), init="zeros")}
    params["fc8"] = {"w": Param((4096, n_classes), ("ffn", "vocab")),
                     "b": Param((n_classes,), ("vocab",), init="zeros")}
    return params


def vgg16_quantize_weights(params: dict, cfg: QuantConfig = QuantConfig(),
                           prestack: bool = True, mesh=None
                           ) -> dict[str, QuantizedWeights]:
    """The L2R weight cache: every matmul/conv weight -> int8 + per-
    out-channel scale, built exactly once at model load.

    ``prestack=True`` (default) also caches each layer's reversed RHS
    digit-plane stack (core/quant.py:PlaneOperands — contraction axis
    -2 for conv weights, 0 for the FC head) so the conv taps and the
    streamed fc8 head consume pre-extracted planes: weight planes are
    extracted exactly once per process instead of once per call.  Costs
    D x the int8 weight bytes; pass False to keep extract-per-call.

    ``mesh`` (default: the installed ``sharding.ctx`` mesh) shards the
    fc8 head cache — int8 weight, scales, window-padded plane stack —
    over the ``model`` axis on the class dim, the layout the
    ``shard_map``ped consensus stream of
    :func:`vgg16_classify_progressive` consumes directly.  The trunk
    caches stay replicated (the trunk runs exactly; only the streamed
    head is vocab-sharded).  Values are unchanged either way.
    """
    if mesh is None:
        from repro.sharding import ctx

        mesh = ctx.get_mesh()
    return {name: quantize_weights(
                p["w"], cfg, prestack=prestack,
                plane_axis=-2 if len(p["w"].shape) == 4 else 0,
                window_pad=prestack and name == "fc8",
                shard=(None, "model") if name == "fc8" and mesh is not None
                else None,
                mesh=mesh if name == "fc8" else None)
            for name, p in params.items()}


def _conv_float(x, w, b):
    out = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return out + b.astype(x.dtype)


def vgg16_apply(
    params: dict,
    images: jax.Array,  # (B, H, W, 3)
    l2r: QuantConfig | None = None,
    levels: int | None = None,
    weights_q: dict[str, QuantizedWeights] | None = None,
    backend: str | None = None,
    n_dense_pool: int = 5,
) -> jax.Array:
    """Forward pass.  Returns logits (B, n_classes).

    Works for any input size that survives 5 pools >= 1 pixel; the FC
    head adapts via average pooling to 7x7 (or the remaining size).
    ``weights_q`` is the load-time cache from
    :func:`vgg16_quantize_weights`; when omitted on the L2R path it is
    built here (once per call — callers that jit or loop should build it
    themselves so weights quantize once per model load, not per forward).
    """
    x, weights_q = _vgg16_trunk(params, images, l2r, levels, weights_q,
                                backend)
    with jax.named_scope("fc8"):
        if l2r is not None:
            return l2r_matmul_f(x, None, l2r, levels, w_q=weights_q["fc8"],
                                backend=backend, name="fc8") \
                + params["fc8"]["b"]
        return x @ params["fc8"]["w"].astype(x.dtype) + params["fc8"]["b"]


def _vgg16_trunk(params, images, l2r, levels, weights_q, backend):
    """Everything up to the fc8 classifier head: (fc7 activations,
    weights_q).  Shared by the one-shot and progressive classify paths.
    Each layer runs under a ``jax.named_scope`` of its name (``conv1_1``
    .. ``fc7``), and its L2R kernel is named after it
    (``l2r_gemm_pallas_stacked_planes_conv1_1``), so a device trace
    puts kernel time on layers."""
    x = images
    if l2r is not None and weights_q is None:
        weights_q = vgg16_quantize_weights(params, l2r)
    if l2r is not None:
        conv = lambda x, p, name: l2r_conv2d(
            x, None, p["b"], l2r, levels, w_q=weights_q[name], backend=backend,
            name=name)
    else:
        conv = lambda x, p, name: _conv_float(x, p["w"], p["b"])
    stage_splits = {1: 2, 3: 2, 6: 2, 9: 2, 12: 2}  # pool after these conv idxs
    for i, layer in enumerate(VGG16_CONV_LAYERS):
        with jax.named_scope(layer.name):
            x = jax.nn.relu(conv(x, params[layer.name], layer.name))
        if i in stage_splits:
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )
    # adaptive head: resize feature map to the canonical 7x7 so the FC
    # head works for any input resolution (smoke tests use 32x32 images)
    bsz, h, w_, c = x.shape
    if (h, w_) != (7, 7):
        x = jax.image.resize(x, (bsz, 7, 7, c), "linear")
    flat = x.reshape(bsz, -1)
    if l2r is not None:
        mm = lambda a, name: l2r_matmul_f(
            a, None, l2r, levels, w_q=weights_q[name], backend=backend,
            name=name)
    else:
        mm = lambda a, name: a @ params[name]["w"].astype(a.dtype)
    x = flat
    for name in ("fc6", "fc7"):
        with jax.named_scope(name):
            x = jax.nn.relu(mm(x, name) + params[name]["b"])
    return x, weights_q


def vgg16_classify_progressive(
    params: dict,
    images: jax.Array,
    l2r: QuantConfig = QuantConfig(),
    weights_q: dict[str, QuantizedWeights] | None = None,
    backend: str | None = None,
    early_exit: bool = False,
    mesh=None,
):
    """Classification with online early exit on the fc8 logit stream.

    The trunk (convs + fc6/fc7) runs exactly (all MSDF levels); the fc8
    head streams level by level and each image commits its class as soon
    as the top-1 logit margin beats the scaled tail bound on the unseen
    digits — the paper's "most significant digits decide first" property
    as a serving primitive.  The committed class ALWAYS equals
    ``argmax(vgg16_apply(..., l2r=l2r))`` (undecided rows fall back to
    the full stream).

    ``early_exit=True`` stops the head's level loop once EVERY image in
    the batch has decided (the while-loop emitter): classes and exit
    levels stay bit-identical, the saved levels become saved wall-clock,
    and the returned logits are the dequantized prefix at the exit level
    (full-depth values only when some image needed the whole stream).

    Returns ``(pred (B,) int32, exit_level (B,) int32, logits (B, C))``;
    exit_level counts MSDF levels consumed (2D-2 = needed everything).

    When a mesh is installed (sharding/ctx.py, or the explicit ``mesh=``
    override), the head stream runs as the ``shard_map``ped consensus
    walk — images batch-sharded over the data axes, fc8 classes over
    ``model``, early exit at the fleet-wide slowest image — with
    predictions, exit levels, and logits bit-identical to the
    single-device stream.
    """
    x, weights_q = _vgg16_trunk(params, images, l2r, None, weights_q, backend)
    w_q = weights_q["fc8"]
    # quantize the head activations exactly as l2r_matmul_f does, so the
    # streamed accumulator is bit-identical to the one-shot fc8 matmul
    xq, xs = quantize(x, l2r, axis=0 if l2r.per_channel else None)
    # the load-time plane-stack cache feeds the stream directly (the
    # stream is bit-identical either way — the inline path extracts the
    # very same stack per call)
    p = w_q.planes
    wq_in = p if (p is not None and p.matches(l2r.n_bits, l2r.log2_radix,
                                              ndim=2, side="rhs")) else w_q.q
    with jax.named_scope("fc8"):
        logits, pred, exit_level = streaming_argmax(
            xq, wq_in, xs, w_q.scale, l2r.n_bits, l2r.log2_radix,
            bias=params["fc8"]["b"], out_dtype=x.dtype,
            early_exit=early_exit, mesh=mesh)
    return pred, exit_level, logits
