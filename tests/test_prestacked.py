"""Pre-stacked plane operands: bit-exact parity with inline extraction.

The digit-plane stacks are the real operands of every L2R schedule, so
building them once (PlaneOperands / the QuantizedWeights.planes load-time
cache) and reusing them across taps, steps and backends must change
NOTHING numerically: every prestacked entry point is swept against its
inline-extraction counterpart (n_bits x radix x levels x ragged shapes,
conv stride/dilation, jnp + pallas-interpret) for bit equality, and the
amortization itself is asserted by counting extraction calls (one
activation stack per feature map, zero weight extractions with a cache).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import (PlaneOperands, QuantConfig, quantize_weights,
                              stack_planes_lhs, stack_planes_rhs)
from repro.kernels.l2r_gemm import (int_gemm_ref, l2r_conv2d,
                                    l2r_conv2d_progressive,
                                    l2r_conv2d_progressive_while, l2r_gemm,
                                    l2r_gemm_progressive)
from repro.kernels.l2r_gemm import ops as l2r_ops


def _rand_ints(rng, n_bits, shape):
    lo, hi = -(1 << (n_bits - 1)), (1 << (n_bits - 1))
    dtype = np.int8 if n_bits <= 8 else np.int16
    return jnp.asarray(rng.integers(lo, hi, shape, dtype=dtype))


# ------------------------------------------------------------ layout core
@pytest.mark.parametrize("n_bits,log2_radix", [(8, 1), (8, 2), (8, 4),
                                               (16, 2)])
def test_plane_layout_conversion_exact(n_bits, log2_radix):
    """raw <-> shifted chunk conversion reproduces the direct extraction
    of either layout bit-for-bit, both sides, with and without the
    streaming window padding."""
    rng = np.random.default_rng(n_bits * 8 + log2_radix)
    a = _rand_ints(rng, n_bits, (9, 11))
    b = _rand_ints(rng, n_bits, (11, 6))
    for wp in (False, True):
        pa = PlaneOperands.prepare_lhs(a, n_bits, log2_radix, shifted=False,
                                       window_pad=wp)
        pb = PlaneOperands.prepare_rhs(b, n_bits, log2_radix, shifted=False,
                                       window_pad=wp)
        np.testing.assert_array_equal(
            np.asarray(pa.core_stack(True)),
            np.asarray(stack_planes_lhs(a, n_bits, log2_radix, shifted=True)))
        np.testing.assert_array_equal(
            np.asarray(pb.core_stack(True)),
            np.asarray(stack_planes_rhs(b, n_bits, log2_radix, shifted=True)))
        # round trip through the shifted layout is the identity
        rt = pa.with_layout(True).with_layout(False)
        np.testing.assert_array_equal(np.asarray(rt.stack),
                                      np.asarray(pa.stack))
        # the window stack is the core stack plus (D-1)*K zero columns
        d = pa.d
        w = np.asarray(pa.window_stack())
        assert w.shape[-1] == (2 * d - 1) * pa.k
        np.testing.assert_array_equal(w[..., :d * pa.k],
                                      np.asarray(pa.core_stack(False)))
        assert (w[..., d * pa.k:] == 0).all()


# ------------------------------------------------------------- GEMM parity
@pytest.mark.parametrize("n_bits,log2_radix", [(8, 1), (8, 2), (8, 4),
                                               (16, 2)])
@pytest.mark.parametrize("shape", [(7, 13, 5), (33, 65, 17)])
def test_gemm_prestacked_parity_jnp(n_bits, log2_radix, shape):
    """Every prestacked combination (lhs/rhs/both x raw/shifted x window
    padding) equals the inline path on the jnp backend, at full depth and
    truncated levels."""
    m, k, n = shape
    rng = np.random.default_rng(m + n_bits + log2_radix)
    a = _rand_ints(rng, n_bits, (m, k))
    b = _rand_ints(rng, n_bits, (k, n))
    d = n_bits // log2_radix
    for levels in (None, 1, min(3, 2 * d - 1)):
        ref = np.asarray(l2r_gemm(a, b, n_bits, log2_radix, levels,
                                  backend="jnp"))
        for shifted in (False, True):
            for wp in (False, True):
                pa = PlaneOperands.prepare_lhs(a, n_bits, log2_radix,
                                               shifted=shifted, window_pad=wp)
                pb = PlaneOperands.prepare_rhs(b, n_bits, log2_radix,
                                               shifted=shifted, window_pad=wp)
                for aa, bb in ((pa, b), (a, pb), (pa, pb)):
                    out = np.asarray(l2r_gemm(aa, bb, n_bits, log2_radix,
                                              levels, backend="jnp"))
                    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("levels", [None, 3])
def test_gemm_prestacked_parity_pallas_interpret(levels):
    """Prestacked operands through the pre-stacked Pallas kernel entry
    (interpret mode) equal the raw-operand kernel path bit-for-bit."""
    rng = np.random.default_rng(11)
    a = _rand_ints(rng, 8, (70, 90))
    b = _rand_ints(rng, 8, (90, 40))
    ref = np.asarray(l2r_gemm(a, b, levels=levels,
                              backend="pallas-interpret"))
    for shifted in (False, True):
        pa = PlaneOperands.prepare_lhs(a, shifted=shifted)
        pb = PlaneOperands.prepare_rhs(b, shifted=shifted, window_pad=True)
        out = np.asarray(l2r_gemm(pa, pb, levels=levels,
                                  backend="pallas-interpret"))
        np.testing.assert_array_equal(out, ref)
        out = np.asarray(l2r_gemm(a, pb, levels=levels,
                                  backend="pallas-interpret"))
        np.testing.assert_array_equal(out, ref)


def test_gemm_prestacked_streaming_schedule():
    """schedule="streaming" consumes prestacked operands (the streaming
    emitters read the same zero-padded window the inline path builds)."""
    rng = np.random.default_rng(12)
    a = _rand_ints(rng, 8, (19, 23))
    b = _rand_ints(rng, 8, (23, 9))
    ref = np.asarray(int_gemm_ref(a, b))
    pa = PlaneOperands.prepare_lhs(a, window_pad=True)
    pb = PlaneOperands.prepare_rhs(b)
    out = np.asarray(l2r_gemm(pa, pb, schedule="streaming", backend="jnp"))
    np.testing.assert_array_equal(out, ref)
    out = np.asarray(l2r_gemm(pa, pb, schedule="streaming", backend="jnp",
                              early_exit=True))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
def test_gemm_progressive_prestacked_parity(backend):
    """The per-level snapshot stream is identical from prestacked and raw
    operands on both backends."""
    rng = np.random.default_rng(13)
    a = _rand_ints(rng, 8, (37, 53))
    b = _rand_ints(rng, 8, (53, 29))
    r_raw = l2r_gemm_progressive(a, b, backend=backend)
    r_pre = l2r_gemm_progressive(PlaneOperands.prepare_lhs(a),
                                 PlaneOperands.prepare_rhs(b),
                                 backend=backend)
    np.testing.assert_array_equal(np.asarray(r_raw.partial),
                                  np.asarray(r_pre.partial))


def test_gemm_prestacked_validation():
    """Mismatched layouts / sides / schedules are rejected loudly."""
    rng = np.random.default_rng(14)
    a = _rand_ints(rng, 8, (8, 8))
    b = _rand_ints(rng, 8, (8, 8))
    pa = PlaneOperands.prepare_lhs(a)
    pb = PlaneOperands.prepare_rhs(b)
    with pytest.raises(ValueError, match="lhs"):
        l2r_gemm(pb, b)  # rhs stack in the lhs slot
    with pytest.raises(ValueError, match="n_bits"):
        l2r_gemm(pa, b, n_bits=8, log2_radix=4)  # layout/config mismatch
    with pytest.raises(TypeError, match="pairs"):
        l2r_gemm(pa, pb, schedule="pairs")


def test_streaming_consumers_reject_mismatched_stack():
    """The streaming emitters (streaming_argmax & friends) validate the
    stack's digit config — a radix-mismatched stack would mis-slice the
    level walk silently otherwise."""
    from repro.core.progressive import streaming_argmax

    rng = np.random.default_rng(15)
    a = _rand_ints(rng, 8, (4, 8))
    b = _rand_ints(rng, 8, (8, 6))
    pb = PlaneOperands.prepare_rhs(b, 8, 2)
    xs = jnp.ones((4, 1), jnp.float32)
    ws = jnp.ones((1, 6), jnp.float32)
    with pytest.raises(ValueError, match="re-prepare"):
        streaming_argmax(a, pb, xs, ws, n_bits=8, log2_radix=4)
    pa = PlaneOperands.prepare_lhs(a, 8, 2)
    with pytest.raises(ValueError, match="rhs"):
        streaming_argmax(a, pa, xs, ws)  # lhs stack in the rhs slot


# ------------------------------------------------------------- conv parity
@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
@pytest.mark.parametrize("stride,dilation", [(1, 1), (2, 1), (1, 2), (2, 2)])
def test_conv_weight_cache_parity(backend, stride, dilation):
    """l2r_conv2d with the prestacked weight cache == without, bit-for-
    bit, across stride/dilation geometries on both backends."""
    rng = np.random.default_rng(stride * 10 + dilation)
    cfg = QuantConfig()
    x = jnp.asarray(rng.standard_normal((2, 9, 7, 5)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((3, 3, 5, 6)).astype(np.float32))
    plain = quantize_weights(w, cfg)
    pre = quantize_weights(w, cfg, prestack=True, plane_axis=-2)
    o_plain = np.asarray(l2r_conv2d(x, None, cfg=cfg, w_q=plain,
                                    backend=backend, stride=stride,
                                    dilation=dilation))
    o_pre = np.asarray(l2r_conv2d(x, None, cfg=cfg, w_q=pre, backend=backend,
                                  stride=stride, dilation=dilation))
    np.testing.assert_array_equal(o_plain, o_pre)


@pytest.mark.parametrize("n_bits,log2_radix", [(8, 1), (8, 4)])
def test_conv_weight_cache_parity_radix_sweep(n_bits, log2_radix):
    """Cache parity holds at every digit width (jnp backend)."""
    rng = np.random.default_rng(n_bits + log2_radix)
    cfg = QuantConfig(n_bits=n_bits, log2_radix=log2_radix)
    x = jnp.asarray(rng.standard_normal((1, 6, 5, 3)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((3, 3, 3, 4)).astype(np.float32))
    plain = quantize_weights(w, cfg)
    pre = quantize_weights(w, cfg, prestack=True, plane_axis=-2)
    for levels in (None, 2):
        o_plain = np.asarray(l2r_conv2d(x, None, cfg=cfg, w_q=plain,
                                        levels=levels, backend="jnp"))
        o_pre = np.asarray(l2r_conv2d(x, None, cfg=cfg, w_q=pre,
                                      levels=levels, backend="jnp"))
        np.testing.assert_array_equal(o_plain, o_pre)


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
def test_conv_progressive_weight_cache_parity(backend):
    """The progressive conv's per-level stream is identical with the
    cached weight stack, and so is the early-exit while form (jnp)."""
    rng = np.random.default_rng(20)
    cfg = QuantConfig()
    x = jnp.asarray(rng.standard_normal((1, 7, 6, 4)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 5)).astype(np.float32))
    plain = quantize_weights(w, cfg)
    pre = quantize_weights(w, cfg, prestack=True, plane_axis=-2)
    r_plain, s_plain = l2r_conv2d_progressive(x, None, cfg=cfg, w_q=plain,
                                              backend=backend)
    r_pre, s_pre = l2r_conv2d_progressive(x, None, cfg=cfg, w_q=pre,
                                          backend=backend)
    np.testing.assert_array_equal(np.asarray(r_plain.partial),
                                  np.asarray(r_pre.partial))
    np.testing.assert_array_equal(np.asarray(s_plain), np.asarray(s_pre))
    if backend == "jnp":
        a_plain = l2r_conv2d_progressive_while(x, None, cfg=cfg, w_q=plain)
        a_pre = l2r_conv2d_progressive_while(x, None, cfg=cfg, w_q=pre)
        np.testing.assert_array_equal(np.asarray(a_plain[0]),
                                      np.asarray(a_pre[0]))


# -------------------------------------------------- extraction amortization
class _Counter:
    def __init__(self, fn):
        self.fn, self.calls = fn, 0

    def __call__(self, *a, **kw):
        self.calls += 1
        return self.fn(*a, **kw)


@pytest.mark.parametrize("backend,shape", [("jnp", (2, 10, 9, 3)),
                                           ("pallas-interpret", (2, 8, 11, 3))])
def test_conv_single_activation_extraction_per_feature_map(
        monkeypatch, backend, shape):
    """The fused conv performs exactly ONE activation plane extraction
    per feature map on every backend, and ZERO weight extractions when
    the load-time cache is present (the 3x3 layer's 9 taps share them).
    Shapes are unique per backend so the jitted conv core re-traces under
    the counting wrappers."""
    cfg = QuantConfig()
    rng = np.random.default_rng(30)
    x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((3, 3, shape[-1], 6))
                    .astype(np.float32))
    pre = quantize_weights(w, cfg, prestack=True, plane_axis=-2)
    lhs = _Counter(l2r_ops.stack_planes_lhs)
    rhs = _Counter(l2r_ops.stack_planes_rhs)
    monkeypatch.setattr(l2r_ops, "stack_planes_lhs", lhs)
    monkeypatch.setattr(l2r_ops, "stack_planes_rhs", rhs)
    jax.block_until_ready(l2r_conv2d(x, None, cfg=cfg, w_q=pre,
                                     backend=backend))
    assert lhs.calls == 1, f"{lhs.calls} activation extractions (want 1)"
    assert rhs.calls == 0, f"{rhs.calls} weight extractions (want 0: cached)"


def test_conv_inline_weight_extraction_once_per_call(monkeypatch):
    """Without the cache the weight stack is still extracted exactly once
    per call (not once per tap)."""
    cfg = QuantConfig()
    rng = np.random.default_rng(31)
    x = jnp.asarray(rng.standard_normal((1, 12, 7, 3)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((3, 3, 3, 4)).astype(np.float32))
    plain = quantize_weights(w, cfg)
    lhs = _Counter(l2r_ops.stack_planes_lhs)
    rhs = _Counter(l2r_ops.stack_planes_rhs)
    monkeypatch.setattr(l2r_ops, "stack_planes_lhs", lhs)
    monkeypatch.setattr(l2r_ops, "stack_planes_rhs", rhs)
    jax.block_until_ready(l2r_conv2d(x, None, cfg=cfg, w_q=plain,
                                     backend="jnp"))
    assert lhs.calls == 1 and rhs.calls == 1


def test_streaming_head_zero_weight_extraction(monkeypatch):
    """streaming_argmax with the window-padded weight-stack cache does no
    weight plane extraction at all (the decode-step hot path)."""
    from repro.core import progressive as prog
    from repro.core.quant import quantize

    cfg = QuantConfig()
    rng = np.random.default_rng(32)
    x = jnp.asarray(rng.standard_normal((5, 24)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((24, 13)).astype(np.float32))
    w_q = quantize_weights(w, cfg, prestack=True, window_pad=True)
    xq, xs = quantize(x, cfg, axis=0)
    ref = prog.streaming_argmax(xq, w_q.q, xs, w_q.scale)
    lhs = _Counter(prog.stack_planes_lhs)
    rhs = _Counter(prog.stack_planes_rhs)
    monkeypatch.setattr(prog, "stack_planes_lhs", lhs)
    monkeypatch.setattr(prog, "stack_planes_rhs", rhs)
    out = prog.streaming_argmax(xq, w_q.planes, xs, w_q.scale)
    assert rhs.calls == 0, f"{rhs.calls} weight extractions (want 0: cached)"
    for r, o in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


# --------------------------------------------------------- model threading
def test_vgg16_prestack_cache_bit_identical():
    """vgg16_apply and the progressive classify path are bit-identical
    with and without the per-layer plane-stack cache."""
    from repro.models.cnn import (vgg16_apply, vgg16_build,
                                  vgg16_classify_progressive,
                                  vgg16_quantize_weights)
    from repro.models.common import materialize

    cfg = QuantConfig()
    params = materialize(vgg16_build(n_classes=12), jax.random.PRNGKey(0))
    rng = np.random.default_rng(40)
    imgs = jnp.asarray(rng.standard_normal((2, 32, 32, 3)).astype(np.float32))
    plain = vgg16_quantize_weights(params, cfg, prestack=False)
    pre = vgg16_quantize_weights(params, cfg, prestack=True)
    np.testing.assert_array_equal(
        np.asarray(vgg16_apply(params, imgs, l2r=cfg, weights_q=plain)),
        np.asarray(vgg16_apply(params, imgs, l2r=cfg, weights_q=pre)))
    for a, b in zip(vgg16_classify_progressive(params, imgs, cfg,
                                               weights_q=plain),
                    vgg16_classify_progressive(params, imgs, cfg,
                                               weights_q=pre)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_prestack_cache_bit_identical():
    """prepare_params(prestack=True): prefill + progressive decode emit
    identical tokens/exit levels/logits to the extract-per-call cache —
    including through the stacked-layer scan (whose slicing strips the
    plane stacks' layer axis)."""
    from repro.configs import get_smoke
    from repro.models.common import materialize
    from repro.models.transformer import lm_build
    from repro.serve.engine import (make_decode_step, make_prefill_step,
                                    prepare_params)

    cfg = dataclasses.replace(get_smoke("smollm-135m"), l2r=QuantConfig())
    params = materialize(lm_build(cfg), jax.random.PRNGKey(0))
    plain = prepare_params(cfg, params, prestack=False)
    pre = prepare_params(cfg, params, prestack=True)
    rng = np.random.default_rng(41)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 6)), jnp.int32)
    prefill = make_prefill_step(cfg, 32, jnp.float32, progressive=True)
    decode = make_decode_step(cfg, progressive=True)
    s1, lg1, t1, lv1 = prefill(plain, {"tokens": prompt})
    s2, lg2, t2, lv2 = prefill(pre, {"tokens": prompt})
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(lv1), np.asarray(lv2))
    np.testing.assert_array_equal(np.asarray(lg1), np.asarray(lg2))
    _, t1b, lg1b, lv1b = decode(plain, s1, t1)
    _, t2b, lg2b, lv2b = decode(pre, s2, t2)
    np.testing.assert_array_equal(np.asarray(t1b), np.asarray(t2b))
    np.testing.assert_array_equal(np.asarray(lv1b), np.asarray(lv2b))
    np.testing.assert_array_equal(np.asarray(lg1b), np.asarray(lg2b))
