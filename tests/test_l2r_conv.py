"""Fused L2R conv (implicit im2col) + the load-time weight cache.

The fused conv must be bit-identical to materialized im2col + the MSDF
digit-plane GEMM on the same quantized operands (the tap decomposition
splits the (kh, kw, cin) contraction exactly), and W8A8-close to
lax.conv in float.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.l2r_gemm import l2r_matmul_int
from repro.core.quant import QuantConfig, QuantizedWeights, quantize_weights
from repro.kernels.l2r_gemm import l2r_conv2d, l2r_conv2d_progressive
from repro.kernels.l2r_gemm.kernel import (TILE_VMEM_BUDGET, stacked_tiles,
                                           stacked_vmem_bytes)
from repro.kernels.l2r_gemm.ops import (_l2r_conv2d_int,
                                        _l2r_conv2d_progressive_int)


def _im2col_int(xq, wq, levels=None):
    """Oracle: materialized patches -> pair-loop MSDF GEMM, same ints."""
    bsz, h, w_, cin = xq.shape
    kh, kw, _, cout = wq.shape
    patches = jax.lax.conv_general_dilated_patches(
        xq.astype(jnp.float32), (kh, kw), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )  # (B, H, W, cin*kh*kw), channel-major (cin, kh, kw) — exact in f32
    flat = jnp.round(patches).astype(jnp.int8).reshape(bsz * h * w_, -1)
    wmat = wq.transpose(2, 0, 1, 3).reshape(-1, cout)
    out = l2r_matmul_int(flat, wmat, 8, 2, levels)
    return np.asarray(out).reshape(bsz, h, w_, cout)


@pytest.mark.parametrize("levels", [None, 1, 3, 5, 7])
def test_fused_conv_bit_identical_to_im2col(levels):
    """Every truncation depth: tap-decomposed == patch-materialized."""
    rng = np.random.default_rng(0 if levels is None else levels)
    xq = jnp.asarray(rng.integers(-128, 128, (2, 9, 7, 5), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-128, 128, (3, 3, 5, 6), dtype=np.int8))
    out = np.asarray(_l2r_conv2d_int(xq, wq, 8, 2, levels, "jnp"))
    np.testing.assert_array_equal(out, _im2col_int(xq, wq, levels))


def test_fused_conv_1x1_and_5x5():
    rng = np.random.default_rng(9)
    xq = jnp.asarray(rng.integers(-128, 128, (1, 8, 8, 4), dtype=np.int8))
    for k in (1, 5):
        wq = jnp.asarray(rng.integers(-128, 128, (k, k, 4, 3), dtype=np.int8))
        out = np.asarray(_l2r_conv2d_int(xq, wq, 8, 2, None, "jnp"))
        np.testing.assert_array_equal(out, _im2col_int(xq, wq))


def test_fused_conv_backends_agree():
    rng = np.random.default_rng(4)
    xq = jnp.asarray(rng.integers(-128, 128, (1, 5, 5, 3), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-128, 128, (3, 3, 3, 4), dtype=np.int8))
    out_jnp = np.asarray(_l2r_conv2d_int(xq, wq, 8, 2, None, "jnp"))
    out_pl = np.asarray(_l2r_conv2d_int(xq, wq, 8, 2, None, "pallas-interpret"))
    np.testing.assert_array_equal(out_pl, out_jnp)


def _pad128(v):
    return -(-v // 128) * 128


@pytest.mark.parametrize("x_shape,cin,cout,levels,tiles", [
    ((1, 16, 16), 200, 192, None, (256, 256, 256)),  # bk = a whole chunk
    ((2, 16, 16), 3, 256, None, (512, 128, 256)),
    ((2, 12, 12), 130, 64, 5, (384, 256, 128)),      # truncated walk
], ids=["bk_whole_chunk", "bm512_bn256", "bm384_levels5"])
def test_fused_conv_shape_chosen_tiles_bit_identical(x_shape, cin, cout,
                                                     levels, tiles):
    """The Pallas conv at tiles wider than 128 in every dimension equals
    the jnp backend and quantized im2col, bit for bit."""
    bsz, h, w_ = x_shape
    assert stacked_tiles(_pad128(bsz * h * w_), _pad128(cin),
                         _pad128(cout)) == tiles
    rng = np.random.default_rng(cin + cout)
    xq = jnp.asarray(rng.integers(-128, 128, (*x_shape, cin), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-128, 128, (3, 3, cin, cout),
                                  dtype=np.int8))
    out_pl = np.asarray(_l2r_conv2d_int(xq, wq, 8, 2, levels,
                                        "pallas-interpret"))
    out_jnp = np.asarray(_l2r_conv2d_int(xq, wq, 8, 2, levels, "jnp"))
    np.testing.assert_array_equal(out_pl, out_jnp)
    np.testing.assert_array_equal(out_pl, _im2col_int(xq, wq, levels))


def _vgg16_taps(batch):
    from repro.core.cycle_model import VGG16_CONV_LAYERS
    return [(_pad128(batch * l.R * l.C), _pad128(l.N), _pad128(l.M))
            for l in VGG16_CONV_LAYERS]


@pytest.mark.parametrize("m,k,n", _vgg16_taps(8) + _vgg16_taps(1) + [
    (128, 128, 128), (1664, 640, 1536), (384, 384, 384), (128 * 97, 128, 64 * 128),
])
def test_stacked_tiles_divide_and_fit(m, k, n):
    """Every tile is a multiple of 128 dividing its padded dimension, and
    the blocks fit the VMEM budget."""
    tiles = stacked_tiles(m, k, n)
    for t, dim in zip(tiles, (m, k, n)):
        assert t % 128 == 0 and dim % t == 0, (tiles, (m, k, n))
    assert stacked_vmem_bytes(*tiles) <= TILE_VMEM_BUDGET


def test_stacked_tiles_vgg16_grid_steps():
    """VGG-16 at batch 8 walks under 100,000 grid steps over its 13 convs
    (1,484,352 at the fixed (128, <=256, 128) tiles): rows x columns x
    16 plane pairs x k-blocks x 9 taps."""
    steps = 0
    for m, k, n in _vgg16_taps(8):
        bm, bk, bn = stacked_tiles(m, k, n)
        steps += (m // bm) * (n // bn) * 16 * (k // bk) * 9
    assert steps < 100_000, steps


def test_fused_conv_w8a8_close_to_lax_conv():
    """Float-level acceptance: fused W8A8 conv vs the lax.conv reference."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 12, 12, 8)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((3, 3, 8, 16)) * 0.1).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((16,)).astype(np.float32))
    out = np.asarray(l2r_conv2d(x, w, b))
    ref = np.asarray(jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b)
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.02, rel  # int8 W8A8 quantization error


def _lax_conv_int(xq, wq, stride=(1, 1), dilation=(1, 1)):
    """Strided/dilated integer conv oracle (f32 is exact for int8 taps)."""
    out = jax.lax.conv_general_dilated(
        xq.astype(jnp.float32), wq.astype(jnp.float32), stride, "SAME",
        rhs_dilation=dilation, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return np.round(np.asarray(out)).astype(np.int64)


@pytest.mark.parametrize("stride,dilation", [
    ((2, 2), (1, 1)), ((1, 1), (2, 2)), ((2, 1), (1, 3)), ((3, 3), (2, 2)),
])
def test_fused_conv_stride_dilation_parity(stride, dilation):
    """Strided/dilated shifted-view slicing vs lax.conv_general_dilated,
    exact on the integer operands."""
    rng = np.random.default_rng(sum(stride) * 10 + sum(dilation))
    xq = jnp.asarray(rng.integers(-128, 128, (2, 11, 9, 5), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-128, 128, (3, 3, 5, 6), dtype=np.int8))
    out = np.asarray(_l2r_conv2d_int(xq, wq, 8, 2, None, "jnp",
                                     stride, dilation))
    np.testing.assert_array_equal(out.astype(np.int64),
                                  _lax_conv_int(xq, wq, stride, dilation))


def test_fused_conv_stride_backends_agree():
    rng = np.random.default_rng(21)
    xq = jnp.asarray(rng.integers(-128, 128, (1, 6, 6, 3), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-128, 128, (3, 3, 3, 4), dtype=np.int8))
    out_j = np.asarray(_l2r_conv2d_int(xq, wq, 8, 2, None, "jnp",
                                       (2, 2), (1, 1)))
    out_p = np.asarray(_l2r_conv2d_int(xq, wq, 8, 2, None,
                                       "pallas-interpret", (2, 2), (1, 1)))
    np.testing.assert_array_equal(out_p, out_j)


def test_fused_conv_strided_float_close_to_lax():
    rng = np.random.default_rng(22)
    x = jnp.asarray(rng.standard_normal((1, 9, 9, 4)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((3, 3, 4, 6)) * 0.2).astype(np.float32))
    out = np.asarray(l2r_conv2d(x, w, None, QuantConfig(), stride=2,
                                dilation=2))
    ref = np.asarray(jax.lax.conv_general_dilated(
        x, w, (2, 2), "SAME", rhs_dilation=(2, 2),
        dimension_numbers=("NHWC", "HWIO", "NHWC")))
    assert out.shape == ref.shape
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.02, rel


# ------------------------------------------------------- progressive conv
@pytest.mark.parametrize("stride,dilation", [((1, 1), (1, 1)), ((2, 2), (1, 1))])
def test_conv_progressive_prefixes_bit_identical(stride, dilation):
    """Level l of the conv stream == the fused conv truncated at l+1 —
    the conv-level analogue of the streaming GEMM invariant."""
    rng = np.random.default_rng(23)
    xq = jnp.asarray(rng.integers(-128, 128, (2, 7, 6, 5), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-128, 128, (3, 3, 5, 4), dtype=np.int8))
    stack = np.asarray(_l2r_conv2d_progressive_int(
        xq, wq, 8, 2, None, "jnp", stride, dilation))
    assert stack.shape[0] == 7
    for t in range(7):
        np.testing.assert_array_equal(
            stack[t],
            np.asarray(_l2r_conv2d_int(xq, wq, 8, 2, t + 1, "jnp",
                                       stride, dilation)),
            err_msg=f"level {t + 1}")


def test_conv_progressive_backends_agree():
    rng = np.random.default_rng(24)
    xq = jnp.asarray(rng.integers(-128, 128, (1, 5, 5, 3), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-128, 128, (3, 3, 3, 4), dtype=np.int8))
    s_j = np.asarray(_l2r_conv2d_progressive_int(xq, wq, 8, 2, None, "jnp",
                                                 (1, 1), (1, 1)))
    s_p = np.asarray(_l2r_conv2d_progressive_int(
        xq, wq, 8, 2, None, "pallas-interpret", (1, 1), (1, 1)))
    np.testing.assert_array_equal(s_p, s_j)


def test_conv_progressive_float_envelope():
    """The dequantized stream converges to the exact W8A8 conv and every
    prefix stays inside the scaled tail-bound envelope."""
    rng = np.random.default_rng(25)
    cfg = QuantConfig()
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 4)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((3, 3, 4, 6)) * 0.2).astype(np.float32))
    res, scale = l2r_conv2d_progressive(x, w, cfg)
    exact = np.asarray(l2r_conv2d(x, w, None, cfg), np.float64)
    final = np.asarray(res.partial[-1], np.float64) * np.asarray(scale,
                                                                 np.float64)
    np.testing.assert_allclose(final, exact, rtol=1e-6, atol=1e-6)
    for t in range(res.partial.shape[0]):
        err = np.abs(np.asarray(res.partial[t], np.int64)
                     - np.asarray(res.partial[-1], np.int64))
        assert (err <= float(res.tail_bound[t])).all(), t


def test_fused_conv_weight_cache_bit_identical():
    """Passing the load-time cache must not change a single bit vs
    quantizing the same weights inside the call."""
    rng = np.random.default_rng(2)
    cfg = QuantConfig()
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 4)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((3, 3, 4, 6)) * 0.2).astype(np.float32))
    w_q = quantize_weights(w, cfg)
    assert isinstance(w_q, QuantizedWeights)
    assert w_q.q.dtype == jnp.int8 and w_q.q.shape == w.shape
    out_cached = np.asarray(l2r_conv2d(x, None, None, cfg, w_q=w_q))
    out_fresh = np.asarray(l2r_conv2d(x, w, None, cfg))
    np.testing.assert_array_equal(out_cached, out_fresh)


def test_quantized_weights_is_pytree():
    """The cache must flow through jit/scan/tree transparently."""
    w_q = quantize_weights(jnp.ones((4, 3)))
    leaves, treedef = jax.tree.flatten(w_q)
    assert len(leaves) == 2
    back = jax.tree.unflatten(treedef, leaves)
    assert isinstance(back, QuantizedWeights)
    doubled = jax.jit(lambda t: jax.tree.map(lambda x: x, t))(w_q)
    assert isinstance(doubled, QuantizedWeights)


def test_vgg16_weight_cache_path():
    """vgg16_apply(l2r=...) through the prebuilt cache: bit-identical to
    the cache built internally, and the cache quantizes each weight once."""
    from repro.models.cnn import (vgg16_apply, vgg16_build,
                                  vgg16_quantize_weights)
    from repro.models.common import materialize

    cfg = QuantConfig()
    params = materialize(vgg16_build(n_classes=10), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.standard_normal((1, 32, 32, 3)).astype(np.float32))
    cache = vgg16_quantize_weights(params, cfg)
    assert all(isinstance(v, QuantizedWeights) for v in cache.values())
    out_cached = np.asarray(vgg16_apply(params, img, l2r=cfg, weights_q=cache))
    out_auto = np.asarray(vgg16_apply(params, img, l2r=cfg))
    np.testing.assert_array_equal(out_cached, out_auto)


def test_dense_quantized_weights_record():
    """models/common.dense consumes QuantizedWeights on both paths."""
    from repro.models.common import dense

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((6, 32)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((32, 10)) * 0.2).astype(np.float32))
    cfg = QuantConfig()
    w_q = quantize_weights(w, cfg)
    # L2R path: cached weights == freshly quantized weights, bit for bit
    np.testing.assert_array_equal(
        np.asarray(dense(x, w_q, l2r=cfg)), np.asarray(dense(x, w, l2r=cfg)))
    # plain W8A8 path (no l2r config): close to the float matmul
    out = np.asarray(dense(x, w_q))
    ref = np.asarray(x @ w)
    assert np.abs(out - ref).max() / np.abs(ref).max() < 0.02
