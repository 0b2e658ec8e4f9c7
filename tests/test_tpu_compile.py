"""The Pallas kernels of the main path, compiled at real widths for a
described (not attached) TPU v5e.

The TPU compiler refuses what interpret mode accepts: unaligned slices,
VMEM overruns, operand dtypes the MXU does not take.  Each case lowers
one kernel for one chip of a ``v5e:2x2`` topology and asserts that the
compiled module holds the Mosaic custom call.  Nothing runs.  The
topology is described inside a fixture, so collecting this file touches
no TPU library, and every test skips where it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cycle_model import VGG16_CONV_LAYERS
from repro.core.quant import plane_count
from repro.kernels.flash_attention import flash_attention_l2r_pallas
from repro.kernels.l2r_gemm import (l2r_gemm_pallas,
                                    l2r_gemm_pallas_stacked_planes,
                                    l2r_gemm_pallas_streaming_planes,
                                    stacked_tiles)

D = plane_count(8, 2)  # radix-4 digit planes of an 8-bit operand


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _conv_tap(m: int, cin: int, cout: int):
    """One tap of the fused conv at layer width: the (M, D*Kp) x
    (D*Kp, N) pre-stacked GEMM at the tiles the conv chooses for it
    (ops.py:_l2r_conv2d_int)."""
    mp, kp, np_ = (v + (-v) % 128 for v in (m, cin, cout))
    bm, bk, bn = stacked_tiles(mp, kp, np_)
    fn = lambda a, b: l2r_gemm_pallas_stacked_planes(a, b, 8, 2, None,
                                                     bm, bk, bn)
    return fn, ((mp, D * kp), jnp.int8), ((D * kp, np_), jnp.int8)


@pytest.mark.parametrize(
    "m,cin,cout", [(8 * l.R * l.C, l.N, l.M) for l in VGG16_CONV_LAYERS],
    ids=[f"vgg_{l.name}" for l in VGG16_CONV_LAYERS])
def test_stacked_kernel_vgg_widths(one_chip, m, cin, cout):
    """Every VGG-16 conv tap at batch 8, 224x224."""
    fn, a, b = _conv_tap(m, cin, cout)
    _compile(fn, one_chip, a, b)


def test_stacked_kernel_smollm_decode(one_chip):
    """SmolLM-135M's up projection at decode: 128 slot rows, K=576 (two
    256-deep blocks after padding), N=1536."""
    k = 576 + (-576) % 256
    fn = lambda a, b: l2r_gemm_pallas_stacked_planes(a, b, 8, 2)
    _compile(fn, one_chip, ((128, D * k), jnp.int8),
             ((D * k, 1536), jnp.int8))


def test_streaming_kernel_decode_head(one_chip):
    """The per-level snapshot stream at a decode-head shape: 128 rows
    against a 4096-column vocab shard, K=576."""
    k = 576 + (-576) % 256
    fn = lambda a, b: l2r_gemm_pallas_streaming_planes(a, b, 8, 2)
    _compile(fn, one_chip, ((128, D * k), jnp.int8),
             ((D * k, 4096), jnp.int8))


def test_flash_attention_l2r_head_dim_64(one_chip):
    """The flash-fused level-walk attention at SmolLM's head_dim 64."""
    fn = lambda q, k, v: flash_attention_l2r_pallas(q, k, v)
    _compile(fn, one_chip, ((1, 512, 9, 64), jnp.float32),
             ((1, 512, 3, 64), jnp.float32), ((1, 512, 3, 64), jnp.float32))


def test_pair_loop_kernel(one_chip):
    """The D^2 pair-loop baseline feeds int8 digit planes to the MXU."""
    fn = lambda a, b: l2r_gemm_pallas(a, b)
    _compile(fn, one_chip, ((256, 512), jnp.int8), ((512, 256), jnp.int8))


def test_kernels_carry_their_layer_name(one_chip):
    """A layer-named conv and fc GEMM compile to kernels named
    ``l2r_gemm_pallas_stacked_planes_<layer>``: the device trace puts
    their time on the layer, and the name keeps the ``l2r_gemm_pallas``
    every kernel-roofline reader matches."""
    import re

    from repro.kernels.l2r_gemm.ops import _l2r_conv2d_int, _l2r_gemm_backend

    conv = lambda x, w: _l2r_conv2d_int(x, w, 8, 2, None, "pallas-tpu",
                                        name="conv1_1")
    text = _compile(conv, one_chip, ((1, 16, 16, 3), jnp.int8),
                    ((3, 3, 3, 64), jnp.int8))
    assert re.search(r"%l2r_gemm_pallas_stacked_planes_conv1_1(\.\d+)? = ",
                     text)
    fc = lambda a, b: _l2r_gemm_backend(a, b, 8, 2, None, 128, 256, 128,
                                        "stacked", "pallas-tpu", name="fc6")
    text = _compile(fc, one_chip, ((8, 512), jnp.int8),
                    ((512, 256), jnp.int8))
    assert re.search(r"%l2r_gemm_pallas_stacked_planes_fc6(\.\d+)? = ", text)
