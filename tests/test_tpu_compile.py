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


def test_held_experts_gemm_published_widths(one_chip):
    """DeepSeek-V2-Lite's held experts at decode: the stacked kernel
    vmapped over 8 experts, 256 slot rows each, up 2048 -> 2 x 1408 and
    down 1408 -> 2048 (K padded to 1536), named for the experts."""
    import re

    kd = 1408 + (-1408) % 256

    def experts(a, b):
        return jax.vmap(lambda x, w: l2r_gemm_pallas_stacked_planes(
            x, w, 8, 2, name="experts"))(a, b)
    for k, n in ((2048, 2 * 1408), (kd, 2048)):
        text = _compile(experts, one_chip, ((8, 256, D * k), jnp.int8),
                        ((8, D * k, n), jnp.int8))
        assert re.search(
            r"%l2r_gemm_pallas_stacked_planes_experts(\.\d+)? = ", text)


def test_mla_decode_step_256_slots(one_chip, monkeypatch):
    """One DeepSeek-V2-Lite MLA layer's decode step at 256 slots over a
    1024-position float32 latent cache: the L2R projections on the
    compiled kernels, the absorbed attention around them."""
    import dataclasses

    import repro.kernels.l2r_gemm.ops as ops
    from repro.configs import get_config
    from repro.core.quant import QuantConfig
    from repro.models.common import abstract
    from repro.models.mla import init_mla_cache, mla_apply, mla_build

    # the dispatcher would pick the CPU's jnp path: steer it to the chip's
    monkeypatch.setattr(ops, "_resolve", lambda backend: "pallas-tpu")
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"),
                              l2r=QuantConfig())
    b, length = 256, 1024

    def step(p, x, cache, pos):
        return mla_apply(cfg, p, x, mode="decode", rope_positions=pos,
                         positions=pos, cache=cache)

    def sds(t):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), t)
    params = sds(abstract(mla_build(cfg)))
    cache = sds(jax.eval_shape(lambda: init_mla_cache(
        b, length, cfg.kv_lora_rank, cfg.qk_rope_dim, jnp.float32)))
    x = jax.ShapeDtypeStruct((b, 1, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    pos = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one_chip)
    text = jax.jit(step).lower(params, x, cache, pos).compile().as_text()
    assert "tpu_custom_call" in text
