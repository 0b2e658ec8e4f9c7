"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle.

The kernel is int32-exact, so assertions are bit-equality (the strongest
possible allclose).  interpret=True executes the kernel body on CPU.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.l2r_gemm import l2r_matmul_int, l2r_matmul_int_stacked
from repro.kernels.l2r_gemm import (int_gemm_ref, l2r_gemm,
                                    l2r_gemm_pallas_stacked, l2r_gemm_ref,
                                    l2r_gemm_ref_stacked, l2r_matmul_f)

SHAPES = [
    (128, 256, 128),   # exactly one block
    (256, 512, 256),   # multi-block every axis
    (64, 64, 64),      # smaller than a block (padding path)
    (130, 300, 77),    # ragged
    (1, 256, 128),     # single row
    (128, 32, 512),    # shallow K
]


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
@pytest.mark.parametrize("m,k,n", SHAPES)
def test_kernel_exact_int8(m, k, n, backend):
    rng = np.random.default_rng(m * 1000 + k + n)
    a = rng.integers(-128, 128, size=(m, k), dtype=np.int8)
    b = rng.integers(-128, 128, size=(k, n), dtype=np.int8)
    out = l2r_gemm(jnp.asarray(a), jnp.asarray(b), backend=backend)
    ref = int_gemm_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
@pytest.mark.parametrize("log2_radix", [1, 2, 4])
def test_kernel_radix_sweep(log2_radix, backend):
    rng = np.random.default_rng(42)
    a = rng.integers(-128, 128, size=(128, 256), dtype=np.int8)
    b = rng.integers(-128, 128, size=(256, 128), dtype=np.int8)
    out = l2r_gemm(jnp.asarray(a), jnp.asarray(b), log2_radix=log2_radix,
                   backend=backend)
    ref = int_gemm_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
@pytest.mark.parametrize("levels", list(range(1, 8)))
def test_kernel_progressive_levels_match_oracle(levels, backend):
    rng = np.random.default_rng(levels)
    a = rng.integers(-128, 128, size=(128, 256), dtype=np.int8)
    b = rng.integers(-128, 128, size=(256, 128), dtype=np.int8)
    out = l2r_gemm(jnp.asarray(a), jnp.asarray(b), levels=levels,
                   backend=backend)
    ref = l2r_gemm_ref(jnp.asarray(a), jnp.asarray(b), levels=levels)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_kernel_progressive_error_decreases():
    rng = np.random.default_rng(5)
    a = rng.integers(-128, 128, size=(128, 256), dtype=np.int8)
    b = rng.integers(-128, 128, size=(256, 128), dtype=np.int8)
    exact = np.asarray(int_gemm_ref(jnp.asarray(a), jnp.asarray(b)), np.int64)
    errs = []
    for lv in range(1, 8):
        out = np.asarray(l2r_gemm(jnp.asarray(a), jnp.asarray(b), levels=lv), np.int64)
        errs.append(np.abs(out - exact).max())
    assert errs[-1] == 0
    assert all(e1 >= e2 for e1, e2 in zip(errs, errs[1:]))


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
@pytest.mark.parametrize("n_bits,dtype", [(8, np.int8), (6, np.int8), (4, np.int8)])
def test_kernel_bitwidth_sweep(n_bits, dtype, backend):
    rng = np.random.default_rng(n_bits)
    lo, hi = -(1 << (n_bits - 1)), 1 << (n_bits - 1)
    a = rng.integers(lo, hi, size=(128, 256), dtype=dtype)
    b = rng.integers(lo, hi, size=(256, 128), dtype=dtype)
    out = l2r_gemm(jnp.asarray(a), jnp.asarray(b), n_bits=n_bits, log2_radix=2,
                   backend=backend)
    ref = int_gemm_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ------------------------------------------------- level-stacked schedule
def _rand_ints(rng, n_bits, shape):
    lo, hi = -(1 << (n_bits - 1)), 1 << (n_bits - 1)
    dt = np.int8 if n_bits <= 8 else np.int16
    return jnp.asarray(rng.integers(lo, hi, size=shape, dtype=dt))


@pytest.mark.parametrize("n_bits,log2_radix", [
    (8, 1), (8, 2), (8, 4), (6, 2), (4, 2), (4, 4), (16, 4),
])
def test_stacked_bit_identical_all_levels(n_bits, log2_radix):
    """The tentpole invariant: the level-stacked schedule is bit-identical
    to l2r_matmul_int for EVERY truncation depth, every radix, and
    non-block-multiple shapes."""
    rng = np.random.default_rng(n_bits * 10 + log2_radix)
    a = _rand_ints(rng, n_bits, (45, 67))   # ragged on purpose
    b = _rand_ints(rng, n_bits, (67, 31))
    d = n_bits // log2_radix
    for lv in [None] + list(range(1, 2 * d)):
        ref = np.asarray(l2r_matmul_int(a, b, n_bits, log2_radix, lv))
        out = np.asarray(l2r_matmul_int_stacked(a, b, n_bits, log2_radix, lv))
        np.testing.assert_array_equal(out, ref, err_msg=f"levels={lv}")


def test_stacked_levels_zero_matches_pair_loop():
    """Degenerate empty MSDF prefix: both schedules return zeros."""
    rng = np.random.default_rng(12)
    a = _rand_ints(rng, 8, (8, 16))
    b = _rand_ints(rng, 8, (16, 4))
    np.testing.assert_array_equal(
        np.asarray(l2r_matmul_int_stacked(a, b, levels=0)),
        np.asarray(l2r_matmul_int(a, b, levels=0)))
    np.testing.assert_array_equal(
        np.asarray(l2r_matmul_int_stacked(a, b, levels=0)), 0)


def test_core_l2r_dense_weight_cache_bit_identical():
    """core l2r_dense/l2r_matmul w_q threading (the non-dispatcher entry
    point used by e.g. MoE per-expert matmuls): cached == fresh, bitwise."""
    from repro.core.l2r_gemm import l2r_dense
    from repro.core.quant import QuantConfig, quantize_weights

    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.standard_normal((3, 5, 32)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((32, 10)) * 0.2).astype(np.float32))
    cfg = QuantConfig()
    w_q = quantize_weights(w, cfg)
    out_cached = np.asarray(l2r_dense(x, None, cfg, w_q=w_q))
    out_fresh = np.asarray(l2r_dense(x, w, cfg))
    np.testing.assert_array_equal(out_cached, out_fresh)


def test_stacked_ref_matches_pair_ref():
    rng = np.random.default_rng(11)
    a = _rand_ints(rng, 8, (37, 100))
    b = _rand_ints(rng, 8, (100, 53))
    for lv in (None, 2, 5):
        np.testing.assert_array_equal(
            np.asarray(l2r_gemm_ref_stacked(a, b, levels=lv)),
            np.asarray(l2r_gemm_ref(a, b, levels=lv)))


@pytest.mark.parametrize("levels", [None, 1, 4])
def test_stacked_pallas_kernel_bit_identical(levels):
    """Pallas stacked kernel (interpret) vs the core pair loop."""
    rng = np.random.default_rng(0 if levels is None else levels)
    a = _rand_ints(rng, 8, (128, 256))
    b = _rand_ints(rng, 8, (256, 128))
    out = np.asarray(l2r_gemm_pallas_stacked(a, b, levels=levels,
                                             interpret=True))
    ref = np.asarray(l2r_matmul_int(a, b, 8, 2, levels))
    np.testing.assert_array_equal(out, ref)


def test_stacked_pallas_multiblock_k():
    """K spanning multiple bk blocks exercises the scalar-prefetch walk."""
    rng = np.random.default_rng(7)
    a = _rand_ints(rng, 8, (128, 512))
    b = _rand_ints(rng, 8, (512, 128))
    out = np.asarray(l2r_gemm_pallas_stacked(a, b, bk=256, interpret=True))
    np.testing.assert_array_equal(out, np.asarray(int_gemm_ref(a, b)))


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
@pytest.mark.parametrize("schedule", ["stacked", "pairs"])
def test_dispatcher_backends_bit_identical(backend, schedule):
    """One ragged shape through every (backend, schedule) combination."""
    rng = np.random.default_rng(3)
    a = _rand_ints(rng, 8, (70, 90))
    b = _rand_ints(rng, 8, (90, 40))
    out = np.asarray(l2r_gemm(a, b, schedule=schedule, backend=backend))
    np.testing.assert_array_equal(out, np.asarray(int_gemm_ref(a, b)))


def test_dispatcher_env_override(monkeypatch):
    from repro.kernels.l2r_gemm import BACKEND_ENV_VAR, resolve_backend

    assert resolve_backend("jnp") == "jnp"
    monkeypatch.setenv(BACKEND_ENV_VAR, "pallas-interpret")
    assert resolve_backend() == "pallas-interpret"
    assert resolve_backend("jnp") == "jnp"  # explicit arg wins
    monkeypatch.setenv(BACKEND_ENV_VAR, "bogus")
    with pytest.raises(ValueError):
        resolve_backend()
    monkeypatch.delenv(BACKEND_ENV_VAR)
    # no TPU in this container -> platform default is the jnp schedule
    assert resolve_backend() == "jnp"


def test_float_wrapper_close_to_matmul():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((64, 256)).astype(np.float32)
    w = rng.standard_normal((256, 96)).astype(np.float32)
    out = np.asarray(l2r_matmul_f(jnp.asarray(x), jnp.asarray(w)))
    ref = x @ w
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.02, rel  # int8 W8A8 quantization error


def test_resolve_backend_pallas_tpu_off_platform(monkeypatch):
    """An explicit pallas-tpu on a non-TPU host fails AT RESOLVE TIME
    with an actionable message (previously: an opaque Mosaic lowering
    error deep inside the first pallas_call)."""
    from repro.kernels.l2r_gemm import BACKEND_ENV_VAR, resolve_backend

    # this container has no TPU — both the explicit arg and the env var
    # must be rejected before any kernel work happens
    with pytest.raises(RuntimeError, match="pallas-interpret"):
        resolve_backend("pallas-tpu")
    monkeypatch.setenv(BACKEND_ENV_VAR, "pallas-tpu")
    with pytest.raises(RuntimeError, match="pallas-interpret"):
        resolve_backend()
    with pytest.raises(RuntimeError, match="TPU"):
        l2r_gemm(jnp.zeros((8, 8), jnp.int8), jnp.zeros((8, 8), jnp.int8),
                 backend="pallas-tpu")


def test_resolve_backend_rejects_wide_digits_on_tpu(monkeypatch):
    """The compiled kernels take int8 digit planes: a >8-bit config
    (int16 planes, refused by Mosaic) fails on pallas-tpu at resolve
    time, naming the config; <=8-bit configs and other backends pass."""
    import jax

    from repro.kernels.l2r_gemm import resolve_backend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_backend(None, 8) == "pallas-tpu"
    with pytest.raises(ValueError, match="n_bits=12"):
        resolve_backend(None, 12)
    with pytest.raises(ValueError, match="n_bits=16"):
        resolve_backend("pallas-tpu", 16)
    assert resolve_backend("jnp", 12) == "jnp"


def test_pad_to_rank_mismatch_raises():
    """pad_to used to zip-truncate when len(mults) != ndim, silently
    leaving dims unpadded — now a ValueError both ways."""
    from repro.kernels.l2r_gemm import pad_to

    x = jnp.zeros((5, 7))
    out = np.asarray(pad_to(x, (4, 4)))
    assert out.shape == (8, 8)
    with pytest.raises(ValueError, match="rank"):
        pad_to(x, (4,))          # too few: trailing dim would go unpadded
    with pytest.raises(ValueError, match="rank"):
        pad_to(x, (4, 4, 4))     # too many: silent zip truncation before
    # rank-3 works when every dim is named (1 = keep)
    out = np.asarray(pad_to(jnp.zeros((2, 5, 7)), (1, 4, 4)))
    assert out.shape == (2, 8, 8)
