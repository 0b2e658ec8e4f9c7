"""Flash attention Pallas kernel: shape/dtype/mask sweeps vs oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention_pallas

CASES = [
    dict(sq=256, skv=256, h=4, kvh=2, dh=64, causal=True, window=None,
         bq=128, bkv=128),
    dict(sq=256, skv=256, h=4, kvh=1, dh=64, causal=True, window=64,
         bq=64, bkv=64),
    dict(sq=200, skv=200, h=2, kvh=2, dh=32, causal=True, window=None,
         bq=128, bkv=128),  # ragged -> padding path
    dict(sq=128, skv=128, h=8, kvh=4, dh=64, causal=False, window=None,
         bq=64, bkv=64),
    dict(sq=64, skv=64, h=2, kvh=2, dh=128, causal=True, window=16,
         bq=32, bkv=32),
]


@pytest.mark.parametrize("case", CASES)
def test_flash_vs_oracle(case):
    rng = np.random.default_rng(1)
    q = rng.standard_normal((2, case["sq"], case["h"], case["dh"])).astype(np.float32)
    k = rng.standard_normal((2, case["skv"], case["kvh"], case["dh"])).astype(np.float32)
    v = rng.standard_normal((2, case["skv"], case["kvh"], case["dh"])).astype(np.float32)
    out = flash_attention_pallas(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        causal=case["causal"], window=case["window"],
        bq=case["bq"], bkv=case["bkv"], interpret=True)
    ref = attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=case["causal"], window=case["window"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_flash_bf16_inputs():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 128, 4, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, bq=64, bkv=64, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_flash_matches_model_attention():
    """Kernel agrees with the model-stack chunked attention (the XLA
    path it replaces on TPU)."""
    from repro.models.attention import chunked_attention
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((2, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, bq=128, bkv=128,
                                 interpret=True)
    ref = chunked_attention(q, k, v, causal=True, q_chunk=128, kv_chunk=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
