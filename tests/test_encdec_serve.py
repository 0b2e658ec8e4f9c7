"""Whisper (enc-dec) serving path: prefill + decode == train forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models.common import materialize
from repro.models.encdec import (encdec_build, encdec_forward,
                                 init_encdec_state)


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke("whisper-base")
    params = materialize(encdec_build(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    frames = jnp.asarray(rng.standard_normal((2, cfg.encoder_seq, cfg.d_model)),
                         jnp.float32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)
    return cfg, params, frames, toks


def test_decode_matches_train(model):
    cfg, params, frames, toks = model
    h, _, _ = encdec_forward(cfg, params, tokens=toks, frames=frames,
                             mode="train")
    st = init_encdec_state(cfg, 2, 16, jnp.float32)
    _, st, _ = encdec_forward(cfg, params, tokens=toks[:, :11], frames=frames,
                              mode="prefill", state=st)
    h_dec, st, _ = encdec_forward(cfg, params, tokens=toks[:, 11:12],
                                  mode="decode", state=st)
    np.testing.assert_allclose(np.asarray(h[:, 11:12]), np.asarray(h_dec),
                               atol=1e-4)


def test_multi_step_decode_consistent(model):
    """Two successive decode steps == the train forward at those positions."""
    cfg, params, frames, toks = model
    h, _, _ = encdec_forward(cfg, params, tokens=toks, frames=frames,
                             mode="train")
    st = init_encdec_state(cfg, 2, 16, jnp.float32)
    _, st, _ = encdec_forward(cfg, params, tokens=toks[:, :10], frames=frames,
                              mode="prefill", state=st)
    for pos in (10, 11):
        h_dec, st, _ = encdec_forward(cfg, params, tokens=toks[:, pos:pos + 1],
                                      mode="decode", state=st)
        np.testing.assert_allclose(np.asarray(h[:, pos:pos + 1]),
                                   np.asarray(h_dec), atol=1e-4)


def test_cross_attention_cache_reused(model):
    """Decode must not need encoder frames (cross-KV cached at prefill)."""
    cfg, params, frames, toks = model
    st = init_encdec_state(cfg, 2, 16, jnp.float32)
    _, st, _ = encdec_forward(cfg, params, tokens=toks[:, :11], frames=frames,
                              mode="prefill", state=st)
    # no frames / enc_out passed:
    h_dec, _, _ = encdec_forward(cfg, params, tokens=toks[:, 11:12],
                                 mode="decode", state=st)
    assert np.isfinite(np.asarray(h_dec)).all()


def test_decode_bf16_compute_with_f32_cache(model):
    """At the published bf16 compute dtype, decoding from the f32 serving
    cache keeps the residual stream in bf16 (the layer scan's carry
    dtype) and follows the train forward."""
    import dataclasses

    cfg, params, frames, toks = model
    cfg = dataclasses.replace(cfg, compute_dtype="bfloat16")
    h, _, _ = encdec_forward(cfg, params, tokens=toks, frames=frames,
                             mode="train")
    st = init_encdec_state(cfg, 2, 16, jnp.float32)
    _, st, _ = encdec_forward(cfg, params, tokens=toks[:, :11], frames=frames,
                              mode="prefill", state=st)
    h_dec, _, _ = encdec_forward(cfg, params, tokens=toks[:, 11:12],
                                 mode="decode", state=st)
    assert h_dec.dtype == jnp.bfloat16
    ref = np.asarray(h[:, 11:12], np.float32)
    err = np.abs(np.asarray(h_dec, np.float32) - ref).max()
    assert err <= 0.05 * np.abs(ref).max(), err
