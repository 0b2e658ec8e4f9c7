"""Serving engine: prefill+decode consistency, greedy generation,
progressive-precision serving."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.quant import QuantConfig
from repro.models.common import materialize
from repro.models.transformer import init_lm_state, lm_build, lm_forward
from repro.serve.engine import greedy_generate, make_decode_step, make_prefill_step


@pytest.mark.parametrize("arch", ["smollm-135m", "gemma3-27b", "mamba2-130m",
                                  "recurrentgemma-2b"])
def test_prefill_decode_matches_train_forward(arch):
    cfg = get_smoke(arch)
    params = materialize(lm_build(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)
    h, _, _ = lm_forward(cfg, params, tokens=toks, mode="train")
    st = init_lm_state(cfg, 2, max_len=16, dtype=jnp.float32)
    _, st, _ = lm_forward(cfg, params, tokens=toks[:, :11], mode="prefill", state=st)
    h_dec, _, _ = lm_forward(cfg, params, tokens=toks[:, 11:12], mode="decode", state=st)
    np.testing.assert_allclose(np.asarray(h[:, 11:12], np.float32),
                               np.asarray(h_dec, np.float32), atol=5e-2)


def test_greedy_generate_deterministic():
    cfg = get_smoke("smollm-135m")
    params = materialize(lm_build(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    out1 = greedy_generate(cfg, params, prompt, steps=5)
    out2 = greedy_generate(cfg, params, prompt, steps=5)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (2, 5)


def test_decode_step_factory_argmax_consistency():
    cfg = get_smoke("smollm-135m")
    params = materialize(lm_build(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    prefill = make_prefill_step(cfg, max_len=16, cache_dtype=jnp.float32)
    decode = make_decode_step(cfg)
    state, logits = prefill(params, {"tokens": prompt})
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    state, tok2, logits2 = decode(params, state, tok)
    assert tok2.shape == (2, 1)
    np.testing.assert_array_equal(
        np.asarray(tok2), np.asarray(jnp.argmax(logits2, -1)))


def test_progressive_precision_serving_is_exact_at_full_levels():
    """The paper's L2R mode with all MSDF levels == plain int8 serving."""
    cfg = get_smoke("smollm-135m")
    cfg_l2r = dataclasses.replace(cfg, l2r=QuantConfig(), l2r_levels=None)
    params = materialize(lm_build(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)
    h_f, _, _ = lm_forward(cfg, params, tokens=toks, mode="train")
    h_q, _, _ = lm_forward(cfg_l2r, params, tokens=toks, mode="train")
    # quantized path close to float path (int8 noise through 6 layers)
    rel = (np.abs(np.asarray(h_f, np.float32) - np.asarray(h_q, np.float32)).max()
           / (np.abs(np.asarray(h_f, np.float32)).max() + 1e-9))
    assert rel < 0.35, rel
    # truncated MSDF stream degrades gracefully (still finite)
    cfg_l3 = dataclasses.replace(cfg, l2r=QuantConfig(), l2r_levels=4)
    h_p, _, _ = lm_forward(cfg_l3, params, tokens=toks, mode="train")
    assert np.isfinite(np.asarray(h_p, np.float32)).all()


def test_decode_step_scopes_in_lowered_text():
    """The decode step's attention, KV-cache read, update and write-back,
    MLP and head carry named scopes in the compiled program's op_name
    metadata."""
    import re

    cfg = dataclasses.replace(get_smoke("smollm-135m"), l2r=QuantConfig())
    params = materialize(lm_build(cfg), jax.random.PRNGKey(0))
    st = init_lm_state(cfg, 2, max_len=16, dtype=jnp.float32)
    tok = jnp.zeros((2, 1), jnp.int32)
    text = make_decode_step(cfg).lower(params, st, tok).compile().as_text()
    scoped = set()
    for name in re.findall(r'op_name="([^"]*)"', text):
        parts = name.split("/")
        scoped.update((p, parts[-1]) for p in parts[:-1])
    for scope in ("attention", "mlp", "head"):
        assert (scope, "dot_general") in scoped, scope
    assert ("kv_cache_read", "dynamic_slice") in scoped
    assert ("kv_cache_update", "scatter") in scoped
    assert ("kv_cache_writeback", "dynamic_update_slice") in scoped
