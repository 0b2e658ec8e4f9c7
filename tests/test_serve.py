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
    """The decode step's attention, KV-cache update and read, MLP and
    head carry named scopes in the compiled program's op_name metadata.
    The update writes the new position into the stacked cache in place
    (its dynamic-update-slices output the whole stack), the read slices
    the layer's slab where it lies, and no slab is written back."""
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
    assert ("kv_cache_update", "dynamic_update_slice") in scoped
    assert "kv_cache_writeback" not in text
    # (layers, slots, positions, ...): k, v and positions of the stack
    stack = ",".join(map(str, st.stack[0].positions.shape))
    writes = re.findall(r"\[([\d,]*)\]\S* dynamic-update-slice\(.*"
                        r'op_name="[^"]*kv_cache_update/', text)
    assert writes and all(w.startswith(stack) for w in writes), writes


def _per_layer_forward(cfg, params, state, tokens, mode):
    """``lm_forward(mode=...)`` with every layer's cache unstacked and
    written by ``update_kv_cache`` / ``update_mla_cache`` without a layer
    index: the stacked layers run as a scan whose inputs and outputs
    (not carry) are the per-layer caches.  A scan, not an unrolled loop,
    so that XLA compiles each layer's arithmetic as it does in
    ``lm_forward`` (an unrolled loop differs in the last bits).  Returns
    (hidden, state)."""
    from repro.models.transformer import (LMState, _add_counts, layer_apply,
                                          layer_norm_fn)

    prefix_k, repeats, unit, suffix_k = cfg.block_grouping()
    x = params["embed"].astype(jnp.dtype(cfg.compute_dtype))[tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    positions = state.pos[:, None] + jnp.arange(tokens.shape[1])[None]

    def run(x, cnt, lp, kk, c):
        x, c, _, n = layer_apply(cfg, lp, kk, x, mode=mode,
                                 rope_positions=positions,
                                 positions=positions, cache=c)
        return x, _add_counts(cnt, n), c

    counts, prefix, suffix, stack = None, [], [], None
    for i, kk in enumerate(prefix_k):
        x, counts, c = run(x, counts, params["prefix"][i], kk,
                           state.prefix[i])
        prefix.append(c)
    if repeats:
        def body(carry, layer):
            x, cnt = carry
            lp, caches = layer
            out = []
            for u, kk in enumerate(unit):
                x, cnt, c = run(x, cnt, lp[u], kk, caches[u])
                out.append(c)
            return (x, cnt), out

        cnt0 = (jnp.zeros((3,), jnp.int32)
                if any(k[1] == "moe" for k in unit) else None)
        (x, cnt), stack = jax.lax.scan(body, (x, cnt0),
                                       (params["stack"], state.stack))
        counts = _add_counts(counts, cnt)
    for i, kk in enumerate(suffix_k):
        x, counts, c = run(x, counts, params["suffix"][i], kk,
                           state.suffix[i])
        suffix.append(c)
    x = layer_norm_fn(cfg)(x, params["final_norm"])
    return x, LMState(prefix=prefix, stack=stack, suffix=suffix,
                      pos=state.pos + tokens.shape[1],
                      moe_counts=_add_counts(state.moe_counts, counts))


@pytest.mark.parametrize("arch,n_layers,attn_l2r", [
    ("smollm-135m", None, False),        # full GQA caches
    ("gemma3-27b", 14, False),           # ring caches that wrap (window 16)
    ("smollm-135m", None, True),         # plane-stacked key cache
    ("deepseek-v2-lite", None, False),   # MLA latent caches
    ("recurrentgemma-2b", 8, False),     # RG-LRU states beside a ring cache
], ids=["gqa", "ring", "planes", "mla", "hybrid"])
def test_stacked_caches_in_place_match_per_layer_loop(arch, n_layers,
                                                      attn_l2r):
    """Prefill and decode through the stacked scan, whose decode writes
    and reads each attention cache inside the carry, match a per-layer
    loop over unstacked caches bit for bit: hidden states, logits,
    tokens and every cache leaf.  Every case stacks at least two blocks,
    so a write or read of the wrong block shows."""
    from repro.models.transformer import logits_from_hidden
    from repro.serve.engine import SERVE_COMPILER_OPTIONS

    cfg = get_smoke(arch)
    if n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    assert cfg.block_grouping()[1] >= 2
    if attn_l2r:
        cfg = dataclasses.replace(cfg, attn_l2r=QuantConfig())
    params = materialize(lm_build(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)
    jit = lambda f: jax.jit(f, compiler_options=SERVE_COMPILER_OPTIONS)  # noqa: E731

    def scanned(mode):
        return jit(lambda p, s, t: lm_forward(cfg, p, tokens=t, mode=mode,
                                              state=s)[:2])

    def looped(mode):
        return jit(lambda p, s, t: _per_layer_forward(cfg, p, s, t, mode))

    def same(a, b):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    st = init_lm_state(cfg, 2, max_len=24, dtype=jnp.float32)
    h_s, st_s = scanned("prefill")(params, st, toks)
    h_l, st_l = looped("prefill")(params, st, toks)
    same((h_s, st_s), (h_l, st_l))
    tok_s = tok_l = toks[:, -1:]
    dec_s, dec_l = scanned("decode"), looped("decode")
    for _ in range(6):  # positions 12..17: the window-16 rings wrap
        h_s, st_s = dec_s(params, st_s, tok_s)
        h_l, st_l = dec_l(params, st_l, tok_l)
        lg_s = logits_from_hidden(cfg, params, h_s)
        lg_l = logits_from_hidden(cfg, params, h_l)
        same((h_s, lg_s, st_s), (h_l, lg_l, st_l))
        tok_s = jnp.argmax(lg_s, -1).astype(jnp.int32)
        tok_l = jnp.argmax(lg_l, -1).astype(jnp.int32)
        same(tok_s, tok_l)
