"""Streaming progressive-precision subsystem.

The load-bearing invariant: every per-level prefix the streaming emitter
produces is bit-identical to the level-stacked schedule truncated at that
depth — so early-exit consumers (VGG classify heads, progressive decode)
are reading the SAME arithmetic the production GEMM would finish, and
their committed decisions can never differ from the full result.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # optional hypothesis

from repro.core.l2r_gemm import l2r_matmul_int_stacked
from repro.core.progressive import (ProgressiveResult, earliest_decision_level,
                                    l2r_matmul_int_streaming, level_bounds,
                                    progressive_matmul, streaming_argmax,
                                    streaming_matmul_scan)
from repro.core.quant import QuantConfig, quantize, quantize_weights
from repro.kernels.l2r_gemm import (int_gemm_ref, l2r_gemm,
                                    l2r_gemm_progressive)

SWEEP = [(8, 1), (8, 2), (8, 4), (6, 2), (4, 2), (16, 4)]
RAGGED = [(13, 37, 11), (1, 64, 16), (45, 67, 31)]


def _rand_ints(rng, n_bits, shape):
    lo, hi = -(1 << (n_bits - 1)), 1 << (n_bits - 1)
    dt = np.int8 if n_bits <= 8 else np.int16
    return jnp.asarray(rng.integers(lo, hi, size=shape, dtype=dt))


# ------------------------------------------------ emitter bit-exactness
@pytest.mark.parametrize("n_bits,log2_radix", SWEEP)
@pytest.mark.parametrize("m,k,n", RAGGED)
def test_streaming_prefixes_bit_identical_to_stacked(n_bits, log2_radix,
                                                     m, k, n):
    """The tentpole invariant: level l of the stream == the stacked
    schedule truncated at levels=l+1, for every radix/bit-width/shape."""
    rng = np.random.default_rng(n_bits * 100 + log2_radix * 10 + m)
    a = _rand_ints(rng, n_bits, (m, k))
    b = _rand_ints(rng, n_bits, (k, n))
    d = n_bits // log2_radix
    res = progressive_matmul(a, b, n_bits, log2_radix)
    assert res.partial.shape == (2 * d - 1, m, n)
    for t in range(2 * d - 1):
        np.testing.assert_array_equal(
            np.asarray(res.partial[t]),
            np.asarray(l2r_matmul_int_stacked(a, b, n_bits, log2_radix,
                                              t + 1)),
            err_msg=f"level {t + 1}")


@pytest.mark.parametrize("n_bits,log2_radix", SWEEP)
def test_streaming_levels_truncation_matches_stacked(n_bits, log2_radix):
    rng = np.random.default_rng(n_bits + log2_radix)
    a = _rand_ints(rng, n_bits, (9, 21))
    b = _rand_ints(rng, n_bits, (21, 7))
    d = n_bits // log2_radix
    for lv in [0, 1, d, 2 * d - 1, None]:
        np.testing.assert_array_equal(
            np.asarray(l2r_matmul_int_streaming(a, b, n_bits, log2_radix,
                                                lv)),
            np.asarray(l2r_matmul_int_stacked(a, b, n_bits, log2_radix, lv)),
            err_msg=f"levels={lv}")


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
def test_streaming_schedule_dispatcher(backend):
    """schedule="streaming" through the backend dispatcher: exact result
    and truncated prefixes, both backends."""
    rng = np.random.default_rng(3)
    a = _rand_ints(rng, 8, (70, 90))
    b = _rand_ints(rng, 8, (90, 40))
    out = np.asarray(l2r_gemm(a, b, schedule="streaming", backend=backend))
    np.testing.assert_array_equal(out, np.asarray(int_gemm_ref(a, b)))
    out3 = np.asarray(l2r_gemm(a, b, levels=3, schedule="streaming",
                               backend=backend))
    np.testing.assert_array_equal(
        out3, np.asarray(l2r_matmul_int_stacked(a, b, 8, 2, 3)))


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
def test_progressive_dispatch_snapshot_stack(backend):
    """l2r_gemm_progressive: per-level stack == stacked prefixes on every
    backend (the Pallas path exercises the per-level output walk)."""
    rng = np.random.default_rng(5)
    a = _rand_ints(rng, 8, (70, 90))
    b = _rand_ints(rng, 8, (90, 40))
    res = l2r_gemm_progressive(a, b, backend=backend)
    assert res.partial.shape == (7, 70, 40)
    for t in range(7):
        np.testing.assert_array_equal(
            np.asarray(res.partial[t]),
            np.asarray(l2r_matmul_int_stacked(a, b, 8, 2, t + 1)),
            err_msg=f"{backend} level {t + 1}")


def test_streaming_fold_sees_every_prefix():
    """The fold consumer receives the exact per-level prefixes, in MSDF
    order, while the scan carries only the accumulator."""
    rng = np.random.default_rng(7)
    a = _rand_ints(rng, 8, (5, 12))
    b = _rand_ints(rng, 8, (12, 4))
    ref = progressive_matmul(a, b)

    def fold(carry, partial, idx):
        count, max_diff = carry
        diff = jnp.abs(partial - ref.partial[idx]).max()
        return count + 1, jnp.maximum(max_diff, diff)

    final, (count, max_diff), stack = streaming_matmul_scan(
        a, b, fold, (jnp.int32(0), jnp.int32(0)))
    assert stack is None  # emit=False: no (L, M, N) materialization
    assert int(count) == 7
    assert int(max_diff) == 0
    np.testing.assert_array_equal(np.asarray(final),
                                  np.asarray(ref.partial[-1]))


# ------------------------------------------------------ decision soundness
@given(st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_early_exit_never_differs_from_full_argmax(seed):
    """Rows that exit early always pick the argmax of the full stream."""
    rng = np.random.default_rng(seed)
    a = _rand_ints(rng, 8, (6, 24))
    b = _rand_ints(rng, 8, (24, 12))
    res = progressive_matmul(a, b)
    lv = np.asarray(earliest_decision_level(res))
    full_arg = np.asarray(res.partial[-1]).argmax(-1)
    for row in range(a.shape[0]):
        chosen = np.asarray(res.partial[lv[row], row]).argmax(-1)
        assert chosen == full_arg[row], (row, lv[row])


@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_streaming_argmax_commits_match_full(seed):
    """The fold-based committer (the serving primitive): every committed
    index equals the argmax of the fully dequantized logits."""
    rng = np.random.default_rng(seed)
    cfg = QuantConfig()
    x = jnp.asarray(rng.standard_normal((8, 48)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((48, 10)) * 0.3).astype(np.float32))
    xq, xs = quantize(x, cfg, axis=0)
    w_q = quantize_weights(w, cfg)
    logits, tok, lv = streaming_argmax(xq, w_q.q, xs, w_q.scale)
    np.testing.assert_array_equal(np.asarray(tok),
                                  np.asarray(logits).argmax(-1))
    assert (np.asarray(lv) <= 6).all()


def test_bound_i32_exactness_guard():
    """Levels whose tail bound exceeds the int32 decision range are
    UNDECIDABLE (never compared in a lossy dtype), not silently clipped
    into unsound early exits."""
    # K large enough that the early-level bounds blow past int32
    bounds = level_bounds(d=4, log2_radix=2, k=1 << 20)
    exact = bounds.exact
    clip = (2**31 - 1) // 2
    dec = np.asarray(bounds.decidable)
    for t, b in enumerate(exact):
        assert dec[t] == (b <= clip)
        if not dec[t]:
            assert int(np.asarray(bounds.i32)[t]) == clip
        else:
            assert int(np.asarray(bounds.i32)[t]) == b
        # the f32 report is always an upper bound of the exact value
        assert float(np.asarray(bounds.f32)[t]) >= b
    assert (~dec).any() and dec.any()
    # a synthetic result whose margin beats ANY in-range bound must still
    # not fire at undecidable levels
    L = len(exact)
    partial = jnp.zeros((L, 1, 2), jnp.int32).at[:, 0, 0].set(2**31 - 1)
    res = ProgressiveResult(partial=partial, tail_bound=bounds.f32,
                            bound_i32=bounds.i32, decidable=bounds.decidable)
    lv = int(np.asarray(earliest_decision_level(res))[0])
    first_decidable = int(np.argmax(dec))
    assert lv == first_decidable  # not 0, despite the level-0 margin


def test_levels_zero_empty_prefix():
    rng = np.random.default_rng(1)
    a = _rand_ints(rng, 8, (4, 8))
    b = _rand_ints(rng, 8, (8, 3))
    np.testing.assert_array_equal(
        np.asarray(l2r_matmul_int_streaming(a, b, levels=0)), 0)
    for backend in ("jnp", "pallas-interpret"):
        np.testing.assert_array_equal(
            np.asarray(l2r_gemm(a, b, levels=0, schedule="streaming",
                                backend=backend)), 0)


# ------------------------------------------------------------ end to end
def test_vgg16_classify_progressive_matches_apply():
    """The conv->head early-exit path: committed classes and returned
    logits are bit-identical to the one-shot vgg16_apply L2R forward."""
    from repro.models.cnn import (vgg16_apply, vgg16_build,
                                  vgg16_classify_progressive,
                                  vgg16_quantize_weights)
    from repro.models.common import materialize

    cfg = QuantConfig()
    params = materialize(vgg16_build(n_classes=10), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.standard_normal((2, 32, 32, 3)).astype(np.float32))
    cache = vgg16_quantize_weights(params, cfg)
    ref = np.asarray(vgg16_apply(params, img, l2r=cfg, weights_q=cache))
    pred, lv, logits = vgg16_classify_progressive(params, img, cfg,
                                                  weights_q=cache)
    np.testing.assert_array_equal(np.asarray(logits), ref)
    np.testing.assert_array_equal(np.asarray(pred), ref.argmax(-1))
    assert (np.asarray(lv) >= 0).all() and (np.asarray(lv) <= 6).all()


@pytest.fixture(scope="module")
def l2r_lm():
    from repro.configs import get_smoke
    from repro.models.common import materialize
    from repro.models.transformer import lm_build

    cfg = dataclasses.replace(get_smoke("smollm-135m"), l2r=QuantConfig())
    params = materialize(lm_build(cfg), jax.random.PRNGKey(0))
    return cfg, params


def test_progressive_decode_tokens_identical_to_greedy(l2r_lm):
    """Progressive decode commits the SAME tokens greedy_generate emits —
    the early exit only changes how many levels were needed, never the
    output."""
    from repro.serve.engine import (greedy_generate, make_decode_step,
                                    make_prefill_step)

    cfg, params = l2r_lm
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    ref = np.asarray(greedy_generate(cfg, params, prompt, steps=6,
                                     max_len=32))
    prefill = make_prefill_step(cfg, 32, jnp.float32)
    decode = make_decode_step(cfg, progressive=True)
    state, logits = prefill(params, {"tokens": prompt})
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out, levels = [np.asarray(tok)], []
    for _ in range(5):
        state, tok, _, lv = decode(params, state, tok)
        out.append(np.asarray(tok))
        levels.append(np.asarray(lv))
    np.testing.assert_array_equal(np.concatenate(out, axis=1), ref)
    levels = np.concatenate(levels, axis=1)
    assert levels.min() >= 0 and levels.max() <= 6


def test_progressive_decode_respects_l2r_levels(l2r_lm):
    """cfg.l2r_levels truncates the streamed head exactly like the
    one-shot head: logits AND tokens bit-identical between the
    progressive and non-progressive decode steps."""
    from repro.serve.engine import make_decode_step, make_prefill_step

    cfg5 = dataclasses.replace(l2r_lm[0], l2r_levels=5)
    params = l2r_lm[1]
    rng = np.random.default_rng(7)
    prompt = jnp.asarray(rng.integers(0, cfg5.vocab, (2, 8)), jnp.int32)
    prefill = make_prefill_step(cfg5, 16, jnp.float32)
    state, logits = prefill(params, {"tokens": prompt})
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    st_r, tok_r, logits_r = make_decode_step(cfg5)(params, state, tok)
    _, tok_p, logits_p, lv = make_decode_step(cfg5, progressive=True)(
        params, state, tok)
    np.testing.assert_array_equal(np.asarray(logits_p),
                                  np.asarray(logits_r))
    np.testing.assert_array_equal(np.asarray(tok_p), np.asarray(tok_r))
    assert np.asarray(lv).max() <= 4  # truncated stream: 5 levels max


def test_prepare_params_head_cache(l2r_lm):
    """prepare_params caches the int8 LM head; cached and fresh head
    quantization are bit-identical on both decode paths."""
    from repro.serve.engine import prepare_params, progressive_logits_from_hidden
    from repro.models.transformer import logits_from_hidden

    cfg, params = l2r_lm
    pp = prepare_params(cfg, params)
    assert "head_q" in pp
    rng = np.random.default_rng(9)
    hidden = jnp.asarray(rng.standard_normal((2, 1, cfg.d_model))
                         .astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(logits_from_hidden(cfg, pp, hidden)),
        np.asarray(logits_from_hidden(cfg, params, hidden)))
    lg_c, tok_c, lv_c = progressive_logits_from_hidden(cfg, pp, hidden)
    lg_f, tok_f, lv_f = progressive_logits_from_hidden(cfg, params, hidden)
    np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_f))
    np.testing.assert_array_equal(np.asarray(tok_c), np.asarray(tok_f))
    np.testing.assert_array_equal(np.asarray(lv_c), np.asarray(lv_f))


def test_batcher_progressive_stats(l2r_lm):
    """The continuous batcher in progressive mode: identical tokens to the
    non-progressive engine, per-request exit levels recorded, and the
    saved-levels histogram surfaced in stats()."""
    from repro.serve.batching import ContinuousBatcher, Request

    cfg, params = l2r_lm
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, (6,)).astype(np.int32)
               for _ in range(3)]

    def run(progressive):
        eng = ContinuousBatcher(cfg, params, n_slots=2, max_len=32,
                                progressive=progressive)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run(max_steps=100)
        return eng, reqs

    eng_p, reqs_p = run(True)
    eng_r, reqs_r = run(False)
    for rp, rr in zip(reqs_p, reqs_r):
        assert rp.output == rr.output, (rp.uid, rp.output, rr.output)
        # one exit level per decoded token (the prefill token has none)
        assert len(rp.exit_levels) == len(rp.output) - 1
    stats = eng_p.stats()
    assert stats["progressive"] and stats["n_levels"] == 7
    assert stats["tokens"] == sum(len(r.exit_levels) for r in reqs_p)
    assert sum(stats["exit_level_hist"]) == stats["tokens"]
    assert 0.0 <= stats["mean_exit_level"] <= 6.0
    assert not eng_r.stats().get("exit_level_hist")
