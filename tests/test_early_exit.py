"""Early-exit streaming scan (`lax.while_loop` over MSDF levels).

The load-bearing invariant: the while-loop emitter executes the IDENTICAL
per-level arithmetic of the fixed-length scan (the oracle), so its prefix
after t levels, its committed decisions, and its exit levels are all
bit-identical — the only thing early exit changes is that the level loop
STOPS once every row has decided, turning saved levels into saved
wall-clock inside the fused computation.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # optional hypothesis

from repro.core.l2r_gemm import l2r_matmul_int_stacked
from repro.core.progressive import (l2r_matmul_int_streaming, plane_count,
                                    streaming_argmax, streaming_matmul_scan,
                                    streaming_matmul_while)
from repro.core.quant import QuantConfig, quantize, quantize_weights
from repro.kernels.l2r_gemm import (l2r_conv2d_progressive,
                                    l2r_conv2d_progressive_while, l2r_gemm,
                                    l2r_gemm_pallas_streaming)

SWEEP = [(8, 1), (8, 2), (8, 4), (6, 2), (4, 2), (16, 4)]
RAGGED = [(13, 37, 11), (1, 64, 16), (45, 67, 31)]


def _rand_ints(rng, n_bits, shape):
    lo, hi = -(1 << (n_bits - 1)), 1 << (n_bits - 1)
    dt = np.int8 if n_bits <= 8 else np.int16
    return jnp.asarray(rng.integers(lo, hi, size=shape, dtype=dt))


# ------------------------------------------------- while == scan, bitwise
@pytest.mark.parametrize("n_bits,log2_radix", SWEEP)
@pytest.mark.parametrize("m,k,n", RAGGED)
def test_while_full_run_bit_identical_to_scan(n_bits, log2_radix, m, k, n):
    """No decision state -> the while loop runs every level and its result
    (and every intermediate prefix) is bit-identical to the scan/stacked
    oracle, across radix/bit-width/ragged shapes."""
    rng = np.random.default_rng(n_bits * 1000 + log2_radix * 100 + m)
    a = _rand_ints(rng, n_bits, (m, k))
    b = _rand_ints(rng, n_bits, (k, n))
    d = plane_count(n_bits, log2_radix)
    acc, _, t = streaming_matmul_while(a, b, n_bits=n_bits,
                                       log2_radix=log2_radix)
    assert int(t) == 2 * d - 1
    np.testing.assert_array_equal(
        np.asarray(acc),
        np.asarray(l2r_matmul_int_stacked(a, b, n_bits, log2_radix)))
    np.testing.assert_array_equal(
        np.asarray(l2r_matmul_int_streaming(a, b, n_bits, log2_radix,
                                            early_exit=True)),
        np.asarray(l2r_matmul_int_streaming(a, b, n_bits, log2_radix)))


@pytest.mark.parametrize("n_bits,log2_radix", SWEEP)
def test_while_stops_at_fold_decision(n_bits, log2_radix):
    """A fold that declares itself done after `stop` levels halts the loop
    there, and the accumulator equals the stacked schedule truncated at
    exactly that depth (the while prefix IS the scan prefix)."""
    rng = np.random.default_rng(n_bits + 7 * log2_radix)
    a = _rand_ints(rng, n_bits, (9, 21))
    b = _rand_ints(rng, n_bits, (21, 7))
    d = plane_count(n_bits, log2_radix)
    for stop in [1, d, 2 * d - 1]:
        acc, count, t = streaming_matmul_while(
            a, b, lambda c, p, i: c + 1, jnp.int32(0),
            lambda c: c >= stop, n_bits, log2_radix)
        assert int(t) == stop == int(count)
        np.testing.assert_array_equal(
            np.asarray(acc),
            np.asarray(l2r_matmul_int_stacked(a, b, n_bits, log2_radix,
                                              stop)))


@pytest.mark.parametrize("levels", [0, 3, None])
def test_while_levels_truncation(levels):
    """`levels` truncates the while emitter exactly like the scan."""
    rng = np.random.default_rng(0)
    a = _rand_ints(rng, 8, (6, 18))
    b = _rand_ints(rng, 8, (18, 5))
    acc_w, _, t = streaming_matmul_while(a, b, levels=levels)
    acc_s, _, _ = streaming_matmul_scan(a, b, levels=levels)
    assert int(t) == (7 if levels is None else levels)
    np.testing.assert_array_equal(np.asarray(acc_w), np.asarray(acc_s))


# ------------------------------------------------ argmax consumer parity
@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_streaming_argmax_early_exit_matches_scan(seed):
    """Committed tokens AND per-row exit levels are bit-identical between
    the early-exit while loop and the fixed scan (the oracle)."""
    rng = np.random.default_rng(seed)
    cfg = QuantConfig()
    x = jnp.asarray(rng.standard_normal((8, 48)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((48, 10)) * 0.3).astype(np.float32))
    xq, xs = quantize(x, cfg, axis=0)
    w_q = quantize_weights(w, cfg)
    _, tok_s, lv_s = streaming_argmax(xq, w_q.q, xs, w_q.scale)
    logits_e, tok_e, lv_e = streaming_argmax(xq, w_q.q, xs, w_q.scale,
                                             early_exit=True)
    np.testing.assert_array_equal(np.asarray(tok_e), np.asarray(tok_s))
    np.testing.assert_array_equal(np.asarray(lv_e), np.asarray(lv_s))
    # the early-exit logits are the dequantized prefix at the exit level:
    # their argmax still equals the committed token on every row
    np.testing.assert_array_equal(np.asarray(logits_e).argmax(-1),
                                  np.asarray(tok_e))


def test_all_rows_undecidable_runs_every_level():
    """Identical weight columns make the top-1 margin zero forever: no
    row can ever decide, so the while loop MUST run every level, fall
    back to the full argmax, and agree with the scan path bit for bit."""
    rng = np.random.default_rng(3)
    cfg = QuantConfig()
    x = jnp.asarray(rng.standard_normal((6, 32)).astype(np.float32))
    w_np = rng.standard_normal((32, 8)).astype(np.float32) * 0.3
    w_np[:] = w_np[:, :1]  # every column tied: margin 0 at every level
    w_q = quantize_weights(jnp.asarray(w_np), cfg)
    xq, xs = quantize(x, cfg, axis=0)
    n_levels = 2 * cfg.planes - 1

    # the raw emitter: an argmax-decision fold that never fires
    def fold(c, partial, idx):
        return c

    acc, _, t = streaming_matmul_while(
        xq, w_q.q, fold, None, lambda c: jnp.bool_(False))
    assert int(t) == n_levels  # undecidable -> full stream executed
    np.testing.assert_array_equal(
        np.asarray(acc), np.asarray(l2r_matmul_int_stacked(xq, w_q.q)))

    logits_s, tok_s, lv_s = streaming_argmax(xq, w_q.q, xs, w_q.scale)
    logits_e, tok_e, lv_e = streaming_argmax(xq, w_q.q, xs, w_q.scale,
                                             early_exit=True)
    assert (np.asarray(lv_e) == n_levels - 1).all()
    np.testing.assert_array_equal(np.asarray(lv_e), np.asarray(lv_s))
    np.testing.assert_array_equal(np.asarray(tok_e), np.asarray(tok_s))
    # stream exhausted -> even the logit values match the oracle exactly
    np.testing.assert_array_equal(np.asarray(logits_e), np.asarray(logits_s))


# --------------------------------------------------- dispatcher + kernel
@pytest.mark.parametrize("levels", [None, 3, 0])
def test_dispatcher_early_exit_mode(levels):
    """schedule="streaming" + early_exit on the jnp backend: bit-identical
    to the stacked schedule at every truncation depth."""
    rng = np.random.default_rng(5)
    a = _rand_ints(rng, 8, (70, 90))
    b = _rand_ints(rng, 8, (90, 40))
    np.testing.assert_array_equal(
        np.asarray(l2r_gemm(a, b, levels=levels, schedule="streaming",
                            backend="jnp", early_exit=True)),
        np.asarray(l2r_matmul_int_stacked(a, b, 8, 2, levels)))


def test_pallas_streaming_level_count_scalar():
    """The streaming kernel's dynamic level-count scalar: planes below the
    count are bit-identical to the full run (steps at higher levels skip
    compute + write); the count is a runtime value, not a static arg."""
    rng = np.random.default_rng(6)
    a = jnp.asarray(rng.integers(-128, 128, (128, 256), dtype=np.int8))
    b = jnp.asarray(rng.integers(-128, 128, (256, 128), dtype=np.int8))
    full = np.asarray(l2r_gemm_pallas_streaming(a, b, interpret=True))
    for cnt in [1, 3, 7]:
        cut = np.asarray(l2r_gemm_pallas_streaming(
            a, b, interpret=True, level_count=jnp.int32(cnt)))
        np.testing.assert_array_equal(cut[:cnt], full[:cnt],
                                      err_msg=f"level_count={cnt}")


# ----------------------------------------------------- conv early exit
def test_conv_progressive_while_matches_scan_stack():
    """The early-exit conv runs the scan's per-level term: full run equals
    the last stack level, a fold-stopped run equals the stack at that
    depth, for default and strided geometry."""
    rng = np.random.default_rng(7)
    cfg = QuantConfig()
    x = jnp.asarray(rng.standard_normal((2, 10, 10, 8)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((3, 3, 8, 16)) * 0.2)
                    .astype(np.float32))
    for stride in [1, 2]:
        res, scale = l2r_conv2d_progressive(x, w, cfg, stride=stride)
        acc, _, t, scale_w = l2r_conv2d_progressive_while(x, w, cfg,
                                                          stride=stride)
        assert int(t) == res.partial.shape[0]
        np.testing.assert_array_equal(np.asarray(acc),
                                      np.asarray(res.partial[-1]))
        np.testing.assert_array_equal(np.asarray(scale_w), np.asarray(scale))
        acc3, _, t3, _ = l2r_conv2d_progressive_while(
            x, w, cfg, fold=lambda c, p, i: c + 1, init=jnp.int32(0),
            done_fn=lambda c: c >= 3, stride=stride)
        assert int(t3) == 3
        np.testing.assert_array_equal(np.asarray(acc3),
                                      np.asarray(res.partial[2]))


# ------------------------------------------------------------ end to end
def test_vgg16_classify_progressive_early_exit_identical():
    """Early-exit classification: classes and exit levels bit-identical to
    the scan path, classes equal to the one-shot vgg16_apply argmax."""
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
    pred_s, lv_s, _ = vgg16_classify_progressive(params, img, cfg,
                                                 weights_q=cache)
    pred_e, lv_e, _ = vgg16_classify_progressive(params, img, cfg,
                                                 weights_q=cache,
                                                 early_exit=True)
    np.testing.assert_array_equal(np.asarray(pred_e), np.asarray(pred_s))
    np.testing.assert_array_equal(np.asarray(lv_e), np.asarray(lv_s))
    np.testing.assert_array_equal(np.asarray(pred_e), ref.argmax(-1))


@pytest.fixture(scope="module")
def l2r_lm():
    from repro.configs import get_smoke
    from repro.models.common import materialize
    from repro.models.transformer import lm_build

    cfg = dataclasses.replace(get_smoke("smollm-135m"), l2r=QuantConfig())
    params = materialize(lm_build(cfg), jax.random.PRNGKey(0))
    return cfg, params


def test_progressive_prefill_streams_last_token_only(l2r_lm):
    """Batch-progressive prefill: the committed first token equals the
    one-shot prefill argmax, the spliced state is identical, and the exit
    level is a valid stream position."""
    from repro.serve.engine import make_prefill_step

    cfg, params = l2r_lm
    rng = np.random.default_rng(5)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    ref_prefill = make_prefill_step(cfg, 32, jnp.float32)
    st_r, logits_r = ref_prefill(params, {"tokens": prompt})
    prog_prefill = make_prefill_step(cfg, 32, jnp.float32, progressive=True)
    st_p, logits_p, tok, lv = prog_prefill(params, {"tokens": prompt})
    np.testing.assert_array_equal(
        np.asarray(tok), np.asarray(logits_r).argmax(-1))
    np.testing.assert_array_equal(np.asarray(logits_p),
                                  np.asarray(logits_r))
    assert np.asarray(lv).min() >= 0 and np.asarray(lv).max() <= 6
    for a, b in zip(jax.tree.leaves(st_p), jax.tree.leaves(st_r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_step_early_exit_tokens_identical(l2r_lm):
    """progressive + early_exit decode: same tokens and exit levels as the
    scan-based progressive step (and hence as greedy decoding)."""
    from repro.serve.engine import make_decode_step, make_prefill_step

    cfg, params = l2r_lm
    rng = np.random.default_rng(13)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    prefill = make_prefill_step(cfg, 32, jnp.float32)
    state, logits = prefill(params, {"tokens": prompt})
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    dec_s = make_decode_step(cfg, progressive=True)
    dec_e = make_decode_step(cfg, progressive=True, early_exit=True)
    st_s, st_e = state, state
    for _ in range(4):
        st_s, tok_s, _, lv_s = dec_s(params, st_s, tok)
        st_e, tok_e, _, lv_e = dec_e(params, st_e, tok)
        np.testing.assert_array_equal(np.asarray(tok_e), np.asarray(tok_s))
        np.testing.assert_array_equal(np.asarray(lv_e), np.asarray(lv_s))
        tok = tok_s


def test_batcher_records_prefill_exit_levels(l2r_lm):
    """ContinuousBatcher(progressive=True): prefill exit levels land on
    the requests and in stats() alongside the decode histogram, and the
    emitted tokens still match the non-progressive engine."""
    from repro.serve.batching import ContinuousBatcher, Request

    cfg, params = l2r_lm
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, cfg.vocab, (5,)).astype(np.int32)
               for _ in range(3)]

    def run(progressive, early_exit=False):
        eng = ContinuousBatcher(cfg, params, n_slots=2, max_len=32,
                                progressive=progressive,
                                early_exit=early_exit)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=3)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run(max_steps=100)
        return eng, reqs

    eng_p, reqs_p = run(True)
    eng_e, reqs_e = run(True, early_exit=True)
    _, reqs_r = run(False)
    for rp, re_, rr in zip(reqs_p, reqs_e, reqs_r):
        assert rp.output == rr.output == re_.output
        assert rp.prefill_exit_level is not None
        assert rp.prefill_exit_level == re_.prefill_exit_level
        assert rp.exit_levels == re_.exit_levels
    for rr in reqs_r:
        assert rr.prefill_exit_level is None
    stats = eng_p.stats()
    assert stats["prefills"] == len(prompts)
    assert sum(stats["prefill_exit_level_hist"]) == stats["prefills"]
    assert 0.0 <= stats["mean_prefill_exit_level"] <= stats["n_levels"] - 1
    assert stats["tokens"] == sum(len(r.exit_levels) for r in reqs_p)


def test_dispatcher_early_exit_rejected_where_unhonorable():
    """early_exit=True is rejected loudly by schedules/backends that have
    no level loop to stop (it used to be silently dropped): pairs and
    stacked schedules raise, and the Pallas backends point to the
    streaming kernel's dynamic level_count scalar."""
    rng = np.random.default_rng(9)
    a = _rand_ints(rng, 8, (16, 16))
    b = _rand_ints(rng, 8, (16, 16))
    for schedule in ("pairs", "stacked"):
        with pytest.raises(ValueError, match="streaming"):
            l2r_gemm(a, b, schedule=schedule, early_exit=True)
    with pytest.raises(ValueError, match="level_count"):
        l2r_gemm(a, b, schedule="streaming", backend="pallas-interpret",
                 early_exit=True)
