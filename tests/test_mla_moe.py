"""DeepSeek-V2-Lite's two mechanisms at the SMOKE size on the CPU:
multi-head latent attention with its latent cache (models/mla.py) and
the held-experts dropless MoE (models/moe.py), held to the benchmark's
plain float32 reference (bench/configs/deepseek-v2-lite-ep8.py, loaded
by path: it imports nothing of the program)."""

import dataclasses
import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke
from repro.core.quant import QuantConfig
from repro.models import moe as moe_mod
from repro.models.common import Param
from repro.models.mla import yarn_inv_freq
from repro.models.moe import moe_build, moe_serve
from repro.models.transformer import init_lm_state, lm_build, lm_forward
from repro.serve.engine import (make_bucket_prefill_step, make_decode_step,
                                prepare_params)

REF_PATH = os.path.join(os.path.dirname(__file__), "..", "bench", "configs",
                        "deepseek-v2-lite-ep8.py")


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("v2lite_reference",
                                                  REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ref_cfg(pc):
    """The reference's configuration (published DeepSeek-V2 keys) of a
    program config."""
    return {
        "hidden_size": pc.d_model, "num_attention_heads": pc.n_heads,
        "kv_lora_rank": pc.kv_lora_rank, "qk_nope_head_dim": pc.qk_nope_dim,
        "qk_rope_head_dim": pc.qk_rope_dim, "v_head_dim": pc.v_head_dim,
        "intermediate_size": pc.dense_d_ff,
        "moe_intermediate_size": pc.moe_d_ff,
        "n_shared_experts": pc.n_shared_experts,
        "n_routed_experts": pc.experts_held,
        "deployment": {"n_routed_experts_published": pc.n_experts,
                       "expert_offset": pc.expert_offset},
        "num_experts_per_tok": pc.experts_per_token,
        "num_hidden_layers": pc.n_layers,
        "first_k_dense_replace": pc.first_k_dense,
        "vocab_size": pc.vocab, "rms_norm_eps": pc.norm_eps,
        "rope_theta": pc.rope_theta,
        "rope_scaling": {"factor": pc.yarn_factor,
                         "original_max_position_embeddings":
                             pc.yarn_original_len,
                         "beta_fast": pc.yarn_beta_fast,
                         "beta_slow": pc.yarn_beta_slow,
                         "mscale": pc.yarn_mscale_all_dim,
                         "mscale_all_dim": pc.yarn_mscale_all_dim},
        "routed_scaling_factor": 1,
    }


def _smoke(**kw):
    return dataclasses.replace(get_smoke("deepseek-v2-lite"), **kw)


def _weights(desc, seed: int):
    """Seeded float32 weights for a Param tree (host RNG: no per-shape
    compile), at materialize's scales: norms zero, 1/sqrt(fan_in)."""
    rng = np.random.default_rng(seed)

    def one(p):
        if p.init == "zeros":
            return jnp.zeros(p.shape, jnp.float32)
        std = p.scale or 1 / math.sqrt(p.shape[0] if len(p.shape) >= 2
                                        else p.shape[-1])
        return jnp.asarray(rng.standard_normal(p.shape) * std, jnp.float32)
    return jax.tree.map(one, desc, is_leaf=lambda x: isinstance(x, Param))


@pytest.fixture(scope="module")
def smoke():
    """The SMOKE config, seeded weights in the layout the program and
    the reference share, and the reference's configuration."""
    pc = _smoke()
    return pc, _weights(lm_build(pc), 3), _ref_cfg(pc)


@functools.lru_cache(maxsize=None)
def _steps(pc):
    """The gateway's jitted step factories for ``pc`` (bucket 16)."""
    return (make_bucket_prefill_step(pc, 24, jnp.float32),
            make_decode_step(pc))


def _served_logits(pc, w, tokens, prompt_len, bits):
    """Logits of each position from ``prompt_len - 1`` on: one bucket
    prefill of the prompt, then decode steps through the latent cache
    fed ``tokens``, as the gateway runs them (its step factories)."""
    pc = dataclasses.replace(
        pc, l2r=QuantConfig(n_bits=bits) if bits else None)
    params = prepare_params(pc, w)
    pre, dec = _steps(pc)
    padded = jnp.zeros((1, 16), jnp.int32).at[0, :prompt_len].set(
        tokens[:prompt_len])
    state, logits = pre(params, padded, jnp.array([prompt_len]))
    out = [logits[0, 0]]
    for p in range(prompt_len, len(tokens)):
        state, _, logits = dec(params, state, tokens[None, p:p + 1])
        out.append(logits[0, 0])
    return jnp.stack(out)


@pytest.mark.parametrize("bits,limit,within",
                         [(None, 1e-5, True), (8, 0.6, True),
                          (4, 0.6, False)])
def test_served_logits_match_reference(ref, smoke, bits, limit, within):
    """Prefill then decode through the latent cache, float32 or W8A8
    through the L2R path, against the float32 reference's full forward.
    The relative L2 distance of the logits: with no L2R, float32
    rounding (measured 1.3e-6); int8 operands land within 0.6
    (measured 0.14-0.41 over six weight seeds, the tail from top-2
    choices that rounding flips at near-ties of this 64-wide router);
    4-bit operands, the precision below the configuration's, land above
    it (measured 0.84-1.38)."""
    pc, w, cfg = smoke
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, pc.vocab, 12), jnp.int32)
    want = jax.jit(lambda w, t: ref.forward_logits(cfg, w, t))(
        w, tokens)[8 - 1:]
    got = _served_logits(pc, w, tokens, 8, bits)
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert (err < limit) == within, err


def test_absorbed_decode_matches_decompressed():
    """In float32 with no L2R, the absorbed decode over the latent cache
    gives the decompressed attention's output: the same sums in another
    order, so within float32 reassociation (1e-4; measured 2.3e-5)."""
    pc = _smoke(capacity_factor=16.0)  # the train forward drops none
    params = _weights(lm_build(pc), 0)
    toks = jnp.asarray(np.random.default_rng(2).integers(0, pc.vocab,
                                                         (2, 10)), jnp.int32)

    fwd = jax.jit(lambda t, st, mode: lm_forward(
        pc, params, tokens=t, mode=mode, state=st)[:2], static_argnums=2)
    full, _ = fwd(toks, None, "train")
    _, st = fwd(toks[:, :7], init_lm_state(pc, 2, 16, jnp.float32),
                "prefill")
    for p in range(7, 10):
        h, st = fwd(toks[:, p:p + 1], st, "decode")
        np.testing.assert_allclose(h[:, 0], full[:, p], rtol=1e-4,
                                   atol=1e-4)


def test_yarn_inv_freq_matches_the_formula():
    """DeepSeek-V2-Lite's rope: 64 dims, theta 1e4, factor 40 over 4096
    positions, beta 32/1.  The ramp runs from floor(d(32)) = 10 to
    ceil(d(1)) = 23 with d(r) = 64 ln(4096/(2 pi r)) / (2 ln 1e4)."""
    cfg = get_config("deepseek-v2-lite")
    d = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(1e4))  # noqa: E731
    lo, hi = math.floor(d(32)), math.ceil(d(1))
    assert (lo, hi) == (10, 23)
    i = np.arange(32)
    extra = 1e4 ** (-2 * i / 64)
    m = 1 - np.clip((i - lo) / (hi - lo), 0, 1)
    want = extra / 40 * (1 - m) + extra * m
    np.testing.assert_allclose(yarn_inv_freq(cfg), want, rtol=1e-6)
    assert np.allclose(yarn_inv_freq(cfg)[:10], extra[:10])
    assert np.allclose(yarn_inv_freq(cfg)[23:], extra[23:] / 40)


def _moe_cfg(**kw):
    return dataclasses.replace(
        get_smoke("deepseek-v2-lite"), d_model=32, n_experts=64,
        experts_per_token=6, moe_d_ff=16, n_shared_experts=2, **kw)


def _moe_numpy(cfg, p, x):
    """The uncut MoE layer in float64 numpy: softmax router over all
    experts, top-k raw probabilities as gates, plus the shared SwiGLU."""
    x = np.asarray(x, np.float64)
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731

    def swiglu(a, wo):  # a (..., T, 2, f)
        return (a[..., 0, :] / (1 + np.exp(-a[..., 0, :])) * a[..., 1, :]) @ wo
    logits = x @ f64(p["router"])
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, -1)[:, :cfg.experts_per_token]
    gate = np.zeros_like(probs)
    np.put_along_axis(gate, top, np.take_along_axis(probs, top, -1), -1)
    every = swiglu(np.einsum("td,edgf->etgf", x, f64(p["wi"])),
                   f64(p["wo"]))  # (E, T, d)
    shared = swiglu(np.einsum("td,dgf->tgf", x, f64(p["shared_wi"])),
                    f64(p["shared_wo"]))
    return shared + np.einsum("te,etd->td", gate, every)


def test_held_shares_sum_to_the_uncut_layer():
    """The eight shares of 8 experts (offsets 0, 8, ..., 56) each route
    over all 64 and add only their experts' part; summed, with the
    shared experts (which every chip computes) counted once, they give
    the uncut layer (float32 against float64: 1e-5)."""
    full_cfg = _moe_cfg()
    p = _weights(moe_build(full_cfg), 5)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, 6, 32)),
                    jnp.float32)
    total = 0.0
    for off in range(0, 64, 8):
        cfg = _moe_cfg(n_experts_held=8, expert_offset=off)
        share = {**p, "wi": p["wi"][off:off + 8], "wo": p["wo"][off:off + 8]}
        out, counts = moe_serve(cfg, share, x)
        total = total + out
        assert int(counts[1]) == 8 * 6  # every held expert, every row
    no_routed = moe_serve(_moe_cfg(n_experts_held=8), {
        **p, "wi": p["wi"][:8] * 0, "wo": p["wo"][:8] * 0}, x)[0]
    total = total - 7 * no_routed  # the shared experts, counted once
    np.testing.assert_allclose(total[0], _moe_numpy(full_cfg, p, x[0]),
                               rtol=1e-5, atol=1e-5)


def test_packed_dispatch_and_its_fallback_agree():
    """384 rows, 64 experts, top-6, all held: each expert's buffer holds
    128 rows (1.25 times its expected 36, rounded up to the row tile).
    Random rows take one round; the same rows beside 160 or 288 copies
    of one row route that many to six experts, which take two or three
    rounds of the buffers.  Each equals the float64 layer (1e-5), and
    through the L2R path a row's output is the same bits in every case
    (rows are independent)."""
    from repro.models.moe import moe_serve_capacity

    cfg = _moe_cfg()
    assert moe_serve_capacity(cfg, 384) == 128
    p = _weights(moe_build(cfg), 10)
    x = np.random.default_rng(11).standard_normal((1, 384, 32))
    serve = jax.jit(moe_serve, static_argnums=0)
    q = dataclasses.replace(cfg, l2r=QuantConfig())
    outs = []
    for rounds, copies in ((1, 0), (2, 160), (3, 288)):
        xx = x.copy()
        xx[0, 384 - copies:] = x[0, 0]  # rows that choose the same six
        out, counts = serve(cfg, p, jnp.asarray(xx, jnp.float32))
        np.testing.assert_allclose(out[0], _moe_numpy(cfg, p, xx[0]),
                                   rtol=1e-5, atol=1e-5)
        assert int(counts[1]) == 64 * 128 * rounds
        assert int(counts[2]) > 128 * (rounds - 1)
        outs.append(np.asarray(serve(q, p, jnp.asarray(xx, jnp.float32))[0]))
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0][0, :96], o[0, :96])


def test_a_request_decodes_alike_alone_and_in_a_full_batch(smoke):
    """Serving MoE drops nothing, so a row's decode logits do not depend
    on the rows beside it: one request decoded alone and as row 0 of a
    batch of 8 other prompts agree to float32 rounding (1e-5; capacity
    dropping at the training factor moves them by 1e-2 and more)."""
    pc, w, _ = smoke
    pc = dataclasses.replace(pc, l2r=QuantConfig(n_bits=8))
    params = prepare_params(pc, w)
    pre, dec = _steps(pc)
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, pc.vocab, (8, 16)), jnp.int32)
    lens = jnp.asarray([8, 5, 7, 3, 8, 6, 2, 8])
    rows = []
    for batch in (toks[:1], toks):
        st, _ = pre(params, batch, lens[:len(batch)])
        _, _, logits = dec(params, st, batch[:, :1])
        rows.append(logits[0, 0])
    np.testing.assert_allclose(rows[0], rows[1], rtol=1e-5, atol=1e-5)


def test_raw_softmax_gates_without_renormalisation():
    """``moe_norm_topk=False``: the gates are the router's softmax
    probabilities of the chosen experts, as they are."""
    cfg = _moe_cfg()
    p = _weights(moe_build(cfg), 8)
    x = jnp.asarray(np.random.default_rng(9).standard_normal((5, 32)),
                    jnp.float32)
    probs, gates, local, held = moe_mod._route(cfg, p, x)
    want = jax.nn.softmax(
        jnp.dot(x, p["router"], precision=jax.lax.Precision.HIGHEST), -1)
    top, idx = jax.lax.top_k(want, 6)
    np.testing.assert_allclose(gates, top, rtol=1e-6)
    np.testing.assert_array_equal(local, idx)
    assert bool(held.all())
    assert float(gates.sum(-1).max()) < 0.99  # not renormalised to sum 1


def test_int8_records_scale_expert_stacks_per_expert(smoke):
    """``quantize_desc`` (the dry run's int8 storage) and
    ``quantize_params`` (the checkpoint codec) store routed-expert
    stacks in int8 with one scale per layer and expert, as
    ``prepare_params`` does per channel, and a serving prefill runs on
    those records: its hidden states lie within W8A8 noise of float32's
    (0.35 relative, the codec's own bound; routers and ``W_kvb`` stay
    float)."""
    from repro.models.common import quantize_desc, quantize_params

    pc, w, _ = smoke
    desc = lm_build(pc)
    qdesc, qp = quantize_desc(desc), quantize_params(desc, w)
    shapes = lambda t: jax.tree.map(  # noqa: E731
        lambda a: tuple(a.shape), t,
        is_leaf=lambda a: hasattr(a, "shape") and hasattr(a, "axes"))
    assert shapes(qdesc) == shapes(qp)
    wi = qp["stack"][0]["ffn"]["wi"]
    reps, e = wi["q"].shape[:2]
    assert wi["q"].dtype == jnp.int8
    assert wi["scale"].shape == (reps, e, 1, 1, 1)
    assert not isinstance(qp["stack"][0]["ffn"]["router"], dict)

    toks = jnp.asarray(np.random.default_rng(4).integers(0, pc.vocab,
                                                         (2, 8)), jnp.int32)
    fwd = jax.jit(lambda p: lm_forward(
        pc, p, tokens=toks, mode="prefill",
        state=init_lm_state(pc, 2, 8, jnp.float32))[0])
    h_f, h_q = np.asarray(fwd(w)), np.asarray(fwd(qp))
    rel = np.abs(h_f - h_q).max() / np.abs(h_f).max()
    assert 0 < rel < 0.35, rel
