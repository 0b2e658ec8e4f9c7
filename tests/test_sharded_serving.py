"""Sharded L2R serving: the shard_mapped consensus streaming walk.

The load-bearing invariant: partitioning the plane-operand schedules over
a mesh — RHS weight stacks vocab-sharded on ``model``, LHS activation
stacks batch-sharded on ``data`` — changes WHERE each accumulator tile
lives but not a single bit of it (the contraction K is never sharded and
the integer/guarded-f32 arithmetic is order-exact), and the per-level
decision reductions (max/min/psum of identical floats across shards) are
exact, so streaming prefixes, committed decisions, and per-row exit
levels are bit-identical to the single-device oracle — including
``early_exit=True``, where the psum consensus stops every device at the
fleet-wide slowest row, exactly where the single-device while loop stops.

Multi-device tests run in a subprocess with 8 virtual host-platform
devices (the flag must be set before jax initializes; the main process
keeps its own device count).  They carry the ``sharded`` marker — the CI
virtual-8-device job runs ``pytest -m sharded``.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.progressive import sharded_walk_axes
from repro.core.quant import QuantConfig
from repro.launch.mesh import virtual_device_env
from repro.sharding import ctx

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subproc(script: str, timeout: int = 900):
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=_REPO, env=virtual_device_env(8), timeout=timeout)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    return out.stdout


# ---------------------------------------------------------- routing logic
class _FakeMesh:
    def __init__(self, **shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_sharded_walk_axes_routing():
    """Mesh routing: divisibility drops exactly the non-dividing side,
    trivial meshes (and no mesh) fall back to the single-device path."""
    mesh = _FakeMesh(data=2, model=4)
    assert sharded_walk_axes((8,), 16, mesh) == (mesh, ("data",), "model")
    # rows not divisible by data -> batch replicates, vocab still shards
    assert sharded_walk_axes((7,), 16, mesh) == (mesh, (), "model")
    # vocab not divisible by model -> vocab replicates, batch still shards
    assert sharded_walk_axes((8,), 10, mesh) == (mesh, ("data",), None)
    # neither divides -> single-device path
    assert sharded_walk_axes((7,), 10, mesh) is None
    # trivial mesh -> single-device path
    assert sharded_walk_axes((8,), 16, _FakeMesh(data=1, model=1)) is None
    # no mesh installed anywhere -> None
    assert sharded_walk_axes((8,), 16, None) is None
    # only 2-D tiles stream sharded
    assert sharded_walk_axes((2, 8), 16, mesh) is None


# ------------------------------------------------------------- satellites
def test_hint_overlong_spec_raises():
    """A hint spec naming more dims than the operand has used to be
    silently zip-truncated (trailing entries dropped, no error); now the
    rank mismatch raises with the shapes — in hint AND hint_uneven."""
    from repro.launch.mesh import make_local_mesh

    ctx.set_mesh(make_local_mesh(1, 1))
    x = jnp.zeros((4, 8))
    ctx.hint(x, "data")  # shorter spec: fine (trailing dims replicate)
    ctx.hint(x, "data", None)
    with pytest.raises(ValueError, match=r"rank 2"):
        ctx.hint(x, "data", None, "model")
    with pytest.raises(ValueError, match=r"\(4, 8\)"):
        ctx.hint_uneven(x, None, None, "model")
    ctx.set_mesh(None)
    # without a mesh both are identities (no constraint to mis-apply)
    assert ctx.hint(x, "data", None, "model") is x


def test_mesh_context_fixture_restores_none():
    """The autouse conftest fixture must have cleared the mesh installed
    by any earlier test before this one runs."""
    assert ctx.get_mesh() is None


def test_resolve_backend_env_typo_rejected_naming_source(monkeypatch):
    """A typo'd $REPRO_L2R_BACKEND fails at resolve time with a message
    naming the env var and listing the valid backends."""
    from repro.kernels.l2r_gemm import BACKEND_ENV_VAR, resolve_backend

    monkeypatch.setenv(BACKEND_ENV_VAR, "jnpp")
    with pytest.raises(ValueError, match=BACKEND_ENV_VAR) as ei:
        resolve_backend()
    msg = str(ei.value)
    for b in ("jnp", "pallas-interpret", "pallas-tpu", "auto"):
        assert b in msg, msg
    # the explicit argument names its own source
    monkeypatch.delenv(BACKEND_ENV_VAR)
    with pytest.raises(ValueError, match="backend argument"):
        resolve_backend("bogus")


def test_batcher_stats_schema_stable_before_first_token():
    """Progressive-mode stats() emits n_levels and the zero-filled exit
    histograms from construction on — the schema must not change shape
    once tokens start landing (monitoring consumers scrape it)."""
    from repro.configs import get_smoke
    from repro.models.common import materialize
    from repro.models.transformer import lm_build
    from repro.serve.batching import ContinuousBatcher, Request

    cfg = dataclasses.replace(get_smoke("smollm-135m"), l2r=QuantConfig())
    params = materialize(lm_build(cfg), jax.random.PRNGKey(0))
    eng = ContinuousBatcher(cfg, params, n_slots=2, max_len=16,
                            progressive=True)
    before = eng.stats()
    n_levels = 2 * cfg.l2r.planes - 1
    assert before["n_levels"] == n_levels
    assert before["tokens"] == 0 and before["prefills"] == 0
    assert before["exit_level_hist"] == [0] * n_levels
    assert before["prefill_exit_level_hist"] == [0] * n_levels
    assert before["mean_exit_level"] == 0.0
    assert before["mean_prefill_exit_level"] == 0.0
    eng.submit(Request(uid=0, prompt=np.asarray([3, 5, 7], np.int32),
                       max_new_tokens=2))
    eng.run(max_steps=8)
    after = eng.stats()
    assert set(after) == set(before), "stats() schema changed shape mid-run"
    assert after["tokens"] > 0 and after["prefills"] == 1


# ------------------------------------------- multi-device: streaming walk
SHARDED_STREAM = textwrap.dedent("""
    import sys
    sys.path.insert(0, "src")
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.progressive import streaming_argmax
    from repro.core.quant import (PlaneOperands, QuantConfig, quantize,
                                  quantize_weights)
    from repro.launch.mesh import make_local_mesh
    from repro.sharding import ctx

    assert len(jax.devices()) == 8, jax.devices()
    eq = np.testing.assert_array_equal

    def oracle_and_sharded(call_kwargs, mesh):
        ctx.set_mesh(None)
        ref = jax.tree.map(np.asarray, streaming_argmax(**call_kwargs))
        # explicit mesh arg AND the installed-context route
        exp = jax.tree.map(np.asarray,
                           streaming_argmax(**call_kwargs, mesh=mesh))
        ctx.set_mesh(mesh)
        got = jax.tree.map(np.asarray, streaming_argmax(**call_kwargs))
        ctx.set_mesh(None)
        return ref, exp, got

    meshes = {"1x4": make_local_mesh(1, 4), "2x2": make_local_mesh(2, 2),
              "4x2": make_local_mesh(4, 2)}
    rng = np.random.default_rng(0)
    m, k, n = 8, 48, 16
    for n_bits, log2_radix in [(8, 2), (4, 2)]:
        cfg = QuantConfig(n_bits=n_bits, log2_radix=log2_radix)
        x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
        w = jnp.asarray((rng.standard_normal((k, n)) * 0.3)
                        .astype(np.float32))
        xq, xs = quantize(x, cfg, axis=0)
        w_q = quantize_weights(w, cfg)
        bias = jnp.asarray(rng.standard_normal((n,)).astype(np.float32))
        for name, mesh in meshes.items():
            for early_exit in (False, True):
                kw = dict(xq=xq, wq=w_q.q, xs=xs, ws=w_q.scale,
                          n_bits=n_bits, log2_radix=log2_radix,
                          bias=bias, early_exit=early_exit)
                ref, exp, got = oracle_and_sharded(kw, mesh)
                for s in (exp, got):
                    for a, b, what in zip(ref, s,
                                          ("logits", "tok", "exit_level")):
                        eq(np.asarray(b), np.asarray(a),
                           err_msg=f"{name} bits={n_bits} ee={early_exit} "
                                   f"{what}")
        print(f"stream sweep ok bits={n_bits} r={1 << log2_radix}")

    # prefix bit-exactness at EVERY truncation depth, with exact
    # power-of-two scales so logits == float(int prefix) exactly: equal
    # logits at depth t <=> equal integer accumulator prefix at depth t
    cfg = QuantConfig()
    n_levels = 2 * cfg.planes - 1
    xq = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-128, 128, (k, n), dtype=np.int8))
    xs2 = jnp.full((m, 1), 2.0 ** -7, jnp.float32)
    ws2 = jnp.full((1, n), 2.0 ** -6, jnp.float32)
    for t in range(1, n_levels + 1):
        kw = dict(xq=xq, wq=wq, xs=xs2, ws=ws2, levels=t)
        ref, exp, got = oracle_and_sharded(kw, meshes["2x2"])
        for s in (exp, got):
            for a, b, what in zip(ref, s, ("logits", "tok", "exit_level")):
                eq(np.asarray(b), np.asarray(a),
                   err_msg=f"prefix depth {t} {what}")
    print("prefix sweep ok (all depths, pow2 scales)")

    # the window-padded weight plane-stack cache feeds the sharded walk
    # directly (vocab-sharded stack, zero per-step operand prep)
    w_pre = quantize_weights(w, cfg, prestack=True, window_pad=True,
                             shard=(None, "model"), mesh=meshes["1x4"])
    xq, xs = quantize(x, cfg, axis=0)
    for early_exit in (False, True):
        ctx.set_mesh(None)
        ref = jax.tree.map(np.asarray, streaming_argmax(
            xq, w_pre.q, xs, w_pre.scale, early_exit=early_exit))
        got = jax.tree.map(np.asarray, streaming_argmax(
            xq, w_pre.planes, xs, w_pre.scale, early_exit=early_exit,
            mesh=meshes["1x4"]))
        for a, b, what in zip(ref, got, ("logits", "tok", "exit_level")):
            eq(np.asarray(b), np.asarray(a),
               err_msg=f"plane-cache ee={early_exit} {what}")
    print("plane-stack cache ok")

    # non-divisible vocab (9 classes over a 2-way model axis): the model
    # axis drops, the batch still shards — result still the oracle's
    # bit for bit
    w10 = jnp.asarray((rng.standard_normal((k, 9)) * 0.3)
                      .astype(np.float32))
    wq10 = quantize_weights(w10, cfg)
    ref = jax.tree.map(np.asarray, streaming_argmax(
        xq, wq10.q, xs, wq10.scale, early_exit=True))
    got = jax.tree.map(np.asarray, streaming_argmax(
        xq, wq10.q, xs, wq10.scale, early_exit=True, mesh=meshes["4x2"]))
    for a, b in zip(ref, got):
        eq(np.asarray(b), np.asarray(a))
    print("uneven-vocab fallback ok")
    print("ALL_OK")
""")


@pytest.mark.sharded
def test_sharded_streaming_bit_exact_vs_oracle():
    """The shard_mapped consensus walk on a virtual 8-device host: logits
    (= the accumulator prefix, via exact pow2 scales), committed tokens,
    and per-row exit levels bit-identical to the single-device oracle —
    across meshes (1x4, 2x2, 4x2), digit configs, every truncation
    depth, both control flows, the cached vocab-sharded plane stack, and
    the non-divisible-vocab fallback."""
    out = _run_subproc(SHARDED_STREAM)
    assert "ALL_OK" in out


# ------------------------------------------- multi-device: serving paths
SHARDED_SERVING = textwrap.dedent("""
    import dataclasses
    import sys
    sys.path.insert(0, "src")
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core.quant import QuantConfig
    from repro.launch.mesh import install_local_mesh, make_local_mesh
    from repro.sharding import ctx

    assert len(jax.devices()) == 8, jax.devices()
    eq = np.testing.assert_array_equal

    # ---- VGG-16 progressive classification, fc8 vocab-sharded ----
    from repro.models.cnn import (vgg16_build, vgg16_classify_progressive,
                                  vgg16_quantize_weights)
    from repro.models.common import materialize

    qcfg = QuantConfig()
    params = materialize(vgg16_build(n_classes=16), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.standard_normal((4, 32, 32, 3))
                      .astype(np.float32))
    ctx.set_mesh(None)
    cache_r = vgg16_quantize_weights(params, qcfg)
    refs = {ee: jax.tree.map(np.asarray, vgg16_classify_progressive(
        params, img, qcfg, weights_q=cache_r, early_exit=ee))
        for ee in (False, True)}
    mesh = install_local_mesh(data=2, model=4)
    cache_s = vgg16_quantize_weights(params, qcfg)  # fc8 vocab-sharded
    for ee in (False, True):
        got = jax.tree.map(np.asarray, vgg16_classify_progressive(
            params, img, qcfg, weights_q=cache_s, early_exit=ee))
        for a, b, what in zip(refs[ee], got,
                              ("pred", "exit_level", "logits")):
            eq(np.asarray(b), np.asarray(a),
               err_msg=f"vgg16 ee={ee} {what}")
    ctx.set_mesh(None)
    print("vgg16 sharded classify ok")

    # ---- progressive prefill/decode, LM head vocab-sharded ----
    from repro.configs import get_smoke
    from repro.models.transformer import lm_build
    from repro.serve.engine import (make_decode_step, make_prefill_step,
                                    prepare_params)

    cfg = dataclasses.replace(get_smoke("smollm-135m"), l2r=QuantConfig())
    raw = materialize(lm_build(cfg), jax.random.PRNGKey(1))
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (4, 6)), jnp.int32)

    def run_engine(mesh):
        ctx.set_mesh(mesh)
        params = prepare_params(cfg, raw)  # head_q vocab-sharded if mesh
        # replicated backbone on a mesh -> interior hints scoped off
        # (the bit-parity serving contract; the head walk still shards)
        hints = mesh is None
        prefill = make_prefill_step(cfg, 24, jnp.float32,
                                    progressive=True, early_exit=True,
                                    backbone_hints=hints)
        state, logits, tok, lv = prefill(params, {"tokens": prompt})
        toks, lvs = [np.asarray(tok)], [np.asarray(lv)]
        dec = make_decode_step(cfg, progressive=True, early_exit=True,
                               backbone_hints=hints)
        cur = tok.astype(jnp.int32)
        for _ in range(3):
            state, cur, _, lv = dec(params, state, cur)
            toks.append(np.asarray(cur))
            lvs.append(np.asarray(lv))
        ctx.set_mesh(None)
        return np.stack(toks), np.stack(lvs)

    tok_r, lv_r = run_engine(None)
    tok_s, lv_s = run_engine(make_local_mesh(2, 4))
    eq(tok_s, tok_r, err_msg="sharded decode tokens")
    eq(lv_s, lv_r, err_msg="sharded decode exit levels")
    print("engine sharded prefill+decode ok")

    # ---- ContinuousBatcher on the mesh ----
    from repro.serve.batching import ContinuousBatcher, Request

    prompts = [rng.integers(0, cfg.vocab, (5,)).astype(np.int32)
               for _ in range(3)]

    def run_batcher(mesh, state_sharding="replicated"):
        ctx.set_mesh(mesh)
        params = prepare_params(cfg, raw)
        eng = ContinuousBatcher(cfg, params, n_slots=2, max_len=24,
                                progressive=True, early_exit=True,
                                state_sharding=state_sharding)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=3)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run(max_steps=50)
        ctx.set_mesh(None)
        return reqs, eng.stats()

    # default ("replicated" state) mesh batcher: bit-identical to the
    # unmeshed run — only the consensus head walk is sharded, and it is
    # exact for ANY hidden states
    reqs_r, stats_r = run_batcher(None)
    reqs_s, stats_s = run_batcher(make_local_mesh(2, 4))
    for rr, rs in zip(reqs_r, reqs_s):
        assert rs.output == rr.output, (rs.output, rr.output)
        assert rs.exit_levels == rr.exit_levels
        assert rs.prefill_exit_level == rr.prefill_exit_level
    assert stats_s == stats_r, (stats_s, stats_r)
    print("batcher sharded ok")

    # explicit mesh= WITHOUT the installed context: the sharded walk
    # must engage through the argument chain alone (batcher -> step
    # factories -> progressive_logits_from_hidden -> streaming_argmax)
    ctx.set_mesh(None)
    m_exp = make_local_mesh(2, 4)
    eng = ContinuousBatcher(cfg, prepare_params(cfg, raw, mesh=m_exp),
                            n_slots=2, max_len=24, progressive=True,
                            early_exit=True, mesh=m_exp)
    reqs_e = [Request(uid=i, prompt=p, max_new_tokens=3)
              for i, p in enumerate(prompts)]
    for r in reqs_e:
        eng.submit(r)
    eng.run(max_steps=50)
    for rr, re_ in zip(reqs_r, reqs_e):
        assert re_.output == rr.output
        assert re_.exit_levels == rr.exit_levels
    assert eng.stats() == stats_r
    print("batcher explicit-mesh ok")

    # the scaling state layouts ("batch": slot axis over data; "specs":
    # the full state_specs policy).  GSPMD may repartition interior
    # float contractions under them, so only structural equality is
    # contractual — tokens flow, counts and schema match
    for mode in ("batch", "specs"):
        reqs_f, stats_f = run_batcher(make_local_mesh(2, 4),
                                      state_sharding=mode)
        assert [len(r.output) for r in reqs_f] == \
            [len(r.output) for r in reqs_r], mode
        assert stats_f["tokens"] == stats_r["tokens"], mode
        assert set(stats_f) == set(stats_r), mode
        print(f"batcher {mode}-sharded ok")
    print("ALL_OK")
""")


@pytest.mark.sharded
def test_sharded_serving_end_to_end_identical():
    """vgg16_classify_progressive, progressive prefill/decode, and the
    ContinuousBatcher on a (2, 4) virtual-device mesh: predictions,
    tokens, exit levels, logits, and stats all bit-identical to the
    unmeshed single-device runs (early_exit included — the consensus
    loop stops at the fleet-wide slowest row)."""
    out = _run_subproc(SHARDED_SERVING, timeout=1500)
    assert "ALL_OK" in out
