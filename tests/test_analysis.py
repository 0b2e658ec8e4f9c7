"""l2r-lint: exactness audit, overflow certifier, compiled-artifact audit.

Three families:
  * positive — every registered claimed-exact entry point passes every
    pass (the CI gate `tools/l2r_lint.py` in miniature);
  * negative — each pass catches a seeded violation (float op on an
    exact path, overflowing digit config, un-donated decode state);
  * adversarial tightness — operands that ACHIEVE the certifier's
    worst-case bound: int32-exact at the bound, wrapped one step beyond,
    so the bound is exact rather than merely safe.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import compiled as comp_audit
from repro.analysis import overflow
from repro.analysis.exactness import (ExactnessContract, audit_exactness,
                                      audit_hlo_text)
from repro.analysis.registry import iter_entries
from repro.core.l2r_gemm import l2r_matmul_int, l2r_matmul_int_stacked
from repro.core.quant import QuantConfig, quantize_weights

pytestmark = pytest.mark.analysis


# ------------------------------------------------------- exactness: positive
@pytest.mark.parametrize("entry", iter_entries(), ids=lambda e: e.name)
def test_registered_entries_pass_exactness(entry):
    if entry.contract is None:
        pytest.skip("sharding-only entry (no exactness contract)")
    if entry.skip:
        pytest.skip(entry.skip)
    fn, args = entry.build()
    rep = audit_exactness(fn, args, entry.contract, entry=entry.name)
    assert rep.ok, [v.to_json() for v in rep.violations]
    assert rep.tainted_eqns > 0  # the walk was actually on the taint path
    assert rep.int_dots + rep.f32_fastpath_dots > 0


@pytest.mark.parametrize("entry", iter_entries(), ids=lambda e: e.name)
def test_registered_entries_certify_overflow(entry):
    c = entry.contract
    if c is None:
        pytest.skip("sharding-only entry (no exactness contract)")
    cert = overflow.certify(c.n_bits, c.log2_radix, c.k, levels=c.levels)
    assert cert.sound, cert.describe()


# ------------------------------------------------------- exactness: negative
def _i8(shape, seed=0):
    return np.asarray(
        np.random.default_rng(seed).integers(-128, 128, shape), np.int8)


def test_exactness_flags_unguarded_f32_dot():
    """The seeded bug: an f32 contraction of digit-derived values
    without precision=HIGHEST (the bit-exactness break XLA's default
    precision introduces on TPU)."""
    def bad(aq, bq):
        out = jax.lax.dot_general(
            aq.astype(jnp.float32), bq.astype(jnp.float32),
            (((1,), (0,)), ((), ())))
        return out.astype(jnp.int32)

    rep = audit_exactness(bad, (_i8((4, 8)), _i8((8, 5), 1)),
                          ExactnessContract(k=8))
    assert not rep.ok
    assert any("HIGHEST" in v.reason for v in rep.violations)


def test_exactness_flags_float_op_on_exact_path():
    """A float op touching digit-derived values before the accumulator
    (the PR 5 float-reassociation bug class)."""
    def bad(aq, bq):
        a = aq.astype(jnp.float32) * 1.0001  # inexact scale mid-path
        out = jax.lax.dot_general(
            a, bq.astype(jnp.float32), (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)
        return out.astype(jnp.int32)

    rep = audit_exactness(bad, (_i8((4, 8)), _i8((8, 5), 1)),
                          ExactnessContract(k=8))
    assert not rep.ok
    assert any("fast-path" in v.reason for v in rep.violations)


def test_exactness_flags_f32_without_contract():
    """allow_f32=False contracts reject ANY float excursion."""
    def walk(aq, bq):
        out = jax.lax.dot_general(
            aq.astype(jnp.float32), bq.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)
        return out.astype(jnp.int32)

    rep = audit_exactness(walk, (_i8((4, 8)), _i8((8, 5), 1)),
                          ExactnessContract(k=8, allow_f32=False))
    assert not rep.ok


def test_exactness_flags_narrow_int_accumulation():
    """Integer dot accumulating in the operand dtype (int8 wraps)."""
    def bad(aq, bq):
        return jax.lax.dot_general(aq, bq, (((1,), (0,)), ((), ())))

    rep = audit_exactness(bad, (_i8((4, 8)), _i8((8, 5), 1)),
                          ExactnessContract(k=8))
    assert not rep.ok
    assert any("int32" in v.reason for v in rep.violations)


def test_exactness_recurses_into_scan():
    """A violation hidden inside a lax.scan body is still found."""
    def bad(aq, bq):
        def body(acc, i):
            t = jax.lax.dot_general(
                aq.astype(jnp.float32), bq.astype(jnp.float32),
                (((1,), (0,)), ((), ())))  # default precision: seeded bug
            return acc + t.astype(jnp.int32), i
        acc0 = jnp.zeros((4, 5), jnp.int32)
        out, _ = jax.lax.scan(body, acc0, jnp.arange(3))
        return out

    rep = audit_exactness(bad, (_i8((4, 8)), _i8((8, 5), 1)),
                          ExactnessContract(k=8))
    assert not rep.ok


def test_audit_hlo_text_flags_bf16_and_unguarded_f32():
    hlo = """
HloModule m

ENTRY %main (a: bf16[4,8], b: bf16[8,5]) -> bf16[4,5] {
  %a = bf16[4,8]{1,0} parameter(0)
  %b = bf16[8,5]{1,0} parameter(1)
  ROOT %dot.0 = bf16[4,5]{1,0} dot(bf16[4,8]{1,0} %a, bf16[8,5]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    v = audit_hlo_text(hlo, ExactnessContract(k=8))
    assert v and "bf16" in v[0].reason
    f32 = hlo.replace("bf16", "f32")
    assert audit_hlo_text(f32, ExactnessContract(k=8)) == []
    # f32 contraction present but the guard cannot hold for this k
    big_k = ExactnessContract(k=10**9)
    assert audit_hlo_text(f32, big_k)
    s32 = hlo.replace("bf16", "s32")
    assert audit_hlo_text(s32, ExactnessContract(k=10**9)) == []


# --------------------------------------------------------- overflow certifier
def test_certifier_known_extremes():
    # n=8, r=4: worst single product is qmin*qmin = 16384, reached at the
    # first MSDF prefix (top digits (-2)*(-2) << 12) and held to the end
    ext = overflow.per_element_extremes(8, 2)
    assert ext.exact
    cert = overflow.certify(8, 2, 1)
    assert cert.per_element == 16384 and cert.exact
    x, y, t = cert.witness
    assert x * y == 16384
    # truncation can only shrink the worst case
    prev = None
    for lv in range(1, 8):
        b = overflow.certify(8, 2, 7, levels=lv).bound
        if prev is not None:
            assert b >= prev
        prev = b


def test_certifier_interval_fallback_is_sound():
    cert = overflow.certify(16, 4, 64)
    assert not cert.exact and not cert.sound
    # the interval bound must dominate the true per-element extreme of a
    # narrower config it contains (8-bit operands are 16-bit operands)
    assert cert.per_element >= overflow.per_element_extremes(8, 4).magnitude()


def test_certificate_bound_is_achievable():
    """Adversarial tightness: operands achieving the worst case run
    int32-exact at the certified bound and WRAP one contraction element
    beyond it — the bound is exact, not merely safe."""
    cert1 = overflow.certify(8, 2, 1)
    x, y, _ = cert1.witness
    k_max = overflow.INT32_LIMIT // cert1.per_element  # 131071
    assert overflow.certify(8, 2, k_max).sound
    assert not overflow.certify(8, 2, k_max + 1).sound

    def run(k):
        aq = np.full((1, k), x, np.int8)
        bq = np.full((k, 1), y, np.int8)
        got = int(np.asarray(l2r_matmul_int_stacked(aq, bq, 8, 2))[0, 0])
        exact = int(x) * int(y) * k
        return got, exact

    got, exact = run(k_max)
    assert exact == cert1.per_element * k_max  # the bound is achieved...
    assert got == exact                        # ...and int32 holds there
    got, exact = run(k_max + 1)
    assert exact > overflow.INT32_LIMIT
    assert got != exact                        # one element beyond: wraps
    assert got == exact - 2**32                # deterministic int32 wrap


def test_dispatcher_guard_warns_by_default():
    from repro.kernels.l2r_gemm.ops import l2r_gemm
    aq = np.asarray(
        np.random.default_rng(0).integers(-100, 100, (2, 48)), np.int16)
    bq = np.asarray(
        np.random.default_rng(1).integers(-100, 100, (48, 3)), np.int16)
    overflow._WARNED.clear()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        out = l2r_gemm(aq, bq, n_bits=16, log2_radix=4)
    assert out.shape == (2, 3)  # mod-2^32 parity workloads keep running
    msgs = [w for w in rec
            if issubclass(w.category, overflow.AccumulatorOverflowWarning)]
    assert msgs and "OVERFLOWS int32" in str(msgs[0].message)


def test_dispatcher_guard_strict_rejects(monkeypatch):
    from repro.kernels.l2r_gemm.ops import l2r_gemm
    monkeypatch.setenv("L2R_CERTIFY", "strict")
    aq = np.zeros((2, 48), np.int16)
    bq = np.zeros((48, 3), np.int16)
    with pytest.raises(OverflowError, match=r"worst-case \|accumulator\|"):
        l2r_gemm(aq, bq, n_bits=16, log2_radix=4)
    # sound configs pass untouched in strict mode
    out = l2r_gemm(_i8((2, 8)), _i8((8, 3), 1), n_bits=8, log2_radix=2)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(l2r_matmul_int(_i8((2, 8)), _i8((8, 3), 1), 8, 2)))


def test_quantize_weights_guard_strict(monkeypatch):
    monkeypatch.setenv("L2R_CERTIFY", "strict")
    cfg = QuantConfig(n_bits=8, log2_radix=2)
    k_max = overflow.INT32_LIMIT // overflow.certify(8, 2, 1).per_element
    w = np.ones((k_max + 1, 2), np.float32)
    with pytest.raises(OverflowError, match="quantize_weights"):
        quantize_weights(w, cfg, prestack=True)
    # without the prestacked contraction cache there is no declared K
    quantize_weights(np.ones((8, 2), np.float32), cfg, prestack=True)


def test_registry_sweep_all_sound():
    rows = overflow.audit_registry()
    # 11 archs x (head, attention), and the three MoE archs' expert,
    # shared-expert and dense-layer down projections
    assert len(rows) == 31
    v2 = {r["site"]: r for r in rows if r["arch"] == "deepseek-v2-lite"}
    assert {s: v2[s]["k"] for s in ("head", "expert_down", "shared_down")} \
        == {"head": 2048, "expert_down": 1408, "shared_down": 2816}
    assert all(r["sound"] for r in rows), \
        [r for r in rows if not r["sound"]]


# ----------------------------------------------------- compiled-artifact pass
def _toy_step():
    def step(params, state):
        return state * params + 1.0
    return step


def test_donation_report_and_probe():
    step = _toy_step()
    p = jnp.float32(2.0)
    s = jnp.arange(4, dtype=jnp.float32)
    donated = jax.jit(step, donate_argnums=(1,)).lower(p, s).compile()
    rep = comp_audit.donation_report(donated)
    assert rep["n_aliases"] >= 1
    plain = jax.jit(step).lower(p, s).compile()
    assert comp_audit.donation_report(plain)["n_aliases"] == 0
    # dynamic probe: the donated buffer is actually dead after the call
    live = comp_audit.probe_donation(
        jax.jit(step, donate_argnums=(1,)), (p, jnp.arange(4.0)), (1,))
    assert live[1] is True
    live = comp_audit.probe_donation(
        jax.jit(step), (p, jnp.arange(4.0)), (1,))
    assert live[1] is False


@pytest.fixture(scope="module")
def prog_model():
    from repro.configs import get_smoke
    from repro.models.common import materialize
    from repro.models.transformer import lm_build
    from repro.serve.engine import prepare_params
    cfg = dataclasses.replace(get_smoke("smollm-135m"), l2r=QuantConfig())
    params = prepare_params(cfg, materialize(lm_build(cfg),
                                             jax.random.PRNGKey(0)))
    return cfg, params


def _requests(cfg, n=2, max_new=3, seed=0):
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, (int(L),)).astype(
                        np.int32),
                    max_new_tokens=max_new)
            for i, L in enumerate(rng.integers(3, 16, n))]


def test_gateway_audit_green(prog_model):
    from repro.serve import ServingGateway
    cfg, params = prog_model
    gw = ServingGateway(cfg, params, n_slots=2, max_len=32)
    gw.warmup()
    gw.run(_requests(cfg))
    rep = comp_audit.audit_gateway(gw)
    assert rep["ok"], rep["violations"]
    assert rep["aot_prefill_buckets"] == list(gw.buckets)
    assert rep["decode_donation"]["n_aliases"] >= 1


def test_batcher_audit_catches_undonated_state(prog_model):
    """The pre-PR 6 copy-per-step regression, deliberately seeded via
    donate_state=False: the auditor must flag it — and must pass the
    donated default."""
    from repro.serve import ContinuousBatcher
    cfg, params = prog_model

    good = ContinuousBatcher(cfg, params, n_slots=2, max_len=32)
    for r in _requests(cfg):
        good.submit(r)
    good.step()
    rep = comp_audit.audit_batcher(good)
    assert rep["ok"], rep["violations"]
    assert rep["donation"]["checked"] and rep["donation"]["n_dead"] > 0

    bad = ContinuousBatcher(cfg, params, n_slots=2, max_len=32,
                            donate_state=False)
    for r in _requests(cfg, seed=1):
        bad.submit(r)
    bad.step()
    rep = comp_audit.audit_batcher(bad)
    assert not rep["ok"]
    assert any("NOT donated" in v["reason"] for v in rep["violations"])
