"""Sharding auditor: collective-schedule linting + sync-cost certs.

Four families:
  * positive — the registered sharded entries verify end-to-end under a
    virtual 8-device mesh: exactly the declared per-level reductions,
    zero GSPMD resharding, conformant layouts, priced certificates;
  * negative (synthetic HLO) — injected all-gather on a plane stack,
    float add all-reduce, resharded-K reduce-scatter, untagged
    collective: each fails `audit_partitioned_hlo` on its own;
  * negative (jaxpr) — float psum over a dequantized (plane-derived)
    value, jaxpr-level data movers, schedule-count mismatches: caught
    at trace time, before any compile;
  * PR 5 regression — the replicated-backbone decode trace with the
    interior sharding hints left ON reproduces the original GSPMD
    float-reassociation bug shape, and the auditor flags it; the same
    trace with hints off verifies clean.

Multi-device cases run in a subprocess with 8 virtual host-platform
devices (the flag must be set before jax initializes); everything else
runs in-process on whatever this host has (a 1x1 mesh traces fine).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis.collective_cost import (CollectiveRecord,
                                            sync_cost_certificate)
from repro.analysis.registry import ExactEntry, iter_entries
from repro.analysis.sharding import (ReductionSpec, ShardingContract,
                                     audit_partitioned_hlo,
                                     audit_sharded_registry, audit_sharding)
from repro.launch.mesh import virtual_device_env

pytestmark = pytest.mark.analysis

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subproc(script: str, timeout: int = 900):
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=_REPO, env=virtual_device_env(8), timeout=timeout)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    return out.stdout


# ------------------------------------------------- positive: the registry
@pytest.mark.sharded
def test_registered_consensus_entries_verify():
    """Both sharded consensus entries pass the full audit on a virtual
    8-device host: declared schedule exactly, zero all-gathers, priced
    sync-cost certificate with the sync-every-k table."""
    _run_subproc(textwrap.dedent("""
        from repro.analysis.registry import iter_entries
        from repro.analysis.sharding import audit_sharded_registry

        entries = [e for e in iter_entries(("head",))
                   if e.sharding is not None]
        assert sorted(e.name for e in entries) == [
            "head/sharded-consensus", "head/sharded-consensus-while"]
        rows = {r["entry"]: r for r in audit_sharded_registry(entries)}

        # XLA's all-reduce combiner merges independent same-combiner
        # reductions into one tuple all-reduce: the 4 per-level pmax
        # land as 2 ops (one per dependency layer), so the census counts
        # ops, while n_coll still prices the traced schedule
        for name, census, n_coll in (
                ("head/sharded-consensus", {"all-reduce": 5}, 37),
                ("head/sharded-consensus-while", {"all-reduce": 6}, 44)):
            r = rows[name]
            assert r["status"] == "ok", r["violations"]
            # the partitioned module: reductions only, nothing moved
            assert r["collectives"]["census"] == census, r["collectives"]
            # the traced per-level schedule: the 4-pmax/1-pmin decision
            # triple (+ the consensus psum on the early-exit walk)
            prims = sorted(rec["prim"] for rec in r["schedule"]["per_level"])
            want = ["pmax"] * 4 + ["pmin"]
            if name.endswith("-while"):
                want = sorted(want + ["psum"])
            assert prims == sorted(want), prims
            assert all(rec["tag"].startswith("l2r_coll")
                       for rec in r["schedule"]["per_level"])
            # layout conformance rows all hold
            assert r["layout"] and all(row["ok"] for row in r["layout"])
            # the certificate prices the declared schedule
            cert = r["cost"]
            assert cert["collectives_per_walk"] == n_coll, cert
            assert cert["wire_bytes_per_walk"] > 0
            ks = cert["sync_every_k"]
            assert [e["k"] for e in ks] == [1, 2, 4, 8]
            assert ks[0]["savings_frac"] == 0.0
            savings = [e["savings_frac"] for e in ks]
            assert savings == sorted(savings) and savings[-1] > 0.5, savings
            assert 0.0 < cert["collective_share"] < 1.0, cert
        print("CONSENSUS-AUDIT-OK")
    """))


def test_every_sharded_entry_declares_a_contract():
    """Registry consistency: the `sharded` tag and a ShardingContract
    come together — a sharded entry with no contract is exactly the
    silent coverage gap the auditor exists to close."""
    entries = [e for e in iter_entries() if "sharded" in e.tags]
    assert entries, "registry lost its sharded entries"
    for e in entries:
        assert e.sharding is not None, e.name
        assert e.sharding.budget >= 0
        assert dict(e.sharding.mesh_axes).keys() == {"data", "model"}
        # sharding-only entries (contract=None) must still be swept by
        # SOME pass — the sharding one
        if e.contract is None:
            assert e.sharding is not None


# -------------------------------------------- negative: synthetic SPMD HLO
_REGIONS = textwrap.dedent("""\
    %region_add (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %r = f32[] add(f32[] %a, f32[] %b)
    }

    %region_max (c: f32[], d: f32[]) -> f32[] {
      %c = f32[] parameter(0)
      %d = f32[] parameter(1)
      ROOT %m = f32[] maximum(f32[] %c, f32[] %d)
    }

    %region_min (e: s32[], f: s32[]) -> s32[] {
      %e = s32[] parameter(0)
      %f = s32[] parameter(1)
      ROOT %n = s32[] minimum(s32[] %e, s32[] %f)
    }
""")


def _module(*body_lines: str) -> str:
    return ("HloModule jit_walk, num_partitions=8\n\n" + _REGIONS
            + "\nENTRY %main.42 (p0: f32[8,16]) -> f32[8,16] {\n"
            + "  %p0 = f32[8,16]{1,0} parameter(0)\n"
            + "".join(f"  {ln}\n" for ln in body_lines)
            + "}\n")


def _contract(**kw) -> ShardingContract:
    from repro.core.policy import COLL_TAG_MAX, COLL_TAG_MIN
    kw.setdefault("mesh_axes", (("data", 2), ("model", 4)))
    kw.setdefault("per_level", (ReductionSpec("pmax", 4, COLL_TAG_MAX),
                                ReductionSpec("pmin", 1, COLL_TAG_MIN)))
    return ShardingContract(**kw)


def test_hlo_injected_all_gather_fails():
    """An all-gather in the partitioned module means GSPMD moved a
    sharded operand — on a plane-stack input that is the K-never-sharded
    invariant breaking."""
    text = _module(
        'ROOT %all-gather.1 = s8[8,7,16,128]{3,2,1,0} all-gather('
        's8[8,7,16,16]{3,2,1,0} %p0), channel_id=1, '
        'replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={3}, '
        'metadata={op_name="jit(walk)/plane_stack_gather"}')
    violations, recs = audit_partitioned_hlo(text, _contract(), "neg")
    assert len(recs) == 1 and recs[0]["kind"] == "all-gather"
    assert any("K-never-sharded" in v.reason for v in violations)


def test_hlo_float_add_all_reduce_fails():
    """A float `add` all-reduce is the PR 5 reassociation class: a
    partitioned float contraction's partial sums joined across shards."""
    text = _module(
        'ROOT %all-reduce.9 = f32[8,16]{1,0} all-reduce('
        'f32[8,16]{1,0} %p0), channel_id=2, '
        'replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%region_add, '
        'metadata={op_name="jit(walk)/dot_general"}')
    violations, recs = audit_partitioned_hlo(text, _contract(), "neg")
    assert recs[0]["reduce_op"] == "add" and recs[0]["dtype"] == "f32"
    assert any("reassociated" in v.reason for v in violations)


def test_hlo_resharded_k_reduce_scatter_fails():
    """A reduce-scatter means the contraction axis was sharded and its
    partial results redistributed — forbidden outright."""
    text = _module(
        'ROOT %reduce-scatter.3 = f32[8,2]{1,0} reduce-scatter('
        'f32[8,16]{1,0} %p0), channel_id=3, '
        'replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={1}, '
        'to_apply=%region_add')
    violations, _ = audit_partitioned_hlo(text, _contract(), "neg")
    assert any(v.primitive == "reduce-scatter" for v in violations)


def test_hlo_untagged_all_reduce_fails():
    """An all-reduce whose op_name carries none of the declared
    l2r_coll tags was inserted by the partitioner, not the walk."""
    text = _module(
        'ROOT %all-reduce.4 = f32[8,16]{1,0} all-reduce('
        'f32[8,16]{1,0} %p0), replica_groups={{0,1,2,3},{4,5,6,7}}, '
        'to_apply=%region_max, metadata={op_name="jit(walk)/some_max"}')
    violations, _ = audit_partitioned_hlo(text, _contract(), "neg")
    assert any("never declared" in v.reason for v in violations)


def test_hlo_declared_tagged_schedule_passes():
    """The clean shape: tagged max/min all-reduces within budget."""
    text = _module(
        '%ar.1 = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %p0), '
        'replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%region_max, '
        'metadata={op_name="jit(walk)/l2r_coll_max/pmax"}',
        'ROOT %all-reduce.2 = f32[8,16]{1,0} all-reduce('
        'f32[8,16]{1,0} %ar.1), replica_groups={{0,1,2,3},{4,5,6,7}}, '
        'to_apply=%region_min, '
        'metadata={op_name="jit(walk)/l2r_coll_min/pmin"}')
    violations, recs = audit_partitioned_hlo(text, _contract(), "pos")
    assert len(recs) == 2
    assert violations == [], [v.reason for v in violations]


def test_hlo_budget_overrun_fails():
    """More static collectives than the contract budget — even if each
    one individually looks legitimate — is a build failure."""
    line = ('%ar.@I@ = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %p0), '
            'replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%region_max, '
            'metadata={op_name="jit(walk)/l2r_coll_max/pmax"}')
    contract = _contract(max_collectives=2)
    text = _module(*[line.replace("@I@", str(i)) for i in range(3)])
    violations, _ = audit_partitioned_hlo(text, contract, "neg")
    assert any("budget exceeded" in v.reason for v in violations)


# ------------------------------------------------- negative: jaxpr checks
def _mesh_1x1() -> Mesh:
    devs = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(devs, ("data", "model"))


def test_float_psum_on_dequantized_value_flagged():
    """The PR 5 class at trace time: an int8 contraction dequantized to
    f32 then summed across shards — the cross-shard add reassociates the
    float sum, so the `deq` provenance taint must flag the psum."""
    mesh = _mesh_1x1()

    def body(aq, bq):
        acc = jax.lax.dot_general(aq.astype(jnp.int32),
                                  bq.astype(jnp.int32),
                                  (((1,), (0,)), ((), ())))
        deq = acc.astype(jnp.float32) * np.float32(0.5)
        return jax.lax.psum(deq, "model")

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                       check_vma=False)
    aq = np.ones((2, 4), np.int8)
    bq = np.ones((4, 3), np.int8)
    contract = ShardingContract(mesh_axes=(("data", 1), ("model", 1)))
    rep = audit_sharding(fn, (aq, bq), contract, entry="neg/float-psum",
                         with_cost=False)
    assert not rep.ok
    assert any("reassociates" in v.reason and v.primitive == "psum"
               for v in rep.violations), [v.reason for v in rep.violations]


def test_int_psum_on_quantized_value_passes_taint():
    """The allowed shape: the cross-shard sum happens on the int32
    accumulator (order-exact), dequantization only after."""
    mesh = _mesh_1x1()

    def body(aq, bq):
        acc = jax.lax.dot_general(aq.astype(jnp.int32),
                                  bq.astype(jnp.int32),
                                  (((1,), (0,)), ((), ())))
        return jax.lax.psum(acc, "model").astype(jnp.float32)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                       check_vma=False)
    contract = ShardingContract(
        mesh_axes=(("data", 1), ("model", 1)),
        per_walk=(ReductionSpec("psum", 1),))
    rep = audit_sharding(fn, (np.ones((2, 4), np.int8),
                              np.ones((4, 3), np.int8)),
                         contract, entry="pos/int-psum", with_cost=False)
    assert rep.ok, [v.reason for v in rep.violations]


def test_jaxpr_all_gather_is_forbidden():
    mesh = _mesh_1x1()

    def body(x):
        return jax.lax.all_gather(x, "model")

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P("model"),
                       check_vma=False)
    contract = ShardingContract(mesh_axes=(("data", 1), ("model", 1)))
    rep = audit_sharding(fn, (np.ones((2, 4), np.int8),), contract,
                         entry="neg/all-gather", with_cost=False)
    assert any(v.primitive == "all_gather"
               and "reductions-only" in v.reason for v in rep.violations)


def test_schedule_count_mismatch_flagged():
    """Declaring 2 pmax but tracing 1 (or vice versa) is a mismatch —
    the contract pins the schedule exactly, both directions."""
    mesh = _mesh_1x1()

    def body(x):
        return jax.lax.pmax(x, "model")

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                       check_vma=False)
    contract = ShardingContract(mesh_axes=(("data", 1), ("model", 1)),
                                per_walk=(ReductionSpec("pmax", 2),))
    rep = audit_sharding(fn, (np.ones((2,), np.float32),), contract,
                         entry="neg/mismatch", with_cost=False)
    assert any("schedule mismatch" in v.reason and "traced 1 x pmax" in
               v.reason for v in rep.violations), \
        [v.reason for v in rep.violations]


# -------------------------------------------------- PR 5 regression shape
@pytest.mark.slow
@pytest.mark.sharded
def test_pr5_hints_enabled_backbone_is_flagged():
    """The original bug, reproduced on purpose: interior sharding hints
    left ON over a replicated backbone make GSPMD repartition float
    contractions — partial sums joined by float add all-reduces, plus a
    storm of gathers.  The auditor must flag that trace; the fixed
    trace (backbone_hints=False, the registered entry) must verify."""
    _run_subproc(textwrap.dedent("""
        import dataclasses

        import jax
        import numpy as np

        from repro.analysis.registry import (_consensus_contract,
                                             _local_mesh, _mesh_shape)
        from repro.analysis.sharding import audit_sharding
        from repro.configs import get_smoke
        from repro.core.quant import QuantConfig
        from repro.models.common import materialize
        from repro.models.transformer import init_lm_state, lm_build
        from repro.serve.engine import make_decode_step, prepare_params
        from repro.sharding import ctx

        data, model = _mesh_shape()
        mesh = _local_mesh(data, model)
        cfg = dataclasses.replace(get_smoke("smollm-135m"),
                                  l2r=QuantConfig())
        params = prepare_params(cfg, materialize(lm_build(cfg),
                                                 jax.random.PRNGKey(0)))
        contract = dataclasses.replace(
            _consensus_contract(data, model, False), in_specs=())
        batch = data * 2
        state = init_lm_state(cfg, batch, 32)
        toks = np.zeros((batch, 1), np.int32)

        # the bug shape: hints ON, backbone replicated
        step = make_decode_step(cfg, progressive=True,
                                backbone_hints=True, mesh=mesh)
        ctx.set_mesh(mesh)
        try:
            rep = audit_sharding(step, (params, state, toks), contract,
                                 entry="pr5-regression", with_cost=False)
        finally:
            ctx.set_mesh(None)
        assert not rep.ok
        reasons = " | ".join(v.reason for v in rep.violations)
        assert "reassociated" in reasons, reasons
        assert any(v.primitive == "all-gather" for v in rep.violations), \\
            reasons
        assert "budget exceeded" in reasons, reasons

        # the fix: hints off — same trace, clean schedule
        step_ok = make_decode_step(cfg, progressive=True,
                                   backbone_hints=False, mesh=mesh)
        rep_ok = audit_sharding(step_ok, (params, state, toks), contract,
                                entry="pr5-fixed", with_cost=False)
        assert rep_ok.ok, [v.reason for v in rep_ok.violations]
        print("PR5-REGRESSION-OK")
    """))


# ------------------------------------------------ skips must fail loudly
def test_skipped_registry_entry_fails_loudly():
    """A registered sharded entry that cannot run is a VIOLATION row by
    default — `skipped` must never read as `passed` in CI; only an
    explicit allow_skips downgrades it."""
    fake = ExactEntry(
        name="fake/sharded", build=lambda: (None, ()),
        tags=("sharded",), skip="needs >= 2 devices (have 1)",
        sharding=ShardingContract(mesh_axes=(("data", 2), ("model", 4))))
    rows = audit_sharded_registry([fake])
    assert rows[0]["status"] == "violation"
    assert "SKIPPED" in rows[0]["violations"][0]["reason"]
    assert "xla_force_host_platform_device_count" in \
        rows[0]["violations"][0]["reason"]

    rows = audit_sharded_registry([fake], allow_skips=True)
    assert rows[0]["status"] == "skip"
    assert rows[0]["reason"] == "needs >= 2 devices (have 1)"


def test_lint_cli_sharding_flag(tmp_path):
    """CLI wiring: --sharding adds the sharding section to the JSON
    report; --allow-skips keeps small hosts green."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "l2r_lint", os.path.join(_REPO, "tools", "l2r_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = tmp_path / "report.json"
    rc = mod.main(["--sharding", "--allow-skips", "--skip-compiled",
                   "--tags", "cache", "--json", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert "sharding" in report
    assert [r["entry"] for r in report["sharding"]] == \
        ["cache/sharded-weights"]
    assert report["sharding"][0]["status"] in ("ok", "skip")


# ------------------------------------------------------ sync-cost pricing
def test_sync_cost_certificate_pricing():
    """Hand-built schedule: counts, ring wire bytes and the
    sync-every-k table are exactly the closed-form values."""
    rec = lambda prim, in_loop, shape=(4,): CollectiveRecord(
        prim=prim, axes=("model",), dtype="float32", shape=shape,
        in_loop=in_loop, tag="l2r_coll_max")
    records = [rec("pmax", True), rec("pmax", True), rec("pmin", True),
               rec("pmax", False)]
    cert = sync_cost_certificate(records, (("data", 2), ("model", 4)),
                                 n_levels=7)
    assert cert["chips"] == 8 and cert["n_levels"] == 7
    assert cert["per_level"]["count"] == 3
    assert cert["per_walk"]["count"] == 1
    assert cert["collectives_per_walk"] == 7 * 3 + 1
    # ring all-reduce over the 4-wide model axis: 2*(4-1)/4 * 16 bytes
    per_red = 2 * 3 / 4 * 16
    assert cert["per_level"]["wire_bytes"] == pytest.approx(3 * per_red)
    assert cert["wire_bytes_per_walk"] == pytest.approx(7 * 3 * per_red
                                                        + per_red)
    ks = {e["k"]: e for e in cert["sync_every_k"]}
    assert ks[1]["sync_levels"] == 7 and ks[1]["savings_frac"] == 0.0
    assert ks[2]["sync_levels"] == 4   # ceil(7/2)
    assert ks[4]["sync_levels"] == 2
    assert ks[8]["sync_levels"] == 1
    assert ks[8]["collectives"] == 3 + 1
    savings = [e["savings_frac"] for e in cert["sync_every_k"]]
    assert savings == sorted(savings)


def test_sync_cost_certificate_axis_of_one_is_free():
    """A reduction over a 1-wide axis moves nothing — the certificate
    prices it at zero wire bytes (matters for 2-device data=1 meshes)."""
    records = [CollectiveRecord(prim="psum", axes=("data",),
                                dtype="int32", shape=(), in_loop=True)]
    cert = sync_cost_certificate(records, (("data", 1), ("model", 2)),
                                 n_levels=3)
    assert cert["collectives_per_walk"] == 3
    assert cert["wire_bytes_per_walk"] == 0.0
    assert cert["collective_s"] == 0.0
    assert all(e["savings_frac"] == 0.0 for e in cert["sync_every_k"])
