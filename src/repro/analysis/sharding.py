"""Sharding auditor: collective-schedule linting for shard_mapped walks.

The sharded consensus walk is the layer where this repo's one real
numeric bug lived: PR 5's GSPMD float-reassociation, where interior
sharding hints on a replicated backbone made GSPMD repartition a float
contraction into partial sums joined by a float ``add`` all-reduce —
bit-parity silently gone.  The exactness pass (exactness.py) cannot see
that class at trace time: GSPMD inserts its collectives during SPMD
partitioning, after the jaxpr.  This pass closes the gap statically,
per registered entry with a :class:`ShardingContract`:

a) **collective schedule** — the traced walk must contain exactly the
   declared cross-shard reductions (the per-level pmax/pmin decision
   triples + the consensus psum) and nothing else; jaxpr-level data
   movers (``all_gather`` & co) are violations outright, and in the
   partitioned HLO any GSPMD-inserted ``all-gather``/reshard on a
   plane-stack operand breaks the K-never-sharded invariant;
b) **exact-reduction taint** — reusing exactness.py's taint walk (with
   the ``"deq"`` provenance extension: dequantized decision floats stay
   tracked), every cross-shard reduction reached by plane-derived
   values must be max/min/int-sum; a float ``psum``/add all-reduce on a
   tainted value is precisely the PR 5 bug class, caught at lint time;
c) **layout conformance** — the compiled module's propagated input
   shardings match the declared specs (RHS vocab-sharded over
   ``model``, LHS batch-sharded, K replicated).

Schedule-to-source matching rides on the named-collective tags
(core/policy.py ``COLL_TAG_*`` + the walk scope in core/progressive.py):
the scope names land in ``source_info.name_stack`` (jaxpr) and
``metadata op_name`` (HLO), so an all-reduce WITHOUT a declared tag was
inserted by the partitioner, not the walk.  On top of the verified
schedule, analysis/collective_cost.py prices the sync cost per
(entry x mesh) — see :func:`audit_sharding`'s ``with_cost``.
"""

from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Callable

import jax
import numpy as np
from jax.extend import core as jex_core

from repro.analysis import exactness
from repro.analysis.collective_cost import (CollectiveRecord,
                                            sync_cost_certificate)
from repro.analysis.exactness import ExactnessContract, Violation

__all__ = [
    "ReductionSpec",
    "ShardingContract",
    "ShardingReport",
    "audit_sharding",
    "audit_partitioned_hlo",
    "audit_sharded_registry",
]

#: value-preserving cross-shard reductions the schedule may declare
_REDUCE_PRIMS = {"psum", "pmax", "pmin"}

#: jaxpr-level collectives that MOVE data between shards: the declared
#: consensus schedule is reductions-only, so any of these on a walk
#: path breaks the K-never-sharded invariant at trace time already
_FORBIDDEN_PRIMS = {"all_gather", "all_to_all", "ppermute", "pshuffle",
                    "pgather"}

#: HLO op kinds a verified partitioned module must not contain (a
#: contract can narrow/widen this via ``forbidden``)
DEFAULT_FORBIDDEN_KINDS = ("all-gather", "all-to-all", "collective-permute",
                           "reduce-scatter")


@dataclasses.dataclass(frozen=True)
class ReductionSpec:
    """One declared cross-shard reduction: primitive, multiplicity per
    scope (per level-loop iteration, or per walk), and the named-scope
    tag its trace carries (core/policy.py ``COLL_TAG_*``)."""

    prim: str       # psum | pmax | pmin
    count: int = 1
    tag: str = ""


@dataclasses.dataclass(frozen=True)
class ShardingContract:
    """What a shard_mapped entry promises about its SPMD lowering.

    ``mesh_axes`` declares the audit mesh as ``(name, size)`` pairs;
    ``per_level`` / ``per_walk`` the exact reduction schedule inside /
    outside the level loop; ``in_specs`` the expected PartitionSpec
    entries per top-level argument (None = unchecked);
    ``max_collectives`` the static collective-count budget of the
    partitioned module (None = the declared schedule's static count —
    a new collective is a build failure either way)."""

    mesh_axes: tuple
    per_level: tuple = ()
    per_walk: tuple = ()
    in_specs: tuple = ()
    n_levels: int = 1
    max_collectives: int | None = None
    forbidden: tuple = DEFAULT_FORBIDDEN_KINDS
    allow_float_psum: bool = False

    @property
    def declared_static(self) -> int:
        """Static collective count of the declared schedule (each spec
        appears once in the loop body + once per per-walk firing)."""
        return (sum(s.count for s in self.per_level)
                + sum(s.count for s in self.per_walk))

    @property
    def budget(self) -> int:
        return (self.declared_static if self.max_collectives is None
                else self.max_collectives)

    @property
    def declared_tags(self) -> tuple:
        return tuple(sorted({s.tag for s in self.per_level + self.per_walk
                             if s.tag}))

    def build_mesh(self):
        shape = tuple(int(s) for _, s in self.mesh_axes)
        names = tuple(a for a, _ in self.mesh_axes)
        n = 1
        for s in shape:
            n *= s
        devs = np.array(jax.devices()[:n]).reshape(shape)
        return jax.sharding.Mesh(devs, names)


@dataclasses.dataclass
class ShardingReport:
    entry: str
    violations: list
    schedule: dict          # traced reductions: per_level / per_walk
    collectives: dict       # partitioned-HLO census + records
    layout: list            # per-arg conformance rows
    cost: dict | None = None

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_json(self) -> dict:
        return {
            "entry": self.entry, "ok": self.ok,
            "schedule": self.schedule,
            "collectives": self.collectives,
            "layout": self.layout,
            "cost": self.cost,
            "violations": [v.to_json() for v in self.violations],
        }


# ---------------------------------------------------- jaxpr schedule walk
class _ScheduleAuditor(exactness._Auditor):
    """exactness' taint walk + collective recording.

    Every psum/pmax/pmin is recorded with axes / dtype / loop depth /
    named-scope tag and the merged operand taint; jaxpr-level data
    movers and float psums over plane-derived values are violations.
    Exactness verdicts are muted (``flag`` is a no-op) — they belong to
    the exactness pass, which sweeps the same entries; this walk only
    borrows its propagation rules and the ``"deq"`` dequant provenance
    (see :meth:`dequant_taint`)."""

    def __init__(self, contract: ExactnessContract | None,
                 sharding: ShardingContract, entry: str):
        super().__init__(contract or ExactnessContract(), entry)
        self.s = sharding
        self.records: list[CollectiveRecord] = []
        self.schedule_violations: list[Violation] = []
        self._depth = 0

    def dequant_taint(self):
        return "deq"

    def flag(self, eqn, reason):
        pass  # exactness rules are the exactness pass's job

    def _sflag(self, prim: str, reason: str, detail: str = ""):
        self.schedule_violations.append(Violation(
            entry=self.entry, primitive=prim, reason=reason, detail=detail))

    def _record(self, eqn, in_t):
        prim = eqn.primitive.name
        axes = eqn.params.get("axes") or ()
        if not isinstance(axes, tuple):
            axes = (axes,)
        axes = tuple(a for a in axes if isinstance(a, str))
        var = next((v for v in eqn.invars
                    if not isinstance(v, jex_core.Literal)), None)
        dt = exactness._aval_dtype(var.aval) if var is not None else None
        shape = tuple(getattr(var.aval, "shape", ())) if var is not None \
            else ()
        tag = ""
        for seg in re.split(r"[/()]", str(eqn.source_info.name_stack)):
            if seg.startswith("l2r_coll"):
                tag = seg
        taint = None
        for t in in_t:
            taint = exactness._merge(taint, t)
        self.records.append(CollectiveRecord(
            prim=prim, axes=axes,
            dtype=str(np.dtype(dt)) if dt is not None else "float32",
            shape=shape, in_loop=self._depth > 0, tag=tag, taint=taint))
        if (prim == "psum" and exactness._is_float(dt)
                and taint is not None and not self.s.allow_float_psum):
            self._sflag(prim,
                        "float cross-shard sum over a plane-derived value: "
                        "reduction order reassociates the float sum (the "
                        "PR 5 bug class) — cross-shard reductions on the "
                        "exact path must be max/min/int-sum",
                        detail=f"dtype={np.dtype(dt)} axes={axes} "
                               f"taint={taint}")

    def eqn_taint(self, eqn, in_t, record):
        prim = eqn.primitive.name
        n_out = len(eqn.outvars)
        if prim in _REDUCE_PRIMS:
            if record:
                self._record(eqn, in_t)
            # value-preserving reductions: taint passes through 1:1
            out = list(in_t)[:n_out]
            return out + [None] * (n_out - len(out))
        if prim in _FORBIDDEN_PRIMS:
            if record:
                self._sflag(prim,
                            f"cross-shard data mover `{prim}` in the walk "
                            "jaxpr: the declared schedule is reductions-"
                            "only (K is never sharded, plane stacks are "
                            "never gathered)")
            return [None] * n_out
        if prim in ("scan", "while"):
            self._depth += 1
            try:
                return super().eqn_taint(eqn, in_t, record)
            finally:
                self._depth -= 1
        out = super().eqn_taint(eqn, in_t, record)
        # "deq" provenance: dequantized floats keep flowing through
        # float ops (the base lattice drops them — exactness only cares
        # up to the dequant exit; the reduction-taint rule cares beyond)
        if "deq" in in_t and "int" not in in_t and "f32exact" not in in_t:
            out = ["deq" if t is None and exactness._is_float(
                       exactness._aval_dtype(v.aval)) else t
                   for v, t in zip(eqn.outvars, out)]
        return out


def _check_schedule(records: list, contract: ShardingContract, entry: str,
                    violations: list):
    for scope, specs in (("per-level", contract.per_level),
                         ("per-walk", contract.per_walk)):
        recs = [r for r in records if r.in_loop == (scope == "per-level")]
        want: Counter = Counter()
        for s in specs:
            want[(s.prim, s.tag)] += s.count
        got = Counter((r.prim, r.tag) for r in recs)
        for key in sorted(set(want) | set(got)):
            if want[key] == got[key]:
                continue
            prim, tag = key
            violations.append(Violation(
                entry=entry, primitive=prim,
                reason=f"{scope} schedule mismatch: traced {got[key]} x "
                       f"{prim}[{tag or 'untagged'}], declared {want[key]}",
                detail=f"scope={scope}"))


# ------------------------------------------------- partitioned-HLO checks
def audit_partitioned_hlo(text: str, contract: ShardingContract,
                          entry: str = "<hlo>") -> tuple[list, list]:
    """Check the SPMD-partitioned module against the contract.

    Returns ``(violations, collective_records)``.  Three rules:
    forbidden kinds (any ``all-gather``/reshard means GSPMD moved a
    sharded operand — the K-never-sharded invariant is gone), float
    ``add`` all-reduces (cross-shard float-sum reassociation, the PR 5
    class), and untagged all-reduces (no declared ``l2r_coll`` tag in
    the op_name metadata: the partitioner added a collective the
    schedule never declared).  Plus the static count budget."""
    from repro.launch import hlo_analysis

    recs = hlo_analysis.collective_records(text)
    violations: list[Violation] = []
    tags = contract.declared_tags
    for r in recs:
        where = f"{r['computation']}::{r['name']}"
        if r["kind"] in contract.forbidden:
            reason = (f"GSPMD-inserted {r['kind']} in the partitioned "
                      "module: a sharded operand is being moved between "
                      "shards")
            if r["kind"] == "all-gather":
                reason += (" — a plane-stack/K operand was resharded "
                           "(the K-never-sharded invariant is broken)")
            violations.append(Violation(entry, r["kind"], reason, where))
            continue
        if r["kind"] != "all-reduce":
            continue
        if (r["dtype"].startswith(("f", "bf")) and r["reduce_op"] == "add"
                and not contract.allow_float_psum):
            violations.append(Violation(
                entry, "all-reduce",
                f"float add all-reduce ({r['dtype']}): a partitioned "
                "float contraction's partial sums are reassociated "
                "across shards (the PR 5 reassociation bug class)",
                where))
        elif tags and not any(t in r["op_name"] for t in tags):
            violations.append(Violation(
                entry, "all-reduce",
                f"{r['dtype']} {r['reduce_op'] or '?'} all-reduce without "
                "a declared l2r_coll tag: the partitioner added a "
                "collective the schedule never declared "
                f"(op_name={r['op_name'] or '<none>'!r})", where))
    if len(recs) > contract.budget:
        violations.append(Violation(
            entry, "module",
            f"collective-count budget exceeded: {len(recs)} static "
            f"collectives in the partitioned module, budget "
            f"{contract.budget} — a new collective entered the schedule",
            detail=",".join(sorted({r['kind'] for r in recs}))))
    return violations, recs


# ----------------------------------------------------- layout conformance
def _audit_layout(compiled, args, contract: ShardingContract, mesh,
                  entry: str) -> tuple[list, list]:
    from jax.sharding import NamedSharding, PartitionSpec

    violations: list[Violation] = []
    rows: list[dict] = []
    if not contract.in_specs:
        return violations, rows
    try:
        shardings = compiled.input_shardings[0]
    except Exception:  # pragma: no cover - old jax layouts
        return violations, rows
    for i, spec in enumerate(contract.in_specs):
        if spec is None or i >= len(shardings) or i >= len(args):
            continue
        expected = NamedSharding(mesh, PartitionSpec(*spec))
        ok = bool(shardings[i].is_equivalent_to(expected, np.ndim(args[i])))
        rows.append({"arg": i, "expected": str(expected.spec), "ok": ok})
        if not ok:
            violations.append(Violation(
                entry, "input-sharding",
                f"arg {i}: propagated sharding {shardings[i]} does not "
                f"match the declared spec {expected.spec}",
                detail=f"arg={i}"))
    return violations, rows


# ------------------------------------------------------------- public API
def audit_sharding(fn: Callable, args: tuple, sharding: ShardingContract,
                   contract: ExactnessContract | None = None,
                   entry: str = "", *,
                   with_cost: bool = True) -> ShardingReport:
    """Audit one shard_mapped entry: trace, partition, certify.

    Runs the three checks of the module docstring — traced schedule +
    reduction taint (jaxpr), collective census vs contract (partitioned
    HLO), input-sharding conformance — and, with ``with_cost``, prices
    the verified schedule into the sync-cost certificate."""
    name = entry or getattr(fn, "__name__", "<fn>")
    # a jitted entry (the serving steps) is audited as it is compiled:
    # trace its body, lower it with its own jit options
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    closed = jax.make_jaxpr(fn.__wrapped__ if fn is jitted else fn)(*args)
    aud = _ScheduleAuditor(contract, sharding, name)
    seeds = ["int" if exactness._is_int(exactness._aval_dtype(v.aval))
             else None for v in closed.jaxpr.invars]
    aud.propagate(closed.jaxpr, seeds, record=True)
    violations = list(aud.schedule_violations)
    _check_schedule(aud.records, sharding, name, violations)

    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    hlo_v, hlo_recs = audit_partitioned_hlo(text, sharding, name)
    violations += hlo_v

    mesh = sharding.build_mesh()
    lay_v, lay_rows = _audit_layout(compiled, args, sharding, mesh, name)
    violations += lay_v

    census: dict[str, int] = {}
    for r in hlo_recs:
        census[r["kind"]] = census.get(r["kind"], 0) + 1
    cost = None
    if with_cost:
        cost = sync_cost_certificate(aud.records, sharding.mesh_axes,
                                     sharding.n_levels, hlo_text=text)
    return ShardingReport(
        entry=name, violations=violations,
        schedule={
            "per_level": [r.to_json() for r in aud.records if r.in_loop],
            "per_walk": [r.to_json() for r in aud.records if not r.in_loop],
        },
        collectives={"census": census, "records": hlo_recs},
        layout=lay_rows, cost=cost)


def audit_sharded_registry(entries=None, *, allow_skips: bool = False,
                           with_cost: bool = True) -> list[dict]:
    """Sweep every registered entry carrying a :class:`ShardingContract`.

    A skipped entry (too few devices) is a VIOLATION unless
    ``allow_skips``: the CI lint job runs under a virtual-8-device env
    (launch/mesh.py:virtual_device_env) precisely so the sharded
    entries cannot silently pass unaudited."""
    from repro.analysis import registry

    rows = []
    for e in (entries if entries is not None else registry.iter_entries()):
        if getattr(e, "sharding", None) is None:
            continue
        row: dict = {"entry": e.name, "tags": list(e.tags)}
        if e.skip:
            if allow_skips:
                row.update(status="skip", reason=e.skip)
            else:
                row.update(status="violation", ok=False, violations=[
                    Violation(
                        entry=e.name, primitive="registry",
                        reason=f"registered sharded entry SKIPPED "
                               f"({e.skip}) — the audit must not silently "
                               "pass; run under XLA_FLAGS="
                               "--xla_force_host_platform_device_count=8 "
                               "(launch.mesh.virtual_device_env) or pass "
                               "allow_skips explicitly").to_json()])
            rows.append(row)
            continue
        fn, args = e.build()
        rep = audit_sharding(fn, args, e.sharding, e.contract,
                             entry=e.name, with_cost=with_cost)
        row.update(status="ok" if rep.ok else "violation", **rep.to_json())
        rows.append(row)
    return rows
