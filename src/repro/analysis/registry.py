"""Registry of claimed-exact entry points.

The exactness audit (analysis/exactness.py) is only as good as its
coverage: a new schedule that never declares itself is never linted.
This registry is the declaration point — every walk that claims the
repo's bit-exactness contract registers an :class:`ExactEntry` binding

* a **build** thunk returning ``(fn, args)`` — a traceable callable and
  small representative operands (tracing is shape-driven, so tiny shapes
  certify the same graph structure the production shapes run),
* an :class:`~repro.analysis.exactness.ExactnessContract` describing
  what the entry promises (digit config, contraction length, whether
  the guarded f32 fast path may appear, taint vs kernel-int mode).

``tools/l2r_lint.py`` runs every registered entry through all passes;
adding a schedule without registering it here is the reviewable gap the
ROADMAP's invariant-registry section calls out.

Out-of-tree schedules register with::

    from repro.analysis import registry
    registry.register(registry.ExactEntry(
        name="gemm/my-schedule/jnp",
        build=lambda: (my_walk_fn, (aq, bq)),
        contract=ExactnessContract(n_bits=8, log2_radix=2, k=K),
    ))

shard_mapped entries additionally declare a
:class:`~repro.analysis.sharding.ShardingContract` (mesh shape, the
exact per-level/per-walk reduction schedule with its ``l2r_coll`` tags,
expected input PartitionSpecs, the static collective-count budget) —
the sharding pass lowers them under the declared mesh and verifies the
partitioned module.  ``contract=None`` marks a sharding-only entry (a
full-model trace whose backbone is not itself a claimed-exact walk);
the exactness/overflow passes skip those.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import numpy as np

from repro.analysis.exactness import ExactnessContract

__all__ = ["ExactEntry", "register", "iter_entries", "default_entries"]


@dataclasses.dataclass(frozen=True)
class ExactEntry:
    name: str
    build: Callable[[], tuple]  # () -> (fn, args)
    contract: ExactnessContract | None = None  # None: sharding-only entry
    tags: tuple = ()
    skip: str | None = None  # present-but-unavailable (e.g. needs devices)
    sharding: object | None = None  # ShardingContract for shard_mapped entries


_EXTRA: list[ExactEntry] = []


def register(entry: ExactEntry) -> ExactEntry:
    """Declare an additional claimed-exact entry point (idempotent per
    name: re-registration replaces)."""
    _EXTRA[:] = [e for e in _EXTRA if e.name != entry.name]
    _EXTRA.append(entry)
    return entry


# ------------------------------------------------------------- builders
def _gemm_operands(m=4, k=24, n=16, seed=0):
    rng = np.random.default_rng(seed)
    aq = rng.integers(-128, 128, (m, k)).astype(np.int8)
    bq = rng.integers(-128, 128, (k, n)).astype(np.int8)
    return aq, bq


def _attn_operands(b=1, q=2, kv=1, g=2, dh=8, s=5, seed=1):
    rng = np.random.default_rng(seed)
    qq = rng.integers(-128, 128, (b, q, kv, g, dh)).astype(np.int8)
    kq = rng.integers(-128, 128, (b, s, kv, dh)).astype(np.int8)
    return qq, kq


def _head_operands(m=4, k=16, n=12, seed=2):
    rng = np.random.default_rng(seed)
    xq = rng.integers(-128, 128, (m, k)).astype(np.int8)
    wq = rng.integers(-128, 128, (k, n)).astype(np.int8)
    xs = np.abs(rng.standard_normal((m, 1))).astype(np.float32) + 0.1
    ws = np.abs(rng.standard_normal((1, n))).astype(np.float32) + 0.1
    return xq, wq, xs, ws


def _gemm_entry(schedule: str, backend: str, early_exit: bool = False,
                levels: int | None = None, mode: str = "taint"):
    name = f"gemm/{schedule}{'-while' if early_exit else ''}/{backend}"
    if levels is not None:
        name += f"/levels-{levels}"

    def build():
        from repro.kernels.l2r_gemm.ops import l2r_gemm
        aq, bq = _gemm_operands()
        fn = functools.partial(l2r_gemm, n_bits=8, log2_radix=2,
                               levels=levels, schedule=schedule,
                               backend=backend, early_exit=early_exit)
        return fn, (aq, bq)

    return ExactEntry(
        name=name, build=build, tags=("gemm", backend),
        contract=ExactnessContract(n_bits=8, log2_radix=2, k=24,
                                   levels=levels, mode=mode))


def _experts_entry():
    """The held experts' GEMM: the stacked schedule vmapped over an
    expert axis, one weight per expert (models/moe.py:_expert_ffn)."""
    def build():
        from repro.kernels.l2r_gemm.ops import l2r_gemm
        ops = [_gemm_operands(seed=s) for s in (3, 4)]
        aq, bq = (np.stack(x) for x in zip(*ops))
        fn = jax.vmap(functools.partial(l2r_gemm, n_bits=8, log2_radix=2,
                                        backend="jnp", name="experts"))
        return fn, (aq, bq)

    return ExactEntry(
        name="gemm/stacked-experts/jnp", build=build, tags=("gemm", "jnp"),
        contract=ExactnessContract(n_bits=8, log2_radix=2, k=24))


def _attn_entry(kind: str):
    def build():
        from repro.core import l2r_attention as la
        fn = {"stacked": la.attn_scores_stacked,
              "streaming-scan": la.attn_scores_streaming_scan,
              "streaming-while": la.attn_scores_streaming_while}[kind]
        return fn, _attn_operands()

    return ExactEntry(
        name=f"attn/{kind}", build=build, tags=("attention",),
        contract=ExactnessContract(n_bits=8, log2_radix=2, k=8))


def _head_entry(early_exit: bool):
    def build():
        from repro.core.progressive import streaming_argmax
        fn = functools.partial(streaming_argmax, early_exit=early_exit)
        return fn, _head_operands()

    return ExactEntry(
        name=f"head/streaming-{'while' if early_exit else 'scan'}",
        build=build, tags=("head",),
        contract=ExactnessContract(n_bits=8, log2_radix=2, k=16))


def _mesh_shape() -> tuple[int, int]:
    """(data, model) of the audit mesh this host can carry — the same
    adaptive split every sharded builder below uses, so build and
    contract always agree."""
    n_dev = len(jax.devices())
    model = 4 if n_dev % 4 == 0 and n_dev > 4 else 2
    return max(n_dev // model, 1), model


def _local_mesh(data: int, model: int):
    from jax.sharding import Mesh
    devs = np.array(jax.devices())[:data * model]
    return Mesh(devs.reshape(data, model), ("data", "model"))


def _consensus_contract(data: int, model: int, early_exit: bool):
    """The consensus walk's declared schedule: per level the decision
    triple reduced over ``model`` as 4 pmax (abs-max envelope, global
    top, winner lower bound, runner-up upper bound) + 1 pmin (first-
    occurrence index tie-break), plus the early-exit consensus psum over
    the data axes; per walk the finalize fallback's pmax/pmin pair."""
    from repro.analysis.sharding import ReductionSpec, ShardingContract
    from repro.core.policy import (COLL_TAG_CONSENSUS, COLL_TAG_MAX,
                                   COLL_TAG_MIN)

    per_level = (ReductionSpec("pmax", 4, COLL_TAG_MAX),
                 ReductionSpec("pmin", 1, COLL_TAG_MIN))
    if early_exit:
        per_level += (ReductionSpec("psum", 1, COLL_TAG_CONSENSUS),)
    return ShardingContract(
        mesh_axes=(("data", data), ("model", model)),
        per_level=per_level,
        per_walk=(ReductionSpec("pmax", 1, COLL_TAG_MAX),
                  ReductionSpec("pmin", 1, COLL_TAG_MIN)),
        in_specs=(("data", None), (None, "model"),
                  ("data", None), (None, "model")),
        n_levels=7)  # n_bits=8, radix-4: 2D-1 levels


def _sharded_entry(early_exit: bool = False):
    n_dev = len(jax.devices())
    data, model = _mesh_shape()
    skip = None if n_dev >= 2 else \
        f"sharded consensus walk needs >= 2 devices (have {n_dev})"

    def build():
        from repro.core.progressive import streaming_argmax
        mesh = _local_mesh(data, model)
        fn = functools.partial(streaming_argmax, mesh=mesh,
                               early_exit=early_exit)
        return fn, _head_operands(m=data * 2, n=model * 3)

    return ExactEntry(
        name="head/sharded-consensus" + ("-while" if early_exit else ""),
        build=build, tags=("head", "sharded"), skip=skip,
        contract=ExactnessContract(n_bits=8, log2_radix=2, k=16),
        sharding=_consensus_contract(data, model, early_exit))


def _sharded_cache_entry():
    """The sharded quantized-weight cache: building a vocab-sharded
    plane stack is slicing, never communication — its partitioned
    module must contain ZERO collectives (budget 0)."""
    n_dev = len(jax.devices())
    data, model = _mesh_shape()
    skip = None if n_dev >= 2 else \
        f"sharded weight cache needs >= 2 devices (have {n_dev})"

    def build():
        from repro.core.quant import QuantConfig, quantize_weights
        mesh = _local_mesh(data, model)
        cfg = QuantConfig(n_bits=8, log2_radix=2)

        def cache(w):
            qw = quantize_weights(w, cfg, prestack=True, window_pad=True,
                                  shard=(None, "model"), mesh=mesh)
            return qw.q, qw.scale, qw.planes.stack

        rng = np.random.default_rng(3)
        w = rng.standard_normal((16, model * 3)).astype(np.float32)
        return cache, (w,)

    from repro.analysis.sharding import ShardingContract
    return ExactEntry(
        name="cache/sharded-weights", build=build,
        tags=("cache", "sharded"), skip=skip,
        # sharding-only: the quantizer consumes a FLOAT weight (taint
        # starts at its int8 output), so the forward-taint exactness
        # pass has nothing to say about this entry
        contract=None,
        sharding=ShardingContract(
            mesh_axes=(("data", data), ("model", model)),
            in_specs=(None,), n_levels=1, max_collectives=0))


def _sharded_decode_entry():
    """The mesh-placed replicated-backbone decode trace: the full smoke
    LM decode step with ``backbone_hints=False`` (the PR 5 fix) — its
    partitioned module must contain EXACTLY the head consensus walk's
    reductions and nothing else.  Sharding-only (``contract=None``):
    the backbone is not itself a claimed-exact walk."""
    n_dev = len(jax.devices())
    data, model = _mesh_shape()
    skip = None if n_dev >= 2 else \
        f"sharded decode trace needs >= 2 devices (have {n_dev})"

    def build():
        from repro.configs import get_smoke
        from repro.core.quant import QuantConfig
        from repro.models.common import materialize
        from repro.models.transformer import init_lm_state, lm_build
        from repro.serve.engine import make_decode_step, prepare_params

        cfg = dataclasses.replace(get_smoke("smollm-135m"),
                                  l2r=QuantConfig())
        params = prepare_params(cfg, materialize(lm_build(cfg),
                                                 jax.random.PRNGKey(0)))
        mesh = _local_mesh(data, model)
        step = make_decode_step(cfg, progressive=True,
                                backbone_hints=False, mesh=mesh)
        batch = data * 2
        state = init_lm_state(cfg, batch, 32)
        toks = np.zeros((batch, 1), np.int32)
        return step, (params, state, toks)

    contract = _consensus_contract(data, model, early_exit=False)
    contract = dataclasses.replace(contract, in_specs=())  # params pytree
    return ExactEntry(
        name="serve/sharded-decode-backbone", build=build,
        tags=("serve", "sharded"), skip=skip,
        contract=None, sharding=contract)


def default_entries() -> list[ExactEntry]:
    """The in-tree claimed-exact walks: head + attention, all three
    schedules, across the backends available on this host."""
    entries = [
        _gemm_entry("stacked", "jnp"),
        _gemm_entry("pairs", "jnp"),
        _gemm_entry("streaming", "jnp"),
        _gemm_entry("streaming", "jnp", early_exit=True),
        _gemm_entry("stacked", "jnp", levels=3),
        _experts_entry(),
        _gemm_entry("stacked", "pallas-interpret", mode="kernel-int"),
        _gemm_entry("streaming", "pallas-interpret", mode="kernel-int"),
        _attn_entry("stacked"),
        _attn_entry("streaming-scan"),
        _attn_entry("streaming-while"),
        _head_entry(early_exit=False),
        _head_entry(early_exit=True),
        _sharded_entry(),
        _sharded_entry(early_exit=True),
        _sharded_cache_entry(),
        _sharded_decode_entry(),
    ]
    if jax.default_backend() == "tpu":
        entries.insert(6, _gemm_entry("stacked", "pallas-tpu",
                                      mode="kernel-int"))
    return entries


def iter_entries(tags: tuple | None = None) -> list[ExactEntry]:
    out = default_entries() + list(_EXTRA)
    if tags:
        out = [e for e in out if set(tags) & set(e.tags)]
    return out
