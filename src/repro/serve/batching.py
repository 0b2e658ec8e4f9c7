"""Continuous batching: slot-based request scheduling over the decode step.

Production serving does not decode one static batch to completion — new
requests join as finished ones leave.  This engine keeps a fixed-size
slot array (the jitted decode step sees a constant batch shape, so XLA
never recompiles), tracks per-slot positions in the LMState, and:

  * admits queued requests into free slots by running a single-slot
    prefill and splicing its KV/state into the live batch state;
  * steps all active slots with one decode call (idle slots masked);
  * retires slots on EOS or max-token budget.

CPU-sized but structurally the real thing: slot splicing is pure
tree-surgery on the cache pytree (dynamic_update_slice on the batch
axis), exactly what a TPU serving binary does.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.policy import LevelPolicy, PrecisionClass
from repro.models.config import ModelConfig
from repro.models.transformer import init_lm_state
from .engine import (_bspec, bucket_for, make_bucket_prefill_step,
                     make_decode_step, make_prefill_step, prefill_buckets,
                     state_specs, supports_bucketed_prefill)

__all__ = ["Request", "ContinuousBatcher", "infer_batch_axes",
           "state_batch_axes", "latency_percentiles", "progressive_stats"]


def latency_percentiles(ttft: list, tpot: list) -> dict:
    """p50/p99 over per-request latency samples (seconds); 0.0 when no
    samples — the stats() schema stays fixed from construction on."""
    def p(xs, q):
        return float(np.percentile(np.asarray(xs), q)) if xs else 0.0

    return {"ttft_p50_s": p(ttft, 50), "ttft_p99_s": p(ttft, 99),
            "tpot_p50_s": p(tpot, 50), "tpot_p99_s": p(tpot, 99)}


def progressive_stats(n_levels: int, exit_hist, prefill_exit_hist,
                      exit_hist_by_class: dict,
                      prefill_exit_hist_by_class: dict) -> dict:
    """The progressive saved-levels stats block, shared by
    `ContinuousBatcher.stats` and `ServingGateway.stats` so the
    histogram schema cannot drift between the two engines (they once
    disagreed on raw-int vs stringified level keys).

    Normalized schema, the ONE place it is defined:

      * level histograms are positional lists indexed by 0-based MSDF
        exit level (``hist[l]`` = tokens committed after ``l + 1``
        levels) — never level-keyed dicts;
      * per-class maps key on the precision class's
        :meth:`~repro.core.policy.PrecisionClass.label` STRING
        ("exact", "budget(3)", "bounded(0.0001)"), sorted, each value a
        positional level-hist list of the same length.
    """
    levels = np.arange(n_levels)
    total = int(np.sum(exit_hist))
    mean_exit = (float((exit_hist * levels).sum() / total)
                 if total else 0.0)
    total_p = int(np.sum(prefill_exit_hist))
    return dict(
        n_levels=n_levels,
        exit_level_hist=np.asarray(exit_hist).tolist(),
        mean_exit_level=mean_exit,
        mean_levels_saved=(float(n_levels - 1 - mean_exit)
                          if total else 0.0),
        prefill_exit_level_hist=np.asarray(prefill_exit_hist).tolist(),
        mean_prefill_exit_level=(
            float((prefill_exit_hist * levels).sum() / total_p)
            if total_p else 0.0),
        exit_level_hist_by_class={
            k: np.asarray(v).tolist()
            for k, v in sorted(exit_hist_by_class.items())},
        prefill_exit_level_hist_by_class={
            k: np.asarray(v).tolist()
            for k, v in sorted(prefill_exit_hist_by_class.items())},
    )


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (prompt_len,)
    max_new_tokens: int
    eos_id: int | None = None
    # filled by the engine:
    output: list = dataclasses.field(default_factory=list)
    # progressive mode: MSDF exit level of each decoded token (the levels
    # a digit-serial deployment would actually compute for that step)
    exit_levels: list = dataclasses.field(default_factory=list)
    # progressive mode: MSDF exit level of the streamed prefill head
    # (the first generated token, committed from the LAST prompt
    # position's logit stream)
    prefill_exit_level: int | None = None
    # progressive mode: this request's precision class (exact / budget /
    # bounded — core/policy.py).  None = the engine's default class.
    precision: PrecisionClass | None = None
    done: bool = False
    # latency timestamps (time.perf_counter seconds).  ``t_arrival`` is
    # stamped at submit() unless the caller pre-stamped it (traffic
    # replay: a Poisson generator stamps the synthetic arrival instant);
    # ``t_admit`` (the gateway only) when its prefill is dispatched,
    # ``t_first_token`` when the first token is committed,
    # ``t_complete`` at retirement.  TTFT = t_first_token - t_arrival,
    # mean TPOT = (t_complete - t_first_token) / (len(output) - 1);
    # t_admit splits TTFT into queue wait and first-token lag.
    t_arrival: float | None = None
    t_admit: float | None = None
    t_first_token: float | None = None
    t_complete: float | None = None


def infer_batch_axes(abstract_a, abstract_b):
    """Per-leaf batch-axis tree, derived from the state pytree STRUCTURE:
    the same init evaluated abstractly at two batch sizes; each leaf's
    batch axis is the unique axis whose size changed.  -1 = no batch axis
    (batch-independent leaf).

    This replaces the old shape-coincidence heuristic in `_splice`
    (``s.shape[0] == b.shape[0] and ... != 1``), which mis-located the
    batch axis for stacked ``(layers, batch, ...)`` leaves with
    ``n_layers == 1`` and for leaves where ``n_slots`` happened to equal
    a non-batch dim.
    """
    def ax(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                 if x != y]
        if not diffs:
            return -1
        assert len(diffs) == 1, (
            f"ambiguous batch axis: {a.shape} vs {b.shape}")
        return diffs[0]

    return jax.tree.map(ax, abstract_a, abstract_b)


def state_batch_axes(cfg: ModelConfig, max_len: int,
                     cache_dtype=jnp.float32):
    """Batch-axis tree of the LM serving state (see infer_batch_axes)."""
    return infer_batch_axes(
        jax.eval_shape(lambda: init_lm_state(cfg, 1, max_len, cache_dtype)),
        jax.eval_shape(lambda: init_lm_state(cfg, 2, max_len, cache_dtype)))


def _splice(batch_tree, single_tree, slot: int, axes_tree):
    """Write `single` (batch=1 leaves) into `batch` at index `slot` of
    each leaf's EXPLICIT batch axis (`axes_tree`, from infer_batch_axes).

    Leaves may differ in non-batch dims (a fresh prefill cache is sized
    to the prompt): the update is placed at offset 0 of each non-batch
    dim, which is correct because positions beyond the prompt are marked
    empty (-1) in the donor cache.
    """
    def f(b, s, ax):
        if ax < 0:  # batch-independent leaf: nothing to splice
            return b
        start = tuple(slot if i == ax else 0 for i in range(b.ndim))
        upd = s
        want = tuple(1 if i == ax else d for i, d in enumerate(b.shape))
        if upd.shape != want:
            pads = [(0, 0) if i == ax else (0, bd - ud)
                    for i, (bd, ud) in enumerate(zip(b.shape, upd.shape))]
            upd = jnp.pad(upd, pads, constant_values=_pad_value(b))
        return jax.lax.dynamic_update_slice(b, upd.astype(b.dtype), start)

    return jax.tree.map(f, batch_tree, single_tree, axes_tree)


def _pad_value(b):
    """Empty sentinel for donor-cache padding.  Integer leaves carry
    position/validity semantics in this state tree (positions use -1 =
    empty), so EVERY integer dtype pads with the all-ones "empty"
    sentinel — keying on int32 alone left int8/int16/uint caches padded
    with 0, silently marking padded positions as valid.  Unsigned
    integers cannot hold -1 and saturate to their max (the same all-ones
    bit pattern); floats are data-only and pad with 0.
    """
    if jnp.issubdtype(b.dtype, jnp.unsignedinteger):
        return int(jnp.iinfo(b.dtype).max)
    if jnp.issubdtype(b.dtype, jnp.integer):
        return -1
    return 0


class ContinuousBatcher:
    def __init__(self, cfg: ModelConfig, params, n_slots: int = 4,
                 max_len: int = 128, cache_dtype=jnp.float32,
                 progressive: bool = False, early_exit: bool = False,
                 mesh=None, state_sharding: str = "replicated",
                 donate_state: bool = True, bucketed: bool | None = None,
                 default_class: PrecisionClass | None = None):
        """``mesh`` (default: the installed ``sharding.ctx`` mesh) makes
        the engine mesh-aware: the progressive head stream runs the
        shard_mapped consensus walk (vocab over "model", slot rows over
        the data axes, early exit at the fleet-wide slowest slot) and
        the slot state is placed on the mesh per ``state_sharding``:

          * ``"replicated"`` (default) — the backbone state replicates;
            only the head walk is sharded (it batch-shards its rows
            internally, and integer arithmetic is immune to the
            partitioning).  Decode is bit-identical to the unmeshed
            engine end to end: tokens, exit levels, stats all match
            exactly.
          * ``"batch"`` — every state leaf shards its BATCH axis (the
            slot axis, over the data axes).  Scales slot memory across
            data; numerically equivalent but NOT bit-pinned: under
            combined data x model shardings GSPMD may repartition
            interior float contractions of the backbone (observed: the
            attention o-projection over the hint-sharded flattened
            heads axis), which reassociates float sums — hidden states,
            and hence MARGINAL early-exit levels, can move by a bit.
          * ``"specs"`` — the full ``engine.state_specs`` policy (kv
            heads / head_dim / SSM channels over "model"): the
            memory-scaling layout for caches that do not fit one
            device.  Partitioning attention's head_dim reassociates its
            float contraction directly — same numerics caveat as
            ``"batch"``, strictly more sharding.

        In every mode the streaming walk itself stays bit-exact for
        whatever hidden states it is fed (committed tokens always pass
        the same decision machinery).

        ``donate_state`` (default True) donates the slot state to the
        jitted decode step (``donate_argnums``): XLA writes the updated
        KV caches in place instead of copying the full cache pytree
        every token — the dominant decode-side memory traffic at real
        cache sizes.  The old reference is rebound to the output each
        step, so the donation is invisible to callers; pass False only
        to debug aliasing.

        ``bucketed`` routes admits through power-of-2 prompt-length
        buckets (engine.make_bucket_prefill_step): prompts right-pad to
        the smallest covering bucket so prefill traces once per BUCKET,
        not once per unique prompt length — the classic serving retrace
        leak.  Bit-exact (pad positions are masked out of the cache).
        Default None = auto: on for attention-mixer families (and, with
        local windows, when the cache bound fits the window), off
        otherwise.

        ``default_class`` (progressive mode) is the
        :class:`~repro.core.policy.PrecisionClass` applied to requests
        that do not carry their own ``Request.precision``, and to idle
        slot rows.  Default ``bounded(0.0)`` — bit-identical to the
        legacy batch-global early-exit walk, so a batcher constructed
        without policies serves exactly what it always served.  Each
        admitted request's class is spliced into the per-slot
        :class:`~repro.core.policy.LevelPolicy` rows, so one fused
        decode loop serves a heterogeneous exact/budget/bounded batch.
        """
        from repro.sharding import ctx

        assert state_sharding in ("replicated", "batch", "specs"), \
            state_sharding
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.progressive = progressive
        self.mesh = mesh if mesh is not None else ctx.get_mesh()
        self.state = init_lm_state(cfg, n_slots, max_len, cache_dtype)
        # explicit per-leaf batch axes for slot splicing (derived from the
        # state pytree structure, never from shape coincidences)
        self._axes = state_batch_axes(cfg, max_len, cache_dtype)
        if self.mesh is not None:
            if state_sharding == "specs":
                spec_tree = state_specs(cfg, self.mesh, n_slots, max_len)
                self._state_sh = jax.tree.map(
                    lambda s: NamedSharding(self.mesh, s), spec_tree,
                    is_leaf=lambda x: isinstance(x, P))
            elif state_sharding == "batch":
                b = _bspec(self.mesh, n_slots)
                self._state_sh = jax.tree.map(
                    lambda leaf, ax: NamedSharding(self.mesh, P(*(
                        b if i == ax else None for i in range(leaf.ndim)))),
                    self.state, self._axes)
            else:  # replicated: committed to the mesh, every leaf whole
                self._state_sh = jax.tree.map(
                    lambda leaf: NamedSharding(self.mesh, P()), self.state)
            self.state = jax.device_put(self.state, self._state_sh)
        self.slot_req: list[Request | None] = [None] * n_slots
        self.cur_tok = jnp.zeros((n_slots, 1), jnp.int32)
        if self.mesh is not None:
            # replicated mode keeps the tokens whole too: a data-sharded
            # token input would batch-shard every backbone activation
            # behind it, re-opening the data x model repartitioning the
            # mode exists to avoid (the head walk row-shards internally)
            tok_spec = (P(None, None) if state_sharding == "replicated"
                        else P(_bspec(self.mesh, n_slots), None))
            self.cur_tok = jax.device_put(
                self.cur_tok, NamedSharding(self.mesh, tok_spec))
        self.queue: list[Request] = []
        # replicated backbone -> trace the steps with the interior
        # sharding hints scoped off (they would pin interior tensors of
        # a replicated computation onto model axes and float-reassociate
        # backbone contractions; see ctx.hints_disabled)
        hints = state_sharding != "replicated"
        self._decode = make_decode_step(
            cfg, progressive=progressive, early_exit=early_exit,
            backbone_hints=hints, mesh=self.mesh,
            donate_argnums=(1,) if donate_state else ())
        self._prefill1 = make_prefill_step(
            cfg, max_len, cache_dtype, progressive=progressive,
            early_exit=early_exit, backbone_hints=hints, mesh=self.mesh)
        if bucketed is None:
            local = any(k == "local" for k, _ in cfg.layer_kinds())
            bucketed = supports_bucketed_prefill(cfg) and \
                (not local or max_len <= cfg.window)
        self.bucketed = bucketed
        if bucketed:
            self._buckets = prefill_buckets(max_len)
            self._bucket_prefill = make_bucket_prefill_step(
                cfg, max_len, cache_dtype, progressive=progressive,
                early_exit=early_exit, backbone_hints=hints, mesh=self.mesh)
        self.steps = 0
        # saved-levels accounting (progressive mode): histograms over the
        # MSDF exit level of every decoded token across all requests AND
        # of every streamed prefill head (the first generated token),
        # plus the same histograms split per precision class
        self.n_levels = (2 * cfg.l2r.planes - 1
                         if progressive and cfg.l2r is not None else 0)
        self.exit_hist = np.zeros(max(self.n_levels, 1), np.int64)
        self.prefill_exit_hist = np.zeros(max(self.n_levels, 1), np.int64)
        if default_class is not None and not progressive:
            raise ValueError("default_class steers the progressive head "
                             "walk: requires progressive=True")
        self.default_class = (default_class or PrecisionClass.bounded()
                              if progressive else None)
        self.slot_policy = (LevelPolicy.from_classes(
            [self.default_class] * n_slots) if progressive else None)
        seed = ({self.default_class.label():
                 np.zeros(max(self.n_levels, 1), np.int64)}
                if progressive else {})
        self.exit_hist_by_class = {k: v.copy() for k, v in seed.items()}
        self.prefill_exit_hist_by_class = dict(seed)
        # per-request latency samples, recorded at retirement (seconds)
        self._ttft: list[float] = []
        self._tpot: list[float] = []

    # ------------------------------------------------------------- api
    def submit(self, req: Request):
        if req.precision is not None and not self.progressive:
            raise ValueError("Request.precision steers the progressive "
                             "head walk: requires progressive=True")
        if req.t_arrival is None:
            req.t_arrival = time.perf_counter()
        self.queue.append(req)

    def _class_of(self, req: Request) -> PrecisionClass:
        return req.precision if req.precision is not None \
            else self.default_class

    def _class_hist(self, hists: dict, label: str) -> np.ndarray:
        if label not in hists:
            hists[label] = np.zeros(max(self.n_levels, 1), np.int64)
        return hists[label]

    def _prefill_request(self, req: Request):
        """One-sequence prefill, through the bucket pad when enabled.

        Bucketed: the prompt right-pads to its power-of-2 bucket and
        runs the bucket step with the true length — one trace per
        BUCKET shape instead of one per unique prompt length, and the
        returned state is bit-identical to the unpadded prefill (pad
        cache entries are masked empty, ``pos`` is the true length).

        Progressive: the request's precision class rides along as a
        one-row LevelPolicy (class VALUES are array contents, never
        trace shapes — mixing classes cannot retrace).
        """
        prompt = np.asarray(req.prompt, np.int32)
        pol1 = (LevelPolicy.from_classes([self._class_of(req)])
                if self.progressive else None)
        if self.bucketed:
            lb = bucket_for(len(prompt), self._buckets)
            padded = np.zeros((1, lb), np.int32)
            padded[0, :len(prompt)] = prompt
            return self._bucket_prefill(
                self.params, jnp.asarray(padded),
                jnp.asarray([len(prompt)], jnp.int32), pol1)
        return self._prefill1(self.params,
                              {"tokens": jnp.asarray(prompt[None, :])},
                              pol1)

    def _admit(self):
        for slot in range(self.n_slots):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            req = self.queue.pop(0)
            if self.progressive:
                # batch-progressive prefill: the head streams the LAST
                # prompt position only, committing the first token at its
                # earliest sound level (under the request's class)
                st1, _, tok, lv = self._prefill_request(req)
                first = tok[0, 0]
                level = int(lv[0, 0])
                req.prefill_exit_level = level
                self.prefill_exit_hist[level] += 1
                cls = self._class_of(req)
                self._class_hist(self.prefill_exit_hist_by_class,
                                 cls.label())[level] += 1
                # splice the class into the live per-slot policy rows
                self.slot_policy = self.slot_policy.set_row(slot, cls)
            else:
                st1, logits = self._prefill_request(req)
                first = jnp.argmax(logits[0, -1]).astype(jnp.int32)
            # splice the single-sequence state into the live batch state
            self.state = _splice(self.state, st1, slot, self._axes)
            if self.mesh is not None:
                # the eager splice lets the output sharding drift toward
                # the (replicated) donor; re-pin the slot state layout
                self.state = jax.device_put(self.state, self._state_sh)
            self.cur_tok = self.cur_tok.at[slot, 0].set(first)
            req.output.append(int(first))
            req.t_first_token = time.perf_counter()
            self.slot_req[slot] = req

    def _retire(self):
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            eos = req.eos_id is not None and req.output and \
                req.output[-1] == req.eos_id
            full = len(req.output) >= req.max_new_tokens
            of_cache = int(self.state.pos[slot]) >= self.max_len - 1
            if eos or full or of_cache:
                req.done = True
                req.t_complete = time.perf_counter()
                if req.t_arrival is not None and req.t_first_token is not None:
                    self._ttft.append(req.t_first_token - req.t_arrival)
                    if len(req.output) > 1:
                        self._tpot.append(
                            (req.t_complete - req.t_first_token)
                            / (len(req.output) - 1))
                self.slot_req[slot] = None
                if self.progressive:
                    # idle rows revert to the default class so an
                    # `exact` occupant cannot pin the early-exit loop
                    # at full depth after retirement
                    self.slot_policy = self.slot_policy.set_row(
                        slot, self.default_class)

    def step(self):
        """One engine iteration: admit, decode all active slots, retire."""
        self._admit()
        if all(r is None for r in self.slot_req):
            return False
        if self.progressive:
            self.state, nxt, _, lv = self._decode(self.params, self.state,
                                                  self.cur_tok, None,
                                                  self.slot_policy)
        else:
            self.state, nxt, _ = self._decode(self.params, self.state,
                                              self.cur_tok)
            lv = None
        self.cur_tok = nxt
        for slot, req in enumerate(self.slot_req):
            if req is not None:
                req.output.append(int(nxt[slot, 0]))
                if lv is not None:
                    level = int(lv[slot, 0])
                    req.exit_levels.append(level)
                    self.exit_hist[level] += 1
                    self._class_hist(self.exit_hist_by_class,
                                     self._class_of(req).label())[level] += 1
        self.steps += 1
        self._retire()
        return True

    def run(self, max_steps: int = 10_000):
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and self.steps < max_steps:
            if not self.step() and self.queue:
                continue
        return self.steps

    def stats(self, latency: bool = False) -> dict:
        """Engine counters; in progressive mode also the saved-levels
        histograms: exit_level_hist[l] tokens committed after l+1 MSDF
        levels during DECODE (a digit-serial deployment skips the
        remaining n_levels-1-l levels of head compute for those tokens),
        and prefill_exit_level_hist[l] streamed PREFILL heads (one per
        admitted request — the first generated token, committed from the
        last prompt position's stream).

        The progressive-mode schema is STABLE: ``n_levels``, the counts,
        both (zero-filled) histograms, and the means are present from
        construction on — they used to appear only once the first
        token/prefill landed, so monitoring consumers scraping stats()
        saw the dict change shape mid-run.  Means over zero events are
        reported as 0.0.  The histogram block (including the per-class
        split, string-label keys) is the shared `progressive_stats`
        schema — identical to `ServingGateway.stats`.

        ``latency=True`` additionally reports per-request wall-clock
        percentiles over RETIRED requests (completed count, p50/p99
        time-to-first-token and per-output-token seconds).  Opt-in
        because the default schema is deterministic for a fixed request
        set — tests and replica-consistency checks compare stats()
        dicts exactly, which wall-clock samples would break.
        """
        out = {"steps": self.steps, "progressive": self.progressive}
        if latency:
            out.update(completed=len(self._ttft),
                       **latency_percentiles(self._ttft, self._tpot))
        if self.progressive:
            out.update(
                tokens=int(self.exit_hist.sum()),
                prefills=int(self.prefill_exit_hist.sum()),
                **progressive_stats(self.n_levels, self.exit_hist,
                                    self.prefill_exit_hist,
                                    self.exit_hist_by_class,
                                    self.prefill_exit_hist_by_class),
            )
        return out
