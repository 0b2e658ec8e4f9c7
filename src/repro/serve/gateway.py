"""Serving gateway: bucketed AOT prefill, donated decode, async emit.

The JetStream-shaped front end over the continuous-batching engine
(ROADMAP item 1).  `ContinuousBatcher` is structurally correct but pays
three per-request / per-step taxes that dominate at fleet scale:

  * prefill retraces for every unique prompt length, and prefills one
    prompt at a time inline with decode;
  * the jitted decode step copies the full KV-cache pytree every token
    (no donation);
  * `step()` blocks the device loop on a host sync per slot
    (``int(nxt[slot, 0])``) before the next decode can dispatch.

`ServingGateway` removes all three:

  * **Bucketed, packed prefill** — prompts right-pad to power-of-2
    length buckets (`engine.prefill_buckets`) and up to
    ``prefill_group`` queued prompts share ONE prefill dispatch at a
    fixed ``(group, bucket)`` shape.  One executable per bucket, ever;
    bit-exact (pad cache entries are masked empty, the head reads the
    true last position — `engine.make_bucket_prefill_step`).
  * **AOT warmup + donated decode** — every per-bucket prefill
    executable and the decode step are compiled at startup via
    ``jit(...).lower(...).compile()`` (in/out shardings pinned by the
    lowered arrays), so the first request pays no trace; decode donates
    the slot state (``donate_argnums``), so XLA updates the KV caches
    in place instead of copying them every token.
  * **Async emit** — the device loop never reads a device value.  Token
    arrays stream through a bounded queue to an emit thread that does
    the host syncs (``np.asarray``), appends tokens to requests, stamps
    latency timestamps, and detects EOS.  Retirement on token budget is
    computed HOST-SIDE at admission (``min(max_new_tokens,
    max_len - prompt_len)`` tokens, exactly the plain batcher's
    semantics), so the loop frees slots without waiting on results; EOS
    retirement necessarily lags by the queue depth and is signalled
    back as a ``(slot, generation)`` pair — the generation counter
    keeps a stale signal from freeing a reassigned slot.

Measurement is always on and costs a clock read or a counter per
dispatch.  ``Request.t_admit`` stamps the dispatch of a request's
prefill, so TTFT splits into queue wait (``t_admit - t_arrival``) and
first-token lag (``t_first_token - t_admit``: device backlog, prefill,
emit).  ``stats()["decode_in_flight_mean"]`` is the mean number of
decode steps dispatched but not yet landed at each decode dispatch: how
far the loop runs ahead of the device.  ``jax.profiler.TraceAnnotation``
spans, which record only inside an active profile, put the host loop on
the device trace's clock: ``gateway.admit`` (one prefill group, with its
request uids), ``gateway.decode`` (one decode dispatch),
``gateway.wait_arrival`` (the realtime sleep), ``gateway.flush`` (a wait
for the emit thread) and, on the emit thread, ``gateway.emit`` (one
item, with its kind and, for a prefill, its uids).

Output streams are bit-identical to `ContinuousBatcher` for the same
request set (tests/test_gateway.py): bucketed prefill is bit-exact,
rows of a packed prefill are independent, and decode rows are
independent, so batching composition cannot move a token.
"""

from __future__ import annotations

import functools
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.policy import LevelPolicy, PrecisionClass
from repro.models.config import ModelConfig
from repro.models.transformer import init_lm_state
from .batching import (Request, _splice, latency_percentiles,
                       progressive_stats, state_batch_axes)
from .engine import (bucket_for, make_bucket_prefill_step, make_decode_step,
                     prefill_buckets, supports_bucketed_prefill)

__all__ = ["ServingGateway"]


def _uids(reqs) -> str:
    """Request uids as one span argument (the profiler's argument
    encoding reserves ``,`` and ``#``)."""
    return " ".join(str(r.uid) for r in reqs)


class _EmitThread:
    """Bounded-queue emit worker: drains (kind, entries, device-arrays)
    items, doing the host syncs (np.asarray) OFF the device loop.  A
    single FIFO drained by a single thread processes dispatches in
    device order, so each request's tokens append in sequence order.
    Worker exceptions are captured and re-raised at flush()/close()."""

    def __init__(self, process, depth: int):
        self._process = process
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._err: BaseException | None = None
        self._t = threading.Thread(target=self._run, daemon=True,
                                   name="gateway-emit")
        self._t.start()

    def put(self, item):
        self._q.put(item)

    def _run(self):
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                if self._err is None:  # fail-stop: keep draining, no work
                    self._process(item)
            except BaseException as e:  # re-raised on the caller's thread
                self._err = e
            finally:
                self._q.task_done()

    def flush(self):
        """Block until every queued item is processed; re-raise worker
        errors on the calling thread."""
        self._q.join()
        if self._err is not None:
            err, self._err = self._err, None
            raise err

    def close(self):
        self.flush()
        self._q.put(None)
        self._t.join()


class _Slot:
    """Host-side per-slot bookkeeping: the owning request, the number of
    decode steps left (token-budget retirement, known at admission), and
    a generation counter so retirement signals for a PREVIOUS occupant
    cannot free the current one."""

    __slots__ = ("req", "rem", "gen")

    def __init__(self):
        self.req: Request | None = None
        self.rem = 0
        self.gen = 0


class ServingGateway:
    """Offline-inference driver and online request-queue server over the
    serving engine.  See the module docstring for the design; the public
    surface mirrors `ContinuousBatcher`:

        gw = ServingGateway(cfg, params, n_slots=8, max_len=128)
        gw.submit(Request(uid=0, prompt=..., max_new_tokens=32))
        gw.run()                  # offline: drain everything
        gw.run(realtime=True)     # online: honor Request.t_arrival stamps
        gw.stats()

    ``prefill_group`` is the packed-prefill width: up to that many
    queued prompts (sharing a length bucket) prefill in one dispatch;
    short groups pad with dummy rows (``true_len = 1``) whose outputs
    are ignored — the executable shape never varies.  ``aot_warmup``
    compiles every per-bucket prefill executable and the decode step at
    construction; ``async_emit=False`` degrades the emit thread to
    inline processing (debug aid — same code path, synchronous).

    ``mesh`` runs the engine mesh-aware with REPLICATED state (the
    batcher's ``state_sharding="replicated"`` mode): the progressive
    head streams through the sharded consensus walk, the backbone
    traces with interior sharding hints scoped off, and tokens/stats
    stay bit-identical to the unmeshed gateway.

    ``default_class`` mirrors `ContinuousBatcher`: the
    :class:`~repro.core.policy.PrecisionClass` for requests without
    their own ``Request.precision`` and for idle/dummy rows (default
    ``bounded(0.0)`` — the legacy walk bit for bit).  Admission splices
    each request's class into the per-slot
    :class:`~repro.core.policy.LevelPolicy` rows, packed prefills carry
    a per-row group policy, and the AOT executables lower the policy as
    a trailing positional argument — classes are array VALUES, so no
    class mix can trigger a trace.
    """

    def __init__(self, cfg: ModelConfig, params, n_slots: int = 8,
                 max_len: int = 128, cache_dtype=jnp.float32,
                 progressive: bool = False, early_exit: bool = False,
                 prefill_group: int = 4, buckets: tuple[int, ...] | None = None,
                 mesh=None, aot_warmup: bool = True, async_emit: bool = True,
                 emit_queue_depth: int = 8,
                 default_class: PrecisionClass | None = None):
        from repro.sharding import ctx

        assert supports_bucketed_prefill(cfg), \
            "gateway serving needs bucketed prefill: attention families only"
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.progressive = progressive
        self.prefill_group = prefill_group
        self.buckets = tuple(buckets) if buckets else prefill_buckets(max_len)
        assert self.buckets[-1] == max_len, \
            "the largest bucket must be the cache bound"
        self.mesh = mesh if mesh is not None else ctx.get_mesh()

        self.state = init_lm_state(cfg, n_slots, max_len, cache_dtype)
        self._axes = state_batch_axes(cfg, max_len, cache_dtype)
        self.cur_tok = jnp.zeros((n_slots, 1), jnp.int32)
        if self.mesh is not None:
            sh = jax.tree.map(
                lambda leaf: NamedSharding(self.mesh, P()), self.state)
            self.state = jax.device_put(self.state, sh)
            self.cur_tok = jax.device_put(
                self.cur_tok, NamedSharding(self.mesh, P(None, None)))

        if default_class is not None and not progressive:
            raise ValueError("default_class steers the progressive head "
                             "walk: requires progressive=True")
        self.default_class = (default_class or PrecisionClass.bounded()
                              if progressive else None)
        self.slot_policy = (LevelPolicy.from_classes(
            [self.default_class] * n_slots) if progressive else None)

        # replicated backbone -> interior sharding hints scoped off (see
        # ContinuousBatcher: they would float-reassociate contractions)
        hints = False if self.mesh is not None else True
        # fallback jitted entry points (shape-keyed cache: still one
        # trace per bucket); AOT warmup swaps in Compiled executables
        self._prefill_jit = make_bucket_prefill_step(
            cfg, max_len, cache_dtype, progressive=progressive,
            early_exit=early_exit, backbone_hints=hints, mesh=self.mesh)
        self._decode_jit = make_decode_step(
            cfg, progressive=progressive, early_exit=early_exit,
            backbone_hints=hints, mesh=self.mesh, donate_argnums=(1,))
        self._prefill_exe: dict[int, object] = {}
        self._decode_exe = None
        if aot_warmup:
            self.warmup()

        self._slots = [_Slot() for _ in range(n_slots)]
        self.queue: list[Request] = []
        self.steps = 0
        self.prefills = 0
        # decode steps in flight at each decode dispatch, as sum and
        # count; ``_decodes_landed`` is written by the emit thread only
        self._decodes_landed = 0
        self._in_flight_sum = 0
        self._in_flight_n = 0

        # emit-side accounting (owned by the emit thread; read after
        # flush())
        self.n_levels = (2 * cfg.l2r.planes - 1
                         if progressive and cfg.l2r is not None else 0)
        self.exit_hist = np.zeros(max(self.n_levels, 1), np.int64)
        self.prefill_exit_hist = np.zeros(max(self.n_levels, 1), np.int64)
        seed = ({self.default_class.label():
                 np.zeros(max(self.n_levels, 1), np.int64)}
                if progressive else {})
        self.exit_hist_by_class = {k: v.copy() for k, v in seed.items()}
        self.prefill_exit_hist_by_class = dict(seed)
        self._ttft: list[float] = []
        self._tpot: list[float] = []
        self._tokens = 0
        self._completed = 0
        self._elapsed = 0.0
        # EOS retirement signals from the emit thread: (slot, generation)
        self._eos_lock = threading.Lock()
        self._eos_signals: set[tuple[int, int]] = set()
        self._emit = (_EmitThread(self._process_emit, emit_queue_depth)
                      if async_emit else None)

    # ---------------------------------------------------------- warmup
    def warmup(self):
        """AOT-compile the decode step and one prefill executable per
        bucket (``jit(...).lower(...).compile()``).  Lowering against
        the live (committed) params/state pins the executables' in/out
        shardings; afterwards no request shape can trigger a trace.
        Progressive executables take the LevelPolicy rows as a trailing
        positional argument (class mixes are array values, not trace
        shapes)."""
        g = self.prefill_group

        def pol_sds(rows):
            return LevelPolicy(
                jax.ShapeDtypeStruct((rows,), jnp.int32),
                jax.ShapeDtypeStruct((rows,), jnp.int32),
                jax.ShapeDtypeStruct((rows,), jnp.float32))

        for lb in self.buckets:
            if lb in self._prefill_exe:
                continue
            args = [self.params,
                    jax.ShapeDtypeStruct((g, lb), jnp.int32),
                    jax.ShapeDtypeStruct((g,), jnp.int32)]
            if self.progressive:
                args.append(pol_sds(g))
            self._prefill_exe[lb] = self._prefill_jit.lower(*args).compile()
        if self._decode_exe is None:
            args = [self.params, self.state,
                    jax.ShapeDtypeStruct((self.n_slots, 1), jnp.int32)]
            if self.progressive:
                args.extend([None, pol_sds(self.n_slots)])
            self._decode_exe = self._decode_jit.lower(*args).compile()

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

    def run(self, requests=None, max_steps: int = 100_000,
            realtime: bool = False):
        """Serve until the queue and all slots drain (or ``max_steps``
        decode dispatches).  ``requests`` is submitted first (offline
        driver convenience).  ``realtime=True`` honors future
        ``Request.t_arrival`` stamps — a pre-stamped trace (e.g. a
        Poisson arrival process) replays in real time; otherwise every
        queued request is admissible immediately."""
        if requests is not None:
            for r in requests:
                self.submit(r)
        t0 = time.perf_counter()
        steps0 = self.steps
        while self.queue or any(s.req is not None for s in self._slots):
            if self.steps - steps0 >= max_steps:
                break
            self._drain_eos_signals()
            self._admit(realtime)
            if all(s.req is None for s in self._slots):
                if not self.queue:
                    break
                if realtime:
                    nxt = min(r.t_arrival for r in self.queue)
                    dt = nxt - time.perf_counter()
                    if dt > 0:
                        with TraceAnnotation("gateway.wait_arrival"):
                            time.sleep(min(dt, 0.05))
                    continue
                # EOS-retirement lag can leave every slot waiting on the
                # emit thread while the queue still holds work
                self._flush_emit()
                continue
            self._decode_step()
        self._flush_emit()
        self._drain_eos_signals()
        self._elapsed += time.perf_counter() - t0
        return self.steps

    def stats(self, latency: bool = True) -> dict:
        """Gateway counters (emit-thread flushed first): dispatch and
        token counts, throughput, the mean number of decode steps in
        flight at a decode dispatch, progressive saved-levels histograms
        (same schema as `ContinuousBatcher.stats`), and — unless
        ``latency=False`` — p50/p99 TTFT and per-output-token seconds
        over completed requests."""
        self._flush_emit()
        out = {"steps": self.steps, "prefills": self.prefills,
               "progressive": self.progressive, "tokens": self._tokens,
               "completed": self._completed,
               "buckets": list(self.buckets),
               "tokens_per_s": (self._tokens / self._elapsed
                                if self._elapsed > 0 else 0.0),
               "decode_in_flight_mean": (
                   self._in_flight_sum / self._in_flight_n
                   if self._in_flight_n else 0.0)}
        if self.progressive:
            out.update(progressive_stats(self.n_levels, self.exit_hist,
                                         self.prefill_exit_hist,
                                         self.exit_hist_by_class,
                                         self.prefill_exit_hist_by_class))
        if latency:
            out.update(latency_percentiles(self._ttft, self._tpot))
        return out

    def close(self):
        if self._emit is not None:
            self._emit.close()
            self._emit = None

    # ------------------------------------------------------ device loop
    def _free_slots(self):
        return [i for i, s in enumerate(self._slots) if s.req is None]

    def _admissible(self, realtime: bool):
        if not realtime:
            return self.queue
        now = time.perf_counter()
        return [r for r in self.queue if r.t_arrival <= now]

    def _admit(self, realtime: bool = False):
        """Admit queued requests by PACKED bucket prefill: up to
        ``prefill_group`` admissible prompts sharing a length bucket go
        through one fixed-shape dispatch; short groups pad with dummy
        rows (true_len 1) whose outputs never leave the device."""
        while True:
            free = self._free_slots()
            cand = self._admissible(realtime)
            if not free or not cand:
                return
            lead = cand[0]
            lb = bucket_for(len(lead.prompt), self.buckets)
            group: list[Request] = []
            for r in cand:  # FIFO scan: later prompts may share the bucket
                if len(group) >= min(len(free), self.prefill_group):
                    break
                if bucket_for(len(r.prompt), self.buckets) <= lb:
                    group.append(r)
            for r in group:
                self.queue.remove(r)
            with TraceAnnotation("gateway.admit", uids=_uids(group)):
                self._prefill_group(group, free, lb)

    def _prefill_group(self, group: list, free: list, lb: int):
        """Dispatch one packed prefill of ``group`` at bucket ``lb`` and
        splice its rows into the ``free`` slots."""
        g = self.prefill_group
        tokens = np.zeros((g, lb), np.int32)
        true_len = np.ones((g,), np.int32)  # dummy rows: one pad token
        for i, r in enumerate(group):
            p = np.asarray(r.prompt, np.int32)
            tokens[i, :len(p)] = p
            true_len[i] = len(p)
        exe = self._prefill_exe.get(lb, self._prefill_jit)
        if self.progressive:
            # per-row group policy: admitted requests' classes,
            # dummy pad rows at the default class
            gcls = [self._class_of(r) for r in group]
            gcls += [self.default_class] * (g - len(group))
            out = exe(self.params, jnp.asarray(tokens),
                      jnp.asarray(true_len),
                      LevelPolicy.from_classes(gcls))
        else:
            out = exe(self.params, jnp.asarray(tokens),
                      jnp.asarray(true_len))
        t_admit = time.perf_counter()
        for r in group:
            r.t_admit = t_admit
        if self.progressive:
            st1, _, tok, lv = out
        else:
            st1, logits = out
            tok = jnp.argmax(logits[:, -1], axis=-1,
                             keepdims=True).astype(jnp.int32)
            lv = None
        self.prefills += 1

        entries = []
        for i, r in enumerate(group):
            slot = free[i]
            s = self._slots[slot]
            s.req = r
            s.rem = self._budget_steps(r)
            row = jax.tree.map(
                lambda x, a: jax.lax.slice_in_dim(x, i, i + 1, axis=a)
                if a >= 0 else x, st1, self._axes)
            self.state = _splice(self.state, row, slot, self._axes)
            self.cur_tok = self.cur_tok.at[slot, 0].set(tok[i, 0])
            if self.progressive:
                self.slot_policy = self.slot_policy.set_row(
                    slot, self._class_of(r))
            entries.append((i, slot, s.gen, r))
        self._dispatch_emit(("prefill", entries, tok, lv))

    def _budget_steps(self, req: Request) -> int:
        """Decode steps owed to a request AFTER its prefill token,
        decided host-side at admission so the device loop retires slots
        without reading a device value.  Mirrors `ContinuousBatcher`
        exactly: retirement is evaluated after a decode, so every
        admitted request receives AT LEAST one decode step, then stops
        at the token budget (``len(output) >= max_new_tokens``) or the
        cache bound (``pos >= max_len - 1``), whichever bites first."""
        return max(1, min(req.max_new_tokens - 1,
                          self.max_len - 1 - len(req.prompt)))

    @functools.partial(jax.profiler.annotate_function, name="gateway.decode")
    def _decode_step(self):
        self._in_flight_sum += self.steps - self._decodes_landed
        self._in_flight_n += 1
        if self.progressive:
            out = (self._decode_exe or self._decode_jit)(
                self.params, self.state, self.cur_tok, None,
                self.slot_policy)
        else:
            out = (self._decode_exe or self._decode_jit)(
                self.params, self.state, self.cur_tok)
        if self.progressive:
            self.state, tok, _, lv = out
        else:
            self.state, tok, _ = out
            lv = None
        self.cur_tok = tok
        self.steps += 1
        entries = []
        for slot, s in enumerate(self._slots):
            if s.req is None:
                continue
            entries.append((slot, s.gen, s.req))
            s.rem -= 1
            if s.rem <= 0:
                self._release(slot)
        self._dispatch_emit(("decode", entries, tok, lv))

    def _release(self, slot: int):
        s = self._slots[slot]
        s.req = None
        s.rem = 0
        s.gen += 1  # stale EOS signals for the old occupant die here
        if self.progressive:
            # idle rows revert to the default class (an `exact` leftover
            # would pin the early-exit loop at full depth)
            self.slot_policy = self.slot_policy.set_row(
                slot, self.default_class)

    def _drain_eos_signals(self):
        with self._eos_lock:
            signals, self._eos_signals = self._eos_signals, set()
        for slot, gen in signals:
            if self._slots[slot].req is not None and \
                    self._slots[slot].gen == gen:
                self._release(slot)

    # ------------------------------------------------------ emit thread
    def _dispatch_emit(self, item):
        if self._emit is not None:
            self._emit.put(item)
        else:
            self._process_emit(item)

    def _flush_emit(self):
        if self._emit is not None:
            with TraceAnnotation("gateway.flush"):
                self._emit.flush()

    def _process_emit(self, item):
        """Host-side token landing (emit thread): sync the device
        arrays, append tokens in dispatch order, stamp timestamps,
        detect EOS.  ``entries`` rows are (row-in-dispatch, slot, gen,
        req) for prefill and (slot, gen, req) for decode."""
        kind, entries, tok, lv = item
        uids = {"uids": _uids(e[3] for e in entries)} \
            if kind == "prefill" else {}
        with TraceAnnotation("gateway.emit", kind=kind, **uids):
            self._land_item(kind, entries, tok, lv)

    def _land_item(self, kind, entries, tok, lv):
        tok = np.asarray(tok).reshape(-1)
        lv = np.asarray(lv).reshape(-1) if lv is not None else None
        now = time.perf_counter()
        if kind == "prefill":
            for row, slot, gen, req in entries:
                req.t_first_token = now
                if lv is not None:
                    level = int(lv[row])
                    req.prefill_exit_level = level
                    self.prefill_exit_hist[level] += 1
                    self._class_hist(self.prefill_exit_hist_by_class,
                                     self._class_of(req).label())[level] += 1
                self._land(req, int(tok[row]), slot, gen)
        else:
            for slot, gen, req in entries:
                if req.done:  # EOS already hit; drop the lagged tokens
                    continue
                if lv is not None:
                    level = int(lv[slot])
                    req.exit_levels.append(level)
                    self.exit_hist[level] += 1
                    self._class_hist(self.exit_hist_by_class,
                                     self._class_of(req).label())[level] += 1
                self._land(req, int(tok[slot]), slot, gen)
            self._decodes_landed += 1

    def _land(self, req: Request, t: int, slot: int, gen: int):
        req.output.append(t)
        self._tokens += 1
        n_expect = 1 + self._budget_steps(req)
        eos = req.eos_id is not None and t == req.eos_id
        if eos or len(req.output) >= n_expect:
            req.done = True
            req.t_complete = time.perf_counter()
            if req.t_arrival is not None and req.t_first_token is not None:
                self._ttft.append(req.t_first_token - req.t_arrival)
                if len(req.output) > 1:
                    self._tpot.append((req.t_complete - req.t_first_token)
                                      / (len(req.output) - 1))
            self._completed += 1
            if eos:  # budget retirement the device loop already knows
                with self._eos_lock:
                    self._eos_signals.add((slot, gen))
