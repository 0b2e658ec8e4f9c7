"""LM serving through the program's ``ServingGateway``.

The timed entry is ``ServingGateway.run(realtime=True)``, called in
chunks of ``chunk_steps`` decode dispatches so that the benchmark can
look at the clock between them.  The mix's ``serving`` block sizes the
gateway (slots, cache length, prefill group); only the prompt-length
buckets that the mix can reach are compiled.

* ``open_loop``: every request due in the window is submitted up front,
  stamped with its due time, and the gateway admits it once due.  The
  run then drains for up to ``drain_s``; a request not answered by then
  has failed.
* ``closed_loop``: set-up fills every slot from the seeded request
  stream, so that the window opens on a full gateway; the queue is
  topped up to ``backlog`` requests at every chunk boundary; the window
  closes at the first boundary past ``--seconds``.

``check`` takes a seeded sample of the finished requests, the longest
among them, and runs the plain reference over each prompt with its
served tokens.  Two numbers are compared: the widest gap by which a
served token's reference logit lies below the reference's best, and the
median, over the sampled requests, of each request's share of served
tokens that are not the reference's first choice.  A random model's
greedy output soon runs in a loop.  Where the loop passes a near-tie of
the reference, W8A8 rounding may flip it at every turn, so that one
sound request misses at many of its tokens while the others miss almost
none: the median leaves such a request aside.  A decode that loses its
cache misses in every request, mostly at near-ties: the median sees it
where the widest gap stays small.
"""

from __future__ import annotations

import time

import numpy as np

from bench import traffic
from bench.reduce import nearest_rank

PREFILL_MODULE = "jit_prefill"  # the bucket prefill executables
DECODE_MODULE = "jit_decode"  # the decode step executable
GAP_SAMPLE_TOKENS = 512  # served tokens the check compares, at least
GAP_SAMPLE_MIN = 9  # requests the check compares, at least (if finished)
GAP_SAMPLE_MAX = 16  # requests the check compares, at most


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.core.quant import QuantConfig
    from repro.models.config import ModelConfig

    sv = cfg["serving"]
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"unsupported activation {cfg['hidden_act']!r}")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        ffn_kind="swiglu", rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"], compute_dtype=sv["compute_dtype"],
        l2r=QuantConfig(**sv["l2r"]))


def precision_class(label: str):
    """``bounded(0)``, ``budget(4)`` or ``exact`` as a PrecisionClass."""
    from repro.core.policy import PrecisionClass

    name, _, arg = label.partition("(")
    arg = arg.rstrip(")")
    if name == "exact":
        return PrecisionClass.exact()
    if name == "budget":
        return PrecisionClass.budget(int(arg))
    if name == "bounded":
        return PrecisionClass.bounded(float(arg))
    raise ValueError(f"unknown precision class {label!r}")


def reachable_buckets(mix: dict) -> tuple[int, ...]:
    """The gateway's buckets that a prompt of the mix can land in; the
    cache bound always stays the last one."""
    from repro.serve.engine import bucket_for, prefill_buckets

    max_len = mix["serving"]["max_len"]
    allb = prefill_buckets(max_len)
    lo = bucket_for(mix["prompt"]["min"], allb)
    hi = bucket_for(mix["prompt"]["max"], allb)
    return tuple(b for b in allb if lo <= b <= hi or b == max_len)


class System:
    def __init__(self, cfg: dict, mix: dict, seed: int, model):
        self.cfg, self.mix, self.seed, self.model = cfg, mix, seed, model
        self.sv = mix["serving"]
        self.vocab = cfg["vocab_size"]

    # ------------------------------------------------------------ set-up
    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.serve import ServingGateway
        from repro.serve.engine import prepare_params

        self.pc = program_config(self.cfg)
        self.raw = self.model.make_weights(self.cfg, self.seed)
        self.params = prepare_params(self.pc, self.raw)
        self.buckets = reachable_buckets(self.mix)
        self.gw = ServingGateway(
            self.pc, self.params, n_slots=self.sv["n_slots"],
            max_len=self.sv["max_len"],
            cache_dtype=jnp.dtype(self.cfg["serving"]["cache_dtype"]),
            progressive=self.cfg["serving"]["progressive"],
            early_exit=self.cfg["serving"]["early_exit"],
            prefill_group=self.sv["prefill_group"], buckets=self.buckets,
            default_class=precision_class(
                self.cfg["serving"]["default_class"]))
        self._warm()
        self.out_at_open: dict[int, int] = {}
        self._uid = 0
        if self.mix["kind"] == "closed_loop":
            self._fill()
        jax.block_until_ready(self.gw.state)

    def _warm(self):
        """Serve one full prefill group per reachable bucket, so that
        every program and every eager operation of admission, decode and
        retirement has run once before the window."""
        from repro.serve import Request

        g, reqs = self.sv["prefill_group"], []
        lo = self.mix["prompt"]["min"]
        prev = 0
        for b in self.buckets:
            n = max(min(b, self.mix["prompt"]["max"]), prev + 1, lo)
            prev = b
            if n > b:
                continue
            reqs += [Request(uid=-1 - len(reqs), prompt=np.full(
                (n,), 1 + i, np.int32), max_new_tokens=3) for i in range(g)]
        self.gw.run(reqs)

    def _fill(self):
        """Admit a first backlog from the stream and run one decode step:
        admission fills every free slot before the step."""
        self.stream = traffic.ClosedLoop(self.mix, self.seed, self.vocab)
        self.filled = self._submit(self.stream.take(self.mix["backlog"]),
                                   None)
        self.gw.run(max_steps=1)
        if self.gw._free_slots():
            raise RuntimeError("the backlog did not fill every slot")

    # ------------------------------------------------------------ window
    def _submit(self, reqs, t_due0: float | None):
        from repro.serve import Request

        out = []
        for r in reqs:
            q = Request(uid=self._uid, prompt=r.prompt,
                        max_new_tokens=r.max_new_tokens,
                        t_arrival=None if t_due0 is None
                        else t_due0 + r.due_s)
            self._uid += 1
            self.gw.submit(q)
            out.append(q)
        return out

    def window(self, seconds: float, tracer) -> dict:
        steps0, prefills0 = self.gw.steps, self.gw.prefills
        chunk = self.mix["chunk_steps"]
        if self.mix["kind"] == "open_loop":
            counts = self._open_loop(seconds, tracer, chunk)
        else:
            counts = self._closed_loop(seconds, tracer, chunk)
        self.executions = {"decode": self.gw.steps - steps0,
                           "prefill": self.gw.prefills - prefills0}
        counts.update(decode_steps=self.executions["decode"],
                      prefill_dispatches=self.executions["prefill"])
        return counts

    def _open_loop(self, seconds, tracer, chunk, rate=None) -> dict:
        self._uid = 0
        plan = traffic.open_loop(self.mix, self.seed, seconds, self.vocab,
                                 rate)
        tracer.start(seconds)
        t0 = time.perf_counter()
        reqs = self._submit(plan, t0)
        deadline = t0 + seconds + self.mix["drain_s"]
        while not all(r.done for r in reqs):
            with tracer.span("run_chunk"):
                self.gw.run(realtime=True, max_steps=chunk)
            tracer.tick()
            if time.perf_counter() > deadline:
                break
        t_end = time.perf_counter()
        tracer.stop()
        self.served = [r for r in reqs if r.done]
        self.touched = [r for r in reqs if r.output]
        cap = t_end  # a request never answered waits until the cut
        ttft, tpot = [], []
        for r in reqs:
            first = r.t_first_token if r.t_first_token is not None else cap
            ttft.append(first - r.t_arrival)
            if not r.done:  # misses every limit
                tpot.append(cap - r.t_arrival)
            elif len(r.output) > 1:
                tpot.append((r.t_complete - r.t_first_token)
                            / (len(r.output) - 1))
        self.unanswered = sum(not r.done for r in reqs)
        close = t0 + seconds
        return {"attempted": len(reqs), "failed": self.unanswered,
                "ttft_s": ttft, "tpot_s": tpot,
                "backlog_at_close": sum(
                    r.t_first_token is None or r.t_first_token > close
                    for r in reqs),
                "drain_s": t_end - close,
                "window_s": seconds, "tokens": sum(len(r.output)
                                                   for r in reqs)}

    def _closed_loop(self, seconds, tracer, chunk) -> dict:
        backlog = self.mix["backlog"]
        tok0 = self.gw.stats(latency=False)["tokens"]  # emit flushed
        reqs = self.filled
        self.out_at_open = {r.uid: len(r.output) for r in reqs if r.output}
        tracer.start(seconds)
        t0 = time.perf_counter()
        while True:
            with tracer.span("top_up"):
                reqs += self._submit(self.stream.take(
                    max(0, backlog - len(self.gw.queue))), None)
            with tracer.span("run_chunk"):
                self.gw.run(max_steps=chunk)
            tracer.tick()
            t_end = time.perf_counter()
            if t_end - t0 >= seconds:
                break
        tracer.stop()
        tokens = self.gw.stats(latency=False)["tokens"] - tok0
        self.unanswered = 0
        self.served = [r for r in reqs if r.done]
        self.touched = [r for r in reqs if r.output]
        return {"attempted": len(self.touched), "failed": 0,
                "tokens": tokens, "window_s": t_end - t0}

    def sweep_point(self, rate: float, seconds: float) -> dict:
        """One open-loop window at ``rate``: how far the gateway keeps up."""
        from bench.run import Tracer

        self._uid = 0
        c = self._open_loop(seconds, Tracer(False), self.mix["chunk_steps"],
                            rate)
        thirds = np.array_split(np.asarray(c["ttft_s"]), 3)
        return {"rate_per_s": rate, "requests": c["attempted"],
                "failed": c["failed"],
                "backlog_at_close": c["backlog_at_close"],
                "drain_s": c["drain_s"],
                "ttft_p50_first_third_ms": 1e3 * float(np.median(thirds[0])),
                "ttft_p50_last_third_ms": 1e3 * float(np.median(thirds[-1])),
                "ttft_p95_ms": 1e3 * nearest_rank(c["ttft_s"], 95),
                "tpot_p95_ms": 1e3 * nearest_rank(c["tpot_s"], 95)
                if c["tpot_s"] else None}

    # ------------------------------------------------------- work counts
    def work(self) -> dict:
        """Model work of everything the gateway did from the window's
        start: decode and prefill executions, the rows they carried at
        their true lengths, and their multiply-adds.  A request admitted
        in set-up counts only the tokens decoded after the window
        opened."""
        m, cfg = self.model, self.cfg
        layer = m.layer_macs_per_token(cfg)
        head = m.macs_per_token(cfg, 0)["dense"] - layer
        attn1 = m.macs_per_token(cfg, 1)["attn"]  # per position attended
        dec = {"rows": 0, "int8_ops": 0.0, "bf16_flops": 0.0}
        pre = {"rows": 0, "int8_ops": 0.0, "bf16_flops": 0.0}
        for r in self.touched:
            p, n = len(r.prompt), len(r.output)
            n0 = self.out_at_open.get(r.uid, 0)
            if n0 == 0:
                pre["rows"] += p
                pre["int8_ops"] += 2.0 * (p * layer + head)
                pre["bf16_flops"] += 2.0 * attn1 * p * (p + 1) / 2
            # decode token j (a..n-1) attends over p + j positions
            a = max(1, n0)
            if n > a:
                dec["rows"] += n - a
                dec["int8_ops"] += 2.0 * (n - a) * (layer + head)
                dec["bf16_flops"] += 2.0 * attn1 * (
                    (n - a) * p + (a + n - 1) * (n - a) / 2)
        dec["executions"] = self.executions["decode"]
        pre["executions"] = self.executions["prefill"]
        dec["module"], pre["module"] = DECODE_MODULE, PREFILL_MODULE
        pre["positions"] = self._prefill_positions(pre["executions"])
        out_bytes = 2 if self.cfg["serving"]["compute_dtype"] == "bfloat16" \
            else 4
        return {"decode": dec, "prefill": pre, "out_bytes": out_bytes,
                "layer_gemms": [[k, n] for name, k, n in m.dense_gemms(cfg)
                                if name != "head"]}

    def _prefill_positions(self, executions: int) -> int | None:
        """Positions the prefill dispatches carried, pad rows and pad
        columns included: the rows of one packed dispatch land together
        and share one first-token stamp, and the dispatch is as wide as
        the bucket of its longest prompt.  None where the stamps do not
        account for every dispatch."""
        from repro.serve.engine import bucket_for

        groups: dict[float, int] = {}
        for r in self.touched:
            if r.uid in self.out_at_open:
                continue  # admitted in set-up
            groups[r.t_first_token] = max(groups.get(r.t_first_token, 0),
                                          len(r.prompt))
        if len(groups) != executions:
            return None
        g = self.sv["prefill_group"]
        return sum(g * bucket_for(p, self.buckets) for p in groups.values())

    # -------------------------------------------------------------- check
    def sample(self) -> list:
        """A seeded sample of the finished requests, the longest among
        them, of at least GAP_SAMPLE_TOKENS served tokens and
        GAP_SAMPLE_MIN requests."""
        done = sorted(self.served, key=lambda r: r.uid)
        if not done:
            return []
        rng = traffic.rng_for(self.seed, 3)
        longest = max(done, key=lambda r: (len(r.prompt) + len(r.output),
                                           -r.uid))
        out, n = [longest], len(longest.output)
        for i in rng.permutation(len(done)):
            if (n >= GAP_SAMPLE_TOKENS and len(out) >= GAP_SAMPLE_MIN) \
                    or len(out) >= GAP_SAMPLE_MAX:
                break
            if done[i] is not longest:
                out.append(done[i])
                n += len(done[i].output)
        return out

    def free_program(self):
        """Drop the program's state before the reference runs."""
        if getattr(self, "gw", None) is not None:
            self.gw.close()
        self.gw = self.params = None

    def padded(self, r) -> np.ndarray:
        seq = np.zeros(self.sv["max_len"], np.int32)
        full = np.concatenate([r.prompt, np.asarray(r.output, np.int32)])
        seq[:len(full)] = full
        return seq

    def served_gaps(self, reqs) -> tuple[float, float]:
        """(widest reference gap of a served token, median over ``reqs``
        of each request's share of served tokens with a gap); a
        non-finite gap reads 1e30."""
        import jax.numpy as jnp

        items = tuple(sorted((k, v) for k, v in self.cfg.items()
                             if not isinstance(v, (dict, list))))
        worst, shares = 0.0, []
        for r in reqs:
            seq = self.padded(r)
            picks = np.concatenate([seq[1:], seq[:1]])
            gaps = np.asarray(self.model.reference_gaps(
                items, self.raw, jnp.asarray(seq), jnp.asarray(picks)))
            p, n = len(r.prompt), len(r.output)
            seg = gaps[p - 1:p - 1 + n]
            worst = max(worst, float(np.max(seg)) if np.isfinite(seg).all()
                        else 1e30)
            shares.append(float(np.mean(~(seg <= 0))))
        return worst, float(np.median(shares))

    def check(self) -> list[dict]:
        self.free_program()
        reqs = self.sample()
        gap, share = self.served_gaps(reqs) if reqs else (1e30, 1.0)
        lim = self.cfg["limits"]
        return [{"name": "served_gap_max", "value": gap,
                 "limit": lim["served_gap_max"],
                 "ok": bool(gap <= lim["served_gap_max"])},
                {"name": "served_miss_median", "value": share,
                 "limit": lim["served_miss_median"],
                 "ok": bool(share <= lim["served_miss_median"])},
                {"name": "unanswered", "value": self.unanswered, "limit": 0,
                 "ok": self.unanswered == 0}]
