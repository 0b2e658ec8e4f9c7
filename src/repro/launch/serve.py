"""Serving launcher: batched greedy decoding with optional W8A8 (L2R) weights.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
        --batch 4 --prompt-len 16 --steps 12 [--wq] [--l2r-levels 5] \
        [--gateway]

--wq stores matmul weights in int8 (the L2R serving format; on TPU the
digit-plane Pallas kernel consumes them MSDF); --l2r-levels enables the
progressive-precision mode through the jnp digit-plane path.

--gateway serves the same prompts through the request-queue gateway
(serve/gateway.py: bucketed AOT prefill, donated decode state, async
emit) instead of the static-batch loop — the ``--batch`` prompts become
queued requests, ``--batch`` also sizes the slot array, and the summary
reports gateway throughput/latency stats.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke
from repro.core.quant import QuantConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.common import materialize, quantize_params
from repro.models.transformer import lm_build
from repro.serve.engine import (make_decode_step, make_prefill_step,
                                prepare_params)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--wq", action="store_true", help="int8 weight storage")
    ap.add_argument("--l2r-levels", type=int, default=None,
                    help="progressive-precision MSDF levels (digit planes)")
    ap.add_argument("--gateway", action="store_true",
                    help="serve through the request-queue gateway "
                         "(bucketed AOT prefill, donated decode, async "
                         "emit) instead of the static-batch loop")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    assert cfg.family not in ("encdec",), "use examples for enc-dec serving"
    if args.l2r_levels is not None:
        cfg = dataclasses.replace(cfg, l2r=QuantConfig(),
                                  l2r_levels=args.l2r_levels)
    desc = lm_build(cfg)
    params = materialize(desc, jax.random.PRNGKey(0))
    if cfg.l2r is not None:
        # the L2R weight cache: quantize once at load, serve int8 weights
        # through the dispatched digit-plane kernel
        params = prepare_params(cfg, params, desc)
    elif args.wq:
        params = quantize_params(desc, params)

    rng = np.random.default_rng(0)
    max_len = args.prompt_len + args.steps
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)),
                         jnp.int32)

    if args.gateway:
        from repro.serve import Request, ServingGateway

        progressive = cfg.l2r is not None
        gw = ServingGateway(cfg, params, n_slots=args.batch,
                            max_len=max_len, progressive=progressive,
                            early_exit=progressive,
                            prefill_group=min(args.batch, 4))
        reqs = [Request(uid=i, prompt=np.asarray(prompt[i]),
                        max_new_tokens=args.steps)
                for i in range(args.batch)]
        gw.run(reqs)
        gw.close()
        st = gw.stats()
        print(f"gateway: {st['tokens']} tokens in {st['steps']} decode "
              f"dispatches + {st['prefills']} prefill dispatches "
              f"(buckets {st['buckets']}); {st['tokens_per_s']:.1f} tok/s, "
              f"ttft_p50 {st['ttft_p50_s'] * 1e3:.1f} ms, "
              f"tpot_p50 {st['tpot_p50_s'] * 1e3:.1f} ms, "
              f"{st['decode_in_flight_mean']:.2f} decode steps in flight")
        seqs = np.asarray([r.output for r in reqs])
        for i, row in enumerate(seqs):
            print(f"seq{i}: {row.tolist()}")
        return seqs
    prefill = make_prefill_step(cfg, max_len, cache_dtype=jnp.float32)
    decode = make_decode_step(cfg)

    t0 = time.time()
    state, logits = prefill(params, {"tokens": prompt})
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for _ in range(args.steps - 1):
        state, tok, _ = decode(params, state, tok)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = (time.time() - t0) / max(args.steps - 1, 1)
    seqs = np.asarray(jnp.concatenate(out, axis=1))
    print(f"prefill {args.batch}x{args.prompt_len}: {t_prefill*1e3:.1f} ms "
          f"(incl. compile); decode: {t_decode*1e3:.1f} ms/token")
    for i, row in enumerate(seqs):
        print(f"seq{i}: {row.tolist()}")
    return seqs


if __name__ == "__main__":
    main()
