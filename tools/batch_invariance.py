#!/usr/bin/env python
"""Does a request's prefill depend on what it is batched with?

Prefills request 0 of ``chip_smoke.py``'s phase B traffic alone (a
one-row bucket) and packed into a four-row bucket with the requests that
share its bucket, the way the gateway packs them, and prints per compute
dtype how far the packed row is from the lone one: its logits, first
token, exit level and KV cache.  Each dtype runs twice: through the
serving step as built (``SERVE_COMPILER_OPTIONS``) and through a plain
``jax.jit`` of the same step, which lets XLA keep excess precision.

    python tools/batch_invariance.py    # SmolLM-135M, on the chip

``compare`` takes the config and sizes, so it runs at smoke size on any
host too.
"""

from __future__ import annotations

import dataclasses
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import chip_smoke  # noqa: E402
from repro.configs import smollm_135m  # noqa: E402
from repro.core.quant import QuantConfig  # noqa: E402
from repro.models.common import materialize  # noqa: E402
from repro.models.transformer import lm_build  # noqa: E402
from repro.serve import (bucket_for, make_bucket_prefill_step,  # noqa: E402
                         prefill_buckets)
from repro.serve.batching import state_batch_axes  # noqa: E402
from repro.serve.engine import prepare_params  # noqa: E402

GROUP = 4  # the gateway's prefill group in chip_smoke.py


def _row0(tree, axes):
    """Row 0 of every leaf of ``tree`` along its batch axis (``axes``:
    one per leaf, negative for a leaf without one), as float64."""
    return [(np.take(np.asarray(x), [0], axis=k) if k >= 0
             else np.asarray(x)).astype(np.float64)
            for x, k in zip(jax.tree.leaves(tree), axes)]


def compare(cfg, sizes: chip_smoke.LmSizes) -> None:
    max_len = sizes.prompt_max + sizes.new_tokens
    reqs = chip_smoke._requests(cfg, sizes)
    buckets = prefill_buckets(max_len)
    lb = bucket_for(len(reqs[0].prompt), buckets)
    rows = [r for r in reqs if bucket_for(len(r.prompt), buckets) == lb]
    rows = (rows + [reqs[0]] * GROUP)[:GROUP]
    print(f"request 0: prompt {len(reqs[0].prompt)}, bucket {lb}, packed "
          f"with {sum(r is not reqs[0] for r in rows)} other requests")

    for dtype in ("bfloat16", "float32"):
        cfg_d = dataclasses.replace(cfg, l2r=QuantConfig(),
                                    compute_dtype=dtype)
        params = prepare_params(cfg_d, materialize(
            lm_build(cfg_d), jax.random.PRNGKey(chip_smoke.SEED)))
        axes = jax.tree.leaves(state_batch_axes(cfg_d, max_len, jnp.float32))
        served = make_bucket_prefill_step(cfg_d, max_len, jnp.float32,
                                          progressive=True, early_exit=True)
        for label, step in (("served", served),
                            ("excess precision",
                             jax.jit(served.__wrapped__))):
            def run(batch):
                tokens = np.zeros((len(batch), lb), np.int32)
                for i, r in enumerate(batch):
                    tokens[i, :len(r.prompt)] = r.prompt
                true_len = np.asarray([len(r.prompt) for r in batch],
                                      np.int32)
                return step(params, jnp.asarray(tokens),
                            jnp.asarray(true_len))

            (st1, lg1, tok1, lv1), (st4, lg4, tok4, lv4) = (
                run([reqs[0]]), run(rows))
            lg1, lg4 = _row0([lg1, lg4], [0, 0])
            cache = max((float(np.max(np.abs(a - b))) for a, b in zip(
                _row0(st1, axes), _row0(st4, axes)) if a.size), default=0.0)
            print(f"  {dtype:8s} {label:16s}: logits differ "
                  f"{int(np.sum(lg1 != lg4))} of {lg1.size} (max "
                  f"{float(np.max(np.abs(lg1 - lg4)))!r}), KV cache max "
                  f"{cache!r}, token "
                  f"{'same' if tok1[0] == tok4[0] else 'differs'}, exit "
                  f"level {'same' if lv1[0] == lv4[0] else 'differs'}")


def main() -> int:
    print(f"device: {jax.devices()[0].device_kind}")
    compare(smollm_135m.CONFIG, chip_smoke.LmSizes())
    return 0


if __name__ == "__main__":
    sys.exit(main())
