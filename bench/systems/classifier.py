"""Closed-loop image classification through the program's L2R forward.

The timed entry is the jitted ``vgg16_apply(l2r=..., weights_q=...)``
with its argmax, fed batches from the mix's seeded host pool with at
most ``in_flight`` batches outstanding.  An image counts when its
logits are back on the host.  ``check`` compares every batch of logits
that landed in the window with the plain float32 reference of the same
images.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from bench import traffic

FORWARD_MODULE = "jit_forward"  # the timed entry's program in the trace


def rel_err(got, ref) -> float:
    """Largest per-image relative L2 distance of logits from the
    reference; a non-finite logit reads 1e30."""
    got = np.asarray(got, np.float64)
    if not np.isfinite(got).all():
        return 1e30
    return float(np.max(np.linalg.norm(got - ref, axis=-1)
                        / np.linalg.norm(ref, axis=-1)))


class System:
    def __init__(self, cfg: dict, mix: dict, seed: int, model):
        self.cfg, self.mix, self.seed, self.model = cfg, mix, seed, model
        self.batch = mix["batch"]

    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.core.quant import QuantConfig
        from repro.models.cnn import vgg16_apply, vgg16_quantize_weights

        q = QuantConfig(**self.cfg["serving"]["l2r"])
        self.w = self.model.make_weights(self.cfg, self.seed)
        self.wq = vgg16_quantize_weights(self.w, q)

        def forward(params, images, weights_q):
            logits = vgg16_apply(params, images, l2r=q, weights_q=weights_q)
            return logits, jnp.argmax(logits, -1)

        self.fwd = jax.jit(forward)
        self.pool = traffic.image_pool(self.mix, self.seed)
        jax.block_until_ready(self.fwd(self.w, jax.device_put(self.pool[0]),
                                       self.wq))

    def window(self, seconds: float, tracer) -> dict:
        import jax

        landed, inflight, i = [], collections.deque(), 0
        n_pool = len(self.pool)
        tracer.start(seconds)
        t0 = time.perf_counter()
        while True:
            while len(inflight) < self.mix["in_flight"]:
                with tracer.span("dispatch"):
                    j = i % n_pool
                    out = self.fwd(self.w, jax.device_put(self.pool[j]),
                                   self.wq)
                inflight.append((j, out))
                i += 1
            j, out = inflight.popleft()
            with tracer.span("wait"):
                logits = np.asarray(out[0])
            t = time.perf_counter()
            landed.append((j, logits))
            tracer.tick()
            if t - t0 >= seconds:
                break
        late = [np.asarray(out[0]) for _, out in inflight]
        self.landed = landed
        n = len(landed) * self.batch
        return {"images": n, "window_s": t - t0,
                "attempted": n + len(late) * self.batch, "failed": 0}

    def work(self) -> dict:
        """Per forward: the batch and each layer's (int8 ops, least
        bytes), from the configuration's shapes."""
        gemms = self.model.gemms(self.cfg, self.batch)
        byts = self.model.gemm_bytes(self.cfg, self.batch)
        return {"forward_module": FORWARD_MODULE, "batch": self.batch,
                "macs_per_image": self.model.macs_per_image(self.cfg),
                "gemms": [[2.0 * m * k * n, b]
                          for (_, m, k, n, _), b in zip(gemms, byts)]}

    def check(self) -> list[dict]:
        import jax

        del self.wq, self.fwd  # the program's state goes before the reference
        ref_fn = jax.jit(lambda w, x: self.model.forward_logits(
            self.cfg, w, x))
        ref = [np.asarray(ref_fn(self.w, jax.device_put(x)), np.float64)
               for x in self.pool]
        worst = max(rel_err(logits, ref[j]) for j, logits in self.landed)
        limit = self.cfg["limits"]["logit_rel_err_max"]
        return [{"name": "logit_rel_err_max", "value": worst,
                 "limit": limit, "ok": bool(worst <= limit)}]
