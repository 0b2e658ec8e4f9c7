"""Bring-up smoke run of the L2R stack on TPU, through the user entry points.

    python chip_smoke.py              # one chip: VGG-16 + SmolLM-135M serving
    python chip_smoke.py --chips 4    # four chips: meshed gateway vs one device

One chip runs two phases at published widths with seeded random weights:

  A. VGG-16, the paper's network (configs/vgg16_l2r.py: 1000 classes,
     n=8, radix 4) on 224x224 images: the compiled integer cores equal
     the jnp oracle, the L2R logits stay within VGG_LOGIT_TOL of the
     float32 forward, and early-exit classification commits argmax.
  B. SmolLM-135M (configs/smollm_135m.py) served through ServingGateway
     (progressive, early exit) exactly as ``launch/serve.py --gateway``:
     every request answered, tokens and exit levels identical to
     ContinuousBatcher, the integer core of an MLP layer equal to the
     jnp oracle, and prefill logits within LM_LOGIT_TOL of float32 on
     the served weights' first layers.

``--chips 4`` runs only the sharded path: phase B's gateway on a
data=1 x model=4 mesh (vocab-sharded head, shard_mapped consensus walk)
against the same requests on one device; tokens and exit levels must
be identical.

Any failed check raises.  The last line of standard output is the JSON
record ``{"ok": true, "device": {...}}``; it is printed only when every
phase passed on a TPU.  The phase functions take their config and sizes,
so the tests run them at smoke size on the CPU.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import smollm_135m, vgg16_l2r  # noqa: E402
from repro.core.quant import QuantConfig, quantize  # noqa: E402
from repro.kernels.l2r_gemm import (l2r_conv2d_int, l2r_gemm,  # noqa: E402
                                    resolve_backend)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.cnn import (vgg16_apply, vgg16_build,  # noqa: E402
                              vgg16_classify_progressive,
                              vgg16_quantize_weights)
from repro.models.common import materialize  # noqa: E402
from repro.models.transformer import lm_build  # noqa: E402
from repro.serve import (ContinuousBatcher, Request,  # noqa: E402
                         ServingGateway, make_bucket_prefill_step)
from repro.serve.engine import prepare_params  # noqa: E402

# Logit error of the L2R path against the float32 forward, per row as
# ||l2r - ref||_2 / ||ref||_2.  The integer core is exact (held `==` the
# jnp oracle below), so the error is the W8A8 quantization (per-row
# activation scales, per-channel weight scales), and in the LM its bf16
# activations, compounded over depth.  Each bound is 2.5-3x what the
# same comparison showed in the CPU rehearsal (PERF.md): wide enough for
# the chip's float reduction order, tight enough that an error of order
# 1 fails.
#
# VGG-16 holds every image to VGG_LOGIT_TOL (rehearsal, batch 2 at
# 32x32: max rel err 0.033).  For the LM, a random-weight residual
# stream amplifies any perturbation with depth, so a 30-layer random
# model cannot tell a right L2R forward from a wrong one.  The LM check
# is therefore the median over requests on the served weights' first
# LM_CHECK_LAYERS layers (rehearsal median 0.159).  Controls on that cut
# in the rehearsal: one weight's most significant digit plane zeroed
# reads 1.41, its dequant scale dropped 0.69.  A lost least significant
# plane (0.32) is not caught here, only by the `==` checks.  The
# full-depth error is printed, not held.
SEED = 0  # weights, images and requests
VGG_LOGIT_TOL = 0.10
LM_LOGIT_TOL = 0.40
LM_CHECK_LAYERS = 2


@dataclasses.dataclass(frozen=True)
class VggSizes:
    batch: int = 8
    image: int = 224


@dataclasses.dataclass(frozen=True)
class LmSizes:
    n_requests: int = 8
    prompt_min: int = 17
    prompt_max: int = 200
    new_tokens: int = 32
    n_slots: int = 8


def _check(ok, what) -> None:
    """A check of the run that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(what)


def _rel_err(got, ref) -> tuple[float, np.ndarray]:
    """(max abs error, per-row relative L2 error) over the last axis."""
    got = np.asarray(got, np.float64).reshape(-1, np.shape(got)[-1])
    ref = np.asarray(ref, np.float64).reshape(got.shape)
    diff = got - ref
    return (float(np.max(np.abs(diff))),
            np.linalg.norm(diff, axis=-1) / np.linalg.norm(ref, axis=-1))


def _check_int_equal(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    _check(got.dtype == np.int32 and got.shape == want.shape,
           (name, got.dtype, got.shape, want.shape))
    n_bad = int(np.sum(got != want))
    _check(n_bad == 0, f"{name}: {n_bad} of {got.size} int32 outputs differ")
    print(f"  int core {name:8s} {str(got.shape):22s} == jnp oracle")


# ------------------------------------------------------------- phase A
def phase_vgg(cfg=vgg16_l2r.CONFIG, sizes: VggSizes = VggSizes(),
              backend: str | None = None,
              tol: float = VGG_LOGIT_TOL) -> dict:
    """VGG-16 through vgg16_quantize_weights -> vgg16_apply(l2r=...) and
    vgg16_classify_progressive, checked three ways (see module doc)."""
    q = cfg.quant
    backend = resolve_backend(backend, q.n_bits)
    print(f"phase A: VGG-16 {cfg.n_classes} classes, batch {sizes.batch} "
          f"at {sizes.image}x{sizes.image}, n={q.n_bits} radix "
          f"{1 << q.log2_radix}, backend {backend}")
    t0 = time.perf_counter()
    params = materialize(vgg16_build(cfg.n_classes), jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    images = jnp.asarray(rng.standard_normal(
        (sizes.batch, sizes.image, sizes.image, 3)), jnp.float32)
    weights_q = vgg16_quantize_weights(params, q)

    logits = jax.jit(lambda p, x, w: vgg16_apply(
        p, x, l2r=q, weights_q=w, backend=backend))(params, images, weights_q)
    logits = np.asarray(logits)
    _check(logits.shape == (sizes.batch, cfg.n_classes), logits.shape)
    _check(np.isfinite(logits).all(), "non-finite L2R logits")

    # the integer cores the claim of bit-exactness rests on, at real
    # widths: conv1_1 (cin 3), conv4_2 (cin 512), fc6 (K 25088)
    xq1, _ = quantize(images, q, axis=0)
    s4 = sizes.image // 8  # conv4 runs after three 2x2 pools
    xq4 = jnp.asarray(rng.integers(-127, 128, (sizes.batch, s4, s4, 512)),
                      jnp.int8)
    xq6 = jnp.asarray(rng.integers(-127, 128, (sizes.batch, 512 * 7 * 7)),
                      jnp.int8)
    for name, xq in (("conv1_1", xq1), ("conv4_2", xq4)):
        conv = jax.jit(lambda x, w, b: l2r_conv2d_int(x, w, q, backend=b),
                       static_argnums=2)
        _check_int_equal(name, conv(xq, weights_q[name], backend),
                         conv(xq, weights_q[name], "jnp"))
    w6 = weights_q["fc6"].planes  # the load-time plane-stack cache
    _check_int_equal("fc6", l2r_gemm(xq6, w6, q.n_bits, q.log2_radix,
                                     backend=backend),
                     l2r_gemm(xq6, w6, q.n_bits, q.log2_radix, backend="jnp"))

    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(vgg16_apply)(params, images))
    abs_err, rel = _rel_err(logits, ref)
    rel_err = float(rel.max())
    top1 = float(np.mean(logits.argmax(-1) == ref.argmax(-1)))
    print(f"  logits vs float32: max abs err {abs_err!r}, max rel err "
          f"{rel_err!r} (tolerance {tol}), top-1 agreement {top1!r}")
    _check(rel_err <= tol, f"L2R logits off the float32 forward: {rel}")

    pred, exit_level, _ = jax.jit(lambda p, x, w: vgg16_classify_progressive(
        p, x, l2r=q, weights_q=w, backend=backend, early_exit=True))(
        params, images, weights_q)
    pred, exit_level = np.asarray(pred), np.asarray(exit_level)
    _check((pred == logits.argmax(-1)).all(), (pred, logits.argmax(-1)))
    mean_exit = float(exit_level.mean())
    print(f"  progressive early exit == argmax for {sizes.batch}/"
          f"{sizes.batch} images, mean exit level {mean_exit!r} of "
          f"{2 * q.planes - 2}")
    print(f"phase A PASS ({time.perf_counter() - t0:.1f} s incl. compile)")
    return {"rel_err": rel_err, "abs_err": abs_err, "top1": top1,
            "mean_exit_level": mean_exit}


# ------------------------------------------------------------- phase B
def _requests(cfg, sizes: LmSizes) -> list[Request]:
    rng = np.random.default_rng(SEED)
    lengths = rng.integers(sizes.prompt_min, sizes.prompt_max + 1,
                           sizes.n_requests)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab, (n,))
                    .astype(np.int32), max_new_tokens=sizes.new_tokens)
            for i, n in enumerate(lengths)]


def _serve(cfg, params, sizes: LmSizes):
    """The ``launch/serve.py --gateway`` path: progressive early-exit
    gateway over seeded requests.  Returns (requests, stats)."""
    max_len = sizes.prompt_max + sizes.new_tokens
    gw = ServingGateway(cfg, params, n_slots=sizes.n_slots, max_len=max_len,
                        progressive=True, early_exit=True,
                        prefill_group=min(sizes.n_slots, 4))
    reqs = _requests(cfg, sizes)
    try:
        gw.run(reqs)
        stats = gw.stats()
    finally:
        gw.close()
    answered = sum(r.done and len(r.output) == sizes.new_tokens for r in reqs)
    _check(answered == len(reqs), f"{answered}/{len(reqs)} answered")
    return reqs, stats


def _stats_line(stats: dict) -> str:
    return json.dumps({k: v for k, v in stats.items()
                       if not isinstance(v, dict)},
                      default=lambda v: np.asarray(v).tolist())


def _check_same_streams(what: str, got: list[Request], want: list[Request]):
    for a, b in zip(got, want):
        _check(a.output == b.output, (what, a.uid, a.output, b.output))
        _check(a.exit_levels == b.exit_levels, (what, a.uid))
        _check(a.prefill_exit_level == b.prefill_exit_level, (what, a.uid))
    print(f"  tokens and exit levels identical to {what} "
          f"({len(got)} requests)")


def _prefill_logit_err(cfg, raw, reqs: list[Request], label: str,
                       params=None) -> np.ndarray:
    """Per-request relative L2 error of the L2R bucket prefill's
    last-prompt-position logits against the float32 path under highest
    matmul precision.  ``params`` is the prepared L2R tree of ``raw``."""
    cfg_q = dataclasses.replace(cfg, l2r=QuantConfig())
    if params is None:
        params = prepare_params(cfg_q, raw)
    lb = max(len(r.prompt) for r in reqs)
    tokens = np.zeros((len(reqs), lb), np.int32)
    for i, r in enumerate(reqs):
        tokens[i, :len(r.prompt)] = r.prompt
    tokens = jnp.asarray(tokens)
    true_len = jnp.asarray([len(r.prompt) for r in reqs], jnp.int32)
    got = make_bucket_prefill_step(cfg_q, lb)(params, tokens, true_len)[1]
    cfg_f = dataclasses.replace(cfg, l2r=None, compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        want = make_bucket_prefill_step(cfg_f, lb)(raw, tokens, true_len)[1]
    got = np.asarray(got, np.float32)
    _check(got.shape == (len(reqs), 1, cfg.vocab), got.shape)
    _check(np.isfinite(got).all(), "non-finite L2R logits")
    abs_err, rel = _rel_err(got, want)
    print(f"  prefill logits vs float32, {label} ({cfg.n_layers} layers): "
          f"max abs err {abs_err!r}, rel err median "
          f"{float(np.median(rel))!r} max {float(rel.max())!r}")
    return rel


def _first_layers(cfg, raw, n: int):
    """(config, parameters) of the model ``raw`` cut to its first ``n``
    layers: the same weights, the layer stack sliced."""
    prefix, _, unit, suffix = cfg.block_grouping()
    _check(not prefix and not suffix and len(unit) == 1,
           f"{cfg.name}: layer cut needs one stacked layer kind")
    n = min(n, cfg.n_layers)
    return (dataclasses.replace(cfg, n_layers=n),
            dict(raw, stack=jax.tree.map(lambda a: a[:n], raw["stack"])))


def phase_lm(cfg=smollm_135m.CONFIG, sizes: LmSizes = LmSizes(),
             tol: float = LM_LOGIT_TOL) -> dict:
    """SmolLM serving through the gateway, checked against the batcher
    and the float32 prefill (see module doc)."""
    cfg_q = dataclasses.replace(cfg, l2r=QuantConfig())
    q = cfg_q.l2r
    backend = resolve_backend(None, q.n_bits)
    print(f"phase B: {cfg.name} serving, {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab}, {sizes.n_requests} requests "
          f"x {sizes.new_tokens} new tokens, backend {backend}")
    t0 = time.perf_counter()
    raw = materialize(lm_build(cfg), jax.random.PRNGKey(SEED))
    params = prepare_params(cfg_q, raw)

    served, stats = _serve(cfg_q, params, sizes)
    print(f"  gateway answered {stats['completed']}/{sizes.n_requests} "
          f"requests")
    ref = _requests(cfg, sizes)
    eng = ContinuousBatcher(cfg_q, params, n_slots=sizes.n_slots,
                            max_len=sizes.prompt_max + sizes.new_tokens,
                            progressive=True, early_exit=True)
    for r in ref:
        eng.submit(r)
    eng.run(max_steps=100_000)
    _check_same_streams("ContinuousBatcher", served, ref)

    # the integer core at the LM's widths, on the served plane cache:
    # layer 0's MLP down projection (K = d_ff)
    w = jax.tree.map(lambda a: a[0], params["stack"][0]["ffn"]["wo"].planes)
    xq = jnp.asarray(np.random.default_rng(SEED).integers(
        -127, 128, (sizes.prompt_max, w.k)), jnp.int8)
    _check_int_equal("mlp wo", *(
        l2r_gemm(xq, w, q.n_bits, q.log2_radix, backend=b)
        for b in (backend, "jnp")))

    # last-prompt-position logits: L2R prefill vs the float32 path, held
    # on the served weights' first layers, printed at full depth (see
    # LM_LOGIT_TOL)
    cut, raw_cut = _first_layers(cfg, raw, LM_CHECK_LAYERS)
    rel_cut = _prefill_logit_err(cut, raw_cut, ref, "layer cut")
    rel_full = _prefill_logit_err(cfg, raw, ref, "full depth", params)
    rel_err = float(np.median(rel_cut))
    print(f"  held: layer-cut median rel err {rel_err!r} <= {tol}")
    _check(rel_err <= tol, f"L2R logits off the float32 path: {rel_cut}")
    print("  gateway stats: " + _stats_line(stats))
    print(f"phase B PASS ({time.perf_counter() - t0:.1f} s incl. compile)")
    return {"rel_err": rel_err, "rel_err_full_depth": rel_full,
            "stats": stats}


# ------------------------------------------------------ four-chip phase
def phase_lm_mesh(cfg=smollm_135m.CONFIG, sizes: LmSizes = LmSizes(),
                  model: int = 4) -> dict:
    """Phase B's gateway on a data=1 x model=``model`` mesh (vocab-
    sharded head, shard_mapped consensus walk, replicated backbone)
    against the same requests on one device of this process."""
    from repro.launch.mesh import install_local_mesh
    from repro.sharding import ctx

    cfg_q = dataclasses.replace(cfg, l2r=QuantConfig())
    print(f"mesh phase: {cfg.name} gateway on data=1 x model={model} vs one "
          f"device, {sizes.n_requests} requests x {sizes.new_tokens} tokens")
    t0 = time.perf_counter()
    raw = materialize(lm_build(cfg), jax.random.PRNGKey(SEED))
    ctx.set_mesh(None)
    single, _ = _serve(cfg_q, prepare_params(cfg_q, raw), sizes)
    try:
        mesh = install_local_mesh(1, model)
        params = prepare_params(cfg_q, raw)  # head cache vocab-sharded
        spec = params["head_q"].q.sharding.spec
        print(f"  head cache sharding {spec} over {mesh.devices.size} "
              f"devices")
        sharded, stats = _serve(cfg_q, params, sizes)
    finally:
        ctx.set_mesh(None)
    _check_same_streams(f"one device (mesh 1x{model})", sharded, single)
    print("  gateway stats: " + _stats_line(stats))
    print(f"mesh phase PASS ({time.perf_counter() - t0:.1f} s incl. "
          f"compile)")
    return {"stats": stats}


# ----------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the meshed gateway vs one device")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found {platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.update([event.rsplit("/", 1)[-1]])
        if event.startswith("/jax/compilation_cache/") else None)
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    kind = devices[0].device_kind
    print(f"device: platform {platform}, kind {kind}, count {len(devices)}")
    print(f"compile cache: {cache_dir} ({n_cached} entries at start)")
    backend = resolve_backend()
    _check(backend == "pallas-tpu", backend)
    print(f"resolve_backend() == {backend!r}")

    if args.chips == 4:
        phase_lm_mesh(model=4)
    else:
        phase_vgg()
        phase_lm()

    n_now = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_events['cache_hits']} hits, "
          f"{cache_events['cache_misses']} misses, {n_now} entries at end")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
