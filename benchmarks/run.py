"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * table1_*    — synthesis model vs paper Table I (area µm² / power mW /
                  critical path ns);
  * table2_*    — Table II columns (peak GOPS, TOPS/W, GOPS/mm²) + the
                  headline multiples vs [4] Cheng and [5] Eyeriss;
  * vgg16_*     — per-layer + total Cycle_P walk (execution-cycles table)
                  for L2R vs the Loom-pattern baseline;
  * kernel_*    — wall-time microbenches of the digit-plane GEMM paths on
                  this host (CPU; interpret-mode Pallas excluded from
                  timing claims, jnp reference path timed);
  * kernel_stacked_* — pair-loop vs level-stacked schedule (the PR's
                  restructured execution order: 2D-1 fused level matmuls
                  instead of D² pair passes), jnp production path timed,
                  pallas-interpret validated; rows also land in
                  BENCH_l2r_gemm.json for the cross-PR perf trajectory;
  * kernel_prestacked_* — pre-stacked plane-operand amortization: GEMM
                  with the load-time RHS plane-stack cache vs inline
                  per-call extraction, the fused conv layer with the
                  cached weight stack, and the prestacked Pallas conv
                  path (correctness rows in interpret mode);
  * kernel_tilesweep_* — (bm, bk, bn) tile sweep of the stacked Pallas
                  kernel: timed on TPU hosts, correctness-validated in
                  interpret mode elsewhere (the real-TPU tuning entry);
  * ipu_*       — cycle-accurate CIPU simulator throughput;
  * online_*    — progressive-precision early-exit statistics;
  * progressive_* — the streaming early-exit suite: VGG-16 logit-head
                  exit levels (prototype-calibrated head — the decisive-
                  margin regime of a trained classifier) + wall-clock of
                  the stacked GEMM truncated at the mean exit level vs
                  the full stream; rows land in BENCH_progressive.json;
  * progressive_sharded_* — the multi-device consensus head walk
                  (core/progressive.py sharded streaming_argmax) vs the
                  single-device stream on a host-platform virtual-device
                  mesh (subprocess: the device-count flag must precede
                  jax init).  Decisions/exit levels verified bit-exact
                  before timing; on one shared CPU the "scaling" number
                  measures partitioning overhead, not parallel speedup —
                  the real-accelerator row is a deployment follow-up.
  * attention_* — digit-serial attention decode modes on one KV cache:
                  float oracle vs quantized QK^T re-extracting K planes
                  per step vs the incrementally plane-stacked cache vs
                  margin-bounded early exit, parity asserted bit-exact
                  before timing (plane cache == re-extraction; early
                  exit == full depth at tight tolerance); plus the
                  chunked quantized prefill and an interpret-mode
                  correctness row for the flash-fused level-walk
                  kernel; rows land in BENCH_attention.json;
  * serving_*   — the gateway under synthetic Poisson traffic (bucketed
                  AOT prefill, donated decode state, async emit):
                  tokens/s + p50/p99 TTFT and per-token latency, early
                  exit on vs off, output asserted bit-identical to the
                  plain batcher; rows land in BENCH_serving.json.

    PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# --check smoke mode (CI): 1 repetition, no warmup — exercises every
# bench path without pretending the numbers are a timing signal.
CHECK_MODE = False


def _timeit(fn, n=5, warmup=2):
    if CHECK_MODE:
        n, warmup = 1, 0
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6  # us


def _best_pair(fa, fb, n, rounds=3):
    """Interleaved min-of-rounds timing for every A-vs-B comparison: the
    effects measured here are 10-30% of a GEMM on a shared CPU host,
    where one-round means drift by that much between the two
    measurements."""
    if CHECK_MODE:
        rounds = 1
    best_a = best_b = float("inf")
    for _ in range(rounds):
        best_a = min(best_a, _timeit(fa, n=n, warmup=0))
        best_b = min(best_b, _timeit(fb, n=n, warmup=0))
    return best_a, best_b


def emit(name: str, us: float | str, derived):
    print(f"{name},{us if isinstance(us, str) else f'{us:.1f}'},{derived}")


def table1():
    from repro.core import hw_model
    t0 = time.perf_counter()
    t1 = hw_model.table1()
    us = (time.perf_counter() - t0) * 1e6
    for design in ("baseline", "l2r_cipu"):
        p = hw_model.PAPER_TABLE1[design]
        m = t1[design]
        emit(f"table1_{design}_area_um2", us,
             f"model={m['area_um2']:.2f} paper={p['area_um2']}")
        emit(f"table1_{design}_power_mw", us,
             f"model={m['power_mw']:.2f} paper={p['power_mw']}")
        emit(f"table1_{design}_latency_ns", us,
             f"model={m['latency_ns']:.3f} paper={p['latency_ns']} "
             f"delta={(m['latency_ns']-p['latency_ns'])/p['latency_ns']*100:+.1f}%")


def table2():
    from repro.core import hw_model
    t2 = hw_model.table2()
    p = hw_model.PAPER_TABLE2
    for design in ("baseline", "l2r_cipu"):
        m = t2[design]
        emit(f"table2_{design}_peak_gops", 0.0,
             f"model={m['gops']:.2f} paper={p[design]['gops']}")
        emit(f"table2_{design}_tops_w", 0.0,
             f"model={m['tops_w']:.3f} paper={p[design]['tops_w']}")
        emit(f"table2_{design}_gops_mm2", 0.0,
             f"model={m['gops_mm2']:.2f} paper={p[design]['gops_mm2']}")
    emit("table2_perf_vs_cheng2024", 0.0,
         f"model={t2['l2r_cipu']['gops']/p['cheng2024']['gops']:.2f}x paper=6.22x")
    emit("table2_energy_vs_cheng2024", 0.0,
         f"model={t2['l2r_cipu']['tops_w']/p['cheng2024']['tops_w']:.1f}x paper=15x")
    emit("table2_perf_vs_eyeriss", 0.0,
         f"model={t2['l2r_cipu']['gops']/p['eyeriss']['gops']:.2f}x paper=1.06x")
    emit("table2_area_vs_eyeriss", 0.0,
         f"model={t2['l2r_cipu']['gops_mm2']/p['eyeriss']['gops_mm2']:.2f}x paper=53.45x")


def vgg16_cycles():
    from repro.core.cycle_model import (VGG16_CONV_LAYERS, layer_cycles,
                                        network_cycles, AcceleratorConfig)
    cfg = AcceleratorConfig()
    for layer in VGG16_CONV_LAYERS:
        c_l2r = layer_cycles(layer, cfg, l2r=True)
        c_base = layer_cycles(layer, cfg, l2r=False)
        emit(f"vgg16_cycles_{layer.name}", 0.0,
             f"l2r={c_l2r} baseline={c_base} speedup={c_base/c_l2r:.3f}x")
    tot_l, tot_b = network_cycles(l2r=True), network_cycles(l2r=False)
    emit("vgg16_cycles_total", 0.0,
         f"l2r={tot_l} baseline={tot_b} speedup={tot_b/tot_l:.3f}x paper=3.40x")


def kernel_bench():
    from repro.kernels.l2r_gemm import l2r_gemm_ref, int_gemm_ref
    rng = np.random.default_rng(0)
    for (m, k, n) in [(256, 512, 256), (512, 1024, 512)]:
        a = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int8))
        b = jnp.asarray(rng.integers(-128, 128, (k, n), dtype=np.int8))
        f_ref = jax.jit(lambda x, y: int_gemm_ref(x, y))
        f_l2r = jax.jit(lambda x, y: l2r_gemm_ref(x, y))
        f_l2r3 = jax.jit(lambda x, y: l2r_gemm_ref(x, y, levels=3))
        us_ref = _timeit(lambda: jax.block_until_ready(f_ref(a, b)))
        us_l2r = _timeit(lambda: jax.block_until_ready(f_l2r(a, b)))
        us_l2r3 = _timeit(lambda: jax.block_until_ready(f_l2r3(a, b)))
        gflop = 2 * m * k * n / 1e9
        emit(f"kernel_int_gemm_{m}x{k}x{n}", us_ref,
             f"gflops={gflop/(us_ref/1e6):.2f}")
        emit(f"kernel_l2r_gemm_full_{m}x{k}x{n}", us_l2r,
             f"planes=16pairs exact=True")
        emit(f"kernel_l2r_gemm_lv3_{m}x{k}x{n}", us_l2r3,
             f"planes=6pairs progressive=True")


def kernel_stacked_bench(json_path: str | None = None):
    """Pair-loop vs level-stacked schedule + backend dispatch regression.

    Emits kernel_stacked_* CSV rows and (optionally) a machine-readable
    BENCH_l2r_gemm.json so future PRs can diff the perf trajectory.
    """
    import json

    from repro.kernels.l2r_gemm import (l2r_gemm, l2r_gemm_ref,
                                        l2r_gemm_ref_stacked)

    rng = np.random.default_rng(0)
    records = []
    for (m, k, n) in [(256, 512, 256), (512, 1024, 512)]:
        a = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int8))
        b = jnp.asarray(rng.integers(-128, 128, (k, n), dtype=np.int8))
        for levels, tag in [(None, "full"), (3, "lv3")]:
            f_pair = jax.jit(lambda x, y, lv=levels: l2r_gemm_ref(x, y, levels=lv))
            f_stack = jax.jit(
                lambda x, y, lv=levels: l2r_gemm_ref_stacked(x, y, levels=lv))
            us_pair = _timeit(lambda: jax.block_until_ready(f_pair(a, b)))
            us_stack = _timeit(lambda: jax.block_until_ready(f_stack(a, b)))
            exact = bool(
                (np.asarray(f_pair(a, b)) == np.asarray(f_stack(a, b))).all())
            emit(f"kernel_stacked_jnp_{tag}_{m}x{k}x{n}", us_stack,
                 f"pair_us={us_pair:.1f} speedup={us_pair/us_stack:.2f}x "
                 f"bit_exact={exact}")
            records.append({
                "name": f"jnp_{tag}_{m}x{k}x{n}", "m": m, "k": k, "n": n,
                "levels": levels, "backend": "jnp",
                "pair_us": us_pair, "stacked_us": us_stack,
                "speedup": us_pair / us_stack, "bit_exact": exact,
            })
    # Pallas interpret mode: correctness-only (CPU interpretation is not a
    # timing signal) — one small shape, both schedules vs the jnp oracle.
    m, k, n = 128, 256, 128
    a = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int8))
    b = jnp.asarray(rng.integers(-128, 128, (k, n), dtype=np.int8))
    ref = np.asarray(l2r_gemm_ref(a, b))
    for sched in ("pairs", "stacked"):
        out = np.asarray(l2r_gemm(a, b, schedule=sched,
                                  backend="pallas-interpret"))
        exact = bool((out == ref).all())
        emit(f"kernel_stacked_pallas_interpret_{sched}_{m}x{k}x{n}",
             "untimed", f"bit_exact={exact}")
        records.append({
            "name": f"pallas_interpret_{sched}_{m}x{k}x{n}",
            "m": m, "k": k, "n": n, "levels": None,
            "backend": "pallas-interpret", "schedule": sched,
            "bit_exact": exact,
        })
    kernel_prestacked_bench(records)
    kernel_tile_sweep(records)
    if json_path:
        payload = {
            "bench": "l2r_gemm_level_stacking",
            "host_backend": jax.default_backend(),
            "timing_note": "jnp path timed on this host; pallas-interpret "
                           "rows are correctness-only",
            "rows": records,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
        emit("kernel_stacked_json", 0.0, f"wrote={json_path}")


def kernel_prestacked_bench(records: list):
    """Pre-stacked plane-operand amortization -> kernel_prestacked_* rows.

    What is measurable on this host: the jnp paths with the load-time
    weight plane-stack cache vs inline per-call extraction (the cache
    removes D mask+shift passes over the weight from every call — the
    decode/conv steady state), timed; the prestacked Pallas conv path
    (activation planes hoisted once per feature map, weight stack cached
    — ONE extraction per call instead of one per tap) is
    correctness-validated in interpret mode, its wall-clock being a
    real-TPU follow-up.
    """
    from repro.core.quant import PlaneOperands, QuantConfig, quantize_weights
    from repro.kernels.l2r_gemm import l2r_conv2d, l2r_gemm

    rng = np.random.default_rng(7)
    # GEMM: cached RHS plane stack vs per-call extraction (jnp stacked)
    for (m, k, n) in [(256, 2048, 512)]:
        a = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int8))
        b = jnp.asarray(rng.integers(-128, 128, (k, n), dtype=np.int8))
        pb = PlaneOperands.prepare_rhs(b)
        f_raw = jax.jit(lambda x, y: l2r_gemm(x, y))
        f_pre = jax.jit(lambda x, y: l2r_gemm(x, y))
        jax.block_until_ready(f_raw(a, b))
        jax.block_until_ready(f_pre(a, pb))
        exact = bool((np.asarray(f_raw(a, b)) == np.asarray(f_pre(a, pb))).all())
        us_raw, us_pre = _best_pair(
            lambda: jax.block_until_ready(f_raw(a, b)),
            lambda: jax.block_until_ready(f_pre(a, pb)), n=10)
        emit(f"kernel_prestacked_gemm_rhs_cache_{m}x{k}x{n}", us_pre,
             f"inline_us={us_raw:.1f} speedup={us_raw/us_pre:.2f}x "
             f"bit_exact={exact}")
        records.append({
            "name": f"prestacked_gemm_rhs_cache_{m}x{k}x{n}",
            "m": m, "k": k, "n": n, "backend": "jnp",
            "inline_us": us_raw, "prestacked_us": us_pre,
            "speedup": us_raw / us_pre, "bit_exact": exact,
        })
    # conv layer: a VGG-shaped 3x3 with and without the cached weight
    # stack (jnp: activation hoist is shared; the delta is the per-call
    # weight extraction the cache removes)
    cfg = QuantConfig()
    x = jnp.asarray(rng.standard_normal((4, 32, 32, 64)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((3, 3, 64, 64)).astype(np.float32))
    plain = quantize_weights(w, cfg)
    pre = quantize_weights(w, cfg, prestack=True, plane_axis=-2)
    f_plain = jax.jit(lambda xx: l2r_conv2d(xx, None, cfg=cfg, w_q=plain,
                                            backend="jnp"))
    f_pre = jax.jit(lambda xx: l2r_conv2d(xx, None, cfg=cfg, w_q=pre,
                                          backend="jnp"))
    jax.block_until_ready(f_plain(x))
    jax.block_until_ready(f_pre(x))
    exact = bool((np.asarray(f_plain(x)) == np.asarray(f_pre(x))).all())
    us_plain, us_pre = _best_pair(
        lambda: jax.block_until_ready(f_plain(x)),
        lambda: jax.block_until_ready(f_pre(x)), n=5)
    emit("kernel_prestacked_conv_w_cache_4x32x32x64", us_pre,
         f"inline_us={us_plain:.1f} speedup={us_plain/us_pre:.2f}x "
         f"bit_exact={exact}")
    records.append({
        "name": "prestacked_conv_w_cache_4x32x32x64", "backend": "jnp",
        "inline_us": us_plain, "prestacked_us": us_pre,
        "speedup": us_plain / us_pre, "bit_exact": exact,
    })
    # prestacked Pallas conv path: correctness in interpret mode (the
    # per-feature-map hoist + cached weight stack reach the pre-stacked
    # kernel entries; timing is a real-TPU follow-up)
    xs_ = jnp.asarray(rng.standard_normal((1, 8, 8, 5)).astype(np.float32))
    ws_ = jnp.asarray(rng.standard_normal((3, 3, 5, 6)).astype(np.float32))
    pre_s = quantize_weights(ws_, cfg, prestack=True, plane_axis=-2)
    o_ref = np.asarray(l2r_conv2d(xs_, None, cfg=cfg,
                                  w_q=quantize_weights(ws_, cfg),
                                  backend="jnp"))
    o_pal = np.asarray(l2r_conv2d(xs_, None, cfg=cfg, w_q=pre_s,
                                  backend="pallas-interpret"))
    exact = bool((o_ref == o_pal).all())
    emit("kernel_prestacked_conv_pallas_interpret_1x8x8x5", "untimed",
         f"bit_exact={exact}")
    records.append({
        "name": "prestacked_conv_pallas_interpret_1x8x8x5",
        "backend": "pallas-interpret", "bit_exact": exact,
    })


def kernel_tile_sweep(records: list):
    """(bm, bk, bn) tile sweep of the stacked Pallas kernel.

    On a TPU host every configuration is compiled and timed (the tuning
    signal the ROADMAP follow-up needs); elsewhere each tile shape is
    validated bit-exact in interpret mode so the sweep machinery itself
    is exercised per CI run.  CHECK_MODE trims the sweep to two configs.
    """
    from repro.kernels.l2r_gemm import l2r_gemm, l2r_gemm_ref

    on_tpu = jax.default_backend() == "tpu"
    tiles = [(128, 128, 128), (128, 256, 128), (128, 512, 128),
             (256, 256, 128), (128, 256, 256)]
    if CHECK_MODE:
        tiles = tiles[:2]
    m, k, n = (1024, 2048, 1024) if on_tpu else (256, 512, 128)
    rng = np.random.default_rng(8)
    a = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int8))
    b = jnp.asarray(rng.integers(-128, 128, (k, n), dtype=np.int8))
    ref = np.asarray(l2r_gemm_ref(a, b))
    backend = "pallas-tpu" if on_tpu else "pallas-interpret"
    for (bm, bk, bn) in tiles:
        fn = jax.jit(lambda x, y, t=(bm, bk, bn): l2r_gemm(
            x, y, bm=t[0], bk=t[1], bn=t[2], backend=backend))
        out = np.asarray(fn(a, b))
        exact = bool((out == ref).all())
        row = {"name": f"tilesweep_{bm}x{bk}x{bn}_{m}x{k}x{n}",
               "m": m, "k": k, "n": n, "bm": bm, "bk": bk, "bn": bn,
               "backend": backend, "bit_exact": exact}
        if on_tpu:
            us = _timeit(lambda: jax.block_until_ready(fn(a, b)), n=10)
            row["us"] = us
            emit(f"kernel_tilesweep_{bm}x{bk}x{bn}_{m}x{k}x{n}", us,
                 f"bit_exact={exact}")
        else:
            emit(f"kernel_tilesweep_{bm}x{bk}x{bn}_{m}x{k}x{n}", "untimed",
                 f"bit_exact={exact} (interpret: correctness only)")
        records.append(row)


def ipu_bench():
    from repro.core.ipu import simulate_cipu
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.integers(0, 256, (64, 72)), jnp.int32)
    b = jnp.asarray(rng.integers(0, 256, (64, 72)), jnp.int32)
    f = jax.jit(lambda x, y: simulate_cipu(x, y, 8).final)
    us = _timeit(lambda: jax.block_until_ready(f(a, b)))
    emit("ipu_cycle_accurate_sim_64sops", us,
         f"cycles_per_sop=64 sops_per_s={64/(us/1e6):.0f}")


def online_stats():
    from repro.core.progressive import (earliest_decision_level,
                                        progressive_matmul)
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.integers(-128, 128, (256, 64), dtype=np.int8))
    b = jnp.asarray(rng.integers(-128, 128, (64, 32), dtype=np.int8))
    res = progressive_matmul(a, b)
    lv = np.asarray(earliest_decision_level(res))
    emit("online_early_exit_mean_level", 0.0,
         f"mean={lv.mean():.2f} of {res.partial.shape[0]-1} "
         f"(argmax decided after {100*(lv.mean()+1)/res.partial.shape[0]:.0f}% of stream)")


def _load_calibrate_levels():
    """Import tools/calibrate_levels.py by path (tools/ is not a
    package: the calibration controller is an offline CLI that the
    bench reuses for fitting and the frontier-row schema)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "..", "tools", "calibrate_levels.py")
    spec = importlib.util.spec_from_file_location("calibrate_levels", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def precision_policy_bench(rows: list):
    """Per-request precision classes on the decisive prototype head:
    the accuracy-vs-levels-vs-latency frontier of the LevelPolicy
    operating points — ``exact`` (full-depth scan), every ``budget(L)``
    clamp, the ``bounded`` margin walk, and the budget CALIBRATED from
    the bounded walk's observed exit histogram
    (tools/calibrate_levels.py, coverage 0.99).  Appends one
    ``precision_policy_frontier`` record (one frontier row per
    operating point) to ``rows`` for BENCH_progressive.json.
    """
    from repro.core.policy import LevelPolicy
    from repro.core.progressive import streaming_argmax
    from repro.core.quant import QuantConfig
    from repro.models.protohead import prototype_head

    cal = _load_calibrate_levels()
    cfg = QuantConfig()
    n_levels = 2 * cfg.planes - 1
    k, classes, m = (512, 32, 64) if CHECK_MODE else (2048, 64, 256)
    xq, xs, w_q, _ = prototype_head(np.random.default_rng(44), k, classes,
                                    m, cfg=cfg)

    def run(policy, early_exit=True):
        f = jax.jit(lambda a, s: streaming_argmax(
            a, w_q.q, s, w_q.scale, cfg.n_bits, cfg.log2_radix,
            early_exit=early_exit, policy=policy)[1:])
        tok, lv = jax.tree.map(np.asarray, f(xq, xs))
        return f, tok, lv

    # exact class = the full-depth scan: the accuracy reference AND the
    # latency baseline every other operating point is timed against
    f_exact, tok_exact, lv_exact = run(LevelPolicy.exact(m),
                                       early_exit=False)
    frontier = [cal.frontier_row("exact", n_levels, n_levels, 1.0,
                                 float(lv_exact.mean()))]

    def point(label, policy, levels):
        f, tok, lv = run(policy)
        us_e, us_p = _best_pair(
            lambda: jax.block_until_ready(f_exact(xq, xs)),
            lambda: jax.block_until_ready(f(xq, xs)), n=5)
        frontier.append(cal.frontier_row(
            label, levels, n_levels, float((tok == tok_exact).mean()),
            float(lv.mean()), us=us_p, full_us=us_e))
        return lv

    for lvl in range(1, n_levels + 1):
        point(f"budget({lvl})", LevelPolicy.budget(lvl, m), lvl)
    lv_b = point("bounded(0)", LevelPolicy.bounded(m),
                 int(lv_exact.max()) + 1)
    # the bounded walk is sound (agreement 1.0 by construction); its
    # exit histogram is what serving stats() observe — fit the smallest
    # clamp covering 99% of those exits and measure the fitted point
    coverage = 0.99
    fitted = cal.fit_budget(np.bincount(lv_b, minlength=n_levels),
                            coverage=coverage)
    point(f"calibrated:budget({fitted})", LevelPolicy.budget(fitted, m),
          fitted)
    frontier[-1].update(calibrated=True, coverage=coverage,
                        fitted_from="bounded(0)")
    agree = frontier[-1]["agreement_vs_exact"]
    emit("precision_policy_frontier", frontier[-1].get("us_per_call", 0.0),
         f"points={len(frontier)} calibrated_budget={fitted}/{n_levels} "
         f"calibrated_agreement={agree:.3f} "
         f"bounded_mean_exit={float(lv_b.mean()):.2f}")
    rows.append({
        "name": "precision_policy_frontier", "n_levels": n_levels,
        "k": k, "classes": classes, "rows": m,
        "coverage": coverage, "calibrated_budget_levels": fitted,
        "frontier": frontier,
    })


def progressive_bench(json_path: str | None = None):
    """Streaming early-exit suite -> progressive_* rows + JSON record.

    The VGG-16 logit benchmark: the L2R trunk runs exactly and the fc8
    head streams most-significant-level first, each image committing its
    class at its earliest sound level.  An untrained random head has
    exchangeable logits (top-1 margins ~0), so the head is **prototype-
    calibrated** — class c's weight column is the trunk feature of a
    reference image — which reproduces the decisive-margin regime a
    trained classifier operates in.  Wall-clock saved is measured by
    timing the stacked head GEMM truncated at the mean exit level
    against the full 2D-1-level stream (identical operands).
    """
    import json

    from repro.core.quant import QuantConfig, quantize
    from repro.kernels.l2r_gemm import l2r_gemm
    from repro.models.cnn import (_vgg16_trunk, vgg16_build,
                                  vgg16_classify_progressive,
                                  vgg16_quantize_weights)
    from repro.models.common import materialize

    cfg = QuantConfig()
    n_classes = 32
    n_levels = 2 * cfg.planes - 1
    rng = np.random.default_rng(0)
    params = materialize(vgg16_build(n_classes=n_classes),
                         jax.random.PRNGKey(0))
    cache = vgg16_quantize_weights(params, cfg)
    # prototype-calibrate the head: one reference image per class, its
    # CENTERED trunk feature becomes that class's fc8 column (random-init
    # VGG features share a large all-positive common mode; centering
    # removes it so class margins are decisive, and the matching bias
    # -mu @ W makes the logit the centered-prototype similarity)
    ref = jnp.asarray(rng.standard_normal((n_classes, 32, 32, 3))
                      .astype(np.float32))
    feats, _ = _vgg16_trunk(params, ref, cfg, None, cache, None)
    f_np = np.asarray(feats, np.float32)
    mu = f_np.mean(0, keepdims=True)
    w8 = (f_np - mu).T  # (4096, n_classes)
    w8 = w8 / (np.linalg.norm(w8, axis=0, keepdims=True) + 1e-9)
    params["fc8"]["w"] = jnp.asarray(w8)
    params["fc8"]["b"] = jnp.asarray(-(mu @ w8)[0])
    cache = vgg16_quantize_weights(params, cfg)
    # queries: noisy copies of reference images
    sel = rng.integers(0, n_classes, 16)
    imgs = ref[sel] + 0.1 * jnp.asarray(
        rng.standard_normal((16, 32, 32, 3)).astype(np.float32))
    pred, lv, _ = vgg16_classify_progressive(params, imgs, cfg,
                                             weights_q=cache)
    lv = np.asarray(lv)
    acc = float((np.asarray(pred) == sel).mean())
    mean_exit = float(lv.mean())
    hist = np.bincount(lv, minlength=n_levels).tolist()
    emit("progressive_vgg16_logit_exit_level", 0.0,
         f"mean={mean_exit:.2f} of {n_levels - 1} "
         f"early_frac={float((lv < n_levels - 1).mean()):.2f} "
         f"proto_acc={acc:.2f}")

    # wall-clock saved: the stacked head GEMM at the mean exit depth vs
    # the full stream, on the real head operands (rows tiled to a
    # serving-sized batch so the timing is dominated by the GEMM, not
    # dispatch noise); _best_pair interleaving throughout
    best_pair = _best_pair

    x, _ = _vgg16_trunk(params, imgs, cfg, None, cache, None)
    xq, xs = quantize(x, cfg, axis=0)
    xqt = jnp.tile(xq, (16, 1))  # (256, 4096)
    wq = cache["fc8"].q
    trunc = int(round(mean_exit)) + 1
    f_full = jax.jit(lambda a, b: l2r_gemm(a, b, cfg.n_bits, cfg.log2_radix))
    f_trunc = jax.jit(
        lambda a, b: l2r_gemm(a, b, cfg.n_bits, cfg.log2_radix, levels=trunc))
    jax.block_until_ready(f_full(xqt, wq))  # compile untimed
    jax.block_until_ready(f_trunc(xqt, wq))
    us_full, us_trunc = best_pair(
        lambda: jax.block_until_ready(f_full(xqt, wq)),
        lambda: jax.block_until_ready(f_trunc(xqt, wq)), n=10)
    saved = 1.0 - us_trunc / us_full
    emit("progressive_vgg16_head_gemm_truncated", us_trunc,
         f"full_us={us_full:.1f} levels={trunc}/{n_levels} "
         f"wallclock_saved={saved * 100:.0f}%")

    # early-exit SCAN wall-clock: the while-loop emitter stops the level
    # loop inside one fused computation the moment every row has decided
    # — measured against the fixed-length scan on the SAME head operands
    # and decision fold (not a static truncation: the exit level is
    # discovered at runtime).  Rows are tiled (decision state is
    # per-row-identical under tiling) so the timing is GEMM-dominated.
    from repro.core.progressive import streaming_argmax

    ws = cache["fc8"].scale
    bias = params["fc8"]["b"]
    xst = jnp.tile(xs, (16, 1))
    f_scan = jax.jit(lambda a, s: streaming_argmax(
        a, wq, s, ws, cfg.n_bits, cfg.log2_radix, bias=bias)[1])
    f_while = jax.jit(lambda a, s: streaming_argmax(
        a, wq, s, ws, cfg.n_bits, cfg.log2_radix, bias=bias,
        early_exit=True)[1])
    tok_scan = np.asarray(f_scan(xqt, xst))
    tok_while = np.asarray(f_while(xqt, xst))
    assert (tok_scan == tok_while).all(), "early-exit changed a decision"
    us_scan, us_while = best_pair(
        lambda: jax.block_until_ready(f_scan(xqt, xst)),
        lambda: jax.block_until_ready(f_while(xqt, xst)), n=10)
    ee_saved = 1.0 - us_while / us_scan
    emit("progressive_vgg16_head_early_exit_scan", us_while,
         f"scan_us={us_scan:.1f} batch_exit_level={int(lv.max())}/"
         f"{n_levels - 1} wallclock_saved={ee_saved * 100:.0f}%")

    # per-image tiles exit at each image's OWN level (a batch tile exits
    # at its slowest row): the serving-shaped measurement
    tiles = [(jnp.tile(xq[i:i + 1], (128, 1)),
              jnp.tile(xs[i:i + 1], (128, 1)))
             for i in range(xq.shape[0])]
    for a, s in tiles[:1]:  # compile the (128, K) traces untimed
        jax.block_until_ready(f_scan(a, s))
        jax.block_until_ready(f_while(a, s))
    us_scan1, us_while1 = best_pair(
        lambda: [jax.block_until_ready(f_scan(a, s)) for a, s in tiles],
        lambda: [jax.block_until_ready(f_while(a, s)) for a, s in tiles],
        n=4)
    ee_saved1 = 1.0 - us_while1 / us_scan1
    emit("progressive_vgg16_head_early_exit_per_image", us_while1,
         f"scan_us={us_scan1:.1f} mean_exit={mean_exit:.2f}/{n_levels - 1} "
         f"wallclock_saved={ee_saved1 * 100:.0f}%")

    # decisive-margin head: a prototype classifier whose logit margins
    # clear the tail bound around mid-stream (exit ~3-4 of 6) — shows the
    # early-exit win scaling with the margin regime (the VGG head above
    # decides at 5/6, so it can only ever skip one of seven levels).
    # Own rng: the shared stream feeds the pre-existing random-head
    # trajectory row below, which must stay draw-for-draw comparable
    # across commits.
    from repro.models.protohead import prototype_head

    dk, dclasses, drows = 2048, 64, 256
    dxq, dxs, dw_q, _ = prototype_head(np.random.default_rng(42), dk,
                                       dclasses, drows, cfg=cfg)
    g_scan = jax.jit(lambda a, s: streaming_argmax(
        a, dw_q.q, s, dw_q.scale, cfg.n_bits, cfg.log2_radix)[1:])
    g_while = jax.jit(lambda a, s: streaming_argmax(
        a, dw_q.q, s, dw_q.scale, cfg.n_bits, cfg.log2_radix,
        early_exit=True)[1:])
    (dtok_s, dlv_s) = jax.tree.map(np.asarray, g_scan(dxq, dxs))
    (dtok_w, dlv_w) = jax.tree.map(np.asarray, g_while(dxq, dxs))
    assert (dtok_s == dtok_w).all() and (dlv_s == dlv_w).all()
    us_dscan, us_dwhile = best_pair(
        lambda: jax.block_until_ready(g_scan(dxq, dxs)),
        lambda: jax.block_until_ready(g_while(dxq, dxs)), n=10)
    d_saved = 1.0 - us_dwhile / us_dscan
    emit("progressive_decisive_head_early_exit_scan", us_dwhile,
         f"scan_us={us_dscan:.1f} batch_exit_level={int(dlv_w.max())}/"
         f"{n_levels - 1} mean_exit={float(dlv_w.mean()):.2f} "
         f"wallclock_saved={d_saved * 100:.0f}%")

    # decode-step weight-stack cache: the streamed head with the
    # load-time window-padded RHS plane stack (prepare_params prestack)
    # vs per-step weight plane extraction + window padding — decisions
    # verified identical before timing.  Decode-shaped operands (small
    # batch x large vocab): the per-step operand prep scales with the
    # WEIGHT, the GEMM with the batch, so this is the regime the cache
    # targets.  The stack is a jit ARGUMENT (as in serving, where it
    # lives in the params tree), not a baked closure constant.
    from repro.core.quant import PlaneOperands

    hk, hv, hm = 2048, 2048, 8  # decode: 8 slots, 2k hidden, 2k vocab
    hrng = np.random.default_rng(43)
    hxq = jnp.asarray(hrng.integers(-128, 128, (hm, hk), dtype=np.int8))
    hxs = jnp.asarray(hrng.uniform(0.01, 0.02, (hm, 1)).astype(np.float32))
    hwq = jnp.asarray(hrng.integers(-128, 128, (hk, hv), dtype=np.int8))
    hws = jnp.asarray(hrng.uniform(0.01, 0.02, (1, hv)).astype(np.float32))
    h_planes = PlaneOperands.prepare_rhs(hwq, cfg.n_bits, cfg.log2_radix,
                                         window_pad=True)
    h_step = jax.jit(lambda a, s, w: streaming_argmax(
        a, w, s, hws, cfg.n_bits, cfg.log2_radix)[1:])
    (htok_i, hlv_i) = jax.tree.map(np.asarray, h_step(hxq, hxs, hwq))
    (htok_c, hlv_c) = jax.tree.map(np.asarray, h_step(hxq, hxs, h_planes))
    assert (htok_i == htok_c).all() and (hlv_i == hlv_c).all()
    us_draw, us_dcache = best_pair(
        lambda: jax.block_until_ready(h_step(hxq, hxs, hwq)),
        lambda: jax.block_until_ready(h_step(hxq, hxs, h_planes)), n=10)
    c_saved = 1.0 - us_dcache / us_draw
    emit("progressive_decode_head_weight_stack_cache", us_dcache,
         f"inline_us={us_draw:.1f} wallclock_saved={c_saved * 100:.0f}% "
         f"batch={hm} k={hk} vocab={hv} (per-step weight plane "
         f"extraction amortized to load time)")

    # random classifier heads (the old online_* setting) for the JSON
    # trajectory: margins come from genuine top-order statistics
    from repro.core.progressive import (earliest_decision_level,
                                        progressive_matmul)
    rows = [{
        "name": "vgg16_logit_head", "n_levels": n_levels,
        "mean_exit_level": mean_exit, "exit_level_hist": hist,
        "early_exit_frac": float((lv < n_levels - 1).mean()),
        "prototype_accuracy": acc, "images": int(lv.size),
        "head_full_us": us_full, "head_truncated_us": us_trunc,
        "truncated_levels": trunc,
        "wallclock_saved_frac": saved,
    }, {
        # the early-exit WHILE scan: runtime-discovered exit, decisions
        # verified identical to the fixed scan before timing
        "name": "vgg16_logit_head_early_exit_scan", "n_levels": n_levels,
        "batch": {
            "scan_us": us_scan, "early_exit_us": us_while,
            "exit_level": int(lv.max()),
            "wallclock_saved_frac": ee_saved,
        },
        "per_image": {
            "scan_us": us_scan1, "early_exit_us": us_while1,
            "mean_exit_level": mean_exit,
            "wallclock_saved_frac": ee_saved1,
        },
    }, {
        "name": "decisive_head_early_exit_scan", "n_levels": n_levels,
        "k": dk, "classes": dclasses, "rows": drows,
        "scan_us": us_dscan, "early_exit_us": us_dwhile,
        "batch_exit_level": int(dlv_w.max()),
        "mean_exit_level": float(dlv_w.mean()),
        "wallclock_saved_frac": d_saved,
    }, {
        # per-decode-step operand amortization: cached window-padded RHS
        # plane stack vs per-step extraction, decisions identical
        "name": "decode_head_weight_stack_cache", "n_levels": n_levels,
        "k": hk, "vocab": hv, "batch": hm,
        "inline_us": us_draw, "cached_stack_us": us_dcache,
        "wallclock_saved_frac": c_saved,
    }]
    # multi-device consensus walk rows (virtual-device subprocess)
    progressive_sharded_bench(rows)
    # per-request precision classes: the calibrated policy frontier
    precision_policy_bench(rows)
    a = jnp.asarray(rng.integers(-128, 128, (256, 64), dtype=np.int8))
    b = jnp.asarray(rng.integers(-128, 128, (64, 32), dtype=np.int8))
    res = progressive_matmul(a, b)
    rlv = np.asarray(earliest_decision_level(res))
    rows.append({
        "name": "random_head_256x64x32", "n_levels": int(res.partial.shape[0]),
        "mean_exit_level": float(rlv.mean()),
        "exit_level_hist": np.bincount(
            rlv, minlength=res.partial.shape[0]).tolist(),
        "early_exit_frac": float((rlv < res.partial.shape[0] - 1).mean()),
    })
    if json_path:
        payload = {
            "bench": "progressive_streaming",
            "host_backend": jax.default_backend(),
            "note": "vgg16 head is prototype-calibrated (random-init "
                    "margins are ~0 by construction; trained classifiers "
                    "operate in the decisive-margin regime measured here)",
            "rows": rows,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
        emit("progressive_json", 0.0, f"wrote={json_path}")


# Body of the multi-device bench subprocess: a decode-head-shaped
# streaming argmax, single-device vs the shard_mapped consensus walk on
# local (data, model) meshes.  Decisions and exit levels are verified
# bit-exact (scan AND early-exit while) before any timing.  Shapes,
# repetition counts, and the mesh list are prepended by the caller.
SHARDED_BENCH_BODY = r"""
import json
import sys
import time
sys.path.insert(0, "src")
import numpy as np
import jax, jax.numpy as jnp
from repro.core.progressive import streaming_argmax
from repro.launch.mesh import make_local_mesh

rng = np.random.default_rng(43)
xq = jnp.asarray(rng.integers(-128, 128, (B, K), dtype=np.int8))
xs = jnp.asarray(rng.uniform(0.01, 0.02, (B, 1)).astype(np.float32))
wq = jnp.asarray(rng.integers(-128, 128, (K, V), dtype=np.int8))
ws = jnp.asarray(rng.uniform(0.01, 0.02, (1, V)).astype(np.float32))


def timeit(fn):
    t0 = time.perf_counter()
    for _ in range(REPS):
        fn()
    return (time.perf_counter() - t0) / REPS * 1e6


f_single = jax.jit(lambda a, s: streaming_argmax(a, wq, s, ws)[1:])
ref = jax.tree.map(np.asarray, f_single(xq, xs))
rows = []
for name in MESHES:
    d, m = (int(t) for t in name.split("x"))
    mesh = make_local_mesh(d, m)
    f_sh = jax.jit(lambda a, s, mesh=mesh: streaming_argmax(
        a, wq, s, ws, mesh=mesh)[1:])
    got = jax.tree.map(np.asarray, f_sh(xq, xs))
    exact = all(bool((np.asarray(a) == np.asarray(b)).all())
                for a, b in zip(ref, got))
    f_ee = jax.jit(lambda a, s, mesh=mesh: streaming_argmax(
        a, wq, s, ws, mesh=mesh, early_exit=True)[1:])
    got_ee = jax.tree.map(np.asarray, f_ee(xq, xs))
    exact_ee = all(bool((np.asarray(a) == np.asarray(b)).all())
                   for a, b in zip(ref, got_ee))
    # parity is the precondition of the timing claim: fail the bench
    # loudly instead of shipping a non-bit-exact row
    assert exact and exact_ee, (
        f"sharded walk lost bit-parity on mesh {name}: "
        f"scan={exact} early_exit={exact_ee}")
    best_s = best_m = float("inf")
    for _ in range(ROUNDS):  # interleaved min-of-rounds
        best_s = min(best_s,
                     timeit(lambda: jax.block_until_ready(f_single(xq, xs))))
        best_m = min(best_m,
                     timeit(lambda: jax.block_until_ready(f_sh(xq, xs))))
    rows.append(dict(
        name="sharded_decode_head_" + name, mesh=name, batch=B, k=K,
        vocab=V, devices=d * m, single_us=best_s, sharded_us=best_m,
        speedup=best_s / best_m, bit_exact=exact,
        early_exit_bit_exact=exact_ee,
        platform=jax.default_backend(),
        note=("host-platform virtual devices share one CPU: this measures "
              "partitioning overhead, not parallel scaling"
              if jax.default_backend() == "cpu" else "")))
print("JSON:" + json.dumps(rows))
"""


def progressive_sharded_bench(rows: list):
    """Multi-device consensus head walk -> progressive_sharded_* rows.

    On the CPU it runs in a subprocess with 8 virtual host-platform
    devices (the XLA device-count flag is consumed at jax init, so this
    process cannot grow devices itself).  On an accelerator this process
    already holds the devices, so the rows run in-process on the meshes
    that fit them, or are reported skipped on a single device.  Each row records the single-device
    streaming argmax vs the shard_mapped walk on a (data, model) local
    mesh — tokens/exit levels verified bit-exact (both control flows)
    before timing.  CHECK_MODE trims shapes, meshes, and repetitions.
    """
    import json
    import subprocess

    from repro.launch.mesh import virtual_device_env

    b, k, v = (4, 256, 512) if CHECK_MODE else (8, 2048, 2048)
    reps, rounds = (1, 1) if CHECK_MODE else (10, 3)
    meshes = ["1x2"] if CHECK_MODE else ["1x2", "1x4", "2x4"]
    on_cpu = jax.default_backend() == "cpu"
    if not on_cpu:
        # this process already holds the accelerator, and a chip belongs
        # to one process: run on the real devices in-process instead of
        # in a child that could not reach them
        n_dev = len(jax.devices())
        meshes = [mm for mm in meshes
                  if np.prod([int(t) for t in mm.split("x")]) <= n_dev]
        if not meshes:
            emit("progressive_sharded_skipped", "n/a",
                 f"{n_dev} {jax.default_backend()} device(s): the consensus "
                 f"rows need >= 2 devices in this process")
            return
    header = (f"B, K, V = {b}, {k}, {v}\n"
              f"REPS, ROUNDS = {reps}, {rounds}\n"
              f"MESHES = {meshes!r}\n")
    if on_cpu:
        out = subprocess.run(
            [sys.executable, "-c", header + SHARDED_BENCH_BODY],
            capture_output=True, text=True,
            cwd=os.path.join(os.path.dirname(__file__), ".."),
            env=virtual_device_env(8), timeout=1800)
        if out.returncode != 0:
            raise RuntimeError(
                f"sharded bench subprocess failed:\n{out.stderr[-3000:]}")
        payload = [ln for ln in out.stdout.splitlines()
                   if ln.startswith("JSON:")][-1]
        new_rows = json.loads(payload[len("JSON:"):])
    else:
        scope: dict = {}
        exec(header + SHARDED_BENCH_BODY, scope)
        new_rows = scope["rows"]
    for r in new_rows:
        emit(f"progressive_{r['name']}", r["sharded_us"],
             f"single_us={r['single_us']:.1f} speedup={r['speedup']:.2f}x "
             f"devices={r['devices']} bit_exact={r['bit_exact']} "
             f"early_exit_bit_exact={r['early_exit_bit_exact']}")
    rows.extend(new_rows)


def serving_bench(json_path: str | None = None):
    """Gateway serving under synthetic Poisson traffic -> serving_* rows
    + BENCH_serving.json.

    The smoke LM serves a mixed-prompt-length request trace through
    `ServingGateway` (bucketed AOT prefill, donated decode state, async
    emit) with the Poisson arrival process replayed in REAL time
    (`run(realtime=True)` honors the pre-stamped `t_arrival` instants),
    so TTFT includes genuine queueing delay.  Measured per mode:
    tokens/s and p50/p99 time-to-first-token / per-output-token
    latency, with MSDF early exit ON vs OFF — the paper's saved
    significance levels showing up as saved fleet latency.  Before any
    timing, the gateway's output streams are asserted bit-identical to
    the plain `ContinuousBatcher` serving the same request set (both
    early-exit modes commit identical tokens by construction).
    CHECK_MODE trims requests, slots, and generation lengths.
    """
    import dataclasses as _dc
    import json
    import time

    from repro.configs import get_smoke
    from repro.core.quant import QuantConfig
    from repro.models.common import materialize
    from repro.models.transformer import lm_build
    from repro.serve import ContinuousBatcher, Request, ServingGateway
    from repro.serve.engine import prepare_params

    cfg = _dc.replace(get_smoke("smollm-135m"), l2r=QuantConfig())
    params = prepare_params(cfg, materialize(lm_build(cfg),
                                             jax.random.PRNGKey(0)))
    if CHECK_MODE:
        n_req, n_slots, max_len, max_new, group = 6, 2, 32, 4, 2
        mean_gap = 0.005
    else:
        n_req, n_slots, max_len, max_new, group = 48, 8, 64, 16, 4
        mean_gap = 0.02
    rng = np.random.default_rng(7)
    lens = rng.integers(3, max_len - max_new, n_req)  # spans the buckets
    prompts = [rng.integers(0, cfg.vocab, (int(L),)).astype(np.int32)
               for L in lens]
    gaps = rng.exponential(mean_gap, n_req)  # one trace, replayed per mode
    offsets = np.cumsum(gaps)

    def make_reqs():
        return [Request(uid=i, prompt=p, max_new_tokens=max_new)
                for i, p in enumerate(prompts)]

    # bit-parity reference: the plain batcher, same request set
    ref = make_reqs()
    eng = ContinuousBatcher(cfg, params, n_slots=n_slots, max_len=max_len,
                            progressive=True, early_exit=True)
    for r in ref:
        eng.submit(r)
    eng.run(max_steps=100_000)

    rows = []
    for ee in (True, False):
        reqs = make_reqs()
        gw = ServingGateway(cfg, params, n_slots=n_slots, max_len=max_len,
                            progressive=True, early_exit=ee,
                            prefill_group=group)
        # stamp arrivals AFTER construction: AOT warmup is startup cost,
        # not queueing delay
        t0 = time.perf_counter() + 0.01
        for r, dt in zip(reqs, offsets):
            r.t_arrival = t0 + float(dt)
            gw.submit(r)
        gw.run(realtime=True)
        gw.close()
        st = gw.stats()
        for a, b in zip(ref, reqs):
            assert a.output == b.output, \
                ("gateway/batcher token divergence", ee, a.uid)
        mode = "on" if ee else "off"
        emit(f"serving_gateway_early_exit_{mode}",
             st["tpot_p50_s"] * 1e6,
             f"tok_s={st['tokens_per_s']:.1f} "
             f"ttft_p50_ms={st['ttft_p50_s'] * 1e3:.1f} "
             f"ttft_p99_ms={st['ttft_p99_s'] * 1e3:.1f} "
             f"tpot_p99_ms={st['tpot_p99_s'] * 1e3:.1f} "
             f"reqs={n_req} slots={n_slots} "
             f"mean_exit={st['mean_exit_level']:.2f}/{st['n_levels'] - 1}")
        rows.append({
            "name": f"poisson_early_exit_{mode}",
            "early_exit": ee,
            "requests": n_req, "n_slots": n_slots, "max_len": max_len,
            "max_new_tokens": max_new, "prefill_group": group,
            "buckets": st["buckets"],
            "prompt_len_min": int(lens.min()),
            "prompt_len_max": int(lens.max()),
            "mean_interarrival_s": mean_gap,
            "tokens": st["tokens"], "completed": st["completed"],
            "decode_steps": st["steps"], "prefill_dispatches": st["prefills"],
            "tokens_per_s": st["tokens_per_s"],
            "ttft_p50_s": st["ttft_p50_s"], "ttft_p99_s": st["ttft_p99_s"],
            "tpot_p50_s": st["tpot_p50_s"], "tpot_p99_s": st["tpot_p99_s"],
            "n_levels": st["n_levels"],
            "mean_exit_level": st["mean_exit_level"],
            "mean_levels_saved": st["mean_levels_saved"],
            "bit_identical_to_batcher": True,
        })
    if json_path:
        payload = {
            "bench": "serving_gateway",
            "host_backend": jax.default_backend(),
            "model": "smollm-135m (smoke)",
            "note": "Poisson arrivals replayed in real time; TTFT "
                    "includes queueing delay.  Gateway output asserted "
                    "bit-identical to the plain ContinuousBatcher for "
                    "the same request set before timing.",
            "rows": rows,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
        emit("serving_json", 0.0, f"wrote={json_path}")


def attention_bench(json_path: str | None = None):
    """Digit-serial attention decode/prefill -> attention_* rows +
    BENCH_attention.json.

    One KV cache, four decode modes: the float oracle; quantized QK^T
    that re-quantizes + re-extracts K planes from the float cache every
    step (what decode costs WITHOUT the incremental stack); the
    incrementally plane-stacked cache (extraction already paid at append
    time); and margin-bounded early exit on top of the plane cache.
    Parity is asserted before any timing: plane-cache scores are
    bit-identical to re-extraction, early exit at tight tolerance is
    bit-identical to full depth, and the quantized output tracks the
    float oracle to W8A8 noise.  The flash-fused level-walk kernel runs
    as an interpret-mode correctness row (never timed off-TPU).
    CHECK_MODE trims shapes.
    """
    import json

    from repro.core.quant import QuantConfig
    from repro.models.attention import (chunked_attention, decode_attention,
                                        init_kv_cache, update_kv_cache)

    cfg = QuantConfig()
    if CHECK_MODE:
        b, length, kvh, g, dh, sq = 2, 64, 2, 2, 32, 32
    else:
        b, length, kvh, g, dh, sq = 4, 512, 4, 2, 64, 256
    h = kvh * g
    rng = np.random.default_rng(11)
    cache = init_kv_cache(b, length, kvh, dh, jnp.float32, quant=cfg)
    ks = jnp.asarray(rng.standard_normal((b, length, kvh, dh)), jnp.float32)
    vs = jnp.asarray(rng.standard_normal((b, length, kvh, dh)), jnp.float32)
    pos = jnp.asarray(np.tile(np.arange(length), (b, 1)), jnp.int32)
    cache = update_kv_cache(cache, ks, vs, pos, quant=cfg)
    q = jnp.asarray(rng.standard_normal((b, 1, h, dh)), jnp.float32)
    qpos = jnp.full((b,), length - 1, jnp.int32)

    fns = {
        "float": lambda q, c: decode_attention(
            q, c.k, c.v, c.positions, qpos),
        "quant_reextract": lambda q, c: decode_attention(
            q, c.k, c.v, c.positions, qpos, l2r=cfg),
        "plane_cache": lambda q, c: decode_attention(
            q, c.k, c.v, c.positions, qpos, l2r=cfg,
            k_planes=c.k_planes, k_scale=c.k_scale),
        "early_exit": lambda q, c: decode_attention(
            q, c.k, c.v, c.positions, qpos, l2r=cfg,
            k_planes=c.k_planes, k_scale=c.k_scale,
            early_exit=True, exit_tol=1e-4),
    }
    # parity gates the timing.  Bit-exactness is asserted on eager
    # (op-by-op) execution — identical int scores and scales make every
    # downstream float op identical; the jitted closures are different
    # XLA graphs, whose fusion may reassociate the f32 epilogue by an
    # ulp, so they get an ulp-level tolerance instead.
    eag = {name: np.asarray(fn(q, cache)) for name, fn in fns.items()}
    np.testing.assert_array_equal(eag["quant_reextract"],
                                  eag["plane_cache"])
    np.testing.assert_array_equal(eag["plane_cache"], eag["early_exit"])
    np.testing.assert_allclose(eag["plane_cache"], eag["float"], atol=0.1)
    modes = {name: jax.jit(fn) for name, fn in fns.items()}
    out = {name: jax.block_until_ready(fn(q, cache))
           for name, fn in modes.items()}
    for name in ("quant_reextract", "early_exit"):
        np.testing.assert_allclose(out[name], out["plane_cache"], atol=2e-6)
    np.testing.assert_allclose(np.asarray(out["plane_cache"]),
                               np.asarray(out["float"]), atol=0.1)

    n_it = 1 if CHECK_MODE else 20
    rounds = 1 if CHECK_MODE else 3
    best = {name: float("inf") for name in modes}
    for _ in range(rounds):  # interleaved min-of-rounds (shared host)
        for name, fn in modes.items():
            best[name] = min(best[name], _timeit(
                lambda fn=fn: jax.block_until_ready(fn(q, cache)), n=n_it,
                warmup=0))
    rows = []
    for name, us in best.items():
        emit(f"attention_decode_{name}", us,
             f"b={b} len={length} kv={kvh} g={g} dh={dh} "
             f"vs_float={best['float'] / us:.2f}x")
        rows.append({"name": f"decode_{name}", "us_per_step": us,
                     "batch": b, "cache_len": length, "kv_heads": kvh,
                     "group": g, "head_dim": dh,
                     "speedup_vs_float": best["float"] / us})

    # chunked prefill: float vs quantized (plane extraction once per call)
    qp = jnp.asarray(rng.standard_normal((b, sq, h, dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((b, sq, kvh, dh)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((b, sq, kvh, dh)), jnp.float32)
    qc_ = min(96, sq)
    kc_ = min(64, sq)
    pf_f = jax.jit(lambda a, b_, c: chunked_attention(
        a, b_, c, q_chunk=qc_, kv_chunk=kc_))
    pf_q = jax.jit(lambda a, b_, c: chunked_attention(
        a, b_, c, q_chunk=qc_, kv_chunk=kc_, l2r=cfg))
    o_f = jax.block_until_ready(pf_f(qp, kp, vp))
    o_q = jax.block_until_ready(pf_q(qp, kp, vp))
    np.testing.assert_allclose(np.asarray(o_q), np.asarray(o_f), atol=0.15)
    us_f, us_q = _best_pair(
        lambda: jax.block_until_ready(pf_f(qp, kp, vp)),
        lambda: jax.block_until_ready(pf_q(qp, kp, vp)), n=max(1, n_it // 4))
    emit("attention_prefill_float", us_f, f"b={b} sq={sq} h={h} dh={dh}")
    emit("attention_prefill_quant", us_q,
         f"b={b} sq={sq} h={h} dh={dh} vs_float={us_f / us_q:.2f}x")
    rows.append({"name": "prefill_float", "us_per_call": us_f,
                 "batch": b, "seq": sq, "heads": h, "head_dim": dh})
    rows.append({"name": "prefill_quant", "us_per_call": us_q,
                 "batch": b, "seq": sq, "heads": h, "head_dim": dh,
                 "speedup_vs_float": us_f / us_q})

    # flash-fused level walk: interpret-mode correctness (tiny — the
    # interpreter is orders of magnitude off any timing signal)
    from repro.kernels.flash_attention import flash_attention_l2r_pallas
    from repro.kernels.flash_attention.ref import attention_ref
    sb = 16
    qs_ = qp[:1, :sb]
    ks_ = kp[:1, :sb]
    vs_ = vp[:1, :sb]
    o_ker = flash_attention_l2r_pallas(qs_, ks_, vs_, bq=8, bkv=8,
                                       interpret=True)
    o_ref = attention_ref(qs_, ks_, vs_, True, None, None)
    err = float(jnp.max(jnp.abs(o_ker - o_ref)))
    assert err < 0.1, err  # W8A8 score noise only
    emit("attention_flash_l2r_interpret", "n/a",
         f"sq={sb} max_err_vs_float={err:.3e} validated=True")
    rows.append({"name": "flash_l2r_interpret", "seq": sb,
                 "max_err_vs_float_ref": err, "validated": True})

    # roofline accounting: bytes a decode step must move, per mode —
    # the model the measured decode rows should be judged against
    from repro.launch.roofline import attn_decode_step_bytes
    acct = attn_decode_step_bytes(b, length, kvh, dh,
                                  n_bits=cfg.n_bits,
                                  log2_radix=cfg.log2_radix,
                                  kv_dtype_bytes=4,  # f32 cache above
                                  levels=2)  # early-decided walk depth
    emit("attention_roofline_bytes", "n/a",
         f"plane_cache_vs_float={acct['plane_cache_vs_float']:.2f}x "
         f"truncated_vs_plane_cache="
         f"{acct['truncated_vs_plane_cache']:.2f}x")
    rows.append({"name": "roofline_decode_bytes", **acct})

    if json_path:
        payload = {
            "bench": "l2r_attention",
            "host_backend": jax.default_backend(),
            "note": "Decode modes share one KV cache; plane-cache scores "
                    "asserted bit-identical to per-step re-extraction and "
                    "early exit bit-identical to full depth before "
                    "timing.  On a CPU host the digit-serial walk is ~D "
                    "integer GEMVs vs one fused float GEMV, so quantized "
                    "rows trail the float oracle in wall-clock; the "
                    "apples-to-apples number is plane_cache vs "
                    "quant_reextract (the per-step extraction the "
                    "incremental stack removes) plus the roofline bytes "
                    "row.  Flash-fused kernel is interpret-validated, "
                    "not timed, off-TPU.",
            "rows": rows,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
        emit("attention_json", 0.0, f"wrote={json_path}")


def main(argv=None) -> None:
    import argparse

    global CHECK_MODE
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="smoke mode: 1 repetition, no warmup, JSON "
                         "records land in a temp dir (exercises every "
                         "bench path in CI without overwriting the "
                         "checked-in trajectory files)")
    ap.add_argument("--json-dir", default=None,
                    help="directory for the BENCH_*.json records "
                         "(default: the benchmarks dir, or a temp dir "
                         "under --check; CI passes an artifact dir so "
                         "the per-run JSONs are uploadable)")
    args = ap.parse_args(argv)
    CHECK_MODE = args.check
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.json_dir:
        json_dir = args.json_dir
        os.makedirs(json_dir, exist_ok=True)
    elif args.check:
        import tempfile
        json_dir = tempfile.mkdtemp(prefix="bench_check_")
    else:
        json_dir = os.path.dirname(__file__)
    print("name,us_per_call,derived")
    table1()
    table2()
    vgg16_cycles()
    kernel_bench()
    kernel_stacked_bench(os.path.join(json_dir, "BENCH_l2r_gemm.json"))
    ipu_bench()
    online_stats()
    progressive_bench(os.path.join(json_dir, "BENCH_progressive.json"))
    attention_bench(os.path.join(json_dir, "BENCH_attention.json"))
    serving_bench(os.path.join(json_dir, "BENCH_serving.json"))


if __name__ == "__main__":
    main()
