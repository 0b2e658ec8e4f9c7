import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count at first init).  This module is the ONLY place that forces
# 512 host devices — tests and benches see the real device count.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, and emit the roofline artifact per cell.

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both
    PYTHONPATH=src python -m repro.launch.dryrun --all --out artifacts/dryrun

Artifacts (JSON, one per cell) carry: cost_analysis FLOPs/bytes,
memory_analysis, parsed collective wire bytes, roofline terms, and
MODEL_FLOPS — EXPERIMENTS.md §Dry-run/§Roofline are generated from them
(benchmarks/roofline_report.py)."""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, all_cells, cell_supported, get_config, input_specs
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import model_flops, roofline_terms
from repro.models.common import abstract, count_params
from repro.models.config import ModelConfig
from repro.models.encdec import encdec_build
from repro.models.transformer import lm_build
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.serve.engine import (abstract_state, make_decode_step,
                                make_prefill_step, state_specs)
from repro.sharding.axes import batch_spec, named, param_specs, safe_spec
from repro.train.step import (TrainConfig, make_train_step,
                              train_step_shardings)
from jax.sharding import PartitionSpec as P


def build_desc(cfg: ModelConfig):
    return encdec_build(cfg) if cfg.family == "encdec" else lm_build(cfg)


def _batch_shardings(mesh, specs: dict):
    out = {}
    for k, v in specs.items():
        if k == "rope_positions":
            out[k] = P(None, batch_spec(mesh, v.shape[1])[0], None)
        else:
            b = batch_spec(mesh, v.shape[0])[0]
            out[k] = P(b, *([None] * (len(v.shape) - 1)))
    return out


def lower_cell(arch: str, shape: str, multi_pod: bool,
               tcfg: TrainConfig | None = None, l2r: bool = False,
               score_bf16: bool = False, moe_hints: bool = False,
               wq: bool = False, kv_shard: str = "heads",
               moe_dp_local: bool = False, head_shard: bool = False):
    """Returns (lowered, compiled, meta) for one cell.

    Hillclimb switches (all default off -> paper-faithful baseline):
      score_bf16 — bf16 attention score blocks (f32 stats);
      moe_hints  — interior sharding hints on the MoE dispatch path
                   (training's moe_apply and serving's moe_serve);
      moe_dp_local — DP-local capacity dispatch, training cells only:
                   serving's MoE is dropless and refuses it;
      wq         — int8-stored weights (W8A8 L2R serving arithmetic).
    """
    import dataclasses as _dc

    if moe_dp_local and SHAPES[shape].kind != "train":
        raise ValueError(
            f"--moe-dp-local drops tokens past a capacity, which serving "
            f"does not (models/moe.py:moe_serve); {shape} is a "
            f"{SHAPES[shape].kind} cell")
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    if l2r:
        from repro.core.quant import QuantConfig
        cfg = _dc.replace(cfg, l2r=QuantConfig())
    if score_bf16:
        cfg = _dc.replace(cfg, attn_score_dtype="bfloat16")
    if head_shard:
        cfg = _dc.replace(cfg, attn_head_shard=True)
    if moe_dp_local:
        cfg = _dc.replace(cfg, moe_dp_local=True)
    if moe_hints or moe_dp_local or head_shard:
        from repro.sharding import ctx
        ctx.set_mesh(mesh)
    sp = SHAPES[shape]
    desc = build_desc(cfg)
    if wq:
        from repro.models.common import quantize_desc
        assert sp.kind != "train", "int8 weight storage is a serving mode"
        desc = quantize_desc(desc)
    specs = input_specs(arch, shape, cfg)
    tcfg = tcfg or TrainConfig()

    if sp.kind == "train":
        params_abs = abstract(desc, param_dtype=jnp.bfloat16)
        opt_abs = jax.eval_shape(adamw_init, params_abs)
        step = make_train_step(cfg, AdamWConfig(), tcfg, mesh)
        ins, outs = train_step_shardings(cfg, mesh, desc, specs)
        fn = jax.jit(step, in_shardings=ins, out_shardings=outs,
                     donate_argnums=(0, 1))
        lowered = fn.lower(params_abs, opt_abs, specs)
        n_tokens = sp.global_batch * sp.seq_len
    elif sp.kind == "prefill":
        params_abs = abstract(desc, param_dtype=jnp.bfloat16)
        pspecs = named(mesh, param_specs(desc, mesh))
        bspecs = named(mesh, _batch_shardings(mesh, specs))
        sspecs = named(mesh, state_specs(cfg, mesh, sp.global_batch, sp.seq_len,
                                         kv_shard))
        lspec = named(mesh, safe_spec(
            (sp.global_batch, 1, cfg.vocab),
            P(batch_spec(mesh, sp.global_batch)[0], None, "model"), mesh))
        fn = make_prefill_step(cfg, max_len=sp.seq_len,
                               in_shardings=(pspecs, bspecs),
                               out_shardings=(sspecs, lspec))
        lowered = fn.lower(params_abs, specs)
        n_tokens = sp.global_batch * sp.seq_len
    else:  # decode
        params_abs = abstract(desc, param_dtype=jnp.bfloat16)
        state_abs = abstract_state(cfg, sp.global_batch, sp.seq_len)
        pspecs = named(mesh, param_specs(desc, mesh))
        sspecs = named(mesh, state_specs(cfg, mesh, sp.global_batch, sp.seq_len,
                                         kv_shard))
        bspec = batch_spec(mesh, sp.global_batch)[0]
        tok_in = named(mesh, P(bspec, None))
        lspec = named(mesh, safe_spec((sp.global_batch, 1, cfg.vocab),
                                      P(bspec, None, "model"), mesh))
        in_sh = (pspecs, sspecs, tok_in)
        args = (params_abs, state_abs, specs["tokens"])
        if "rope_positions" in specs:
            in_sh = in_sh + (named(mesh, P(None, bspec, None)),)
            args = args + (specs["rope_positions"],)
        fn = make_decode_step(
            cfg, in_shardings=in_sh,
            out_shardings=(sspecs, named(mesh, P(bspec, None)), lspec),
            donate_argnums=(1,))
        lowered = fn.lower(*args)
        n_tokens = sp.global_batch  # one new token per sequence

    if moe_hints or moe_dp_local or head_shard:
        from repro.sharding import ctx
        ctx.set_mesh(None)
    meta = dict(arch=arch, shape=shape, kind=sp.kind,
                multi_pod=multi_pod, chips=mesh.size,
                params=count_params(desc),
                n_tokens=n_tokens, l2r=l2r,
                opts=dict(score_bf16=score_bf16, moe_hints=moe_hints, wq=wq))
    return lowered, cfg, desc, meta


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             tcfg: TrainConfig | None = None, l2r: bool = False,
             tag: str = "", skip_existing: bool = False,
             score_bf16: bool = False, moe_hints: bool = False,
             wq: bool = False, kv_shard: str = "heads",
             moe_dp_local: bool = False, head_shard: bool = False) -> dict:
    mp_name = "2pod" if multi_pod else "1pod"
    path = os.path.join(out_dir, f"{arch}_{shape}_{mp_name}{tag}.json")
    if skip_existing and os.path.exists(path):
        with open(path) as fh:
            rec = json.load(fh)
        print(f"[CACHED] {arch} x {shape} x {mp_name}{tag}")
        return rec
    t0 = time.time()
    lowered, cfg, desc, meta = lower_cell(arch, shape, multi_pod, tcfg, l2r,
                                          score_bf16, moe_hints, wq, kv_shard,
                                          moe_dp_local, head_shard)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            k: getattr(mem, k)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes")
            if hasattr(mem, k)
        }
    except Exception as e:  # CPU backend may not implement it
        mem_d = {"error": str(e)}

    hlo = compiled.as_text()
    # trip-count-aware analysis (XLA's cost_analysis counts while bodies
    # once; see launch/hlo_analysis.py) — this is the roofline source.
    ana = analyze(hlo)
    flops = ana["flops"]
    bytes_hbm = ana["bytes"]
    rl = roofline_terms(flops, bytes_hbm, ana["total_wire_bytes"], meta["chips"])
    mf = model_flops(cfg, desc, meta["n_tokens"], meta["kind"])

    rec = {
        **meta,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "cost_analysis_raw": {k: cost[k] for k in ("flops", "bytes accessed")
                              if k in cost},
        "memory_analysis": mem_d,
        "collectives": {"wire_bytes": ana["collective_wire_bytes"],
                        "counts": ana["collective_counts"],
                        "total_wire_bytes": ana["total_wire_bytes"]},
        "roofline": rl.asdict(),
        "model_flops_per_chip": mf / meta["chips"],
        "useful_compute_ratio": (mf / meta["chips"]) / flops if flops else None,
        "hlo_bytes": len(hlo),
    }
    os.makedirs(out_dir, exist_ok=True)
    mp = "2pod" if multi_pod else "1pod"
    name = f"{arch}_{shape}_{mp}{tag}.json"
    with open(os.path.join(out_dir, name), "w") as fh:
        json.dump(rec, fh, indent=1)
    try:  # archive compressed HLO: re-analysis without recompilation
        import zstandard
        with open(os.path.join(out_dir, name.replace(".json", ".hlo.zst")),
                  "wb") as fh:
            fh.write(zstandard.ZstdCompressor(level=3).compress(hlo.encode()))
    except Exception:
        pass
    print(f"[OK] {arch} x {shape} x {mp}{tag}: compile {t_compile:.1f}s "
          f"dominant={rl.dominant} bound={rl.bound_s*1e3:.2f}ms "
          f"useful={rec['useful_compute_ratio'] and round(rec['useful_compute_ratio'],3)}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"], default="off")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--l2r", action="store_true",
                    help="enable the paper's digit-plane arithmetic in matmuls")
    ap.add_argument("--tag", default="")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--xent-chunk", type=int, default=512)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--score-bf16", action="store_true",
                    help="bf16 attention score blocks (hillclimb)")
    ap.add_argument("--moe-hints", action="store_true",
                    help="interior sharding hints on MoE dispatch (hillclimb)")
    ap.add_argument("--wq", action="store_true",
                    help="int8-stored weights: W8A8 L2R serving (hillclimb)")
    ap.add_argument("--kv-seq-shard", action="store_true",
                    help="shard KV caches on the sequence dim (hillclimb)")
    ap.add_argument("--moe-dp-local", action="store_true",
                    help="DP-local-capacity MoE dispatch, training "
                         "cells only (hillclimb)")
    ap.add_argument("--head-shard", action="store_true",
                    help="shard attention on the KV-head dim (hillclimb)")
    args = ap.parse_args()

    tcfg = TrainConfig(remat=not args.no_remat, seq_shard=not args.no_seq_shard,
                       xent_chunk=args.xent_chunk)
    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]

    cells = []
    if args.all:
        for a, s, ok, why in all_cells():
            if ok:
                cells.append((a, s))
            else:
                print(f"[SKIP] {a} x {s}: {why}")
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        ok, why = cell_supported(args.arch, args.shape)
        if not ok:
            print(f"[SKIP] {args.arch} x {args.shape}: {why}")
            return
        cells.append((args.arch, args.shape))

    failures = []
    for (a, s) in cells:
        for mp in pods:
            try:
                run_cell(a, s, mp, args.out, tcfg, args.l2r, args.tag,
                         args.skip_existing, args.score_bf16,
                         args.moe_hints, args.wq,
                         "seq" if args.kv_seq_shard else "heads",
                         args.moe_dp_local, args.head_shard)
            except Exception:
                failures.append((a, s, mp))
                print(f"[FAIL] {a} x {s} x {'2pod' if mp else '1pod'}")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cell(s) failed: {failures}")
    print("dry-run complete")


if __name__ == "__main__":
    main()
