"""Readings of the numbers that decide ``correct``, over many seeds.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--bits 4 | --fault <kind>]

Runs the cell once per seed in one process, as ``bench/run.py`` does,
and prints each run's compared numbers.  ``--bits`` serves the cell with
the program's own L2R path at that operand width instead of the one the
configuration states: the lower-precision control, whose readings have
to come out above each limit.  ``--fault`` plants one of ``FAULTS`` in
the timed path; its readings, too, have to come out above a limit.
Without either they are the readings of sound runs, which set the lower
end of each limit.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def control_config(config: dict, bits: int | None) -> dict:
    """The configuration with its L2R operand width set to ``bits``."""
    config = copy.deepcopy(config)
    if bits is not None:
        config["serving"]["l2r"]["n_bits"] = bits
    return config


# ------------------------------------------------------------------ faults
def broken_vgg16_apply(orig, kind: str):
    """``vgg16_apply`` with a fault: ``half_batch`` computes the first
    half of the batch and returns it twice; ``wrong_image`` returns each
    image's logits for its neighbour's; ``answer_altered`` reverses the
    classes."""
    import jax.numpy as jnp

    def broken(params, images, **kw):
        if kind == "half_batch":
            half = orig(params, images[: images.shape[0] // 2], **kw)
            return jnp.concatenate([half, half])
        out = orig(params, images, **kw)
        if kind == "wrong_image":
            return jnp.roll(out, 1, axis=0)
        return out[:, ::-1]
    return broken


def broken_decode_warmup(orig, kind: str):
    """``ServingGateway.warmup`` that wraps the decode step with a
    fault: ``state_unchanged`` returns the state it was given;
    ``token_altered`` shifts every emitted token id by one."""
    import jax
    import jax.numpy as jnp

    def warmup(self):
        orig(self)
        exe = self._decode_exe

        def step(params, state, tok, *rest):
            if kind == "state_unchanged":
                kept = jax.tree.map(jnp.copy, state)
                _, nxt, logits, lv = exe(params, state, tok, *rest)
                return kept, nxt, logits, lv
            new, nxt, logits, lv = exe(params, state, tok, *rest)
            return new, (nxt + 1) % self.cfg.vocab, logits, lv
        self._decode_exe = step
    return warmup


FAULTS = {"half_batch": "vgg", "wrong_image": "vgg", "answer_altered": "vgg",
          "state_unchanged": "lm", "token_altered": "lm"}


def plant(kind: str, setattr_=setattr):
    """Plant fault ``kind`` in the program (``setattr_`` may be a test's
    ``monkeypatch.setattr``, which undoes it)."""
    if FAULTS[kind] == "vgg":
        import repro.models.cnn as cnn

        setattr_(cnn, "vgg16_apply", broken_vgg16_apply(cnn.vgg16_apply,
                                                        kind))
    else:
        from repro.serve.gateway import ServingGateway

        setattr_(ServingGateway, "warmup",
                 broken_decode_warmup(ServingGateway.warmup, kind))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    group = ap.add_mutually_exclusive_group()
    group.add_argument("--bits", type=int, default=None)
    group.add_argument("--fault", choices=sorted(FAULTS), default=None)
    args = ap.parse_args(argv)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = run.Cell(bench, args.workload)
    cell = run.Cell(bench, args.workload,
                    config=control_config(cell.config, args.bits))
    ok, err = run.check_device(cell.spec["chips"])
    if err:
        return run.fail(err)
    devices, peaks = ok
    run.enable_compile_cache()
    if args.fault:
        plant(args.fault)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(cell, seed, args.seconds, False, devices, peaks,
                           t_start=time.perf_counter())
        print(json.dumps({"seed": seed, "bits": args.bits,
                          "fault": args.fault, "correct": out["correct"],
                          "failed": out["failed"],
                          "checks": out["checks"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
