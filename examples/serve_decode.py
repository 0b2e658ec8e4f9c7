"""Batched serving with the L2R W8A8 weight format.

    PYTHONPATH=src python examples/serve_decode.py

Runs the same prompts through (a) bf16/f32 weights, (b) int8-stored
weights (the L2R serving format — exactly the integer arithmetic the
composite IPU streams MSDF), and (c) the digit-plane progressive mode,
comparing outputs and timing.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.launch.serve import main as serve_main

print("--- float weights ---")
a = serve_main(["--arch", "smollm-135m", "--smoke", "--batch", "2",
                "--prompt-len", "12", "--steps", "8"])
print("--- int8 (L2R W8A8) weights ---")
b = serve_main(["--arch", "smollm-135m", "--smoke", "--batch", "2",
                "--prompt-len", "12", "--steps", "8", "--wq"])
print("--- progressive MSDF (5/7 levels) ---")
c = serve_main(["--arch", "smollm-135m", "--smoke", "--batch", "2",
                "--prompt-len", "12", "--steps", "8", "--l2r-levels", "5"])

agree_q = (a == b).mean()
agree_p = (a == c).mean()
print(f"\ntoken agreement: int8 vs float {agree_q*100:.0f}% | "
      f"progressive vs float {agree_p*100:.0f}%")
print("(random untrained weights -> near-uniform logits, so argmax is "
      "maximally quantization-sensitive; on trained checkpoints W8A8 "
      "agreement is the ~99% regime — see tests/test_vgg16.py for the "
      "bounded-error checks on realistic activations)")

# --- sharded serving: the same progressive engine on a device mesh ---
# Installing a mesh routes the whole stack onto the sharded paths: the
# LM-head plane stack is vocab-sharded over "model" at load
# (prepare_params), slot state is placed per engine.state_specs, and the
# head streams as the shard_mapped consensus walk whose early exit stops
# at the fleet-wide slowest row — tokens and exit levels bit-identical
# to the single-device engine.  A multi-device CPU needs the virtual-
# device flag BEFORE jax initializes, so on the CPU the demo runs in a
# subprocess.  On an accelerator this process already holds the chips
# (a child could not reach them), so the demo runs here on the real
# devices, or is skipped on a single one.
import subprocess

import jax

from repro.launch.mesh import virtual_device_env

SHARDED_DEMO = """
import dataclasses, sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_smoke
from repro.core.quant import QuantConfig
from repro.launch.mesh import install_local_mesh
from repro.models.common import materialize
from repro.models.transformer import lm_build
from repro.serve.batching import ContinuousBatcher, Request
from repro.serve.engine import prepare_params
from repro.sharding import ctx

cfg = dataclasses.replace(get_smoke("smollm-135m"), l2r=QuantConfig())
raw = materialize(lm_build(cfg), jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab, (6,)).astype(np.int32)
           for _ in range(3)]

def run(mesh_shape):
    ctx.set_mesh(None)
    if mesh_shape:
        install_local_mesh(*mesh_shape)  # (data, model)
    eng = ContinuousBatcher(cfg, prepare_params(cfg, raw), n_slots=2,
                            max_len=24, progressive=True, early_exit=True)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    eng.run(max_steps=50)
    return eng

single = run(None)
sharded = run(MESH)  # (data, model)
s1, s2 = single.stats(), sharded.stats()
assert s1 == s2, (s1, s2)
print(f"sharded{MESH} == single-device: tokens={s2['tokens']} "
      f"mean_exit={s2['mean_exit_level']:.2f}/{s2['n_levels'] - 1} "
      f"stats identical")
"""
if jax.default_backend() == "cpu":
    print("--- sharded progressive serving (2x4 virtual-device mesh) ---")
    out = subprocess.run(
        [sys.executable, "-c", "MESH = (2, 4)\n" + SHARDED_DEMO], text=True,
        capture_output=True,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env=virtual_device_env(8))
    print(out.stdout.strip())
    if out.returncode != 0:
        print(out.stderr[-2000:])
        sys.exit("sharded serving demo failed")
elif len(jax.devices()) >= 2:
    n_dev = len(jax.devices())
    mesh_shape = (2, 4) if n_dev >= 8 else (1, n_dev)
    print(f"--- sharded progressive serving ({mesh_shape[0]}x{mesh_shape[1]} "
          f"{jax.default_backend()} mesh) ---")
    exec(f"MESH = {mesh_shape}\n" + SHARDED_DEMO, {})
else:
    print("--- sharded progressive serving skipped: one "
          f"{jax.default_backend()} device, and a child process cannot "
          "reach a chip this process holds ---")
