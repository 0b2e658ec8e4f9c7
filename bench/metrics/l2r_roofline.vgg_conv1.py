"""``l2r_roofline.vgg`` restricted to VGG-16's first stage, conv1_1 and
conv1_2: their roofline time (the larger of int8 operations over the
int8 peak and least bytes over HBM bandwidth, per layer, from the
configuration's ``gemms`` and ``gemm_bytes`` by name), summed over the
forwards that ran wholly inside the traced window, over the device time
of the L2R kernel events of those two layers inside them.  The program
names each layer's kernel after it
(``l2r_gemm_pallas_stacked_planes_conv1_1.12``); a trace whose kernels
carry no layer name reads nothing."""

import json
import os

from bench import trace
from bench.peaks import roofline_s
from bench.run import load_module

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "vgg16-l2r"  # the configuration of the cell this metric reads
LAYERS = ("conv1_1", "conv1_2")
L2R_KERNELS = ("l2r_gemm_pallas",)


def least_s(batch: int, peaks: dict) -> float:
    """Roofline time of one forward's ``LAYERS`` at ``batch`` images."""
    path = os.path.join(BENCH_DIR, "configs", CONFIG)
    with open(path + ".json") as f:
        cfg = json.load(f)
    model = load_module(path + ".py", "bench_model_vgg16_l2r")
    return sum(roofline_s(2.0 * m * k * n, b, peaks)
               for (name, m, k, n, _), b in zip(model.gemms(cfg, batch),
                                                model.gemm_bytes(cfg, batch))
               if name in LAYERS)


def layer_kernels(ops) -> list:
    """The L2R kernel events named for one of ``LAYERS``."""
    return [o for o in trace.matching(ops, L2R_KERNELS)
            if trace.op_kind(o[0]).endswith(tuple("_" + n for n in LAYERS))]


def read(rec):
    tr, w = rec.get("trace"), rec["work"]
    if not tr or "forward_module" not in w:
        return None
    lo, hi = rec["window_ns"]
    dev = tr["device"][0]
    runs = trace.runs(dev, [w["forward_module"]], lo, hi)
    kernel_s = trace.duration_ns(layer_kernels(
        trace.ops_in_runs(dev, runs))) / 1e9
    if not runs or kernel_s <= 0:
        return None
    return 100.0 * len(runs) * least_s(w["batch"], rec["peaks"]) / kernel_s
