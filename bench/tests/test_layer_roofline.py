"""``l2r_roofline.vgg_conv1`` on traces whose L2R kernels carry their
layer's name, and on the recorded slice, whose kernels carry none."""

import copy
import json
import os

import pytest

from bench import trace as tr
from bench.peaks import peaks_for
from bench.run import BENCH_DIR, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL = "l2r_gemm_pallas_stacked_planes"


def _metric(name):
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))


def _work(batch=8):
    cfg_path = os.path.join(BENCH_DIR, "configs", "vgg16-l2r")
    with open(cfg_path + ".json") as f:
        cfg = json.load(f)
    model = load_module(cfg_path + ".py", "bench_model_vgg16_l2r")
    gemms = model.gemms(cfg, batch)
    return {"forward_module": "jit_forward", "batch": batch,
            "macs_per_image": model.macs_per_image(cfg),
            "gemms": [[2.0 * m * k * n, b] for (_, m, k, n, _), b in
                      zip(gemms, model.gemm_bytes(cfg, batch))]}, gemms


def _rec(t, lo, hi):
    return {"trace": t, "window_ns": (lo, hi), "work": _work()[0],
            "peaks": peaks_for("TPU v5 lite")}


def test_conv1_share_of_a_named_forward():
    """Two forwards, each with two conv1_1 taps of 30 ns and one conv1_2
    tap of 40 ns, beside an fc6 kernel that does not count."""
    conv1 = _metric("l2r_roofline.vgg_conv1")
    modules, ops = [], []
    for f0 in (0, 1000):
        modules.append([f"jit_forward({f0})", f0, f0 + 500])
        ops += [[f"{KERNEL}_conv1_1.1", f0 + 10, f0 + 40],
                [f"{KERNEL}_conv1_1.2", f0 + 40, f0 + 70],
                [f"{KERNEL}_conv1_2.3", f0 + 70, f0 + 110],
                [f"{KERNEL}_fc6.4", f0 + 200, f0 + 400]]
    tr._tag_ops(ops, modules)
    t = {"device": [{"plane": "/device:TPU:0", "modules": modules,
                     "ops": ops}], "host": []}
    rec = _rec(t, 0, 2000)
    least = conv1.least_s(8, rec["peaks"])
    assert least > 0
    assert conv1.read(rec) == pytest.approx(100.0 * 2 * least / 200e-9)
    # a forward cut by the window does not count
    assert conv1.read(_rec(t, 0, 1200)) == pytest.approx(
        100.0 * least / 100e-9)
    assert conv1.read(_rec(t, 600, 900)) is None


def test_conv1_roofline_counts_only_the_first_stage():
    _, gemms = _work()
    conv1 = _metric("l2r_roofline.vgg_conv1")
    peaks = peaks_for("TPU v5 lite")
    from bench.peaks import roofline_s

    model_bytes = _work()[0]["gemms"]
    want = sum(roofline_s(ops, b, peaks)
               for (name, *_), (ops, b) in zip(gemms, model_bytes)
               if name in ("conv1_1", "conv1_2"))
    assert conv1.least_s(8, peaks) == pytest.approx(want)


def test_recorded_slice_reads_the_same_named_or_not():
    """The recorded chip slice predates layer names: the new metric reads
    nothing there, and naming its kernels by layer (nine taps per conv,
    in call order, in the forwards that hold all 120 kernels) leaves
    ``l2r_roofline.vgg`` as it was."""
    with open(os.path.join(HERE, "data", "vgg16_trace_slice.json")) as f:
        t = json.load(f)
    lo, hi = tr.window(t)
    vgg, conv1 = _metric("l2r_roofline.vgg"), _metric("l2r_roofline.vgg_conv1")
    assert conv1.read(_rec(t, lo, hi)) is None
    before = vgg.read(_rec(t, lo, hi))

    _, gemms = _work()
    layers = [g[0] for g in gemms]
    per_layer = [n for n in layers if n.startswith("conv")
                 for _ in range(9)] + [n for n in layers if n.startswith("fc")]
    named = copy.deepcopy(t)
    dev = named["device"][0]
    renamed = 0
    for run in tr.runs(dev, ["jit_forward"], lo, hi):
        kernels = tr.matching(tr.ops_in_runs(dev, [run]), (KERNEL,))
        if len(kernels) != len(per_layer):
            continue
        renamed += 1
        for op, layer in zip(kernels, per_layer):
            num = op[0].rsplit(".", 1)[1]
            op[0] = f"{KERNEL}_{layer}.{num}"
    assert renamed
    assert vgg.read(_rec(named, lo, hi)) == before
    share = conv1.read(_rec(named, lo, hi))
    assert 0 < share < 1  # conv1 runs far below its roofline
