"""The trace reduction on a small trace: busy union, attribution of ops
to the module execution that encloses them, idle gaps and their labels,
and the matching of kernel names."""

import json
import os

from bench import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def small_trace():
    modules = [["jit_forward(11)", 100, 200], ["jit_decode(12)", 300, 400],
               ["jit_decode(13)", 450, 520]]
    ops = [["l2r_stacked.1", 110, 150], ["fusion.3", 150, 180],
           ["custom-call.2", 310, 350], ["fusion.7", 460, 470],
           ["copy.1", 600, 610]]
    tr._tag_ops(ops, modules)
    host = [["bench.window", 90, 500], ["bench.dispatch", 190, 320],
            ["bench.wait", 350, 460]]
    return {"device": [{"plane": "/device:TPU:0", "modules": modules,
                        "ops": ops}], "host": host}


def test_ops_are_tagged_with_their_module():
    t = small_trace()
    assert [o[3] for o in t["device"][0]["ops"]] == [
        "jit_forward", "jit_forward", "jit_decode", "jit_decode", ""]


def test_window_busy_and_gaps():
    t = small_trace()
    lo, hi = tr.window(t)
    assert (lo, hi) == (90, 500)
    ops = t["device"][0]["ops"]
    # 110-180 (two ops back to back), 310-350, 460-470; 600-610 lies outside
    assert tr.union(ops, lo, hi) == [(110, 180), (310, 350), (460, 470)]
    assert tr.busy_ns(ops, lo, hi) == 70 + 40 + 10
    assert tr.gaps(ops, lo, hi) == [(90, 110), (180, 310), (350, 460),
                                    (470, 500)]


def test_window_without_span_is_the_device_extent():
    t = small_trace()
    t["host"] = []
    assert tr.window(t) == (100, 610)


def test_runs_inside_window_and_their_ops():
    t = small_trace()
    dev = t["device"][0]
    runs = tr.runs(dev, ["jit_decode"], 90, 500)
    assert [r[0] for r in runs] == ["jit_decode(12)"]  # 13 ends after 500
    inside = tr.ops_in_runs(dev, runs)
    assert [o[0] for o in inside] == ["custom-call.2"]
    assert tr.duration_ns(runs) == 100
    assert tr.runs(dev, ["jit_prefill"], 0, 10**9) == []


def test_name_matching():
    ops = small_trace()["device"][0]["ops"]
    assert [o[0] for o in tr.matching(ops, ("l2r",))] == ["l2r_stacked.1"]
    assert tr.matching(ops, ("nothing",)) == []
    assert tr.op_kind("fusion.123") == "fusion"
    assert tr.module_name("jit_decode(7)") == "jit_decode"


def test_self_time_of_nested_ops():
    ops = [["while.1", 0, 100, "m"], ["k.1", 10, 30, "m"],
           ["k.2", 40, 60, "m"], ["f.1", 120, 130, "m"]]
    got = {op[0]: t for op, t in tr.self_ns(ops, 0, 200)}
    assert got == {"while.1": 60, "k.1": 20, "k.2": 20, "f.1": 10}
    assert tr.instruction("%k.2 = s32[8]{0} custom-call(%a.1)") == "k.2"


def test_breakdown_labels_gaps_by_host_span():
    t = small_trace()
    b = tr.breakdown(t, 90, 500)
    assert b["device_ops"][0] == ["jit_forward:l2r_stacked", 40 / 1e9]
    gaps = dict((round(s * 1e9), name) for name, s in b["idle_gaps"])
    assert gaps[130] == "bench.dispatch"  # 180-310, midpoint 245
    assert gaps[110] == "bench.wait"  # 350-460, midpoint 405
    assert gaps[20] == "outside any benchmark span"  # 90-110


def test_recorded_chip_trace():
    """A slice of a trace recorded on a TPU v5e (VGG-16 forwards), as
    load_xplane reduced it."""
    path = os.path.join(HERE, "data", "vgg16_trace_slice.json")
    with open(path) as f:
        t = json.load(f)
    lo, hi = tr.window(t)
    dev = t["device"][0]
    busy = tr.busy_ns(dev["ops"], lo, hi)
    assert 0 < busy <= hi - lo
    runs = tr.runs(dev, ["jit_forward"], lo, hi)
    assert runs
    kernels = tr.matching(tr.ops_in_runs(dev, runs), t["l2r_kernels"])
    assert 0 < tr.duration_ns(kernels) <= tr.duration_ns(runs)
    assert t["expect"]["busy_ns"] == busy
    assert t["expect"]["runs"] == len(runs)
    assert t["expect"]["kernel_ns"] == tr.duration_ns(kernels)
    # the ops nest (no op outlives the forward that encloses it), so
    # self times add up to the busy time
    assert sum(s for _, s in tr.self_ns(dev["ops"], lo, hi)) == busy


def test_load_xplane_reads_the_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load_xplane(str(tmp_path))
    names = [h[0] for h in t["host"]]
    assert "bench.window" in names and "bench.dispatch" in names
    lo, hi = tr.window(t)
    assert hi > lo


def test_mfu_vgg_counts_a_cut_forward_by_its_share():
    """A forward cut by the window's edge adds the share of it inside,
    so the reading does not jump by a whole forward with the window."""
    from bench.peaks import peaks_for
    from bench.run import BENCH_DIR, load_module

    mfu = load_module(os.path.join(BENCH_DIR, "metrics", "mfu.vgg.py"),
                      "bench_metric_mfu_vgg")
    t = small_trace()
    peaks = peaks_for("TPU v5 lite")
    work = {"forward_module": "jit_forward", "batch": 8,
            "macs_per_image": 15_000_000_000}

    def read(lo, hi):
        return mfu.read({"trace": t, "window_ns": (lo, hi), "work": work,
                         "peaks": peaks})

    whole = read(100, 200)
    assert abs(read(100, 150) - whole) < 1e-9 * whole  # half in half
    assert abs(read(50, 250) - whole / 2) < 1e-9 * whole
    assert read(210, 290) is None
