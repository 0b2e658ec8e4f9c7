"""tools/profile_cell.py on small inputs: scope paths from ``op_name``
metadata, device self time by scope, idle gaps labelled by the gateway's
host spans, the split of TTFT at admission, and the gateway spans read
back from a profile."""

import importlib.util
import os
import threading
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tool():
    path = os.path.join(_REPO, "tools", "profile_cell.py")
    spec = importlib.util.spec_from_file_location("profile_cell", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


KERNEL = "l2r_gemm_pallas_stacked_planes_conv1_1"


def test_scope_path(tool):
    sp = tool.scope_path
    assert sp("jit(decode)/while/body/closed_call/attention/kv_cache_update/"
              "jit(remainder)/rem") == "attention/kv_cache_update"
    assert sp("jit(forward)/conv1_1/jit(_l2r_conv2d_int)/"
              f"jit(l2r_gemm_pallas_stacked_planes)/{KERNEL}/pallas_call",
              KERNEL) == "conv1_1"
    assert sp("jit(decode)/head/while/body/jit(_where)/select_n") == "head"
    assert sp("jit(decode)/add") == "" and sp("") == ""


def test_hlo_scopes_of_a_compiled_program(tool):
    def step(x):
        with jax.named_scope("conv1_1"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("mlp"):
            return (y @ x).sum()

    text = jax.jit(step).lower(jnp.ones((16, 16))).compile().as_text()
    module, names = tool.hlo_scopes(text)
    assert module == "jit_step"
    assert {"conv1_1", "mlp"} <= {tool.scope_path(n) for n in names.values()}


def _scoped_trace():
    ops = [["while.1", 0, 100, "jit_forward"],
           [f"{KERNEL}.3", 10, 40, "jit_forward"],
           ["l2r_gemm_pallas_stacked_planes_conv1_2.4", 40, 60,
            "jit_forward"],
           ["fusion.2", 60, 70, "jit_forward"],
           ["copy.5", 120, 150, "jit_decode"]]
    names = {"jit_forward": {
        f"{KERNEL}.3": f"jit(forward)/conv1_1/jit(k)/{KERNEL}/pallas_call",
        "l2r_gemm_pallas_stacked_planes_conv1_2.4":
            "jit(forward)/conv1_2/jit(k)/"
            "l2r_gemm_pallas_stacked_planes_conv1_2/pallas_call",
        "fusion.2": "jit(forward)/conv1_2/add"},
        "jit_decode": {"copy.5": "jit(decode)/while/body/closed_call/"
                                 "kv_cache_writeback/dynamic_update_slice"}}
    return ops, names


def test_scope_table_of_scoped_kernels(tool):
    ops, names = _scoped_trace()
    scopes = tool.op_scopes(ops, names)
    assert scopes == ["", "conv1_1", "conv1_2", "conv1_2",
                      "kv_cache_writeback"]
    by_scope = {(m, s): t for m, s, t in tool.scope_table(ops, scopes, 0, 200)}
    # the while op keeps only the time its body ops do not cover
    assert by_scope == {("jit_forward", ""): 40e-9,
                        ("jit_forward", "conv1_1"): 30e-9,
                        ("jit_forward", "conv1_2"): 30e-9,
                        ("jit_decode", "kv_cache_writeback"): 30e-9}
    by_op = tool.scope_table(ops, scopes, 0, 200,
                             key=lambda mod, kind, path: (mod, kind, path))
    assert ["jit_forward", KERNEL, "conv1_1", 30e-9] in by_op
    assert sum(row[-1] for row in by_op) == pytest.approx(130e-9)


def test_idle_gaps_labelled_by_gateway_spans(tool):
    trace = {"device": [{"plane": "/device:TPU:0", "modules": [], "ops": [
        ["a.1", 0, 10, "m"], ["b.1", 30, 40, "m"], ["c.1", 70, 80, "m"],
        ["d.1", 120, 130, "m"]]}],
        "host": [["bench.window", 0, 130], ["bench.run_chunk", 0, 130]]}
    loop, emit = "python#1", "python#2"
    program = [["gateway.decode", 5, 35, loop, {}],
               ["gateway.admit", 40, 90, loop, {"uids": "3 4"}],
               ["gateway.flush", 45, 65, loop, {}],
               ["gateway.emit", 80, 125, emit, {"kind": "decode"}]]
    gaps = {round(s * 1e9): name
            for name, s in tool.label_gaps(trace, program, 0, 130)}
    assert gaps[20] == "gateway.decode"  # 10-30
    assert gaps[30] == "gateway.flush"  # 40-70: the innermost span
    # 80-120: only the emit thread is in a span, so the benchmark's
    assert gaps[40] == "bench.run_chunk"
    assert tool.label_gaps(trace, [], 0, 130)[0][0] == "bench.run_chunk"


def test_gateway_spans_read_back_from_a_profile(tool, tmp_path):
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()

    def emit():
        with TraceAnnotation("gateway.emit", kind="prefill", uids="1 2"):
            pass

    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("gateway.decode"):
            f(x).block_until_ready()
        t = threading.Thread(target=emit)
        t.start()
        t.join(timeout=30)
    jax.profiler.stop_trace()
    assert not t.is_alive()
    spans = tool.program_spans(str(tmp_path))
    assert sorted(s[0] for s in spans) == ["gateway.decode", "gateway.emit"]
    threads = {s[0]: s[3] for s in spans}
    assert threads["gateway.decode"] != threads["gateway.emit"]
    assert all(s[1] <= s[2] for s in spans)
    args = {s[0]: s[4] for s in spans}
    assert args["gateway.emit"] == {"kind": "prefill", "uids": "1 2"}


def test_prefill_lag_parts_join_admit_and_emit_by_uids(tool):
    ms = 10**6
    program = [["gateway.admit", 0, 2 * ms, "l", {"uids": "1 2"}],
               ["gateway.admit", 10 * ms, 11 * ms, "l", {"uids": "3"}],
               ["gateway.emit", 5 * ms, 9 * ms, "e",
                {"kind": "prefill", "uids": "1 2"}],
               ["gateway.emit", 9 * ms, 12 * ms, "e", {"kind": "decode"}],
               ["gateway.emit", 31 * ms, 40 * ms, "e",
                {"kind": "prefill", "uids": "3"}]]
    out = tool.prefill_lag_parts(program)
    assert out["groups"] == 2
    assert (out["emit_queue_p50_ms"], out["emit_queue_p95_ms"]) == (3, 20)
    assert (out["emit_p50_ms"], out["emit_p95_ms"]) == (4, 9)


def test_request_split(tool):
    reqs = [SimpleNamespace(t_arrival=0.0, t_admit=0.01 * i,
                            t_first_token=0.01 * i + 0.5)
            for i in range(20)]
    reqs.append(SimpleNamespace(t_arrival=0.0, t_admit=None,
                                t_first_token=None))
    out = tool.request_split(reqs)
    assert (out["requests"], out["admitted"]) == (21, 20)
    assert out["queue_wait_p50_ms"] == pytest.approx(90.0)
    assert out["queue_wait_p95_ms"] == pytest.approx(180.0)
    assert out["first_token_lag_p95_ms"] == pytest.approx(500.0)
    assert out["ttft_p95_ms"] == pytest.approx(680.0)


def test_progress_rates_around_the_traced_slice(tool):
    # 10 a second, but 5 a second while traced (4 s to 8 s)
    marks = [(float(t), 10 * t - 5 * max(0, min(t, 8) - 4))
             for t in range(0, 13)]
    assert tool.rates(marks, 4.0, 8.0) == {
        "before": 10.0, "traced": 5.0, "after": 10.0}
    assert "after" not in tool.rates(marks, 6.0, 10.0)
