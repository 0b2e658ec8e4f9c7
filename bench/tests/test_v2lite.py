"""The DeepSeek-V2-Lite cell: its work counts against the program's
jaxpr, and whole runs on the CPU at a small size (2 layers, hidden 256,
4 slots, short prompts) where a sound run reads ``correct: true`` and
the lower-precision control and each fault read ``correct: false``.
The limits are the configuration's own."""

import dataclasses
import json
import math
import time

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.control import control_config, plant
from bench.run import load_module

CELL = "deepseek-v2-lite-offline"


def _bench():
    with open(f"{run.ROOT}/BENCHMARK.json") as f:
        return json.load(f)


def _eqns(jaxpr, mult=1):
    """(equation, how many times it runs) through scan and call bodies."""
    for e in jaxpr.eqns:
        yield e, mult
        for val in e.params.values():
            m = mult * e.params["length"] if e.primitive.name == "scan" \
                else mult
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, m)


def _dot(e):
    """(batch size, multiply-adds) of a dot_general equation."""
    (lc, rc), (lb, rb) = e.params["dimension_numbers"]
    ls, rs = e.invars[0].aval.shape, e.invars[1].aval.shape
    contract = math.prod(ls[i] for i in lc)
    batch = math.prod(ls[i] for i in lb)
    lfree = math.prod(d for i, d in enumerate(ls) if i not in lc + lb)
    rfree = math.prod(d for i, d in enumerate(rs) if i not in rc + rb)
    return batch, lfree * contract * rfree * batch


def test_work_counts_match_the_model():
    """At published widths with one dense and one MoE layer, the float
    program's prefill jaxpr holds the per-token matmuls of
    ``token_gemms`` and ``float_gemms`` for every token, the held
    experts' matmuls for every held expert and token (at 12 rows serving
    computes them all; the work counts only the routed rows), and
    attention's
    full score square at ``attn_macs`` per position attended."""
    from repro.models.common import abstract
    from repro.models.transformer import init_lm_state, lm_build, lm_forward

    cell = run.Cell(_bench(), CELL)
    cfg = dict(cell.config, num_hidden_layers=2)
    pc = dataclasses.replace(cell.system.program_config(cfg), l2r=None,
                             compute_dtype="float32")
    model = cell.model
    params = abstract(lm_build(pc))
    s = 12  # neither the heads' 16 nor the held experts' 8

    def fwd(p, t):
        st = init_lm_state(pc, 1, s, jnp.float32)
        return lm_forward(pc, p, tokens=t, mode="prefill", state=st)[0]

    jaxpr = jax.make_jaxpr(fwd)(params, jax.ShapeDtypeStruct(
        (1, s), jnp.int32)).jaxpr
    dots = [(_dot(e), n) for e, n in _eqns(jaxpr)
            if e.primitive.name == "dot_general"]
    # unbatched: the weight matmuls and the rope scores (one k_pe for
    # every head); batched over heads: the other scores and the values
    heads = cfg["num_attention_heads"]
    plain = sum(n * macs for (b, macs), n in dots if b in (1, heads))
    per_token = sum(k * nn for name, k, nn in model.token_gemms(cfg)
                    if name != "head") \
        + sum(k * nn for _, k, nn in model.float_gemms(cfg))
    assert plain == s * per_token + s * model.attn_macs(cfg, s)
    eh = cfg["n_routed_experts"]
    experts = sum(n * macs for (b, macs), n in dots if b == eh)
    assert experts == eh * s * sum(k * nn for _, k, nn
                                   in model.expert_gemms(cfg))
    # the published count: 15.7B parameters at 27 layers, 64 experts and
    # the whole vocabulary (norms and the router's bias-free rows aside)
    full = dict(cfg, num_hidden_layers=27, n_routed_experts=64,
                vocab_size=102400)
    weights = (sum(k * nn for _, k, nn in model.token_gemms(full))
               + sum(k * nn for _, k, nn in model.float_gemms(full))
               + 26 * 64 * sum(k * nn for _, k, nn
                               in model.expert_gemms(full))
               + 102400 * 2048)
    assert abs(weights / 15.7e9 - 1) < 0.01


def _cell(bits=None):
    bench = _bench()
    cell = run.Cell(bench, CELL)
    small = dict(cell.config, num_hidden_layers=2, hidden_size=256,
                 num_attention_heads=4, num_key_value_heads=4,
                 kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
                 v_head_dim=32, intermediate_size=512,
                 moe_intermediate_size=128, vocab_size=2048)
    mix = json.loads(json.dumps(cell.mix))
    mix.update(backlog=8, cycle=16, block=16, chunk_steps=8)
    mix["serving"].update(n_slots=4, max_len=128)
    mix["prompt"].update(median=30, min=8, max=60)
    # outputs long enough that a decode which loses its cache drifts
    mix["output"].update(median=36, min=24, max=48)
    return run.Cell(bench, CELL, config=control_config(small, bits),
                    mix=mix)


def _run(cell, seconds=8.0):
    return run.run_cell(cell, 2**31 + 17, seconds, False,
                        t_start=time.perf_counter())


def test_sound_run_is_correct():
    out = _run(_cell())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["metrics"]["tokens_per_s"]["value"] > 0


def test_control_fails():
    out = _run(_cell(bits=4))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_fault_fails(monkeypatch, fault):
    plant(fault, monkeypatch.setattr)
    out = _run(_cell())
    assert not out["correct"], out["checks"]


def test_reference_imports_nothing_of_the_program():
    import ast

    src = open(f"{run.BENCH_DIR}/configs/deepseek-v2-lite-ep8.py").read()
    mods = {a.name.split(".")[0] for n in ast.walk(ast.parse(src))
            if isinstance(n, ast.Import) for a in n.names} | {
        n.module.split(".")[0] for n in ast.walk(ast.parse(src))
        if isinstance(n, ast.ImportFrom) and n.module}
    assert mods <= {"__future__", "functools", "math", "jax", "numpy"}
    load_module(f"{run.BENCH_DIR}/configs/deepseek-v2-lite-ep8.py", "ref")
