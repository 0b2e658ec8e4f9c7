"""Whole runs at a size the CPU holds, with the chip check skipped: a
sound run reads ``correct: true``; the lower-precision control (the
program's own L2R path at 4-bit operands) and each fault the cell can
have, planted in the timed path, read ``correct: false``.

The limits are the configurations' own.  Sizes: VGG-16 as published,
batch 2; SmolLM-135M at its published widths with 4 layers,
4 slots, short prompts and outputs of 24-48 tokens.
"""

import json
import time

import pytest

from bench import run
from bench.control import control_config, plant


def _bench():
    with open(f"{run.ROOT}/BENCHMARK.json") as f:
        return json.load(f)


def _vgg_cell(bits=None):
    bench = _bench()
    cell = run.Cell(bench, "vgg16-224-b8")
    cfg = control_config(cell.config, bits)
    mix = dict(cell.mix, batch=2, pool_batches=1, in_flight=1)
    return run.Cell(bench, "vgg16-224-b8", config=cfg, mix=mix)


def _lm_cell(bits=None):
    bench = _bench()
    cell = run.Cell(bench, "smollm-135m-chat")
    cfg = control_config(dict(cell.config, num_hidden_layers=4), bits)
    mix = json.loads(json.dumps(cell.mix))
    mix.update(rate_per_s=3.0, drain_s=300)
    mix["serving"].update(n_slots=4, max_len=128)
    mix["prompt"].update(median=30, min=8, max=60)
    # outputs long enough that a decode which loses its cache drifts
    # from the reference
    mix["output"].update(median=36, min=24, max=48)
    return run.Cell(bench, "smollm-135m-chat", config=cfg, mix=mix)


def _run(cell, seconds):
    return run.run_cell(cell, 2**31 + 17, seconds, False,
                        t_start=time.perf_counter())


@pytest.fixture
def broken(monkeypatch):
    return lambda kind: plant(kind, monkeypatch.setattr)


# ------------------------------------------------------------------ VGG
def test_vgg_sound_run_is_correct():
    out = _run(_vgg_cell(), 1.0)
    assert out["correct"], out["checks"]
    assert out["metrics"]["images_per_s"]["value"] > 0


def test_vgg_control_fails():
    out = _run(_vgg_cell(bits=4), 1.0)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch",
                                   "wrong_image"])
def test_vgg_fault_fails(broken, fault):
    broken(fault)
    out = _run(_vgg_cell(), 1.0)
    assert not out["correct"], out["checks"]


# ------------------------------------------------------------------- LM
def test_lm_sound_run_is_correct():
    out = _run(_lm_cell(), 2.0)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


def test_lm_control_fails():
    out = _run(_lm_cell(bits=4), 2.0)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_lm_fault_fails(broken, fault):
    broken(fault)
    out = _run(_lm_cell(), 2.0)
    assert not out["correct"], out["checks"]
