"""chip_smoke.py on the CPU: both phases at smoke size through the same
functions the chip run calls, and ``main()`` refusing a host without a
TPU."""

import importlib.util
import os
import sys

import jax
import pytest

from repro.configs import smollm_135m, vgg16_l2r

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def test_phase_vgg_smoke(chip_smoke):
    out = chip_smoke.phase_vgg(vgg16_l2r.SMOKE,
                               chip_smoke.VggSizes(batch=2, image=32),
                               backend="jnp")
    assert out["rel_err"] <= chip_smoke.VGG_LOGIT_TOL
    assert 0 <= out["mean_exit_level"] <= 2 * vgg16_l2r.SMOKE.quant.planes - 2


def test_phase_lm_smoke(chip_smoke):
    sizes = chip_smoke.LmSizes(n_requests=3, prompt_min=5, prompt_max=20,
                               new_tokens=4, n_slots=2)
    out = chip_smoke.phase_lm(smollm_135m.SMOKE, sizes)
    st = out["stats"]
    assert st["completed"] == 3 and st["tokens"] == 3 * 4
    assert out["rel_err"] <= chip_smoke.LM_LOGIT_TOL


def test_phase_lm_mesh_smoke(chip_smoke):
    """The four-chip phase on a 1x1 mesh: the meshed gateway path runs
    and serves exactly what one device serves."""
    sizes = chip_smoke.LmSizes(n_requests=2, prompt_min=5, prompt_max=12,
                               new_tokens=3, n_slots=2)
    out = chip_smoke.phase_lm_mesh(smollm_135m.SMOKE, sizes, model=1)
    assert out["stats"]["completed"] == 2


def test_main_refuses_cpu(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    cache_dir = jax.config.jax_compilation_cache_dir
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
    # refused before the compile cache is pointed anywhere
    assert jax.config.jax_compilation_cache_dir == cache_dir



def test_compile_cache_dir(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR when set, else the fixed in-checkout
    path; nothing is compiled while it is pointed here."""
    from repro.launch.compile_cache import (CACHE_ENV_VAR,
                                            default_cache_dir,
                                            enable_compile_cache)

    repo = os.path.dirname(_PATH)
    assert default_cache_dir() == os.path.join(repo, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(CACHE_ENV_VAR, "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"
        monkeypatch.delenv(CACHE_ENV_VAR)
        assert enable_compile_cache() == default_cache_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
