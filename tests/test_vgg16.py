"""VGG-16 with the L2R conv path (the paper's evaluation network)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import QuantConfig
from repro.models.cnn import vgg16_build, vgg16_apply
from repro.models.common import count_params, materialize


@pytest.fixture(scope="module")
def setup():
    params = materialize(vgg16_build(n_classes=10), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.standard_normal((2, 32, 32, 3)).astype(np.float32))
    return params, img


def test_param_count_matches_vgg16():
    n = count_params(vgg16_build(n_classes=1000))
    # VGG-16: 138.36M params
    assert abs(n - 138.36e6) / 138.36e6 < 0.01, n


def test_float_forward(setup):
    params, img = setup
    logits = vgg16_apply(params, img)
    assert logits.shape == (2, 10)
    assert np.isfinite(np.asarray(logits)).all()


def test_l2r_path_close_to_float(setup):
    params, img = setup
    lf = np.asarray(vgg16_apply(params, img))
    lq = np.asarray(vgg16_apply(params, img, l2r=QuantConfig()))
    rel = np.abs(lq - lf).max() / (np.abs(lf).max() + 1e-9)
    assert rel < 0.25, rel  # int8 noise through 16 layers


def test_l2r_progressive_monotone(setup):
    params, img = setup
    exact = np.asarray(vgg16_apply(params, img, l2r=QuantConfig()))
    errs = []
    for lv in (3, 5, 7):
        out = np.asarray(vgg16_apply(params, img, l2r=QuantConfig(), levels=lv))
        errs.append(np.abs(out - exact).max())
    assert errs[-1] == 0  # 7 levels == full stream for radix-4 int8
    assert errs[0] >= errs[1] >= errs[2]


def test_l2r_radix16_exact_match(setup):
    """Radix choice must not change the exact result (same integer math)."""
    params, img = setup
    r4 = np.asarray(vgg16_apply(params, img, l2r=QuantConfig(log2_radix=2)))
    r16 = np.asarray(vgg16_apply(params, img, l2r=QuantConfig(log2_radix=4)))
    np.testing.assert_allclose(r4, r16, atol=1e-4)


def _scoped_ops(lowered) -> set[tuple[str, str]]:
    """(scope component, primitive) of every op of a lowered program, from
    the ``op_name`` metadata of the compiled module (where inner jits are
    inlined, so each op carries its caller's scopes)."""
    import re

    out = set()
    for name in re.findall(r'op_name="([^"]*)"',
                           lowered.compile().as_text()):
        parts = name.split("/")
        out.update((p, parts[-1]) for p in parts[:-1])
    return out


def test_layer_scopes_in_lowered_forward(setup):
    """Each layer's matmuls carry the layer's named scope, conv1_1 to fc8,
    so a device trace can put their time on the layer."""
    from repro.core.cycle_model import VGG16_CONV_LAYERS
    from repro.models.cnn import vgg16_quantize_weights

    params, img = setup
    q = QuantConfig()
    wq = vgg16_quantize_weights(params, q)
    low = jax.jit(lambda p, x, w: vgg16_apply(p, x, l2r=q, weights_q=w)
                  ).lower(params, img, wq)
    ops = _scoped_ops(low)
    for name in [layer.name for layer in VGG16_CONV_LAYERS] + [
            "fc6", "fc7", "fc8"]:
        assert (name, "dot_general") in ops, name
