"""The work counts that the roofline and MFU numerators use equal the
matmul shapes the program's model calls, read from its jaxpr, so they
are not typed in by hand."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp

from bench.run import load_module

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f), load_module(os.path.join(CONFIGS, name + ".py"),
                                         "bench_model_" + name.replace("-",
                                                                       "_"))


def _eqns(jaxpr, mult=1):
    """(equation, how many times it runs) through scan and call bodies."""
    for e in jaxpr.eqns:
        yield e, mult
        for name, val in e.params.items():
            m = mult * e.params["length"] if e.primitive.name == "scan" \
                else mult
            subs = val if isinstance(val, (tuple, list)) else (val,)
            for sub in subs:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, m)


def _dot_macs(e):
    (lc, rc), (lb, rb) = e.params["dimension_numbers"]
    ls, rs = e.invars[0].aval.shape, e.invars[1].aval.shape
    contract = math.prod(ls[i] for i in lc)
    batch = math.prod(ls[i] for i in lb)
    lfree = math.prod(d for i, d in enumerate(ls) if i not in lc + lb)
    rfree = math.prod(d for i, d in enumerate(rs) if i not in rc + rb)
    return batch, lfree * contract * rfree * batch


def test_vgg16_gemms_match_the_model():
    from repro.models.cnn import vgg16_apply, vgg16_build
    from repro.models.common import abstract

    cfg, model = _config("vgg16-l2r")
    batch = 2
    params = abstract(vgg16_build(cfg["num_classes"], cfg["in_channels"]))
    images = jax.ShapeDtypeStruct(
        (batch, cfg["image_size"], cfg["image_size"], cfg["in_channels"]),
        jnp.float32)
    jaxpr = jax.make_jaxpr(vgg16_apply)(params, images).jaxpr
    got = []
    for e, n in _eqns(jaxpr):
        if e.primitive.name == "conv_general_dilated":
            out = e.outvars[0].aval.shape
            kh, kw, cin, cout = e.invars[1].aval.shape
            got.append((math.prod(out[:3]), kh * kw * cin, cout))
        elif e.primitive.name == "dot_general":
            ls, rs = e.invars[0].aval.shape, e.invars[1].aval.shape
            got.append((ls[0], ls[1], rs[1]))
    want = [(m, k, n) for _, m, k, n, _ in model.gemms(cfg, batch)]
    assert got == want
    assert model.macs_per_image(cfg) == sum(m * k * n for m, k, n in want) \
        // batch
    # the published count of VGG-16 at 224x224: 15.47 GMACs per image
    assert abs(model.macs_per_image(cfg) / 15.47e9 - 1) < 0.01


def test_smollm_dense_macs_match_the_model():
    from repro.configs import smollm_135m
    from repro.models.common import abstract
    from repro.models.transformer import (lm_build, lm_forward,
                                          logits_from_hidden)

    cfg, model = _config("smollm-135m")
    cfg = dict(cfg, num_hidden_layers=3)
    pc = dataclasses.replace(smollm_135m.CONFIG, n_layers=3,
                             compute_dtype="float32")
    params = abstract(lm_build(pc))
    s = 16

    def fwd(p, t):
        h, _, _ = lm_forward(pc, p, tokens=t, mode="train")
        return logits_from_hidden(pc, p, h)

    tokens = jax.ShapeDtypeStruct((1, s), jnp.int32)
    jaxpr = jax.make_jaxpr(fwd)(params, tokens).jaxpr
    dense = sum(n * macs for e, n in _eqns(jaxpr)
                if e.primitive.name == "dot_general"
                for batch, macs in [_dot_macs(e)] if batch == 1)
    assert dense == s * model.macs_per_token(cfg, 0)["dense"]
    layer = model.layer_macs_per_token(cfg)
    assert layer == model.macs_per_token(cfg, 0)["dense"] \
        - cfg["hidden_size"] * cfg["vocab_size"]
    # attention at context c: QK^T and PV, each heads x head_dim x c
    assert model.macs_per_token(cfg, 10)["attn"] == 2 * 3 * 9 * 64 * 10


def test_smollm_weight_count():
    cfg, model = _config("smollm-135m")
    weights = sum(k * n for name, k, n in model.dense_gemms(cfg)
                  if name != "head")
    # the published parameter count: 134.5M with the 28.3M tied embedding
    assert abs((weights + 49152 * 576 + 61 * 576) / 134.5e6 - 1) < 0.01
