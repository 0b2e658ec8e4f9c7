"""SmolLM-135M: weights from the seed, the plain reference, work counts.

The reference is the published Llama-architecture forward in
``jax.numpy`` at float32 and ``highest`` matmul precision, with no
kernels, cache or batching: embedding, per layer a pre-RMSNorm GQA
attention with rotary positions (the rotate-half form) and a pre-RMSNorm
SwiGLU MLP, a final RMSNorm, and the head tied to the embedding.  It
imports nothing of the program.  Departures from the published model:
none in the equations; the weights are random (``make_weights``).

Weights are laid out as the serving program loads them: layers stacked
on a leading axis under ``stack[0]``, q/k/v/o as ``mixer.wq`` ...,
gate and up projections side by side in ``ffn.wi`` (d, 2, d_ff), and
each RMSNorm gain stored as ``g`` with the layer computing ``x * (1 + g)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "H": cfg["num_attention_heads"],
            "K": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "V": cfg["vocab_size"]}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number a run may be given."""
    seed &= 2**64 - 1
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed >> 31)


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight, float32, made on the device in one jitted call."""
    n = dims(cfg)
    d, f, L, H, K, dh, V = (n[k] for k in ("d", "f", "L", "H", "K", "dh",
                                           "V"))
    std = cfg["initializer_range"]
    shapes = {"embed": (V, d), "wq": (L, d, H * dh), "wk": (L, d, K * dh),
              "wv": (L, d, K * dh), "wo": (L, H * dh, d),
              "wi": (L, d, 2, f), "wo2": (L, f, d)}

    @jax.jit
    def make(key):
        keys = dict(zip(shapes, jax.random.split(key, len(shapes))))
        w = {k: jax.random.normal(keys[k], s, jnp.float32) * std
             for k, s in shapes.items()}
        zeros = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
        return {
            "embed": w["embed"], "final_norm": zeros(d), "prefix": [],
            "suffix": [],
            "stack": [{"mixer_norm": zeros(L, d), "ffn_norm": zeros(L, d),
                       "mixer": {"wq": w["wq"], "wk": w["wk"],
                                 "wv": w["wv"], "wo": w["wo"]},
                       "ffn": {"wi": w["wi"], "wo": w["wo2"]}}]}

    return make(seed_key(seed))


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, theta):
    """x (S, heads, dh): rotate-half rotary embedding at positions 0..S-1."""
    s, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward_logits(cfg: dict, w: dict, tokens: jax.Array) -> jax.Array:
    """Logits (S, V) of one causal sequence ``tokens`` (S,)."""
    n = dims(cfg)
    H, K, dh = n["H"], n["K"], n["dh"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = tokens.shape[0]

    def mm(x, wt):
        return x @ wt.reshape(wt.shape[0], -1)

    mask = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        h = _rms(x, p["mixer_norm"], eps)
        q = _rope(mm(h, p["mixer"]["wq"]).reshape(s, H, dh), theta)
        k = _rope(mm(h, p["mixer"]["wk"]).reshape(s, K, dh), theta)
        v = mm(h, p["mixer"]["wv"]).reshape(s, K, dh)
        k = jnp.repeat(k, H // K, axis=1)  # head i reads kv head i // (H/K)
        v = jnp.repeat(v, H // K, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dh)
        sc = jnp.where(mask[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        x = x + mm(o.reshape(s, H * dh), p["mixer"]["wo"])
        h = _rms(x, p["ffn_norm"], eps)
        a = mm(h, p["ffn"]["wi"]).reshape(s, 2, -1)
        x = x + mm(jax.nn.silu(a[:, 0]) * a[:, 1], p["ffn"]["wo"])
        return x, None

    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens]
        x, _ = jax.lax.scan(layer, x, w["stack"][0])
        x = _rms(x, w["final_norm"], eps)
        return mm(x, w["embed"].T)


@functools.partial(jax.jit, static_argnums=0)
def reference_gaps(cfg_items: tuple, w: dict, tokens: jax.Array,
                   picks: jax.Array) -> jax.Array:
    """At every position p of one sequence ``tokens`` (S,): the reference's
    best logit minus its logit of ``picks[p]``, the token some path put
    next.  0 means the reference agrees; the gap says by how much a
    token it did not put first lies below its best."""
    ref = forward_logits(dict(cfg_items), w, tokens)
    return ref.max(-1) - jnp.take_along_axis(ref, picks[:, None], -1)[:, 0]


# ------------------------------------------------------------ work counts
def dense_gemms(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of every weight matmul one token makes: per layer
    q, k, v, o, the gate/up pair and down, then the tied head."""
    n = dims(cfg)
    d, f, H, K, dh = n["d"], n["f"], n["H"], n["K"], n["dh"]
    per_layer = [("wq", d, H * dh), ("wk", d, K * dh), ("wv", d, K * dh),
                 ("wo", H * dh, d), ("wi", d, 2 * f), ("wo2", f, d)]
    return per_layer * n["L"] + [("head", d, n["V"])]


def macs_per_token(cfg: dict, context: int) -> dict:
    """Multiply-adds of one token at ``context`` positions of attention
    (its own included): the weight matmuls (int8 on the L2R path) and
    attention's QK^T and PV (float)."""
    n = dims(cfg)
    dense = sum(k * nn for _, k, nn in dense_gemms(cfg))
    attn = 2 * n["L"] * n["H"] * n["dh"] * context
    return {"dense": dense, "attn": attn}


def layer_macs_per_token(cfg: dict) -> int:
    """Multiply-adds of one token in the layers' weight matmuls: the L2R
    GEMM work (the head runs as the head walk)."""
    return sum(k * nn for name, k, nn in dense_gemms(cfg) if name != "head")
