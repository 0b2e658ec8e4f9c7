"""DeepSeek-V2-Lite, one chip's share of an 8-way expert-parallel
deployment: weights from the seed, the plain reference, work counts.

The reference is the published DeepSeek-V2 forward (arXiv:2405.04434
§2.1-2.2; the ``deepseek_v2`` modelling code of the published
checkpoint) in ``jax.numpy`` at float32 and ``highest`` matmul
precision, with no kernels, cache or batching.  It imports nothing of
the program.  Per layer: a pre-RMSNorm multi-head latent attention with
no query compression, always in its decompressed form (``c_kv`` through
``W_kvb`` into per-head K and V), YaRN rope on the pairs (2i, 2i+1) of
the rope slice (the published permute-then-rotate-half); then a
pre-RMSNorm FFN: a SwiGLU MLP in layer 0, in the others a float32
softmax router over all ``n_routed_experts_published`` experts, top-k
greedy, gates the raw probabilities times ``routed_scaling_factor``
(``norm_topk_prob`` false), plus the shared experts as one SwiGLU of
``n_shared_experts`` x the expert width.  A final RMSNorm and the untied
head.

Departures from the published model, each the configuration's cut:
  * depth: ``num_hidden_layers`` of the published 27 (the dense layer
    and the first MoE layers; the rest lie on further pipeline stages);
  * held experts: the router keeps every published expert, but only the
    ``n_routed_experts`` from ``deployment.expert_offset`` on are here,
    and a token's choices of the others add nothing (what the chips
    holding them would add);
  * vocabulary: the first ``vocab_size`` rows (an eighth);
  * weights: random (``make_weights``).

Weights are laid out as the serving program loads them: layer 0 under
``prefix[0]``, the MoE layers stacked on a leading axis under
``stack[0]``; each RMSNorm gain stored as ``g`` with the layer computing
``x * (1 + g)``; gate and up projections side by side (``wi`` (d, 2,
f)); the routed experts stacked (``wi`` (E_held, d, 2, f)).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "r": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "f_dense": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "E": cfg["deployment"]["n_routed_experts_published"],
            "Eh": cfg["n_routed_experts"],
            "off": cfg["deployment"]["expert_offset"],
            "k": cfg["num_experts_per_tok"],
            "L": cfg["num_hidden_layers"],
            "L0": cfg["first_k_dense_replace"], "V": cfg["vocab_size"]}


def cfg_key(cfg: dict) -> tuple:
    """``cfg`` as a hashable static argument (nested groups as tuples)."""
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in cfg.items()
                        if k in ("rope_scaling", "deployment")
                        or not isinstance(v, (dict, list))))


def _cfg(key: tuple) -> dict:
    return {k: dict(v) if k in ("rope_scaling", "deployment") else v
            for k, v in key}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number a run may be given."""
    seed &= 2**64 - 1
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed >> 31)


def _shapes(cfg: dict) -> tuple[dict, dict, dict]:
    n = dims(cfg)
    d, H, r, dn, dr, dv = (n[k] for k in ("d", "H", "r", "dn", "dr", "dv"))
    mla = {"wq": (d, H * (dn + dr)), "wkv_a": (d, r + dr),
           "wkv_b": (r, H * (dn + dv)), "wo": (H * dv, d)}
    dense = {"wi": (d, 2, n["f_dense"]), "wo": (n["f_dense"], d)}
    moe = {"router": (d, n["E"]), "wi": (n["Eh"], d, 2, n["f"]),
           "wo": (n["Eh"], n["f"], d), "shared_wi": (d, 2, n["fs"]),
           "shared_wo": (n["fs"], d)}
    return mla, dense, moe


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight, float32, made on the device in one jitted call."""
    n = dims(cfg)
    d, V, Lm = n["d"], n["V"], n["L"] - n["L0"]
    assert n["L0"] == 1 and Lm >= 1, "one dense layer, then MoE layers"
    std = cfg["initializer_range"]
    mla, dense, moe = _shapes(cfg)
    shapes = {"embed": (V, d), "head": (d, V)}
    shapes.update({("pre", "mixer", k): s for k, s in mla.items()})
    shapes.update({("pre", "ffn", k): s for k, s in dense.items()})
    shapes.update({("stk", "mixer", k): (Lm, *s) for k, s in mla.items()})
    shapes.update({("stk", "ffn", k): (Lm, *s) for k, s in moe.items()})

    @jax.jit
    def make(key):
        keys = dict(zip(shapes, jax.random.split(key, len(shapes))))
        w = {k: jax.random.normal(keys[k], s, jnp.float32) * std
             for k, s in shapes.items()}
        zeros = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731

        def group(where, part):
            return {k[2]: v for k, v in w.items()
                    if isinstance(k, tuple) and k[:2] == (where, part)}
        pre = {"mixer_norm": zeros(d), "ffn_norm": zeros(d),
               "mixer": {**group("pre", "mixer"), "kv_norm": zeros(n["r"])},
               "ffn": group("pre", "ffn")}
        stk = {"mixer_norm": zeros(Lm, d), "ffn_norm": zeros(Lm, d),
               "mixer": {**group("stk", "mixer"),
                         "kv_norm": zeros(Lm, n["r"])},
               "ffn": group("stk", "ffn")}
        return {"embed": w["embed"], "head": w["head"],
                "final_norm": zeros(d), "prefix": [pre], "stack": [stk],
                "suffix": []}

    return make(seed_key(seed))


# --------------------------------------------------------------- reference
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def inv_freq(cfg: dict) -> np.ndarray:
    """YaRN inverse frequencies of the rope slice, as published:
    freq_extra = theta^(-2i/dim), freq_inter = freq_extra / factor, a
    linear ramp on i from floor(c(beta_fast)) to ceil(c(beta_slow)) with
    c(rot) = dim ln(original / (2 pi rot)) / (2 ln theta), and
    inv_freq = freq_inter (1 - m) + freq_extra m with m = 1 - ramp."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    i2 = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / base ** (i2 / dim)
    inter = 1.0 / (rs["factor"] * base ** (i2 / dim))

    def c(rot):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(c(rs["beta_fast"])), 0)
    high = min(math.ceil(c(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    m = 1.0 - ramp
    return inter * (1 - m) + extra * m


def _rope(x, cfg):
    """x (S, heads, dr): the published rope at positions 0..S-1 — the
    pairs (2i, 2i+1) permuted into halves, then rotate-half."""
    rs = cfg["rope_scaling"]
    s, h, dr = x.shape
    x = x.reshape(s, h, dr // 2, 2).transpose(0, 1, 3, 2).reshape(s, h, dr)
    f = jnp.asarray(inv_freq(cfg), jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * f[None]
    emb = jnp.concatenate([ang, ang], -1)[:, None]  # (S, 1, dr)
    m = _mscale(rs["factor"], rs["mscale"]) / _mscale(
        rs["factor"], rs["mscale_all_dim"])
    cos, sin = jnp.cos(emb) * m, jnp.sin(emb) * m
    rot = jnp.concatenate([-x[..., dr // 2:], x[..., :dr // 2]], -1)
    return x * cos + rot * sin


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = _mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def forward_logits(cfg: dict, w: dict, tokens: jax.Array) -> jax.Array:
    """Logits (S, V) of one causal sequence ``tokens`` (S,)."""
    n = dims(cfg)
    H, r, dn, dr, dv = (n[k] for k in ("H", "r", "dn", "dr", "dv"))
    eps = cfg["rms_norm_eps"]
    s = tokens.shape[0]
    mask = jnp.tril(jnp.ones((s, s), bool))
    scale = softmax_scale(cfg)

    def mla(h, p):
        q = (h @ p["wq"]).reshape(s, H, dn + dr)
        kva = h @ p["wkv_a"]
        c = _rms(kva[:, :r], p["kv_norm"], eps)
        kv = (c @ p["wkv_b"]).reshape(s, H, dn + dv)
        q_pe = _rope(q[..., dn:], cfg)
        k_pe = _rope(kva[:, None, r:], cfg)[:, 0]
        sc = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kv[..., :dn])
              + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)) * scale
        sc = jnp.where(mask[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), kv[..., dn:])
        return o.reshape(s, H * dv) @ p["wo"]

    def swiglu(h, wi, wo):
        a = (h @ wi.reshape(wi.shape[0], -1)).reshape(s, 2, -1)
        return (jax.nn.silu(a[:, 0]) * a[:, 1]) @ wo

    def moe(h, p):
        probs = jax.nn.softmax(h @ p["router"], -1)  # (S, E)
        top, idx = jax.lax.top_k(probs, n["k"])
        gates = top * cfg["routed_scaling_factor"]
        local = idx - n["off"]  # choices of experts held elsewhere: none
        comb = jnp.einsum("sk,ske->se", gates,
                          jax.nn.one_hot(local, n["Eh"]))
        y = sum(comb[:, e:e + 1] * swiglu(h, p["wi"][e], p["wo"][e])
                for e in range(n["Eh"]))
        return y + swiglu(h, p["shared_wi"], p["shared_wo"])

    def layer(x, p, ffn):
        x = x + mla(_rms(x, p["mixer_norm"], eps), p["mixer"])
        return x + ffn(_rms(x, p["ffn_norm"], eps), p["ffn"])

    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens]
        x = layer(x, w["prefix"][0],
                  lambda h, p: swiglu(h, p["wi"], p["wo"]))
        x, _ = jax.lax.scan(lambda x, p: (layer(x, p, moe), None), x,
                            w["stack"][0])
        return _rms(x, w["final_norm"], eps) @ w["head"]


@functools.partial(jax.jit, static_argnums=0)
def reference_gaps(key: tuple, w: dict, tokens: jax.Array,
                   picks: jax.Array) -> jax.Array:
    """At every position p of one sequence ``tokens`` (S,): the reference's
    best logit minus its logit of ``picks[p]``.  ``key`` is
    :func:`cfg_key` of the configuration."""
    ref = forward_logits(_cfg(key), w, tokens)
    return ref.max(-1) - jnp.take_along_axis(ref, picks[:, None], -1)[:, 0]


# ------------------------------------------------------------ work counts
def token_gemms(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of the L2R weight matmuls one token makes in every
    layer, whatever its routing: MLA's q, kv_a and o, layer 0's MLP,
    each MoE layer's shared experts; then the head."""
    n = dims(cfg)
    d, H = n["d"], n["H"]
    mla = [("wq", d, H * (n["dn"] + n["dr"])), ("wkv_a", d, n["r"] + n["dr"]),
           ("wo", H * n["dv"], d)]
    dense = [("wi", d, 2 * n["f_dense"]), ("wo2", n["f_dense"], d)]
    shared = [("shared_wi", d, 2 * n["fs"]), ("shared_wo", n["fs"], d)]
    return ((mla + dense) * n["L0"] + (mla + shared) * (n["L"] - n["L0"])
            + [("head", d, n["V"])])


def float_gemms(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of the float weight matmuls one token makes: MLA's
    latent up-projection in every layer, the router in MoE layers."""
    n = dims(cfg)
    kvb = ("wkv_b", n["r"], n["H"] * (n["dn"] + n["dv"]))
    return [kvb] * n["L"] + [("router", n["d"], n["E"])] * (n["L"] - n["L0"])


def expert_gemms(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of one routed expert's matmuls for one routed row."""
    n = dims(cfg)
    return [("experts_wi", n["d"], 2 * n["f"]), ("experts_wo", n["f"], n["d"])]


def attn_macs(cfg: dict, context: int) -> int:
    """Attention multiply-adds of one token at ``context`` positions, in
    the decompressed form: every head's score over nope + rope widths
    and its weighted sum of values, in every layer."""
    n = dims(cfg)
    return n["L"] * n["H"] * (n["dn"] + n["dr"] + n["dv"]) * context


def routed_per_token(cfg: dict) -> float:
    """Expert rows a token sends here on average per MoE layer: k of the
    router's experts, held here in the share E_held / E."""
    n = dims(cfg)
    return n["k"] * n["Eh"] / n["E"]
