"""VGG-16: weights from the seed, the plain reference, work counts.

The reference is configuration D of the VGG paper in ``jax.numpy`` at
float32 and ``highest`` matmul precision: thirteen 3x3 'SAME'
convolutions with bias and ReLU in five stages, each stage closed by a
2x2 max pool, then fc6 and fc7 with ReLU and the fc8 classifier.  It
imports nothing of the program.  Departures from the paper: no dropout
(inference) and random weights (``make_weights``).

Weights are laid out as the program loads them: ``conv<s>_<i>`` and
``fc6``..``fc8``, each ``{"w", "b"}``, convolution kernels HWIO, and the
flattened 7x7x512 feature map in height, width, channel order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number a run may be given."""
    seed &= 2**64 - 1
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed >> 31)


def conv_layers(cfg: dict) -> list[tuple[str, int, int, int]]:
    """(name, cin, cout, size of its output feature map) of every conv."""
    out, cin, size = [], cfg["in_channels"], cfg["image_size"]
    for s, stage in enumerate(cfg["stages"], 1):
        for i, cout in enumerate(stage, 1):
            out.append((f"conv{s}_{i}", cin, cout, size))
            cin = cout
        size //= 2
    return out


def fc_layers(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of fc6, fc7, fc8."""
    size = cfg["image_size"] // 2 ** len(cfg["stages"])
    k = cfg["stages"][-1][-1] * size * size
    out = []
    for i, n in enumerate(cfg["fc_sizes"] + [cfg["num_classes"]]):
        out.append((f"fc{6 + i}", k, n))
        k = n
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight, float32, made on the device in one jitted call."""
    kk = cfg["conv_kernel"]
    convs, fcs = conv_layers(cfg), fc_layers(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(convs) + len(fcs))
        w = {}
        for key_, (name, cin, cout, _) in zip(keys, convs):
            std = (2.0 / (kk * kk * cout)) ** 0.5  # Kaiming, fan_out, relu
            w[name] = {"w": jax.random.normal(key_, (kk, kk, cin, cout))
                       * std, "b": jnp.zeros((cout,))}
        for key_, (name, k, n) in zip(keys[len(convs):], fcs):
            w[name] = {"w": jax.random.normal(key_, (k, n)) * 0.01,
                       "b": jnp.zeros((n,))}
        return w

    return make(seed_key(seed))


def forward_logits(cfg: dict, w: dict, images: jax.Array) -> jax.Array:
    """Logits (B, classes) of images (B, H, W, C), float32."""
    with jax.default_matmul_precision("highest"):
        x = images.astype(jnp.float32)
        for s, stage in enumerate(cfg["stages"], 1):
            for i in range(1, len(stage) + 1):
                p = w[f"conv{s}_{i}"]
                x = jax.lax.conv_general_dilated(
                    x, p["w"], (1, 1), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
                x = jax.nn.relu(x)
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        x = x.reshape(x.shape[0], -1)
        names = [n for n, _, _ in fc_layers(cfg)]
        for name in names[:-1]:
            x = jax.nn.relu(x @ w[name]["w"] + w[name]["b"])
        return x @ w[names[-1]]["w"] + w[names[-1]]["b"]


# ------------------------------------------------------------ work counts
def gemms(cfg: dict, batch: int) -> list[tuple[str, int, int, int, int]]:
    """(name, M, K, N, A bytes) of every layer's matmul for one batch: a
    convolution is the GEMM of its output pixels (M) by its window
    (K = k*k*cin) by cout; its int8 input is the feature map itself
    (A bytes), not the window matrix."""
    kk = cfg["conv_kernel"]
    out = [(name, batch * size * size, kk * kk * cin, cout,
            batch * size * size * cin)
           for name, cin, cout, size in conv_layers(cfg)]
    out += [(name, batch, k, n, batch * k) for name, k, n in fc_layers(cfg)]
    return out


def macs_per_image(cfg: dict) -> int:
    return sum(m * k * n for _, m, k, n, _ in gemms(cfg, 1))


def gemm_bytes(cfg: dict, batch: int, out_bytes: int = 4) -> list[float]:
    """Least bytes of each layer's matmul: int8 input and weights read
    once, the output written once."""
    return [float(a + k * n + m * n * out_bytes)
            for _, m, k, n, a in gemms(cfg, batch)]
