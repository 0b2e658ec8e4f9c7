"""Production mesh construction (device state touched only inside fns)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "install_local_mesh",
           "VIRTUAL_DEVICES_FLAG", "virtual_device_env"]

# Host-platform virtual devices: the ONE way to get a multi-device CPU
# process (must be set before jax initializes — subprocess tests, the
# sharded bench rows, and the virtual-8-device CI job all use it).
VIRTUAL_DEVICES_FLAG = "--xla_force_host_platform_device_count={n}"


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: (data=16, model=16) = 256 chips; multi_pod adds a
    leading pod axis: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke).

    Axes are ``Auto``: GSPMD propagates shardings from the
    ``with_sharding_constraint`` hints (sharding/ctx.py), which reject
    ``Explicit`` axes — the ``jax.make_mesh`` default."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def install_local_mesh(data: int = 1, model: int = 1):
    """Build a local (data, model) mesh AND install it as the module
    mesh context (sharding/ctx.py) so the whole serving stack — the
    sharded consensus head walk, the weight-cache plane-stack sharding,
    the batcher's slot-state placement — routes through it.  Returns the
    mesh; ``sharding.ctx.set_mesh(None)`` uninstalls."""
    from repro.sharding import ctx

    mesh = make_local_mesh(data, model)
    ctx.set_mesh(mesh)
    return mesh


def virtual_device_env(n: int, env: dict | None = None) -> dict:
    """A copy of ``env`` (default os.environ) whose XLA_FLAGS force ``n``
    host-platform virtual devices — for SUBPROCESSES that need a
    multi-device CPU (the flag is read once at jax init, so the current
    process cannot apply it to itself).  Existing XLA_FLAGS are
    preserved; an existing device-count flag is overridden."""
    import os

    out = dict(os.environ if env is None else env)
    flags = [f for f in out.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(VIRTUAL_DEVICES_FLAG.format(n=n))
    out["XLA_FLAGS"] = " ".join(flags)
    return out
