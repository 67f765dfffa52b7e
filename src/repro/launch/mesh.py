"""Production mesh definitions (TPU v5e pods).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and tests/benches must keep seeing 1 device.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips with a 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """A ``(data, model)`` mesh over this host's devices.

    Raises when the host has fewer devices than the mesh asks for: a
    mesh that quietly shrank would serve unsharded under a sharded
    name."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"mesh data={data},model={model} needs {data * model} "
            f"devices; this host has {n} (CPU: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N before JAX starts)")
    return jax.make_mesh((data, model), ("data", "model"))


def parse_mesh_spec(spec: str):
    """``"data=4"`` / ``"data=4,model=2"`` -> axis-size dict.

    The grammar of the launchers' ``--mesh`` flag; axes it doesn't name
    default to 1.  Raises ValueError on unknown axes so a typo doesn't
    silently serve unsharded.
    """
    sizes = {"data": 1, "model": 1}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, val = part.partition("=")
        name = name.strip()
        if (name not in sizes or not val.strip().isdigit()
                or int(val) < 1):
            raise ValueError(
                f"--mesh {spec!r}: want e.g. 'data=4' or "
                f"'data=4,model=2' with positive sizes "
                f"(axes: {sorted(sizes)})")
        sizes[name] = int(val)
    return sizes


# Hardware constants for the roofline analysis (TPU v5e).
PEAK_FLOPS_BF16 = 197e12        # per chip, FLOP/s
HBM_BW = 819e9                  # per chip, bytes/s
ICI_BW = 50e9                   # per link, bytes/s (~per chip per direction)
HBM_PER_CHIP = 16 * 1024**3     # 16 GiB
