"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (one TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

A FUNCTION, not a module constant, so importing never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""

from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Elastic variant: any (pod, data, model) factorization of the job's
    device count (checkpoints are mesh-independent, see checkpoint/)."""
    return _mk(shape, axes)


def make_serve_mesh(data: int, model: int = 1):
    """(data, model) mesh for the mesh-sharded serving engine
    (`EngineConfig(mesh=...)`): decode slots + the slot-affine KV pool split
    over "data", packed weights over "model". Tests simulate `data=2` on CPU
    via `--xla_force_host_platform_device_count` (set before any jax import;
    tests/conftest.py does this for the whole suite)."""
    return _mk((data, model), ("data", "model"))
