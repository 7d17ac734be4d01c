"""Production mesh construction.

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before any jax import,
smoke tests must keep seeing one device).
"""
from __future__ import annotations

import jax


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; (2, 16, 16) = 512 chips across 2 pods.

    Axes: ``data`` carries DP/FSDP + sequence-parallel KV pages, ``model``
    carries TP/EP, ``pod`` is pure cross-pod data parallelism (gradient
    reduction hierarchy: reduce-scatter in-pod over ICI, all-reduce of the
    scattered shards across pods over DCN).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests use small CPU meshes, e.g. (2, 4))."""
    return _mesh(shape, axes)
