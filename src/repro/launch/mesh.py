"""Production mesh builders.

Defined as functions (never module-level constants) so importing this
module never touches jax device state -- the dry-run must set
``XLA_FLAGS`` before the first jax initialisation.
"""
from __future__ import annotations

from repro.core.distributed import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Scaled-down mesh for CI (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def batch_axes(mesh) -> tuple:
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))
