"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — jax locks the device count on first init,
and only the dry-run entry point is allowed to request 512 placeholder
devices via XLA_FLAGS.

Every mesh here has ``Auto`` axes: the model and step code shard by
PartitionSpec annotations and ``with_sharding_constraint`` and leave the
rest to the partitioner, which ``jax.make_mesh``'s default ``Explicit`` axes
refuse.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "available_devices",
           "mesh_split_options", "parse_mesh_split"]


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh: one v5e pod is 16×16 = 256 chips
    (data × model); the multi-pod config is 2 pods = 512 chips with a
    leading 'pod' axis (DP across pods over DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """Arbitrary mesh (tests, elastic re-meshing, deployment search) over
    ``devices`` (default: all)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def available_devices() -> int:
    return len(jax.devices())


def mesh_split_options(devices: int) -> tuple:
    """Canonical ``data×model`` splits of a ``devices``-chip slice, as
    ``"DxM"`` labels: full-TP, the most-square split, full-DP.

    Every power-of-two topology yields the SAME number of options in the
    same semantic order (TP-heavy → balanced → DP-heavy) for ``devices >=
    4``, so two family-sibling Discovery Spaces on different topologies have
    same-cardinality categorical mesh dimensions — exactly what the
    catalog's positional rename inference needs to bridge them (§IV-1).
    Pure arithmetic: never touches jax device state.
    """
    if devices < 1 or devices & (devices - 1):
        raise ValueError(f"devices must be a power of two, got {devices}")
    half = 1
    while half * half < devices:
        half *= 2
    splits = [(1, devices), (devices // half, half), (devices, 1)]
    seen, out = set(), []
    for data, model in splits:
        if (data, model) not in seen:
            seen.add((data, model))
            out.append(f"{data}x{model}")
    return tuple(out)


def parse_mesh_split(label: str) -> tuple:
    """``"2x4"`` → ``(2, 4)`` (data, model)."""
    data, _, model = label.partition("x")
    return int(data), int(model)
