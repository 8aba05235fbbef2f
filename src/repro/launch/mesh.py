"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  The dry-run is the
only entry point that forces 512 host devices (see launch/dryrun.py's first
two lines).

Mesh-to-torus mapping: the logical ("data", "model") axes are laid out so the
"model" axis maps onto one face of the physical 3D torus slice (densest
collectives on the shortest paths) and "data"/"pod" span the remaining dims —
the §2.7 guidance made concrete by ``mesh_to_slice``.
"""
from __future__ import annotations

from typing import Tuple

import jax

from repro.core.topology import SliceTopology


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: the model code places
    arrays with sharding constraints and PartitionSpecs, never with
    explicit-sharding types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(shape: Tuple[int, ...] = (1, 1),
                    axes: Tuple[str, ...] = ("data", "model")):
    """Mesh over however many devices exist (tests/smoke)."""
    return make_mesh(shape, axes)


def mesh_to_slice(multi_pod: bool = False,
                  twisted: bool = False) -> SliceTopology:
    """The physical torus slice a production mesh runs on.

    Single pod: 256 chips as the 8×8×4 slice (the model axis maps to the
    8×8 faces).  Multi-pod: 512 chips as 8×8×8 — twistable per §2.8? No:
    twisting needs n×n×2n; 512 = 4×8×16_T would twist, 8×8×8 is the
    max-bisection cube (§2.8).  ``twisted`` selects 4×8×16_T where legal.
    """
    if multi_pod:
        dims = (4, 8, 16) if twisted else (8, 8, 8)
    else:
        dims = (4, 4, 16) if twisted else (4, 8, 8)
    return SliceTopology(dims, twisted=twisted)
