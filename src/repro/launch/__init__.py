"""`repro.launch` — meshes, dry-run lowering, rooflines, HLO cost reads.

`dryrun`/`serve`/`train` stay module imports (they are CLI entry points
with heavy import-time work); the mesh helpers and analysis classes are the
programmatic surface.
"""
from repro.launch.hlo_cost import HloCost
from repro.launch.mesh import (make_local_mesh, make_mesh,
                               make_production_mesh, mesh_to_slice)
from repro.launch.roofline import Roofline, collective_bytes_from_hlo

__all__ = [
    "HloCost", "Roofline", "collective_bytes_from_hlo", "make_local_mesh",
    "make_mesh", "make_production_mesh", "mesh_to_slice",
]
