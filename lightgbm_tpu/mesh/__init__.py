"""Mesh runtime: the shared device-topology layer under training AND
serving.

 - ``topology`` — discovery + normalization of 1-D, 2-level (dcn×ici)
                  and virtual-CPU meshes; the ``mesh_shape`` param.
 - ``placement``— mesh-divisible padding math, per-device placement
                  accounting, streamed datastore→device sharding.

``parallel/`` (distributed training) and ``serving/sharded.py`` (the
striped serving plane) both build on this package; ``parallel/mesh.py``
remains as a thin re-export shim for older imports.
"""
from .placement import (collective_span, padded_feature_count,  # noqa: F401
                        padded_row_count, place_from_datastore,
                        record_placement)
from .topology import (build_mesh, describe, get_mesh,  # noqa: F401
                       get_mesh_2level, init, parse_mesh_shape)

__all__ = [
    "build_mesh", "describe", "get_mesh", "get_mesh_2level", "init",
    "parse_mesh_shape",
    "collective_span", "padded_feature_count", "padded_row_count",
    "place_from_datastore", "record_placement",
]
