"""Topology discovery and mesh normalization.

Absorbs ``parallel/mesh.py`` (which remains as a re-export shim) and
extends it with a declarative ``mesh_shape`` surface shared by training
and serving:

 - 1-D data meshes (``get_mesh``) — rows sharded over every device.
 - 2-level dcn×ici meshes (``get_mesh_2level``) — histogram traffic
   rides ICI within a slice, only the reduced blocks cross DCN.
 - virtual CPU meshes for CI: ``XLA_FLAGS=--xla_force_host_platform_
   device_count=N`` makes one host expose N devices; every shape here
   works identically on them (that is how the tier-1 distributed suite
   runs on the 8-virtual-device mesh).

ref parity: `Network::Init` + `Linkers::Construct`
(src/network/network.cpp, linkers_socket.cpp) and the Dask
machines/ports bootstrap (python-package/lightgbm/dask.py).  On TPU all
of it is `jax.distributed.initialize()` (multi-host) + one `Mesh` over
the devices; XLA routes collectives over ICI within a slice and DCN
across slices.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import numpy as np

from ..utils import log
from jax.sharding import Mesh

_initialized = False


def init(coordinator_address: Optional[str] = None,
         num_processes: Optional[int] = None,
         process_id: Optional[int] = None) -> None:
    """Multi-host bring-up (replaces machines/machine_list_file/port config;
    ref: Config network params + LGBM_NetworkInit).  Single-host callers can
    skip this entirely."""
    global _initialized
    if _initialized:
        return
    if coordinator_address is not None or num_processes is not None:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
    _initialized = True
    log.info(f"parallel.init: {jax.process_count()} process(es), "
             f"{len(jax.devices())} device(s)")


def get_mesh(num_shards: int = 0, axis: str = "data",
             devices: Optional[Sequence] = None) -> Mesh:
    """Build a 1-D data mesh over `num_shards` devices (0 = all visible)."""
    devs = list(devices) if devices is not None else jax.devices()
    if num_shards and num_shards > 0:
        if num_shards > len(devs):
            raise ValueError(
                f"num_shards={num_shards} exceeds visible devices "
                f"({len(devs)})")
        devs = devs[:num_shards]
    return Mesh(np.array(devs), (axis,))


def get_mesh_2level(n_dcn: int, n_ici: int = 0,
                    devices: Optional[Sequence] = None) -> Mesh:
    """2-level ("dcn", "ici") mesh for multi-slice training.

    The data-parallel grower reduce-scatters histograms over the fast
    "ici" axis (within a slice) and allreduces the summed blocks over
    "dcn" (across slices) — the layout SURVEY §2.7.5 prescribes so heavy
    traffic rides ICI, not the datacenter network.  With
    `jax.distributed.initialize` (see `init`), devices enumerate
    slice-major, so reshaping [n_dcn, n_ici] aligns axis 1 with real ICI
    neighbours."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_ici <= 0:
        if len(devs) % n_dcn:
            raise ValueError(f"{len(devs)} devices not divisible by "
                             f"n_dcn={n_dcn}")
        n_ici = len(devs) // n_dcn
    need = n_dcn * n_ici
    if need > len(devs):
        raise ValueError(f"mesh {n_dcn}x{n_ici} exceeds visible devices "
                         f"({len(devs)})")
    return Mesh(np.array(devs[:need]).reshape(n_dcn, n_ici),
                ("dcn", "ici"))


def parse_mesh_shape(value: Union[str, int, None]) -> Optional[Tuple[int, ...]]:
    """Parse the ``mesh_shape`` param: ``"8"`` → ``(8,)``,
    ``"2x4"`` → ``(2, 4)``, empty/None/0 → None (auto topology).

    Accepts ``x``, ``*`` or ``,`` as the separator; at most two levels
    (dcn × ici) are meaningful to the growers today."""
    if value is None:
        return None
    if isinstance(value, int):
        return (value,) if value > 0 else None
    s = str(value).strip().lower()
    if not s or s in ("0", "auto", "none"):
        return None
    for sep in ("x", "*", ","):
        s = s.replace(sep, " ")
    try:
        dims = tuple(int(p) for p in s.split())
    except ValueError:
        raise ValueError(f"mesh_shape={value!r} is not of the form "
                         f"'N' or 'DxI'")
    if not dims or any(d <= 0 for d in dims):
        raise ValueError(f"mesh_shape={value!r} must use positive dims")
    if len(dims) > 2:
        raise ValueError(f"mesh_shape={value!r}: at most 2 mesh levels "
                         f"(dcn x ici) are supported")
    return dims


def build_mesh(mesh_shape: Union[str, int, None] = None,
               num_shards: int = 0, dcn_slices: int = 0,
               devices: Optional[Sequence] = None) -> Mesh:
    """One resolver for every mesh the repo builds.

    Precedence: an explicit ``mesh_shape`` wins; otherwise
    ``dcn_slices>1`` selects the 2-level mesh and ``num_shards``
    (0 = all) sizes the 1-D data mesh — the pre-existing param surface.
    """
    dims = parse_mesh_shape(mesh_shape)
    if dims is not None:
        if len(dims) == 2:
            return get_mesh_2level(dims[0], dims[1], devices=devices)
        return get_mesh(dims[0], devices=devices)
    if dcn_slices and dcn_slices > 1:
        return get_mesh_2level(dcn_slices, devices=devices)
    return get_mesh(num_shards, devices=devices)


def describe(mesh: Mesh) -> dict:
    """Telemetry-friendly topology summary of a mesh."""
    devs = list(mesh.devices.flat)
    return {
        "axes": {name: int(mesh.shape[name]) for name in mesh.axis_names},
        "n_devices": len(devs),
        "platform": devs[0].platform if devs else "none",
        "device_ids": [int(d.id) for d in devs],
    }
