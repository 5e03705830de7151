"""Per-device placement: padding math, accounting, streamed sharding.

Three jobs, shared by training and serving:

 - the canonical mesh-divisible padding helpers (`padded_feature_count`
   / `padded_row_count`) — every placement and every grower must agree
   on these or shard shapes drift;
 - per-device placement accounting (`record_placement`): one
   ``parallel.dev{id}.placed_bytes`` gauge per device holding a shard
   of a mesh-resident array, read back by the flight recorder's memory
   watermarks when ``memory_stats()`` is unavailable (CPU fallback);
 - streamed sharded placement (`place_from_datastore`): external-memory
   datasets go from disk shards straight to their owning device through
   the PR-9 bounded prefetcher, so the host never materializes the full
   matrix — peak host residency is one device slice + the prefetch
   window, instead of the whole ``[F, N]`` array.

Collective-labeled spans (`collective_span`) give replication /
placement traffic a uniform ``mesh.collective.*`` prefix in the
telemetry timeline, mirroring how the in-jit collectives are labeled
with ``jax.named_scope`` (parallel.grow_sharded).
"""
from __future__ import annotations

import jax
import numpy as np

from ..resilience import FAULTS, Supervisor
from ..utils.log import LightGBMError
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["padded_feature_count", "padded_row_count",
           "record_placement", "collective_span", "emit_collective_round",
           "local_device_ids", "place_from_datastore", "stream_shard_plan"]


def padded_feature_count(num_feature: int, shards: int) -> int:
    return -(-num_feature // shards) * shards


def padded_row_count(num_data: int, shards: int) -> int:
    return -(-num_data // shards) * shards


def record_placement(placed, prefix: str = "parallel") -> None:
    """Per-device attribution of one mesh-resident array: a
    ``{prefix}.dev{id}.placed_bytes`` gauge per addressable device.

    Metadata only, on purpose: reading ``shard.data`` materializes a
    persistent aliasing ``jax.Array`` (cached on the parent), which
    permanently inflates ``jax.live_arrays()`` and breaks the memory
    ledger's reconciliation — so the per-device bytes are derived from
    the sharding instead (replicated = full nbytes per device, sharded
    = an even split, the same convention memledger uses)."""
    from ..telemetry import REGISTRY
    sharding = getattr(placed, "sharding", None)
    devs = sorted(getattr(sharding, "addressable_devices", None)
                  or placed.devices(), key=lambda d: int(d.id))
    if not devs:
        return
    if getattr(sharding, "is_fully_replicated", True):
        per = int(placed.nbytes)
    else:
        per = int(placed.nbytes) // len(devs)
    for d in devs:
        REGISTRY.gauge(f"{prefix}.dev{int(d.id)}.placed_bytes").set(per)


class _CollectiveTimer:
    """Context manager pairing the ``mesh.collective.<name>`` span with
    an ALWAYS-ON ``REGISTRY.timing`` observation.  Spans only record
    when a tracer sink is attached, but the skew view in
    ``telemetry/ops.py`` (`/debug/fleet`, `top`) needs collective
    wall-clock unconditionally — a straggling device must show up in a
    process that never configured a telemetry_sink."""

    __slots__ = ("_name", "_span", "_t0")

    def __init__(self, name: str, span_cm):
        self._name = name
        self._span = span_cm

    def __enter__(self):
        import time
        self._t0 = time.perf_counter()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        import time
        from ..telemetry import REGISTRY
        out = self._span.__exit__(*exc)
        REGISTRY.timing(self._name).observe(
            time.perf_counter() - self._t0)
        return out


def collective_span(name: str, **attrs):
    """Host-side span labeling mesh traffic: ``mesh.collective.<name>``.

    In-jit collectives are labeled via ``jax.named_scope`` instead (they
    trace into the compiled program); this wrapper is for the host-driven
    phases — placement, replication, gather — so both sides of the mesh
    runtime share one searchable prefix.  Every exit also observes the
    ``mesh.collective.<name>`` timing accumulator (see
    ``_CollectiveTimer``)."""
    from ..telemetry import span
    full = f"mesh.collective.{name}"
    return _CollectiveTimer(full, span(full, **attrs))


def local_device_ids(mesh: Mesh):
    """Global ids of THIS process's devices inside `mesh` — the devices
    whose collective participation this process can stamp (in a
    multi-controller SPMD program every process runs the same dispatch,
    so per-process local stamps tile the whole mesh)."""
    pidx = jax.process_index()
    return [int(d.id) for d in mesh.devices.flat
            if d.process_index == pidx]


def emit_collective_round(name: str, device_ids, payload_bytes: int,
                          round_idx: int, **attrs) -> None:
    """Stamp one ``mesh.collective.<name>`` point event per local device
    for one collective round: device id, payload bytes, round counter.

    Host-side only, at dispatch time — telemetry never enters jitted
    code (graft-lint R005) and nothing here blocks on the device (zero
    added syncs).  The spool aggregator groups these events by
    (collective, round) across processes and reads per-device skew from
    the timestamp spread (telemetry/spool.py `_collective_skew`); the
    straggler surfaces as ``mesh.skew.device``.  Callers gate on
    ``TRACER.active`` themselves so the inactive path stays one branch.
    """
    from ..telemetry import event
    for dev in device_ids:
        event(f"mesh.collective.{name}", device=int(dev),
              payload_bytes=int(payload_bytes), round=int(round_idx),
              **attrs)


def stream_shard_plan(store, mesh: Mesh = None):
    """Pinned shard read order for streamed training.

    Serial (``mesh=None``): the whole datastore in ascending shard
    order — ONE canonical order, because streamed f32 histogram
    accumulation is order-sensitive and byte-identity to the assembled
    matrix requires exactly the storage row order.

    With a mesh: one plan per device (row-major over the flat device
    list, same row mapping as ``place_from_datastore``), each covering
    only the rows that device owns — shards straddling a device
    boundary appear in both plans with a shard-relative row selection,
    so a data-parallel learner can re-stream per device without ever
    assembling its block.  Tail padding rows (beyond ``store.n_rows``)
    are absent from every plan; the caller pads state, not bins.
    """
    if mesh is None:
        return [(k, None) for k in range(store.n_shards)]
    S_total = 1
    for a in tuple(mesh.axis_names):
        S_total *= int(mesh.shape[a])
    rows_per = padded_row_count(store.n_rows, S_total) // S_total
    plans = []
    for d_i in range(S_total):
        lo, hi = d_i * rows_per, (d_i + 1) * rows_per
        plan = []
        for k in range(store.n_shards):
            row0 = store.row0_of(k)
            rk = store.rows_of(k)
            a, b = max(row0, lo), min(row0 + rk, hi)
            if b <= a:
                continue
            rel = None if (a == row0 and b == row0 + rk) \
                else np.arange(a - row0, b - row0)
            plan.append((k, rel))
        plans.append(plan)
    return plans


def place_from_datastore(store, mesh: Mesh, kind: str,
                         payload: str = "bins",
                         pad_features: bool = True,
                         prefetch_depth: int = 2,
                         collective_timeout_ms: float = 0.0,
                         run_stats=None):
    """Stream datastore shards straight into per-device row blocks.

    The sharded equivalent of ``datastore.assemble.assemble_feature_
    major`` + ``parallel.learner.place_training_data`` without the
    intermediate full matrix: shards arrive in row order through the
    bounded prefetcher, each is copied into the (zero-padded) host
    staging block of the device that owns those rows, and each completed
    block is committed to its device.  The final array is identical —
    shape, padding, NamedSharding — to the assemble-then-place route, so
    the unchanged grower produces byte-identical models.

    Rows shard over the whole mesh (1-D ``("data",)`` or 2-level
    ``("dcn", "ici")``); ``kind="feature"`` replicates rows and must use
    the assemble path instead.
    """
    from ..datastore.prefetch import ShardPrefetcher
    from .. import telemetry

    if kind == "feature":
        raise LightGBMError(
            "place_from_datastore shards rows; the feature-parallel "
            "learner replicates them (use the assemble path)")
    axes = tuple(mesh.axis_names)
    S_last = int(mesh.shape[axes[-1]])
    S_total = 1
    for a in axes:
        S_total *= int(mesh.shape[a])
    f = store.payload_cols(payload)
    if f <= 0:
        raise LightGBMError(
            f"datastore has no '{payload}' payload to place")
    n = store.n_rows
    dtype = np.uint16 if store.dtype == "uint16" else np.uint8
    f_pad = padded_feature_count(f, S_last) if pad_features else f
    n_pad = padded_row_count(n, S_total)
    rows_per = n_pad // S_total
    devs = list(mesh.devices.flat)

    hit = telemetry.REGISTRY.counter("datastore.prefetch.hit")
    stall = telemetry.REGISTRY.counter("datastore.prefetch.stall")

    def on_hit():
        hit.inc()
        if run_stats is not None:
            run_stats.hit()

    def on_stall():
        stall.inc()
        if run_stats is not None:
            run_stats.stall()

    if run_stats is not None:
        run_stats.start_pass()
    pf = ShardPrefetcher(store, payload=payload, depth=prefetch_depth,
                         on_hit=on_hit, on_stall=on_stall)
    it = iter(pf)
    cur = None  # carried (row0, block) straddling a device boundary
    bufs = []
    # one watchdog lane for the whole placement: a device_put that
    # wedges raises DeviceTimeoutError instead of hanging assembly
    sup = Supervisor("mesh.collective", collective_timeout_ms)
    with collective_span("place", kind=kind, rows=n, cols=f,
                         shards=S_total, payload=payload):
        try:
            for d_i, dev in enumerate(devs):
                lo, hi = d_i * rows_per, (d_i + 1) * rows_per
                host = np.zeros((f_pad, rows_per), dtype=dtype)
                filled = lo
                while filled < hi:
                    if cur is None:
                        try:
                            _, row0, block = next(it)
                        except StopIteration:
                            break       # tail padding rows stay zero
                        cur = (row0, np.asarray(block))
                    row0, block = cur
                    rk = int(block.shape[-1])
                    a, b = max(row0, filled), min(row0 + rk, hi)
                    if b <= a:
                        raise LightGBMError(
                            "datastore shards are not in ascending row "
                            f"order (shard rows [{row0}, {row0 + rk}) "
                            f"vs device fill cursor {filled})")
                    host[:f, a - lo:b - lo] = block[:, a - row0:b - row0]
                    filled = b
                    if row0 + rk <= hi:
                        cur = None      # fully consumed
                    else:
                        break           # remainder owned by next device
                with telemetry.span("mesh.place.device",
                                    device=int(dev.id), rows=rows_per):
                    # each staging block is committed then never mutated,
                    # so a zero-copy device_put alias is safe
                    def _put(host=host, dev=dev):
                        FAULTS.inject("mesh.collective")
                        return jax.device_put(host, dev)
                    # RESOURCE_EXHAUSTED here dumps the attributed
                    # snapshot ({"ev":"oom"}) before re-raising
                    with telemetry.MEMLEDGER.oom_guard("mesh.place"):
                        bufs.append(sup.call(_put))
        finally:
            pf.close()
            peak = pf.peak_resident_bytes
            if run_stats is not None:
                # run-max, not this placement's transient — repeated
                # placements in one run must not reset the watermark
                run_stats.absorb(pf)
                peak = run_stats.peak_resident_bytes
            telemetry.REGISTRY.gauge("datastore.peak_resident_mb").set(
                round(peak / (1024.0 * 1024.0), 3))
    placed = jax.make_array_from_single_device_arrays(
        (f_pad, n_pad), NamedSharding(mesh, P(None, axes)), bufs)
    # `assign`, not `register`: a re-placement replaces the previous
    # run's attribution for the same owner instead of double-counting
    telemetry.MEMLEDGER.assign("datastore.place", bufs)
    record_placement(placed)
    return placed
