"""TreeLearner factory — public-API distributed training dispatch.

TPU re-design of the reference's learner factory
(ref: src/treelearner/tree_learner.cpp `TreeLearner::CreateTreeLearner`,
cross product of {serial, feature, data, voting} x {cpu, gpu, cuda}): given
`tree_learner=data|feature|voting` (and >1 visible device), Booster training
routes through a `jax.shard_map`ped grower over a 1-D device mesh instead of
the serial single-chip grower.  The factory returns a grower with the SAME
signature as the serial one, so the boosting loop (booster.py `__boost`)
is oblivious to the device topology — the reference achieves the same with
virtual dispatch, we do it with jit + sharding.

Strategy mapping (SURVEY §2.7):
 - data    → rows sharded; every pass's histograms summed across shards
             (`deterministic_reduce`, the default: chained limb-wise in
             ascending shard order around a ring and gathered back, each
             shard keeping its column block; else `psum_scatter` over the
             feature axis), per-shard split finding on its block,
             SplitInfo allreduce-max
             (ref: data_parallel_tree_learner.cpp).
 - feature → bins replicated, per-shard feature-block search, SplitInfo
             allreduce-max, shard-local split apply
             (ref: feature_parallel_tree_learner.cpp).
 - voting  → real PV-Tree (ref: voting_parallel_tree_learner.cpp): each
             shard proposes its local top-k features, a deterministic
             global election picks ~2·top_k, and only the elected
             features' histograms are psum-reduced (`mode="voting"` in
             ops/grow.py; election subset asserted in
             tests/test_distributed.py).

Row counts need not divide the shard count: rows are padded with
weight-0 entries inside the jitted wrapper (the fixed-shape analog of the
reference's `pre_partition`ed per-rank files), features are padded with
never-allowed columns to a multiple of the shard count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import itertools

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..mesh.placement import emit_collective_round, local_device_ids, \
    padded_feature_count, padded_row_count, record_placement
from ..ops.grow import DeviceTree, GrowerSpec, make_grower
from ..ops import leaf_rows as leaf_rows_op
from ..utils import log

TREE_LEARNER_ALIASES = {
    "serial": "serial",
    "feature": "feature", "feature_parallel": "feature",
    "data": "data", "data_parallel": "data",
    "voting": "voting", "voting_parallel": "voting",
}


def resolve_tree_learner(name: str, bundled: bool = False,
                         two_level: bool = False,
                         quiet: bool = False) -> str:
    """Canonicalize the tree_learner param (ref: config.cpp
    `Config::GetTreeLearnerType`).  Downgrades happen HERE — before data
    placement — so placement and grower padding always agree on the
    strategy: feature-parallel falls back to data-parallel under EFB
    (bundle columns don't align with feature blocks) and on 2-level
    meshes (feature blocks ride a single ICI axis).  `quiet` suppresses
    the downgrade warnings (cache-hit re-resolution)."""
    kind = TREE_LEARNER_ALIASES.get(str(name).lower())
    if kind is None:
        raise ValueError(f"Unknown tree learner type {name}")
    if bundled and kind == "feature":
        if not quiet:
            log.warning("tree_learner=feature with EFB bundling falls "
                        "back to the data-parallel strategy")
        kind = "data"
    if two_level and kind == "feature":
        if not quiet:
            log.warning("tree_learner=feature over a 2-level mesh falls "
                        "back to the data-parallel strategy")
        kind = "data"
    return kind


@functools.lru_cache(maxsize=32)
def make_distributed_grower(spec: GrowerSpec, mesh: Mesh, kind: str,
                            num_feature: int, num_data: int,
                            wave: bool = False, det_reduce: bool = True):
    """Grower with the serial signature, running SPMD over `mesh`.

    Expects `bins_fm` already padded + placed by `place_training_data`
    ([f_pad, n_pad] — the one-time cost); pads the per-iteration [N]
    vectors itself.  Returns `grow(bins_fm, grad [N], hess [N], sw [N],
    feat, allowed) -> DeviceTree` with `leaf_id` of length N.

    Memoized (lru_cache): the factory ends in a fresh `jax.jit`, so
    every uncached call would recompile the whole sharded grower
    (graft-lint R002); the booster's learner-rebuild path hits the
    cache for a repeated (spec, mesh, kind, shape) tuple.

    `wave=True` plugs in the wave-batched grower (ops/grow_wave.py) —
    data-parallel only (rows sharded; the booster downgrades other kinds
    before reaching here).  Like the strict grower, the wave runs the
    `data_rs` mode (each shard searches its block of the columns,
    per-wave SplitInfo allreduce-max) except under EFB, where bundle
    columns force the full histogram on every shard.  How a pass's
    histograms are summed across shards is `det_reduce`'s: true (the
    booster's default, one mesh axis) chains every shard's histogram in
    ascending shard order (`ring_ordered_sum` / `ring_fold`: ppermute
    hops, then an all_gather of the total) and each shard slices its
    block; false runs `psum_scatter` (`psum` under EFB).

    The returned function carries `reduce_bytes`: the bytes one shard
    hands to the collectives of ONE histogram reduction
    (`hist_reduce_bytes`), for the booster's `grow.reduce_bytes`;
    `jitted`, the program itself (`jit_grow`); and `leaf_rows(table,
    leaf_id)`, the score update's look-up (`ops/leaf_rows.py`) over the
    grower's own `leaf_id`: each shard looks its rows up in the
    replicated table, no collective.
    """
    axes = tuple(mesh.axis_names)     # ("data",) or ("dcn", "ici")
    S_last = int(mesh.shape[axes[-1]])
    S_total = 1
    for a in axes:
        S_total *= int(mesh.shape[a])
    mode = {"data": "data_rs", "voting": "voting", "feature": "feature"}[kind]
    assert not (kind == "feature" and len(axes) > 1), \
        "feature kind must be downgraded before placement (2-level mesh)"
    if spec.bundled:
        # bundle columns don't align with per-feature blocks — use the
        # full-histogram psum strategy (still row-sharded).  feature kind
        # was already downgraded by resolve_tree_learner, so placement and
        # padding agree.
        assert kind != "feature", \
            "feature kind must be downgraded before placement (EFB)"
        mode = "data"
    if wave:
        assert kind == "data", \
            "wave policy must be downgraded for non-data learners"
    # feature blocks split over the LAST (ICI) axis only; rows shard over
    # the whole mesh
    f_extra = (padded_feature_count(num_feature, S_last) - num_feature) \
        if mode in ("data_rs", "feature") else 0
    n_extra = (padded_row_count(num_data, S_total) - num_data) \
        if mode != "feature" else 0
    # block modes split features over the last (ICI) axis; voting's local
    # vote scales size constraints by the TOTAL shard count
    if wave:
        from ..ops.grow_wave import make_wave_grower
        grow = make_wave_grower(spec,
                                axis_name=axes if len(axes) > 1
                                else axes[0],
                                mode=mode, n_shards=S_last,
                                det_reduce=det_reduce, num_data=num_data)
    else:
        grow = make_grower(spec,
                           axis_name=axes if len(axes) > 1 else axes[0],
                           mode=mode,
                           n_shards=S_total if mode == "voting" else S_last,
                           det_reduce=det_reduce, num_data=num_data)

    row_sp = P(axes) if mode != "feature" else P(None)
    tree_specs = DeviceTree(
        n_splits=P(), split_leaf=P(), split_feature=P(), threshold_bin=P(),
        default_left=P(), split_is_cat=P(), split_cat_mask=P(),
        split_gain=P(), internal_g=P(), internal_h=P(), internal_cnt=P(),
        leaf_value=P(), leaf_g=P(), leaf_h=P(), leaf_cnt=P(),
        leaf_id=row_sp, tail_stats=P() if wave else None,
        hist_calls=P() if wave and spec.hist_impl == "pallas" else None)
    in_specs = (P(None, axes) if mode != "feature" else P(None, None),
                row_sp, row_sp, row_sp, P(None), P(None))
    sharded = shard_map(grow, mesh=mesh, in_specs=in_specs,
                        out_specs=tree_specs, check_vma=False)

    # named `grow` like the serial growers' jitted function: one grower
    # is one program name, `jit_grow`, on one chip and on four
    def grow(bins_fm, grad, hess, sw, feat, allowed):
        # named scopes label the XProf timeline: padding vs the SPMD body
        # (whose collectives — psum_scatter / allreduce-max — show up
        # under parallel.grow_sharded); zero runtime cost, compile-time
        # metadata only
        with jax.named_scope("parallel.pad_inputs"):
            if f_extra:
                # pad the per-feature [F] arrays; ic_groups is [K, F]
                # (axis 1), ff_key (RNG key) and qscales (quantization
                # scales) have no feature axis
                feat = {k: (v if k in ("ff_key", "qscales")
                            else jnp.pad(v, ((0, 0), (0, f_extra)))
                            if k == "ic_groups"
                            else jnp.pad(v, (0, f_extra)))
                        for k, v in feat.items()}
                allowed = jnp.pad(allowed, (0, f_extra))  # never split
            if n_extra:
                grad = jnp.pad(grad, (0, n_extra))
                hess = jnp.pad(hess, (0, n_extra))
                sw = jnp.pad(sw, (0, n_extra))  # weight 0 → inert rows
        with jax.named_scope("parallel.grow_sharded"):
            dev = sharded(bins_fm, grad, hess, sw, feat, allowed)
        if n_extra:
            dev = dev._replace(leaf_id=dev.leaf_id[:num_data])
        return dev

    jitted = jax.jit(grow)
    # per-device collective timeline (ISSUE 16): stamp one
    # mesh.collective.<name> point event per local device per dispatch
    # round, host-side around the jitted call (graft-lint R005 keeps
    # telemetry out of the SPMD body; the `hist_reduce` named_scope
    # inside ops/grow*.py labels the device trace instead).  Payload =
    # what one hop of one histogram reduction carries (`hist_hop_bytes`).
    # Zero added device syncs: events ride the async dispatch.
    coll_name = "ring_fold" if det_reduce else "hist_psum"
    slots = 1
    if wave:
        from ..ops.grow_wave import wave_sizes
        slots = wave_sizes(spec)[1]
    det = bool(det_reduce) and len(axes) == 1   # as the growers decide
    payload_bytes = hist_hop_bytes(spec, num_feature + f_extra, slots, det)
    rounds = itertools.count()

    def dispatched(*args):
        from ..telemetry import TRACER
        if not TRACER.active:
            return jitted(*args)
        emit_collective_round(coll_name, local_device_ids(mesh),
                              payload_bytes, next(rounds),
                              mode=mode, shards=S_total)
        return jitted(*args)

    rows_lookup = shard_map(
        functools.partial(leaf_rows_op.leaf_rows, hist_impl=spec.hist_impl,
                          interpret=spec.hist_interpret),
        mesh=mesh, in_specs=(P(), row_sp), out_specs=row_sp,
        check_vma=False)

    # named like the serial look-up: one program name, `jit_leaf_rows`
    def leaf_rows(table, leaf_id):
        if not n_extra:
            return rows_lookup(table, leaf_id)
        # pad rows carry -1 like the grower's; their values are dropped
        return rows_lookup(table, jnp.pad(
            leaf_id, (0, n_extra), constant_values=-1))[:num_data]

    dispatched.leaf_rows = jax.jit(leaf_rows)
    dispatched.jitted = jitted      # ahead-of-time compiles lower this
    dispatched.reduce_bytes = hist_reduce_bytes(
        spec, num_feature + f_extra, slots, det, S_last)
    return dispatched


def hist_hop_bytes(spec: GrowerSpec, columns: int, slots: int,
                   det: bool) -> int:
    """Bytes of what ONE shard hands to ONE collective of a histogram
    reduction over `columns` (padded) columns and `slots` leaf slots:
    the f32 families' two-limb sums [slots, columns, bins, 6] (three
    channels from the quantized ones), or under `det` on the XLA
    families the streamed carry that is chained in their place
    (`ops/histogram.hist_stream_*`)."""
    from ..ops import histogram as H
    hb = spec.bundle_max_bin if spec.bundled else spec.max_bin
    quantized = spec.hist_impl in ("pallas_q", "packed")
    if det and spec.hist_impl not in ("pallas", "pallas_q"):
        init = functools.partial(
            H.hist_stream_packed_init, columns, slots, hb,
            spec.packed_const_hess_level) if quantized else \
            functools.partial(H.hist_stream_init, columns, slots, hb)
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(jax.eval_shape(init)))
    return slots * columns * hb * (3 if quantized else 6) * 4


def hist_reduce_bytes(spec: GrowerSpec, columns: int, slots: int, det: bool,
                      n_shards: int) -> int:
    """Bytes one shard hands to the collectives of ONE histogram
    reduction: the ordered chain permutes its carry `n_shards - 1` times
    and gathers it once; `psum_scatter` / `psum` take one operand."""
    return hist_hop_bytes(spec, columns, slots, det) * \
        (n_shards if det else 1)


def place_training_data(bins_fm, mesh: Mesh, kind: str,
                        pad_features: bool = True):
    """Pad the bin matrix to mesh-divisible shape and place it: rows
    sharded for data/voting, replicated for feature (ref: the reference's
    per-rank pre-partitioned files / full per-rank copies).  One-time cost;
    the per-iteration jit then never re-transfers the big array.
    `pad_features` only for the block strategies (data_rs/feature) —
    voting and bundled-data keep the original column count."""
    import numpy as np
    from ..telemetry import TRACER, span
    axes = tuple(mesh.axis_names)
    S_last = int(mesh.shape[axes[-1]])
    S_total = 1
    for a in axes:
        S_total *= int(mesh.shape[a])
    f, n = bins_fm.shape
    with span("parallel.place_data", kind=kind, rows=n, cols=f,
              shards=S_total):
        f_pad = padded_feature_count(f, S_last) if pad_features else f
        n_pad = padded_row_count(n, S_total) if kind != "feature" else n
        if (f_pad, n_pad) != (f, n):
            out = np.zeros((f_pad, n_pad), dtype=np.asarray(bins_fm).dtype)
            out[:f, :n] = np.asarray(bins_fm)
            bins_fm = out
        sp = P(None, axes) if kind != "feature" else P(None, None)
        placed = jax.device_put(bins_fm, NamedSharding(mesh, sp))
        if TRACER.active:
            placed.block_until_ready()  # span measures the real transfer
            record_placement(placed)
        return placed
