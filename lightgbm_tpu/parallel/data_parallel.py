"""Data-parallel GBDT training step over a mesh.

TPU-native re-design of the reference's data-parallel tree learner
(ref: src/treelearner/data_parallel_tree_learner.cpp — row shards, histogram
`Network::ReduceScatter`, `SplitInfo` `Allreduce(max)`, shard-local split
application; SURVEY §3.4).

Mapping:
 - row shard            → `Mesh` axis "data", bins_fm [F, N] sharded on N
 - histogram reduce     → inside the grower (ops/grow.py,
                          `make_grower(spec, axis_name="data")`, its
                          `mode="data"`: the whole histogram on every
                          shard).  With `det_reduce` (the default) the
                          shards' histograms are added in ascending shard
                          order around a ring (`ppermute` hops, then an
                          `all_gather` of the total:
                          `ops/histogram.ring_ordered_sum` / `ring_fold`)
                          and the root sums reduce the gathered rows;
                          without it, `lax.psum`
 - SplitInfo allreduce  → every shard argmaxes the identical summed
                          histogram (replicated compute, no exchange)
 - split application    → shard-local `where` on the local leaf_id vector

The full training step (grad/hess → grow → score update) runs under ONE
`jax.shard_map`: a boosting iteration is a single SPMD program, one
histogram reduction a split.  `parallel/learner.py` is what
`tree_learner=data` runs through `Booster.update` (block search,
`data_rs`); this step is the multi-controller path (tests/mh_worker.py).
"""
from __future__ import annotations

import functools
import itertools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..mesh.placement import emit_collective_round, local_device_ids
from ..ops.grow import DeviceTree, GrowerSpec, make_grower
from ..ops.leaf_rows import leaf_rows
from .learner import hist_hop_bytes

Array = jax.Array


def shard_dataset(bins_nf: np.ndarray, label: np.ndarray, mesh: Mesh,
                  axis: str = "data",
                  weight: Optional[np.ndarray] = None):
    """Place the binned dataset on the mesh, rows sharded over `axis`.

    Rows are padded (with weight 0) to a multiple of the shard count —
    the fixed-shape analog of the reference's pre-partitioned per-rank files
    (ref: DatasetLoader distributed path, `pre_partition`).
    Returns (bins_fm [F, N'], label [N'], weight [N'], n_padded).
    """
    from ..telemetry import span
    n, f = bins_nf.shape
    shards = mesh.shape[axis]
    with span("parallel.shard_dataset", rows=n, cols=f, shards=int(shards)):
        n_pad = (-n) % shards
        if n_pad:
            bins_nf = np.concatenate(
                [bins_nf, np.zeros((n_pad, f), dtype=bins_nf.dtype)])
            label = np.concatenate([label, np.zeros(n_pad, label.dtype)])
        w = weight if weight is not None else np.ones(n, np.float32)
        if n_pad:
            w = np.concatenate([w.astype(np.float32),
                                np.zeros(n_pad, np.float32)])
        bins_fm = np.ascontiguousarray(bins_nf.T)
        dev_bins = jax.device_put(bins_fm, NamedSharding(mesh, P(None, axis)))
        dev_label = jax.device_put(label.astype(np.float32),
                                   NamedSharding(mesh, P(axis)))
        dev_w = jax.device_put(w.astype(np.float32),
                               NamedSharding(mesh, P(axis)))
        return dev_bins, dev_label, dev_w, n_pad


@functools.lru_cache(maxsize=32)
def make_sharded_train_step(spec: GrowerSpec, mesh: Mesh,
                            grad_fn: Callable, learning_rate: float,
                            axis: str = "data", det_reduce: bool = True,
                            num_data: int = 0):
    """One full boosting iteration as a single SPMD program.

    Memoized on (spec, mesh, grad_fn, lr, axis): the factory returns a
    fresh `jax.jit` wrapper, so an uncached call site would silently
    retrace/recompile the whole SPMD step every invocation
    (graft-lint R002).

    grad_fn(score, label) -> (grad, hess), elementwise and UNWEIGHTED —
    the grower applies `weight` exactly once (payload = [g·w, h·w, w]),
    so row weights (incl. the 0-weight padding rows from `shard_dataset`)
    enter the histogram a single time, matching the reference's
    weighted-gradient semantics (ref: objective_function.h GetGradients
    weighted variants).
    Returns step(score, label, weight, bins_fm, feat, allowed)
    -> (new_score, DeviceTree) with the tree arrays replicated across
    shards and score/leaf_id sharded.

    `det_reduce` (default ON, ROADMAP 1a) pins the histogram/root-stat
    accumulation order to the serial grower's, so round-2+ models are
    byte-identical to serial; it needs the REAL row count (`num_data`,
    pre-padding) to keep pad rows out of the pinned order — without it
    the grower keeps the legacy tree-psum reduction.
    """
    grow = make_grower(spec, axis_name=axis,
                       n_shards=int(mesh.shape[axis]),
                       det_reduce=det_reduce, num_data=num_data)
    lr = learning_rate

    def step(score, label, weight, bins_fm, feat, allowed):
        # named scopes only — this body is inside shard_map/jit, so the
        # labels reach the XProf device timeline at zero runtime cost
        with jax.named_scope("grad_hess"):
            grad, hess = grad_fn(score, label)
        with jax.named_scope("grow_tree"):
            dev = grow(bins_fm, grad.astype(jnp.float32),
                       hess.astype(jnp.float32), weight, feat, allowed)
        with jax.named_scope("update_scores"):
            new_score = score + leaf_rows(
                dev.leaf_value, dev.leaf_id, spec.hist_impl,
                spec.hist_interpret) * lr
        return new_score, dev

    tree_specs = DeviceTree(
        n_splits=P(), split_leaf=P(), split_feature=P(), threshold_bin=P(),
        default_left=P(), split_is_cat=P(), split_cat_mask=P(),
        split_gain=P(), internal_g=P(), internal_h=P(),
        internal_cnt=P(), leaf_value=P(), leaf_g=P(), leaf_h=P(),
        leaf_cnt=P(), leaf_id=P(axis))

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(None, axis),
                  P(None), P(None)),
        out_specs=(P(axis), tree_specs),
        check_vma=False)
    jitted = jax.jit(sharded)
    # per-device collective timeline (ISSUE 16): one point event per
    # LOCAL device per training round, stamped host-side at dispatch —
    # this is the path a multi-controller gloo cluster runs
    # (tests/mh_worker.py), so the spool aggregator sees every rank's
    # devices and can name the straggler.  Host-computed payload: what
    # one hop of one histogram reduction carries (`hist_hop_bytes`).
    # R005: no telemetry inside the shard_map body; zero added syncs.
    coll_name = "ring_fold" if det_reduce else "hist_psum"
    rounds = itertools.count()

    def dispatched(score, label, weight, bins_fm, feat, allowed):
        from ..telemetry import TRACER
        if not TRACER.active:
            return jitted(score, label, weight, bins_fm, feat, allowed)
        # .shape is metadata — no transfer, no sync
        payload_bytes = hist_hop_bytes(spec, int(bins_fm.shape[0]), 1,
                                       det_reduce and num_data > 0)
        emit_collective_round(coll_name, local_device_ids(mesh),
                              payload_bytes, next(rounds),
                              shards=int(mesh.shape[axis]))
        return jitted(score, label, weight, bins_fm, feat, allowed)

    return dispatched
