"""Leaf-wise tree growth as ONE jitted XLA program.

TPU-native re-design of the reference's tree learner orchestration
(ref: src/treelearner/serial_tree_learner.cpp `SerialTreeLearner::Train` /
`FindBestSplits` / `Split`; src/treelearner/cuda/
cuda_single_gpu_tree_learner.cpp `CUDASingleGPUTreeLearner::Train`).

Key TPU-first departures from the reference:
 - Rows are never reordered.  Instead of `DataPartition`'s index-range
   shuffle (src/treelearner/data_partition.hpp `DataPartition::Split`), a
   dense per-row ``leaf_id`` vector is updated with a `where` — embarrassingly
   parallel, static shapes, no compaction (the CUDA learner's bit-vector
   partition is halfway to this design).
 - All per-leaf state lives in fixed `[num_leaves]` slots; the best-first
   growth loop is a `lax.while_loop` with early exit when no positive-gain
   split remains, so the whole tree compiles into a single XLA program with
   zero host sync.
 - The histogram subtraction trick is preserved: the smaller child is
   histogrammed, the larger is parent − smaller
   (ref: serial_tree_learner.cpp smaller_leaf/larger_leaf logic).
 - Monotone constraints ride along as fixed per-leaf [lb, ub] output bounds
   (ref: monotone_constraints.hpp `BasicLeafConstraints` — the "basic"
   method: split candidates violating the direction are masked, child
   outputs clamped at the parents' midpoint).

Per-feature metadata travels as one dict pytree `feat`:
  nb [F] i32 bins per feature; missing [F] i32 missing type;
  default [F] i32 zero bin; is_cat [F] bool; mono [F] i32 in {-1, 0, +1}.

The grower is specialized per `GrowerSpec` (static shapes + hyperparams) and
cached, so repeated boosting iterations reuse one compiled executable.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..analysis.contracts import contract
from .histogram import (hist_stream_finalize, hist_stream_init,
                        hist_stream_update, hist_sub, hist_value,
                        leaf_histogram_limbs, ring_fold, ring_ordered_sum)
from .split import NEG_INF, SplitResult, bin_goes_left, find_best_split, \
    leaf_output, refine_child_sums, smooth_output

Array = jax.Array

INF = jnp.inf


class GrowerSpec(NamedTuple):
    """Static configuration of one compiled grower."""
    num_leaves: int
    max_depth: int        # <=0 means unlimited
    max_bin: int          # padded bin-axis size MB
    lambda_l1: float
    lambda_l2: float
    min_data_in_leaf: float
    min_sum_hessian_in_leaf: float
    min_gain_to_split: float
    max_delta_step: float
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    # categorical group gate (ops/split.py; upstream's default)
    min_data_per_group: float = 100.0
    hist_impl: str = "segment_sum"  # or "pallas" (ops/pallas_hist.py)
    # EFB (ref: dataset.cpp FindGroups / feature_group.h): bins_fm holds
    # BUNDLE columns [G, N]; histograms are built per bundle and expanded
    # to the per-feature [F, MB] grid at split time (utils/efb.py)
    bundled: bool = False
    bundle_max_bin: int = 0
    # bounded per-leaf histogram cache (ref: feature_histogram.hpp
    # `HistogramPool` LRU, sized by histogram_pool_size MB); 0 = one slot
    # per leaf (no eviction, no recompute — the fastest mode when it fits)
    hist_pool_slots: int = 0
    # path smoothing strength (ref: feature_histogram.hpp USE_SMOOTHING)
    path_smooth: float = 0.0
    # per-node column sampling (ref: col_sampler.hpp `GetByNode`); the RNG
    # key rides in feat["ff_key"]
    feature_fraction_bynode: float = 1.0
    # interaction constraints (ref: col_sampler.hpp interaction filtering):
    # number of groups; the [K, F] group masks ride in feat["ic_groups"]
    n_ic_groups: int = 0
    # forced splits (ref: serial_tree_learner.cpp `ForceSplits`): BFS-order
    # tuple of (leaf_slot, feature, threshold_bin) applied before best-gain
    # growth
    forced_splits: tuple = ()
    # REAL feature count when the feat arrays are padded for distributed
    # block modes (0 = no padding); keeps bynode sampling exact
    num_features_hint: int = 0
    # CEGB (ref: cost_effective_gradient_boosting.hpp): gain penalties
    # per candidate; per-feature penalty vectors ride in
    # feat["cegb_coupled"] / feat["cegb_lazy"] / feat["cegb_used"]
    cegb_tradeoff: float = 0.0   # 0 = CEGB off
    cegb_penalty_split: float = 0.0
    cegb_coupled: bool = False
    cegb_lazy: bool = False
    # extremely randomized trees (ref: config.h extra_trees → the split
    # search evaluates ONE random threshold per feature per node); shares
    # the feat["ff_key"] per-tree RNG stream
    extra_trees: bool = False
    # voting-parallel (PV-Tree) local top-k (ref: config.h top_k /
    # voting_parallel_tree_learner.cpp)
    voting_top_k: int = 20
    # packed quantized histogram with constant unit hessian: counts
    # derive from the hess field (ONE scatter sweep); 0 = off
    packed_const_hess_level: int = 0
    # wave growth policy (ops/grow_wave.py): max smaller-child histograms
    # per batched kernel pass; 0 = strict policy (field inert here, rides
    # the spec so the two growers share one cache key space)
    wave_width: int = 0
    # wave depth bias: a ready leaf only splits while its gain >= ratio x
    # the wave's best gain x tree-fullness (leaves-used / num_leaves) —
    # capacity-aware, so early waves run at full width and the late,
    # capacity-scarce waves become selective; weaker leaves wait (and may
    # never split if capacity runs out — how the wave policy keeps the
    # strict policy's deep-where-it-matters allocation).  0 = off
    wave_gain_ratio: float = 0.0
    # wave grow-then-prune (classic CART wisdom applied to the batched
    # order): grow to ceil(overgrow x num_leaves) leaves wave-style, then
    # prune the lowest-gain leaf-parent splits back to num_leaves — the
    # final tree recovers (and in measurements beats) the strict policy's
    # capacity allocation at wave throughput.  <= 1 = off
    wave_overgrow: float = 0.0
    # hybrid wave/strict schedule: once remaining leaf capacity drops to
    # this many splits, waves collapse to width 1 — which IS strict
    # best-first order (one batched pass per split, children re-searched
    # before the next pick).  Early growth gets MXU-batched waves while
    # capacity is plentiful (splitting weak leaves costs nothing yet);
    # the capacity-scarce endgame — where the wave policy's AUC tax
    # lives (PROFILE.md r3c: wave DEPTH binds) — gets exact strict
    # allocation.  0 = off
    wave_strict_tail: int = 0
    # False = every feature is numerical (static): the split finder skips
    # the categorical cases — four [F, MB] argsorts per call
    has_cat: bool = True
    # debug mode (tpu_debug_nans): enable host-callback precondition
    # checks inside the traced step — currently the quantized lattice's
    # w ∈ {0, 1} invariant (pallas_hist.quantized_lattice_rows).  Part
    # of the spec so flipping it re-traces instead of reusing a cached
    # check-free program
    debug_checks: bool = False
    # monotone_constraints_method=intermediate (ref:
    # monotone_constraints.hpp `IntermediateLeafConstraints`): per-leaf
    # bounds are recomputed every split from the CURRENT outputs of the
    # opposite subtrees of each monotone ancestor (instead of the basic
    # method's one-shot parent midpoint), and leaves whose bounds moved
    # get their cached best split re-searched — the analog of the
    # reference's `leaves_to_update` re-search.  Serial, un-pooled
    # growers only (booster downgrades otherwise).
    monotone_intermediate: bool = False
    # run the Pallas kernels in interpret mode (CPU parity tests: the
    # pallas/pallas_q families become runnable — and byte-comparable —
    # off-TPU); never set on real backends
    hist_interpret: bool = False
    # static lane plan of the f32 Pallas histogram kernel
    # (ops/pallas_hist.py `lane_plan`): which few-bin columns share one
    # 128-lane multi-hot group.  Derived by the booster from the bin
    # counts of the columns the kernel will see; None = every column on
    # its own max_bin lanes.  Same sums bit for bit either way
    hist_lane_plan: Optional[tuple] = None


class DeviceTree(NamedTuple):
    """Flat-array tree as produced on device (host `Tree` is built from it).

    Internal-node arrays are sized [L-1], leaf arrays [L]; `n_splits` gives
    the populated prefix.  Node i's children are encoded by `split_leaf`:
    left child reuses leaf slot `split_leaf[i]`, right child is leaf slot
    `i + 1` (ref: include/LightGBM/tree.h `Tree::Split` — the split leaf
    keeps its index, the new leaf gets index num_leaves).
    """
    n_splits: Array       # i32 scalar
    split_leaf: Array     # [L-1] i32 — which leaf was split at step i
    split_feature: Array  # [L-1] i32
    threshold_bin: Array  # [L-1] i32
    default_left: Array   # [L-1] bool
    split_is_cat: Array   # [L-1] bool
    split_cat_mask: Array  # [L-1, MB] bool — left-subset bins of cat splits
    split_gain: Array     # [L-1] f32
    internal_g: Array     # [L-1] f32 — node Σgrad (left+right)
    internal_h: Array     # [L-1] f32
    internal_cnt: Array   # [L-1] f32
    leaf_value: Array     # [L] f32 — raw outputs (no shrinkage)
    leaf_g: Array         # [L] f32
    leaf_h: Array         # [L] f32
    leaf_cnt: Array       # [L] f32
    leaf_id: Array        # [N] i32 — final row→leaf assignment (train rows)
    # [7] i32 ([8] with categorical columns), wave grower only (None
    # elsewhere) — its strict tail's histogram passes, splits served from
    # a speculated histogram, speculated histograms never used / made, the
    # histogram passes of the waves before the tail, and the routing
    # passes over the rows with the picks and slots they routed and, with
    # categorical columns, how many of those were categorical
    # (ops/grow_wave.py)
    tail_stats: Array = None
    # [len(pallas_hist.hist_bodies()) + 1] i32, wave grower on the f32
    # Pallas kernel only (None elsewhere) — the kernel's calls by the
    # body that ran (full, then each compacting capacity; summed over the
    # shards) and, last, the 128-row groups those calls contracted
    hist_calls: Array = None


def _split_to_arrays(s: SplitResult):
    return (s.gain, s.feature, s.threshold_bin, s.default_left,
            s.left_sum_g, s.left_sum_h, s.left_cnt,
            s.right_sum_g, s.right_sum_h, s.right_cnt,
            s.is_cat, s.cat_mask)


def _merge_split_across_shards(s: SplitResult, axis_name: str,
                               n_shards: int) -> SplitResult:
    """SplitInfo Allreduce(max) over the mesh (ref: network.cpp
    `Network::Allreduce` with `SplitInfo::MaxReducer`).

    Each shard proposes the best split of ITS feature block; the winner is
    the max gain with a deterministic tie-break on lowest shard index (the
    blocks are disjoint, so ties are between distinct features — the
    reference breaks these on smaller feature index, which lowest-shard +
    first-wins-within-shard reproduces for block feature order).  The
    winner's whole payload is broadcast with a masked psum — O(MB) bytes,
    the TPU analog of allreducing the packed SplitInfo struct."""
    me = jax.lax.axis_index(axis_name)
    best_gain = jax.lax.pmax(s.gain, axis_name)
    cand = jnp.where(s.gain >= best_gain, me, n_shards)
    winner = jax.lax.pmin(cand, axis_name)
    sel = me == winner

    def pick(x):
        masked = jnp.where(sel, x, jnp.zeros_like(x))
        if masked.dtype == jnp.bool_:
            return jax.lax.psum(masked.astype(jnp.int32), axis_name) > 0
        return jax.lax.psum(masked, axis_name)

    return jax.tree_util.tree_map(pick, s)


# --------------------------------------------------------------------------
# helpers shared by the strict (below) and wave (ops/grow_wave.py) growers —
# one definition so the two policies can never drift on partition decode,
# per-node sampling, EFB expansion, monotone-basic child bounds, or the
# block-sharded (data_rs/feature) search machinery
# --------------------------------------------------------------------------

def make_feature_blocks(feat: Dict[str, Array], mono: Array, F: int,
                        axis_last: str, n_shards: int, mode: str):
    """This shard's feature block for distributed split finding:
    `(Fb, offset, bslice, bfeat, bmono)` with the [F] per-feature
    metadata sliced to this shard's `[offset, offset + Fb)` window.
    Raises (not asserts — direct callers must hit it under `python -O`
    too) on a non-divisible F, with the 'pad features first' message
    instead of an opaque downstream psum_scatter shape error."""
    if F % n_shards != 0:
        raise ValueError(
            f"{mode} learner requires features ({F}) divisible by "
            f"shards ({n_shards}); pad features first")
    Fb = F // n_shards
    offset = jax.lax.axis_index(axis_last) * Fb

    def bslice(x):
        return jax.lax.dynamic_slice_in_dim(x, offset, Fb, axis=0)

    bfeat = {k: bslice(feat[k])
             for k in ("nb", "missing", "default", "is_cat")}
    return Fb, offset, bslice, bfeat, bslice(mono)


def rebase_and_merge_block_split(s: SplitResult, offset, axis_last: str,
                                 n_shards: int) -> SplitResult:
    """Rebase a block-local SplitResult's feature index to the global
    feature space, then SplitInfo allreduce-max across shards."""
    s = s._replace(feature=jnp.where(s.feature >= 0, s.feature + offset,
                                     s.feature))
    return _merge_split_across_shards(s, axis_last, n_shards)

def make_bundled_expander(spec: GrowerSpec, feat: Dict[str, Array]):
    """(expand_bundled, decode_bins) for EFB bundle matrices.

    expand_bundled: [G, HB, 3] bundle histogram → per-feature [F, MB, 3]
    view — member bins are a gather; the default bin 0 is parent −
    Σ(nonzero bins), the sparse-bin identity the reference exploits the
    same way (dense_bin vs sparse_bin zero handling).
    decode_bins: the split feature's original bin column from its bundle
    column (off..off+nb-2 ↔ original bins 1..nb-1, else 0)."""
    MB = spec.max_bin
    HB = spec.bundle_max_bin
    bcol = feat["bundle_col"]
    boff = feat["bundle_off"]
    bident = feat["bundle_identity"]
    b_ar_mb = jnp.arange(MB, dtype=jnp.int32)
    src_bins = boff[:, None] + b_ar_mb[None, :] - 1            # [F, MB]
    valid_b = (b_ar_mb[None, :] >= 1) \
        & (b_ar_mb[None, :] < feat["nb"][:, None])

    def expand_bundled(histg, pg, ph, pc):
        gath = histg[bcol[:, None],
                     jnp.clip(src_bins, 0, HB - 1)]            # [F, MB, 3]
        hist = jnp.where(valid_b[..., None], gath, 0.0)
        rest = hist.sum(axis=1)                                # [F, 3]
        parent = jnp.stack([pg, ph, pc]).astype(jnp.float32)
        zero_row = jnp.where(bident[:, None],
                             histg[bcol, 0, :],
                             parent[None, :] - rest)
        return hist.at[:, 0, :].set(zero_row)

    def decode_bins(bins_fm, f):
        col = bcol[f]
        off = boff[f]
        raw_col = jnp.take(bins_fm, col, axis=0).astype(jnp.int32)
        in_range = (raw_col >= off) & \
            (raw_col < off + feat["nb"][f] - 1)
        return jnp.where(in_range, raw_col - off + 1, 0)

    return expand_bundled, decode_bins


def make_node_samplers(spec: GrowerSpec, feat: Dict[str, Array], F: int):
    """(bynode_mask, extra_mask) — per-node column sampling (ref:
    col_sampler.hpp `GetByNode`) and extra_trees random-threshold masks.
    Node index derives the RNG key, so both growers draw IDENTICAL
    per-node samples for the same tree."""
    MB = spec.max_bin
    if spec.feature_fraction_bynode < 1.0:
        f_real = spec.num_features_hint or F
        n_pick = max(1, int(spec.feature_fraction_bynode * f_real + 1e-9))

        def bynode_mask(node_idx):
            key = jax.random.fold_in(feat["ff_key"], node_idx)
            perm = jax.random.permutation(key, f_real)
            return jnp.zeros((F,), bool).at[perm[:n_pick]].set(True)
    else:
        def bynode_mask(node_idx):
            return jnp.ones((F,), bool)

    if spec.extra_trees:
        def extra_mask(node_idx):
            """One random numerical threshold per feature per node (ref:
            extra_trees); categorical features keep their candidates."""
            key = jax.random.fold_in(feat["ff_key"], (1 << 24) + node_idx)
            r = jax.random.uniform(key, (F,))
            t_max = jnp.maximum(feat["nb"] - 2, 0)
            pick = (r * (t_max + 1).astype(jnp.float32)).astype(jnp.int32)
            m = jnp.zeros((F, MB), bool)\
                .at[jnp.arange(F), jnp.clip(pick, 0, MB - 1)].set(True)
            return m | feat["is_cat"][:, None]
    else:
        def extra_mask(node_idx):
            return None

    return bynode_mask, extra_mask


def split_go_left(spec: GrowerSpec, feat: Dict[str, Array], bins_fm: Array,
                  decode_bins, f, t, dl, node_cat, node_mask) -> Array:
    """[N] left/right routing of one applied split: the bundled decode,
    then `split.bin_goes_left` on the rows' bins of the split column."""
    if spec.bundled:
        fbins = decode_bins(bins_fm, f)
    else:
        fbins = jnp.take(bins_fm, f, axis=0).astype(jnp.int32)
    if spec.has_cat:
        return bin_goes_left(fbins, feat["nb"][f], feat["missing"][f], t, dl,
                             node_cat, node_mask)
    return bin_goes_left(fbins, feat["nb"][f], feat["missing"][f], t, dl)


def child_bounds_basic(mono_f, l_sm, r_sm, lb, ub):
    """Monotone "basic" method at one split (ref:
    monotone_constraints.hpp `BasicLeafConstraints`): one-shot midpoint
    bounds at child creation, children clamped to THEIR bounds.
    Returns (l_fin, r_fin, l_lb, l_ub, r_lb, r_ub)."""
    l_out = jnp.clip(l_sm, lb, ub)
    r_out = jnp.clip(r_sm, lb, ub)
    mid = 0.5 * (l_out + r_out)
    l_ub = jnp.where(mono_f == 1, jnp.minimum(ub, mid), ub)
    r_lb = jnp.where(mono_f == 1, jnp.maximum(lb, mid), lb)
    l_lb = jnp.where(mono_f == -1, jnp.maximum(lb, mid), lb)
    r_ub = jnp.where(mono_f == -1, jnp.minimum(ub, mid), ub)
    return (jnp.clip(l_sm, l_lb, l_ub), jnp.clip(r_sm, r_lb, r_ub),
            l_lb, l_ub, r_lb, r_ub)


def make_cegb_penalty(spec: GrowerSpec, feat: Dict[str, Array], F: int):
    """(cegb_on, cegb_penalty) — per-candidate [F] gain penalties (ref:
    cost_effective_gradient_boosting.hpp
    `CostEfficientGradientBoosting::DetlaGain`: split cost +
    once-per-model coupled feature cost + per-row lazy feature cost).
    ONE definition shared by the strict and wave growers so the two
    policies price identical candidates identically; `feat["cegb_used"]`
    is frozen for the duration of a tree (the booster commits it
    after each tree), so the penalty of a candidate depends only on its
    leaf's count and path — not on growth order."""
    cegb_on = spec.cegb_tradeoff > 0.0 and \
        (spec.cegb_penalty_split > 0.0 or spec.cegb_coupled
         or spec.cegb_lazy)

    def cegb_penalty(n_child, path_used):
        if not cegb_on:
            return None
        p = jnp.full((F,), spec.cegb_penalty_split * n_child,
                     jnp.float32)
        if spec.cegb_coupled:
            p = p + feat["cegb_coupled"] * \
                (1.0 - feat["cegb_used"].astype(jnp.float32))
        if spec.cegb_lazy:
            p = p + feat["cegb_lazy"] * n_child * \
                (1.0 - path_used.astype(jnp.float32))
        return spec.cegb_tradeoff * p

    return cegb_on, cegb_penalty


def forced_split_arrays(spec: GrowerSpec):
    """(forced_leaf, forced_feat, forced_bin) [n_forced] i32 arrays from
    the spec's BFS-ordered forced-splits tuple — shared by both growers."""
    return (jnp.array([s[0] for s in spec.forced_splits], jnp.int32),
            jnp.array([s[1] for s in spec.forced_splits], jnp.int32),
            jnp.array([s[2] for s in spec.forced_splits], jnp.int32))


def empty_split_arrays(MB: int):
    """The SplitResult-shaped all-infeasible placeholder (gain -inf) used
    as the lax.cond partner of a forced-split evaluation.  ONE definition
    so the tuple layout can never drift between the growers — must match
    `_split_to_arrays` element-for-element."""
    return (jnp.float32(NEG_INF), jnp.int32(-1), jnp.int32(0),
            jnp.bool_(False), jnp.float32(0), jnp.float32(0),
            jnp.float32(0), jnp.float32(0), jnp.float32(0),
            jnp.float32(0), jnp.bool_(False), jnp.zeros((MB,), bool))


def ic_allowed_from_used(feat: Dict[str, Array], used: Array) -> Array:
    """[F] features allowed under interaction constraints for a node
    whose root path already used `used` [F] (ref: col_sampler.hpp
    interaction-constraint filtering): the union of constraint groups
    that contain the path's entire used set."""
    groups = feat["ic_groups"]
    ok_k = ~jnp.any(used[None, :] & ~groups, axis=1)
    return jnp.any(groups & ok_k[:, None], axis=0)


@functools.lru_cache(maxsize=64)
def make_grower(spec: GrowerSpec, axis_name: str = None, mode: str = "data",
                n_shards: int = 1, det_reduce: bool = False,
                num_data: int = 0):
    """Build (and cache) the jitted grow function for a static spec.

    With `axis_name`, the grower becomes a DISTRIBUTED tree learner; call it
    under `jax.shard_map`.  `mode` picks the parallelism strategy (the TPU
    re-design of the reference's TreeLearner factory cross product,
    ref: src/treelearner/tree_learner.cpp `TreeLearner::CreateTreeLearner`):

    - "data" (ref: data_parallel_tree_learner.cpp): rows sharded over the
      axis, each shard histograms its local rows, full [F, MB, 3] histograms
      are `psum`med, every shard finds the identical best split (replicated
      compute, zero extra collectives).  No divisibility requirements.
    - "data_rs" (same reference, closer comm pattern): histograms are
      `psum_scatter`ed over the feature axis — the literal TPU analog of
      `Network::ReduceScatter` — so each shard scans only its F/S feature
      block for splits, then the winning `SplitInfo` is allreduce-maxed.
      Requires F % n_shards == 0 (callers pad features).

    `axis_name` may be a TUPLE of mesh axes for a 2-level ("dcn", "ici")
    mesh (multi-slice training): histograms reduce-scatter over the LAST
    (ICI) axis and allreduce over the leading (DCN) axes — feature blocks
    ride the fast interconnect, slices exchange only summed blocks, the
    SplitInfo max reduces over ICI only (DCN replicas are identical after
    the block psum).  `n_shards` is the LAST axis size.
    - "feature" (ref: feature_parallel_tree_learner.cpp): every shard holds
      ALL rows (bins replicated), searches only its feature block, and the
      winning SplitInfo is allreduce-maxed; split application is local on
      every shard since all rows are present.  Requires F % n_shards == 0.
    - "voting" (ref: voting_parallel_tree_learner.cpp, PV-Tree): rows
      sharded; each shard votes its local top-k features (local gains on
      its row shard, size constraints scaled by 1/shards), the global top
      2k by votes are elected, and ONLY those features' histograms are
      psummed — communication drops from O(F·MB) to O(2k·MB), the
      strategy for DCN-crossing meshes.  `n_shards` = total shard count.
    """
    L = spec.num_leaves
    MB = spec.max_bin
    find = functools.partial(
        find_best_split,
        l1=spec.lambda_l1, l2=spec.lambda_l2,
        min_data_in_leaf=spec.min_data_in_leaf,
        min_sum_hessian=spec.min_sum_hessian_in_leaf,
        min_gain_to_split=spec.min_gain_to_split,
        max_delta_step=spec.max_delta_step,
        cat_smooth=spec.cat_smooth, cat_l2=spec.cat_l2,
        max_cat_threshold=spec.max_cat_threshold,
        max_cat_to_onehot=spec.max_cat_to_onehot,
        min_data_per_group=spec.min_data_per_group,
        path_smooth=spec.path_smooth, has_cat=spec.has_cat)
    # voting: local votes use the shard's row subset, so size constraints
    # scale by 1/shards (ref: VotingParallelTreeLearner ctor divides
    # min_data_in_leaf / min_sum_hessian by num_machines)
    find_local_vote = functools.partial(
        find_best_split,
        l1=spec.lambda_l1, l2=spec.lambda_l2,
        min_data_in_leaf=spec.min_data_in_leaf / max(n_shards, 1),
        min_sum_hessian=spec.min_sum_hessian_in_leaf / max(n_shards, 1),
        min_gain_to_split=spec.min_gain_to_split,
        max_delta_step=spec.max_delta_step,
        cat_smooth=spec.cat_smooth, cat_l2=spec.cat_l2,
        max_cat_threshold=spec.max_cat_threshold,
        max_cat_to_onehot=spec.max_cat_to_onehot,
        min_data_per_group=spec.min_data_per_group,
        path_smooth=spec.path_smooth, want_feature_gains=True,
        has_cat=spec.has_cat)

    def clamp_output(g, h):
        return leaf_output(g, h, spec.lambda_l1, spec.lambda_l2,
                           spec.max_delta_step)

    # 2-level mesh: leading axes are DCN (cross-slice), last axis is ICI
    axes_all = axis_name if isinstance(axis_name, tuple) else \
        ((axis_name,) if axis_name is not None else None)
    axis_last = axes_all[-1] if axes_all else None
    axes_dcn = axes_all[:-1] if axes_all else ()
    block = axis_name is not None and mode in ("data_rs", "feature")
    # deterministic fixed-order reduction (ROADMAP 1a): replay the SERIAL
    # accumulation order across shards — histograms fold shard-by-shard
    # around a ring in ascending shard order (the streamed-carry entries
    # of ops/histogram.py guarantee fold == one-pass bitwise), and root
    # sums reduce the gathered row vector with the serial expression —
    # so every round's tree is byte-identical to the serial grower and
    # multi-round sharded training cannot drift.  Single data axis only;
    # voting/feature keep their own merge semantics.
    det = bool(det_reduce) and axes_all is not None \
        and len(axes_all) == 1 and mode in ("data", "data_rs") \
        and n_shards > 1 and num_data > 0
    if det_reduce and axes_all is not None and not det:
        from ..utils import log
        log.info(f"deterministic_reduce: unsupported topology "
                 f"(mode={mode}, axes={axes_all}, shards={n_shards}, "
                 f"num_data={num_data}) — keeping the tree-psum reduction")
    if block and axes_dcn and mode == "feature":
        raise ValueError("feature-parallel over a 2-level mesh is not "
                         "supported; use the data strategy")
    if spec.bundled and block:
        raise ValueError("EFB bundling requires mode='data' for "
                         "distributed growers (bundle columns do not align "
                         "with per-feature blocks)")
    # histogram bin-axis size: bundle columns can be wider than any single
    # feature's bin count
    HB = spec.bundle_max_bin if spec.bundled else spec.max_bin

    # bin axis is `_` (not F): under EFB bundling bins_fm is [G, N]
    # bundle-major while `allowed` stays [F] over real features
    @contract(bins_fm="[_, N] int", grad="[N] f32", hess="[N] f32",
              sample_weight="[N] f32", feat="tree", allowed="[F] bool",
              ret="tree")
    def grow(bins_fm: Array,       # [F, N] (or [G, N] bundled) feature-major
             grad: Array,          # [N] f32
             hess: Array,          # [N] f32
             sample_weight: Array,  # [N] f32 bagging/GOSS weights (0 = out)
             feat: Dict[str, Array],  # per-feature metadata pytree (above)
             allowed: Array,       # [F] bool (trivial/colsample masked out)
             ) -> DeviceTree:
        N = bins_fm.shape[1]
        F = feat["nb"].shape[0]
        payload = jnp.stack([grad * sample_weight, hess * sample_weight,
                             sample_weight], axis=1)  # [N, 3]
        mono = feat.get("mono")
        if mono is None:
            mono = jnp.zeros((F,), jnp.int32)

        if spec.bundled:
            expand_bundled, decode_bins = make_bundled_expander(spec, feat)
        else:
            decode_bins = None

        if block:
            # this shard owns feature block [offset, offset + Fb) for split
            # finding; partition still uses the full (global) feature space
            Fb, offset, bslice, bfeat, bmono = make_feature_blocks(
                feat, mono, F, axis_last, n_shards, mode)
            # feature mode histograms only this shard's columns (bins are
            # replicated); data_rs histograms all columns of its row shard
            hist_bins = bslice(bins_fm) if mode == "feature" else bins_fm
        else:
            bfeat, bmono, hist_bins = feat, mono, bins_fm

        # the kernel payload rows are loop-INVARIANT per tree: prepare
        # them once here, not inside every while-loop iteration's call
        # (XLA does not reliably hoist the split/lattice encoding out of
        # the while body — same hoisting as the wave grower)
        if spec.hist_impl == "pallas":
            from .pallas_hist import (_split_payload9, assert_bins_in_plan,
                                      pallas_histogram_multi_rows)
            pw_prep = _split_payload9(payload)
            if spec.debug_checks and spec.hist_lane_plan is not None:
                assert_bins_in_plan(hist_bins, spec.hist_lane_plan)
        elif spec.hist_impl == "pallas_q":
            from .pallas_hist import (
                pallas_histogram_multi_quantized_rows,
                quantized_lattice_rows)
            pw_prep = quantized_lattice_rows(payload, feat["qscales"][0],
                                             feat["qscales"][1],
                                             debug=spec.debug_checks)
        one_slot = jnp.zeros((1,), jnp.int32)

        def kernel_hist(mask_rows):
            """This shard's rows through the Pallas kernel."""
            lid = jnp.where(mask_rows, 0, -1).astype(jnp.int32)
            if spec.hist_impl == "pallas":
                return pallas_histogram_multi_rows(
                    hist_bins, pw_prep, lid, one_slot, HB,
                    interpret=spec.hist_interpret,
                    plan=spec.hist_lane_plan)[0]
            return pallas_histogram_multi_quantized_rows(
                hist_bins, pw_prep, lid, one_slot, HB,
                feat["qscales"][0], feat["qscales"][1],
                interpret=spec.hist_interpret)[0]

        if det:
            # ring-chained deterministic histogram: shard t folds its
            # local rows onto the carry received from shard t-1, so the
            # scatter-add sequence is exactly the serial one-pass order
            # over rows 0..num_data.  Pad rows (weight 0, absent from the
            # serial program) carry leaf -1 and key to the dropped slot
            # instead of adding a bit-flipping +0.0 to live cells.  On the
            # Pallas families the kernel stays the histogram: its per-shard
            # partials are chained in shard order instead
            # (`ring_ordered_sum`) — fixed order, not bitwise serial.
            row0_g = jax.lax.axis_index(axis_last) * N
            det_valid = row0_g + jnp.arange(N) < num_data
            kernel_fam = spec.hist_impl in ("pallas", "pallas_q")
            packed_fam = spec.hist_impl == "packed"
            if packed_fam:
                from .histogram import (hist_stream_packed_finalize,
                                        hist_stream_packed_init,
                                        hist_stream_packed_update)

            def det_hist(mask_rows):
                Fh = hist_bins.shape[0]
                if kernel_fam:
                    with jax.named_scope("ring_fold"):
                        h = ring_ordered_sum(kernel_hist(mask_rows),
                                             axis_last, n_shards)
                elif packed_fam:
                    chl = spec.packed_const_hess_level
                    lid = jnp.where(mask_rows & det_valid, 0, -1)\
                        .astype(jnp.int32)

                    def fold(acc):
                        return hist_stream_packed_update(
                            acc, hist_bins, payload, lid, one_slot, HB,
                            feat["qscales"][0], feat["qscales"][1],
                            const_hess_level=chl)

                    full = ring_fold(
                        fold, hist_stream_packed_init(Fh, 1, HB, chl),
                        axis_last, n_shards)
                    h = hist_stream_packed_finalize(
                        full, Fh, 1, HB, feat["qscales"][0],
                        feat["qscales"][1], const_hess_level=chl)[0]
                else:
                    lid = jnp.where(mask_rows & det_valid, 0, -1)\
                        .astype(jnp.int32)

                    def fold(acc):
                        return hist_stream_update(acc, hist_bins, payload,
                                                  lid, one_slot, HB)

                    full = ring_fold(fold, hist_stream_init(Fh, 1, HB),
                                     axis_last, n_shards)
                    h = hist_stream_finalize(full, Fh, 1, HB)[0]
                if mode == "data_rs":
                    Fb_h = h.shape[0] // n_shards
                    h = jax.lax.dynamic_slice_in_dim(
                        h, jax.lax.axis_index(axis_last) * Fb_h, Fb_h,
                        axis=0)
                return h

        def hist_of(mask_rows):
            # named scopes feed XProf/Perfetto timelines (SURVEY §5: the
            # reference only has USE_TIMETAG chrono counters)
            with jax.named_scope("histogram"):
                if det:
                    return det_hist(mask_rows)
                if spec.hist_impl in ("pallas", "pallas_q"):
                    h = kernel_hist(mask_rows)
                elif spec.hist_impl == "packed":
                    # quantized-gradient packed-int scatter (2 sweeps);
                    # scales ride in feat["qscales"] (booster/fused set
                    # them right after quantize_gradients)
                    from .histogram import leaf_histogram_packed
                    h = leaf_histogram_packed(
                        hist_bins, payload, mask_rows, HB,
                        feat["qscales"][0], feat["qscales"][1],
                        const_hess_level=spec.packed_const_hess_level)
                else:
                    h = leaf_histogram_limbs(hist_bins, payload, mask_rows,
                                             HB)
                if axis_name is not None:
                    if mode == "data":
                        h = jax.lax.psum(h, axes_all)
                    elif mode == "data_rs":
                        # ref: Network::ReduceScatter of histogram buffers —
                        # each shard receives the summed block it will scan
                        # (over ICI); DCN slices then allreduce the block
                        h = jax.lax.psum_scatter(h, axis_last,
                                                 scatter_dimension=0,
                                                 tiled=True)
                        if axes_dcn:
                            h = jax.lax.psum(h, axes_dcn)
            return h

        cegb_on, cegb_penalty = make_cegb_penalty(spec, feat, F)

        def split_of(hist, g, h, c, node_allowed, lb, ub, p_out,
                     cand_mask=None, penalty=None):
            with jax.named_scope("find_split"):
                return _split_of(hist, g, h, c, node_allowed, lb, ub,
                                 p_out, cand_mask, penalty)

        def _split_of(hist, g, h, c, node_allowed, lb, ub, p_out,
                      cand_mask=None, penalty=None):
            if axis_name is not None and mode == "voting" \
                    and cand_mask is not None:
                # forced splits bypass the vote (the reference forces
                # regardless of search heuristics): sum the full histogram
                # so the designated cell sees GLOBAL stats
                hist = jax.lax.psum(hist, axes_all)
            elif axis_name is not None and mode == "voting":
                # PV-Tree (ref: voting_parallel_tree_learner.cpp): each
                # shard votes its local top-k features, the global top-2k
                # by votes are elected, and only THOSE histograms are
                # summed across shards — O(2k·MB) traffic instead of
                # O(F·MB)
                local = hist_value(hist)
                ltot = local.sum(axis=1)[0]           # local (g, h, cnt)
                fg = find_local_vote(local, ltot[0], ltot[1], ltot[2],
                                     bfeat["nb"], bfeat["missing"],
                                     bfeat["default"], node_allowed,
                                     bfeat["is_cat"], mono=bmono)
                k = min(spec.voting_top_k, F)
                top_idx = jax.lax.top_k(fg, k)[1]
                votes = jnp.zeros((F,), jnp.float32).at[top_idx].set(1.0)
                votes = jax.lax.psum(votes, axes_all)
                # deterministic election: votes desc, feature index asc
                vote_key = votes * (F + 1.0) \
                    - jnp.arange(F, dtype=jnp.float32)
                elected = jax.lax.top_k(vote_key, min(2 * k, F))[1]
                sel = jax.lax.psum(hist[elected], axes_all)
                hist = jnp.zeros_like(hist).at[elected].set(sel)
                node_allowed = node_allowed & \
                    jnp.zeros((F,), bool).at[elected].set(True)
            if spec.bundled:
                # bundle columns expand to features by value: no limbs
                hist = expand_bundled(hist_value(hist), g, h, c)
            if block:
                node_allowed = jax.lax.dynamic_slice_in_dim(
                    node_allowed, offset, Fb, axis=0)
                if cand_mask is not None:
                    cand_mask = jax.lax.dynamic_slice_in_dim(
                        cand_mask, offset, Fb, axis=0)
                if penalty is not None:
                    penalty = jax.lax.dynamic_slice_in_dim(
                        penalty, offset, Fb, axis=0)
            s = find(hist_value(hist), g, h, c, bfeat["nb"],
                     bfeat["missing"], bfeat["default"], node_allowed,
                     bfeat["is_cat"], mono=bmono, out_lb=lb, out_ub=ub,
                     parent_output=p_out, cand_mask=cand_mask,
                     gain_penalty=penalty)
            s = refine_child_sums(s, hist, bfeat["nb"], bfeat["missing"])
            if block:
                s = rebase_and_merge_block_split(s, offset, axis_last,
                                                 n_shards)
            return s

        # per-node column sampling + extra_trees (shared derivations —
        # the wave grower draws IDENTICAL per-node samples)
        bynode_mask, extra_mask = make_node_samplers(spec, feat, F)

        # forced splits (BFS order), applied before best-gain growth
        n_forced = len(spec.forced_splits)
        if n_forced:
            forced_leaf, forced_feat, forced_bin = forced_split_arrays(spec)

        # ---- root ----
        root_mask = jnp.ones((N,), dtype=bool)
        hist0 = hist_of(root_mask)
        if det:
            # deterministic root stats: gather the rows back into storage
            # order (pad tail sliced off) and reduce with the serial
            # grower's own expression — no psum of per-shard partials
            gp = jax.lax.all_gather(payload, axis_last, axis=0,
                                    tiled=True)[:num_data]
            root_g = gp[:, 0].sum()
            root_h = gp[:, 1].sum()
            root_c = gp[:, 2].sum()
        else:
            root_g = payload[:, 0].sum()
            root_h = payload[:, 1].sum()
            root_c = payload[:, 2].sum()
            if axis_name is not None and mode != "feature":
                # ref: DataParallelTreeLearner::BeforeTrain root-stat
                # Allreduce (feature mode holds all rows on every shard —
                # already global)
                root_g = jax.lax.psum(root_g, axes_all)
                root_h = jax.lax.psum(root_h, axes_all)
                root_c = jax.lax.psum(root_c, axes_all)
        root_out = clamp_output(root_g, root_h)
        if spec.n_ic_groups:
            # only features inside some constraint group may ever split
            allowed = allowed & jnp.any(feat["ic_groups"], axis=0)
        s0 = split_of(hist0, root_g, root_h, root_c,
                      allowed & bynode_mask(0),
                      jnp.float32(-INF), jnp.float32(INF), root_out,
                      cand_mask=extra_mask(0),
                      penalty=cegb_penalty(root_c, jnp.zeros((F,), bool)))

        # per-leaf histogram storage: one slot per leaf by default, or a
        # bounded LRU pool (ref: feature_histogram.hpp `HistogramPool`) —
        # a pool miss recomputes the parent histogram from its rows, trading
        # FLOPs for carry memory exactly like the reference's cache miss
        pooled = 0 < spec.hist_pool_slots < L
        P = max(2, spec.hist_pool_slots) if pooled else L
        hist = jnp.zeros((P,) + hist0.shape, dtype=jnp.float32)\
            .at[0].set(hist0)
        leaf_best = [jnp.zeros((L,) + a.shape, dtype=a.dtype)
                     .at[0].set(a) for a in _split_to_arrays(s0)]
        leaf_best[0] = jnp.full((L,), NEG_INF, dtype=jnp.float32).at[0]\
            .set(s0.gain)
        leaf_g = jnp.zeros((L,), jnp.float32).at[0].set(root_g)
        leaf_h = jnp.zeros((L,), jnp.float32).at[0].set(root_h)
        leaf_c = jnp.zeros((L,), jnp.float32).at[0].set(root_c)
        leaf_depth = jnp.zeros((L,), jnp.int32)

        nodes = dict(
            split_leaf=jnp.zeros((L - 1,), jnp.int32),
            split_feature=jnp.zeros((L - 1,), jnp.int32),
            threshold_bin=jnp.zeros((L - 1,), jnp.int32),
            default_left=jnp.zeros((L - 1,), bool),
            split_is_cat=jnp.zeros((L - 1,), bool),
            split_cat_mask=jnp.zeros((L - 1, MB), bool),
            split_gain=jnp.zeros((L - 1,), jnp.float32),
            internal_g=jnp.zeros((L - 1,), jnp.float32),
            internal_h=jnp.zeros((L - 1,), jnp.float32),
            internal_cnt=jnp.zeros((L - 1,), jnp.float32),
        )

        state = dict(
            step=jnp.int32(0), nl=jnp.int32(1),
            leaf_id=jnp.zeros((N,), jnp.int32),
            hist=hist, leaf_gain=leaf_best[0], leaf_feat=leaf_best[1],
            leaf_thr=leaf_best[2], leaf_dl=leaf_best[3],
            leaf_lg=leaf_best[4], leaf_lh=leaf_best[5], leaf_lc=leaf_best[6],
            leaf_rg=leaf_best[7], leaf_rh=leaf_best[8], leaf_rc=leaf_best[9],
            leaf_iscat=leaf_best[10], leaf_catmask=leaf_best[11],
            leaf_g=leaf_g, leaf_h=leaf_h, leaf_c=leaf_c,
            leaf_lb=jnp.full((L,), -INF, jnp.float32),
            leaf_ub=jnp.full((L,), INF, jnp.float32),
            # each leaf's final (smoothed + clamped) output; children
            # smooth toward their parent's entry (ref: USE_SMOOTHING)
            leaf_out=jnp.zeros((L,), jnp.float32).at[0].set(root_out),
            leaf_depth=leaf_depth, nodes=nodes,
        )
        if pooled:
            # owner[p] = leaf whose histogram lives in slot p (-1 empty);
            # used[p] = step of last touch (-1 sorts empty slots first)
            state["owner"] = jnp.full((P,), -1, jnp.int32).at[0].set(0)
            state["used"] = jnp.full((P,), -1, jnp.int32).at[0].set(0)
        track_used = spec.n_ic_groups > 0 or (cegb_on and spec.cegb_lazy)
        if track_used:
            # features used on each leaf's root path (ref: col_sampler.hpp
            # interaction-constraint filtering; CEGB lazy feature costs)
            state["leaf_used"] = jnp.zeros((L, F), bool)
        interm = spec.monotone_intermediate
        if interm:
            if pooled:
                raise ValueError("monotone intermediate requires the "
                                 "un-pooled histogram layout")
            # ancestor incidence: anc_left[leaf, s] ⇔ leaf lies in the left
            # subtree of the split made at step s (ref:
            # monotone_constraints.hpp IntermediateLeafConstraints tracks
            # the same relation via tree walks)
            state["anc_left"] = jnp.zeros((L, L - 1), bool)
            state["anc_right"] = jnp.zeros((L, L - 1), bool)
            # node id of the search that produced each leaf's cached split
            # (reproduces per-node column samples on re-search)
            state["leaf_nid"] = jnp.zeros((L,), jnp.int32)

        def cond(st):
            go = (jnp.max(st["leaf_gain"]) > 0.0)
            if n_forced:
                # forced_n shrinks to `step` if a forced split proves
                # infeasible — abandoning the rest of the forced prefix
                go = go | (st["step"] < st["forced_n"])
            return (st["step"] < L - 1) & go

        def body(st):
            step = st["step"]
            new = st["nl"]
            free_best = jnp.argmax(st["leaf_gain"]).astype(jnp.int32)

            def fetch_hist(leaf, leaf_mask):
                """Parent histogram of `leaf` (pool miss → recompute from
                its rows, the reference's cache-miss path)."""
                if pooled:
                    match = st["owner"] == leaf
                    hit = match.any()
                    pslot = jnp.argmax(match).astype(jnp.int32)
                    ph = jax.lax.cond(
                        hit, lambda _: st["hist"][pslot],
                        lambda _: hist_of(leaf_mask), None)
                    return ph, hit, pslot
                return st["hist"][leaf], jnp.bool_(True), leaf

            # ---- forced split (if any): evaluate the designated
            # (feature, bin) on ITS leaf's histogram ----
            if n_forced:
                idx = jnp.clip(step, 0, n_forced - 1)
                active_forced = step < st["forced_n"]

                def eval_forced(_):
                    fl = forced_leaf[idx]
                    ph, _, _ = fetch_hist(fl, st["leaf_id"] == fl)
                    cand = jnp.zeros((F, MB), bool)\
                        .at[forced_feat[idx], forced_bin[idx]].set(True)
                    # forced splits bypass column sampling (ref:
                    # SerialTreeLearner::ForceSplits runs before the
                    # ColSampler-gated search) — force-allow the feature
                    fs = split_of(ph, st["leaf_g"][fl], st["leaf_h"][fl],
                                  st["leaf_c"][fl],
                                  allowed.at[forced_feat[idx]].set(True),
                                  st["leaf_lb"][fl], st["leaf_ub"][fl],
                                  st["leaf_out"][fl], cand_mask=cand)
                    return _split_to_arrays(fs)

                fa = jax.lax.cond(active_forced, eval_forced,
                                  lambda _: empty_split_arrays(MB), None)
                forced_ok = active_forced & jnp.isfinite(fa[0])
                best = jnp.where(forced_ok, forced_leaf[idx], free_best)
                # infeasible forced split → abandon the remaining prefix
                # (its BFS leaf numbering no longer matches the tree)
                forced_n = jnp.where(active_forced & ~forced_ok,
                                     step, st["forced_n"])
            else:
                best = free_best
            in_leaf = st["leaf_id"] == best

            parent_hist, hit, pslot = fetch_hist(best, in_leaf)

            stored = (st["leaf_gain"][best], st["leaf_feat"][best],
                      st["leaf_thr"][best], st["leaf_dl"][best],
                      st["leaf_lg"][best], st["leaf_lh"][best],
                      st["leaf_lc"][best], st["leaf_rg"][best],
                      st["leaf_rh"][best], st["leaf_rc"][best],
                      st["leaf_iscat"][best], st["leaf_catmask"][best])
            if n_forced:
                chosen = tuple(jnp.where(forced_ok, a, b)
                               for a, b in zip(fa, stored))
            else:
                chosen = stored
            (gain_s, f, t, dl, lg, lh, lc, rg, rh, rc, node_cat,
             node_mask) = chosen

            # ---- partition: dense leaf_id update (no row movement);
            # the scope name is the wave grower's (one vocabulary for the
            # trace readers) ----
            with jax.named_scope("partition"):
                go_left = split_go_left(spec, feat, bins_fm, decode_bins,
                                        f, t, dl, node_cat, node_mask)
                leaf_id = jnp.where(in_leaf & ~go_left, new,
                                    st["leaf_id"])

            # ---- record the internal node ----
            nodes = st["nodes"]
            nodes = dict(
                split_leaf=nodes["split_leaf"].at[step].set(best),
                split_feature=nodes["split_feature"].at[step].set(f),
                threshold_bin=nodes["threshold_bin"].at[step].set(t),
                default_left=nodes["default_left"].at[step].set(dl),
                split_is_cat=nodes["split_is_cat"].at[step].set(node_cat),
                split_cat_mask=nodes["split_cat_mask"].at[step].set(node_mask),
                split_gain=nodes["split_gain"].at[step].set(gain_s),
                internal_g=nodes["internal_g"].at[step].set(st["leaf_g"][best]),
                internal_h=nodes["internal_h"].at[step].set(st["leaf_h"][best]),
                internal_cnt=nodes["internal_cnt"].at[step].set(
                    st["leaf_c"][best]),
            )

            def put2(arr, a, b):
                return arr.at[best].set(a).at[new].set(b)

            # ---- child outputs: smoothing → monotone clamp ----
            lb, ub = st["leaf_lb"][best], st["leaf_ub"][best]
            parent_out = st["leaf_out"][best]
            mc_f = jnp.where(node_cat, 0, mono[f])
            l_sm = smooth_output(clamp_output(lg, lh), lc, parent_out,
                                 spec.path_smooth)
            r_sm = smooth_output(clamp_output(rg, rh), rc, parent_out,
                                 spec.path_smooth)
            if not interm:
                # basic method: one-shot midpoint bounds at creation
                (l_fin, r_fin, l_lb, l_ub, r_lb, r_ub) = \
                    child_bounds_basic(mc_f, l_sm, r_sm, lb, ub)
            else:
                # intermediate method: outputs only clip to the parent's
                # bounds (the split search already enforced the direction
                # between siblings); bounds for EVERY leaf are then
                # recomputed from the current outputs of the opposite
                # subtree of each monotone ancestor
                l_fin = jnp.clip(l_sm, lb, ub)
                r_fin = jnp.clip(r_sm, lb, ub)
                anc_left = st["anc_left"].at[new].set(st["anc_left"][best])\
                    .at[best, step].set(True)
                anc_right = st["anc_right"].at[new]\
                    .set(st["anc_right"][best]).at[new, step].set(True)
                leaf_out_upd = put2(st["leaf_out"], l_fin, r_fin)
                leaf_nid = put2(st["leaf_nid"], 2 * step + 1, 2 * step + 2)
                signs = jnp.where(nodes["split_is_cat"], 0,
                                  mono[nodes["split_feature"]])    # [L-1]
                act = jnp.arange(L) < (new + 1)
                Ml = anc_left & act[:, None]                       # [L,L-1]
                Mr = anc_right & act[:, None]
                outs_r = leaf_out_upd[None, :]                     # [1, L]
                left_max = jnp.max(jnp.where(Ml.T, outs_r, -INF), axis=1)
                left_min = jnp.min(jnp.where(Ml.T, outs_r, INF), axis=1)
                right_max = jnp.max(jnp.where(Mr.T, outs_r, -INF), axis=1)
                right_min = jnp.min(jnp.where(Mr.T, outs_r, INF), axis=1)
                pos_s = (signs == 1)[None, :]
                neg_s = (signs == -1)[None, :]
                new_ub = jnp.minimum(
                    jnp.min(jnp.where(Ml & pos_s, right_min[None, :], INF),
                            axis=1),
                    jnp.min(jnp.where(Mr & neg_s, left_min[None, :], INF),
                            axis=1))
                new_lb = jnp.maximum(
                    jnp.max(jnp.where(Mr & pos_s, left_max[None, :], -INF),
                            axis=1),
                    jnp.max(jnp.where(Ml & neg_s, right_max[None, :],
                                      -INF), axis=1))
                l_lb, l_ub = new_lb[best], new_ub[best]
                r_lb, r_ub = new_lb[new], new_ub[new]
                slotL = jnp.arange(L)
                bounds_moved = act & (slotL != best) & (slotL != new) & \
                    ((new_lb != st["leaf_lb"]) | (new_ub != st["leaf_ub"]))

                def reeval(_):
                    """Re-search cached best splits of leaves whose bounds
                    moved (ref: IntermediateLeafConstraints
                    leaves_to_update re-running FindBestSplits)."""
                    def eval_one(i):
                        lu = st["leaf_used"][i] if track_used \
                            else jnp.zeros((F,), bool)
                        deep = (spec.max_depth <= 0) | \
                            (st["leaf_depth"][i] < spec.max_depth)
                        a = allowed & deep
                        if spec.n_ic_groups:
                            a = a & ic_allowed_from_used(feat, lu)
                        a = a & bynode_mask(st["leaf_nid"][i])
                        s = split_of(st["hist"][i], st["leaf_g"][i],
                                     st["leaf_h"][i], st["leaf_c"][i], a,
                                     new_lb[i], new_ub[i], leaf_out_upd[i],
                                     cand_mask=extra_mask(st["leaf_nid"][i]),
                                     penalty=cegb_penalty(st["leaf_c"][i],
                                                          lu))
                        return _split_to_arrays(s)
                    return jax.vmap(eval_one)(jnp.arange(L))

                def keep(_):
                    return (st["leaf_gain"], st["leaf_feat"],
                            st["leaf_thr"], st["leaf_dl"], st["leaf_lg"],
                            st["leaf_lh"], st["leaf_lc"], st["leaf_rg"],
                            st["leaf_rh"], st["leaf_rc"], st["leaf_iscat"],
                            st["leaf_catmask"])

                searched = jax.lax.cond(bounds_moved.any(), reeval, keep,
                                        None)
                cur = keep(None)
                upd = tuple(
                    jnp.where(bounds_moved.reshape((L,) + (1,) *
                                                   (c.ndim - 1)), s_, c)
                    for s_, c in zip(searched, cur))

            # ---- histogram: smaller child scanned, larger by subtraction ----
            left_smaller = lc <= rc
            small_leaf = jnp.where(left_smaller, best, new)
            small_hist = hist_of(leaf_id == small_leaf)
            large_hist = hist_sub(parent_hist, small_hist)
            lhist = jnp.where(left_smaller, small_hist, large_hist)
            rhist = jnp.where(left_smaller, large_hist, small_hist)
            if pooled:
                # place both children, evicting least-recently-used slots
                slot_l = jnp.where(hit, pslot,
                                   jnp.argmin(st["used"]).astype(jnp.int32))
                used1 = st["used"].at[slot_l].set(step + 1)
                slot_r = jnp.argmin(used1).astype(jnp.int32)
                hist = st["hist"].at[slot_l].set(lhist).at[slot_r].set(rhist)
                pool_owner = st["owner"].at[slot_l].set(best)\
                    .at[slot_r].set(new)
                pool_used = used1.at[slot_r].set(step + 1)
            else:
                hist = st["hist"].at[best].set(lhist).at[new].set(rhist)

            # ---- find best splits for the two children ----
            depth = st["leaf_depth"][best] + 1
            deep_ok = (spec.max_depth <= 0) | (depth < spec.max_depth)
            child_allowed = allowed & deep_ok
            extra = {"owner": pool_owner, "used": pool_used} if pooled else {}
            child_used = None
            if track_used:
                # both children share the path's used-feature set
                child_used = st["leaf_used"][best].at[f].set(True)
                extra["leaf_used"] = st["leaf_used"].at[best]\
                    .set(child_used).at[new].set(child_used)
            if spec.n_ic_groups:
                # allowed = union of constraint groups containing the path
                child_allowed = child_allowed & \
                    ic_allowed_from_used(feat, child_used)
            ls = split_of(lhist, lg, lh, lc,
                          child_allowed & bynode_mask(2 * step + 1),
                          l_lb, l_ub, l_fin,
                          cand_mask=extra_mask(2 * step + 1),
                          penalty=cegb_penalty(lc, child_used))
            rs = split_of(rhist, rg, rh, rc,
                          child_allowed & bynode_mask(2 * step + 2),
                          r_lb, r_ub, r_fin,
                          cand_mask=extra_mask(2 * step + 2),
                          penalty=cegb_penalty(rc, child_used))

            la, ra = _split_to_arrays(ls), _split_to_arrays(rs)
            if interm:
                base = upd
                lb_arr, ub_arr = new_lb, new_ub
                extra["anc_left"] = anc_left
                extra["anc_right"] = anc_right
                extra["leaf_nid"] = leaf_nid
            else:
                base = (st["leaf_gain"], st["leaf_feat"], st["leaf_thr"],
                        st["leaf_dl"], st["leaf_lg"], st["leaf_lh"],
                        st["leaf_lc"], st["leaf_rg"], st["leaf_rh"],
                        st["leaf_rc"], st["leaf_iscat"], st["leaf_catmask"])
                lb_arr = put2(st["leaf_lb"], l_lb, r_lb)
                ub_arr = put2(st["leaf_ub"], l_ub, r_ub)
            new_state = dict(
                **extra,
                step=step + 1, nl=new + 1, leaf_id=leaf_id, hist=hist,
                leaf_out=put2(st["leaf_out"], l_fin, r_fin),
                leaf_gain=put2(base[0], la[0], ra[0]),
                leaf_feat=put2(base[1], la[1], ra[1]),
                leaf_thr=put2(base[2], la[2], ra[2]),
                leaf_dl=put2(base[3], la[3], ra[3]),
                leaf_lg=put2(base[4], la[4], ra[4]),
                leaf_lh=put2(base[5], la[5], ra[5]),
                leaf_lc=put2(base[6], la[6], ra[6]),
                leaf_rg=put2(base[7], la[7], ra[7]),
                leaf_rh=put2(base[8], la[8], ra[8]),
                leaf_rc=put2(base[9], la[9], ra[9]),
                leaf_iscat=put2(base[10], la[10], ra[10]),
                leaf_catmask=put2(base[11], la[11], ra[11]),
                leaf_g=put2(st["leaf_g"], lg, rg),
                leaf_h=put2(st["leaf_h"], lh, rh),
                leaf_c=put2(st["leaf_c"], lc, rc),
                leaf_lb=lb_arr,
                leaf_ub=ub_arr,
                leaf_depth=put2(st["leaf_depth"], depth, depth),
                nodes=nodes,
            )
            if n_forced:
                # if neither the forced split nor the free best is
                # applicable (both infeasible), keep the state untouched —
                # the shrunken forced_n makes cond() exit the loop
                new_state["forced_n"] = forced_n
                apply_ok = forced_ok | (gain_s > 0.0)
                fallback = {**st, "forced_n": forced_n}
                new_state = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(apply_ok, a, b),
                    new_state, fallback)
            return new_state

        if n_forced:
            state["forced_n"] = jnp.int32(n_forced)
        st = jax.lax.while_loop(cond, body, state)

        n_splits = st["step"]
        # each leaf's final output was fixed at its creation (smoothing +
        # monotone clamp applied there); slots >= nl stay zero
        slot = jnp.arange(L)
        active = slot < st["nl"]
        # single-leaf tree predicts 0 (ref: GBDT logs "no more leaves that
        # meet the split requirements" and the tree contributes nothing)
        values = jnp.where(active & (st["nl"] > 1), st["leaf_out"], 0.0)

        return DeviceTree(
            n_splits=n_splits,
            split_leaf=st["nodes"]["split_leaf"],
            split_feature=st["nodes"]["split_feature"],
            threshold_bin=st["nodes"]["threshold_bin"],
            default_left=st["nodes"]["default_left"],
            split_is_cat=st["nodes"]["split_is_cat"],
            split_cat_mask=st["nodes"]["split_cat_mask"],
            split_gain=st["nodes"]["split_gain"],
            internal_g=st["nodes"]["internal_g"],
            internal_h=st["nodes"]["internal_h"],
            internal_cnt=st["nodes"]["internal_cnt"],
            leaf_value=values,
            leaf_g=st["leaf_g"], leaf_h=st["leaf_h"], leaf_cnt=st["leaf_c"],
            leaf_id=st["leaf_id"],
        )

    return jax.jit(grow)
