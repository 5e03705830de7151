"""Wave-batched leaf-wise growth — the TPU-first growth policy.

Motivation (PROFILE.md round 3c): the strict best-first loop in
`ops/grow.py` needs ONE new histogram per split, and each histogram is a
full pass over the bin matrix whose MXU cost is IDENTICAL whether the LHS
carries one leaf's payload (9 rows) or fourteen (126 rows) — the MXU pads
the M axis to 128 either way.  A pass that builds one histogram wastes
~93% of itself.  Two schedules fill the pass; neither touches the split
math.

The wave policy changes the growth order: each wave splits EVERY current
leaf whose cached best gain is positive (best-first within the wave, up
to the `wave_width` batch capacity), then computes all the new
smaller-children histograms in ONE batched kernel pass
(`pallas_histogram_multi`), derives the larger children by subtraction,
and re-searches the new leaves' best splits vmapped.  31 leaves grown in
waves alone cost ~7 passes instead of 30.

The strict tail (`wave_strict_tail`: the last splits of a tree are made
in strict best-first order, which allocates the remaining capacity the
way the reference does) keeps the order and changes WHEN a histogram is
built.  Every frontier leaf already carries its best split, so the rows
of its would-be smaller child are known before it is picked, and they do
not change until it is.  A tail pass is therefore made BEFORE the split
it is for, and fills the kernel's other slots with the would-be smaller
children of the next-best leaves (`speculate`); the histograms wait in a
per-leaf cache.  Best-first then commits split after split with no pass
for as long as the best leaf's histogram is already there, and pays a
pass only when the best leaf is one created since the last pass.  A
slot's sums depend on its own rows alone, so every histogram is bit for
bit the one a pass after the split would have built: the trees are those
of one pass per split (tests/data/wave_tail_goldens.json), at about a
third of the passes where the frontier is wide (a 16-split tail: 15
passes before, about 5 measured, PERF.md section 6, PR 26); a chain-shaped
tree, whose next best leaf is always the newest, still pays one per
split.  `DeviceTree.tail_stats` counts passes, hits and unused
histograms.  The cache is one histogram per leaf slot, the size of
`hist`.

Relation to the reference: LightGBM grows strictly best-first
(ref: serial_tree_learner.cpp `SerialTreeLearner::Train` — one
`FindBestSplits` per split); XGBoost exposes the same trade as
`grow_policy=depthwise|lossguide`.  Wave order sits between the two: it
is best-first over the frontier but fills each level before descending,
so trees are more balanced than strict leaf-wise on skewed data and
identical on data where the frontier's gains dominate the children's
(always identical for num_leaves <= 3); with `wave_strict_tail >=
num_leaves - 1` every split is a tail split and the trees are the strict
grower's byte for byte.  Accuracy on benchmark-scale data matches strict
to within noise (tests/test_wave.py); the default policy remains
`leafwise` for stock-exact trees.

Feature scope (the booster downgrades to the strict grower otherwise):
numerical + categorical splits, missing handling, monotone basic,
path smoothing, per-tree/per-node column sampling, extra_trees,
max_depth/min_* constraints, EFB bundling, all histogram impls,
interaction constraints + CEGB (r5: per-leaf used-feature tracking +
the shared candidate pricing of `make_cegb_penalty`, order-independent
within a tree because `cegb_used` is frozen per tree), and distributed
data-parallel training — in the production reduce-scatter mode
(`mode="data_rs"`: block-scattered wave histograms + per-wave SplitInfo
allreduce-max; features block-padded), or full-histogram psum under EFB
(see `make_wave_grower`), and forced splits (r5: the BFS prefix runs as
width-1 waves — strict order by construction — then free growth resumes
at full width).  Monotone intermediate and the bounded histogram pool
keep the strict grower (priced downgrade warning in the booster).

Device phases: the work of one tree runs under `jax.named_scope`s whose
names the trace readers select by (`perfbench/program_readers.py`), so
they are fixed: `payload` (kernel payload carrier), `init` (root sums,
empty node and leaf tables), `histogram_wave` (every histogram pass),
`find_split` (the root's search, the per-child fan-out and its scatter),
`hist_reduce` (inside `histogram_wave`, sharded growers only: the
cross-shard sum of a pass's histograms — the ordered chain, under
`ring_fold`, or `psum_scatter` / `psum` — and the slice of this shard's
column block), `split_allreduce` (inside `find_split`, sharded block
search only: the SplitInfo exchange),
`partition` (the pick loop: choice, node and leaf records; then the rows'
routing, ONCE a wave and once a speculating pass: the wave's picks are
distinct leaves that were ready at its start and the loop's choices read
the per-leaf tables only, so the loop carries no [N] array and one pass
of `ops/route.py` (`route_wave_rows`, a Pallas call of that name on the
kernel families; (F + 8) bytes a row for up to W picks) applies the
recorded picks to `leaf_id`, and the same pass writes a speculation's
slot ids.  A model with categorical columns hands every pick to the pass
as its left set over the bins (`route.left_sets`: `bin_goes_left` on all
256 bins), so a category-set split is one more record of the same pass.
The strict tail's single pick routes itself by `split_go_left` and a
`leaf_id` rewrite, one bin row for all, where the model has no
categorical column (with one it goes through the pass too: a look-up in
the pass costs a hundredth of a gather in the pick's mask at [N]); so does
every pick of a bundled spec, of two-byte bins and of
more than `route.ROUTE_MAX_COLUMNS` columns, whose routing is another
computation or whose all-column pass costs more than a row a pick:
static facts, no option.  `DeviceTree.tail_stats[5:]` count the routing
passes, the picks and slots they routed and, with categorical columns,
how many of those were categorical), `hist_cache` (sibling subtraction,
the two cache scatters, the speculated histograms' reads and scatters),
`prune` (`prune_wave_tail`, only with overgrow).  An
op's phase is the innermost of these on its name stack; what is under
none (loop control, the tree's final selects) is the readers'
"unattributed".  Scopes change HLO metadata only, never the program
(tests/test_wave.py holds that).
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

from .grow import (DeviceTree, GrowerSpec, _split_to_arrays,
                   child_bounds_basic, empty_split_arrays,
                   forced_split_arrays, ic_allowed_from_used,
                   make_bundled_expander, make_cegb_penalty,
                   make_feature_blocks, make_node_samplers,
                   rebase_and_merge_block_split, split_go_left)
from ..analysis.contracts import contract
from .route import (batched_route_applies, left_sets, pick_records,
                    route_rows_xla, route_wave_rows)
from .histogram import (hist_stream_finalize, hist_stream_init,
                        hist_stream_packed_finalize,
                        hist_stream_packed_init,
                        hist_stream_packed_update, hist_stream_update,
                        hist_sub, hist_value, leaf_histogram_multi_limbs,
                        leaf_histogram_packed_multi, ring_fold,
                        ring_ordered_sum)
from .split import (NEG_INF, find_best_split, leaf_output,
                    refine_child_sums, smooth_output)

Array = jax.Array

INF = jnp.inf

# the accuracy-sweep default width (PROFILE.md round 3c: W=6 keeps AUC,
# W=14 leaks ~0.016 of capacity into breadth).  ONE definition: the
# Booster's knob resolution imports this, and `wave_sizes`' fallback for
# directly-built GrowerSpecs resolves to the same swept value.
WAVE_WIDTH_DEFAULT = 6


def wave_sizes(spec: GrowerSpec):
    """(LB, W): internal grow size (overgrow x num_leaves, pruned back
    after growth) and wave width.  ONE definition shared with the
    booster's probe gate so the probed kernel width always matches the
    width the grower runs."""
    L = spec.num_leaves
    LB = L if spec.wave_overgrow <= 1.0 else \
        max(L, int(math.ceil(spec.wave_overgrow * L)))
    return LB, max(1, min(spec.wave_width or WAVE_WIDTH_DEFAULT, LB - 1))


@functools.lru_cache(maxsize=64)
def make_wave_grower(spec: GrowerSpec, axis_name=None, mode: str = "data",
                     n_shards: int = 1, det_reduce: bool = False,
                     num_data: int = 0):
    """Build (and cache) the jitted wave grower for a static spec.

    Same contract as `ops.grow.make_grower`; with `axis_name` the grower
    runs row-sharded data parallelism in one of two histogram-reduction
    modes (the block/voting strategies keep the strict grower):

    - mode="data": batched histograms fully `psum`med; every shard then
      searches all features.  Required under EFB bundling (bundle
      columns don't align with feature blocks).
    - mode="data_rs": the production distributed mode (ref:
      data_parallel_tree_learner.cpp `Network::ReduceScatter`): the
      [S, F, MB, 3] wave histogram is `psum_scatter`ed over the feature
      axis of the LAST mesh axis (ICI), each shard searches only its
      F/n_shards block for ALL the wave's children, and the per-child
      SplitInfo vector is allreduce-max merged across shards
      (`_merge_split_across_shards`, vmapped over the wave).  DCN slices
      allreduce the scattered block, so heavy traffic rides ICI.

    With `det_reduce` over one mesh axis (the booster's default) neither
    collective sums: each shard's histograms are added in ascending shard
    order around a ring and the total is gathered back onto every shard
    (`ops/histogram.ring_ordered_sum`, `ring_fold`); in `data_rs` a shard
    then keeps its column block of that total.

    Histograms are globally summed/scattered before split finding, so
    size constraints need no per-shard rescaling (unlike the voting
    learner's local vote)."""
    L = spec.num_leaves
    MB = spec.max_bin
    # grow-then-prune: grow to LB leaves, prune back to L (off: LB == L)
    LB, W = wave_sizes(spec)
    # resolved wave geometry, recorded ONCE per built program (this body
    # runs host-side at build time, never under jit — R005-safe); the
    # flight recorder reads these back for its wave-utilization block
    from ..telemetry import REGISTRY
    REGISTRY.gauge("wave.width").set(W)
    REGISTRY.gauge("wave.grow_leaves").set(LB)
    REGISTRY.gauge("wave.shards").set(n_shards)
    n_forced = len(spec.forced_splits)
    # splits of the strict tail (0: no tail); capped against LB-1 (not
    # num_leaves) so that under overgrow the tail is the endgame of the
    # GROW phase (pruning then trims by gain)
    tail = min(max(spec.wave_strict_tail, 0), LB - 1)
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

    def clamp_output(g, h):
        return leaf_output(g, h, spec.lambda_l1, spec.lambda_l2,
                           spec.max_delta_step)

    axes_all = axis_name if isinstance(axis_name, tuple) else \
        ((axis_name,) if axis_name is not None else None)
    block = axes_all is not None and mode == "data_rs"
    axis_last = axes_all[-1] if axes_all else None
    axes_dcn = axes_all[:-1] if axes_all else ()
    # deterministic fixed-order reduction (ROADMAP 1a): wave histograms
    # fold shard-by-shard around a ring in ascending shard order (the
    # Pallas families chain each shard's kernel sums limb-wise,
    # `ring_ordered_sum`; the XLA families chain the scatter-add itself,
    # which keeps their histograms bitwise the one-pass builders') and
    # the root sums add the shards' own sums in the same order: the same
    # trees in every run, and the serial learner's up to the order of
    # summation.  Single data axis only.
    det = bool(det_reduce) and axes_all is not None \
        and len(axes_all) == 1 and n_shards > 1 and num_data > 0
    if det_reduce and axes_all is not None and not det:
        from ..utils import log
        log.info(f"deterministic_reduce: unsupported topology "
                 f"(mode={mode}, axes={axes_all}, shards={n_shards}, "
                 f"num_data={num_data}) — keeping the tree-psum reduction")
    if block and spec.bundled:
        raise ValueError("EFB bundling requires mode='data' for the "
                         "distributed wave grower (bundle columns do not "
                         "align with per-feature blocks)")
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
             feat: Dict[str, Array],  # per-feature metadata pytree
             allowed: Array,       # [F] bool
             ) -> DeviceTree:
        N = bins_fm.shape[1]
        F = feat["nb"].shape[0]
        with jax.named_scope("payload"):
            payload = jnp.stack([grad * sample_weight,
                                 hess * sample_weight,
                                 sample_weight], axis=1)  # [N, 3]
        mono = feat.get("mono")
        if mono is None:
            mono = jnp.zeros((F,), jnp.int32)

        if spec.bundled:
            expand_bundled, decode_bins = make_bundled_expander(spec, feat)
        else:
            decode_bins = None
        # one routing pass a wave and a speculation (`route_picks`), or
        # one a pick: static facts of the spec and of the bins
        batch_route = not spec.bundled and batched_route_applies(bins_fm)

        def route_picks(leaf_id, live, leaf, f, t, dl, cat, if_left,
                        if_right, fill=None):
            """[N]: the rows of each live pick's leaf take the pick's
            value of their side under its split (column `f`, threshold
            `t`, `dl`), the ONE rule `split.bin_goes_left`; every other
            row `fill` (None: its own `leaf_id`).  [W] arrays a field.
            `cat`: the picks' (`is_cat`, left bins `cat_mask`) where the
            model has categorical columns, and then every pick is handed
            over as its left set; else None."""
            rec = pick_records(live, leaf, f, t, dl, feat["nb"],
                               feat["missing"], if_left, if_right)
            sets = None if cat is None else left_sets(
                f, t, dl, feat["nb"], feat["missing"], *cat)
            if spec.hist_impl in ("pallas", "pallas_q"):
                return route_wave_rows(bins_fm, leaf_id, rec, fill=fill,
                                       interpret=spec.hist_interpret,
                                       sets=sets)
            return route_rows_xla(bins_fm, leaf_id, rec, fill=fill,
                                  sets=sets)

        # the kernel payload carrier is loop-INVARIANT: prepare it once
        # per tree here, not inside every wave's while_loop body (XLA's
        # loop-invariant code motion does not reliably hoist the f32
        # 3-way split / int8 lattice conversion out of the loop)
        if spec.hist_impl == "pallas":
            from .pallas_hist import (LANE, ROW_TILE, _split_payload9,
                                      assert_bins_in_plan, hist_bodies,
                                      pallas_histogram_multi_rows)
            with jax.named_scope("payload"):
                pw_prep = _split_payload9(payload)
                if spec.debug_checks and spec.hist_lane_plan is not None:
                    assert_bins_in_plan(bins_fm, spec.hist_lane_plan)
        elif spec.hist_impl == "pallas_q":
            from .pallas_hist import (
                pallas_histogram_multi_quantized_rows,
                quantized_lattice_rows)
            with jax.named_scope("payload"):
                pw_prep = quantized_lattice_rows(
                    payload, feat["qscales"][0], feat["qscales"][1],
                    debug=spec.debug_checks)
        # data_rs: each shard stores/searches only its feature block
        # (the SAME shared machinery as the strict grower's block path)
        if block:
            Fb, offset, _, bfeat, bmono = make_feature_blocks(
                feat, mono, F, axis_last, n_shards, mode)
        else:
            bfeat, bmono = feat, mono

        # kernel calls by the body that ran (`hist_bodies`): only the f32
        # Pallas kernel has more than one
        no_calls = jnp.zeros((len(hist_bodies())
                              if spec.hist_impl == "pallas" else 0,),
                             jnp.int32)

        def kernel_hist_multi(leaf_id, slots, root=False):
            """This shard's rows through the Pallas kernel: (sums, the
            f32 kernel's calls by the body that ran).  The root pass
            holds every row: it is traced with the full body alone."""
            if spec.hist_impl == "pallas":
                return pallas_histogram_multi_rows(
                    bins_fm, pw_prep, leaf_id, slots, HB,
                    interpret=spec.hist_interpret,
                    plan=spec.hist_lane_plan, count_bodies=True,
                    body="full" if root else None)
            return pallas_histogram_multi_quantized_rows(
                bins_fm, pw_prep, leaf_id, slots, HB,
                feat["qscales"][0], feat["qscales"][1],
                interpret=spec.hist_interpret), no_calls

        if det:
            def det_hist_multi(leaf_id, slots, root):
                """Ring-chained deterministic wave histogram.  On the XLA
                families it is bitwise the serial `hist_multi` (pad rows
                carry leaf_id -1 and match no slot, so they never touch
                live cells); on the Pallas families the kernel stays the
                histogram and its per-shard partials are chained in
                shard order (`ring_ordered_sum`)."""
                Fh = bins_fm.shape[0]
                S = slots.shape[0]
                calls = no_calls
                if spec.hist_impl in ("pallas", "pallas_q"):
                    h, calls = kernel_hist_multi(leaf_id, slots, root)
                    with jax.named_scope("hist_reduce"), \
                            jax.named_scope("ring_fold"):
                        h = ring_ordered_sum(h, axis_last, n_shards)
                elif spec.hist_impl == "packed":
                    chl = spec.packed_const_hess_level

                    def fold(acc):
                        return hist_stream_packed_update(
                            acc, bins_fm, payload, leaf_id, slots, HB,
                            feat["qscales"][0], feat["qscales"][1],
                            const_hess_level=chl)

                    with jax.named_scope("hist_reduce"):
                        full = ring_fold(
                            fold, hist_stream_packed_init(Fh, S, HB, chl),
                            axis_last, n_shards)
                    h = hist_stream_packed_finalize(
                        full, Fh, S, HB, feat["qscales"][0],
                        feat["qscales"][1], const_hess_level=chl)
                else:
                    def fold(acc):
                        return hist_stream_update(acc, bins_fm, payload,
                                                  leaf_id, slots, HB)

                    with jax.named_scope("hist_reduce"):
                        full = ring_fold(fold, hist_stream_init(Fh, S, HB),
                                         axis_last, n_shards)
                    h = hist_stream_finalize(full, Fh, S, HB)
                if block:
                    Fb_h = h.shape[1] // n_shards
                    with jax.named_scope("hist_reduce"):
                        h = jax.lax.dynamic_slice_in_dim(
                            h, jax.lax.axis_index(axis_last) * Fb_h, Fb_h,
                            axis=1)
                return h, calls

        def hist_multi(leaf_id, slots, root=False):
            """([S, F|G|Fb, HB, 6] histograms of the listed leaf slots in
            one batched sweep (both limbs of every sum; [.., 3] from the
            quantized families); pad slots (value LB) yield zeros, this
            shard's kernel calls by body (`no_calls`' shape)).  Under
            data_rs the returned feature axis is this shard's summed
            block (psum_scatter over ICI + psum over DCN)."""
            calls = no_calls
            with jax.named_scope("histogram_wave"):
                if det:
                    return det_hist_multi(leaf_id, slots, root)
                if spec.hist_impl in ("pallas", "pallas_q"):
                    h, calls = kernel_hist_multi(leaf_id, slots, root)
                elif spec.hist_impl == "packed":
                    h = leaf_histogram_packed_multi(
                        bins_fm, payload, leaf_id, slots, HB,
                        feat["qscales"][0], feat["qscales"][1],
                        const_hess_level=spec.packed_const_hess_level)
                else:
                    h = leaf_histogram_multi_limbs(bins_fm, payload,
                                                   leaf_id, slots, HB)
                if block:
                    # ref: Network::ReduceScatter of histogram buffers —
                    # each shard receives the summed feature block it
                    # will scan (over ICI); DCN slices allreduce it
                    with jax.named_scope("hist_reduce"):
                        h = jax.lax.psum_scatter(h, axis_last,
                                                 scatter_dimension=1,
                                                 tiled=True)
                        if axes_dcn:
                            h = jax.lax.psum(h, axes_dcn)
                elif axes_all is not None:
                    with jax.named_scope("hist_reduce"):
                        h = jax.lax.psum(h, axes_all)
            return h, calls

        # per-node column sampling / extra_trees / CEGB pricing — the
        # SAME shared derivations as the strict grower (ops/grow.py), so
        # both policies draw identical per-node samples and price
        # identical candidates identically for the same tree
        bynode_mask, extra_mask = make_node_samplers(spec, feat, F)
        cegb_on, cegb_penalty = make_cegb_penalty(spec, feat, F)
        # per-leaf used-feature tracking feeds interaction constraints
        # and CEGB lazy costs; the state is a [LB, F] plane updated at
        # every committed split (both children inherit path ∪ {f})
        track_used = spec.n_ic_groups > 0 or (cegb_on and spec.cegb_lazy)

        # forced splits (ref: serial_tree_learner.cpp `ForceSplits`) —
        # r5: wave-eligible.  The BFS-ordered prefix runs as WIDTH-1
        # waves (each forced child needs its histogram before the next
        # forced split, exactly strict order — which width-1 waves are),
        # then free growth resumes at full wave width.
        if n_forced:
            forced_leaf, forced_feat, forced_bin = forced_split_arrays(spec)

        def split_of(hist, g, h, c, node_allowed, lb, ub, p_out, nid,
                     penalty=None, cand=None):
            if cand is None:
                na = node_allowed & bynode_mask(nid)
                cm = extra_mask(nid)
            else:
                # forced split: the designated (feature, bin) only,
                # bypassing column sampling / extra_trees (the reference
                # forces before the ColSampler-gated search)
                na = node_allowed
                cm = cand
            if block:
                # block search on this shard's scattered histogram, then
                # SplitInfo allreduce-max (vmapped over the wave's
                # children by the caller) — ref: DataParallelTreeLearner
                # FindBestSplitsFromHistograms + SplitInfo MaxReducer
                na = jax.lax.dynamic_slice_in_dim(na, offset, Fb, axis=0)
                if cm is not None:
                    cm = jax.lax.dynamic_slice_in_dim(cm, offset, Fb,
                                                      axis=0)
                if penalty is not None:
                    penalty = jax.lax.dynamic_slice_in_dim(
                        penalty, offset, Fb, axis=0)
                s = find(hist_value(hist), g, h, c, bfeat["nb"],
                         bfeat["missing"], bfeat["default"], na,
                         bfeat["is_cat"], mono=bmono, out_lb=lb, out_ub=ub,
                         parent_output=p_out, cand_mask=cm,
                         gain_penalty=penalty)
                s = refine_child_sums(s, hist, bfeat["nb"],
                                      bfeat["missing"])
                with jax.named_scope("split_allreduce"):
                    return rebase_and_merge_block_split(s, offset,
                                                        axis_last, n_shards)
            if spec.bundled:
                # bundle columns expand to features by value: no limbs
                hist = expand_bundled(hist_value(hist), g, h, c)
            s = find(hist_value(hist), g, h, c, feat["nb"],
                     feat["missing"], feat["default"], na, feat["is_cat"],
                     mono=mono, out_lb=lb, out_ub=ub, parent_output=p_out,
                     cand_mask=cm, gain_penalty=penalty)
            return refine_child_sums(s, hist, feat["nb"], feat["missing"])

        # ---- root ----
        # the root pass uses the SAME [W]-slot call shape as every wave
        # (pad slots LB match nothing), so exactly ONE multi-kernel block
        # shape is ever compiled/run per spec — the shape the booster's
        # probe gate checks.  leaf_id0 is a compile-time CONSTANT here:
        # without the barrier XLA constant-folds the segment-sum path's
        # [W, N] slot compare + reduce at COMPILE time (observed: 10.3 s
        # fold stall per chunk program at N=100k — BENCH_r03 tail); the
        # barrier trades that for a trivial runtime zeros-fill
        with jax.named_scope("init"):
            if det:
                # pad rows (beyond num_data) start at leaf -1: they match
                # no histogram slot and no partition descriptor, so the
                # det chain never replays a +0.0 the serial program
                # doesn't have
                row0_g = jax.lax.axis_index(axis_last) * N
                det_valid = row0_g + jnp.arange(N) < num_data
                leaf_id0 = jax.lax.optimization_barrier(
                    jnp.where(det_valid, 0, -1).astype(jnp.int32))
            else:
                leaf_id0 = jax.lax.optimization_barrier(
                    jnp.zeros((N,), jnp.int32))
            root_slots = jnp.full((W,), LB, jnp.int32).at[0].set(0)
            if det:
                # deterministic root stats: every shard sums its own rows
                # (pad rows carry payload 0) and the shards' sums are
                # added in ascending shard order, on every shard alike —
                # no reduction tree of the backend's choosing, and no
                # shard ever holds another's rows (the rows of all shards
                # gathered for the serial expression were 12 B a row of
                # the WHOLE table on every chip)
                parts = jax.lax.all_gather(
                    jnp.stack([payload[:, 0].sum(), payload[:, 1].sum(),
                               payload[:, 2].sum()]), axis_last)
                root_g, root_h, root_c = functools.reduce(
                    jnp.add, [parts[i] for i in range(n_shards)])
            else:
                root_g = payload[:, 0].sum()
                root_h = payload[:, 1].sum()
                root_c = payload[:, 2].sum()
                if axes_all is not None:
                    root_g = jax.lax.psum(root_g, axes_all)
                    root_h = jax.lax.psum(root_h, axes_all)
                    root_c = jax.lax.psum(root_c, axes_all)
            root_out = clamp_output(root_g, root_h)
            if spec.n_ic_groups:
                # only features inside a constraint group may ever split
                allowed = allowed & jnp.any(feat["ic_groups"], axis=0)
            root_pen = cegb_penalty(root_c, jnp.zeros((F,), bool))
        hist0, root_calls = hist_multi(leaf_id0, root_slots, root=True)
        hist0 = hist0[0]
        with jax.named_scope("find_split"):
            s0 = split_of(hist0, root_g, root_h, root_c, allowed,
                          jnp.float32(-INF), jnp.float32(INF),
                          root_out, 0, penalty=root_pen)

        with jax.named_scope("init"):
            hist = jnp.zeros((LB,) + hist0.shape, dtype=jnp.float32)\
                .at[0].set(hist0)
            leaf_best = [jnp.zeros((LB,) + a.shape, dtype=a.dtype)
                         .at[0].set(a) for a in _split_to_arrays(s0)]
            leaf_best[0] = jnp.full((LB,), NEG_INF, dtype=jnp.float32)\
                .at[0].set(s0.gain)

            nodes = dict(
                split_leaf=jnp.zeros((LB - 1,), jnp.int32),
                split_feature=jnp.zeros((LB - 1,), jnp.int32),
                threshold_bin=jnp.zeros((LB - 1,), jnp.int32),
                default_left=jnp.zeros((LB - 1,), bool),
                split_is_cat=jnp.zeros((LB - 1,), bool),
                split_cat_mask=jnp.zeros((LB - 1, MB), bool),
                split_gain=jnp.zeros((LB - 1,), jnp.float32),
                internal_g=jnp.zeros((LB - 1,), jnp.float32),
                internal_h=jnp.zeros((LB - 1,), jnp.float32),
                internal_cnt=jnp.zeros((LB - 1,), jnp.float32),
            )

            state = dict(
                step=jnp.int32(0), nl=jnp.int32(1),
                leaf_id=leaf_id0, hist=hist,
                leaf_gain=leaf_best[0], leaf_feat=leaf_best[1],
                leaf_thr=leaf_best[2], leaf_dl=leaf_best[3],
                leaf_lg=leaf_best[4], leaf_lh=leaf_best[5],
                leaf_lc=leaf_best[6], leaf_rg=leaf_best[7],
                leaf_rh=leaf_best[8], leaf_rc=leaf_best[9],
                leaf_iscat=leaf_best[10], leaf_catmask=leaf_best[11],
                leaf_g=jnp.zeros((LB,), jnp.float32).at[0].set(root_g),
                leaf_h=jnp.zeros((LB,), jnp.float32).at[0].set(root_h),
                leaf_c=jnp.zeros((LB,), jnp.float32).at[0].set(root_c),
                leaf_lb=jnp.full((LB,), -INF, jnp.float32),
                leaf_ub=jnp.full((LB,), INF, jnp.float32),
                leaf_out=jnp.zeros((LB,), jnp.float32).at[0]
                .set(root_out),
                leaf_depth=jnp.zeros((LB,), jnp.int32),
                nodes=nodes,
                # histogram passes the waves made (DeviceTree.tail_stats)
                wave_passes=jnp.int32(0),
                # kernel calls by body so far (DeviceTree.hist_calls)
                hist_calls=root_calls,
                # routing passes over the rows, and the picks and slots
                # they routed (the last of DeviceTree.tail_stats); with
                # categorical columns also how many of those were
                # categorical
                route_stats=jnp.zeros((3 if spec.has_cat else 2,),
                                      jnp.int32),
            )
            if track_used:
                state["leaf_used"] = jnp.zeros((LB, F), bool)
            if n_forced:
                # shrinks to `step` if a forced split proves infeasible —
                # abandoning the rest of the prefix (its BFS leaf
                # numbering no longer matches the tree), same as the
                # strict grower
                state["forced_n"] = jnp.int32(n_forced)
            if tail:
                # the strict tail's speculated smaller-child histograms,
                # one per leaf slot (the size of `hist`), valid while the
                # leaf stands unsplit; tail_stats = passes, hits, unused,
                # speculated (the first four of DeviceTree.tail_stats)
                state["spec_hist"] = jnp.zeros_like(hist)
                state["spec_ok"] = jnp.zeros((LB,), bool)
                state["tail_stats"] = jnp.zeros((4,), jnp.int32)

        LEAF_KEYS = ("leaf_gain", "leaf_feat", "leaf_thr", "leaf_dl",
                     "leaf_lg", "leaf_lh", "leaf_lc", "leaf_rg", "leaf_rh",
                     "leaf_rc", "leaf_iscat", "leaf_catmask")

        def cond(st):
            go = jnp.max(st["leaf_gain"]) > 0.0
            if n_forced:
                go = go | (st["step"] < st["forced_n"])
            return (st["step"] < LB - 1) & go

        def in_tail(st):
            """The strict tail has begun: at most `tail` splits of
            capacity are left and no forced split is pending (the forced
            prefix keeps the wave body, at width 1)."""
            strict = LB - st["nl"] <= tail
            if n_forced:
                strict = strict & (st["step"] >= st["forced_n"])
            return strict

        def body(st, small_hists=None):
            """One wave: the pick loop, one histogram pass for the
            picks' smaller children, the children's searches.  With
            `small_hists` [LB, ...] (the strict tail, `tail_body`) it is
            ONE pick whose smaller child's histogram is read from there
            instead of built."""
            strict = small_hists is not None
            # a wave's picks are distinct leaves that were READY at its
            # start and its choices read the per-leaf tables only: the
            # loop records them and ONE pass routes their rows after it.
            # The tail's single pick routes itself (one bin row for all),
            # unless it may be categorical: its look-up in the pass costs
            # a hundredth of a gather in its mask at [N]
            batched = batch_route and (spec.has_cat or not strict)
            # ---- split phase: best-first among READY leaves (leaves
            # created this wave have no histogram yet and wait for the
            # next wave), up to the batch capacity W ----
            carry_keys = ("step", "nl", "nodes", "leaf_g",
                          "leaf_h", "leaf_c", "leaf_lb", "leaf_ub",
                          "leaf_out", "leaf_depth") + \
                (() if batched else ("leaf_id",)) + \
                (("leaf_used",) if track_used else ()) + \
                (("forced_n",) if n_forced else ())
            istate = {k: st[k] for k in carry_keys + LEAF_KEYS}
            if n_forced:
                # the forced evaluation searches the designated leaf's
                # stored histogram inside the pick loop (read-only ride)
                istate["hist"] = st["hist"]
            istate["ready"] = jnp.arange(LB) < st["nl"]
            istate["w"] = jnp.int32(0)
            # hybrid wave/strict schedule (spec.wave_strict_tail): the
            # last `tail` splits of capacity are made in strict
            # best-first order, one pick at a time with the children
            # re-searched before the next pick (`tail_body`).  The wave
            # that CROSSES the boundary is clipped to `remaining - tail`
            # so the promised strict endgame is never consumed by a wide
            # boundary wave.  A forced split still pending at the
            # boundary is made here at width 1: one pick, then its pass.
            if strict:
                istate["wcap"] = jnp.int32(1)
            elif tail:
                istate["wcap"] = jnp.clip(LB - st["nl"] - tail, 1, W)\
                    .astype(jnp.int32)
            else:
                istate["wcap"] = jnp.int32(W)
            # (forced prefix: no wcap pinning here — a pending forced
            # split is gated INSIDE icond to the wave's first pick, so
            # the wave that commits the LAST forced split continues into
            # free picks at full width instead of burning a whole
            # histogram pass on width 1)
            # per-wave pair records; pad slot LB drops out of every scatter
            istate["p_small"] = jnp.full((W,), LB, jnp.int32)
            istate["p_left"] = jnp.full((W,), LB, jnp.int32)
            istate["p_new"] = jnp.full((W,), LB, jnp.int32)
            istate["p_step"] = jnp.zeros((W,), jnp.int32)
            # depth bias (wave_gain_ratio): the wave stops early once the
            # best remaining ready gain falls below the floor — weaker
            # leaves wait for a later wave, so capacity flows to deep
            # high-gain branches like the strict policy allocates it.
            # The floor is CAPACITY-AWARE: ratio x opening gain x
            # (leaves used / num_leaves), so early waves (capacity
            # plentiful — splitting weak leaves costs nothing yet) run at
            # full width and only the late, capacity-scarce waves become
            # selective.
            istate["g_floor"] = jnp.float32(0.0)
            fullness = st["nl"].astype(jnp.float32) / LB

            def icond(s):
                rg = jnp.where(s["ready"], s["leaf_gain"], NEG_INF)
                go = jnp.max(rg) > jnp.maximum(s["g_floor"], 0.0)
                if n_forced:
                    # forced splits come strictly first (BFS prefix), and
                    # a forced pick needs its leaf's WAVE-START histogram
                    # (the next forced target is a child created by this
                    # very pick), so: while one is pending, only the
                    # wave's first pick runs; after the last forced
                    # commit `pending` flips off and free picks continue
                    # in the SAME wave under the normal gain gate
                    pending = s["step"] < s["forced_n"]
                    go = jnp.where(pending, s["w"] == 0, go)
                return (s["w"] < s["wcap"]) & (s["step"] < LB - 1) & go

            def ibody(s):
                step = s["step"]
                new = step + 1           # nl == step + 1 invariant
                rg = jnp.where(s["ready"], s["leaf_gain"], NEG_INF)
                free_best = jnp.argmax(rg).astype(jnp.int32)
                if n_forced:
                    # evaluate the designated (feature, bin) on ITS
                    # leaf's stored histogram — same semantics (and the
                    # same no-penalty, sampling-bypassing search) as the
                    # strict grower's forced prefix
                    idx = jnp.clip(step, 0, n_forced - 1)
                    active_forced = step < s["forced_n"]

                    def eval_forced(_):
                        fl = forced_leaf[idx]
                        cand = jnp.zeros((F, MB), bool)\
                            .at[forced_feat[idx], forced_bin[idx]]\
                            .set(True)
                        with jax.named_scope("find_split"):
                            fs = split_of(
                                s["hist"][fl], s["leaf_g"][fl],
                                s["leaf_h"][fl], s["leaf_c"][fl],
                                allowed.at[forced_feat[idx]].set(True),
                                s["leaf_lb"][fl], s["leaf_ub"][fl],
                                s["leaf_out"][fl], 0, cand=cand)
                        return _split_to_arrays(fs)

                    fa = jax.lax.cond(active_forced, eval_forced,
                                      lambda _: empty_split_arrays(MB),
                                      None)
                    forced_ok = active_forced & jnp.isfinite(fa[0])
                    best = jnp.where(forced_ok, forced_leaf[idx],
                                     free_best)
                    forced_n_new = jnp.where(active_forced & ~forced_ok,
                                             step, s["forced_n"])
                else:
                    best = free_best
                stored = tuple(s[k][best] for k in LEAF_KEYS)
                if n_forced:
                    chosen = tuple(jnp.where(forced_ok, a, b)
                                   for a, b in zip(fa, stored))
                else:
                    chosen = stored
                (gain_s, f, t, dl, lg, lh, lc, rg_, rh, rc, node_cat,
                 node_mask) = chosen

                nodes = s["nodes"]
                nodes = dict(
                    split_leaf=nodes["split_leaf"].at[step].set(best),
                    split_feature=nodes["split_feature"].at[step].set(f),
                    threshold_bin=nodes["threshold_bin"].at[step].set(t),
                    default_left=nodes["default_left"].at[step].set(dl),
                    split_is_cat=nodes["split_is_cat"].at[step]
                    .set(node_cat),
                    split_cat_mask=nodes["split_cat_mask"].at[step]
                    .set(node_mask),
                    split_gain=nodes["split_gain"].at[step].set(gain_s),
                    internal_g=nodes["internal_g"].at[step]
                    .set(s["leaf_g"][best]),
                    internal_h=nodes["internal_h"].at[step]
                    .set(s["leaf_h"][best]),
                    internal_cnt=nodes["internal_cnt"].at[step]
                    .set(s["leaf_c"][best]),
                )

                def put2(arr, a, b):
                    return arr.at[best].set(a).at[new].set(b)

                # ---- child outputs: smoothing → monotone basic clamp ----
                lb, ub = s["leaf_lb"][best], s["leaf_ub"][best]
                parent_out = s["leaf_out"][best]
                mc_f = jnp.where(node_cat, 0, mono[f])
                l_sm = smooth_output(clamp_output(lg, lh), lc, parent_out,
                                     spec.path_smooth)
                r_sm = smooth_output(clamp_output(rg_, rh), rc, parent_out,
                                     spec.path_smooth)
                (l_fin, r_fin, l_lb, l_ub, r_lb, r_ub) = \
                    child_bounds_basic(mc_f, l_sm, r_sm, lb, ub)

                left_smaller = lc <= rc
                small = jnp.where(left_smaller, best, new)
                depth = s["leaf_depth"][best] + 1

                floor_w0 = jnp.float32(spec.wave_gain_ratio) * gain_s \
                    * fullness
                if n_forced:
                    # a forced first pick must not seed the capacity-aware
                    # floor — its gain is whatever the designated split
                    # scores, not the wave's best free gain; leave the
                    # floor open (wave-start 0) so the free picks that
                    # follow in this wave aren't throttled by it
                    floor_w0 = jnp.where(forced_ok, s["g_floor"],
                                         floor_w0)

                out = dict(s)
                if not batched:
                    # ---- partition (shared decode with the strict
                    # grower) ----
                    go_left = split_go_left(spec, feat, bins_fm,
                                            decode_bins, f, t, dl,
                                            node_cat, node_mask)
                    out["leaf_id"] = jnp.where(
                        (s["leaf_id"] == best) & ~go_left, new,
                        s["leaf_id"])
                if track_used:
                    # both children share the path's used set ∪ {f}
                    child_used = s["leaf_used"][best].at[f].set(True)
                    out["leaf_used"] = s["leaf_used"].at[best]\
                        .set(child_used).at[new].set(child_used)
                if n_forced:
                    out["forced_n"] = forced_n_new
                out.update(
                    step=step + 1, nl=new + 1,
                    nodes=nodes, w=s["w"] + 1,
                    g_floor=jnp.where(s["w"] == 0, floor_w0,
                                      s["g_floor"]),
                    ready=s["ready"].at[best].set(False)
                    .at[new].set(False),
                    p_small=s["p_small"].at[s["w"]].set(small),
                    p_left=s["p_left"].at[s["w"]].set(best),
                    p_new=s["p_new"].at[s["w"]].set(new),
                    p_step=s["p_step"].at[s["w"]].set(step),
                    leaf_gain=put2(s["leaf_gain"], NEG_INF, NEG_INF),
                    leaf_g=put2(s["leaf_g"], lg, rg_),
                    leaf_h=put2(s["leaf_h"], lh, rh),
                    leaf_c=put2(s["leaf_c"], lc, rc),
                    leaf_lb=put2(s["leaf_lb"], l_lb, r_lb),
                    leaf_ub=put2(s["leaf_ub"], l_ub, r_ub),
                    leaf_out=put2(s["leaf_out"], l_fin, r_fin),
                    leaf_depth=put2(s["leaf_depth"], depth, depth),
                )
                if n_forced:
                    # if neither the forced split nor the free best is
                    # applicable (both infeasible), keep the state
                    # untouched — the shrunken forced_n flips icond's
                    # forced clause off so the pick loop exits or moves
                    # on cleanly (mirrors the strict grower's apply_ok
                    # mask; without it this iteration would commit a
                    # gain=-inf split with zero child stats → NaN leaf
                    # outputs)
                    apply_ok = forced_ok | (gain_s > 0.0)
                    hist_ride = out.pop("hist")   # read-only: keep out
                    fallback = {**s, "forced_n": forced_n_new}
                    fallback.pop("hist")          # of the select
                    out = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(apply_ok, a, b),
                        out, fallback)
                    out["hist"] = hist_ride
                return out

            with jax.named_scope("partition"):
                # the tail's loop condition IS icond at w == 0: one pick
                s1 = ibody(istate) if strict else \
                    jax.lax.while_loop(icond, ibody, istate)
                # a slot whose pick was not applied (the wave ended, a
                # forced split failed) kept the pad leaf LB: no rows
                live = s1["p_left"] < LB
                picks = jnp.sum(live, dtype=jnp.int32)
                at = s1["p_step"]
                if batched:
                    s1["leaf_id"] = route_picks(
                        st["leaf_id"], live, s1["p_left"],
                        s1["nodes"]["split_feature"][at],
                        s1["nodes"]["threshold_bin"][at],
                        s1["nodes"]["default_left"][at],
                        (s1["nodes"]["split_is_cat"][at],
                         s1["nodes"]["split_cat_mask"][at])
                        if spec.has_cat else None,
                        s1["p_left"], s1["p_new"])
                routed = [jnp.int32(1) if batched else picks, picks]
                if spec.has_cat:
                    routed.append(jnp.sum(
                        live & s1["nodes"]["split_is_cat"][at],
                        dtype=jnp.int32))
                routed = jnp.stack(routed)

            def hist_and_find(_):
                # ---- histogram phase: ONE batched pass for all smaller
                # children; larger children by subtraction (the parent
                # histogram still lives in the left child's slot) ----
                calls = no_calls
                if strict:
                    # speculated for the leaf that was just split, under
                    # the very split it was split by; pad slots gather
                    # junk that every scatter below drops
                    with jax.named_scope("hist_cache"):
                        small_h = small_hists[
                            jnp.clip(s1["p_left"], 0, LB - 1)]
                else:
                    small_h, calls = hist_multi(s1["leaf_id"],
                                                s1["p_small"])
                with jax.named_scope("hist_cache"):
                    parents = st["hist"][jnp.clip(s1["p_left"], 0, LB - 1)]
                    large_h = hist_sub(parents, small_h)
                    p_large = jnp.where(s1["p_small"] == s1["p_left"],
                                        s1["p_new"], s1["p_left"])
                    hist = st["hist"].at[s1["p_small"]]\
                        .set(small_h, mode="drop")
                    hist = hist.at[p_large].set(large_h, mode="drop")

                # ---- find phase: best splits of the new children ----
                with jax.named_scope("find_split"):
                    child_slots = jnp.concatenate([s1["p_left"],
                                                   s1["p_new"]])
                    node_ids = jnp.concatenate([2 * s1["p_step"] + 1,
                                                2 * s1["p_step"] + 2])

                    def eval_child(slot, nid):
                        sl = jnp.clip(slot, 0, LB - 1)
                        g, h, c = s1["leaf_g"][sl], s1["leaf_h"][sl], \
                            s1["leaf_c"][sl]
                        deep_ok = (spec.max_depth <= 0) | \
                            (s1["leaf_depth"][sl] < spec.max_depth)
                        lu = s1["leaf_used"][sl] if track_used \
                            else jnp.zeros((F,), bool)
                        a = allowed & deep_ok
                        if spec.n_ic_groups:
                            a = a & ic_allowed_from_used(feat, lu)
                        sr = split_of(hist[sl], g, h, c, a,
                                      s1["leaf_lb"][sl], s1["leaf_ub"][sl],
                                      s1["leaf_out"][sl], nid,
                                      penalty=cegb_penalty(c, lu))
                        return _split_to_arrays(sr)

                    res = jax.vmap(eval_child)(child_slots, node_ids)
                    return hist, tuple(
                        s1[k].at[child_slots].set(r, mode="drop")
                        for k, r in zip(LEAF_KEYS, res)), calls

            def tree_full(_):
                # capacity reached mid-wave: the children can never be
                # split, so skip the whole histogram pass + find fan-out
                # (one full-data pass saved on every capacity-bound tree)
                return st["hist"], tuple(s1[k] for k in LEAF_KEYS), no_calls

            hist, leaf_upd, calls = jax.lax.cond(
                s1["step"] >= LB - 1, tree_full, hist_and_find, None)

            new_state = {**st, **{k: s1[k] for k in carry_keys}}
            new_state["leaf_id"] = s1["leaf_id"]
            new_state["route_stats"] = st["route_stats"] + routed
            if not strict:
                new_state["wave_passes"] = st["wave_passes"] + \
                    (s1["step"] < LB - 1).astype(jnp.int32)
            new_state["hist"] = hist
            new_state["hist_calls"] = st["hist_calls"] + calls
            for k, v in zip(LEAF_KEYS, leaf_upd):
                new_state[k] = v
            return new_state

        def speculate(st):
            """The tail's histogram pass, made BEFORE the split it is
            for.  Every leaf carries its best split, so the rows of its
            would-be smaller child are known before it is picked, and
            they stay the same until it is: the pass the best leaf needs
            also fills the kernel's other W-1 slots with the smaller
            children of the next-best leaves that have no histogram yet.
            A slot's sums depend on its own rows only, so each is bit
            for bit the histogram a pass after the split would build."""
            with jax.named_scope("partition"):
                # (slots no leaf has reached yet carry gain -inf; every
                # leaf of the tail is ready.)  Ties go to the lower leaf,
                # as in the pick's argmax: the best leaf is always chosen
                top_gain, top_leaf = jax.lax.top_k(
                    jnp.where(st["spec_ok"], NEG_INF, st["leaf_gain"]), W)
                chosen = top_gain > 0.0
                small_is_left = st["leaf_lc"][top_leaf] \
                    <= st["leaf_rc"][top_leaf]

                if batch_route:
                    # the wave's pass with another value a side: slot k
                    # on the smaller side, no slot elsewhere
                    k = jnp.arange(W, dtype=jnp.int32)
                    slot_of_row = route_picks(
                        st["leaf_id"], chosen, top_leaf,
                        st["leaf_feat"][top_leaf], st["leaf_thr"][top_leaf],
                        st["leaf_dl"][top_leaf],
                        (st["leaf_iscat"][top_leaf],
                         st["leaf_catmask"][top_leaf])
                        if spec.has_cat else None,
                        jnp.where(small_is_left, k, -1),
                        jnp.where(small_is_left, -1, k), fill=-1)
                else:
                    def fill_slot(k, slot_of_row):
                        # the partition's own routing and the pick's own
                        # smaller-side rule, under the leaf's stored split
                        lf = top_leaf[k]
                        go_left = split_go_left(
                            spec, feat, bins_fm, decode_bins,
                            st["leaf_feat"][lf], st["leaf_thr"][lf],
                            st["leaf_dl"][lf], st["leaf_iscat"][lf],
                            st["leaf_catmask"][lf])
                        return jnp.where(
                            chosen[k] & (st["leaf_id"] == lf)
                            & (go_left == small_is_left[k]), k, slot_of_row)

                    # one slot at a time: one [N] routing mask alive, not W
                    slot_of_row = jax.lax.fori_loop(
                        0, W, fill_slot, jnp.full((N,), -1, jnp.int32))
            small_h, calls = hist_multi(slot_of_row,
                                        jnp.arange(W, dtype=jnp.int32))
            with jax.named_scope("hist_cache"):
                dst = jnp.where(chosen, top_leaf, LB)
                # (a model without categorical columns counts none: it
                # runs the program it ran before the count existed)
                n_cat = (jnp.sum(chosen & st["leaf_iscat"][top_leaf],
                                 dtype=jnp.int32),) if spec.has_cat else ()
                return (st["spec_hist"].at[dst].set(small_h, mode="drop"),
                        st["spec_ok"].at[dst].set(True, mode="drop"),
                        jnp.sum(chosen, dtype=jnp.int32), calls) + n_cat

        def tail_body(st):
            """One split of the strict tail: a histogram pass only if
            the best leaf's smaller child has no speculated histogram
            yet (and its children could still be split), then the pick
            and the children's searches from the cache."""
            with jax.named_scope("partition"):
                best = jnp.argmax(st["leaf_gain"])   # the pick's choice
                # this split fills the tree: its children need no
                # histogram, whatever the cache holds
                fills = st["step"] + 1 >= LB - 1
                held = st["spec_ok"][best]
                miss = ~held & ~fills
            spec_hist, spec_ok, n_spec, calls, *n_cat = jax.lax.cond(
                miss, speculate,
                lambda st: (st["spec_hist"], st["spec_ok"], jnp.int32(0),
                            no_calls) + (jnp.int32(0),) * spec.has_cat,
                st)
            # the pick rewrites `leaf_id`, which the pass reads: tie the
            # pick behind the pass, or XLA keeps both orders open and
            # copies the [N] ids every split
            leaf_id, spec_hist = jax.lax.optimization_barrier(
                (st["leaf_id"], spec_hist))
            # a speculating pass routes W slots: in one pass, or in W
            spec_routed = miss.astype(jnp.int32) * jnp.array(
                [1 if batch_route else W, W], jnp.int32)
            if spec.has_cat:
                spec_routed = jnp.concatenate([spec_routed,
                                               n_cat[0][None]])
            new_state = body({**st, "leaf_id": leaf_id,
                              "hist_calls": st["hist_calls"] + calls,
                              "route_stats": st["route_stats"]
                              + spec_routed},
                             small_hists=spec_hist)
            # the split leaf's two children are new leaves with no entry
            new_state["spec_hist"] = spec_hist
            new_state["spec_ok"] = spec_ok.at[best].set(False)
            new_state["tail_stats"] = st["tail_stats"] + jnp.stack([
                miss, held & ~fills, held & fills, n_spec])\
                .astype(jnp.int32)
            return new_state

        # the waves stop where the strict tail begins
        st = jax.lax.while_loop(
            (lambda st: cond(st) & ~in_tail(st)) if tail else cond,
            body, state)
        if tail:
            st = jax.lax.while_loop(cond, tail_body, st)
            # entries still standing were never used
            tail_stats = st["tail_stats"].at[2].add(
                jnp.sum(st["spec_ok"], dtype=jnp.int32))
        else:
            tail_stats = jnp.zeros((4,), jnp.int32)
        tail_stats = jnp.concatenate([tail_stats, st["wave_passes"][None],
                                      st["route_stats"]])
        hist_calls = None
        if spec.hist_impl == "pallas":
            # and the 128-row groups those calls contracted (int32 holds
            # a tree's groups where it would not hold its rows)
            groups_a_call = jnp.array(
                [-(-N // ROW_TILE) * rows // LANE
                 for _, rows in hist_bodies()], jnp.int32)
            hist_calls = jnp.concatenate(
                [st["hist_calls"],
                 jnp.sum(st["hist_calls"] * groups_a_call)[None]])
            if axes_all is not None:         # every shard's calls
                hist_calls = jax.lax.psum(hist_calls, axes_all)

        if LB > L:
            with jax.named_scope("prune"):
                nodes_f, leaves_f, leaf_id_f, n_splits = prune_wave_tail(
                    st, LB=LB, L=L, n_forced=n_forced,
                    clamp_output=clamp_output)
            nl_f = n_splits + 1
            slot = jnp.arange(L)
            active = slot < nl_f
            values = jnp.where(active & (nl_f > 1), leaves_f["out"], 0.0)
            return DeviceTree(
                n_splits=n_splits,
                leaf_value=values,
                leaf_g=leaves_f["g"], leaf_h=leaves_f["h"],
                leaf_cnt=leaves_f["c"],
                leaf_id=leaf_id_f,
                tail_stats=tail_stats, hist_calls=hist_calls,
                **nodes_f,
            )

        n_splits = st["step"]
        slot = jnp.arange(L)
        active = slot < st["nl"]
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
            leaf_g=st["leaf_g"], leaf_h=st["leaf_h"],
            leaf_cnt=st["leaf_c"],
            leaf_id=st["leaf_id"],
            tail_stats=tail_stats, hist_calls=hist_calls,
        )

    return jax.jit(grow)


def prune_wave_tail(st, *, LB, L, n_forced, clamp_output):
    """Prune the LB-leaf wave tree back to L leaves (classic
    grow-then-prune): iteratively remove the lowest-gain split whose
    both children are leaves, restore each pruned parent's leaf
    stats/output from its recorded node sums, then compact the split
    log to [L-1] — preserving the DeviceTree encoding invariant
    (right child of split k = leaf slot k+1) by renumbering slots.

    Only reachable with monotone constraints and path smoothing OFF
    (the booster gates `wave_overgrow`): a restored parent's output
    is the plain closed form of its (g, h) sums.

    Module-level (closure-free) so the streaming engine's host-driven
    finalize program can reuse it verbatim — the in-memory and streamed
    growers must prune identically for byte-identity to hold.
    """
    nd = st["nodes"]
    n = st["step"]
    idx = jnp.arange(LB - 1)
    sl = nd["split_leaf"]
    target = jnp.minimum(n, L - 1)

    # forced splits are NEVER prune candidates — the forced-split
    # contract outranks gain-based pruning.  They occupy the BFS
    # prefix (indices < the applied forced count), clamped to the
    # prune target so an absurdly deep forced chain cannot make the
    # prune loop unsatisfiable.
    if n_forced:
        forced_floor = jnp.minimum(st["forced_n"], target)
    else:
        forced_floor = jnp.int32(0)

    def pcond(ps):
        return ps["n_alive"] > target

    def pbody(ps):
        alive = ps["alive"]
        # split i's children are both leaves iff no LATER alive
        # split targets its left slot (sl[i]) or right slot (i+1)
        later = alive[None, :] & (idx[None, :] > idx[:, None])
        hit = (sl[None, :] == sl[:, None]) \
            | (sl[None, :] == idx[:, None] + 1)
        removable = alive & ~jnp.any(later & hit, axis=1) \
            & (idx >= forced_floor)
        cand = jnp.where(removable, nd["split_gain"], jnp.inf)
        r = jnp.argmin(cand).astype(jnp.int32)
        b = sl[r]
        # the parent becomes a leaf again — restore from node sums
        return dict(
            alive=alive.at[r].set(False),
            n_alive=ps["n_alive"] - 1,
            leaf_out=ps["leaf_out"].at[b].set(
                clamp_output(nd["internal_g"][r],
                             nd["internal_h"][r])),
            leaf_g=ps["leaf_g"].at[b].set(nd["internal_g"][r]),
            leaf_h=ps["leaf_h"].at[b].set(nd["internal_h"][r]),
            leaf_c=ps["leaf_c"].at[b].set(nd["internal_cnt"][r]),
        )

    ps = jax.lax.while_loop(pcond, pbody, dict(
        alive=idx < n, n_alive=n, leaf_out=st["leaf_out"],
        leaf_g=st["leaf_g"], leaf_h=st["leaf_h"],
        leaf_c=st["leaf_c"]))
    alive = ps["alive"]

    # ---- compact the log: new index k <- old index old_of_new[k] ----
    new_idx = jnp.cumsum(alive.astype(jnp.int32)) - 1         # [LB-1]
    old_of_new = jnp.zeros((L - 1,), jnp.int32)\
        .at[jnp.where(alive, new_idx, L)].set(idx, mode="drop")
    # big slot s survives iff s == 0 or its creator split is alive;
    # otherwise its rows belong to the nearest surviving ancestor
    slot_alive = jnp.concatenate([jnp.ones((1,), bool), alive])
    parent_slot = jnp.concatenate([jnp.zeros((1,), jnp.int32), sl])

    def resolve(_, t):
        return jnp.where(slot_alive[t], t, parent_slot[t])

    anc = jax.lax.fori_loop(0, LB, resolve,
                            jnp.arange(LB, dtype=jnp.int32))   # [LB]
    new_slot = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), new_idx + 1])[anc]        # [LB]

    def g(a):
        return a[old_of_new]

    n_splits = target
    valid = jnp.arange(L - 1) < n_splits
    nodes_f = dict(
        split_leaf=jnp.where(valid, new_slot[g(sl)], 0),
        split_feature=jnp.where(valid, g(nd["split_feature"]), 0),
        threshold_bin=jnp.where(valid, g(nd["threshold_bin"]), 0),
        default_left=jnp.where(valid, g(nd["default_left"]), False),
        split_is_cat=jnp.where(valid, g(nd["split_is_cat"]), False),
        split_cat_mask=jnp.where(valid[:, None],
                                 g(nd["split_cat_mask"]), False),
        split_gain=jnp.where(valid, g(nd["split_gain"]), 0.0),
        internal_g=jnp.where(valid, g(nd["internal_g"]), 0.0),
        internal_h=jnp.where(valid, g(nd["internal_h"]), 0.0),
        internal_cnt=jnp.where(valid, g(nd["internal_cnt"]), 0.0),
    )
    # final leaf slot k: big slot 0 for k = 0, else the right child
    # of the kept split with new index k-1
    big_of = jnp.zeros((L,), jnp.int32)\
        .at[jnp.where(alive, new_idx + 1, L)].set(idx + 1,
                                                  mode="drop")
    leaves_f = dict(out=ps["leaf_out"][big_of],
                    g=ps["leaf_g"][big_of],
                    h=ps["leaf_h"][big_of],
                    c=ps["leaf_c"][big_of])
    leaf_id_f = new_slot[st["leaf_id"]]
    return nodes_f, leaves_f, leaf_id_f, n_splits
