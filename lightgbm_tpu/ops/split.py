"""Vectorized best-split finding over (feature, threshold) grids.

TPU-native re-design of the reference's split finder
(ref: src/treelearner/feature_histogram.hpp
`FeatureHistogram::FindBestThresholdNumerical` [fwd+bwd missing-direction
scans], `FindBestThresholdCategorical` [one-vs-rest for few categories,
sorted many-vs-rest by grad/hess ratio with cat_smooth/cat_l2 otherwise],
`GetSplitGains`, `CalculateSplittedLeafOutput`, `GetLeafGain`;
src/treelearner/cuda/cuda_best_split_finder.cu `FindBestSplitsForLeafKernel`).

The reference scans each feature's bins serially (two missing-direction
scans for numerical; two sorted-prefix scans for categorical).  Here all
scans are one vectorized computation: cumulative sums along the bin axis
give every candidate partition in parallel, the gain formula is evaluated
over the whole [case, F, MB] grid, and a single flat argmax (first-wins,
matching `SplitInfo` deterministic tie-break order) picks the winner:

  case 0: numerical, missing right      case 3: categorical asc-prefix
  case 1: numerical, missing left       case 4: categorical desc-prefix
  case 2: categorical one-vs-rest

The categorical candidate set is upstream's (`FindBestThresholdCategorical
Inner`): a bin is admitted with at least `cat_smooth` rows; at most
`max_cat_to_onehot` admitted bins are tried one against the rest, more are
sorted by `sum_gradient / (sum_hessian + cat_smooth)` and the prefixes of
1 .. min(`max_cat_threshold`, (admitted + 1) // 2) bins are tried from each
end; walking a direction, a prefix is a candidate only where both sides
pass the leaf gates, the right side keeps `min_data_per_group` rows and the
rows gained since the last candidate are at least `min_data_per_group`
(the count starts again after each candidate).  Stated departures
(COVERAGE.md, `perfbench/configs/airline13-lgbcat-l255.json`): the
histogram's exact row counts stand where upstream estimates a count from
the hessian sum; a one-vs-rest candidate's gain carries `cat_l2` too;
leaf values use `lambda_l2` alone; and bin 0 (this build's "other/rare +
missing" categorical bin) is never placed in the left subset, so unseen
categories and NaN always route right, which keeps bin-level training
decisions and raw-value bitset prediction exactly consistent.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..analysis.contracts import contract

Array = jax.Array

NEG_INF = -jnp.inf

# missing_type codes (must match utils/binning.py)
MISSING_NONE, MISSING_ZERO, MISSING_NAN = 0, 1, 2


class SplitResult(NamedTuple):
    """Best split for one leaf (ref: src/treelearner/split_info.hpp
    `SplitInfo` — the fixed-layout struct the reference Allreduces; here a
    NamedTuple of fixed-shape arrays so it pmax/psums cleanly over a mesh)."""
    gain: Array          # f32; -inf when no valid split
    feature: Array       # i32
    threshold_bin: Array  # i32; numerical: left iff bin <= threshold_bin
    default_left: Array  # bool; missing direction
    is_cat: Array        # bool; categorical split
    cat_mask: Array      # [MB] bool; categorical: left iff mask[bin]
    left_sum_g: Array
    left_sum_h: Array
    left_cnt: Array
    right_sum_g: Array
    right_sum_h: Array
    right_cnt: Array


def threshold_l1(s: Array, l1: float) -> Array:
    """ref: feature_histogram.hpp `ThresholdL1`."""
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_gain(g: Array, h: Array, l1: float, l2: float) -> Array:
    """ref: feature_histogram.hpp `GetLeafGain` (w/o path smoothing)."""
    t = threshold_l1(g, l1)
    denom = h + l2
    return jnp.where(denom > 0, t * t / jnp.where(denom > 0, denom, 1.0), 0.0)


def leaf_output(g: Array, h: Array, l1: float, l2: float,
                max_delta_step: float = 0.0) -> Array:
    """ref: feature_histogram.hpp `CalculateSplittedLeafOutput`."""
    denom = h + l2
    out = jnp.where(denom > 0,
                    -threshold_l1(g, l1) / jnp.where(denom > 0, denom, 1.0),
                    0.0)
    if max_delta_step > 0.0:
        out = jnp.clip(out, -max_delta_step, max_delta_step)
    return out


def smooth_output(out: Array, cnt: Array, parent_out: Array,
                  path_smooth: float) -> Array:
    """Path smoothing: shrink a node's output toward its parent's
    (ref: feature_histogram.hpp `CalculateSplittedLeafOutput` under
    USE_SMOOTHING — `w * n/(n+λ_path) + w_parent * λ_path/(n+λ_path)`)."""
    if path_smooth <= 0.0:
        return out
    frac = cnt / (cnt + path_smooth)
    return out * frac + parent_out * (1.0 - frac)


def size_constraints_ok(left: Array, right: Array,
                        min_data_in_leaf: float,
                        min_sum_hessian: float) -> Array:
    """Child-size gate (ref: feature_histogram.hpp the min_data_in_leaf /
    min_sum_hessian_in_leaf guards in both threshold finders)."""
    return ((left[..., 2] >= min_data_in_leaf)
            & (right[..., 2] >= min_data_in_leaf)
            & (left[..., 1] >= min_sum_hessian)
            & (right[..., 1] >= min_sum_hessian))


def plain_split_gain(left: Array, right: Array, l1: float, l2_eff: float,
                     shift: Array) -> Array:
    """Closed-form split gain `GetLeafGain(l) + GetLeafGain(r) - shift`
    (ref: feature_histogram.hpp `GetSplitGains` without constraints)."""
    return (leaf_gain(left[..., 0], left[..., 1], l1, l2_eff)
            + leaf_gain(right[..., 0], right[..., 1], l1, l2_eff)
            - shift)


@contract(hist="[F, MB, 3] f32",
          parent_g="[] float", parent_h="[] float", parent_c="[] float",
          feat_nb="[F] int", feat_missing="[F] int", feat_default="[F] int",
          allowed="[F] bool", is_cat="[F] bool",
          l1="static", l2="static",
          min_data_in_leaf="static", min_sum_hessian="static",
          min_gain_to_split="static",
          cat_smooth="static", cat_l2="static",
          max_cat_threshold="static int", max_cat_to_onehot="static int",
          max_delta_step="static", min_data_per_group="static",
          mono="[F] int?", out_lb="[] float?", out_ub="[] float?",
          path_smooth="static",
          parent_output="[] float?",
          cand_mask="[F, MB] bool?",
          gain_penalty="[F] float?",
          want_feature_gains="static", has_cat="static",
          ret="tree")
def find_best_split(hist: Array,
                    parent_g: Array, parent_h: Array, parent_c: Array,
                    feat_nb: Array, feat_missing: Array, feat_default: Array,
                    allowed: Array, is_cat: Array,
                    l1: float, l2: float,
                    min_data_in_leaf: float, min_sum_hessian: float,
                    min_gain_to_split: float,
                    cat_smooth: float, cat_l2: float,
                    max_cat_threshold: int, max_cat_to_onehot: int,
                    max_delta_step: float = 0.0,
                    mono: Array = None, out_lb: Array = None,
                    out_ub: Array = None,
                    path_smooth: float = 0.0,
                    parent_output: Array = None,
                    cand_mask: Array = None,
                    gain_penalty: Array = None,
                    want_feature_gains: bool = False,
                    has_cat: bool = True,
                    min_data_per_group: float = 0.0):
    """Best split over all features of one leaf (numerical + categorical).

    `mono` [F] in {-1, 0, +1} plus scalar leaf output bounds [out_lb, out_ub]
    implement the reference's "basic" monotone method (ref:
    feature_histogram.hpp under USE_MC + monotone_constraints.hpp
    `BasicLeafConstraints`): candidate child outputs are clamped to the
    leaf's bounds, splits whose clamped outputs violate the feature's
    direction are masked, and the gain of constrained candidates uses the
    given-output form `-(2·ThresholdL1(g)·w + (h+λ₂)·w²)` — which equals the
    closed form when no clamping binds, so unconstrained training is
    bit-identical to passing mono=0.

    `path_smooth` > 0 shrinks candidate child outputs toward
    `parent_output` (ref: USE_SMOOTHING paths in feature_histogram.hpp).
    `cand_mask` [F, MB] restricts the candidate grid (forced splits).

    `has_cat=False` (static) promises every feature is numerical and
    skips the categorical cases entirely — four [F, MB] argsorts plus
    three gain grids per call; callers with a static feature inventory
    (the growers) thread it from their spec.

    `min_data_per_group` (static) is the categorical group gate of the
    module docstring; 0 gates nothing.
    """
    F, MB, _ = hist.shape
    bin_ar = jnp.arange(MB, dtype=jnp.int32)
    valid_bin = bin_ar[None, :] < feat_nb[:, None]              # [F, MB]
    h = jnp.where(valid_bin[..., None], hist, 0.0)
    parent = jnp.stack([parent_g, parent_h, parent_c])           # [3]
    num_ok = allowed & ~is_cat
    cat_ok = allowed & is_cat
    if mono is None:
        mono = jnp.zeros((F,), jnp.int32)
    lb = jnp.float32(-jnp.inf) if out_lb is None else out_lb
    ub = jnp.float32(jnp.inf) if out_ub is None else out_ub
    p_out = jnp.float32(0.0) if parent_output is None else parent_output

    def constraints_ok(left, right):
        return size_constraints_ok(left, right,
                                   min_data_in_leaf, min_sum_hessian)

    def split_gain(left, right, l2_eff, shift):
        return plain_split_gain(left, right, l1, l2_eff, shift)

    def gain_given_output(side, out, l2_eff):
        # ref: feature_histogram.hpp GetLeafGainGivenOutput
        t = threshold_l1(side[..., 0], l1)
        return -(2.0 * t * out + (side[..., 1] + l2_eff) * out * out)

    # ---------------------------------------------------------- numerical
    cum = jnp.cumsum(h, axis=1)                                  # [F, MB, 3]
    has_nan = feat_missing == MISSING_NAN                        # [F]
    nan_idx = jnp.where(has_nan, feat_nb - 1, 0)
    nanv = jnp.take_along_axis(h, nan_idx[:, None, None]
                               .astype(jnp.int32), axis=1)[:, 0, :]  # [F, 3]
    nanv = jnp.where(has_nan[:, None], nanv, 0.0)

    # threshold t valid iff at least one numeric bin remains on each side:
    # numeric bins are [0, nb - 1 - has_nan); t in [0, nb - 2 - has_nan]
    t_max = feat_nb - 2 - has_nan.astype(jnp.int32)
    valid_t = (bin_ar[None, :] <= t_max[:, None]) & num_ok[:, None]

    shift_num = leaf_gain(parent_g, parent_h, l1, l2) + min_gain_to_split
    # any active constraint (finite bounds / nonzero mono / smoothing)
    # switches the candidate to given-output gain; otherwise closed form
    constrained = (jnp.isfinite(lb) | jnp.isfinite(ub)
                   | (mono[:, None] != 0))                       # [F, 1]
    if path_smooth > 0.0:
        constrained = jnp.ones_like(constrained)

    def num_gain(left, right, valid):
        plain = split_gain(left, right, l2, shift_num)
        l_out = jnp.clip(smooth_output(
            leaf_output(left[..., 0], left[..., 1], l1, l2, max_delta_step),
            left[..., 2], p_out, path_smooth), lb, ub)
        r_out = jnp.clip(smooth_output(
            leaf_output(right[..., 0], right[..., 1], l1, l2,
                        max_delta_step),
            right[..., 2], p_out, path_smooth), lb, ub)
        cg = (gain_given_output(left, l_out, l2)
              + gain_given_output(right, r_out, l2)) - shift_num
        viol = (((mono[:, None] > 0) & (l_out > r_out))
                | ((mono[:, None] < 0) & (l_out < r_out)))
        g = jnp.where(constrained, jnp.where(viol, NEG_INF, cg), plain)
        return jnp.where(valid & constraints_ok(left, right), g, NEG_INF)

    # case 0: missing right (NaN bin is last; prefix sums exclude it).
    left0 = cum
    right0 = parent[None, None, :] - left0
    gain0 = num_gain(left0, right0, valid_t)
    # case 1: missing left.
    left1 = cum + nanv[:, None, :]
    right1 = parent[None, None, :] - left1
    gain1 = num_gain(left1, right1, valid_t & has_nan[:, None])

    if not has_cat:
        return _decide_numerical(
            gain0, gain1, left0, left1, parent, feat_missing, feat_default,
            F, MB, gain_penalty, cand_mask, want_feature_gains)

    # --------------------------------------------------------- categorical
    # ancestor output bounds clamp categorical candidates too (reference:
    # GetSplitGains is constraint-aware in FindBestThresholdCategorical);
    # no direction check — monotone on a categorical feature is meaningless
    # and treated as 0 (the reference rejects it at config time)
    l2c = l2 + cat_l2
    shift_cat = leaf_gain(parent_g, parent_h, l1, l2c) + min_gain_to_split
    cat_bounded = jnp.isfinite(lb) | jnp.isfinite(ub) | (path_smooth > 0.0)

    def cat_gain(left, right, valid):
        plain = split_gain(left, right, l2c, shift_cat)
        l_out = jnp.clip(smooth_output(
            leaf_output(left[..., 0], left[..., 1], l1, l2c,
                        max_delta_step),
            left[..., 2], p_out, path_smooth), lb, ub)
        r_out = jnp.clip(smooth_output(
            leaf_output(right[..., 0], right[..., 1], l1, l2c,
                        max_delta_step),
            right[..., 2], p_out, path_smooth), lb, ub)
        cg = (gain_given_output(left, l_out, l2c)
              + gain_given_output(right, r_out, l2c)) - shift_cat
        g = jnp.where(cat_bounded, cg, plain)
        return jnp.where(valid & constraints_ok(left, right), g, NEG_INF)
    cnt = h[..., 2]
    # bin 0 = other/missing bin: never in the left subset (see docstring);
    # a bin is admitted with at least cat_smooth rows
    cat_valid = (bin_ar[None, :] >= 1) & valid_bin & (cnt >= cat_smooth) \
        & (cnt > 0) & cat_ok[:, None]                            # [F, MB]
    used = cat_valid.sum(axis=1)                                 # [F]

    # case 2: one-vs-rest (used <= max_cat_to_onehot)
    left2 = h
    right2 = parent[None, None, :] - left2
    gain2 = cat_gain(left2, right2,
                     cat_valid & (used[:, None] <= max_cat_to_onehot))

    # cases 3/4: sorted many-vs-rest (used > max_cat_to_onehot)
    # ref: FindBestThresholdCategorical sorts by sum_grad/(sum_hess+cat_smooth)
    k_max = jnp.minimum(max_cat_threshold, (used + 1) // 2)      # [F]
    group_steps = min(int(max_cat_threshold), MB)

    def group_gate(rows_gained, eligible):
        """[F, MB] bool: the eligible prefixes that are candidates.  Rows
        gained are counted since the last candidate, along the sorted
        order; only the first `max_cat_threshold` prefixes can be one."""
        def step(group, x):
            n_k, e_k = x
            group = group + n_k
            cand = e_k & (group >= min_data_per_group)
            return jnp.where(cand, 0.0, group), cand

        _, cand = jax.lax.scan(
            step, jnp.zeros((F,), rows_gained.dtype),
            (rows_gained[:, :group_steps].T, eligible[:, :group_steps].T))
        return jnp.zeros((F, MB), bool).at[:, :group_steps].set(cand.T)

    def prefix_gains(order):
        hs = jnp.take_along_axis(h, order[..., None], axis=1)
        cumk = jnp.cumsum(hs, axis=1)       # prefix of k = t+1 sorted bins
        k = bin_ar[None, :] + 1
        okk = (k <= k_max[:, None]) \
            & (used[:, None] > max_cat_to_onehot) & cat_ok[:, None]
        right = parent[None, None, :] - cumk
        if min_data_per_group > 0:
            okk = group_gate(hs[..., 2], okk & constraints_ok(cumk, right)
                             & (right[..., 2] >= min_data_per_group))
        g = cat_gain(cumk, right, okk)
        return g, cumk

    with jax.named_scope("cat_scan"):
        ratio = jnp.where(cat_valid,
                          h[..., 0] / (h[..., 1] + cat_smooth), jnp.inf)
        order_asc = jnp.argsort(ratio, axis=1)                   # [F, MB]
        ratio_desc = jnp.where(cat_valid, ratio, -jnp.inf)
        order_desc = jnp.argsort(-ratio_desc, axis=1)
        gain3, cum3 = prefix_gains(order_asc)
        gain4, cum4 = prefix_gains(order_desc)

    # ------------------------------------------------------------- decide
    gains = jnp.stack([gain0, gain1, gain2, gain3, gain4])       # [5, F, MB]
    if gain_penalty is not None:
        # CEGB feature-acquisition penalties (ref:
        # cost_effective_gradient_boosting.hpp — subtracted from the split
        # gain before selection); -inf candidates stay -inf
        gains = gains - gain_penalty[None, :, None]
    if cand_mask is not None:
        # forced splits: only the designated (feature, bin) cell competes
        gains = jnp.where(cand_mask[None, :, :], gains, NEG_INF)
    if want_feature_gains:
        # per-feature best gain — the voting-parallel learner's local vote
        # (ref: voting_parallel_tree_learner.cpp local FindBestSplits)
        return gains.max(axis=(0, 2))
    flat = gains.reshape(-1)
    best = jnp.argmax(flat)
    best_gain = flat[best]
    case = best // (F * MB)
    rem = best % (F * MB)
    feat = (rem // MB).astype(jnp.int32)
    thr = (rem % MB).astype(jnp.int32)

    lefts = jnp.stack([left0[feat, thr], left1[feat, thr], left2[feat, thr],
                       cum3[feat, thr], cum4[feat, thr]])        # [5, 3]
    left = lefts[case]
    right = parent - left

    best_is_cat = case >= 2
    # categorical left-subset membership mask over bins
    rank_asc = jnp.argsort(order_asc[feat])    # position of bin in asc order
    rank_desc = jnp.argsort(order_desc[feat])
    mask2 = bin_ar == thr
    mask3 = rank_asc <= thr
    mask4 = rank_desc <= thr
    cat_mask = jnp.where(case == 2, mask2,
                         jnp.where(case == 3, mask3, mask4)) \
        & cat_valid[feat] & best_is_cat

    # default_left (numerical only): NaN-missing → which scan won;
    # zero-missing → whether the zero bin landed left; else False
    mtype = feat_missing[feat]
    dl = jnp.where(best_is_cat, False,
                   jnp.where(mtype == MISSING_NAN, case == 1,
                             jnp.where(mtype == MISSING_ZERO,
                                       feat_default[feat] <= thr, False)))

    no_split = ~jnp.isfinite(best_gain)
    return SplitResult(
        gain=jnp.where(no_split, NEG_INF, best_gain),
        feature=jnp.where(no_split, -1, feat),
        threshold_bin=thr,
        default_left=dl,
        is_cat=best_is_cat & ~no_split,
        cat_mask=cat_mask & ~no_split,
        left_sum_g=left[0], left_sum_h=left[1], left_cnt=left[2],
        right_sum_g=right[0], right_sum_h=right[1], right_cnt=right[2],
    )


def _decide_numerical(gain0, gain1, left0, left1, parent, feat_missing,
                      feat_default, F, MB, gain_penalty, cand_mask,
                      want_feature_gains):
    """Decide stage of `find_best_split` for the all-numerical fast path
    (has_cat=False): identical selection semantics over the two numerical
    missing-direction cases only."""
    gains = jnp.stack([gain0, gain1])                            # [2, F, MB]
    if gain_penalty is not None:
        gains = gains - gain_penalty[None, :, None]
    if cand_mask is not None:
        gains = jnp.where(cand_mask[None, :, :], gains, NEG_INF)
    if want_feature_gains:
        return gains.max(axis=(0, 2))
    flat = gains.reshape(-1)
    best = jnp.argmax(flat)
    best_gain = flat[best]
    case = best // (F * MB)
    rem = best % (F * MB)
    feat = (rem // MB).astype(jnp.int32)
    thr = (rem % MB).astype(jnp.int32)

    left = jnp.stack([left0[feat, thr], left1[feat, thr]])[case]
    right = parent - left

    mtype = feat_missing[feat]
    dl = jnp.where(mtype == MISSING_NAN, case == 1,
                   jnp.where(mtype == MISSING_ZERO,
                             feat_default[feat] <= thr, False))

    no_split = ~jnp.isfinite(best_gain)
    return SplitResult(
        gain=jnp.where(no_split, NEG_INF, best_gain),
        feature=jnp.where(no_split, -1, feat),
        threshold_bin=thr,
        default_left=dl,
        is_cat=jnp.bool_(False),
        cat_mask=jnp.zeros((MB,), bool),
        left_sum_g=left[0], left_sum_h=left[1], left_cnt=left[2],
        right_sum_g=right[0], right_sum_h=right[1], right_cnt=right[2],
    )


def bin_goes_left(bins: Array, nb: Array, missing: Array, thr: Array,
                  default_left: Array, is_cat=None, cat_mask=None) -> Array:
    """Which side of one split each entry of `bins` (bin indices of the
    split column: a row's bin, or every bin of a histogram column) goes
    to.  The ONE routing rule: the partition (`ops/grow.py
    split_go_left`) and a node's child sums (`refine_child_sums`) both
    call it.  The NaN bin, the column's last, follows `default_left`;
    with `is_cat` (None: the model has no categorical column) a
    categorical split reads `cat_mask`, behind `lax.cond` because the
    [MB]-table gather at N rows is ~7 ms per split at 1M rows on TPU."""
    nan_bin = (missing == MISSING_NAN) & (bins == nb - 1)
    go_left_num = jnp.where(nan_bin, default_left, bins <= thr)
    if is_cat is None:
        return go_left_num
    return jax.lax.cond(is_cat, lambda: cat_mask[bins],
                        lambda: go_left_num)


def refine_child_sums(s: SplitResult, hist: Array, feat_nb: Array,
                      feat_missing: Array) -> SplitResult:
    """The chosen split's child sums, read again from the node's own
    two-limb histogram ([F, MB, 6], ops/histogram.py): each side is the
    sum of ITS bins of the split column, right at its own size.  The
    search's `right = parent - left` is right at the PARENT's size only,
    and a chain of such differences hands the round-off of a 100M-row
    root down to a 100-row leaf.  A histogram without limbs (the quantized
    families, an expanded bundle) leaves `s` as it is."""
    if hist.shape[-1] != 6:
        return s
    F, MB, _ = hist.shape
    f = jnp.clip(s.feature, 0, F - 1)
    col = hist[f]                                                # [MB, 6]
    b = jnp.arange(MB, dtype=jnp.int32)
    valid = b < feat_nb[f]
    go_left = bin_goes_left(b, feat_nb[f], feat_missing[f], s.threshold_bin,
                            s.default_left, s.is_cat, s.cat_mask)

    def side(mask):
        x = jnp.where((mask & valid)[:, None], col, 0.0).sum(axis=0)  # [6]
        return x[:3] + x[3:]

    found = s.feature >= 0
    left = jnp.where(found, side(go_left), jnp.stack(
        [s.left_sum_g, s.left_sum_h, s.left_cnt]))
    right = jnp.where(found, side(~go_left), jnp.stack(
        [s.right_sum_g, s.right_sum_h, s.right_cnt]))
    return s._replace(left_sum_g=left[0], left_sum_h=left[1],
                      left_cnt=left[2], right_sum_g=right[0],
                      right_sum_h=right[1], right_cnt=right[2])
