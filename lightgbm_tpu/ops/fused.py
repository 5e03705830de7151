"""Fused multi-iteration training: the whole boosting loop on device.

TPU-native design with no reference counterpart: where the reference's
`GBDT::TrainOneIter` crosses the host boundary once per iteration, this
compiles a CHUNK of boosting
iterations into ONE XLA program via `lax.scan`:

    score ─┬─> grad/hess ─> grow_tree ─> score += lr·tree ─┬─> ...
           └──────────────── per-class unroll ─────────────┘

Outputs are the stacked flat-tree arrays for every iteration in the chunk;
the host syncs once per chunk and decodes trees lazily.  Bagging / GOSS /
feature_fraction run inside the scan with `jax.random` keys folded per
iteration — the SAME key derivation the per-iteration path in booster.py
uses, so chunked and looped training produce identical models.

This is the bench/TPU hot path; the per-iteration path remains for
callback-driven training (eval between iterations needs host sync anyway).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .grow import GrowerSpec, make_grower
from .leaf_rows import leaf_rows
from ..analysis.contracts import contract

Array = jax.Array


# --------------------------------------------------------- sampling (shared)
@contract(it="[] int", key0="key", n="static:N",
          bagging_fraction="static", bagging_freq="static int",
          ret="[N] f32")
def bagging_weights(it, key0: Array, n: int, *, bagging_fraction: float,
                    bagging_freq: int) -> Array:
    """Bagging mask for iteration `it` (ref: GBDT::Bagging / bagging.hpp).
    The bag renews every `bagging_freq` iterations."""
    bag_it = it // max(bagging_freq, 1)
    key = jax.random.fold_in(key0, bag_it * 2)
    return (jax.random.uniform(key, (n,)) <
            bagging_fraction).astype(jnp.float32)


@contract(it="[] int", key0="key", grad="array", hess="array",
          n="static:N", top_rate="static", other_rate="static",
          goss_start_iter="static int", ret="[N] f32")
def goss_weights(it, key0: Array, grad: Array, hess: Array, n: int, *,
                 top_rate: float, other_rate: float,
                 goss_start_iter: int) -> Array:
    """GOSS weights (ref: src/boosting/goss.hpp `GOSS::Bagging`): keep
    top_rate by |g·h|, Bernoulli-sample the rest at other_rate/(1-top_rate)
    and amplify by (1-a)/b.  Deviation from the reference: sampled count is
    binomial rather than exactly N·b (fixed shapes; unbiased either way)."""
    if grad.ndim == 2:
        score_r = jnp.sum(jnp.abs(grad * hess), axis=1)
    else:
        score_r = jnp.abs(grad * hess)
    a, b = top_rate, other_rate
    top_n = max(1, int(a * n))
    kth = jnp.sort(score_r)[n - top_n]
    top_mask = score_r >= kth
    key = jax.random.fold_in(key0, it * 2)
    rand = jax.random.uniform(key, (n,))
    rest_mask = (~top_mask) & (rand < b / max(1.0 - a, 1e-12))
    w = top_mask.astype(jnp.float32) \
        + rest_mask.astype(jnp.float32) * ((1.0 - a) / b)
    # ref: GOSS leaves the first 1/learning_rate iterations unsampled
    return jnp.where(it >= goss_start_iter, w, jnp.ones((n,), jnp.float32))


@contract(grad="array", hess="array", n_bins="static int", key="key?",
          return_scales="static", const_hess_level="static int",
          ret="tree")
def quantize_gradients(grad: Array, hess: Array, n_bins: int,
                       key: Array = None, return_scales: bool = False,
                       const_hess_level: int = 0):
    """Gradient discretization (ref: cuda_gradient_discretizer.cu /
    v4 quantized training `use_quantized_grad`): gradients snap to
    `n_bins` signed levels, hessians to `n_bins` unsigned levels, with
    optional stochastic rounding (key != None).

    TPU note: the quantize→dequantize round trip reproduces the
    reference's int-histogram MODEL semantics exactly (f32 sums of scaled
    small ints are exact well past 2^24 rows/bin); the further int16
    accumulation perf win belongs to the Pallas histogram kernel.
    """
    half = max(n_bins // 2, 1)
    s_g = jnp.max(jnp.abs(grad)) / half
    s_g = jnp.where(s_g > 0, s_g, 1.0)
    vg = grad / s_g
    if const_hess_level > 0:
        # declared-constant hessian (exactly 1 before weighting): skip
        # hessian quantization entirely so payload sums and the packed
        # histogram's derived values agree EXACTLY — s_h = 1/level makes
        # the kernel reconstruct hq = round(1/(1/level)) = level for
        # every live row (stochastic floor on vh could yield level-1 for
        # level in {7, 13, 14, 15} where f32 1/(1/nb) rounds below nb)
        hq_s = hess
        s_h = jnp.float32(1.0 / const_hess_level)
    else:
        s_h = jnp.max(jnp.abs(hess)) / max(n_bins, 1)
        s_h = jnp.where(s_h > 0, s_h, 1.0)
        vh = hess / s_h
        if key is not None:
            kh = jax.random.split(key)[1]
            hq_s = jnp.floor(vh + jax.random.uniform(kh, hess.shape)) * s_h
        else:
            hq_s = jnp.round(vh) * s_h
    if key is not None:
        kg = jax.random.split(key)[0]
        gq = jnp.floor(vg + jax.random.uniform(kg, grad.shape))
    else:
        gq = jnp.round(vg)
    if return_scales:
        return gq * s_g, hq_s, (s_g.astype(jnp.float32),
                                jnp.asarray(s_h, jnp.float32))
    return gq * s_g, hq_s


@contract(it="[] int", k="static int", key0="key",
          base_allowed="[F] bool", feature_fraction="static",
          ret="[F] bool")
def feature_mask(it, k: int, key0: Array, base_allowed: Array, *,
                 feature_fraction: float) -> Array:
    """Per-tree column mask (ref: col_sampler.hpp `ColSampler::ResetByTree`)."""
    if feature_fraction >= 1.0:
        return base_allowed
    f = base_allowed.shape[0]
    n_pick = max(1, int(feature_fraction * f + 0.999999))
    key = jax.random.fold_in(jax.random.fold_in(key0, it * 2 + 1), k)
    perm = jax.random.permutation(key, f)
    chosen = jnp.zeros((f,), bool).at[perm[:n_pick]].set(True)
    return base_allowed & chosen


# ------------------------------------------------------------- bulk trainer
class BulkSpec(NamedTuple):
    grower: GrowerSpec
    chunk: int               # iterations per compiled program
    num_class: int
    learning_rate: float
    bagging_fraction: float
    bagging_freq: int
    use_goss: bool
    top_rate: float
    other_rate: float
    goss_start_iter: int
    feature_fraction: float
    rf: bool = False          # RF mode: no shrinkage, grads at base score
    needs_rng: bool = False   # objective draws per-iteration randomness
    n_valid: int = 0          # valid sets scored inside the chunk
    emit_train_scores: bool = False  # emit per-iteration train scores
    renew_alpha: float = -1.0  # >=0: L1-family leaf percentile refit
    renew_weighted: bool = False
    quant_bins: int = 0        # >0: gradient discretization levels
    quant_stochastic: bool = True


def make_bulk_trainer(spec: BulkSpec, grad_fn: Callable, renew_args=None,
                      grow_fn: Callable = None):
    """Build the jitted chunk trainer.

    grad_fn(score) -> (grad, hess) (or grad_fn(score, key) when
    spec.needs_rng), closed over label/weight device arrays ([N] or [N, K]
    to match score).

    With `n_valid > 0` the chunk ALSO carries validation scores: each grown
    tree is replayed over the valid bin matrices on device
    (ops/predict.py `replay_leaf_ids`) and the post-iteration scores are
    emitted per iteration, so `lgb.train` with eval/early-stopping syncs the
    host once per chunk instead of once per iteration — the reference has no
    counterpart (its per-iteration `ScoreUpdater::AddScore` on valid data
    crosses the host boundary every iteration).

    Returns train_chunk(score, vscores, it0, key0, ff_key0, grad_key0,
    bins_fm, feat, base_allowed, valid_bins) ->
    (final_score, final_vscores, stacked_trees,
     per_iter_vscores, per_iter_tscores).
    """
    from .predict import replay_leaf_ids

    # grow_fn: an alternative grower with the serial signature — the
    # distributed shard_map'ped learner plugs in here, so multi-chip
    # training gets the same one-sync-per-chunk behavior
    grow = grow_fn if grow_fn is not None else make_grower(spec.grower)
    # a tree's value for every row (`ops/leaf_rows.py`): the distributed
    # grower brings the look-up over its own row shards; the valid rows
    # are not split like them and keep the gather there
    serial_lookup = functools.partial(
        leaf_rows, hist_impl=spec.grower.hist_impl,
        interpret=spec.grower.hist_interpret)
    lookup = getattr(grow, "leaf_rows", serial_lookup)
    valid_lookup = serial_lookup if grow_fn is None \
        else (lambda table, ids: table[ids])
    K = spec.num_class
    lr = 1.0 if spec.rf else spec.learning_rate
    if spec.renew_alpha >= 0.0:
        # L1/quantile/MAPE per-leaf percentile refit on device
        # (ref: RenewTreeOutput; renew_args = (label [N], base weight [N]))
        from .renew import renew_leaf_values
        renew_label, renew_w = renew_args

    def chunk_step(carry, it, *, bins_fm, feat, base_allowed, key0, ff_key0,
                   grad_key0, valid_bins):
        score, vscores = carry
        # RF trees are independent: gradients at the constant base score
        # (ref: rf.hpp RF::Boosting)
        # named scopes (grad_hess / grow_tree / update_scores) label the
        # XProf device timeline per phase — compile-time metadata only
        with jax.named_scope("grad_hess"):
            grad_at = jnp.zeros_like(score) if spec.rf else score
            if spec.needs_rng:
                grad, hess = grad_fn(grad_at,
                                     jax.random.fold_in(grad_key0, it))
            else:
                grad, hess = grad_fn(grad_at)
        # row count from the score, NOT bins_fm — the distributed grower's
        # bin matrix is pre-padded to the mesh shard multiple
        n = score.shape[0]
        if spec.use_goss:
            # GOSS ranks EXACT gradients; quantization follows (reference
            # order: sample strategy, then gradient discretizer)
            sw = goss_weights(it, key0, grad, hess, n,
                              top_rate=spec.top_rate,
                              other_rate=spec.other_rate,
                              goss_start_iter=spec.goss_start_iter)
        elif spec.bagging_freq > 0 and spec.bagging_fraction < 1.0:
            sw = bagging_weights(it, key0, n,
                                 bagging_fraction=spec.bagging_fraction,
                                 bagging_freq=spec.bagging_freq)
        else:
            sw = jnp.ones((n,), jnp.float32)
        if spec.quant_bins:
            # odd stream ids — bagging/GOSS use even fold_in ids on key0
            qkey = jax.random.fold_in(key0, it * 2 + 1) \
                if spec.quant_stochastic else None
            if spec.grower.hist_impl in ("packed", "pallas_q"):
                grad, hess, qs = quantize_gradients(
                    grad, hess, spec.quant_bins, qkey, return_scales=True,
                    const_hess_level=spec.grower.packed_const_hess_level)
                feat = {**feat, "qscales": jnp.stack(qs)}
            else:
                grad, hess = quantize_gradients(grad, hess,
                                                spec.quant_bins, qkey)
        trees = []
        new_score = score
        new_vscores = list(vscores)
        for k in range(K):
            gk = grad if K == 1 else grad[:, k]
            hk = hess if K == 1 else hess[:, k]
            allowed = feature_mask(it, k, ff_key0, base_allowed,
                                   feature_fraction=spec.feature_fraction)
            tree_feat = feat
            if spec.grower.feature_fraction_bynode < 1.0 \
                    or spec.grower.extra_trees:
                # same per-tree stream derivation as booster.__boost
                tree_feat = {**feat, "ff_key": jax.random.fold_in(
                    jax.random.fold_in(ff_key0, 2 ** 20 + it), k)}
            with jax.named_scope("grow_tree"):
                dev = grow(bins_fm, gk.astype(jnp.float32),
                           hk.astype(jnp.float32), sw, tree_feat, allowed)
                if spec.renew_alpha >= 0.0:
                    renewed = renew_leaf_values(
                        dev.leaf_value, renew_label - score, renew_w, sw,
                        dev.leaf_id, spec.grower.num_leaves,
                        spec.renew_alpha, spec.renew_weighted)
                    # stump trees keep the closed-form output — the
                    # per-iteration path gates renew on num_leaves > 1
                    # (ref: RenewTreeOutput is only invoked for trees that
                    # actually split)
                    dev = dev._replace(leaf_value=jnp.where(
                        dev.n_splits > 0, renewed, dev.leaf_value))
            with jax.named_scope("update_scores"):
                contrib = lookup(dev.leaf_value, dev.leaf_id) * lr
                if K == 1:
                    new_score = new_score + contrib
                else:
                    new_score = new_score.at[:, k].add(contrib)
                for vi, vbins in enumerate(valid_bins):
                    vlid = replay_leaf_ids(dev, vbins, feat["nb"],
                                           feat["missing"])
                    vcontrib = valid_lookup(dev.leaf_value, vlid) * lr
                    if K == 1:
                        new_vscores[vi] = new_vscores[vi] + vcontrib
                    else:
                        new_vscores[vi] = \
                            new_vscores[vi].at[:, k].add(vcontrib)
            # leaf_id is per-row train state — not part of the model output
            trees.append(dev._replace(leaf_id=jnp.zeros((0,), jnp.int32)))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees) \
            if K > 1 else trees[0]
        t_emit = new_score if spec.emit_train_scores \
            else jnp.zeros((0,), jnp.float32)
        return (new_score, tuple(new_vscores)), \
            (stacked, tuple(new_vscores), t_emit)

    # score/vscores are pure carries: the caller immediately rebinds its
    # references to the returned buffers (booster._dispatch_chunk), so XLA
    # may reuse the input HBM for the output in place of a copy — one
    # fewer num_data-sized live buffer per chunk, and a prerequisite for
    # keeping several pipelined chunks in flight without doubling the
    # score footprint
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_chunk(score, vscores, it0, key0, ff_key0, grad_key0,
                    bins_fm, feat, base_allowed, valid_bins):
        step = functools.partial(
            chunk_step, bins_fm=bins_fm, feat=feat,
            base_allowed=base_allowed, key0=key0, ff_key0=ff_key0,
            grad_key0=grad_key0, valid_bins=valid_bins)
        its = it0 + jnp.arange(spec.chunk)
        (fs, fvs), (stacked, v_iter, t_iter) = \
            jax.lax.scan(step, (score, tuple(vscores)), its)
        return fs, fvs, stacked, v_iter, t_iter

    return train_chunk
