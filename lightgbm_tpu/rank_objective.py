"""Ranking objectives: LambdaRank NDCG and RankXENDCG.

TPU-native re-design of the reference's ranking objectives
(ref: src/objective/rank_objective.hpp `LambdarankNDCG`
[`GetGradientsForOneQuery`: per-query pair loop over score-sorted docs,
ΔNDCG-weighted sigmoid lambdas, truncation_level, `norm_`],
`RankXENDCG`).

The reference loops queries on OpenMP threads with O(trunc·len) serial pair
scans.  The TPU formulation pads queries to a bucket length P and computes
the pairs of a BLOCK of queries at once:
 - sort each padded query by score (stable argsort, descending),
 - enumerate pairs (i, j) with i in the top `truncation_level` ranks only
   (matching the reference's truncation), j over all P slots,
 - ΔNDCG, sigmoid lambda, hessian evaluated on the whole [B, T, P] block,
 - scatter-add back to flat [N] via the padded index map.

Shapes are static: each bucket's queries are padded to P and cut into
blocks of B queries at Dataset bind time, masks cover padding; a scan over
the blocks keeps the pair program's temporaries at one block's, whatever
the number of queries.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry
from .objectives import ObjectiveFunction, _weighted_percentile
from .utils.config import Config
from .utils.log import LightGBMError

Array = jax.Array


def _bucket_queries(sizes: np.ndarray, max_buckets: int = 3,
                    min_saving: float = 0.2):
    """Group queries into <= `max_buckets` length buckets, each padded
    to ITS OWN max (r5).  Real LTR data has long-tailed query sizes
    (MSLR: median ~120 docs, max ~1k+), and one global pad length makes
    every median query pay the longest query's [Q, T, P] pair tensor —
    ~5-8x padded FLOPs.  Per-query math is independent, so bucketing is
    exactly equivalent; cuts sit at the ~50%/~90% length quantiles, tiny
    buckets merge into their neighbor, and the flat single-bucket layout
    is kept unless bucketing saves >= `min_saving` of the padded area
    (so small/uniform datasets keep the identical old layout and
    jit-compile exactly one block shape).

    Returns a list of ascending-length query-index arrays."""
    Q = len(sizes)
    order = np.argsort(sizes, kind="stable")
    flat_area = Q * int(sizes[order[-1]])
    cuts = sorted({int(Q * 0.5), int(Q * 0.9)})
    cuts = [c for c in cuts if 0 < c < Q][:max_buckets - 1]
    groups = []
    prev = 0
    for c in cuts + [Q]:
        if c > prev:
            groups.append(order[prev:c])
            prev = c
    # merge tiny buckets into their successor (last one merges backward)
    merged = []
    pending = None
    for g in groups:
        if pending is not None:
            g = np.concatenate([pending, g])
            pending = None
        if len(g) < 8:
            pending = g
        else:
            merged.append(g)
    if pending is not None:
        if merged:
            merged[-1] = np.concatenate([merged[-1], pending])
        else:
            merged.append(pending)
    area = sum(len(g) * int(sizes[g].max()) for g in merged)
    if len(merged) <= 1 or area > (1.0 - min_saving) * flat_area:
        return [np.arange(Q, dtype=np.int64)]
    return merged


def _build_buckets(qb: np.ndarray, sizes: np.ndarray, device: bool = True):
    """The padded gather maps for each length bucket — ONE builder shared
    by both ranking objectives.  Each bucket dict carries the host map
    (`idx_np`, -1 padded, for position binding) and the queries it holds
    (`qidx`); with `device`, also the device-side pre-clipped gather
    index (`gather` — padding already clipped to row 0; `mask` is the
    truth about padding) and the pad mask."""
    buckets = []
    for qidx in _bucket_queries(sizes):
        Pb = int(sizes[qidx].max())
        idx = np.full((len(qidx), Pb), -1, dtype=np.int32)
        for row, q in enumerate(qidx):
            idx[row, :sizes[q]] = np.arange(qb[q], qb[q + 1],
                                            dtype=np.int32)
        b = {"idx_np": idx, "qidx": qidx}
        if device:
            b.update(gather=jnp.asarray(np.maximum(idx, 0)),
                     mask=jnp.asarray(idx >= 0))
        buckets.append(b)
    return buckets


#: bytes of ONE [B, T, P] float32 pair temporary: a bucket's block of B
#: queries is the most whose pair tensor stays within it (at least one
#: query), so the pair program's live temporaries are a block's at any
#: number of queries (tests/test_lgbrank_aot.py reads them at the
#: Istella-X cell's shape)
PAIR_BLOCK_BYTES = 32 << 20


def _blocked(a: np.ndarray, n_blocks: int, fill) -> np.ndarray:
    """`a` [Q, ...] padded with `fill` to a whole number of blocks and
    reshaped [n_blocks, B, ...]."""
    per = -(-len(a) // n_blocks)
    pad = np.full((n_blocks * per - len(a),) + a.shape[1:], fill, a.dtype)
    return np.concatenate([a, pad]).reshape((n_blocks, per) + a.shape[1:])


class LambdarankNDCG(ObjectiveFunction):
    """ref: rank_objective.hpp `LambdarankNDCG::GetGradientsForOneQuery`
    (v3, from memory; the rule the Istella-X cell states,
    `perfbench/configs/istellax220-lgbrank-l31.json`).  Per query, docs
    stable-sorted by score descending; for each i in the top
    min(T, n) ranks and j > i with a different label, `high` the better-
    labelled doc of the pair:

        Δ = |gain_high - gain_low| · |1/log2(2+r_high) - 1/log2(2+r_low)|
            · inverse_max_dcg@T
        norm and best score != worst score:  Δ /= 0.01 + |s_high - s_low|
        p = 1 / (1 + exp(σ (s_high - s_low)))
        λ = -σ p Δ,  h = σ² p (1 - p) Δ;  high += λ, low -= λ, both += h
        norm and S = Σ_pairs 2|λ| > 0:  λ, h *= log2(1 + S) / S

    Upstream reads the sigmoid from a table of 2^20 bins; here it is
    computed (the cell's `departures`).

    Position-bias correction (ref: v4 rank_objective.hpp position handling,
    `lambdarank_position_bias_regularization`; algorithm per Unbiased
    LambdaMART, Hu et al. WSDM'19): when `Dataset.position` is supplied the
    booster activates the stateful path — each pair's Δ above is divided by
    the learned propensities `t_plus[pos_high] * t_minus[pos_low]`, and the
    propensities are re-estimated each iteration from the aggregated raw
    lambdas (alternating minimization, normalized to position 0, exponent
    1/(1+reg)).  Formula-level parity with upstream's position handling
    is not verified (no reference and no cell for it); the contract
    (positions in, per-position bias factors learned jointly with the
    model) matches.

    The query maps are ARGUMENTS of the jitted gradients
    (`device_maps()`, handed to `lambdarank` by the booster as labels
    are): closed over, they would be baked into the executable as
    constants.  Each call counts `rank.pair_slots` (pair slots the blocks
    evaluate) and `rank.pair_positions` (the (i, j) positions of the rule
    between real docs) on the host (`count_calls`).
    """
    name = "lambdarank"
    is_ranking = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.sigmoid = config.sigmoid
        self.truncation_level = config.lambdarank_truncation_level
        self.norm = config.lambdarank_norm
        self.bias_reg = config.lambdarank_position_bias_regularization
        label_gain = config.label_gain
        if not label_gain:
            label_gain = [float((1 << i) - 1) for i in range(31)]
        self.label_gain = np.asarray(label_gain, dtype=np.float64)
        self.has_state = False        # set by set_positions
        self.num_positions = 0

    def init_meta(self, label, weight, query_boundaries):
        super().init_meta(label, weight, query_boundaries)
        if query_boundaries is None:
            raise LightGBMError("Lambdarank tasks require query information")
        if np.any(label < 0) or np.any(label != np.floor(label)):
            raise LightGBMError("Ranking labels must be non-negative integers")
        if int(label.max()) >= len(self.label_gain):
            raise LightGBMError(
                f"Label {int(label.max())} exceeds label_gain size")
        qb = np.asarray(query_boundaries, dtype=np.int64)
        sizes = np.diff(qb)
        if len(sizes) == 0:
            raise LightGBMError("Ranking objective requires query "
                                "information (set group in the Dataset)")
        self._num_data = int(qb[-1])
        # per-query inverse max DCG at truncation_level (ref:
        # LambdarankNDCG Init computes inverse_max_dcgs_)
        gains = self.label_gain[label.astype(np.int64)]
        inv_max = np.zeros(len(sizes), dtype=np.float64)
        T = self.truncation_level
        for q in range(len(sizes)):
            g = np.sort(gains[qb[q]:qb[q + 1]])[::-1][:T]
            dcg = np.sum(g / np.log2(np.arange(2, len(g) + 2)))
            inv_max[q] = 1.0 / dcg if dcg > 0 else 0.0
        inv_max = inv_max.astype(np.float32)
        self.gain_table = jnp.asarray(self.label_gain.astype(np.float32))
        # r5: length-bucketed padded layout — each bucket pads to its
        # own max, so median queries stop paying the longest query's
        # pair tensor (see _bucket_queries); each bucket in blocks of
        # queries (PAIR_BLOCK_BYTES)
        self._buckets = []
        slots = 0
        for b in _build_buckets(qb, sizes, device=False):
            P = b["idx_np"].shape[1]
            per = max(1, PAIR_BLOCK_BYTES // (4 * min(T, P) * P))
            n_blocks = -(-len(b["qidx"]) // per)
            b["blocks"] = n_blocks
            b["maps"] = {
                "gather": jnp.asarray(_blocked(
                    np.maximum(b["idx_np"], 0), n_blocks, 0)),
                "mask": jnp.asarray(_blocked(b["idx_np"] >= 0, n_blocks,
                                             False)),
                "inv_max": jnp.asarray(_blocked(inv_max[b["qidx"]],
                                                n_blocks, 0.0)),
                "pos": None}
            slots += int(np.prod(b["maps"]["gather"].shape)) * min(T, P)
            self._buckets.append(b)
        t = np.minimum(sizes, T)
        self._pair_counts = (slots, int(np.sum(t * (sizes - 1)
                                               - t * (t - 1) // 2)))
        telemetry.REGISTRY.gauge("rank.queries").set(len(sizes))
        telemetry.REGISTRY.gauge("rank.buckets").set(len(self._buckets))
        # re-binding a dataset invalidates any previously bound positions
        # (the buckets above were just rebuilt with pos=None): without
        # this reset a stale has_state/num_positions pair from an earlier
        # set_positions would make get_gradients reach for the missing
        # position grids and crash — set_positions must be called again
        self.has_state = False
        self.num_positions = 0

    def device_maps(self):
        """The query maps of every bucket, `[n_blocks, B, P]` (inverse
        max DCG `[n_blocks, B]`): the gradients' argument `maps`."""
        return [b["maps"] for b in self._buckets]

    def count_calls(self, calls: int = 1) -> None:
        slots, positions = self._pair_counts
        telemetry.REGISTRY.counter("rank.pair_slots").inc(calls * slots)
        telemetry.REGISTRY.counter("rank.pair_positions").inc(
            calls * positions)

    # ------------------------------------------------- position debiasing
    def set_positions(self, position: np.ndarray) -> None:
        """Bind per-row positions (call after `init_meta`).

        Raw position values are remapped through their sorted unique ids
        (ref: v4 Metadata position_ids_) so id 0 — the propensity
        normalization anchor — is always an OBSERVED position; 1-based or
        gappy encodings would otherwise leave the anchor empty and blow
        up the normalizer."""
        pos = np.asarray(position, dtype=np.int64).reshape(-1)
        if len(pos) != self._num_data:
            raise LightGBMError(
                f"Length of position ({len(pos)}) does not match "
                f"number of data ({self._num_data})")
        if pos.min() < 0:
            raise LightGBMError("positions must be non-negative integers")
        uniq, inv = np.unique(pos, return_inverse=True)
        self.num_positions = len(uniq)
        pos_ids = inv.astype(np.int32)
        for b in self._buckets:
            grid = pos_ids[np.maximum(b["idx_np"], 0)]
            grid[b["idx_np"] < 0] = 0
            b["maps"]["pos"] = jnp.asarray(_blocked(grid, b["blocks"], 0))
        self.has_state = True

    def init_state(self):
        """(t_plus, t_minus) propensity factors, identity at start."""
        k = max(self.num_positions, 1)
        return (jnp.ones((k,), jnp.float32), jnp.ones((k,), jnp.float32))

    def _block_lambdas(self, blk, score, label, state):
        """One block's [B, T, P] pair computation → (rows, lam, h, lp,
        lm): the rows of the block's padded slots in score order, their
        lambdas and hessians (0 on padding), and the block's raw
        propensity mass (lp/lm are None without position state)."""
        idx, mask = blk["gather"], blk["mask"]                 # [B, P]
        P = idx.shape[1]
        T = min(self.truncation_level, P)
        sig = self.sigmoid
        s = jnp.where(mask, score[idx], -jnp.inf)
        # rank by score desc, ties in row order (padding sinks via -inf)
        order = jnp.argsort(-s, axis=1)
        rows = jnp.take_along_axis(idx, order, axis=1)
        m = jnp.take_along_axis(mask, order, axis=1)
        s = jnp.where(m, jnp.take_along_axis(s, order, axis=1), 0.0)
        y = jnp.where(m, label[rows], -1.0)
        gain = jnp.where(m, self.gain_table[jnp.maximum(y, 0).astype(
            jnp.int32)], 0.0)
        discount = 1.0 / jnp.log2(jnp.arange(P, dtype=jnp.float32) + 2.0)

        # pairs: i over the top-T ranks, j over all ranks below i
        yi, yj = y[:, :T, None], y[:, None, :]
        valid = (jnp.arange(P)[None, None, :] > jnp.arange(T)[None, :, None]) \
            & m[:, :T, None] & m[:, None, :] & (yi != yj)
        high_is_i = yi > yj
        ds = jnp.where(high_is_i, 1.0, -1.0) * (s[:, :T, None]
                                                - s[:, None, :])
        delta = jnp.abs(gain[:, :T, None] - gain[:, None, :]) \
            * jnp.abs(discount[None, :T, None] - discount[None, None, :]) \
            * blk["inv_max"][:, None, None]                    # [B, T, P]
        if self.norm:
            # regularised by the pair's score distance where the query's
            # scores are not all equal
            best = s[:, 0]
            worst = jnp.min(jnp.where(m, s, jnp.inf), axis=1)
            delta = jnp.where((best != worst)[:, None, None],
                              delta / (0.01 + jnp.abs(ds)), delta)
        p = jax.nn.sigmoid(-sig * ds)                       # 1/(1+e^{σΔs})

        lp = lm = None
        if state is not None and blk["pos"] is not None:
            # unbiased-LambdaMART correction: divide each pair's weight by
            # the learned click propensities; the raw lambda mass (lp/lm)
            # is returned for the caller's cross-bucket re-estimate
            t_plus, t_minus = state
            pos = jnp.take_along_axis(blk["pos"], order, axis=1)
            p_i = jnp.broadcast_to(pos[:, :T, None], valid.shape)
            p_j = jnp.broadcast_to(pos[:, None, :], valid.shape)
            pos_high = jnp.where(high_is_i, p_i, p_j)
            pos_low = jnp.where(high_is_i, p_j, p_i)
            lam_mag = jnp.where(valid, sig * p * delta, 0.0)
            lp = jnp.zeros((max(self.num_positions, 1),), jnp.float32)\
                .at[pos_high.reshape(-1)].add(
                (lam_mag / t_minus[pos_low]).reshape(-1))
            lm = jnp.zeros((max(self.num_positions, 1),), jnp.float32)\
                .at[pos_low.reshape(-1)].add(
                (lam_mag / t_plus[pos_high]).reshape(-1))
            delta = delta / (t_plus[pos_high] * t_minus[pos_low])

        lam = jnp.where(valid, -sig * p * delta, 0.0)          # d/ds_high
        hess = jnp.where(valid, sig * sig * p * (1.0 - p) * delta, 0.0)
        # high gets +lam, low gets -lam; both get +hess
        lam_sorted = jnp.where(high_is_i, -lam, lam).sum(axis=1)\
            .at[:, :T].add(jnp.where(high_is_i, lam, -lam).sum(axis=2))
        h_sorted = hess.sum(axis=1).at[:, :T].add(hess.sum(axis=2))
        if self.norm:
            # ref: lambdarank_norm — rescale per query by log2(1+S)/S,
            # S = Σ_pairs 2|λ|
            total = 2.0 * jnp.sum(jnp.abs(lam), axis=(1, 2))
            factor = jnp.where(total > 0, jnp.log2(1.0 + total)
                               / jnp.where(total > 0, total, 1.0), 1.0)
            lam_sorted = lam_sorted * factor[:, None]
            h_sorted = h_sorted * factor[:, None]
        # zero the pad slots so the scatter-add at clipped index 0 adds
        # nothing
        return (rows.reshape(-1), jnp.where(m, lam_sorted, 0.0).reshape(-1),
                jnp.where(m, h_sorted, 0.0).reshape(-1), lp, lm)

    def lambdarank(self, score, label, weight, maps, state=None):
        """The gradients over the query maps `maps` (`device_maps()`):
        (grad, hess), and with `state` the re-estimated propensities
        third.  Each bucket's blocks run one after another (`lax.scan`),
        each scatter-adding its rows into the carried [N] sums."""
        debias = state is not None and maps[0]["pos"] is not None
        k = max(self.num_positions, 1)
        zero = jnp.zeros((k,), jnp.float32) if debias else None

        def block(carry, blk):
            grad, hess, lp_acc, lm_acc = carry
            rows, lam, h, lp, lm = self._block_lambdas(blk, score, label,
                                                       state)
            grad = grad.at[rows].add(lam)
            hess = hess.at[rows].add(h)
            if debias:
                lp_acc, lm_acc = lp_acc + lp, lm_acc + lm
            return (grad, hess, lp_acc, lm_acc), None

        carry = (jnp.zeros_like(score), jnp.zeros_like(score), zero, zero)
        with jax.named_scope("lambdarank"):
            for blocks in maps:
                carry, _ = jax.lax.scan(block, carry, blocks)
        grad, hessian, lp_acc, lm_acc = carry
        new_state = None
        if debias:
            # re-estimate propensities from the GLOBAL raw lambda mass
            # (alternating minimization, normalized to position 0,
            # exponent 1/(1+reg)) — summed across blocks first so the
            # estimate is identical to the flat layout's
            exponent = 1.0 / (1.0 + self.bias_reg)
            tp_new = jnp.where(
                lp_acc > 0,
                (lp_acc / jnp.maximum(lp_acc[0], 1e-20)) ** exponent, 1.0)
            tm_new = jnp.where(
                lm_acc > 0,
                (lm_acc / jnp.maximum(lm_acc[0], 1e-20)) ** exponent, 1.0)
            new_state = (tp_new.astype(jnp.float32),
                         tm_new.astype(jnp.float32))
        if weight is not None:
            grad = grad * weight
            hessian = hessian * weight
        if state is not None:
            return grad, hessian, new_state
        return grad, hessian

    def grad_hess(self, score, label, weight, state=None):
        return self.lambdarank(score, label, weight, self.device_maps(),
                               state)


class RankXENDCG(ObjectiveFunction):
    """ref: rank_objective.hpp `RankXENDCG` (cross-entropy NDCG surrogate
    with per-iteration sampled gammas)."""
    name = "rank_xendcg"
    is_ranking = True
    needs_rng = True

    def init_meta(self, label, weight, query_boundaries):
        super().init_meta(label, weight, query_boundaries)
        if query_boundaries is None:
            raise LightGBMError("Ranking tasks require query information")
        qb = np.asarray(query_boundaries, dtype=np.int64)
        sizes = np.diff(qb)
        if len(sizes) == 0:
            raise LightGBMError("Ranking objective requires query "
                                "information (set group in the Dataset)")
        # same length-bucketed layout as LambdarankNDCG (per-query math)
        self._buckets = _build_buckets(qb, sizes)

    def grad_hess(self, score, label, weight, key=None):
        if key is None:
            key = jax.random.PRNGKey(self.config.objective_seed)
        grad = jnp.zeros_like(score)
        hessian = jnp.zeros_like(score)
        single = len(self._buckets) == 1
        for k, b in enumerate(self._buckets):
            # single bucket keeps the PRE-bucketing RNG stream (the raw
            # key), so uniform-query datasets reproduce old-seed models
            bkey = key if single else jax.random.fold_in(key, k)
            idx = b["gather"]
            mask = b["mask"]
            s = jnp.where(mask, score[idx], -jnp.inf)
            y = jnp.where(mask, label[idx], 0.0)
            gammas = jax.random.uniform(bkey, s.shape)
            # phi = 2^y - gamma (ref: RankXENDCG::GetGradientsForOneQuery)
            phi = jnp.where(mask, jnp.exp2(y) - gammas, 0.0)
            phi_sum = phi.sum(axis=1, keepdims=True)
            p_target = phi / jnp.maximum(phi_sum, 1e-20)
            rho = jax.nn.softmax(s, axis=1)
            rho = jnp.where(mask, rho, 0.0)
            grad_q = jnp.where(mask, rho - p_target, 0.0)
            hess_q = jnp.where(mask,
                               jnp.maximum(rho * (1.0 - rho), 1e-16), 0.0)
            grad = grad.at[idx.reshape(-1)].add(grad_q.reshape(-1))
            hessian = hessian.at[idx.reshape(-1)].add(hess_q.reshape(-1))
        if weight is not None:
            grad = grad * weight
            hessian = hessian * weight
        return grad, hessian
