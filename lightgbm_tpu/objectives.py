"""Objective functions — pure JAX (grad, hess) producers.

TPU-native re-design of the reference's objective layer
(ref: src/objective/objective_function.cpp `CreateObjectiveFunction`;
regression_objective.hpp, binary_objective.hpp, multiclass_objective.hpp,
xentropy_objective.hpp, rank_objective.hpp).

Every objective is a small class whose `grad_hess(score, label, weight)` is a
pure jnp function traced inside the jitted boosting step — the TPU equivalent
of the reference keeping CUDA mirrors of each objective so gradients never
leave the device (ref: src/objective/cuda/).  Host-side one-time work
(`boost_from_score`, label validation) stays in numpy.

Score layout: [N] for single-score objectives, [N, K] for multiclass.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .utils import log
from .utils.config import Config
from .utils.log import LightGBMError

Array = jax.Array


def _apply_weight(grad, hess, weight):
    if weight is None:
        return grad, hess
    if grad.ndim == 2 and weight.ndim == 1:
        weight = weight[:, None]
    return grad * weight, hess * weight


#: objectives whose hessian is identically 1 before weighting — an
#: OPT-IN registry (exact name match) so a future RegressionL2 subclass
#: with a non-unit hessian cannot silently inherit the packed histogram's
#: derived-count shortcut (booster._packed_const_hess_level)
UNIT_HESSIAN_OBJECTIVES = frozenset(
    {"regression", "regression_l1", "huber", "quantile"})


class ObjectiveFunction:
    """Base objective (ref: include/LightGBM/objective_function.h)."""

    name: str = "custom"
    num_tree_per_iteration: int = 1
    is_ranking: bool = False
    #: whether raw scores pass through a link function in `convert_output`
    need_convert: bool = False

    def __init__(self, config: Config):
        self.config = config

    # -- host-side -------------------------------------------------------
    def init_meta(self, label: np.ndarray, weight: Optional[np.ndarray],
                  query_boundaries: Optional[np.ndarray]) -> None:
        """Validate labels / precompute host-side state
        (ref: ObjectiveFunction::Init(metadata, num_data))."""
        self.num_data = len(label)

    def boost_from_score(self, label: np.ndarray,
                         weight: Optional[np.ndarray]) -> float:
        """Initial score (ref: ObjectiveFunction::BoostFromScore)."""
        return 0.0

    # -- device-side (traced) -------------------------------------------
    def grad_hess(self, score: Array, label: Array,
                  weight: Optional[Array]) -> Tuple[Array, Array]:
        raise NotImplementedError

    def convert_output(self, score: Array) -> Array:
        """Raw score -> output (ref: ObjectiveFunction::ConvertOutput)."""
        return score

    def count_calls(self, calls: int = 1) -> None:
        """Count `calls` gradient calls on the host's counters (lambdarank
        counts its pairs; nothing by default)."""

    # leaf-output refit for L1-family (ref: RenewTreeOutput in
    # regression_objective.hpp); percentile computed per leaf host-side.
    renew_tree_output: Optional[Callable] = None


# ---------------------------------------------------------------- regression
class RegressionL2(ObjectiveFunction):
    """ref: regression_objective.hpp `RegressionL2loss`."""
    name = "regression"

    def __init__(self, config: Config):
        super().__init__(config)
        self.sqrt = bool(config.reg_sqrt)

    def transform_label(self, label: np.ndarray) -> np.ndarray:
        if self.sqrt:
            return np.sign(label) * np.sqrt(np.abs(label))
        return label

    def boost_from_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        if weight is None:
            return float(np.mean(label))
        return float(np.average(label, weights=weight))

    def grad_hess(self, score, label, weight):
        grad = score - label
        hess = jnp.ones_like(score)
        return _apply_weight(grad, hess, weight)

    def convert_output(self, score):
        if self.sqrt:
            return jnp.sign(score) * score * score
        return score


class RegressionL1(RegressionL2):
    """ref: regression_objective.hpp `RegressionL1loss` (grad=sign, median refit)."""
    name = "regression_l1"

    def boost_from_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        return _weighted_percentile(label, weight, 0.5)

    def grad_hess(self, score, label, weight):
        grad = jnp.sign(score - label)
        hess = jnp.ones_like(score)
        return _apply_weight(grad, hess, weight)

    # per-leaf refit: alpha-percentile of residuals (ref: RenewTreeOutput)
    renew_percentile = 0.5


class HuberLoss(RegressionL2):
    """ref: regression_objective.hpp `RegressionHuberLoss`."""
    name = "huber"

    def grad_hess(self, score, label, weight):
        d = score - label
        a = self.config.alpha
        grad = jnp.clip(d, -a, a)
        hess = jnp.ones_like(score)
        return _apply_weight(grad, hess, weight)


class FairLoss(RegressionL2):
    """ref: regression_objective.hpp `RegressionFairLoss`."""
    name = "fair"

    def boost_from_score(self, label, weight):
        return 0.0

    def grad_hess(self, score, label, weight):
        c = self.config.fair_c
        d = score - label
        grad = c * d / (jnp.abs(d) + c)
        hess = c * c / ((jnp.abs(d) + c) ** 2)
        return _apply_weight(grad, hess, weight)


class PoissonLoss(RegressionL2):
    """ref: regression_objective.hpp `RegressionPoissonLoss` (log-link)."""
    name = "poisson"
    need_convert = True

    def init_meta(self, label, weight, qb):
        super().init_meta(label, weight, qb)
        if np.any(label < 0):
            raise LightGBMError("[poisson]: at least one target label is negative")

    def boost_from_score(self, label, weight):
        avg = (np.average(label, weights=weight) if weight is not None
               else np.mean(label))
        return float(np.log(max(avg, 1e-9)))

    def grad_hess(self, score, label, weight):
        exp_s = jnp.exp(score)
        grad = exp_s - label
        hess = jnp.exp(score + self.config.poisson_max_delta_step)
        return _apply_weight(grad, hess, weight)

    def convert_output(self, score):
        return jnp.exp(score)


class QuantileLoss(RegressionL2):
    """ref: regression_objective.hpp `RegressionQuantileloss`."""
    name = "quantile"

    def boost_from_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        return _weighted_percentile(label, weight, self.config.alpha)

    def grad_hess(self, score, label, weight):
        a = self.config.alpha
        d = score - label
        # ties (delta == 0) get gradient 1-alpha, matching the reference
        # (ref: regression_objective.hpp RegressionQuantileloss::GetGradients
        # `if (delta >= 0) grad = 1-alpha else -alpha`)
        grad = jnp.where(d >= 0, 1.0 - a, -a)
        hess = jnp.ones_like(score)
        return _apply_weight(grad, hess, weight)

    @property
    def renew_percentile(self):
        return self.config.alpha


class MAPELoss(RegressionL2):
    """ref: regression_objective.hpp `RegressionMAPELOSS` (weighted-median refit)."""
    name = "mape"

    def init_meta(self, label, weight, qb):
        super().init_meta(label, weight, qb)
        # label-derived weights (ref: MAPE label_weight_)
        lw = 1.0 / np.maximum(1.0, np.abs(label))
        self.label_weight = lw.astype(np.float32)

    def boost_from_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        lw = 1.0 / np.maximum(1.0, np.abs(label))
        if weight is not None:
            lw = lw * weight
        return _weighted_percentile(label, lw, 0.5)

    def grad_hess(self, score, label, weight):
        lw = 1.0 / jnp.maximum(1.0, jnp.abs(label))
        d = score - label
        grad = jnp.sign(d) * lw
        hess = lw
        return _apply_weight(grad, hess, weight)

    renew_percentile = 0.5


class GammaLoss(PoissonLoss):
    """ref: regression_objective.hpp `RegressionGammaLoss` (log-link)."""
    name = "gamma"

    def init_meta(self, label, weight, qb):
        ObjectiveFunction.init_meta(self, label, weight, qb)
        if np.any(label <= 0):
            raise LightGBMError("[gamma]: at least one target label is not positive")

    def grad_hess(self, score, label, weight):
        exp_ns = jnp.exp(-score)
        grad = 1.0 - label * exp_ns
        hess = label * exp_ns
        return _apply_weight(grad, hess, weight)


class TweedieLoss(PoissonLoss):
    """ref: regression_objective.hpp `RegressionTweedieLoss`."""
    name = "tweedie"

    def init_meta(self, label, weight, qb):
        ObjectiveFunction.init_meta(self, label, weight, qb)
        if np.any(label < 0):
            raise LightGBMError("[tweedie]: at least one target label is negative")

    def grad_hess(self, score, label, weight):
        rho = self.config.tweedie_variance_power
        e1 = jnp.exp((1.0 - rho) * score)
        e2 = jnp.exp((2.0 - rho) * score)
        grad = -label * e1 + e2
        hess = -label * (1.0 - rho) * e1 + (2.0 - rho) * e2
        return _apply_weight(grad, hess, weight)


# -------------------------------------------------------------------- binary
class BinaryLogloss(ObjectiveFunction):
    """ref: binary_objective.hpp `BinaryLogloss`."""
    name = "binary"
    need_convert = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.sigmoid = config.sigmoid
        if self.sigmoid <= 0:
            raise LightGBMError("Sigmoid parameter should be greater than zero")

    def init_meta(self, label, weight, qb):
        super().init_meta(label, weight, qb)
        uniq = np.unique(label)
        if not np.all(np.isin(uniq, [0, 1])):
            raise LightGBMError("Binary objective requires labels in {0, 1}, "
                                f"got values {uniq[:5]}")
        cnt_pos = float((label == 1).sum() if weight is None
                        else weight[label == 1].sum())
        cnt_neg = float((label == 0).sum() if weight is None
                        else weight[label == 0].sum())
        self.cnt_pos, self.cnt_neg = cnt_pos, cnt_neg
        # per-class weights (ref: is_unbalance / scale_pos_weight in BinaryLogloss)
        if self.config.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                self.label_weight = (1.0, cnt_pos / cnt_neg)
            else:
                self.label_weight = (cnt_neg / cnt_pos, 1.0)
        else:
            self.label_weight = (1.0, self.config.scale_pos_weight)

    def boost_from_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        w_neg, w_pos = self.label_weight
        spos = self.cnt_pos * w_pos
        sneg = self.cnt_neg * w_neg
        if spos <= 0 or sneg <= 0:
            return 0.0
        pavg = spos / (spos + sneg)
        init = float(np.log(pavg / (1.0 - pavg)) / self.sigmoid)
        log.info(f"[binary:BoostFromScore]: pavg={pavg:.6f} -> initscore={init:.6f}")
        return init

    def grad_hess(self, score, label, weight):
        sig = self.sigmoid
        p = jax.nn.sigmoid(sig * score)
        w_neg, w_pos = self.label_weight
        cls_w = jnp.where(label > 0, w_pos, w_neg)
        grad = sig * (p - label) * cls_w
        hess = sig * sig * p * (1.0 - p) * cls_w
        return _apply_weight(grad, hess, weight)

    def convert_output(self, score):
        return jax.nn.sigmoid(self.sigmoid * score)


# ---------------------------------------------------------------- multiclass
class MulticlassSoftmax(ObjectiveFunction):
    """ref: multiclass_objective.hpp `MulticlassSoftmax`."""
    name = "multiclass"
    need_convert = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = config.num_class
        self.num_tree_per_iteration = config.num_class

    def init_meta(self, label, weight, qb):
        super().init_meta(label, weight, qb)
        ilab = label.astype(np.int64)
        if np.any(ilab < 0) or np.any(ilab >= self.num_class):
            raise LightGBMError(
                f"Label must be in [0, {self.num_class}) for multiclass objective")

    def boost_from_score(self, label, weight):
        # class priors as init scores (ref: MulticlassSoftmax::BoostFromScore
        # returns per-class average of one-hot-ish; LightGBM inits at 0 and lets
        # the softmax handle it — we follow suit for parity)
        return [0.0] * self.num_class

    def grad_hess(self, score, label, weight):
        # score: [N, K]
        p = jax.nn.softmax(score, axis=1)
        onehot = jax.nn.one_hot(label.astype(jnp.int32), self.num_class,
                                dtype=score.dtype)
        grad = p - onehot
        # ref: multiclass_objective.hpp factor_ = num_class/(num_class-1)
        factor = self.num_class / max(self.num_class - 1, 1)
        hess = factor * p * (1.0 - p)
        return _apply_weight(grad, hess, weight)

    def convert_output(self, score):
        return jax.nn.softmax(score, axis=-1)


class MulticlassOVA(ObjectiveFunction):
    """ref: multiclass_objective.hpp `MulticlassOVA` (K independent sigmoids)."""
    name = "multiclassova"
    need_convert = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = config.num_class
        self.num_tree_per_iteration = config.num_class
        self.sigmoid = config.sigmoid

    def init_meta(self, label, weight, qb):
        super().init_meta(label, weight, qb)

    def boost_from_score(self, label, weight):
        return [0.0] * self.num_class

    def grad_hess(self, score, label, weight):
        sig = self.sigmoid
        onehot = jax.nn.one_hot(label.astype(jnp.int32), self.num_class,
                                dtype=score.dtype)
        p = jax.nn.sigmoid(sig * score)
        grad = sig * (p - onehot)
        hess = sig * sig * p * (1.0 - p)
        return _apply_weight(grad, hess, weight)

    def convert_output(self, score):
        return jax.nn.sigmoid(self.sigmoid * score)


# ------------------------------------------------------------- cross-entropy
class CrossEntropy(ObjectiveFunction):
    """ref: xentropy_objective.hpp `CrossEntropy` (labels in [0,1])."""
    name = "cross_entropy"
    need_convert = True

    def init_meta(self, label, weight, qb):
        super().init_meta(label, weight, qb)
        if np.any(label < 0) or np.any(label > 1):
            raise LightGBMError("[cross_entropy]: labels must be in [0, 1]")

    def boost_from_score(self, label, weight):
        avg = (np.average(label, weights=weight) if weight is not None
               else np.mean(label))
        avg = min(max(avg, 1e-9), 1 - 1e-9)
        return float(np.log(avg / (1.0 - avg)))

    def grad_hess(self, score, label, weight):
        p = jax.nn.sigmoid(score)
        grad = p - label
        hess = p * (1.0 - p)
        return _apply_weight(grad, hess, weight)

    def convert_output(self, score):
        return jax.nn.sigmoid(score)


class CrossEntropyLambda(ObjectiveFunction):
    """ref: xentropy_objective.hpp `CrossEntropyLambda` (alternative param)."""
    name = "cross_entropy_lambda"
    need_convert = True

    def init_meta(self, label, weight, qb):
        super().init_meta(label, weight, qb)
        if np.any(label < 0):
            raise LightGBMError("[cross_entropy_lambda]: labels must be >= 0")

    def boost_from_score(self, label, weight):
        avg = (np.average(label, weights=weight) if weight is not None
               else np.mean(label))
        return float(np.log(np.expm1(max(avg, 1e-9)))) if avg > 1e-9 else -9.0

    @staticmethod
    def _point_loss(s, y, w):
        # link: p = 1 - exp(-w * hhat), hhat = log1p(exp(s))
        # (ref: CrossEntropyLambda — weights enter through the link)
        hhat = jnp.log1p(jnp.exp(s))
        wh = w * hhat
        log_p = jnp.log(-jnp.expm1(-jnp.maximum(wh, 1e-12)))
        return -(y * log_p - (1.0 - y) * (-wh))

    def grad_hess(self, score, label, weight):
        w = weight if weight is not None else jnp.ones_like(score)
        # exact grad/hess via elementwise autodiff — bit-matches the
        # reference's hand-derived closed forms for the default w=1 case
        g1 = jax.vmap(jax.grad(self._point_loss), in_axes=(0, 0, 0))
        g2 = jax.vmap(jax.grad(jax.grad(self._point_loss)), in_axes=(0, 0, 0))
        return g1(score, label, w), g2(score, label, w)

    def convert_output(self, score):
        return jnp.log1p(jnp.exp(score))


# --------------------------------------------------------------------- utils
def _weighted_percentile(values: np.ndarray, weight: Optional[np.ndarray],
                         alpha: float) -> float:
    """Weighted percentile matching the reference's PercentileFun semantics
    (ref: regression_objective.hpp `PercentileFun`/`WeightedPercentileFun`)."""
    values = np.asarray(values, dtype=np.float64)
    if len(values) == 0:
        return 0.0
    if weight is None:
        order = np.argsort(values)
        pos = alpha * (len(values) - 1)
        lo = int(np.floor(pos))
        hi = min(lo + 1, len(values) - 1)
        frac = pos - lo
        return float(values[order[lo]] * (1 - frac) + values[order[hi]] * frac)
    order = np.argsort(values)
    sv, sw = values[order], np.asarray(weight, dtype=np.float64)[order]
    cum = np.cumsum(sw) - 0.5 * sw
    t = alpha * sw.sum()
    idx = np.searchsorted(cum, t)
    idx = min(max(idx, 0), len(sv) - 1)
    return float(sv[idx])


_OBJECTIVES: Dict[str, type] = {
    "regression": RegressionL2,
    "regression_l1": RegressionL1,
    "huber": HuberLoss,
    "fair": FairLoss,
    "poisson": PoissonLoss,
    "quantile": QuantileLoss,
    "mape": MAPELoss,
    "gamma": GammaLoss,
    "tweedie": TweedieLoss,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "cross_entropy": CrossEntropy,
    "cross_entropy_lambda": CrossEntropyLambda,
}


def register_objective(name: str, cls: type) -> None:
    _OBJECTIVES[name] = cls


def create_objective(config: Config) -> Optional[ObjectiveFunction]:
    """Factory (ref: ObjectiveFunction::CreateObjectiveFunction)."""
    name = config.objective
    if name in ("custom", "none", None):
        return None
    if name not in _OBJECTIVES:
        # ranking objectives live in rank_objective.py; lazy-register to
        # avoid an import cycle
        from . import rank_objective
        _OBJECTIVES.setdefault("lambdarank", rank_objective.LambdarankNDCG)
        _OBJECTIVES.setdefault("rank_xendcg", rank_objective.RankXENDCG)
    if name not in _OBJECTIVES:
        raise LightGBMError(f"Unknown objective: {name}")
    return _OBJECTIVES[name](config)
