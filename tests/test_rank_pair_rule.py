"""`LambdarankNDCG` follows LightGBM's lambdarank pair rule.

The rule (upstream `rank_objective.hpp` `GetGradientsForOneQuery`, v3, as
`perfbench/configs/istellax220-lgbrank-l31.json` states it: the distance
regularisation and the pair-sum norm) is computed independently in float64
numpy by `perfbench/reference/gbdt_rank.py`; the program's float32
gradients must equal it on every shape below.  Tolerance: the program sums
a document's pairs in float32 (up to T x P terms) where the reference sums
in float64, so a gradient may differ by float32 rounding of its largest
terms: 2e-6 of the query set's largest |lambda|, plus 1e-4 of its own
size.  The rule the program followed before (per-document norm, no score
distance) misses by far more.
"""
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
import lightgbm_tpu.rank_objective as ro
from lightgbm_tpu import telemetry
from lightgbm_tpu.rank_objective import LambdarankNDCG
from lightgbm_tpu.utils.config import Config
from perfbench.reference import gbdt_rank

ATOL, RTOL = 2e-6, 1e-4


def _case(name):
    """(sizes, labels, scores, params) of one shape."""
    rng = np.random.RandomState(zlib.crc32(name.encode()))
    sizes = {"single_docs": [1] * 6 + [3, 2],
             "equal_labels": [12, 40, 7],
             "tied_scores": [25, 60, 31],
             "shorter_and_longer_than_T": [5, 29, 30, 31, 77, 140],
             "norm_off": [9, 33, 64],
             "best_equals_worst": [16, 40, 8],
             "several_buckets": list(rng.randint(20, 61, 60))
             + list(rng.randint(300, 501, 8))}[name]
    sizes = np.asarray(sizes, np.int64)
    qb = np.concatenate([[0], np.cumsum(sizes)])
    n = int(qb[-1])
    label = rng.randint(0, 5, n).astype(np.float64)
    score = rng.randn(n).astype(np.float32)
    params = {"objective": "lambdarank"}
    if name == "equal_labels":
        label[qb[0]:qb[1]] = 3.0           # one query of one grade
        label[qb[2]:qb[3]] = 0.0
    if name == "tied_scores":
        score = np.round(score * 2) / 2     # many equal scores a query
    if name == "norm_off":
        params["lambdarank_norm"] = False
    if name == "best_equals_worst":
        score[qb[0]:qb[1]] = 0.25           # one score in a whole query
        score[qb[2]:qb[3]] = 0.0
    return sizes, qb, label, score, params


CASES = ["single_docs", "equal_labels", "tied_scores",
         "shorter_and_longer_than_T", "norm_off", "best_equals_worst",
         "several_buckets"]


def _program(qb, label, score, params):
    obj = LambdarankNDCG(Config(params))
    obj.init_meta(label, None, qb)
    g, h = obj.grad_hess(jnp.asarray(score), jnp.asarray(label, jnp.float32),
                         None)
    return obj, np.asarray(g, np.float64), np.asarray(h, np.float64)


def _close(got, want):
    return np.abs(got - want) <= ATOL * np.abs(want).max() \
        + RTOL * np.abs(want)


@pytest.mark.parametrize("case", CASES)
def test_gradients_follow_the_upstream_rule(case):
    sizes, qb, label, score, params = _case(case)
    obj, g, h = _program(qb, label, score, params)
    lam, hess = gbdt_rank.lambdas(score.astype(np.float64), label, qb,
                                  params)
    assert _close(g, lam).all(), np.abs(g - lam).max()
    assert _close(h, hess).all(), np.abs(h - hess).max()
    if case == "single_docs":
        assert (g[:6] == 0).all() and (h[:6] == 0).all()
    if case == "equal_labels":
        assert (g[qb[0]:qb[1]] == 0).all() and (g[qb[2]:qb[3]] == 0).all()
    if case == "several_buckets":
        assert len(obj._buckets) > 1
    if case == "shorter_and_longer_than_T":
        # a query longer than T: docs below rank T still get the pairs
        # they form with the top T
        q = 5
        assert np.count_nonzero(g[qb[q]:qb[q + 1]]) > 30


@pytest.mark.parametrize("case", ["tied_scores", "several_buckets"])
def test_the_previous_rule_misses_the_reference(case):
    """The per-document norm without the score distance (the program's
    rule before this one) is caught by the tolerance above."""
    sizes, qb, label, score, params = _case(case)
    lam, _ = gbdt_rank.lambdas(score.astype(np.float64), label, qb, params)
    old, _ = gbdt_rank.lambdas(score.astype(np.float64), label, qb, params,
                               rule="per_doc_norm")
    assert not _close(old, lam).all()
    assert np.abs(old - lam).max() > 100 * ATOL * np.abs(lam).max()


def test_block_size_does_not_change_the_gradients(monkeypatch):
    """One query a block and a whole bucket a block: the same bits."""
    sizes, qb, label, score, params = _case("several_buckets")
    _, g_big, h_big = _program(qb, label, score, params)
    monkeypatch.setattr(ro, "PAIR_BLOCK_BYTES", 1)
    obj, g_one, h_one = _program(qb, label, score, params)
    assert all(b["maps"]["gather"].shape[1] == 1 for b in obj._buckets)
    np.testing.assert_array_equal(g_one, g_big)
    np.testing.assert_array_equal(h_one, h_big)


def test_the_maps_are_arguments_and_the_pairs_are_counted():
    """The booster hands the query maps to `jit_lambdarank` as arguments
    (no constant of their size in the program), and each round counts the
    pair slots and positions; the gauges name the queries and buckets."""
    sizes, qb, label, score, params = _case("several_buckets")
    X = np.random.RandomState(0).randn(len(label), 4)
    ds = lgb.Dataset(X, label=label, group=sizes)
    bst = lgb.Booster(params={**params, "num_leaves": 7, "verbosity": -1},
                      train_set=ds)
    obj = bst.objective_
    maps = obj.device_maps()
    text = jax.jit(obj.lambdarank).lower(
        bst._train_score, bst._dd.label, None, maps).as_text()
    main = next(line for line in text.splitlines() if "@main(" in line)
    consts = [line for line in text.splitlines() if "constant" in line]
    for b in maps:
        typ = "tensor<%sxi32>" % "x".join(map(str, b["gather"].shape))
        assert typ in main
        assert not any(typ in line for line in consts)
    slots = sum(int(np.prod(b["gather"].shape))
                * min(30, b["gather"].shape[-1]) for b in maps)
    positions = sum(min(30, n) * (n - 1) - min(30, n) * (min(30, n) - 1) // 2
                    for n in sizes)
    reg = telemetry.REGISTRY
    before = (reg.counter("rank.pair_slots").value,
              reg.counter("rank.pair_positions").value)
    for _ in range(2):
        bst.update()
    assert reg.counter("rank.pair_slots").value - before[0] == 2 * slots
    assert reg.counter("rank.pair_positions").value - before[1] \
        == 2 * positions
    assert reg.gauge("rank.queries").value == len(sizes)
    assert reg.gauge("rank.buckets").value == len(maps) > 1

