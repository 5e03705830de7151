"""Plain reference for histogram GBDT training with LightGBM's lambdarank.

The pair rule (microsoft/LightGBM `src/objective/rank_objective.hpp`,
`LambdarankNDCG::GetGradientsForOneQuery`, v3, from memory; the cell's
configuration states it under `rule`) in float64 numpy, one query at a
time, nothing of the program imported:

  docs of the query stable-sorted by score, descending; for each i among
  the top min(T, n) ranks and each j > i whose label differs, `high` the
  better-labelled doc of the pair, r its rank, s its score:
    Δ = |gain_high - gain_low| * |1/log2(2+r_high) - 1/log2(2+r_low)|
        * inverse max DCG of the query's labels at T
    norm, and the query's best score != its worst:  Δ /= 0.01 + |s_high - s_low|
    p = 1 / (1 + exp(σ (s_high - s_low)))
    λ = -σ p Δ,  h = σ² p (1 - p) Δ;   high += λ, low -= λ, both += h
  norm, and S = Σ_pairs 2|λ| > 0:  the query's λ and h times log2(1+S)/S

`rule="per_doc_norm"` is the rule the program followed before: no score
distance, and S = Σ_docs |λ_doc| (the control that has to fail).

`follow` follows the program's trees from score 0 (lambdarank boosts from
no average) as `gbdt.follow` does: per round the lambdas at the
reference's own float64 scores, the rows routed through the stated tree,
per-leaf sums, the checked nodes' histograms and their gains, all with
`gbdt.py`'s device functions (float32 at `highest`, compensated sums; the
histograms a group of at most `COLUMNS_A_CALL` columns at a time, so a
block's one-hot fits beside the rows), then score += the reference's own
leaf steps.  `dtype=jnp.bfloat16` rounds the lambdas and hessians to
bfloat16 before they are summed: the lower-precision control.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import gbdt
from .gbdt import RoundReading, TreeArrays, tree_from_dump  # noqa: F401

#: columns of one histogram call (a block's one-hot is 16,384 rows x 256
#: codes x this many f32)
COLUMNS_A_CALL = 16
RULES = ("upstream", "per_doc_norm")


def label_gains(n_grades: int = 31) -> np.ndarray:
    """LightGBM's default `label_gain`: 2^i - 1."""
    return np.asarray([(1 << i) - 1 for i in range(n_grades)], np.float64)


def inverse_max_dcg(label: np.ndarray, gain: np.ndarray, T: int) -> float:
    """1 / (the best DCG of the query's labels at T), 0 where it is 0."""
    top = np.sort(gain[label.astype(np.int64)])[::-1][:T]
    dcg = float(np.sum(top / np.log2(np.arange(2, len(top) + 2))))
    return 1.0 / dcg if dcg > 0 else 0.0


def query_lambdas(score: np.ndarray, label: np.ndarray, gain: np.ndarray,
                  T: int, sigma: float, norm: bool, rule: str = "upstream"):
    """(λ, h) float64 of one query's docs (module docstring)."""
    n = len(score)
    lam = np.zeros(n)
    hess = np.zeros(n)
    inv_max = inverse_max_dcg(label, gain, T)
    if n < 2 or inv_max == 0.0:
        return lam, hess
    order = np.argsort(-np.asarray(score, np.float64), kind="stable")
    s = np.asarray(score, np.float64)[order]
    y = np.asarray(label)[order]
    g = gain[y.astype(np.int64)]
    disc = 1.0 / np.log2(np.arange(n) + 2.0)
    t = min(T, n)
    valid = (np.arange(n)[None, :] > np.arange(t)[:, None]) \
        & (y[:t, None] != y[None, :])
    high_is_i = y[:t, None] > y[None, :]
    ds = np.where(high_is_i, 1.0, -1.0) * (s[:t, None] - s[None, :])
    delta = np.abs(g[:t, None] - g[None, :]) \
        * np.abs(disc[:t, None] - disc[None, :]) * inv_max
    if rule == "upstream" and norm and s[0] != s[-1]:
        delta = delta / (0.01 + np.abs(ds))
    with np.errstate(over="ignore"):
        p = 1.0 / (1.0 + np.exp(sigma * ds))
    pair_lam = np.where(valid, -sigma * p * delta, 0.0)
    pair_h = np.where(valid, sigma * sigma * p * (1.0 - p) * delta, 0.0)
    lam += np.where(high_is_i, -pair_lam, pair_lam).sum(axis=0)
    lam[:t] += np.where(high_is_i, pair_lam, -pair_lam).sum(axis=1)
    hess += pair_h.sum(axis=0)
    hess[:t] += pair_h.sum(axis=1)
    if norm:
        total = 2.0 * np.abs(pair_lam).sum() if rule == "upstream" \
            else np.abs(lam).sum()
        if total > 0:
            factor = np.log2(1.0 + total) / total
            lam *= factor
            hess *= factor
    out_l = np.empty(n)
    out_h = np.empty(n)
    out_l[order] = lam
    out_h[order] = hess
    return out_l, out_h


def lambdas(score: np.ndarray, label: np.ndarray,
            query_boundaries: np.ndarray, params: dict,
            rule: str = "upstream", threads: int = 0):
    """(λ, h) float64 [N] of every query, by `query_lambdas` on threads."""
    if rule not in RULES:
        raise ValueError(f"unknown pair rule {rule!r}")
    qb = np.asarray(query_boundaries, np.int64)
    gain = label_gains()
    T = int(params.get("lambdarank_truncation_level", 30))
    sigma = float(params.get("sigmoid", 1.0))
    norm = bool(params.get("lambdarank_norm", True))
    lam = np.zeros(len(score))
    hess = np.zeros(len(score))

    def work(q):
        lo, hi = qb[q], qb[q + 1]
        lam[lo:hi], hess[lo:hi] = query_lambdas(
            score[lo:hi], label[lo:hi], gain, T, sigma, norm, rule)

    threads = threads or max(1, min(12, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(work, range(len(qb) - 1)))
    return lam, hess


def _column_groups(n_feat: int) -> List[tuple]:
    k = -(-n_feat // COLUMNS_A_CALL)
    base, extra = divmod(n_feat, k)
    out, c0 = [], 0
    for j in range(k):
        c1 = c0 + base + (j < extra)
        out.append((c0, c1))
        c0 = c1
    return out


def node_histograms(codes, leaf, grad, hess, member, dtype=jnp.float32):
    """`gbdt.node_histograms` a group of columns at a time, concatenated
    along the column axis (host arrays)."""
    parts = [jax.device_get(gbdt.node_histograms(
        codes[c0:c1], leaf, grad, hess, member, dtype))
        for c0, c1 in _column_groups(codes.shape[0])]
    return (np.concatenate([p[0] for p in parts], axis=1),
            np.concatenate([p[1] for p in parts], axis=1))


def follow(codes, label, query_boundaries, trees: List[TreeArrays],
           params: dict, dtype=jnp.float32, n_check: int = 1 << 30,
           seed: int = 0, update_scores: bool = True,
           rule: str = "upstream", rows: Optional[np.ndarray] = None
           ) -> List[RoundReading]:
    """Follow the given trees round by round over the rows (module
    docstring).  `codes` [F, N] uint8, `label` [N] and `query_boundaries`
    [Q + 1] are host arrays.  `rows` (a permutation of the rows) plants
    the fault "documents in the wrong queries": the lambdas are computed
    over the rows in that order under the same boundaries.
    `update_scores=False`: every round sees score 0."""
    label = np.asarray(label)
    codes_d = jnp.asarray(codes)
    score = np.zeros(len(label))
    out = []
    with jax.default_matmul_precision("highest"):
        for r, tree in enumerate(trees):
            if rows is None:
                lam, h = lambdas(score, label, query_boundaries, params,
                                 rule)
            else:
                lam, h = np.empty(len(label)), np.empty(len(label))
                lam[rows], h[rows] = lambdas(score[rows], label[rows],
                                             query_boundaries, params, rule)
            g = jnp.asarray(lam.astype(np.float32))
            hs = jnp.asarray(h.astype(np.float32))
            leaf = gbdt.route(codes_d, tree)
            sums, count = gbdt.leaf_sums(leaf, g, hs, tree.num_leaves, dtype)
            nodes = gbdt.nodes_to_check(tree, n_check, seed, r)
            member = gbdt.leaves_under(tree)[nodes].astype(np.float32)
            if len(nodes):
                gh, cnt = node_histograms(codes_d, leaf, g, hs,
                                          jnp.asarray(member), dtype)
            else:
                gh = cnt = np.zeros((0,))
            reading = gbdt.read_round(jax.device_get(sums),
                                      jax.device_get(count), gh, cnt, nodes,
                                      params, 0.0)
            out.append(reading)
            if update_scores:
                score = score + reading.leaf_step[np.asarray(leaf)]
    return out
