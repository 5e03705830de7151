"""The comparison that decides `correct` for a training cell.

`stated` is what the program's timed rounds stated (its first trees, read
from `Booster.dump_model()`); `readings` is what the plain reference reads
when it follows those trees over the same rows
(`perfbench/reference/gbdt.py`).  These numbers are formed, each the
worst over the followed rounds; those that the cell's file gives a limit
are compared:

  leaf_value_gap   worst leaf: |stated value - reference value| over the
                   larger of the reference's step of that leaf and of the
                   median leaf.  Gradients, the rows' partition into
                   leaves, the score update (rounds after the first).
  leaf_count_gap   worst leaf: |stated count - reference count| over the
                   reference count.  The partition, and rows left out.
  split_gain_gap   worst internal node: how far the gain of the split the
                   program chose there lies below the best gain the
                   reference finds at that node, over that best.
                   Histogram kernel and split scan.  A widest gap over
                   near-tied candidates: at a weak node the program's
                   float32 rounding can turn the choice, so it swings.
  split_gain_loss  the same shortfall at the worst node, over the sum of
                   the best gains of the tree's checked nodes: the share
                   of the tree's gain that the worst choice gives away.
                   A weak node's near-tie weighs what the node is worth.

`stated_by(readings)` puts another computation in the program's place: the
leaf values, counts and favourite splits it reads on the same trees.  That
is how the lower-precision control is judged by the same numbers.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from .reference.gbdt import RoundReading, TreeArrays

NUMBERS = ("leaf_value_gap", "leaf_count_gap", "split_gain_gap",
           "split_gain_loss")


def stated_of(trees: Sequence[TreeArrays]) -> List[dict]:
    """What the program's trees state, in the comparison's terms."""
    return [{"leaf_value": t.leaf_value, "leaf_count": t.leaf_count,
             "leaf_weight": t.leaf_weight,
             "split": np.stack([t.split_feature.astype(np.int64),
                                np.floor(t.threshold).astype(np.int64)],
                               axis=1).reshape(-1, 2)}
            for t in trees]


def stated_by(readings: Sequence[RoundReading], trees: Sequence[TreeArrays]
              ) -> List[dict]:
    """What another computation (the control) states on the same trees:
    its leaf values and counts, and at each node it checked its own
    favourite split (the trees' split elsewhere)."""
    out = []
    for r, s in zip(readings, stated_of(trees)):
        split = s["split"].copy()
        split[r.nodes] = r.best_split
        out.append({"leaf_value": r.leaf_value, "leaf_count": r.leaf_count,
                    "leaf_weight": r.leaf_hess, "split": split})
    return out


def _leaf_gaps(s: dict, r: RoundReading):
    """Per leaf of one round: the value gap and the count gap."""
    scale = np.maximum(np.abs(r.leaf_step), np.median(np.abs(r.leaf_step)))
    with np.errstate(divide="ignore", invalid="ignore"):
        return (np.abs(s["leaf_value"] - r.leaf_value) / scale,
                np.abs(s["leaf_count"] - r.leaf_count) / r.leaf_count)


def _shortfalls(s: dict, r: RoundReading) -> np.ndarray:
    """Per checked node of one round: the reference's best gain less the
    reference's gain of the split that is stated there (inf where the
    stated split is none the reference allows)."""
    out = np.zeros(len(r.nodes))
    for k, node in enumerate(r.nodes):
        f, t = s["split"][int(node)]
        chosen = r.gains[k][int(f), int(np.clip(t, 0, 255))]
        out[k] = r.best_gain[k] - chosen if np.isfinite(chosen) else np.inf
    return out


def compare(stated: Sequence[dict], readings: Sequence[RoundReading]
            ) -> Dict[str, float]:
    """The three numbers, worst over rounds.  A number that cannot be
    formed (no finite reference) comes out as inf."""
    if not stated or len(stated) != len(readings):
        return {k: float("inf") for k in NUMBERS}
    out = {k: 0.0 for k in NUMBERS}
    for s, r in zip(stated, readings):
        if len(s["leaf_value"]) != len(r.leaf_value):
            return {k: float("inf") for k in NUMBERS}
        v, c = _leaf_gaps(s, r)
        short = _shortfalls(s, r)
        tree_gain = float(np.sum(r.best_gain[r.best_gain > 0]))
        with np.errstate(divide="ignore", invalid="ignore"):
            gap = np.where(r.best_gain > 0, short / r.best_gain, np.inf)
            loss = short / tree_gain if tree_gain > 0 else \
                np.full_like(short, np.inf)
        for k, x in (("leaf_value_gap", np.max(v)),
                     ("leaf_count_gap", np.max(c)),
                     ("split_gain_gap", np.max(gap, initial=0.0)),
                     ("split_gain_loss", np.max(loss, initial=0.0))):
            x = float(x) if np.isfinite(x) else float("inf")
            out[k] = max(out[k], x)
    return out


def worst(stated: Sequence[dict], readings: Sequence[RoundReading]) -> dict:
    """Where the worst leaf of each leaf number sits, and what was read
    there: for the run's earlier lines, not for the verdict."""
    out = {}
    for i, (s, r) in enumerate(zip(stated, readings)):
        if len(s["leaf_value"]) != len(r.leaf_value):
            continue
        for key, x in zip(("leaf_value_gap", "leaf_count_gap"),
                          _leaf_gaps(s, r)):
            j = int(np.nanargmax(x))
            if key not in out or x[j] > out[key]["gap"]:
                out[key] = {
                    "gap": float(x[j]), "round": i, "leaf": j,
                    "stated_value": float(s["leaf_value"][j]),
                    "reference_value": float(r.leaf_value[j]),
                    "stated_count": float(s["leaf_count"][j]),
                    "reference_count": float(r.leaf_count[j]),
                    "stated_hessian": float(s["leaf_weight"][j]),
                    "reference_hessian": float(r.leaf_hess[j]),
                    "median_step": float(np.median(np.abs(r.leaf_step)))}
        short = _shortfalls(s, r)
        if len(short) and ("split" not in out
                           or short.max() > out["split"]["shortfall"]):
            k = int(np.argmax(short))
            out["split"] = {
                "shortfall": float(short[k]), "round": i,
                "node": int(r.nodes[k]), "best_gain": float(r.best_gain[k]),
                "tree_gain": float(np.sum(r.best_gain[r.best_gain > 0])),
                "root_gain": float(r.best_gain[0])}
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True where every compared number is within its limit."""
    return all(k in numbers and numbers[k] <= float(limits[k])
               for k in limits)


def compared_lines(numbers: Dict[str, float], limits: Dict[str, float]
                   ) -> Dict[str, dict]:
    """Each number beside its limit, for the result line and stderr."""
    return {k: {"value": numbers.get(k, float("inf")),
                "limit": float(limits[k])} for k in limits}
