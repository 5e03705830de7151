"""The plain reference of `gbdt.py`, for trees with CATEGORICAL nodes.

A configuration that declares columns through `categorical_feature` grows
nodes that send a SET of categories left (`"decision_type": "=="`,
`"threshold": "3||7||12"` in `Booster.dump_model()`), chosen by LightGBM's
rule for categorical columns as the configuration's file states it under
`rule`.  This module follows such trees the way `gbdt.follow` follows
numerical ones, with `gbdt`'s own functions wherever a node's kind makes
no difference (gradients, leaf sums, leaf values, node histograms per
code, the score update): plain `jax.numpy`, float32 at `highest`,
compensated sums, nothing of the program imported.  What it adds:

  tree_from_dump  reads `==` nodes into a [L-1, 256] left-set table over
                  CODES (a declared column's raw value is its code)
  route           a row goes left at a categorical node where its code is
                  in the node's set (eight 32-bit words a node and a
                  shift: no per-row table look-up), else `code <= t`
  split_gains     for a declared column the candidates of the stated rule,
                  evaluated in float64 on the host over the node's
                  per-code sums, in place of the thresholds `code <= t`

It takes no bin mapper from the program.  Which categories of a declared
column are "other" (never in a left set) it works out itself from the
first `sample_rows` training rows by the stated binning rule
(`kept_categories`).

A declared column's row of a node's [F, 256] gain grid holds, by slot:
0..31 the ascending prefixes of 1..32 categories, 32..63 the descending
ones, 64.. the one-vs-rest candidates (by code), and at `STATED_SLOT` the
gain of the set the followed tree states at that node (-inf where the rule
does not allow that set): a categorical node's `threshold` is
`STATED_SLOT`, so `check.py` reads the stated split's gain there as it
reads `gains[column, t]` of a numerical node.  The best gain of a node is
the best over every other slot.

`tree_from_dump`, `follow(codes, label, trees, params, n_check=, seed=,
dtype=, update_scores=)` keep `gbdt.py`'s signatures but for `follow`'s
`categorical` (`declared_columns(config)`; None: no declared column, and
every number is `gbdt.follow`'s).
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import gbdt
from .gbdt import CODES, RoundReading  # noqa: F401

STATED_SLOT = CODES - 1     # where a categorical node's own set is priced
ASC, DESC, ONE = 0, 32, 64  # first slot of each family of candidates
# two ratios g / (h + cat_smooth) closer than this are taken as tied: the
# program orders float32 sums, the reference float64 ones
RATIO_TIE = 1e-5


class TreeArrays(NamedTuple):
    """`gbdt.TreeArrays` and, per internal node, whether it is
    categorical and the codes it sends left."""
    split_feature: np.ndarray   # [L-1] int32
    threshold: np.ndarray       # [L-1] f64: code <= t left; STATED_SLOT
    left: np.ndarray            # [L-1] int32
    right: np.ndarray           # [L-1] int32
    leaf_value: np.ndarray      # [L] f64
    leaf_count: np.ndarray      # [L] f64
    split_gain: np.ndarray      # [L-1] f64
    leaf_weight: np.ndarray     # [L] f64
    is_cat: np.ndarray          # [L-1] bool
    left_set: np.ndarray        # [L-1, 256] bool: codes that go left

    @property
    def num_leaves(self) -> int:
        return len(self.leaf_value)


def tree_from_dump(tree_info: dict) -> TreeArrays:
    """Flatten one `tree_info` entry of `Booster.dump_model()`; a node
    with `decision_type` `==` lists its left set's category values joined
    by `||`."""
    n_leaves = int(tree_info["num_leaves"])
    ni = max(n_leaves - 1, 0)
    sf = np.zeros(ni, np.int32)
    th = np.zeros(ni, np.float64)
    lc = np.zeros(ni, np.int32)
    rc = np.zeros(ni, np.int32)
    gain = np.zeros(ni, np.float64)
    lv = np.zeros(n_leaves, np.float64)
    cnt = np.zeros(n_leaves, np.float64)
    wgt = np.zeros(n_leaves, np.float64)
    is_cat = np.zeros(ni, bool)
    left_set = np.zeros((ni, CODES), bool)

    def ref_of(node: dict) -> int:
        return ~int(node["leaf_index"]) if "leaf_index" in node \
            else int(node["split_index"])

    stack = [tree_info["tree_structure"]]
    while stack:
        node = stack.pop()
        if "leaf_index" in node:
            i = int(node["leaf_index"])
            lv[i] = node["leaf_value"]
            cnt[i] = node.get("leaf_count", 0)
            wgt[i] = node.get("leaf_weight", 0.0)
            continue
        i = int(node["split_index"])
        kind = node.get("decision_type", "<=")
        if kind == "==":
            values = [int(v) for v in str(node["threshold"]).split("||")]
            if not values or min(values) < 0 or max(values) >= CODES:
                raise ValueError(f"node {i}: categories {values} are no "
                                 "codes")
            is_cat[i] = True
            left_set[i, values] = True
            th[i] = STATED_SLOT
        elif kind == "<=":
            th[i] = node["threshold"]
        else:
            raise ValueError(f"node {i}: decision_type {kind!r}")
        sf[i] = node["split_feature"]
        gain[i] = node.get("split_gain", 0.0)
        lc[i] = ref_of(node["left_child"])
        rc[i] = ref_of(node["right_child"])
        stack += [node["left_child"], node["right_child"]]
    return TreeArrays(sf, th, lc, rc, lv, cnt, gain, wgt, is_cat, left_set)


def declared_columns(config: dict) -> dict:
    """What `follow` needs to know of a configuration's declared columns:
    their indices, the rows the binning rule counts, and `max_bin`."""
    names = [c["name"] for c in config["data"]["columns"]]
    return {"columns": [names.index(c)
                        for c in config.get("categorical_feature", [])],
            "sample_rows": int(config.get("sample_rows", 200_000)),
            "max_bin": int(config["params"].get("max_bin", 255))}


def kept_categories(sample: np.ndarray, max_bin: int) -> np.ndarray:
    """[256] bool: the codes of one declared column that are categories of
    their own; every other code is "other".  The stated binning rule:
    counted on the sample, ordered by count descending (ties: the smaller
    value first), at most `max_bin - 1` kept, and where more exist only
    the leading ones that reach 99% of the sampled rows."""
    counts = np.bincount(np.asarray(sample, np.int64), minlength=CODES)
    cats = np.nonzero(counts)[0]
    cats = cats[np.argsort(-counts[cats], kind="stable")]
    keep = min(len(cats), max_bin - 1)
    if len(cats) > keep:
        reach = np.cumsum(counts[cats[:keep]])
        keep = min(int(np.searchsorted(reach, 0.99 * counts.sum())) + 1,
                   keep)
    kept = np.zeros(CODES, bool)
    kept[cats[:keep]] = True
    return kept


# ------------------------------------------------------------------ routing
def set_words(left_set: np.ndarray) -> np.ndarray:
    """[K, 256] bool -> [K, 8] uint32: bit j of word w is code 32 w + j."""
    bits = left_set.reshape(len(left_set), 8, 32).astype(np.uint64)
    return (bits << np.arange(32, dtype=np.uint64)).sum(axis=2).astype(
        np.uint32)


@jax.jit
def _route(codes, order, split_feature, threshold_code, left, right, is_cat,
           words):
    """`gbdt._route` with the categorical nodes' sets: one sweep over the
    internal nodes, parents before children."""
    def step(node, i):
        col = jax.lax.dynamic_index_in_dim(codes, split_feature[i], 0,
                                           keepdims=False).astype(jnp.uint32)
        w = words[i]
        word = jnp.zeros_like(col)
        for k in range(8):
            word = jnp.where((col >> 5) == k, w[k], word)
        in_set = ((word >> (col & 31)) & 1) == 1
        go_left = jnp.where(is_cat[i], in_set,
                            col.astype(jnp.int32) <= threshold_code[i])
        nxt = jnp.where(go_left, left[i], right[i])
        return jnp.where(node == i, nxt, node), None

    node, _ = jax.lax.scan(step, jnp.zeros((codes.shape[1],), jnp.int32),
                           order)
    return ~node


def route(codes, tree: TreeArrays):
    """Leaf index [N] int32 of every row of `codes` [F, N] under the tree:
    at a categorical node a code of the node's set goes left, elsewhere
    code <= floor(threshold)."""
    if tree.num_leaves < 2:
        return jnp.zeros((codes.shape[1],), jnp.int32)
    return _route(codes, jnp.asarray(gbdt.parents_first(tree), jnp.int32),
                  jnp.asarray(tree.split_feature, jnp.int32),
                  jnp.asarray(np.floor(tree.threshold).astype(np.int32)),
                  jnp.asarray(tree.left, jnp.int32),
                  jnp.asarray(tree.right, jnp.int32),
                  jnp.asarray(tree.is_cat),
                  jnp.asarray(set_words(tree.left_set)))


# ------------------------------------------------- the categorical candidates
def _rule(params: dict) -> dict:
    return {"lam": float(params.get("lambda_l2", 0.0))
            + float(params.get("cat_l2", 10.0)),
            "cat_smooth": float(params.get("cat_smooth", 10.0)),
            "max_cat_threshold": int(params.get("max_cat_threshold", 32)),
            "max_cat_to_onehot": int(params.get("max_cat_to_onehot", 4)),
            "min_data_per_group": float(params.get("min_data_per_group",
                                                   100)),
            "min_data": float(params.get("min_data_in_leaf", 20)),
            "min_hess": float(params.get("min_sum_hessian_in_leaf", 1e-3))}


def _gain(left, total, lam: float) -> float:
    """The stated gain of (G_L, H_L) against the rest of (G, H)."""
    gl, hl = left
    g, h = total
    with np.errstate(divide="ignore", invalid="ignore"):
        return gl * gl / (hl + lam) + (g - gl) ** 2 / (h - hl + lam) \
            - g * g / (h + lam)


def _sides_ok(n_left, h_left, n, h, rule) -> bool:
    return n_left >= rule["min_data"] and n - n_left >= rule["min_data"] \
        and h_left >= rule["min_hess"] and h - h_left >= rule["min_hess"]


def walk_prefixes(order, g, h, n, rule) -> np.ndarray:
    """Gains of the prefixes of `order` (codes, best end first), -inf
    where the group gate or a side's gate refuses the prefix: rows gained
    are counted since the last candidate, and the count starts again
    after each candidate."""
    total = (g.sum(), h.sum())
    rows = n.sum()
    out = np.full(len(order), -np.inf)
    gl = hl = nl = group = 0.0
    for k, b in enumerate(order):
        gl, hl, nl, group = gl + g[b], hl + h[b], nl + n[b], group + n[b]
        if not _sides_ok(nl, hl, rows, total[1], rule) \
                or rows - nl < rule["min_data_per_group"] \
                or group < rule["min_data_per_group"]:
            continue
        group = 0.0
        out[k] = _gain((gl, hl), total, rule["lam"])
    return out


def categorical_gains(g, h, n, kept, rule, stated=None) -> np.ndarray:
    """[256] gains of one declared column's candidates at one node, by
    slot (module docstring), from the node's per-code sums `g`, `h`, `n`
    [256] f64 and `kept` [256] bool.  `stated` [256] bool: the left set
    the followed tree states here, priced at `STATED_SLOT`."""
    out = np.full(CODES, -np.inf)
    admitted = np.nonzero(kept & (n >= rule["cat_smooth"]))[0]
    used = len(admitted)
    total = (g.sum(), h.sum())
    rows = n.sum()
    # "other" and every category under cat_smooth stay on the right: their
    # sums are in the totals and in no prefix
    if used == 0:
        return out
    ratio = g / (h + rule["cat_smooth"])
    one_vs_rest = used <= rule["max_cat_to_onehot"]
    k_max = min(rule["max_cat_threshold"], (used + 1) // 2)
    if one_vs_rest:
        for j, b in enumerate(admitted):
            if _sides_ok(n[b], h[b], rows, total[1], rule):
                out[ONE + j] = _gain((g[b], h[b]), total, rule["lam"])
    else:
        asc = admitted[np.argsort(ratio[admitted], kind="stable")]
        out[ASC:ASC + k_max] = walk_prefixes(asc[:k_max], g, h, n, rule)
        out[DESC:DESC + k_max] = walk_prefixes(asc[::-1][:k_max], g, h, n,
                                               rule)
    if stated is None or not stated.any():
        return out
    members = np.nonzero(stated)[0]
    if not np.isin(members, admitted).all():
        return out                  # an "other" or too rare category left
    if one_vs_rest:
        if len(members) == 1:
            out[STATED_SLOT] = out[ONE + int(np.searchsorted(admitted,
                                                             members[0]))]
        return out
    rest = np.setdiff1d(admitted, members)
    if len(members) > k_max or not len(rest):
        return out
    # the set has to be one end of the sorted order, up to ties
    if ratio[members].max() <= ratio[rest].min() + RATIO_TIE:
        order = members[np.argsort(ratio[members], kind="stable")]
    elif ratio[members].min() >= ratio[rest].max() - RATIO_TIE:
        order = members[np.argsort(-ratio[members], kind="stable")]
    else:
        return out
    out[STATED_SLOT] = walk_prefixes(order, g, h, n, rule)[-1]
    return out


def split_gains(gh: np.ndarray, cnt: np.ndarray, params: dict,
                kept: Optional[dict] = None, stated=None) -> np.ndarray:
    """`gbdt.split_gains` [F, 256] with the rows of the declared columns
    (`kept`: column -> [256] bool) replaced by their categorical
    candidates.  `stated` (column, [256] bool): the node's own left set."""
    gains = gbdt.split_gains(gh, cnt, params)
    rule = _rule(params)
    for f, keep in (kept or {}).items():
        mine = stated[1] if stated is not None and stated[0] == f else None
        gains[f] = categorical_gains(
            gh[f, :, 0].astype(np.float64), gh[f, :, 1].astype(np.float64),
            cnt[f].astype(np.float64), keep, rule, mine)
    return gains


def read_round(sums, count, gh, cnt, nodes, params: dict, bias: float,
               tree: TreeArrays, kept: dict) -> RoundReading:
    """`gbdt.read_round` with the categorical candidates; a node's best
    gain leaves out the slot that prices the tree's own set."""
    plain = gbdt.read_round(sums, count, np.zeros((0,)), np.zeros((0,)),
                            np.zeros((0,), np.int64), params, bias)
    gains = []
    for k, node in enumerate(nodes):
        stated = (int(tree.split_feature[node]), tree.left_set[node]) \
            if tree.is_cat[node] else None
        gains.append(split_gains(np.asarray(gh[k], np.float64),
                                 np.asarray(cnt[k], np.float64), params,
                                 kept, stated))
    # STATED_SLOT of a declared column is no candidate of the search; of a
    # numerical column it is threshold 255, which no split can state
    best = [np.where(np.arange(CODES) == STATED_SLOT, -np.inf, g)
            if kept else g for g in gains]
    return RoundReading(
        plain.leaf_value, plain.leaf_step, plain.leaf_count, plain.leaf_hess,
        np.asarray(nodes), np.asarray([g.max() for g in best]), gains,
        np.asarray([np.unravel_index(int(g.argmax()), g.shape)
                    for g in best], np.int64).reshape(-1, 2))


def follow(codes, label, trees: List[TreeArrays], params: dict,
           dtype=jnp.float32, n_check: int = 1 << 30, seed: int = 0,
           update_scores: bool = True, categorical: Optional[dict] = None
           ) -> List[RoundReading]:
    """`gbdt.follow` (its docstring) over trees that may hold categorical
    nodes.  `categorical`: `declared_columns(config)`."""
    kept = {}
    if categorical:
        take = min(int(categorical["sample_rows"]), np.shape(codes)[1])
        kept = {int(f): kept_categories(np.asarray(codes[f, :take]),
                                        int(categorical["max_bin"]))
                for f in categorical["columns"]}
    codes = jnp.asarray(codes)
    label = jnp.asarray(label)
    bias = gbdt.init_score(float(jnp.mean(label.astype(jnp.float32))))
    score = jnp.full(label.shape, bias, jnp.float32)
    out = []
    for r, tree in enumerate(trees):
        g, h = gbdt.grad_hess(score, label, dtype)
        leaf = route(codes, tree)
        sums, count = gbdt.leaf_sums(leaf, g, h, tree.num_leaves, dtype)
        nodes = gbdt.nodes_to_check(tree, n_check, seed, r)
        member = gbdt.leaves_under(tree)[nodes].astype(np.float32)
        if len(nodes):
            gh, cnt = jax.device_get(gbdt.node_histograms(
                codes, leaf, g, h, jnp.asarray(member), dtype))
        else:
            gh = cnt = np.zeros((0,))
        reading = read_round(jax.device_get(sums), jax.device_get(count),
                             gh, cnt, nodes, params,
                             bias if r == 0 else 0.0, tree, kept)
        out.append(reading)
        if update_scores:
            score = gbdt.add_leaf_values(
                score, leaf, jnp.asarray(reading.leaf_step, jnp.float32))
    return out
