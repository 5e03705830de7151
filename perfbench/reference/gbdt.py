"""Plain reference for histogram GBDT training with a binary log-loss.

Straightforward `jax.numpy` in float32 at `highest` matmul precision, no
kernels, no caches, nothing of the program imported and nothing it made
taken over (no bin mappers, no scores, no histograms).  It works on the
generator's codes, so the program's own binning is inside what is
compared.

The reference does not grow trees of its own: two sound growers that
differ by one rounding in a near-tied gain grow different trees from there
on, and nothing could be compared.  It FOLLOWS the trees the program's
timed rounds produced, round by round, with its own numbers:

  round r:  g, h   = gradients of the log-loss at the REFERENCE's score
            leaf   = each row routed through the program's tree r by the
                     thresholds the tree states
            sums   = per leaf: sum g, sum h, row count
            value  = -sum g / (sum h + lambda_l2) * learning_rate per leaf
            hist   = per checked internal node, column and code: sum g,
                     sum h, row count of the rows under that node
            score += value[leaf]          (the reference's own values)

and from `hist` the gain of every (column, threshold) at the node, and with
it the gain of the split the program chose there.  All internal nodes are
checked where a tree has at most `n_check`; else the root and a sample
drawn from the seed (a 255-leaf tree's 254 full histograms over 84M rows
would take longer than the window they check).
What the program's rounds stated (leaf values, leaf counts, the chosen
splits) is compared with these in `perfbench/check.py`.

`dtype=jnp.bfloat16` computes the same in the next precision down
(gradients and histogram operands in bfloat16, float32 accumulation): the
control that has to come out as not correct.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

CODES = 256          # histogram width: a uint8 code
BLOCK = 16384        # rows per histogram block


class TreeArrays(NamedTuple):
    """One tree as flat arrays (LightGBM's layout: a child >= 0 is an
    internal node, < 0 is leaf ~child)."""
    split_feature: np.ndarray   # [L-1] int32
    threshold: np.ndarray       # [L-1] f64, in code space: code <= t goes left
    left: np.ndarray            # [L-1] int32
    right: np.ndarray           # [L-1] int32
    leaf_value: np.ndarray      # [L] f64, as the model states it
    leaf_count: np.ndarray      # [L] f64
    split_gain: np.ndarray      # [L-1] f64
    leaf_weight: np.ndarray     # [L] f64, the leaf's sum of hessians

    @property
    def num_leaves(self) -> int:
        return len(self.leaf_value)


def tree_from_dump(tree_info: dict) -> TreeArrays:
    """Flatten one `tree_info` entry of `Booster.dump_model()`."""
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
        if node.get("decision_type", "<=") != "<=":
            raise ValueError("the reference follows numerical splits only")
        i = int(node["split_index"])
        sf[i] = node["split_feature"]
        th[i] = node["threshold"]
        gain[i] = node.get("split_gain", 0.0)
        lc[i] = ref_of(node["left_child"])
        rc[i] = ref_of(node["right_child"])
        stack += [node["left_child"], node["right_child"]]
    return TreeArrays(sf, th, lc, rc, lv, cnt, gain, wgt)


def init_score(label_mean: float) -> float:
    """boost_from_average for the binary log-loss: the log-odds."""
    p = min(max(float(label_mean), 1e-15), 1 - 1e-15)
    return float(np.log(p / (1 - p)))


def grad_hess(score, label, dtype=jnp.float32):
    """d/ds and d2/ds2 of log(1 + exp(-(2y-1) s)): p - y and p (1 - p)."""
    s = score.astype(dtype)
    p = jax.nn.sigmoid(s)
    y = label.astype(dtype)
    return p - y, p * (1 - p)


@jax.jit
def _route(codes, order, split_feature, threshold_code, left, right):
    """One sweep over the internal nodes, parents before children: the rows
    that stand at node i move to its left or right child.  One pass over
    the node ids and one column per node, no per-row table look-up."""
    def step(node, i):
        col = jax.lax.dynamic_index_in_dim(codes, split_feature[i], 0,
                                           keepdims=False).astype(jnp.int32)
        nxt = jnp.where(col <= threshold_code[i], left[i], right[i])
        return jnp.where(node == i, nxt, node), None

    node, _ = jax.lax.scan(step, jnp.zeros((codes.shape[1],), jnp.int32),
                           order)
    return ~node


def route(codes, tree: "TreeArrays"):
    """Leaf index [N] int32 of every row of `codes` [F, N] under the tree:
    code <= floor(threshold) goes left."""
    if tree.num_leaves < 2:
        return jnp.zeros((codes.shape[1],), jnp.int32)
    return _route(codes, jnp.asarray(parents_first(tree), jnp.int32),
                  jnp.asarray(tree.split_feature, jnp.int32),
                  jnp.asarray(np.floor(tree.threshold).astype(np.int32)),
                  jnp.asarray(tree.left, jnp.int32),
                  jnp.asarray(tree.right, jnp.int32))


def _blocks(n: int):
    n_blocks = -(-n // BLOCK)
    return n_blocks, n_blocks * BLOCK - n


def _pad(a, pad: int, value=0):
    if not pad:
        return a
    widths = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
    return jnp.pad(a, widths, constant_values=value)


def _kahan(acc, comp, part):
    """acc + part with the rounding error carried in comp."""
    y = part - comp
    t = acc + y
    return t, (t - acc) - y


@functools.partial(jax.jit, static_argnames=("n_leaves", "dtype"))
def leaf_sums(leaf, grad, hess, n_leaves: int, dtype=jnp.float32):
    """Per leaf: (sum g, sum h) f32 [L, 2] and the row count int32 [L],
    over all rows in blocks of BLOCK.  Block sums are one-hot products; in
    float32 at `highest` precision and added across blocks with a
    compensated sum, in the control's dtype as the dtype gives them."""
    n_blocks, pad = _blocks(leaf.shape[0])
    leaf_p, g_p, h_p = _pad(leaf, pad, -1), _pad(grad, pad), _pad(hess, pad)
    exact = dtype == jnp.float32
    prec = jax.lax.Precision.HIGHEST if exact else jax.lax.Precision.DEFAULT

    def block(carry, b):
        acc, comp, cnt = carry
        lo = b * BLOCK
        lf = jax.lax.dynamic_slice(leaf_p, (lo,), (BLOCK,))
        gh = jnp.stack([jax.lax.dynamic_slice(g_p, (lo,), (BLOCK,)),
                        jax.lax.dynamic_slice(h_p, (lo,), (BLOCK,))], axis=1)
        in_leaf = (lf[:, None] == jnp.arange(n_leaves)[None, :]).astype(
            jnp.float32)                                            # [B, L]
        # operands rounded to `dtype`; products accumulate in float32
        part = jnp.einsum("bl,bk->lk", in_leaf,
                          gh.astype(dtype).astype(jnp.float32),
                          precision=prec)
        if exact:
            acc, comp = _kahan(acc, comp, part)
        else:
            acc = acc + part
        return (acc, comp, cnt + in_leaf.sum(axis=0).astype(jnp.int32)), None

    zero = jnp.zeros((n_leaves, 2), jnp.float32)
    (acc, _, cnt), _ = jax.lax.scan(
        block, (zero, zero, jnp.zeros((n_leaves,), jnp.int32)),
        jnp.arange(n_blocks))
    return acc, cnt


@functools.partial(jax.jit, static_argnames=("dtype",))
def node_histograms(codes, leaf, grad, hess, member, dtype=jnp.float32):
    """Histograms of K tree nodes.  `member` [K, L] f32 says which leaves
    lie under each node.  Per node, column and code: (sum g, sum h) f32
    [K, F, 256, 2] and the row count int32 [K, F, 256]; blocks, precision
    and summation as in `leaf_sums`."""
    n_feat, n = codes.shape
    n_nodes, n_leaves = member.shape
    n_blocks, pad = _blocks(n)
    codes_p, leaf_p = _pad(codes, pad), _pad(leaf, pad, -1)
    g_p, h_p = _pad(grad, pad), _pad(hess, pad)
    exact = dtype == jnp.float32
    prec = jax.lax.Precision.HIGHEST if exact else jax.lax.Precision.DEFAULT

    def block(carry, b):
        acc, comp, cnt = carry
        lo = b * BLOCK
        c = jax.lax.dynamic_slice(codes_p, (0, lo), (n_feat, BLOCK))
        lf = jax.lax.dynamic_slice(leaf_p, (lo,), (BLOCK,))
        g = jax.lax.dynamic_slice(g_p, (lo,), (BLOCK,))
        h = jax.lax.dynamic_slice(h_p, (lo,), (BLOCK,))
        in_leaf = (lf[:, None] == jnp.arange(n_leaves)[None, :]).astype(
            jnp.float32)                                            # [B, L]
        in_node = in_leaf @ member.T                                # [B, K]
        w = jnp.stack([in_node * g[:, None], in_node * h[:, None]],
                      axis=-1)                                      # [B,K,2]
        hot = (c[:, :, None] == jnp.arange(CODES, dtype=jnp.uint8)[
            None, None, :]).astype(jnp.float32)                     # [F,B,256]
        part = jnp.einsum("bkj,fbc->kfcj",
                          w.astype(dtype).astype(jnp.float32), hot,
                          precision=prec)
        # 0/1 operands: exact at any precision
        n_part = jnp.einsum("bk,fbc->kfc", in_node, hot)
        if exact:
            acc, comp = _kahan(acc, comp, part)
        else:
            acc = acc + part
        return (acc, comp, cnt + n_part.astype(jnp.int32)), None

    zero = jnp.zeros((n_nodes, n_feat, CODES, 2), jnp.float32)
    (acc, _, cnt), _ = jax.lax.scan(
        block, (zero, zero, jnp.zeros((n_nodes, n_feat, CODES), jnp.int32)),
        jnp.arange(n_blocks))
    return acc, cnt


@jax.jit
def add_leaf_values(score, leaf, values):
    """score + values[leaf], leaf by leaf (no per-row table look-up)."""
    def step(s, l):
        return s + jnp.where(leaf == l, values[l], 0.0), None

    out, _ = jax.lax.scan(step, score, jnp.arange(values.shape[0]))
    return out


# ------------------------------------------------------------------ host side
def parents_first(tree: TreeArrays) -> np.ndarray:
    """The internal nodes from the root down, each before its children."""
    order, stack = [], [0] if len(tree.split_feature) else []
    while stack:
        i = stack.pop()
        order.append(i)
        stack += [c for c in (tree.left[i], tree.right[i]) if c >= 0]
    return np.asarray(order, np.int64)


def leaves_under(tree: TreeArrays) -> np.ndarray:
    """[L-1, L] bool: which leaves lie under each internal node."""
    under = np.zeros((len(tree.split_feature), tree.num_leaves), bool)
    for i in parents_first(tree)[::-1]:     # children before parents
        for c in (tree.left[i], tree.right[i]):
            if c < 0:
                under[i, ~c] = True
            else:
                under[i] |= under[c]
    return under


def split_gains(gh: np.ndarray, cnt: np.ndarray, params: dict) -> np.ndarray:
    """Gain of every (column, threshold) of one node, [F, 256] f64, -inf
    where a side breaks `min_data_in_leaf` or `min_sum_hessian_in_leaf`.
    `gh` [F, 256, 2] and `cnt` [F, 256] are the node's histogram;
    threshold t sends code <= t left.  LightGBM's gain with lambda_l1 = 0:
    G_l^2/(H_l+l2) + G_r^2/(H_r+l2) - G^2/(H+l2)."""
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = float(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    left = np.cumsum(gh.astype(np.float64), axis=1)
    n_left = np.cumsum(cnt.astype(np.float64), axis=1)
    total, n_total = left[:, -1:, :], n_left[:, -1:]
    right, n_right = total - left, n_total - n_left

    def leaf_gain(s):
        return s[..., 0] ** 2 / (s[..., 1] + l2)

    ok = (n_left >= min_data) & (n_right >= min_data) & \
        (left[..., 1] >= min_hess) & (right[..., 1] >= min_hess)
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = leaf_gain(left) + leaf_gain(right) - leaf_gain(total)
    return np.where(ok, gain, -np.inf)


class RoundReading(NamedTuple):
    """What one followed round gives, per leaf and per checked node."""
    leaf_value: np.ndarray      # [L] as a model states it (tree 0: + bias)
    leaf_step: np.ndarray       # [L] the shrunken step alone
    leaf_count: np.ndarray      # [L]
    leaf_hess: np.ndarray       # [L] the leaf's sum of hessians
    nodes: np.ndarray           # [K] the internal nodes that were checked
    best_gain: np.ndarray       # [K] best gain over all splits of the node
    gains: List[np.ndarray]     # per checked node [F, 256]
    best_split: np.ndarray      # [K, 2] (column, threshold code) of the best


def nodes_to_check(tree: TreeArrays, n_check: int, seed: int, r: int
                   ) -> np.ndarray:
    """The internal nodes whose split is checked: all where there are at
    most `n_check`, else the root and a sample of the others drawn from
    the seed."""
    ni = len(tree.split_feature)
    if ni <= n_check:
        return np.arange(ni)
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), 0xC4EC, int(r)])))
    rest = rng.choice(np.arange(1, ni), size=n_check - 1, replace=False)
    return np.concatenate([[0], np.sort(rest)])


def read_round(sums, count, gh, cnt, nodes, params: dict, bias: float
               ) -> RoundReading:
    """Leaf values and counts from the leaf sums, node gains from the
    nodes' histograms (all float64 on the host)."""
    sums = np.asarray(sums, np.float64)
    count = np.asarray(count, np.float64)
    l2 = float(params.get("lambda_l2", 0.0))
    with np.errstate(divide="ignore", invalid="ignore"):
        step = -sums[:, 0] / (sums[:, 1] + l2) * float(params["learning_rate"])
    step = np.where(count > 0, step, 0.0)
    gains = [split_gains(np.asarray(gh[k], np.float64),
                         np.asarray(cnt[k], np.float64), params)
             for k in range(len(nodes))]
    return RoundReading(
        step + bias, step, count, sums[:, 1], np.asarray(nodes),
        np.asarray([g.max() for g in gains]), gains,
        np.asarray([np.unravel_index(int(g.argmax()), g.shape)
                    for g in gains], np.int64).reshape(-1, 2))


def follow(codes, label, trees: List[TreeArrays], params: dict,
           dtype=jnp.float32, n_check: int = 1 << 30, seed: int = 0,
           update_scores: bool = True) -> List[RoundReading]:
    """Follow the given trees round by round over the rows (module
    docstring).  `codes` [F, N] uint8 and `label` [N] f32 are host or
    device arrays; everything per row stays on the device.
    `update_scores=False` plants the fault "a step that returns its state
    unchanged": every round sees the first round's scores."""
    codes = jnp.asarray(codes)
    label = jnp.asarray(label)
    bias = init_score(float(jnp.mean(label.astype(jnp.float32))))
    score = jnp.full(label.shape, bias, jnp.float32)
    out = []
    for r, tree in enumerate(trees):
        g, h = grad_hess(score, label, dtype)
        leaf = route(codes, tree)
        sums, count = leaf_sums(leaf, g, h, tree.num_leaves, dtype)
        nodes = nodes_to_check(tree, n_check, seed, r)
        member = leaves_under(tree)[nodes].astype(np.float32)
        if len(nodes):
            gh, cnt = jax.device_get(node_histograms(
                codes, leaf, g, h, jnp.asarray(member), dtype))
        else:
            gh = cnt = np.zeros((0,))
        reading = read_round(jax.device_get(sums), jax.device_get(count),
                             gh, cnt, nodes, params,
                             bias if r == 0 else 0.0)
        out.append(reading)
        if update_scores:
            score = add_leaf_values(
                score, leaf, jnp.asarray(reading.leaf_step, jnp.float32))
    return out
