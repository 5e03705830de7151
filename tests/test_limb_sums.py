"""Histogram sums that are right to the round-off of the SUM, not of N
additions (ISSUE 29).

One float32 accumulator over N near-equal addends drifts by up to N/2 ulps,
because equal addends round the same way every time; sibling subtraction
then hands the drift of the root's sums down to the smallest leaf, where a
split on a column of few bins reads the children's sums from that column's
two or three cells.  The f32 histogram families carry two limbs a cell
(`ops/histogram.py`, `ops/pallas_hist.py`), the growers subtract limb-wise
and read a chosen split's child sums from the node's own limbs.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops import pallas_hist as ph
from perfbench import check, manifest
from perfbench.generators import tabular_codes
from perfbench.jobs.train import build_dataset
from perfbench.reference import gbdt

ROWS = 204_800
H0 = np.float32(0.24915)        # every row's hessian in a first round
G0 = np.float32(-0.3137)


def _equal_addends(n):
    return np.stack([np.full(n, G0), np.full(n, H0),
                     np.ones(n, np.float32)], axis=1)


def _f32_running_sum(x, n):
    acc = np.float32(0.0)
    for _ in range(n):
        acc = np.float32(acc + x)
    return float(acc)


# ------------------------------------------------ (b) the accumulators alone
def test_one_f32_accumulator_drifts_on_equal_addends():
    """What the cure is for: numpy, float32, a loop."""
    n = ROWS // 2
    assert abs(_f32_running_sum(H0, n) / (n * float(H0)) - 1) > 5e-4


def test_leaf_histogram_two_bin_column_against_float64():
    rng = np.random.RandomState(0)
    bins = np.stack([rng.randint(0, 2, ROWS),
                     rng.randint(0, 255, ROWS)]).astype(np.uint8)
    pay = _equal_addends(ROWS)
    got = np.asarray(H.leaf_histogram(jnp.asarray(bins), jnp.asarray(pay),
                                      jnp.ones(ROWS, bool), 256),
                     np.float64)
    for f in range(2):
        for c in range(3):
            want = np.bincount(bins[f], weights=pay[:, c].astype(np.float64),
                               minlength=256)
            live = want != 0
            assert np.max(np.abs(got[f, live, c] / want[live] - 1)) < 1e-6


def test_kernel_over_1600_tiles_against_float64():
    """The kernel's accumulator one level up: a cell's running sum takes
    a tile's partial 1,600 times (a row tile of 128 under interpret, 64
    rows a cell).  The carrier row is handed over UNSPLIT, so that a
    partial has a full mantissa, as the sum of a 2048-row tile's terms
    has on the chip: one accumulator reads 1.05e-5 off."""
    tiles, tile = 1600, 128
    n = tiles * tile
    bins = (np.arange(n) % 2).astype(np.uint8)[None, :]
    hi, lo, _ = ph._run_kernel_multi(
        jnp.asarray(bins), jnp.full((1, n), H0), jnp.zeros(n, jnp.int32),
        jnp.zeros((1,), jnp.int32), 8, tile, 0, True)
    got = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
    want = n / 2 * float(H0)
    assert np.max(np.abs(got[0, 0, :2] / want - 1)) < 1e-6
    assert np.all(got[0, 0, 2:] == 0)
    # the parent's arithmetic on the same partials
    partial = np.float32(tile / 2 * float(H0))
    assert abs(_f32_running_sum(partial, tiles)
               / (tiles * float(partial)) - 1) > 3e-6
    # and through the public entry, split in three terms, two leaves
    rng = np.random.RandomState(1)
    pay = _equal_addends(n)
    lid = rng.randint(0, 2, n).astype(np.int32)
    out = np.asarray(ph.pallas_histogram_multi(
        jnp.asarray(bins), jnp.asarray(pay), jnp.asarray(lid),
        jnp.arange(2, dtype=jnp.int32), 8, row_tile=tile, interpret=True),
        np.float64)
    for s in range(2):
        for c in range(3):
            want = np.bincount(bins[0][lid == s],
                               weights=pay[lid == s, c].astype(np.float64),
                               minlength=8)[:2]
            assert np.max(np.abs(out[s, 0, :2, c] / want - 1)) < 1e-6


def test_limbs_of_kernel_and_scatter_agree_in_value():
    """Both f32 families state the same sums (not the same bits: the
    kernel splits a value in three terms, the scatter in two)."""
    rng = np.random.RandomState(2)
    n = 4096
    bins = rng.randint(0, 32, (3, n)).astype(np.uint8)
    pay = np.stack([rng.randn(n), rng.rand(n) * 0.25, np.ones(n)],
                   axis=1).astype(np.float32)
    lid = rng.randint(0, 4, n).astype(np.int32)
    slots = jnp.array([0, 2, 3], jnp.int32)
    a = np.asarray(ph.pallas_histogram_multi_rows(
        jnp.asarray(bins), ph._split_payload9(jnp.asarray(pay)),
        jnp.asarray(lid), slots, 32, row_tile=256,
        interpret=True), np.float64)
    b = np.asarray(H.leaf_histogram_multi_limbs(
        jnp.asarray(bins), jnp.asarray(pay), jnp.asarray(lid), slots, 32),
        np.float64)
    assert a.shape == b.shape == (3, 3, 32, 6)
    va, vb = a[..., :3] + a[..., 3:], b[..., :3] + b[..., 3:]
    assert np.max(np.abs(va - vb) / np.maximum(1.0, np.abs(vb))) < 1e-6
    # a low limb stays under an ulp of its high limb
    assert np.all(np.abs(b[..., 3:]) <= np.spacing(
        np.abs(b[..., :3]).astype(np.float32)))


@pytest.mark.parametrize("shard", [300, 8192, 30_000])
def test_streamed_carry_is_the_one_pass_build_bit_for_bit(shard):
    """Shards of any size, folded in order, make the additions of the
    one-pass build: a tile closes where the ROWS SEEN pass a multiple of
    HIST_TILE, wherever a shard ends."""
    rng = np.random.RandomState(3)
    n = 3 * H.HIST_TILE + 1234
    bins = rng.randint(0, 16, (2, n)).astype(np.uint8)
    pay = np.stack([rng.randn(n), rng.rand(n) * 0.25, np.ones(n)],
                   axis=1).astype(np.float32)
    lid = rng.randint(0, 5, n).astype(np.int32)
    slots = jnp.array([1, 4], jnp.int32)
    one = np.asarray(H.leaf_histogram_multi_limbs(
        jnp.asarray(bins), jnp.asarray(pay), jnp.asarray(lid), slots, 16))
    acc = H.hist_stream_init(2, 2, 16)
    for lo in range(0, n, shard):
        hi = min(n, lo + shard)
        acc = H.hist_stream_update(acc, jnp.asarray(bins[:, lo:hi]),
                                   jnp.asarray(pay[lo:hi]),
                                   jnp.asarray(lid[lo:hi]), slots, 16)
    assert np.array_equal(
        one, np.asarray(H.hist_stream_finalize(acc, 2, 2, 16)))


# ------------------------------------------------ (c) the subtraction chain
def test_ten_generations_of_parent_minus_small_keep_a_100_row_node():
    rng = np.random.RandomState(4)
    bins = rng.randint(0, 2, (1, ROWS)).astype(np.uint8)
    pay = np.stack([rng.randn(ROWS) * 0.4, np.full(ROWS, H0),
                    np.ones(ROWS)], axis=1).astype(np.float32)
    jb, jp = jnp.asarray(bins), jnp.asarray(pay)

    def hist_of(mask):
        return H.leaf_histogram_limbs(jb, jp, jnp.asarray(mask), 2)

    keep = np.ones(ROWS, bool)
    node = hist_of(keep)
    plain = np.asarray(H.hist_value(node))
    sizes = [int(100 * (ROWS / 100) ** (1 - (k + 1) / 10))
             for k in range(10)]
    for size in sizes:
        inside = np.flatnonzero(keep)
        gone = np.zeros(ROWS, bool)
        gone[inside[size:]] = True
        small = hist_of(gone)
        node = H.hist_sub(node, small)          # the cure: both limbs
        plain = plain - np.asarray(H.hist_value(small))     # the parent
        keep &= ~gone
    assert keep.sum() == 100
    got = np.asarray(H.hist_value(node), np.float64)[0]
    want = np.stack([np.bincount(bins[0][keep],
                                 weights=pay[keep, c].astype(np.float64),
                                 minlength=2) for c in range(3)], axis=1)
    scale = np.stack([np.bincount(bins[0][keep],
                                  weights=np.abs(pay[keep, c]),
                                  minlength=2) for c in range(3)], axis=1)
    assert np.max(np.abs(got - want) / scale) < 1e-5
    # and one limb does not: the root's round-off, 51,000 hessian a cell
    assert np.max(np.abs(plain[0] - want) / scale) > 1e-5


# ------------------------- (a) every node of a 255-leaf tree, at 204,800 rows
@pytest.fixture(scope="module")
def airline_rows():
    config = manifest.config("airline13-lgbexp-l255")
    rows = tabular_codes.make(3000000021, config["data"], ROWS, 1)
    return config, rows


def _node_sums(node, leaf_of_row, label, out):
    """Float64 (rows, positives) of every node of a dumped tree, by the
    leaves under it; appends (stated dict, rows, positives)."""
    if "leaf_index" in node:
        rows = float(np.sum(leaf_of_row == node["leaf_index"]))
        pos = float(label[leaf_of_row == node["leaf_index"]].sum())
        out.append(({"leaf": True, "count": node["leaf_count"],
                     "hess": node["leaf_weight"],
                     "value": node["leaf_value"]}, rows, pos))
        return rows, pos
    rl, pl = _node_sums(node["left_child"], leaf_of_row, label, out)
    rr, pr = _node_sums(node["right_child"], leaf_of_row, label, out)
    out.append(({"leaf": False, "count": node["internal_count"],
                 "hess": node["internal_weight"],
                 "value": node["internal_value"]}, rl + rr, pl + pr))
    return rl + rr, pl + pr


@pytest.mark.parametrize("hist_impl", ["auto", "segment_sum"])
@pytest.mark.parametrize("policy", ["wave", "leafwise"])
@pytest.mark.parametrize("gates", ["experiment", "default"])
def test_every_node_states_its_rows_sums(airline_rows, gates, policy,
                                         hist_impl):
    """One round at 255 leaves: every row's hessian is the same h0 and its
    gradient p0 - y, so a node's sums are h0 * rows and p0 * rows -
    positives, to float64.  Every node states them to 1e-6, and the
    benchmark's comparison reads `correct`.  (The parent stated a hessian
    26.5 off on a node of 7,177 rows, under a split on the 2-bin column.)"""
    config, rows = airline_rows
    params = dict(config["params"], tree_grow_policy=policy,
                  hist_impl=hist_impl)
    if gates == "default":
        params.update(min_data_in_leaf=20, min_sum_hessian_in_leaf=0.001)
    names = [c["name"] for c in config["data"]["columns"]]
    ds = build_dataset(lgb, rows["codes"], rows["label"], params, names)
    bst = lgb.Booster(params=params, train_set=ds)
    bst.update()
    tree = bst.dump_model(num_iteration=1)["tree_info"][0]
    assert tree["num_leaves"] == 255
    followed = gbdt.tree_from_dump(tree)
    leaf_of_row = np.asarray(gbdt.route(jnp.asarray(rows["codes"]),
                                        followed))
    label = rows["label"].astype(np.float64)
    nodes = []
    _node_sums(tree["tree_structure"], leaf_of_row, label, nodes)
    assert len(nodes) == 2 * 255 - 1
    root, n_all, pos_all = nodes[-1]
    assert root["count"] == n_all == ROWS
    h0 = root["hess"] / n_all
    lr = params["learning_rate"]
    for stated, n, _ in nodes:
        assert stated["count"] == n
        assert abs(stated["hess"] / (h0 * n) - 1) < 1e-6, (stated, n)
    # a first tree's leaf states init - lr * G / H, init the log-odds of
    # the labels' mean: the gradient sums the leaves state, against
    # p0 * rows - positives (p0 from all the leaves together)
    mean = pos_all / n_all
    init = np.log(mean / (1 - mean))
    leaves = [x for x in nodes if x[0]["leaf"]]
    stated_g = np.array([-(x[0]["value"] - init) * x[0]["hess"] / lr
                         for x in leaves])
    n_rows = np.array([x[1] for x in leaves])
    n_pos = np.array([x[2] for x in leaves])
    p0 = (stated_g.sum() + pos_all) / n_all
    assert abs(p0 / mean - 1) < 1e-6
    assert np.max(np.abs(stated_g - (p0 * n_rows - n_pos))
                  / (n_rows * max(p0, 1 - p0))) < 1e-6
    ref = gbdt.follow(rows["codes"], rows["label"], [followed], params,
                      n_check=32, seed=7)
    numbers = check.compare(check.stated_of([followed]), ref)
    limits = manifest.workload("airline13-lgbexp-l255.train")[
        "traffic_params"]["limits"]
    assert check.verdict(numbers, limits), numbers
    assert numbers["leaf_value_gap"] < 1e-4, numbers


# ------------- (a) again, where the routing is not `bin <= threshold` alone
def _splits(node, out):
    if "leaf_index" not in node:
        out.append(node)
        _splits(node["left_child"], out)
        _splits(node["right_child"], out)
    return out


@pytest.mark.parametrize("policy", ["wave", "leafwise"])
@pytest.mark.parametrize("kind", ["nan_missing", "categorical"])
def test_child_sums_follow_the_partition_on_nan_and_categorical_splits(
        kind, policy):
    """`refine_child_sums` reads a split's child sums bin by bin with the
    partition's own rule (`split.bin_goes_left`): where the NaN bin
    follows `default_left`, or a category mask decides, every node still
    states the float64 sums of the rows the MODEL routes to it
    (`pred_leaf`, on raw values: a third statement of the rule)."""
    rng = np.random.RandomState(11)
    n = 20_000
    X = rng.randn(n, 4)
    score = X[:, 1] + 0.3 * rng.randn(n)
    kw = {}
    if kind == "nan_missing":
        # NaN rows lean both ways, by the sign of another column
        gone = rng.rand(n) < 0.3
        score = score + np.where(gone, 1.5 * np.sign(X[:, 2]), X[:, 0])
        X[gone, 0] = np.nan
    else:
        X[:, 0] = rng.randint(0, 12, n)
        score = score + 1.5 * np.isin(X[:, 0], [1, 4, 6, 9])
        kw["categorical_feature"] = [0]
    y = (score > 0.2).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 63, "verbosity": -1,
              "min_data_in_leaf": 0, "min_sum_hessian_in_leaf": 1.0,
              "tree_grow_policy": policy}
    bst = lgb.train(params, lgb.Dataset(X, label=y, **kw),
                    num_boost_round=1)
    tree = bst.dump_model()["tree_info"][0]
    on_col = [s for s in _splits(tree["tree_structure"], [])
              if s["split_feature"] == 0]
    if kind == "nan_missing":
        assert {s["default_left"] for s in on_col
                if s["missing_type"] == "NaN"} == {True, False}
    else:
        # (`dump_model` writes "<=" for every split: the tree's own bits)
        t = bst.trees[0]
        assert (t.decision_type[:t.num_internal()] & 1).any()
    leaf_of_row = np.asarray(bst.predict(X, pred_leaf=True)).reshape(n)
    nodes = []
    _node_sums(tree["tree_structure"], leaf_of_row, y, nodes)
    root, n_all, _ = nodes[-1]
    assert root["count"] == n_all == n
    h0 = root["hess"] / n_all
    for stated, rows, _ in nodes:
        assert stated["count"] == rows, (stated, rows)
        assert abs(stated["hess"] / (h0 * rows) - 1) < 1e-6, (stated, rows)
