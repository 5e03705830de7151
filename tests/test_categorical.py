"""Categorical split tests — the TPU build's slice of the reference's
test_engine.py categorical scenarios."""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def make_cat_data(n=1500, n_cats=12, seed=5):
    rng = np.random.RandomState(seed)
    cat = rng.randint(0, n_cats, n).astype(np.float64)
    # target depends on a subset of categories plus a numeric feature
    cat_effect = np.where(np.isin(cat, [1, 4, 7]), 2.0,
                          np.where(np.isin(cat, [2, 9]), -1.5, 0.0))
    x_num = rng.randn(n)
    y = cat_effect + 0.5 * x_num + 0.2 * rng.randn(n)
    X = np.column_stack([cat, x_num, rng.randn(n)])
    return X, y


class TestCategorical:
    def test_categorical_split_learns(self):
        X, y = make_cat_data()
        ds = lgb.Dataset(X, label=y, categorical_feature=[0])
        bst = lgb.train({"objective": "regression", "verbosity": -1,
                         "min_data_in_leaf": 20}, ds, 30)
        pred = bst.predict(X)
        assert np.mean((pred - y) ** 2) < 0.15 * np.var(y)
        # categorical splits were actually used
        n_cat_splits = sum(t.num_cat for t in bst.trees)
        assert n_cat_splits > 0

    def test_categorical_beats_numerical_encoding(self):
        X, y = make_cat_data()
        ds_cat = lgb.Dataset(X, label=y, categorical_feature=[0])
        ds_num = lgb.Dataset(X, label=y)
        p = {"objective": "regression", "verbosity": -1, "num_leaves": 8}
        bst_cat = lgb.train(p, ds_cat, 10)
        bst_num = lgb.train(p, ds_num, 10)
        mse_cat = np.mean((bst_cat.predict(X) - y) ** 2)
        mse_num = np.mean((bst_num.predict(X) - y) ** 2)
        # set-splits isolate {1,4,7} / {2,9} faster than ordered thresholds
        assert mse_cat < mse_num

    def test_internal_external_prediction_consistency(self):
        X, y = make_cat_data(800)
        ds = lgb.Dataset(X, label=y, categorical_feature=[0],
                         free_raw_data=False)
        bst = lgb.train({"objective": "regression", "verbosity": -1}, ds, 10)
        internal = np.asarray(bst._train_score, dtype=np.float64)
        external = bst.predict(X, raw_score=True)
        np.testing.assert_allclose(internal, external, atol=1e-5)

    def test_model_text_roundtrip_with_cats(self):
        X, y = make_cat_data(800)
        ds = lgb.Dataset(X, label=y, categorical_feature=[0])
        bst = lgb.train({"objective": "regression", "verbosity": -1}, ds, 8)
        s = bst.model_to_string()
        assert "num_cat=" in s
        b2 = lgb.Booster(model_str=s)
        np.testing.assert_array_equal(bst.predict(X), b2.predict(X))

    def test_unseen_category_goes_right(self):
        X, y = make_cat_data(800)
        ds = lgb.Dataset(X, label=y, categorical_feature=[0])
        bst = lgb.train({"objective": "regression", "verbosity": -1}, ds, 10)
        Xq = X[:10].copy()
        Xq[:, 0] = 99  # never seen in training
        out = bst.predict(Xq)
        assert np.isfinite(out).all()

    def test_nan_category(self):
        X, y = make_cat_data(800)
        X[::5, 0] = np.nan
        ds = lgb.Dataset(X, label=y, categorical_feature=[0])
        bst = lgb.train({"objective": "regression", "verbosity": -1}, ds, 10)
        assert np.isfinite(bst.predict(X)).all()

    def test_max_cat_to_onehot(self):
        # few categories → one-vs-rest splits (single-category subsets)
        X, y = make_cat_data(1000, n_cats=3)
        ds = lgb.Dataset(X, label=y, categorical_feature=[0],
                         params={"max_cat_to_onehot": 4})
        bst = lgb.train({"objective": "regression", "verbosity": -1,
                         "max_cat_to_onehot": 4}, ds, 5)
        for t in bst.trees:
            for i in range(t.num_internal()):
                if t.decision_type[i] & 1:
                    cat_idx = int(t.threshold_bin[i])
                    mask = t.cat_bin_masks[cat_idx]
                    assert mask.sum() == 1  # one-vs-rest

    def test_pandas_category_dtype(self):
        pd = pytest.importorskip("pandas")
        X, y = make_cat_data(600)
        df = pd.DataFrame({"c": X[:, 0].astype(int), "x1": X[:, 1],
                           "x2": X[:, 2]})
        ds = lgb.Dataset(df, label=y, categorical_feature=["c"])
        bst = lgb.train({"objective": "regression", "verbosity": -1}, ds, 5)
        assert np.isfinite(bst.predict(df)).all()


# ------------------------------------------------- dump_model (ISSUE 35)
def _nodes(tree_info):
    stack, out = [tree_info["tree_structure"]], []
    while stack:
        node = stack.pop()
        if "split_index" in node:
            out.append(node)
            stack += [node["left_child"], node["right_child"]]
    return out


def _predict_by_dump(tree_info, X):
    """Route raw rows by what the dump states: `==` sends the listed
    category values left and everything else (unseen, NaN) right."""
    out = np.zeros(len(X))
    for r, row in enumerate(X):
        node = tree_info["tree_structure"]
        while "split_index" in node:
            v = row[node["split_feature"]]
            if node["decision_type"] == "==":
                left = not np.isnan(v) and int(v) in {
                    int(c) for c in node["threshold"].split("||")}
            else:
                left = v <= node["threshold"]
            node = node["left_child"] if left else node["right_child"]
        out[r] += node["leaf_value"]
    return out


def test_dump_model_states_categorical_nodes_as_upstream_does():
    X, y = make_cat_data()
    bst = lgb.train({"objective": "regression", "verbosity": -1,
                     "min_data_in_leaf": 20, "min_data_per_group": 20},
                    lgb.Dataset(X, label=y, categorical_feature=[0]), 5)
    dump = bst.dump_model()
    cat_nodes = 0
    for t, info in zip(bst.trees, dump["tree_info"]):
        assert info["num_cat"] == t.num_cat
        for node in _nodes(info):
            if node["split_feature"] != 0:
                assert node["decision_type"] == "<="
                assert isinstance(node["threshold"], float)
                continue
            cat_nodes += 1
            assert node["decision_type"] == "=="
            assert node["default_left"] is False
            cats = [int(c) for c in node["threshold"].split("||")]
            assert cats == sorted(set(cats)) and 0 <= min(cats)
            # the node's own bitset, value by value
            i = int(t.threshold[node["split_index"]])
            words = t.cat_threshold[t.cat_boundaries[i]:
                                    t.cat_boundaries[i + 1]]
            assert cats == [c for c in range(32 * len(words))
                            if (int(words[c // 32]) >> (c % 32)) & 1]
    assert cat_nodes == sum(t.num_cat for t in bst.trees) > 0
    # predict on raw values is routing by the dumped lists, unseen
    # categories and NaN included
    Xq = X[:200].copy()
    Xq[:5, 0] = 99.0
    Xq[5:10, 0] = np.nan
    by_dump = sum(_predict_by_dump(info, Xq) for info in dump["tree_info"])
    np.testing.assert_allclose(bst.predict(Xq), by_dump, rtol=0, atol=1e-6)


def test_numerical_dumps_are_what_they_were():
    """A model without categorical nodes: every node `<=` with the float
    threshold of `Tree.threshold`, as before `==` existed."""
    X, y = make_cat_data()
    bst = lgb.train({"objective": "regression", "verbosity": -1},
                    lgb.Dataset(X, label=y), 3)
    for t, info in zip(bst.trees, bst.dump_model()["tree_info"]):
        nodes = _nodes(info)
        assert len(nodes) == t.num_leaves - 1
        for node in nodes:
            assert node["decision_type"] == "<="
            assert node["threshold"] == float(
                t.threshold[node["split_index"]])
            assert list(node)[:6] == ["split_index", "split_feature",
                                      "split_gain", "threshold",
                                      "decision_type", "default_left"]


def test_min_data_per_group_binds():
    """The group gate of the categorical scan: a larger group changes
    the trees, and no left set gains fewer rows than the gate."""
    X, y = make_cat_data(n=3000, n_cats=40)
    ds = lambda: lgb.Dataset(X, label=y, categorical_feature=[0])  # noqa
    p = {"objective": "regression", "verbosity": -1, "num_leaves": 8,
         "min_data_in_leaf": 5, "cat_smooth": 1}
    small = lgb.train(dict(p, min_data_per_group=1), ds(), 3)
    large = lgb.train(dict(p, min_data_per_group=400), ds(), 3)
    assert small.model_to_string().split("parameters:")[0] != \
        large.model_to_string().split("parameters:")[0]
    for info in large.dump_model()["tree_info"]:
        for node in _nodes(info):
            if node["decision_type"] == "==":
                left = node["left_child"]
                n_left = left.get("internal_count", left.get("leaf_count"))
                right = node["right_child"]
                n_right = right.get("internal_count",
                                    right.get("leaf_count"))
                assert n_left >= 400 and n_right >= 400
