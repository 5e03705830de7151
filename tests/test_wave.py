"""Wave-batched growth policy (ops/grow_wave.py, tree_grow_policy=wave).

Covers: the batched multi-leaf histogram primitives against per-leaf
references, exact equivalence to the strict policy where the orders
coincide (num_leaves <= 3), accuracy parity at benchmark-ish settings,
constraint handling (max_depth / min_data / monotone basic), the
quantized + EFB + bagging paths, distributed data-parallel parity on the
8-virtual-device CPU mesh, and the eligibility downgrades.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.histogram import (leaf_histogram,
                                        leaf_histogram_multi,
                                        leaf_histogram_packed,
                                        leaf_histogram_packed_multi)


def make_binary(n=3000, f=8, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    score = X[:, 0] + X[:, 1] * X[:, 2] + 0.5 * np.sin(3 * X[:, 3])
    y = (score + 0.5 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def auc_of(bst, X, y):
    from lightgbm_tpu.metrics import _auc
    return float(_auc(bst.predict(X, raw_score=True), y, None, None))


@pytest.mark.quick
class TestMultiHistogram:
    def test_multi_matches_per_leaf(self):
        rng = np.random.RandomState(0)
        n, f, mb, L = 5000, 6, 32, 9
        bins = jnp.asarray(rng.randint(0, mb, (f, n)).astype(np.uint8))
        payload = jnp.asarray(rng.randn(n, 3).astype(np.float32))
        leaf_id = jnp.asarray(rng.randint(0, L, n).astype(np.int32))
        # slots include a pad entry (L) that matches no row
        slots = jnp.asarray(np.array([4, 0, 7, L, 2], np.int32))
        got = leaf_histogram_multi(bins, payload, leaf_id, slots, mb)
        for i, sl in enumerate([4, 0, 7, None, 2]):
            if sl is None:
                assert float(jnp.abs(got[i]).max()) == 0.0
            else:
                want = leaf_histogram(bins, payload, leaf_id == sl, mb)
                np.testing.assert_allclose(np.asarray(got[i]),
                                           np.asarray(want),
                                           rtol=1e-5, atol=1e-5)

    def test_packed_multi_matches_per_leaf(self):
        rng = np.random.RandomState(1)
        n, f, mb, L = 4000, 5, 16, 6
        bins = jnp.asarray(rng.randint(0, mb, (f, n)).astype(np.uint8))
        s_g, s_h = jnp.float32(0.5), jnp.float32(0.25)
        gq = rng.randint(-8, 9, n).astype(np.float32)
        hq = rng.randint(0, 9, n).astype(np.float32)
        w = (rng.rand(n) < 0.8).astype(np.float32)
        payload = jnp.asarray(
            np.stack([gq * 0.5 * w, hq * 0.25 * w, w], axis=1))
        leaf_id = jnp.asarray(rng.randint(0, L, n).astype(np.int32))
        slots = jnp.asarray(np.array([3, 1, L, 0], np.int32))
        got = leaf_histogram_packed_multi(bins, payload, leaf_id, slots,
                                          mb, s_g, s_h)
        for i, sl in enumerate([3, 1, None, 0]):
            if sl is None:
                assert float(jnp.abs(got[i]).max()) == 0.0
            else:
                want = leaf_histogram_packed(bins, payload, leaf_id == sl,
                                             mb, s_g, s_h)
                np.testing.assert_allclose(np.asarray(got[i]),
                                           np.asarray(want),
                                           rtol=1e-5, atol=1e-5)


@pytest.mark.quick
class TestWavePolicy:
    def test_small_tree_exact_match(self):
        """For num_leaves <= 3 (and overgrow off) wave order IS strict
        order — trees must be byte-identical (only the params dump in
        the model text differs)."""
        X, y = make_binary(2000)
        dumps = {}
        for pol in ("leafwise", "wave"):
            bst = lgb.train({"objective": "binary", "num_leaves": 3,
                             "verbosity": -1, "tree_grow_policy": pol,
                             "tpu_wave_overgrow": 0},
                            lgb.Dataset(X, label=y), num_boost_round=8)
            txt = bst.model_to_string()
            body = "\n".join(ln for ln in txt.splitlines()
                             if not ln.startswith("[tree_grow_policy")
                             and not ln.startswith("[tpu_wave_overgrow"))
            dumps[pol] = (body, bst.predict(X))
        assert dumps["leafwise"][0] == dumps["wave"][0]
        np.testing.assert_array_equal(dumps["leafwise"][1],
                                      dumps["wave"][1])

    def test_full_strict_tail_matches_strict(self):
        """tpu_wave_strict_tail >= num_leaves - 1 collapses EVERY wave
        to width 1 — strict best-first order: trees must be
        byte-identical to the leafwise grower at any num_leaves (the
        hybrid schedule's endgame is exactly this path)."""
        X, y = make_binary(2500)
        dumps = {}
        strip = ("[tree_grow_policy", "[tpu_wave")
        for pol, extra in (("leafwise", {}),
                           ("wave", {"tpu_wave_strict_tail": 1000,
                                     "tpu_wave_gain_ratio": 0})):
            bst = lgb.train({"objective": "binary", "num_leaves": 15,
                             "verbosity": -1, "tree_grow_policy": pol,
                             "tpu_wave_overgrow": 0, **extra},
                            lgb.Dataset(X, label=y), num_boost_round=8)
            txt = bst.model_to_string()
            body = "\n".join(ln for ln in txt.splitlines()
                             if not ln.startswith(strip))
            dumps[pol] = (body, bst.predict(X))
        assert dumps["leafwise"][0] == dumps["wave"][0]
        np.testing.assert_array_equal(dumps["leafwise"][1],
                                      dumps["wave"][1])

    def test_strict_tail_partial_quality(self):
        """A partial strict tail (the auto default) must keep the wave
        policy's held-out quality at least at the floorless wave's level
        and grow num_leaves-bounded trees."""
        X, y = make_binary(4000)
        Xv, yv = make_binary(1500, seed=123)
        aucs = {}
        for tail in (0, -1):
            bst = lgb.train({"objective": "binary", "num_leaves": 31,
                             "verbosity": -1, "tree_grow_policy": "wave",
                             "tpu_wave_strict_tail": tail,
                             "tpu_wave_gain_ratio": 0},
                            lgb.Dataset(X, label=y), num_boost_round=16)
            from lightgbm_tpu.metrics import _auc
            aucs[tail] = float(_auc(bst.predict(Xv, raw_score=True),
                                    yv, None, None))
            for t in bst.trees:
                assert t.num_internal() + 1 <= 31
        # auto tail (~L/2 strict endgame since r5) should not hurt; allow noise
        assert aucs[-1] >= aucs[0] - 0.004, aucs

    def test_overgrow_prune_invariants(self):
        """Grow-then-prune (opt-in via tpu_wave_overgrow): the emitted
        tree must have <= num_leaves leaves, its split log must replay to
        EXACTLY the returned row→leaf assignment (validates the
        compaction/renumbering), and the model text must round-trip."""
        import jax.numpy as jnp
        from lightgbm_tpu.booster import Booster
        from lightgbm_tpu.ops.predict import replay_leaf_ids
        X, y = make_binary(2500)
        bst = Booster(params={"objective": "binary", "num_leaves": 9,
                              "verbosity": -1,
                              "tree_grow_policy": "wave",
                              "tpu_wave_overgrow": 2.0},
                      train_set=lgb.Dataset(X, label=y))
        assert bst._grower_spec.wave_overgrow > 1.0
        g, h = bst._grad_fn(bst._train_score)
        dev = bst._grower(bst._train_bins, g.astype(jnp.float32),
                          h.astype(jnp.float32), bst._ones, bst._feat,
                          jnp.asarray(bst._dd.base_allowed))
        n_splits = int(dev.n_splits)
        assert 0 < n_splits <= 8
        replayed = replay_leaf_ids(dev, bst._train_bins,
                                   bst._feat["nb"], bst._feat["missing"])
        np.testing.assert_array_equal(np.asarray(replayed),
                                      np.asarray(dev.leaf_id))
        # through the public API: train, leaf counts, roundtrip
        bst2 = lgb.train({"objective": "binary", "num_leaves": 9,
                          "verbosity": -1, "tree_grow_policy": "wave",
                          "tpu_wave_overgrow": 2.0},
                         lgb.Dataset(X, label=y), num_boost_round=6)
        d = bst2.dump_model()
        for t in d["tree_info"]:
            assert t["num_leaves"] <= 9
        rt = lgb.Booster(model_str=bst2.model_to_string())
        np.testing.assert_array_equal(bst2.predict(X), rt.predict(X))

    def test_overgrow_quality(self):
        """Overgrow-prune must not lose accuracy vs the plain wave."""
        X, y = make_binary(4000)
        Xe, ye = make_binary(2000, seed=23)
        aucs = {}
        for og in (0.0, 2.0):
            bst = lgb.train({"objective": "binary", "num_leaves": 15,
                             "verbosity": -1, "tree_grow_policy": "wave",
                             "tpu_wave_overgrow": og},
                            lgb.Dataset(X, label=y), num_boost_round=25)
            aucs[og] = auc_of(bst, Xe, ye)
        assert aucs[2.0] > aucs[0.0] - 0.005, aucs

    def test_overgrow_monotone_downgrade(self):
        from lightgbm_tpu.booster import Booster
        X, y = make_binary(1200)
        bst = Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbosity": -1, "tree_grow_policy": "wave",
                              "tpu_wave_overgrow": 2.0,
                              "monotone_constraints": [1, 0, 0, 0, 0, 0,
                                                       0, 0]},
                      train_set=lgb.Dataset(X, label=y))
        assert bst._grower_spec.wave_overgrow == 0.0
        assert bst._grow_policy == "wave"

    def test_accuracy_parity_with_strict(self):
        X, y = make_binary(4000)
        Xe, ye = make_binary(2000, seed=11)
        aucs = {}
        for pol in ("leafwise", "wave"):
            bst = lgb.train({"objective": "binary", "num_leaves": 31,
                             "verbosity": -1, "tree_grow_policy": pol},
                            lgb.Dataset(X, label=y), num_boost_round=30)
            aucs[pol] = auc_of(bst, Xe, ye)
        assert aucs["wave"] > aucs["leafwise"] - 0.01, aucs

    def test_constraints_respected(self):
        X, y = make_binary(2500)
        bst = lgb.train({"objective": "binary", "num_leaves": 31,
                         "max_depth": 3, "min_data_in_leaf": 50,
                         "verbosity": -1, "tree_grow_policy": "wave"},
                        lgb.Dataset(X, label=y), num_boost_round=5)
        d = bst.dump_model()
        for t in d["tree_info"]:
            def walk(node, depth):
                if "leaf_value" in node:
                    assert depth <= 3
                    assert node.get("leaf_count", 50) >= 50
                    return 1
                return walk(node["left_child"], depth + 1) + \
                    walk(node["right_child"], depth + 1)
            assert walk(t["tree_structure"], 0) <= 8   # depth-3 cap

    def test_monotone_basic(self):
        rng = np.random.RandomState(5)
        n = 2500
        X = rng.rand(n, 3).astype(np.float32)
        y = 2 * X[:, 0] - X[:, 1] + 0.2 * rng.randn(n)
        bst = lgb.train({"objective": "regression", "num_leaves": 15,
                         "verbosity": -1, "tree_grow_policy": "wave",
                         "monotone_constraints": [1, -1, 0]},
                        lgb.Dataset(X, label=y), num_boost_round=20)
        grid = np.tile(np.float32([[0.5, 0.5, 0.5]]), (41, 1))
        grid[:, 0] = np.linspace(0, 1, 41)
        assert np.all(np.diff(bst.predict(grid)) >= -1e-9)
        grid[:, 0] = 0.5
        grid[:, 1] = np.linspace(0, 1, 41)
        assert np.all(np.diff(bst.predict(grid)) <= 1e-9)

    def test_quantized_and_bagging(self):
        X, y = make_binary(3000)
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbosity": -1, "tree_grow_policy": "wave",
                         "use_quantized_grad": True,
                         "bagging_fraction": 0.7, "bagging_freq": 1},
                        lgb.Dataset(X, label=y), num_boost_round=25)
        assert auc_of(bst, X, y) > 0.85

    def test_goss_and_dart(self):
        """GOSS rescale weights and DART drops ride the wave payload
        unchanged (non-{0,1} weights force the f32 kernel family)."""
        X, y = make_binary(3000)
        for boosting in ("goss", "dart"):
            bst = lgb.train({"objective": "binary", "num_leaves": 15,
                             "verbosity": -1, "tree_grow_policy": "wave",
                             "boosting": boosting},
                            lgb.Dataset(X, label=y), num_boost_round=25)
            assert auc_of(bst, X, y) > 0.85, boosting

    def test_efb_bundled(self):
        rng = np.random.RandomState(9)
        n = 2500
        dense = rng.randn(n, 3).astype(np.float32)
        sparse = np.zeros((n, 6), np.float32)
        for j in range(6):
            idx = rng.choice(n, n // 10, replace=False)
            sparse[idx, j] = rng.randn(n // 10)
        X = np.hstack([dense, sparse])
        y = (dense[:, 0] + sparse[:, 0] - sparse[:, 3]
             + 0.3 * rng.randn(n) > 0).astype(np.float64)
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbosity": -1, "tree_grow_policy": "wave",
                         "enable_bundle": True},
                        lgb.Dataset(X, label=y), num_boost_round=20)
        assert auc_of(bst, X, y) > 0.85

    def test_categorical(self):
        rng = np.random.RandomState(13)
        n = 2500
        cat = rng.randint(0, 8, n)
        num = rng.randn(n).astype(np.float32)
        y = ((cat % 3 == 0).astype(float) + 0.5 * num
             + 0.3 * rng.randn(n) > 0.4).astype(np.float64)
        X = np.stack([cat.astype(np.float32), num], axis=1)
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbosity": -1, "tree_grow_policy": "wave"},
                        lgb.Dataset(X, label=y,
                                    categorical_feature=[0]),
                        num_boost_round=20)
        assert auc_of(bst, X, y) > 0.8

    def test_reset_parameter_flips_bulk_trainer(self):
        """The fused chunk trainer must be rebuilt when reset_parameter
        switches tree_grow_policy (its cache key includes the policy)."""
        from lightgbm_tpu.booster import Booster
        X, y = make_binary(1500)
        bst = Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbosity": -1},
                      train_set=lgb.Dataset(X, label=y))
        bst.update_many(bst._BULK_CHUNK)
        key_leafwise = bst._bulk_key
        assert bst._grow_policy == "leafwise"
        bst.reset_parameter({"tree_grow_policy": "wave"})
        assert bst._grow_policy == "wave"
        bst.update_many(bst._BULK_CHUNK)
        assert bst._bulk_key != key_leafwise
        assert bst.current_iteration() == 2 * bst._BULK_CHUNK

    def test_wave_knobs_plumb_through(self):
        """tpu_wave_width / tpu_wave_gain_ratio reach the grower spec and
        produce a working model.  The gain floor is capacity-aware
        (ratio x opening gain x tree-fullness), so even ratio ~1 only
        bites in the late, capacity-scarce waves — early waves still run
        at full width."""
        from lightgbm_tpu.booster import Booster
        X, y = make_binary(1500)
        bst = Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbosity": -1, "tree_grow_policy": "wave",
                              "tpu_wave_width": 2,
                              "tpu_wave_gain_ratio": 0.99},
                      train_set=lgb.Dataset(X, label=y))
        assert bst._grower_spec.wave_width == 2
        assert bst._grower_spec.wave_gain_ratio == 0.99
        bst.update_many(4)
        assert bst.num_trees() == 4
        from lightgbm_tpu.metrics import _auc
        assert float(_auc(bst.predict(X, raw_score=True), y,
                          None, None)) > 0.75

    def test_multiclass_and_ranking(self):
        """Wave grows per-class trees (multiclass) and consumes ranking
        lambdas like any other gradient source."""
        rng = np.random.RandomState(31)
        n = 2400
        X = rng.randn(n, 6).astype(np.float32)
        ym = (X[:, 0] + 0.5 * rng.randn(n) > 0).astype(int) \
            + (X[:, 1] > 0.5).astype(int)
        bst = lgb.train({"objective": "multiclass", "num_class": 3,
                         "num_leaves": 7, "verbosity": -1,
                         "tree_grow_policy": "wave"},
                        lgb.Dataset(X, label=ym.astype(float)),
                        num_boost_round=10)
        acc = (bst.predict(X).argmax(axis=1) == ym).mean()
        assert acc > 0.7
        # lambdarank
        q = 40
        group = np.full(n // q, q)
        rel = X[:, 0] + 0.3 * rng.randn(n)
        yr = np.zeros(n)
        for i in range(n // q):
            s = slice(i * q, (i + 1) * q)
            yr[s] = np.minimum(4, np.argsort(np.argsort(rel[s])) * 5 // q)
        bstr = lgb.train({"objective": "lambdarank", "num_leaves": 7,
                          "verbosity": -1, "tree_grow_policy": "wave"},
                         lgb.Dataset(X, label=yr, group=group),
                         num_boost_round=10)
        # higher raw score should correlate with higher relevance
        sc = bstr.predict(X, raw_score=True)
        assert np.corrcoef(sc, yr)[0, 1] > 0.5

    def test_overgrow_tiny_trees(self):
        """Edge sizes: overgrow with num_leaves 2 and 4 prunes back
        correctly (replay == leaf_id, leaf counts respected)."""
        import jax.numpy as jnp
        from lightgbm_tpu.booster import Booster
        from lightgbm_tpu.ops.predict import replay_leaf_ids
        X, y = make_binary(1500)
        for L in (2, 4):
            bst = Booster(params={"objective": "binary", "num_leaves": L,
                                  "verbosity": -1,
                                  "tree_grow_policy": "wave",
                                  "tpu_wave_overgrow": 2.0},
                          train_set=lgb.Dataset(X, label=y))
            g, h = bst._grad_fn(bst._train_score)
            dev = bst._grower(bst._train_bins, g.astype(jnp.float32),
                              h.astype(jnp.float32), bst._ones,
                              bst._feat,
                              jnp.asarray(bst._dd.base_allowed))
            assert int(dev.n_splits) <= L - 1
            replayed = replay_leaf_ids(dev, bst._train_bins,
                                       bst._feat["nb"],
                                       bst._feat["missing"])
            np.testing.assert_array_equal(np.asarray(replayed),
                                          np.asarray(dev.leaf_id))

    def test_eval_driven_training_and_determinism(self):
        """Wave under the fused eval-driven chunk path (valid sets +
        early stopping sync once per chunk) and bit-identical reruns
        for the same seed."""
        X, y = make_binary(3000)
        Xe, ye = make_binary(1200, seed=17)

        def train_once():
            ev = {}
            bst = lgb.train({"objective": "binary", "num_leaves": 15,
                             "verbosity": -1, "tree_grow_policy": "wave",
                             "metric": "auc", "seed": 7},
                            lgb.Dataset(X, label=y), num_boost_round=40,
                            valid_sets=[lgb.Dataset(Xe, label=ye)],
                            callbacks=[lgb.early_stopping(5,
                                                          verbose=False),
                                       lgb.record_evaluation(ev)])
            return bst, ev

        b1, ev1 = train_once()
        b2, ev2 = train_once()
        assert b1.model_to_string() == b2.model_to_string()
        aucs = ev1["valid_0"]["auc"]
        assert aucs[-1] >= aucs[0]
        assert max(aucs) > 0.85

    def test_downgrade_reasons(self, caplog):
        # r5: CEGB, interaction constraints, and forced splits are all
        # wave-ELIGIBLE; monotone intermediate still downgrades, and the
        # warning prices the fallback
        import logging
        X, y = make_binary(1500)
        with caplog.at_level(logging.WARNING, logger="lightgbm_tpu"):
            bst = lgb.train({"objective": "binary", "num_leaves": 7,
                             "verbosity": 1, "tree_grow_policy": "wave",
                             "monotone_constraints": [1] + [0] * 7,
                             "monotone_constraints_method": "intermediate"},
                            lgb.Dataset(X, label=y), num_boost_round=3)
        assert bst._grow_policy == "leafwise"
        assert "lower training throughput" in caplog.text, caplog.text
        for extra in ({"cegb_tradeoff": 1.0, "cegb_penalty_split": 0.1},
                      {"interaction_constraints": [[0, 1], [2, 3]]},
                      {}):
            bst = lgb.train({"objective": "binary", "num_leaves": 7,
                             "verbosity": -1, "tree_grow_policy": "wave",
                             **extra},
                            lgb.Dataset(X, label=y), num_boost_round=3)
            assert bst._grow_policy == "wave", extra

    def test_forced_splits_under_wave(self, tmp_path):
        """r5: forced splits run under wave — the BFS prefix is honored
        (width-1 waves), free growth resumes after, and a full strict
        tail stays byte-identical to the leafwise grower."""
        import json as _json
        X, y = make_binary(2500)
        forced = {"feature": 4, "threshold": 0.0,
                  "left": {"feature": 5, "threshold": 0.5}}
        fn = str(tmp_path / "forced.json")
        with open(fn, "w") as f:
            _json.dump(forced, f)
        # real waves: prefix honored, policy stays wave, still learns
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbosity": -1, "tree_grow_policy": "wave",
                         "tpu_wave_width": 8, "tpu_wave_gain_ratio": 0,
                         "forcedsplits_filename": fn},
                        lgb.Dataset(X, label=y), num_boost_round=5)
        assert bst._grow_policy == "wave"
        for t in bst.trees:
            assert t.split_feature[0] == 4
            assert t.split_feature[1] == 5
        # byte-identity at full strict tail (width-1 waves == strict)
        strip = ("[tree_grow_policy", "[tpu_wave")
        dumps = {}
        for pol, wav in (("leafwise", {}),
                         ("wave", {"tpu_wave_strict_tail": 1000,
                                   "tpu_wave_gain_ratio": 0})):
            b = lgb.train({"objective": "binary", "num_leaves": 15,
                           "verbosity": -1, "tree_grow_policy": pol,
                           "tpu_wave_overgrow": 0,
                           "forcedsplits_filename": fn, **wav},
                          lgb.Dataset(X, label=y), num_boost_round=6)
            assert b._grow_policy == pol
            txt = b.model_to_string()
            dumps[pol] = "\n".join(ln for ln in txt.splitlines()
                                   if not ln.startswith(strip))
        assert dumps["leafwise"] == dumps["wave"]

    def test_forced_prefix_does_not_pin_wave_width(self, tmp_path):
        """Regression (r6): the forced prefix used to pin wcap to 1 for
        every wave it STARTED in, so the wave committing the last
        forced split ended immediately instead of continuing into free
        picks — and with a forced first pick seeding the capacity-aware
        gain floor, later free picks could be throttled by the forced
        split's arbitrary gain.  After the fix, forced ordering is still
        strict (gated in icond to the wave's first pick) but trees must
        reach full capacity with the floor intact."""
        import json as _json
        X, y = make_binary(2500)
        forced = {"feature": 4, "threshold": 0.0,
                  "left": {"feature": 5, "threshold": 0.5}}
        fn = str(tmp_path / "forced.json")
        with open(fn, "w") as f:
            _json.dump(forced, f)
        # wide waves + a nonzero gain ratio (exercises the g_floor
        # guard: a forced pick must leave the floor open for the free
        # picks that now share its wave)
        bst = lgb.train({"objective": "binary", "num_leaves": 31,
                         "verbosity": -1, "tree_grow_policy": "wave",
                         "tpu_wave_width": 8,
                         "tpu_wave_gain_ratio": 0.5,
                         "min_data_in_leaf": 5,
                         "forcedsplits_filename": fn},
                        lgb.Dataset(X, label=y), num_boost_round=4)
        assert bst._grow_policy == "wave"
        for t in bst.trees:
            assert t.split_feature[0] == 4
            assert t.split_feature[1] == 5
            # free growth resumed at full width: capacity is actually
            # consumed, not stalled behind the forced prefix
            assert t.num_leaves >= 20, t.num_leaves
        p = bst.predict(X)
        assert np.isfinite(p).all()

    def test_forced_splits_survive_overgrow_prune(self, tmp_path):
        """Grow-then-prune must never prune the forced prefix — the
        forced-split contract outranks gain-based pruning (code-review
        r5 finding: argmin over split_gain had no prefix exclusion)."""
        import json as _json
        X, y = make_binary(2500)
        # force a LOW-VALUE split (a feature the data barely uses) so
        # the prune would certainly remove it if allowed to
        forced = {"feature": 7, "threshold": 0.0}
        fn = str(tmp_path / "forced.json")
        with open(fn, "w") as f:
            _json.dump(forced, f)
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbosity": -1, "tree_grow_policy": "wave",
                         "tpu_wave_width": 8, "tpu_wave_gain_ratio": 0,
                         "tpu_wave_overgrow": 2.0,
                         "forcedsplits_filename": fn},
                        lgb.Dataset(X, label=y), num_boost_round=4)
        assert bst._grow_policy == "wave"
        for t in bst.trees:
            assert t.num_leaves <= 15
            assert t.split_feature[0] == 7, \
                "overgrow prune removed the forced root split"

    def test_infeasible_forced_split_under_wave(self, tmp_path):
        """A forced chain deeper than min_data_in_leaf allows must
        abandon the remaining prefix under wave too, not corrupt the
        tree (mirrors the strict grower's regression test)."""
        import json as _json
        X, y = make_binary(300)
        deep = {"feature": 0, "threshold": 0.0}
        node = deep
        for i in range(1, 6):
            node["left"] = {"feature": i % 8, "threshold": 0.0}
            node = node["left"]
        fn = str(tmp_path / "deep.json")
        with open(fn, "w") as f:
            _json.dump(deep, f)
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbosity": -1, "tree_grow_policy": "wave",
                         "min_data_in_leaf": 100,
                         "forcedsplits_filename": fn},
                        lgb.Dataset(X, label=y), num_boost_round=3)
        assert bst._grow_policy == "wave"
        p = bst.predict(X)
        assert np.isfinite(p).all()

    def test_cegb_ic_strict_tail_byte_identical(self):
        """r5: CEGB / interaction constraints under wave with a full
        strict tail (width-1 waves ARE strict order) must produce
        byte-identical models to the leafwise grower — candidate
        pricing and allowed-feature filtering are shared code and
        order-independent within a tree."""
        X, y = make_binary(2500)
        strip = ("[tree_grow_policy", "[tpu_wave")
        F = X.shape[1]
        for extra in ({"cegb_tradeoff": 0.8, "cegb_penalty_split": 0.05},
                      {"cegb_tradeoff": 1.0,
                       "cegb_penalty_feature_coupled": [5.0] * F,
                       "cegb_penalty_feature_lazy": [0.01] * F},
                      {"interaction_constraints": [[0, 1, 2], [3, 4, 5],
                                                   [0, 6, 7]]}):
            dumps = {}
            for pol, wav in (("leafwise", {}),
                             ("wave", {"tpu_wave_strict_tail": 1000,
                                       "tpu_wave_gain_ratio": 0})):
                bst = lgb.train({"objective": "binary", "num_leaves": 15,
                                 "verbosity": -1, "tree_grow_policy": pol,
                                 "tpu_wave_overgrow": 0, **extra, **wav},
                                lgb.Dataset(X, label=y),
                                num_boost_round=6)
                assert bst._grow_policy == pol, (pol, extra)
                txt = bst.model_to_string()
                body = "\n".join(ln for ln in txt.splitlines()
                                 if not ln.startswith(strip))
                dumps[pol] = (body, bst.predict(X))
            assert dumps["leafwise"][0] == dumps["wave"][0], extra
            np.testing.assert_array_equal(dumps["leafwise"][1],
                                          dumps["wave"][1])

    def test_ic_paths_respected_under_wide_waves(self):
        """Real waves (W > 1, no tail): every root path must stay inside
        one constraint group — the per-leaf used-feature plane threads
        through the batched split phase."""
        X, y = make_binary(3000)
        groups = [[0, 1, 3], [2, 4, 5], [6, 7]]
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbosity": -1, "tree_grow_policy": "wave",
                         "tpu_wave_width": 8, "tpu_wave_gain_ratio": 0,
                         "tpu_wave_strict_tail": 0,
                         "interaction_constraints": groups},
                        lgb.Dataset(X, label=y), num_boost_round=6)
        assert bst._grow_policy == "wave"
        gsets = [frozenset(g) for g in groups]

        def paths(t):
            # leaf slot k's path = features of splits on its root chain
            out = []
            for leaf in range(t.num_leaves):
                feats, nd = set(), -leaf - 1
                # walk up: find parent of node nd
                def parent_of(target):
                    for i in range(t.num_internal()):
                        if t.left_child[i] == target \
                                or t.right_child[i] == target:
                            return i
                    return None
                cur = nd
                while True:
                    p = parent_of(cur)
                    if p is None:
                        break
                    feats.add(int(t.split_feature[p]))
                    cur = p
                out.append(frozenset(feats))
            return out

        for t in bst.trees:
            for path in paths(t):
                assert any(path <= g for g in gsets), \
                    f"path {set(path)} violates constraints"

    def test_cegb_effects_hold_under_wide_waves(self):
        """CEGB's qualitative behavior must survive real waves: the
        split penalty still prunes leaves and the coupled penalty still
        concentrates the used-feature set."""
        rng = np.random.RandomState(0)
        X = rng.randn(3000, 8)
        y = X.sum(axis=1) * 0.5 + 0.5 * rng.randn(3000)
        wave = {"tree_grow_policy": "wave", "tpu_wave_width": 8,
                "tpu_wave_gain_ratio": 0, "tpu_wave_strict_tail": 0}
        base = lgb.train({"objective": "regression", "num_leaves": 31,
                          "verbosity": -1, **wave},
                         lgb.Dataset(X, label=y), num_boost_round=3)
        pen = lgb.train({"objective": "regression", "num_leaves": 31,
                         "cegb_tradeoff": 1.0, "cegb_penalty_split": 0.2,
                         "verbosity": -1, **wave},
                        lgb.Dataset(X, label=y), num_boost_round=3)
        assert pen._grow_policy == "wave"
        n_base = sum(t.num_leaves for t in base.trees)
        n_pen = sum(t.num_leaves for t in pen.trees)
        assert n_pen < n_base, (n_pen, n_base)

        coup = lgb.train({"objective": "regression", "num_leaves": 15,
                          "cegb_tradeoff": 1.0,
                          "cegb_penalty_feature_coupled": [50.0] * 8,
                          "verbosity": -1, **wave},
                         lgb.Dataset(X, label=y), num_boost_round=8)

        def used(b):
            s = set()
            for t in b.trees:
                s.update(t.split_feature[:t.num_internal()].tolist())
            return s

        free = lgb.train({"objective": "regression", "num_leaves": 15,
                          "verbosity": -1, **wave},
                         lgb.Dataset(X, label=y), num_boost_round=8)
        assert len(used(coup)) <= len(used(free))


class TestWaveDistributed:
    def test_data_parallel_matches_serial(self):
        """Wave + tree_learner=data over the 8-device CPU mesh: per-shard
        partial histograms psum to EXACTLY the serial sums (same f32
        add order per segment), so trees must match the serial wave's."""
        assert len(jax.devices()) == 8
        X, y = make_binary(3000)
        preds = {}
        for learner in ("serial", "data"):
            bst = lgb.train({"objective": "binary", "num_leaves": 15,
                             "verbosity": -1, "tree_grow_policy": "wave",
                             "tree_learner": learner},
                            lgb.Dataset(X, label=y), num_boost_round=10)
            assert bst._grow_policy == "wave"
            preds[learner] = bst.predict(X, raw_score=True)
        np.testing.assert_allclose(preds["serial"], preds["data"],
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.quick
class TestPhaseScopes:
    """The device phase scopes of ops/grow_wave.py name operations; they
    must not change the program (ISSUE 25: metric selectors are scope
    names, and a scope that moved a fusion would move what it measures)."""

    PHASES = ("payload", "init", "histogram_wave", "find_split",
              "partition", "hist_cache")

    @staticmethod
    def _compiled(params):
        import re
        from lightgbm_tpu.ops.grow_wave import make_wave_grower
        make_wave_grower.cache_clear()
        X, y = make_binary(1500, 6)
        bst = lgb.Booster(params={"objective": "binary", "verbosity": -1,
                                  "tree_grow_policy": "wave", **params},
                          train_set=lgb.Dataset(X, label=y))
        assert bst._grow_policy == "wave"
        n, f = X.shape
        ones = jnp.ones((n,), jnp.float32)
        text = bst._make_serial_grower().lower(
            bst._train_bins, ones, ones, ones, bst._feat,
            jnp.ones((f,), bool)).compile().as_text()
        # the program without its names: no `metadata={...}` on an
        # instruction, and none of the module's source-location tables
        tables = ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames")
        blocks = [b for b in re.sub(r",? ?metadata=\{[^}]*\}", "", text)
                  .split("\n\n") if not b.lstrip().startswith(tables)]
        return text, "\n\n".join(blocks)

    @pytest.mark.parametrize("params", [
        {"num_leaves": 7},
        {"num_leaves": 7, "tpu_wave_overgrow": 1.5},
    ], ids=["plain", "overgrow"])
    def test_scopes_change_names_only(self, params, monkeypatch):
        import contextlib
        with_names, with_scopes = self._compiled(params)
        for phase in self.PHASES + (("prune",) if "tpu_wave_overgrow"
                                    in params else ()):
            assert f"/{phase}/" in with_names, phase
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        without_names, without_scopes = self._compiled(params)
        assert "/partition/" not in without_names
        assert with_scopes == without_scopes
        from lightgbm_tpu.ops.grow_wave import make_wave_grower
        make_wave_grower.cache_clear()     # no unnamed grower left cached


# ------------------------------------------------ the strict tail's schedule
def _golden_script():
    """`tests/data/make_wave_tail_goldens.py`: the cases and how each is
    trained."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "make_wave_tail_goldens.py")
    spec = importlib.util.spec_from_file_location("wave_tail_goldens", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_GOLD = _golden_script()


@pytest.fixture(scope="module")
def golden_models():
    """The models the PARENT of ISSUE 26 dumped for `_GOLD.CASES`."""
    import json
    with open(_GOLD.GOLDENS) as fh:
        return json.load(fh)


def _grow_once(X, y, **params):
    """One tree of the wave grower on a fresh booster: (DeviceTree, LB)."""
    from lightgbm_tpu.booster import Booster
    from lightgbm_tpu.ops.grow_wave import wave_sizes
    bst = Booster(params={"verbosity": -1, "tree_grow_policy": "wave",
                          "tpu_wave_gain_ratio": 0, "tpu_wave_overgrow": 0,
                          **params},
                  train_set=lgb.Dataset(X, label=y))
    assert bst._grow_policy == "wave"
    g, h = bst._grad_fn(bst._train_score)
    dev = bst._grower(bst._train_bins, g.astype(jnp.float32),
                      h.astype(jnp.float32), bst._ones, bst._feat,
                      jnp.asarray(bst._dd.base_allowed))
    return dev, wave_sizes(bst._grower_spec)[0], bst


def _tail_splits(dev, LB, tail):
    """(splits the tree made inside its strict tail, whether the last of
    them filled the tree) from the schedule's own arithmetic."""
    n = int(dev.n_splits)
    before = LB - 1 - min(tail, LB - 1)
    return max(n - before, 0), n == LB - 1


@pytest.mark.quick
class TestSpeculativeTail:
    """ISSUE 26: a tail pass speculates the smaller child of up to W
    frontier leaves, and a split whose child histogram is cached costs no
    pass.  The schedule changed; the trees must not have."""

    @pytest.mark.parametrize("case", _GOLD.CASES, ids=lambda c: c[0])
    def test_golden_models(self, case, golden_models):
        cid, data, leaves, tail, extra = case
        assert _GOLD.train_case(data, leaves, tail, extra) \
            == golden_models[cid]

    @pytest.mark.parametrize("leaves,tail,width", [
        (31, 16, 8), (31, 16, 2), (31, 4, 6), (15, 1000, 4), (8, 1, 6),
        (63, 32, 8)], ids=lambda v: str(v))
    def test_every_speculated_histogram_is_accounted(self, leaves, tail,
                                                     width):
        X, y = make_binary(2500)
        dev, LB, _ = _grow_once(X, y, objective="binary", num_leaves=leaves,
                                tpu_wave_strict_tail=tail,
                                tpu_wave_width=width, min_data_in_leaf=5)
        passes, hits, unused, speculated = (int(v) for v in dev.tail_stats[:4])
        # a speculated slot is the missed leaf its pass was made for, a
        # later hit, or never used
        assert speculated == passes + hits + unused
        assert passes <= speculated <= passes * min(width, LB - 1)
        # every tail split whose children needed histograms got them from
        # a pass of its own or from the cache; the split that fills the
        # tree needs none
        made, filled = _tail_splits(dev, LB, tail)
        assert made > 0
        assert passes + hits == made - int(filled)

    def test_fewer_passes_than_one_per_split(self):
        """The 31-leaf numerical case: the parent made 15 passes for its
        16 tail splits."""
        X, y = make_binary(3000)
        dev, LB, _ = _grow_once(X, y, objective="binary", num_leaves=31,
                                tpu_wave_strict_tail=16, tpu_wave_width=8,
                                min_data_in_leaf=5)
        passes, hits, _, _ = (int(v) for v in dev.tail_stats[:4])
        assert _tail_splits(dev, LB, 16) == (16, True)
        assert passes + hits == 15
        assert passes < 15 and hits > 0

    def test_chain_tree_never_hits(self):
        """One feature of 16 values, a target that quadruples from each
        to the next: the best split always cuts the top value off and
        only the rest can be split again, so each split's leaf is a
        child of the split before.  No speculated histogram is ever for
        another leaf than the missed one, and the passes are the
        parent's: one per split but the one that fills the tree."""
        x = (np.arange(4096) % 16).astype(np.float32)
        dev, LB, _ = _grow_once(x[:, None], 4.0 ** x,
                                objective="regression", num_leaves=8,
                                tpu_wave_strict_tail=1000,
                                min_data_in_leaf=5)
        n_splits = int(dev.n_splits)
        assert n_splits == LB - 1 == 7
        split_leaf = np.asarray(dev.split_leaf)
        for i in range(1, n_splits):
            assert split_leaf[i] in (split_leaf[i - 1], i)
        passes, hits, unused, speculated = (int(v) for v in dev.tail_stats[:4])
        assert hits == 0
        assert passes == n_splits - 1
        assert speculated == passes + unused

    def test_early_stop_drains_the_cache(self):
        """A tree that runs out of positive gains before it is full
        decodes as the strict grower's, and leaves nothing speculated
        behind: a histogram is speculated only for a leaf of positive
        gain, and the tree stops when no such leaf is left."""
        X, y = make_binary(400)
        dumps = {}
        strip = ("[tree_grow_policy", "[tpu_wave")
        params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
                  "min_data_in_leaf": 40, "tpu_wave_overgrow": 0}
        for pol, extra in (("leafwise", {}),
                           ("wave", {"tpu_wave_strict_tail": 1000,
                                     "tpu_wave_gain_ratio": 0})):
            bst = lgb.train({**params, "tree_grow_policy": pol, **extra},
                            lgb.Dataset(X, label=y), num_boost_round=4)
            assert bst._grow_policy == pol
            assert all(1 < t.num_leaves < 31 for t in bst.trees)
            dumps[pol] = ("\n".join(
                ln for ln in bst.model_to_string().splitlines()
                if not ln.startswith(strip)), bst.predict(X), bst)
        assert dumps["leafwise"][0] == dumps["wave"][0]
        np.testing.assert_array_equal(dumps["leafwise"][1],
                                      dumps["wave"][1])
        for t in dumps["wave"][2].trees:
            passes, hits, unused, speculated = t.tail_stats[:4]
            assert unused == 0 and hits > 0
            # no split filled the tree: each got its children's histograms
            assert passes + hits == speculated == t.num_internal()
        assert all(t.tail_stats is None for t in dumps["leafwise"][2].trees)

    def test_counters_follow_the_trees(self):
        """`grow.tail_passes` / `grow.tail_spec_hits` /
        `grow.tail_spec_unused` / `grow.wave_passes` / `grow.leaves` grow
        at decode by what each tree reports, on the per-round path and
        the fused chunk's alike."""
        from lightgbm_tpu import telemetry
        names = ("grow.tail_passes", "grow.tail_spec_hits",
                 "grow.tail_spec_unused", "grow.wave_passes", "grow.leaves")

        def read():
            return [telemetry.REGISTRY.counter(k).value for k in names]

        X, y = make_binary(1500)
        bst = lgb.Booster(params={"objective": "binary", "num_leaves": 15,
                                  "verbosity": -1,
                                  "tree_grow_policy": "wave"},
                          train_set=lgb.Dataset(X, label=y))
        before = read()
        for _ in range(2):
            bst.update()
        bst.update_many(bst._BULK_CHUNK)
        assert len(bst.trees) == 2 + bst._BULK_CHUNK
        want = np.sum([t.tail_stats[:3] + (t.tail_stats[4], t.num_leaves)
                       for t in bst.trees], axis=0)
        assert want[0] > 0 and want[3] > 0
        np.testing.assert_array_equal(np.subtract(read(), before), want)

    @pytest.mark.parametrize("leaves,tail", [(31, 16), (31, 0), (12, 4)])
    def test_a_trees_passes_are_root_waves_and_tail(self, leaves, tail):
        """`tail_stats[4]` counts the waves' kernel passes: with the
        tail's passes and hits it accounts for every split of the tree
        (a wave of width W takes up to W splits a pass; a tail pass or a
        hit takes one; the split that fills the tree takes none)."""
        X, y = make_binary(2000)
        bst = lgb.train({"objective": "binary", "num_leaves": leaves,
                         "verbosity": -1, "tree_grow_policy": "wave",
                         "min_data_in_leaf": 5, "tpu_wave_width": 4,
                         "tpu_wave_gain_ratio": 0,
                         "tpu_wave_strict_tail": tail},
                        lgb.Dataset(X, label=y), num_boost_round=2)
        for t in bst.trees:
            passes, hits, _, _, waves = t.tail_stats[:5]
            assert t.num_leaves == leaves
            in_tail = min(tail, leaves - 1)
            in_waves = leaves - 1 - in_tail
            assert -(-in_waves // 4) <= waves <= max(in_waves, 0)
            if tail:
                assert passes + hits in (in_tail - 1, in_tail)
            else:
                assert passes == hits == 0
