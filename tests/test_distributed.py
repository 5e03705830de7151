"""Distributed data-parallel correctness on the virtual 8-device CPU mesh —
the TPU build's analog of the reference's tests/distributed/
_test_distributed.py (N workers vs single-process metric/prediction parity,
here N shards vs 1 shard on one host)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.grow import GrowerSpec, make_grower
from lightgbm_tpu.parallel import get_mesh, make_sharded_train_step, \
    shard_dataset


def _binary_grad(score, label):
    p = jax.nn.sigmoid(score)
    return p - label, p * (1 - p)


def make_data(n=2048, f=6, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] - 0.5 * X[:, 1] + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def _feat_of(mappers, f):
    return dict(
        nb=jnp.asarray(np.array([m.num_bin for m in mappers], np.int32)),
        missing=jnp.asarray(np.array([m.missing_type for m in mappers],
                                     np.int32)),
        default=jnp.asarray(np.array([m.default_bin for m in mappers],
                                     np.int32)),
        is_cat=jnp.asarray(np.array([m.bin_type == 1 for m in mappers],
                                    dtype=bool)),
        mono=jnp.zeros(f, jnp.int32))


# Tiering: every test here passes on the virtual 8-device mesh, but the
# full-parity trainings compile large shard_map programs (~2.5 min for
# the file on a shared CPU box).  Tier-1 (-m 'not slow') keeps one fast
# representative per distributed surface (grower parity, public-API data
# learner, dcn mesh, fused chunks); the heavyweight parity variants run
# in `scripts/run_ci.sh full`.
class TestShardedGrower:
    def test_eight_devices_available(self):
        assert len(jax.devices()) == 8

    @pytest.mark.parametrize(
        "shards", [2, pytest.param(8, marks=pytest.mark.slow)])
    def test_sharded_matches_single(self, shards):
        """Multi-round BYTE-identity to the serial grower (ROADMAP 1a):
        with the default deterministic fixed-order reduction, every
        round's tree — leaf values included — and the carried score
        vector must be bit-equal to serial, so sharded training cannot
        drift after round 1."""
        X, y = make_data()
        ds = lgb.Dataset(X, label=y)
        ds.construct()
        bins = np.asarray(ds.bin_data)
        mappers = ds.bin_mappers
        spec = GrowerSpec(num_leaves=15, max_depth=-1,
                          max_bin=max(m.num_bin for m in mappers),
                          lambda_l1=0.0, lambda_l2=0.0,
                          min_data_in_leaf=20.0,
                          min_sum_hessian_in_leaf=1e-3,
                          min_gain_to_split=0.0, max_delta_step=0.0)
        feat = _feat_of(mappers, bins.shape[1])
        allowed = jnp.asarray(np.array(
            [not m.is_trivial for m in mappers], dtype=bool))

        # single-device multi-round reference; the score update runs
        # jitted with the sharded step's exact expression (an eager
        # update re-associates the fused multiply-add)
        grow = make_grower(spec)
        label32 = jnp.asarray(y.astype(np.float32))
        ones = jnp.ones(len(y), jnp.float32)

        @jax.jit
        def serial_update(score, lv, lid):
            return score + lv[lid] * 0.1

        score_ref = jnp.zeros(len(y), jnp.float32)
        refs = []
        for _ in range(3):
            g, h = _binary_grad(score_ref, label32)
            ref = grow(jnp.asarray(bins.T), g, h, ones, feat, allowed)
            refs.append(ref)
            score_ref = serial_update(score_ref, ref.leaf_value,
                                      ref.leaf_id)

        # sharded steps (det_reduce defaults ON; num_data pins pad rows
        # out of the deterministic accumulation order)
        mesh = get_mesh(shards)
        step = make_sharded_train_step(spec, mesh, _binary_grad, 0.1,
                                       num_data=len(y))
        dev_bins, dev_label, dev_w, n_pad = shard_dataset(bins, y, mesh)
        assert n_pad == 0
        score = jax.device_put(
            np.zeros(len(y), np.float32),
            jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("data")))
        for r in range(3):
            score, tree = step(score, dev_label, dev_w, dev_bins,
                               feat, allowed)
            ref = refs[r]
            assert int(tree.n_splits) == int(ref.n_splits), f"round {r}"
            np.testing.assert_array_equal(np.asarray(tree.split_feature),
                                          np.asarray(ref.split_feature))
            np.testing.assert_array_equal(np.asarray(tree.threshold_bin),
                                          np.asarray(ref.threshold_bin))
            np.testing.assert_array_equal(np.asarray(tree.leaf_value),
                                          np.asarray(ref.leaf_value))
            np.testing.assert_array_equal(np.asarray(tree.leaf_id),
                                          np.asarray(ref.leaf_id))
        np.testing.assert_array_equal(np.asarray(score),
                                      np.asarray(score_ref))

    @pytest.mark.parametrize("wave", [True, False])
    def test_det_reduce_keeps_the_pallas_kernel(self, wave, monkeypatch):
        """With `hist_impl=pallas` the deterministic reduction must chain
        the per-shard KERNEL histograms (`ring_ordered_sum`), never swap
        the kernel for the streamed XLA scatter-add: on four real v5e
        chips that swap cost 1308.7 s instead of 27.9 s for 48 rounds
        (PR 21).  Trace only — nothing is compiled or run."""
        from lightgbm_tpu.ops import grow_wave
        from lightgbm_tpu.parallel.learner import make_distributed_grower

        def scatter_path(*a, **k):
            raise AssertionError("det reduce left the Pallas kernel for "
                                 "the streamed scatter-add")

        monkeypatch.setattr(grow_wave, "hist_stream_update", scatter_path)
        n, f, mb = 1024, 8, 32
        spec = GrowerSpec(num_leaves=9, max_depth=-1, max_bin=mb,
                          lambda_l1=0.0, lambda_l2=0.0,
                          min_data_in_leaf=5.0,
                          min_sum_hessian_in_leaf=1e-3,
                          min_gain_to_split=0.0, max_delta_step=0.0,
                          hist_impl="pallas", hist_interpret=True,
                          wave_width=4)
        grow = make_distributed_grower(spec, get_mesh(4), "data", f, n,
                                       wave=wave, det_reduce=True)
        feat = dict(nb=jnp.full((f,), mb, jnp.int32),
                    missing=jnp.zeros((f,), jnp.int32),
                    default=jnp.zeros((f,), jnp.int32),
                    is_cat=jnp.zeros((f,), bool),
                    mono=jnp.zeros((f,), jnp.int32))
        ones = jnp.ones((n,), jnp.float32)
        text = str(jax.make_jaxpr(grow)(
            jnp.zeros((f, n), jnp.uint8), ones, ones, ones, feat,
            jnp.ones((f,), bool)))
        # the old det path never traced the kernel at all
        assert "pallas_call" in text and "ppermute" in text

    @pytest.mark.slow
    def test_multi_iteration_sharded_training(self):
        X, y = make_data(1600)
        ds = lgb.Dataset(X, label=y)
        ds.construct()
        bins = np.asarray(ds.bin_data)
        mappers = ds.bin_mappers
        spec = GrowerSpec(15, -1, max(m.num_bin for m in mappers),
                          0.0, 0.0, 20.0, 1e-3, 0.0, 0.0)
        feat = _feat_of(mappers, bins.shape[1])
        allowed = jnp.asarray(np.ones(bins.shape[1], dtype=bool))
        mesh = get_mesh(8)
        step = make_sharded_train_step(spec, mesh, _binary_grad, 0.2)
        dev_bins, dev_label, dev_w, _ = shard_dataset(bins, y, mesh)
        score = jax.device_put(
            np.zeros(len(y), np.float32),
            jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("data")))
        for _ in range(10):
            score, _tree = step(score, dev_label, dev_w, dev_bins,
                                feat, allowed)
        p = 1.0 / (1.0 + np.exp(-np.asarray(score)))
        logloss = -np.mean(y * np.log(p + 1e-9)
                           + (1 - y) * np.log(1 - p + 1e-9))
        assert logloss < 0.45  # learned something across 8 shards

    @pytest.mark.slow
    def test_public_api_tree_learner_parity(self):
        """`lgb.train({"tree_learner": ...})` must actually shard and grow
        the same trees as the serial learner (ref: the reference's
        tests/distributed/_test_distributed.py N-worker vs single-process
        parity).  Row/feature counts deliberately do NOT divide 8."""
        X, y = make_data(1100, f=7, seed=11)
        params = {"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 20, "learning_rate": 0.1,
                  "verbosity": -1}
        serial = lgb.train({**params, "tree_learner": "serial"},
                           lgb.Dataset(X, label=y), num_boost_round=5)
        preds_ref = serial.predict(X, raw_score=True)
        for kind in ("data", "feature", "voting_parallel"):
            dist = lgb.train({**params, "tree_learner": kind},
                             lgb.Dataset(X, label=y), num_boost_round=5)
            assert getattr(dist, "_mesh", None) is not None, \
                f"{kind}: mesh was not set up"
            for ts, td in zip(serial.trees, dist.trees):
                np.testing.assert_array_equal(
                    ts.split_feature[:ts.num_internal()],
                    td.split_feature[:td.num_internal()])
                np.testing.assert_array_equal(
                    ts.threshold_bin[:ts.num_internal()],
                    td.threshold_bin[:td.num_internal()])
            np.testing.assert_allclose(dist.predict(X, raw_score=True),
                                       preds_ref, rtol=2e-4, atol=2e-5)

    @pytest.mark.slow
    def test_wave_data_rs_parity(self):
        """The wave policy composes with tree_learner=data's production
        reduce-scatter mode (VERDICT r3 #3): block-scattered multi-leaf
        histograms + per-wave SplitInfo allreduce-max must grow the SAME
        trees as the single-device wave grower."""
        X, y = make_data(1100, f=7, seed=31)
        params = {"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 20, "learning_rate": 0.1,
                  "tree_grow_policy": "wave", "verbosity": -1}
        serial = lgb.train({**params, "tree_learner": "serial"},
                           lgb.Dataset(X, label=y), num_boost_round=5)
        assert serial._grow_policy == "wave"
        dist = lgb.train({**params, "tree_learner": "data"},
                         lgb.Dataset(X, label=y), num_boost_round=5)
        assert dist._mesh is not None, "mesh was not set up"
        assert dist._grow_policy == "wave", \
            "wave must no longer downgrade under tree_learner=data"
        for ts, td in zip(serial.trees, dist.trees):
            np.testing.assert_array_equal(
                ts.split_feature[:ts.num_internal()],
                td.split_feature[:td.num_internal()])
            np.testing.assert_array_equal(
                ts.threshold_bin[:ts.num_internal()],
                td.threshold_bin[:td.num_internal()])
        np.testing.assert_allclose(dist.predict(X, raw_score=True),
                                   serial.predict(X, raw_score=True),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.slow
    def test_wave_data_rs_with_cegb_and_ic_parity(self):
        """r5: CEGB penalties + interaction constraints must survive the
        distributed wave grower's block split search (penalty/mask
        vectors are block-sliced per shard before the SplitInfo merge) —
        same trees as the serial wave grower."""
        X, y = make_data(1200, f=8, seed=33)
        params = {"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 20, "learning_rate": 0.1,
                  "tree_grow_policy": "wave", "verbosity": -1,
                  "cegb_tradeoff": 0.5, "cegb_penalty_split": 0.01,
                  "cegb_penalty_feature_coupled": [2.0] * 8,
                  "interaction_constraints": [[0, 1, 2, 3], [4, 5, 6, 7]]}
        serial = lgb.train({**params, "tree_learner": "serial"},
                           lgb.Dataset(X, label=y), num_boost_round=5)
        assert serial._grow_policy == "wave"
        dist = lgb.train({**params, "tree_learner": "data"},
                         lgb.Dataset(X, label=y), num_boost_round=5)
        assert dist._mesh is not None and dist._grow_policy == "wave"
        for ts, td in zip(serial.trees, dist.trees):
            np.testing.assert_array_equal(
                ts.split_feature[:ts.num_internal()],
                td.split_feature[:td.num_internal()])
        gsets = [frozenset(g) for g in ([0, 1, 2, 3], [4, 5, 6, 7])]
        for t in dist.trees:
            ni = t.num_internal()
            for leaf in range(t.num_leaves):
                feats, cur = set(), -leaf - 1
                while True:
                    p = next((i for i in range(ni)
                              if t.left_child[i] == cur
                              or t.right_child[i] == cur), None)
                    if p is None:
                        break
                    feats.add(int(t.split_feature[p]))
                    cur = p
                assert any(frozenset(feats) <= g for g in gsets), feats
        np.testing.assert_allclose(dist.predict(X, raw_score=True),
                                   serial.predict(X, raw_score=True),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.slow
    def test_wave_data_rs_forced_splits_parity(self, tmp_path):
        """r5: forced splits under the distributed wave grower — the
        forced feature lives on ONE shard's block; its shard proposes
        the forced split, the others propose -inf, and the SplitInfo
        merge must still honor the BFS prefix.  Same trees as serial."""
        import json
        X, y = make_data(1200, f=8, seed=35)
        forced = {"feature": 6, "threshold": 0.0,
                  "left": {"feature": 1, "threshold": 0.3}}
        fn = str(tmp_path / "forced.json")
        with open(fn, "w") as f:
            json.dump(forced, f)
        params = {"objective": "binary", "num_leaves": 15,
                  "min_data_in_leaf": 20, "learning_rate": 0.1,
                  "tree_grow_policy": "wave", "verbosity": -1,
                  "forcedsplits_filename": fn}
        serial = lgb.train({**params, "tree_learner": "serial"},
                           lgb.Dataset(X, label=y), num_boost_round=4)
        dist = lgb.train({**params, "tree_learner": "data"},
                         lgb.Dataset(X, label=y), num_boost_round=4)
        assert serial._grow_policy == dist._grow_policy == "wave"
        for b in (serial, dist):
            for t in b.trees:
                assert t.split_feature[0] == 6
                assert t.split_feature[1] == 1
        for ts, td in zip(serial.trees, dist.trees):
            np.testing.assert_array_equal(
                ts.split_feature[:ts.num_internal()],
                td.split_feature[:td.num_internal()])
        np.testing.assert_allclose(dist.predict(X, raw_score=True),
                                   serial.predict(X, raw_score=True),
                                   rtol=2e-4, atol=2e-5)

    def test_distributed_fused_chunks_match_periter(self):
        """The fused chunk trainer accepts the shard_map'ped grower —
        multi-chip training syncs once per chunk and must equal the
        per-iteration distributed path exactly."""
        import lightgbm_tpu.booster as booster_mod
        X, y = make_data(1100, f=7, seed=21)
        params = {"objective": "binary", "num_leaves": 15,
                  "tree_learner": "data", "learning_rate": 0.1,
                  "verbosity": -1}
        bc = lgb.train(dict(params), lgb.Dataset(X, label=y),
                       num_boost_round=16)
        assert bc._mesh is not None
        old = booster_mod.Booster._BULK_CHUNK
        booster_mod.Booster._BULK_CHUNK = 10 ** 9
        try:
            bp = lgb.train(dict(params), lgb.Dataset(X, label=y),
                           num_boost_round=16)
        finally:
            booster_mod.Booster._BULK_CHUNK = old
        np.testing.assert_allclose(bc.predict(X, raw_score=True),
                                   bp.predict(X, raw_score=True),
                                   rtol=1e-5, atol=1e-7)

    @pytest.mark.slow
    def test_voting_elects_subset_when_features_exceed_2k(self):
        """Real PV-Tree path: with top_k < F/2, only elected features'
        histograms are reduced — the model must still learn and only use
        a plausible feature set."""
        rng = np.random.RandomState(41)
        X = rng.randn(1600, 24)
        y = (X[:, 3] - 0.8 * X[:, 17] + 0.3 * rng.randn(1600) > 0)\
            .astype(np.float64)
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "tree_learner": "voting", "top_k": 3,
                         "verbosity": -1},
                        lgb.Dataset(X, label=y), num_boost_round=8)
        assert bst._mesh is not None
        p = bst.predict(X)
        assert np.mean(p[y > 0]) > np.mean(p[y == 0])
        # the informative features must be among those used
        used = set()
        for t in bst.trees:
            used.update(t.split_feature[:t.num_internal()].tolist())
        assert 3 in used and 17 in used

    def test_two_level_dcn_mesh_parity(self):
        """2-level ("dcn", "ici") mesh (multi-slice layout): histograms
        reduce-scatter over ICI, allreduce over DCN — results must equal
        the serial learner."""
        X, y = make_data(1100, f=7, seed=31)
        params = {"objective": "binary", "num_leaves": 15,
                  "learning_rate": 0.1, "verbosity": -1}
        serial = lgb.train(dict(params), lgb.Dataset(X, label=y),
                           num_boost_round=5)
        dist = lgb.train({**params, "tree_learner": "data",
                          "tpu_dcn_slices": 2},
                         lgb.Dataset(X, label=y), num_boost_round=5)
        assert dist._mesh is not None
        assert dict(dist._mesh.shape) == {"dcn": 2, "ici": 4}
        np.testing.assert_allclose(dist.predict(X, raw_score=True),
                                   serial.predict(X, raw_score=True),
                                   rtol=2e-4, atol=2e-5)

    def test_num_machines_limits_shards(self):
        X, y = make_data(512, f=4, seed=5)
        bst = lgb.train({"objective": "binary", "num_leaves": 7,
                         "tree_learner": "data", "num_machines": 2,
                         "verbosity": -1},
                        lgb.Dataset(X, label=y), num_boost_round=2)
        assert bst._mesh is not None
        assert bst._mesh.shape["data"] == 2

    @pytest.mark.slow
    def test_fractional_weights_not_squared(self):
        """Row weights must enter the histogram exactly once (g·w, h·w, w) —
        a rank-weighted run must match an unsharded grower given the same
        weighted payload."""
        X, y = make_data(1024)
        w = np.full(len(y), 0.5, np.float32)
        ds = lgb.Dataset(X, label=y)
        ds.construct()
        bins = np.asarray(ds.bin_data)
        mappers = ds.bin_mappers
        spec = GrowerSpec(15, -1, max(m.num_bin for m in mappers),
                          0.0, 0.0, 5.0, 1e-3, 0.0, 0.0)
        feat = _feat_of(mappers, bins.shape[1])
        allowed = jnp.asarray(np.ones(bins.shape[1], dtype=bool))

        grow = make_grower(spec)
        label32 = jnp.asarray(y.astype(np.float32))
        score0 = jnp.zeros(len(y), jnp.float32)
        g, h = _binary_grad(score0, label32)
        ref = grow(jnp.asarray(bins.T), g, h, jnp.asarray(w), feat, allowed)

        mesh = get_mesh(8)
        step = make_sharded_train_step(spec, mesh, _binary_grad, 0.1)
        dev_bins, dev_label, dev_w, _ = shard_dataset(bins, y, mesh,
                                                      weight=w)
        score = jax.device_put(
            np.zeros(len(y), np.float32),
            jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("data")))
        _, tree = step(score, dev_label, dev_w, dev_bins, feat, allowed)
        assert int(tree.n_splits) == int(ref.n_splits)
        np.testing.assert_allclose(np.asarray(tree.leaf_value),
                                   np.asarray(ref.leaf_value),
                                   rtol=2e-4, atol=2e-6)


# ---------------------------------------------------------------------------
# ISSUE 33: four shards x 67 columns, the wave grower under the default
# `deterministic_reduce`, the Pallas kernel (interpreted) on every shard —
# the CPU stand-in of the cell `criteo67-lgbpar-l255.train`
# ---------------------------------------------------------------------------
WIDE_SHARDS = 4


@pytest.fixture(scope="module")
def wide_runs():
    """The 67 columns of the four-chip cell at 4 x 1,024 rows, 15 leaves,
    two rounds: the serial learner once, the data-parallel learner over
    four shards twice; each with what it counted."""
    from lightgbm_tpu import telemetry
    from perfbench import manifest
    from perfbench.generators import tabular_codes
    from perfbench.jobs.train import build_dataset
    config = manifest.config("criteo67-lgbpar-l255")
    rows = tabular_codes.make(5, config["data"], WIDE_SHARDS * 1024, 1)
    names = [c["name"] for c in config["data"]["columns"]]
    base = dict(config["params"], num_leaves=15, hist_impl="pallas",
                hist_interpret=True, num_machines=WIDE_SHARDS)

    def run_of(learner):
        params = dict(base, tree_learner=learner)
        ds = build_dataset(lgb, rows["codes"], rows["label"], params, names)
        bst = lgb.Booster(params=params, train_set=ds)
        before = telemetry.REGISTRY.snapshot()["counters"]
        for _ in range(2):
            bst.update()
        after = telemetry.REGISTRY.snapshot()["counters"]
        grown = {k: after[k] - before.get(k, 0) for k in after
                 if k.startswith("grow.")}
        gauges = telemetry.REGISTRY.snapshot()["gauges"]
        return {"booster": bst, "grown": grown,
                "gauges": {k: gauges[k] for k in
                           ("mesh.shards", "hist.lanes_per_row",
                            "hist.packed_columns")},
                "dump": json.dumps(bst.dump_model()["tree_info"],
                                   sort_keys=True)}

    import json
    return {"serial": run_of("serial"), "data": run_of("data"),
            "again": run_of("data")}


def _permuted(jaxpr, found):
    """Operand avals of every ppermute / all_gather in a jaxpr, nested
    jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("ppermute", "all_gather"):
            found.append((eqn.primitive.name, eqn.invars[0].aval))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _permuted(inner, found)
    return found


@pytest.mark.parametrize("what", [
    "the_serial_learners_trees", "the_same_dump_twice",
    "a_lane_plan_over_the_padded_columns", "hist_passes_of_four_shards",
    "reduce_bytes_are_the_arrays_permuted", "one_program_name"])
def test_four_shards_67_columns_wave(wide_runs, what):
    serial, data, again = (wide_runs[k] for k in ("serial", "data", "again"))
    bst = data["booster"]
    assert bst._mesh is not None \
        and dict(bst._mesh.shape) == {"data": WIDE_SHARDS}
    assert bst._grow_policy == serial["booster"]._grow_policy == "wave"
    if what == "the_serial_learners_trees":
        # node for node; the sums are the serial learner's up to the
        # order of summation
        for ts, td in zip(serial["booster"].trees, bst.trees):
            assert ts.num_leaves == td.num_leaves == 15
            for field in ("split_feature", "threshold_bin", "left_child",
                          "right_child", "leaf_count", "internal_count"):
                np.testing.assert_array_equal(getattr(ts, field),
                                              getattr(td, field), field)
            np.testing.assert_allclose(td.leaf_value, ts.leaf_value,
                                       rtol=0, atol=1e-6)
    elif what == "the_same_dump_twice":
        assert data["dump"] == again["dump"]
    elif what == "a_lane_plan_over_the_padded_columns":
        from lightgbm_tpu.ops.pallas_hist import plan_columns, plan_lanes
        plan = bst._hist_lane_plan()
        assert plan is not None and plan == bst._grower_spec.hist_lane_plan
        assert [c for c, _, _ in plan_columns(plan)] == list(range(68))
        assert plan_columns(plan)[-1][2] == 1          # the pad column
        assert plan_lanes(plan) == 66 * 256 + 128
        assert data["gauges"] == {"mesh.shards": 4,
                                  "hist.lanes_per_row": 17024,
                                  "hist.packed_columns": 2}
        # the serial learner sees 67 columns: I10 alone in its group
        assert [c for c, _, _ in plan_columns(
            serial["booster"]._hist_lane_plan())] == list(range(67))
        assert serial["gauges"] == {"mesh.shards": 1,
                                    "hist.lanes_per_row": 17024,
                                    "hist.packed_columns": 0}
    elif what == "hist_passes_of_four_shards":
        def passes(run):
            g = run["grown"]
            return g["grow.wave_passes"] + g["grow.tail_passes"] + 2

        def calls(run):
            return sum(v for k, v in run["grown"].items()
                       if k.startswith("grow.hist_passes_"))
        assert passes(data) == passes(serial) == calls(serial)
        assert calls(data) == WIDE_SHARDS * calls(serial)
        assert data["grown"]["grow.reduce_passes"] == passes(data)
        assert "grow.reduce_passes" not in serial["grown"] \
            or serial["grown"]["grow.reduce_passes"] == 0
    elif what == "reduce_bytes_are_the_arrays_permuted":
        n = WIDE_SHARDS * 1024
        ones = jnp.ones((n,), jnp.float32)
        found = _permuted(jax.make_jaxpr(bst._grower)(
            bst._train_bins, ones, ones, ones, bst._feat,
            jnp.ones((67,), bool)).jaxpr, [])
        hops = [a for name, a in found if name == "ppermute"]
        # root pass, wave pass, tail pass: three hops each, of one shape
        assert len(hops) == 3 * (WIDE_SHARDS - 1)
        assert {a.shape for a in hops} == {(8, 68, 255, 6)}
        hop = hops[0].size * hops[0].dtype.itemsize
        gathered = [a for name, a in found
                    if name == "all_gather" and a.shape == hops[0].shape]
        assert len(gathered) == 3
        a_pass = (WIDE_SHARDS - 1) * hop + hop
        assert bst._grower.reduce_bytes == a_pass
        assert data["grown"]["grow.reduce_bytes"] \
            == data["grown"]["grow.reduce_passes"] * a_pass
    else:
        # the jitted function is `grow` on one chip and on four
        from lightgbm_tpu.parallel.learner import make_distributed_grower
        grow = make_distributed_grower(
            bst._grower_spec, bst._mesh, "data", 67, WIDE_SHARDS * 1024,
            wave=True, det_reduce=True)
        assert grow is bst._grower
        assert grow.jitted.__name__ == "grow"
        assert serial["booster"]._make_serial_grower().__name__ == "grow"


@pytest.mark.parametrize("learner", ["serial", "data"])
def test_a_deleted_booster_frees_its_device_arrays(learner):
    """`perfbench.readings` trains one booster a seed in one process, and
    `jobs/train.py` frees the program's state before the reference takes
    the device: a booster that outlives its last reference keeps its bin
    matrix on the chip (PR 33: a jaxlib bound method in a reference cycle
    is invisible to the collector)."""
    import gc
    import weakref
    X, y = make_data(1024, f=5, seed=9)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                              "tree_learner": learner, "num_machines": 4,
                              "verbosity": -1},
                      train_set=lgb.Dataset(X, label=y))
    bst.update()
    assert (bst._mesh is not None) == (learner == "data")
    bins = weakref.ref(bst._train_bins)
    alive = weakref.ref(bst)
    del bst
    gc.collect()
    assert alive() is None and bins() is None
