"""Pallas histogram kernel equality vs the segment-sum path (interpret mode
on CPU; the driver's TPU bench exercises the compiled kernel).

Analog of the reference's CPU-vs-GPU histogram consistency checks
(tests/python_package_test/test_dual.py)."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.histogram import leaf_histogram
from lightgbm_tpu.ops.pallas_hist import (pallas_histogram,
                                          pallas_histogram_quantized, probe)


def _case(n, f, mb, seed, weights=True):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, mb, (f, n)).astype(np.uint8)
    payload = rng.randn(n, 3).astype(np.float32)
    if not weights:
        payload[:, 2] = 1.0
    mask = rng.rand(n) < 0.6
    return (jnp.asarray(bins), jnp.asarray(payload), jnp.asarray(mask))


class TestPallasHistogram:
    @pytest.mark.parametrize("impl", ["onehot", "hilo"])
    @pytest.mark.parametrize("n,f,mb", [
        (512, 4, 16), (1000, 7, 32), (2048, 3, 256), (700, 5, 64),
    ])
    def test_matches_segment_sum(self, impl, n, f, mb):
        bins, payload, mask = _case(n, f, mb, seed=n + mb)
        want = np.asarray(leaf_histogram(bins, payload, mask, mb))
        got = np.asarray(pallas_histogram(bins, payload, mask, mb,
                                          impl=impl, row_tile=256,
                                          interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        # counts are exact sums of 0/1 within f32 range
        np.testing.assert_allclose(got[..., 2], want[..., 2], atol=1e-4)

    def test_empty_mask(self):
        bins, payload, _ = _case(256, 3, 16, seed=1)
        mask = jnp.zeros(256, dtype=bool)
        got = np.asarray(pallas_histogram(bins, payload, mask, 16,
                                          row_tile=128, interpret=True))
        assert np.all(got == 0.0)

    def test_row_padding(self):
        # n not a multiple of row_tile: padded rows must contribute nothing
        bins, payload, mask = _case(300, 4, 16, seed=2)
        want = np.asarray(leaf_histogram(bins, payload, mask, 16))
        got = np.asarray(pallas_histogram(bins, payload, mask, 16,
                                          row_tile=256, interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_feature_tiling(self):
        bins, payload, mask = _case(512, 10, 32, seed=3)
        want = np.asarray(leaf_histogram(bins, payload, mask, 32))
        got = np.asarray(pallas_histogram(bins, payload, mask, 32,
                                          row_tile=256, feat_tile=4,
                                          interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_probe(self):
        assert probe(interpret=True)

    def test_probe_multi(self):
        # the wave-policy gate: full-M multi-leaf block shapes
        assert probe(interpret=True, multi=True)

    def test_refused_kernel_raises_on_tpu_degrades_elsewhere(
            self, monkeypatch):
        # off-TPU a kernel the backend refuses is a falsy result that
        # carries the message (compiled Pallas on CPU is refused); on a
        # TPU the probes raise instead of letting training fall to
        # segment-sum
        from lightgbm_tpu.ops import pallas_hist as ph
        from lightgbm_tpu.utils.log import LightGBMError
        res = probe(interpret=False)
        assert not res and res.cause == "compile"
        assert "interpret mode" in res.detail

        class _Tpu:
            platform = "tpu"

        monkeypatch.setattr(ph.jax, "devices", lambda *a: [_Tpu()])
        for kw in ({}, {"multi": True, "width": 4, "quantized": False}):
            with pytest.raises(LightGBMError, match="interpret mode"):
                probe(interpret=False, **kw)

    def test_multi_matches_per_leaf_interpret(self):
        rng = np.random.RandomState(21)
        n, f, mb = 512, 4, 16
        bins = jnp.asarray(rng.randint(0, mb, (f, n)).astype(np.uint8))
        payload = jnp.asarray(rng.randn(n, 3).astype(np.float32))
        leaf_id = jnp.asarray(rng.randint(0, 6, n).astype(np.int32))
        slots = jnp.asarray(np.array([2, 0, 6, 4], np.int32))  # 6 = pad
        from lightgbm_tpu.ops.pallas_hist import pallas_histogram_multi
        got = np.asarray(pallas_histogram_multi(
            bins, payload, leaf_id, slots, mb, row_tile=256,
            interpret=True))
        for i, sl in enumerate([2, 0, None, 4]):
            if sl is None:
                assert np.all(got[i] == 0.0)
            else:
                want = np.asarray(leaf_histogram(bins, payload,
                                                 leaf_id == sl, mb))
                np.testing.assert_allclose(got[i], want, rtol=1e-5,
                                           atol=1e-5)


class TestPallasHistogramQuantized:
    def _quant_case(self, n, f, mb, bins_q, seed, all_ones_w=True):
        rng = np.random.RandomState(seed)
        bins = rng.randint(0, mb, (f, n)).astype(np.uint8)
        s_g = np.float32(0.37)
        s_h = np.float32(0.11)
        gq = rng.randint(-bins_q, bins_q + 1, n).astype(np.float32)
        hq = rng.randint(0, bins_q + 1, n).astype(np.float32)
        w = np.ones(n, np.float32) if all_ones_w else \
            (rng.rand(n) < 0.8).astype(np.float32)
        payload = np.stack([gq * s_g * w, hq * s_h * w, w], axis=1)
        mask = rng.rand(n) < 0.6
        return (jnp.asarray(bins), jnp.asarray(payload), jnp.asarray(mask),
                jnp.float32(s_g), jnp.float32(s_h))

    @pytest.mark.parametrize("n,f,mb,bins_q", [
        (512, 4, 16, 8), (1000, 7, 32, 15), (2048, 3, 256, 4),
    ])
    def test_matches_segment_sum(self, n, f, mb, bins_q):
        bins, payload, mask, s_g, s_h = self._quant_case(
            n, f, mb, bins_q, seed=n + mb)
        want = np.asarray(leaf_histogram(bins, payload, mask, mb))
        got = np.asarray(pallas_histogram_quantized(
            bins, payload, mask, mb, s_g, s_h, row_tile=256,
            interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        # counts and the recovered integer sums are exact
        np.testing.assert_array_equal(got[..., 2], want[..., 2])

    def test_bagging_zero_weights(self):
        # w in {0, 1}: zero-weight rows must vanish from every channel
        bins, payload, mask, s_g, s_h = self._quant_case(
            700, 5, 64, 8, seed=9, all_ones_w=False)
        want = np.asarray(leaf_histogram(bins, payload, mask, 64))
        got = np.asarray(pallas_histogram_quantized(
            bins, payload, mask, 64, s_g, s_h, row_tile=256,
            interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(got[..., 2], want[..., 2])


# ------------------------------------------------------------- lane plan
# the thirteen airline columns as the public Dataset constructor bins them
AIRLINE_NUM_BIN = (22, 12, 31, 7, 255, 255, 29, 255, 255, 255, 255, 255, 2)


def _plan_case(num_bins, n, s_n, seed):
    rng = np.random.RandomState(seed)
    from lightgbm_tpu.ops.pallas_hist import _split_payload9
    bins = np.stack([rng.randint(0, nb, n) for nb in num_bins])
    # a wide dynamic range, so the folds round and the lo limb is live
    pay = rng.randn(n, 3) * np.exp(4 * rng.randn(n, 3))
    pw9 = _split_payload9(jnp.asarray(pay.astype(np.float32)))
    lid = jnp.asarray(rng.randint(0, s_n + 2, n).astype(np.int32))
    return (jnp.asarray(bins.astype(np.uint8)), pw9, lid,
            jnp.arange(s_n, dtype=jnp.int32))


def _rows_jaxpr(num_bins, max_bin, **kw):
    import jax
    from lightgbm_tpu.ops.pallas_hist import pallas_histogram_multi_rows
    args = _plan_case(num_bins, 300, 2, seed=0)
    return str(jax.make_jaxpr(lambda *a: pallas_histogram_multi_rows(
        *a, max_bin, row_tile=128, interpret=True, **kw))(*args))


class TestLanePlan:
    @pytest.mark.parametrize("s_n", [1, 8])
    def test_packed_sums_bitwise_equal(self, s_n):
        # more than FLUSH_TILES row tiles and a ragged last one: both
        # limbs of every cell equal with and without the plan
        from lightgbm_tpu.ops import pallas_hist as ph
        n = 128 * (ph.FLUSH_TILES + 2) + 50
        args = _plan_case(AIRLINE_NUM_BIN, n, s_n, seed=s_n)
        plan = ph.lane_plan(AIRLINE_NUM_BIN, 255)
        want = ph._run_kernel_multi(*args, 255, 128, 0, True, None)
        got = ph._run_kernel_multi(*args, 255, 128, 0, True, plan)
        for g, w in zip(got[:2], want[:2]):
            assert g.shape == w.shape == (13, s_n * 9, 255)
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert np.abs(np.asarray(want[1])).sum() > 0   # the lo limb is live

    @pytest.mark.parametrize("case", [
        "airline", "all_255", "seven_of_30", "129_bins", "uint16",
        "max_bin_64", "split_feat_tile", "efb_bundle_widths"])
    def test_lane_plan(self, case):
        from lightgbm_tpu.ops.pallas_hist import lane_plan, plan_lanes
        if case == "airline":
            plan = lane_plan(AIRLINE_NUM_BIN, 255)
            assert plan_lanes(plan) == 1920
            assert plan[0] == (128, ((0, 0, 22), (1, 22, 12), (2, 34, 31),
                                     (3, 65, 7), (6, 72, 29), (12, 101, 2)))
            assert plan[1:] == tuple((256, ((c, 0, 255),))
                                     for c in (4, 5, 7, 8, 9, 10, 11))
        elif case == "all_255":
            # nothing to pack: no plan, and the parent's program
            assert lane_plan((255,) * 5, 255) is None
            assert _rows_jaxpr((255,) * 5, 255,
                               plan=lane_plan((255,) * 5, 255)) \
                == _rows_jaxpr((255,) * 5, 255)
        elif case == "seven_of_30":
            plan = lane_plan((30,) * 7, 255)
            assert [len(m) for _, m in plan] == [4, 3]
            assert plan[1][1][0] == (4, 0, 30) and plan_lanes(plan) == 256
        elif case == "129_bins":
            plan = lane_plan((129, 3, 128, 1), 255)
            assert plan == ((256, ((0, 0, 129),)), (128, ((1, 0, 3),)),
                            (128, ((2, 0, 128),)), (128, ((3, 0, 1),)))
        elif case == "uint16":
            assert lane_plan((3, 5, 1000), 1000) is None
            assert lane_plan((3, 5, 257), 257) is None
        elif case == "max_bin_64":
            # narrow tables pack too; equal lanes to no plan means no plan
            assert plan_lanes(lane_plan((64, 64, 60), 64)) == 256
            assert lane_plan((100, 90), 100) is None
        elif case == "split_feat_tile":
            # a group's members share a column block: each block of a
            # split pass plans its own columns, numbered from its first
            from lightgbm_tpu.ops.pallas_hist import column_blocks
            plan = lane_plan(AIRLINE_NUM_BIN, 255)
            blocks = column_blocks(13, 18, 255, plan, feat_tile=4)
            assert [(c0, c1) for c0, c1, _ in blocks] \
                == [(0, 4), (4, 7), (7, 10), (10, 13)]
            assert blocks[0][2] == ((128, ((0, 0, 22), (1, 22, 12),
                                           (2, 34, 31), (3, 65, 7))),)
            assert blocks[1][2] == ((256, ((0, 0, 255),)),
                                    (256, ((1, 0, 255),)),
                                    (128, ((2, 0, 29),)))
            assert blocks[2][2] is None        # three wide columns
            assert column_blocks(13, 18, 255, plan) == ((0, 13, plan),)
            assert _rows_jaxpr(AIRLINE_NUM_BIN, 255, feat_tile=4, plan=plan) \
                != _rows_jaxpr(AIRLINE_NUM_BIN, 255, feat_tile=4)
            assert _rows_jaxpr(AIRLINE_NUM_BIN, 255, plan=plan) \
                != _rows_jaxpr(AIRLINE_NUM_BIN, 255)
        else:
            import lightgbm_tpu as lgb
            from lightgbm_tpu.booster import Booster
            rng = np.random.RandomState(3)
            X = np.zeros((600, 24), np.float32)     # two exclusive sets
            for lo in (0, 12):
                X[np.arange(600), lo + rng.randint(0, 12, 600)] = \
                    rng.randint(1, 4, 600)
            bst = Booster(params={"objective": "regression",
                                  "verbosity": -1, "min_data_in_leaf": 5},
                          train_set=lgb.Dataset(X, label=rng.randn(600)))
            efb = bst.train_set.efb
            assert efb is not None and efb.n_cols == 2
            w0, w1 = (int(b) for b in efb.col_num_bin)
            assert bst._grower_spec.hist_lane_plan \
                == ((128, ((0, 0, w0), (1, w0, w1))),)

    def test_probe_runs_the_plan(self):
        from lightgbm_tpu.ops.pallas_hist import lane_plan
        plan = lane_plan(AIRLINE_NUM_BIN, 255)
        assert probe(interpret=True, max_bin=255, num_feature=13, plan=plan)
        assert probe(interpret=True, max_bin=255, num_feature=13,
                     multi=True, width=8, quantized=False, plan=plan)
        # a plan over other columns than the kernel sees is refused
        with pytest.raises(ValueError, match="lane plan"):
            _rows_jaxpr(AIRLINE_NUM_BIN[:12], 255, plan=plan)

    @pytest.mark.parametrize("quantized", [False, True])
    def test_single_leaf_probe_runs_the_named_family(self, quantized,
                                                     monkeypatch):
        """The booster names the kernel family it will run: the int8
        kernel is refused by the v5e compiler from 67 columns up (scoped
        VMEM), and an f32 booster must not be stopped by its probe."""
        from lightgbm_tpu.ops import pallas_hist as ph

        def other(*a, **k):
            raise AssertionError("the probe ran the other family's kernel")
        monkeypatch.setattr(ph, "pallas_histogram" if quantized
                            else "pallas_histogram_quantized", other)
        assert ph.probe(interpret=True, max_bin=255, num_feature=5,
                        quantized=quantized)
        # unnamed, it probes both: off the TPU a raising kernel is a
        # falsy result that carries the message
        res = ph.probe(interpret=True, max_bin=255, num_feature=5)
        assert not res and "other family" in res.detail

    def test_out_of_range_bin_raises_under_debug_checks(self):
        import jax
        from lightgbm_tpu.ops.pallas_hist import (assert_bins_in_plan,
                                                  lane_plan)
        plan = lane_plan(AIRLINE_NUM_BIN, 255)
        bins = _plan_case(AIRLINE_NUM_BIN, 64, 1, seed=5)[0]
        assert_bins_in_plan(bins, plan)
        jax.effects_barrier()
        bad = bins.at[12, 7].set(2)          # Diverted has bins 0 and 1
        check = jax.jit(lambda b: assert_bins_in_plan(b, plan))
        with pytest.raises(Exception, match="precondition"):
            check(bad)
            jax.effects_barrier()

    @pytest.mark.parametrize("policy", ["wave", "leafwise"])
    def test_booster_runs_the_plan_same_trees(self, policy, monkeypatch):
        # the booster derives the plan from the mappers, records the two
        # gauges, and grows the trees it grows without a plan
        import lightgbm_tpu as lgb
        from lightgbm_tpu import telemetry
        from lightgbm_tpu.booster import Booster
        rng = np.random.RandomState(11)
        n = 700
        X = np.stack([rng.randint(0, 7, n), rng.randn(n),
                      rng.randint(0, 2, n), rng.randint(0, 12, n),
                      rng.randn(n)], axis=1).astype(np.float32)
        y = (X[:, 1] + 0.3 * X[:, 0] - X[:, 2] + 0.1 * rng.randn(n) > 1)
        params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
                  "min_data_in_leaf": 5, "hist_impl": "pallas",
                  "hist_interpret": True, "tpu_debug_nans": True,
                  "tree_grow_policy": policy}

        def model(planned):
            if not planned:
                monkeypatch.setattr(Booster, "_hist_lane_plan",
                                    lambda self: None)
            bst = lgb.train(params, lgb.Dataset(X, label=y.astype(float)),
                            num_boost_round=2)
            snap = telemetry.REGISTRY.snapshot()["gauges"]
            return bst, snap["hist.lanes_per_row"], \
                snap["hist.packed_columns"]
        bst, lanes, packed = model(True)
        assert bst._grow_policy == policy
        plan = bst._grower_spec.hist_lane_plan
        assert [len(m) for _, m in plan] == [3, 1, 1]
        assert (lanes, packed) == (128 + 2 * 256, 3)
        ref, lanes, packed = model(False)
        assert ref._grower_spec.hist_lane_plan is None
        assert (lanes, packed) == (5 * 256, 0)
        assert bst.model_to_string() == ref.model_to_string()


# ------------------------------------------------ one histogram route
# (PR 31: the fused hist+split family, its two `hist_impl` names and its
# parameter are gone; what their tests guarded is held here on the
# routes that remain)
def _trees_text(bst):
    """Model text up to the parameter echo: header and trees."""
    s = bst.model_to_string()
    return s[:s.index("\nparameters:")]


def _mini_train(**extra):
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(400, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 8, "verbosity": -1,
              **extra}
    return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=1)


@pytest.mark.parametrize("impl", ["pallas_fused", "pallas_fused_q"])
def test_removed_hist_impl_is_refused(impl):
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError, match="Unknown hist_impl") as e:
        _mini_train(hist_impl=impl)
    legal = str(e.value).split("expected one of ")[1].rstrip(")")
    assert legal.split(", ") == ["auto", "segment_sum", "packed", "pallas",
                                 "pallas_q"]


@pytest.mark.parametrize("key", ["tpu_fused_split", "fused_split"])
def test_removed_parameter_is_unknown(key):
    # a stale configuration takes the path of any unknown key: recorded,
    # warned about once a Config, and the trees are the trees without it
    bst = _mini_train(**{key: False})
    assert bst.config.unknown_params == {key: False}
    assert _trees_text(bst) == _trees_text(_mini_train())


def _cell_configs():
    from perfbench import manifest
    d = os.path.join(manifest.HERE, "configs")
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


@pytest.mark.parametrize("name", _cell_configs())
def test_cell_params_build_without_fallback(name):
    # the booster of every benchmark configuration, over a few rows of
    # its own data, resolves to the planned f32 kernel without a
    # `fallback.*` event (built as tests/perfbench/test_perfbench_aot.py
    # `grower_for_chip` builds it)
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from perfbench import manifest
    from perfbench.generators import ltr_codes, tabular_codes
    from perfbench.jobs.train import build_dataset
    config = manifest.config(name)
    data = config["data"]
    ranking = config["generator"] == "ltr_codes"
    if ranking:     # query-grouped rows: four queries of its own law
        rows = ltr_codes.make(7, dict(data, queries=4), 8192, 1)
        codes, label = rows["codes"], rows["label"]
        names = ltr_codes.column_names(data)
    else:
        codes, label = tabular_codes.generate(7, data, 0, 8192)
        names = [c["name"] for c in data["columns"]]
    params = {**config["params"], "hist_impl": "pallas",
              "hist_interpret": True}
    ds = build_dataset(lgb, codes, label, params, names)
    if ranking:
        ds._query_boundaries = rows["query_boundaries"]
    counter = telemetry.REGISTRY.counter("fallback.events")
    before = counter.value
    sink = telemetry.TRACER.add_sink(telemetry.MemorySink())
    try:
        bst = lgb.Booster(params=params, train_set=ds)
    finally:
        telemetry.TRACER.remove_sink(sink)
    assert counter.value == before
    assert [e["name"] for e in sink.events if e.get("ev") == "event"
            and str(e.get("name", "")).startswith("fallback.")] == []
    assert bst._grower_spec.hist_impl == "pallas"
    assert bst._grower_spec.hist_lane_plan is not None
    assert bst._grow_policy == config["params"]["tree_grow_policy"]


def _wave_case(seed=7, n=3000, f=6, mb=32):
    """Bins with a short column and a NaN-bin column; gradients and
    hessians on a dyadic lattice (small integers times 2^-4), so every
    sum of them is exact in float32 in any order: two histogram routes
    that share no kernel must then agree to the last bit."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, mb, (f, n)).astype(np.int32)
    nb = np.full(f, mb, np.int32)
    nb[1] = 17
    bins[1] %= 17
    missing = np.zeros(f, np.int32)
    missing[2] = 2
    s = np.float32(2.0 ** -4)
    grad = (rng.randint(-15, 16, n) * s).astype(np.float32)
    hess = (rng.randint(1, 16, n) * s).astype(np.float32)
    sw = np.ones(n, np.float32)
    feat = dict(nb=jnp.asarray(nb), missing=jnp.asarray(missing),
                default=jnp.zeros(f, jnp.int32),
                is_cat=jnp.zeros(f, bool), mono=jnp.zeros(f, jnp.int32),
                qscales=jnp.asarray(np.stack([s, s])))
    return bins, grad, hess, sw, feat, jnp.ones(f, bool)


def _wave_grower(impl, mb=32, **spec_kw):
    from lightgbm_tpu.ops.grow import GrowerSpec
    from lightgbm_tpu.ops.grow_wave import make_wave_grower
    kw = dict(num_leaves=15, max_depth=0, max_bin=mb, lambda_l1=0.0,
              lambda_l2=1.0, min_data_in_leaf=5.0,
              min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
              max_delta_step=0.0, hist_impl=impl, wave_width=4,
              has_cat=False, hist_interpret=True)
    kw.update(spec_kw)
    return make_wave_grower(GrowerSpec(**kw))


def _grow(grow, bins, grad, hess, sw, feat, allowed):
    import jax
    return jax.block_until_ready(grow(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(sw), feat, allowed))


def _assert_trees_equal(a, b, ctx=""):
    for name, x, y in zip(a._fields, a, b):
        if name == "hist_calls":    # the kernel's record, not the tree's
            continue
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            f"{ctx}: field {name} differs"


@pytest.mark.parametrize("kernel,reference,spec_kw", [
    pytest.param("pallas", "segment_sum", {}, id="plain"),
    pytest.param("pallas", "segment_sum", {"has_cat": True},
                 id="categorical"),
    pytest.param("pallas", "segment_sum", {"path_smooth": 1.0},
                 id="path_smooth"),
    pytest.param("pallas_q", "packed", {}, id="quantized"),
])
def test_wave_kernel_route_grows_the_reference_tree(kernel, reference,
                                                    spec_kw):
    # a kernel route through the whole wave grower (sibling subtraction
    # and limbs included) against a route that shares no kernel with it
    bins, grad, hess, sw, feat, allowed = _wave_case()
    if spec_kw.get("has_cat"):
        feat = dict(feat, is_cat=jnp.asarray(
            np.array([0, 0, 0, 1, 0, 0], bool)))
    args = (bins, grad, hess, sw, feat, allowed)
    a = _grow(_wave_grower(kernel, **spec_kw), *args)
    b = _grow(_wave_grower(reference, **spec_kw), *args)
    assert int(a.n_splits) > 0
    _assert_trees_equal(a, b, f"{kernel} vs {reference} {spec_kw}")


@pytest.mark.parametrize("impl", ["pallas", "pallas_q"])
def test_wave_recompile_bound(impl):
    # repeated trees of one shape share one compiled program
    from lightgbm_tpu import telemetry
    assert telemetry.install_compile_listener()
    grow = _wave_grower(impl)
    _grow(grow, *_wave_case(seed=19))                # warm: compiles
    recompiles = telemetry.REGISTRY.counter("jit.recompiles")
    before = recompiles.value
    _grow(grow, *_wave_case(seed=23))
    assert recompiles.value == before, \
        f"second same-shape wave tree recompiled " \
        f"({recompiles.value - before} new)"
