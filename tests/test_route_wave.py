"""One routing pass for a wave's picks and a speculation's slots
(`ops/route.py`; the wave grower's phase `partition`).

The batched pass, in both its forms (the Pallas kernel, interpreted here,
and the plain `jax.numpy` one), against a loop of `ops/grow.split_go_left`
+ the `leaf_id` rewrite that it replaces: every case bit-equal.  Then the
grower: the same trees with the pass as with the per-pick loop, the specs
that keep the loop, and the counters of how often the pass engages.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.ops import grow_wave
from lightgbm_tpu.ops import route as rt
from lightgbm_tpu.ops.grow import GrowerSpec, split_go_left
from lightgbm_tpu.ops.split import (MISSING_NAN, MISSING_NONE, MISSING_ZERO,
                                    bin_goes_left)

AIRLINE_NUM_BIN = (22, 12, 31, 7, 255, 255, 29, 255, 255, 255, 255, 255, 2)
WIDE_NUM_BIN = (255,) * 68
K = 8                              # the wave width: records a pass
LEAVES = 24                        # leaves the rows are spread over
SPEC = GrowerSpec(num_leaves=31, max_depth=0, max_bin=255, lambda_l1=0.0,
                  lambda_l2=0.0, min_data_in_leaf=1.0,
                  min_sum_hessian_in_leaf=0.0, min_gain_to_split=0.0,
                  max_delta_step=0.0, has_cat=False)


def _case(num_bin, n, live, missing, default_left, seed):
    """Rows over `LEAVES` leaves, K records of which `live` are picks:
    distinct leaves, one of them (LEAVES + 3) with no rows, every
    threshold two below its column's last bin (where it has three): the
    NaN bin's rows go right by the threshold and left by `default_left`,
    the bin before it right by both."""
    rng = np.random.RandomState(seed)
    f = len(num_bin)
    nb = np.asarray(num_bin, np.int32)
    bins = np.stack([rng.randint(0, b, n) for b in nb]).astype(np.uint8)
    leaf_id = rng.randint(0, LEAVES, n).astype(np.int32)
    leaf = rng.permutation(LEAVES)[:K].astype(np.int32)
    if live > 1:
        leaf[live - 1] = LEAVES + 3
    feature = rng.randint(0, f, K).astype(np.int32)
    feature[-1] = f - 1                            # the 2-bin column too
    thr = np.array([rng.randint(0, max(nb[c] - 2, 1)) for c in feature],
                   np.int32)
    is_live = np.arange(K) < live
    return dict(
        bins=jnp.asarray(bins), leaf_id=jnp.asarray(leaf_id),
        live=jnp.asarray(is_live), leaf=jnp.asarray(leaf),
        feature=jnp.asarray(feature), thr=jnp.asarray(thr),
        dl=jnp.full((K,), default_left, bool),
        new=jnp.asarray(LEAVES + 10 + np.arange(K), jnp.int32),
        small_is_left=jnp.asarray(rng.randint(0, 2, K) > 0),
        feat={"nb": jnp.asarray(nb),
              "missing": jnp.full((f,), missing, jnp.int32)})


def _go_left(c, k):
    return split_go_left(SPEC, c["feat"], c["bins"], None, c["feature"][k],
                         c["thr"][k], c["dl"][k], False, None)


def _loop_leaf_id(c):
    """The pick loop as it was: one mask and one rewrite a live pick."""
    leaf_id = c["leaf_id"]
    for k in range(K):
        if bool(c["live"][k]):
            leaf_id = jnp.where((leaf_id == c["leaf"][k]) & ~_go_left(c, k),
                                c["new"][k], leaf_id)
    return np.asarray(leaf_id)


def _loop_slot_of_row(c):
    """`speculate`'s slot fill as it was: slot k for the rows of leaf k's
    would-be smaller child."""
    slot = jnp.full_like(c["leaf_id"], -1)
    for k in range(K):
        slot = jnp.where(c["live"][k] & (c["leaf_id"] == c["leaf"][k])
                         & (_go_left(c, k) == c["small_is_left"][k]),
                         k, slot)
    return np.asarray(slot)


def _records(c, speculation):
    k = jnp.arange(K, dtype=jnp.int32)
    sides = (jnp.where(c["small_is_left"], k, -1),
             jnp.where(c["small_is_left"], -1, k)) if speculation else \
        (c["leaf"], c["new"])
    return rt.pick_records(c["live"], c["leaf"], c["feature"], c["thr"],
                           c["dl"], c["feat"]["nb"], c["feat"]["missing"],
                           *sides)


def _both_forms(c, speculation):
    rec = _records(c, speculation)
    fill = -1 if speculation else None
    return (np.asarray(rt.route_rows_xla(c["bins"], c["leaf_id"], rec,
                                         fill=fill)),
            np.asarray(rt.route_wave_rows(c["bins"], c["leaf_id"], rec,
                                          fill=fill, interpret=True)))


@pytest.mark.parametrize("speculation", [False, True],
                         ids=["leaf_id", "slot_of_row"])
@pytest.mark.parametrize("default_left", [False, True], ids=["dr", "dl"])
@pytest.mark.parametrize("missing", [MISSING_NONE, MISSING_ZERO,
                                     MISSING_NAN],
                         ids=["none", "zero", "nan"])
@pytest.mark.parametrize("live", [1, 3, 8])
@pytest.mark.parametrize("num_bin,n", [(AIRLINE_NUM_BIN, 5000),
                                       (WIDE_NUM_BIN, 4099)],
                         ids=["airline13", "wide68"])
def test_one_pass_routes_as_the_pick_loop(num_bin, n, live, missing,
                                          default_left, speculation):
    """Neither row count is a whole compute chunk; pad slots (records
    beyond `live`) and the picked leaf with no rows route nothing."""
    c = _case(num_bin, n, live, missing, default_left,
              seed=live * 7 + missing)
    want = _loop_slot_of_row(c) if speculation else _loop_leaf_id(c)
    xla, kernel = _both_forms(c, speculation)
    np.testing.assert_array_equal(xla, want)
    np.testing.assert_array_equal(kernel, want)
    moved = want >= 0 if speculation else want != np.asarray(c["leaf_id"])
    assert moved.any() and not moved.all()


def test_a_pass_of_several_grid_steps_with_a_short_last_one(monkeypatch):
    """More rows than a tile and no whole number of tiles: the last grid
    step reads and writes past the rows, and nothing of that shows."""
    monkeypatch.setattr(rt, "ROUTE_TILE", 8192)
    monkeypatch.setattr(rt, "ROUTE_CHUNK", 2048)
    c = _case(AIRLINE_NUM_BIN, 3 * 8192 + 2048 + 17, 8, MISSING_NAN, True, 5)
    for speculation in (False, True):
        want = _loop_slot_of_row(c) if speculation else _loop_leaf_id(c)
        rec = _records(c, speculation)
        got = rt.route_wave_rows.__wrapped__(
            c["bins"], c["leaf_id"], rec, fill=-1 if speculation else None,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(got), want)


# ------------------------------------------------ categorical records too
CAT_SPEC = SPEC._replace(has_cat=True)


def _cat_case(num_bin, n, live, seed, cat=(0, 2, 3, 5, 6)):
    """`_case` with the picks of `cat` categorical: a left set of up to
    25 of the column's bins, never bin 0."""
    c = _case(num_bin, n, live, MISSING_NAN, True, seed)
    rng = np.random.RandomState(seed + 1)
    nb = np.asarray(num_bin)
    mask = np.zeros((K, 255), bool)
    for k, f in enumerate(np.asarray(c["feature"])):
        top = max(int(nb[f]) - 1, 1)
        mask[k, 1 + rng.choice(top, min(25, top), replace=False)] = True
    c["is_cat"] = jnp.asarray(np.isin(np.arange(K), cat))
    c["cat_mask"] = jnp.asarray(mask)
    return c


def _cat_go_left(c, k):
    return split_go_left(CAT_SPEC, c["feat"], c["bins"], None,
                         c["feature"][k], c["thr"][k], c["dl"][k],
                         c["is_cat"][k], c["cat_mask"][k])


def _sets(c):
    return rt.left_sets(c["feature"], c["thr"], c["dl"], c["feat"]["nb"],
                        c["feat"]["missing"], c["is_cat"], c["cat_mask"])


@pytest.mark.parametrize("speculation", [False, True],
                         ids=["leaf_id", "slot_of_row"])
@pytest.mark.parametrize("n,tile", [(100, None), (5000, None),
                                    (70001, None),
                                    (3 * 8192 + 2048 + 17, 8192)],
                         ids=["100", "5000", "70001", "short_last_step"])
def test_mixed_records_route_as_the_pick_loop(monkeypatch, n, tile,
                                              speculation):
    """Numerical and categorical picks in one pass, in both forms,
    against the per-pick loop whose categorical picks gather
    `cat_mask[bins]`: the same integers, also where the last grid step is
    short."""
    if tile:
        monkeypatch.setattr(rt, "ROUTE_TILE", tile)
        monkeypatch.setattr(rt, "ROUTE_CHUNK", 2048)
    c = _cat_case(AIRLINE_NUM_BIN, n, 8, seed=n % 97)
    want = jnp.full_like(c["leaf_id"], -1) if speculation else c["leaf_id"]
    for k in range(K):
        in_leaf = c["live"][k] & (c["leaf_id"] == c["leaf"][k])
        if speculation:
            want = jnp.where(
                in_leaf & (_cat_go_left(c, k) == c["small_is_left"][k]),
                k, want)
        else:
            want = jnp.where((want == c["leaf"][k]) & c["live"][k]
                             & ~_cat_go_left(c, k), c["new"][k], want)
    rec, sets = _records(c, speculation), _sets(c)
    fill = -1 if speculation else None
    np.testing.assert_array_equal(
        np.asarray(rt.route_rows_xla(c["bins"], c["leaf_id"], rec,
                                     fill=fill, sets=sets)), want)
    np.testing.assert_array_equal(
        np.asarray(rt.route_wave_rows.__wrapped__(
            c["bins"], c["leaf_id"], rec, fill=fill, interpret=True,
            sets=sets)), want)
    # the categorical picks moved rows their thresholds would not have
    plain = rt.route_rows_xla(c["bins"], c["leaf_id"], rec, fill=fill)
    assert not np.array_equal(np.asarray(plain), np.asarray(want))


@pytest.mark.parametrize("missing", [MISSING_NONE, MISSING_ZERO,
                                     MISSING_NAN],
                         ids=["none", "zero", "nan"])
def test_the_pass_and_bin_goes_left_agree_on_all_256_bins(missing):
    """ONE rule: a table column of every bin 0..255, each record's leaf
    holding all of them; what the kernel sends left is what
    `bin_goes_left` sends left, for numerical and categorical records,
    with the left sets and (numerical records) without."""
    c = _cat_case((255,) * 4, 256, 8, seed=3)
    c["feat"]["missing"] = jnp.full((4,), missing, jnp.int32)
    c["feat"]["nb"] = jnp.asarray([256, 255, 31, 2], jnp.int32)
    c["bins"] = jnp.tile(jnp.arange(256, dtype=jnp.uint8), (4, 1))
    c["dl"] = jnp.asarray(np.arange(K) % 2 == 0)
    every = jnp.arange(256, dtype=jnp.int32)
    for k in range(K):
        f = c["feature"][k]
        c["leaf_id"] = jnp.full((256,), c["leaf"][k], jnp.int32)
        rec = _records(c, False)
        for with_sets in (True, False):
            is_cat = c["is_cat"][k] if with_sets else jnp.bool_(False)
            want = bin_goes_left(every, c["feat"]["nb"][f],
                                 c["feat"]["missing"][f], c["thr"][k],
                                 c["dl"][k], is_cat,
                                 # bin 255 is beyond a 255-bin mask: in no set
                                 jnp.pad(c["cat_mask"][k], (0, 1)))
            got = rt.route_wave_rows.__wrapped__(
                c["bins"], c["leaf_id"], rec, interpret=True,
                sets=_sets(c) if with_sets else None)
            np.testing.assert_array_equal(
                np.asarray(got) == int(c["leaf"][k]), np.asarray(want))


def test_a_numerical_spec_lowers_without_the_left_sets():
    """No categorical column: the Pallas call has the three operands it
    had (records, bins, ids), and a fourth only with the left sets."""
    c = _cat_case(AIRLINE_NUM_BIN, 5000, 8, seed=9)
    rec = _records(c, False)

    def operands(**kw):
        jaxpr = jax.make_jaxpr(lambda *a: rt.route_wave_rows.__wrapped__(
            *a, interpret=True, **kw))(c["bins"], c["leaf_id"], rec)
        calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        return [v.aval.shape for v in calls[0].invars]

    assert operands() == [(8, rt.REC_FIELDS), (13, 5000), (1, 5000)]
    assert operands(sets=_sets(c)) == [(8, rt.REC_FIELDS),
                                       (8, rt.SET_WORDS), (13, 5000),
                                       (1, 5000)]


def test_wide_or_two_byte_bins_keep_the_pick_loop():
    assert rt.batched_route_applies(jnp.zeros((13, 8), jnp.uint8))
    assert rt.batched_route_applies(
        jnp.zeros((rt.ROUTE_MAX_COLUMNS, 8), jnp.uint8))
    assert not rt.batched_route_applies(
        jnp.zeros((rt.ROUTE_MAX_COLUMNS + 1, 8), jnp.uint8))
    assert not rt.batched_route_applies(jnp.zeros((13, 8), jnp.uint16))


# ------------------------------------------------------------- the grower
def _table(n=6000, seed=11):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 10).astype(np.float32)
    X[rng.rand(n) < 0.1, 2] = np.nan               # a NaN bin that splits
    score = X[:, 0] + X[:, 1] * np.nan_to_num(X[:, 2]) \
        + 0.5 * np.sin(3 * X[:, 3]) + 0.3 * X[:, 4]
    y = (score + 0.5 * rng.randn(n) > 0).astype(np.float64)
    return X, y


WAVE = {"objective": "binary", "verbosity": -1, "tree_grow_policy": "wave",
        "min_data_in_leaf": 5, "tpu_wave_width": 8,
        "tpu_wave_gain_ratio": 0, "tpu_wave_strict_tail": 16}


def _spy(monkeypatch):
    """Count the routing passes a grower traces, by form."""
    traced = {"kernel": 0, "xla": 0}

    def counting(name, fn):
        def wrapper(*a, **kw):
            traced[name] += 1
            return fn(*a, **kw)
        return wrapper
    monkeypatch.setattr(grow_wave, "route_wave_rows",
                        counting("kernel", rt.route_wave_rows))
    monkeypatch.setattr(grow_wave, "route_rows_xla",
                        counting("xla", rt.route_rows_xla))
    grow_wave.make_wave_grower.cache_clear()
    jax.clear_caches()
    return traced


def _grown(before):
    after = telemetry.REGISTRY.snapshot()["counters"]
    return {k: after[k] - before.get(k, 0) for k in after
            if k.startswith("grow.")}


@pytest.mark.parametrize("family", ["xla", "kernel"])
def test_the_pass_grows_the_per_pick_loops_trees(monkeypatch, family):
    """The same model text with one pass a wave as with one a pick, on
    the XLA histogram family (`route_rows_xla`) and on the interpreted
    Pallas one (`route_wave_rows`)."""
    X, y = _table(3000)
    params = dict(WAVE, num_leaves=40)
    if family == "kernel":
        params.update(hist_impl="pallas", hist_interpret=True)
    texts, traced = [], []
    for batched in (True, False):
        if not batched:
            monkeypatch.setattr(grow_wave, "batched_route_applies",
                                lambda bins: False)
        traced.append(_spy(monkeypatch))
        bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=2)
        assert bst._grow_policy == "wave"
        assert all(t.num_leaves == 40 for t in bst.trees)
        texts.append(bst.model_to_string())
    assert texts[0] == texts[1]
    other = "xla" if family == "kernel" else "kernel"
    # a wave body and a speculation trace the pass once each
    assert traced[0][family] == 2 and traced[0][other] == 0
    assert traced[1] == {"kernel": 0, "xla": 0}
    grow_wave.make_wave_grower.cache_clear()
    jax.clear_caches()


def _cat_table(n=3000, seed=2):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6).astype(np.float32)
    X[:, 0] = rng.randint(0, 9, n)
    y = ((X[:, 0] % 3 == 0) ^ (X[:, 1] > 0)).astype(np.float64)
    return X, y


def test_a_bundled_spec_keeps_the_pick_loop(monkeypatch):
    """Its routing is another computation (`decode_bins`): a static flag
    of the spec, read at trace."""
    traced = _spy(monkeypatch)
    rng = np.random.RandomState(2)
    n = 3000
    # mutually exclusive sparse columns: EFB bundles them
    X = np.zeros((n, 12), np.float32)
    X[np.arange(n), rng.randint(0, 12, n)] = rng.rand(n) + 0.5
    y = (X[:, 0] + X[:, 3] - X[:, 7] + 0.1 * rng.randn(n) > 0.2)
    before = telemetry.REGISTRY.snapshot()["counters"]
    bst = lgb.train(dict(WAVE, num_leaves=12, enable_bundle=True),
                    lgb.Dataset(X, label=y.astype(np.float64)),
                    num_boost_round=2)
    assert bst._grow_policy == "wave"
    assert bst._grower_spec.bundled
    assert traced == {"kernel": 0, "xla": 0}
    # one routing pass a pick and a slot
    grown = _grown(before)
    assert grown["grow.route_passes"] == grown["grow.route_picks"] > 0
    grow_wave.make_wave_grower.cache_clear()
    jax.clear_caches()


@pytest.mark.parametrize("family", ["xla", "kernel"])
def test_a_categorical_spec_routes_in_the_pass_the_loops_trees(monkeypatch,
                                                               family):
    """A model with a categorical column: every wave, speculation AND
    tail pick goes through the pass (three traces: the wave body, the
    tail body, the speculation), and the model text is the per-pick
    loop's."""
    X, y = _cat_table()
    params = dict(WAVE, num_leaves=40, min_data_per_group=20)
    if family == "kernel":
        params.update(hist_impl="pallas", hist_interpret=True)
    texts, traced, grown = [], [], []
    for batched in (True, False):
        if not batched:
            monkeypatch.setattr(grow_wave, "batched_route_applies",
                                lambda bins: False)
        traced.append(_spy(monkeypatch))
        before = telemetry.REGISTRY.snapshot()["counters"]
        bst = lgb.train(params, lgb.Dataset(X, label=y,
                                            categorical_feature=[0]),
                        num_boost_round=2)
        assert bst._grow_policy == "wave" and bst._grower_spec.has_cat
        texts.append(bst.model_to_string())
        grown.append(_grown(before))
    assert texts[0] == texts[1]
    other = "xla" if family == "kernel" else "kernel"
    assert traced[0][family] == 3 and traced[0][other] == 0
    assert traced[1] == {"kernel": 0, "xla": 0}
    cat_splits = sum(t.num_cat for t in bst.trees)
    assert cat_splits > 0
    for g in grown:
        assert g["grow.cat_splits"] == cat_splits
        assert g["grow.cat_left_bins"] >= cat_splits
        # the categorical picks, and the categorical slots speculated
        assert g["grow.route_cat_picks"] >= cat_splits
    # one pass a wave, a speculation and a tail pick, against one a pick
    # and a slot
    assert grown[0]["grow.route_passes"] < grown[0]["grow.route_picks"]
    assert grown[1]["grow.route_passes"] == grown[1]["grow.route_picks"]
    assert grown[0]["grow.route_picks"] == grown[1]["grow.route_picks"]
    grow_wave.make_wave_grower.cache_clear()
    jax.clear_caches()


def test_a_255_leaf_trees_passes_and_picks_are_the_schedules():
    """Waves of eight, then sixteen strict splits: a routing pass a wave,
    a speculating pass and a tail pick; the picks are the tree's splits
    and eight slots a speculating pass."""
    X, y = _table(24000, seed=4)
    before = telemetry.REGISTRY.snapshot()["counters"]
    bst = lgb.train(dict(WAVE, num_leaves=255, min_data_in_leaf=2),
                    lgb.Dataset(X, label=y), num_boost_round=2)
    for t in bst.trees:
        assert t.num_leaves == 255
        tail_passes, hits, _, _, waves, route_passes, route_picks = \
            t.tail_stats
        # the tail's splits: a pass or a hit each, but the one that fills
        # the tree
        tail_splits = tail_passes + hits + 1
        assert tail_splits in (15, 16)
        assert route_passes == waves + tail_passes + tail_splits
        assert route_picks == 254 + 8 * tail_passes
        # 254 - 16 splits in waves of at most eight
        assert waves >= 30 and route_picks / route_passes > 4.5
    grown = _grown(before)
    assert grown["grow.route_passes"] == sum(t.tail_stats[5]
                                             for t in bst.trees)
    assert grown["grow.route_picks"] == sum(t.tail_stats[6]
                                            for t in bst.trees)
