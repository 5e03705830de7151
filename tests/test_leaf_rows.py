"""The score update's look-up `ops/leaf_rows.leaf_rows` (ISSUE 36): the
Pallas pass is `table[leaf_id]` to the bit, on one device and over a
mesh's row shards, and a booster that runs it grows the trees and the
training scores it grew with the gather."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import booster as booster_mod
from lightgbm_tpu import telemetry
from lightgbm_tpu.ops import leaf_rows as lr

TILE = lr.LEAF_TILE
# one id; under one 128-lane row; over a chunk's rounding; a short last
# step behind a whole one; three steps and five rows
ROWS = (1, 127, 2049, TILE + 1000, 3 * TILE + 5)
# values no arithmetic may touch: both zeros, the smallest denormal and a
# larger one, the largest finite f32, an infinity, and values whose three
# bf16 limbs all differ (24 significant bits)
SPECIAL = np.array([-0.0, 0.0, 1e-45, -7e-42, 3.4e38, -3.4e38, np.inf,
                    1.2345678, -0.033333335, 16777215.0, 1.0000001],
                   np.float32)


def table_of(entries, seed=0):
    rng = np.random.RandomState(seed + entries)
    t = (rng.standard_normal(entries) * 0.05).astype(np.float32)
    at = rng.permutation(entries)[:len(SPECIAL)]
    t[at] = SPECIAL[:len(at)]
    return t


def ids_of(n, entries, seed=0):
    """Skewed like a grown tree's: a few large leaves, many small; every
    entry taken at least once where the rows allow."""
    rng = np.random.RandomState(seed + n)
    ids = np.minimum((entries * rng.random_sample(n) ** 3).astype(np.int32),
                     entries - 1)
    ids[:min(n, entries)] = np.arange(entries)[:n][::-1]
    return ids


def bits(x):
    return np.asarray(x).view(np.int32)


def on_pass(table, ids):
    return lr.leaf_rows(jnp.asarray(table), jnp.asarray(ids), "pallas",
                        interpret=True)


@pytest.mark.parametrize("n", ROWS)
@pytest.mark.parametrize("entries", [2, 31, 255, 256, 1023,
                                     lr.LEAF_MAX_ENTRIES])
def test_the_pass_is_the_gather_to_the_bit(entries, n):
    assert lr.pass_serves(entries, "pallas")
    table, ids = table_of(entries), ids_of(n, entries)
    got = on_pass(table, ids)
    assert got.shape == (n,) and got.dtype == jnp.float32
    np.testing.assert_array_equal(bits(got), bits(table[ids]))
    np.testing.assert_array_equal(
        bits(got), bits(jnp.asarray(table)[jnp.asarray(ids)]))


@pytest.mark.parametrize("entries", [2, 255, 256, lr.LEAF_MAX_ENTRIES])
def test_an_id_outside_the_table_reads_zero(entries):
    """A mesh's pad rows carry -1: the pass gives them (and any id
    outside `[0, L)`) +0.0, whatever the table holds."""
    table = np.full(entries, np.nan, np.float32)
    outside = np.array([-1, -2, -128, entries, entries + 3, 2 ** 20,
                        -2 ** 31, 2 ** 31 - 1], np.int32)
    ids = np.concatenate([outside, ids_of(300, entries)])
    got = np.asarray(on_pass(table, ids))
    np.testing.assert_array_equal(bits(got[:len(outside)]), 0)
    assert np.isnan(got[len(outside):]).all()


@pytest.mark.parametrize("entries,impl", [
    (lr.LEAF_MAX_ENTRIES + 1, "pallas"), (255, "segment_sum"),
    (255, "packed"), (31, "segment_sum")])
def test_elsewhere_the_gather_is_kept(entries, impl):
    """A table wider than the pass serves, or no Pallas family: the
    program holds no Pallas call and gives JAX's `table[leaf_id]`."""
    assert not lr.pass_serves(entries, impl)
    table, ids = table_of(entries), ids_of(2049, entries)
    text = lr.leaf_rows.lower(jnp.asarray(table), jnp.asarray(ids),
                              impl).as_text()
    assert "gather" in text and "custom_call" not in text
    np.testing.assert_array_equal(
        bits(lr.leaf_rows(jnp.asarray(table), jnp.asarray(ids), impl)),
        bits(table[ids]))


def test_the_call_is_not_counted_as_a_histogram():
    """`hist.time_pct` and `hist_kernel_roofline` select
    `^pallas_histogram`."""
    jaxpr = jax.make_jaxpr(functools.partial(
        lr.leaf_rows, hist_impl="pallas"))(
            jnp.zeros(255, jnp.float32), jnp.zeros(4096, jnp.int32))
    assert "name=leaf_rows" in str(jaxpr)
    assert "pallas_histogram" not in str(jaxpr)


# --------------------------------------------------------------- on a mesh
def sharded_booster(n, leaves=31):
    rng = np.random.RandomState(3)
    X = rng.randn(n, 5).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + X[:, 2] > 0).astype(np.float32)
    return X, lgb.Booster(
        {"objective": "binary", "num_leaves": leaves, "verbosity": -1,
         "tree_learner": "data", "num_machines": 4, "hist_impl": "pallas",
         "hist_interpret": True, "tree_grow_policy": "wave",
         "min_data_in_leaf": 1}, lgb.Dataset(X, label=y))


@pytest.mark.parametrize("n", [4096, 4099])
def test_over_four_row_shards_the_look_up_is_the_serial_one(n):
    """`make_distributed_grower`'s `leaf_rows`: each shard looks up its
    own rows (4099: behind the grower's pad rows, which carry -1)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    _, bst = sharded_booster(n)
    assert bst._mesh is not None and bst._mesh.devices.size == 4
    table, ids = table_of(31), ids_of(n, 31)
    placed = jnp.asarray(ids) if bst._dd.row_sharding is None \
        else jax.device_put(ids, bst._dd.row_sharding)
    got = bst._grower.leaf_rows(jnp.asarray(table), placed)
    assert got.shape == (n,)
    if n % 4 == 0:
        assert got.sharding.is_equivalent_to(bst._dd.row_sharding, 1)
    np.testing.assert_array_equal(bits(got), bits(on_pass(table, ids)))
    np.testing.assert_array_equal(bits(got), bits(table[ids]))
    text = bst._grower.leaf_rows.lower(jnp.asarray(table), placed).as_text()
    for collective in ("all_gather", "all_reduce", "collective_permute",
                       "all_to_all"):
        assert collective not in text


def test_a_sharded_booster_trains_through_the_sharded_look_up():
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    X, bst = sharded_booster(4096)
    before = counters()
    bst.update()
    bst.update()
    after = counters()
    assert after["score.lookup_rows"] - before["score.lookup_rows"] == 8192
    assert after["score.gather_rows"] == before["score.gather_rows"]
    # the scores are the trees' own leaf values, row by row
    np.testing.assert_allclose(np.asarray(bst._train_score),
                               bst.predict(X, raw_score=True), rtol=0,
                               atol=1e-6)


# ------------------------------------------------------------ the counters
def counters():
    c = telemetry.REGISTRY.counter
    return {k: c(k).value for k in ("score.lookup_rows",
                                    "score.gather_rows")}


def rows_fixture(n=65536, cat=False, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6).astype(np.float32)
    kinds = None
    if cat:
        X[:, 4] = rng.randint(0, 40, n)
        X[:, 5] = rng.randint(0, 7, n)
        kinds = [4, 5]
    y = X[:, 0] * X[:, 1] + np.sin(3 * X[:, 2]) + 0.3 * (X[:, 4] % 3) \
        + 0.2 * rng.randn(n)
    return X, y.astype(np.float32), kinds


PARAMS = {"num_leaves": 255, "learning_rate": 0.1, "verbosity": -1,
          "min_data_in_leaf": 20, "hist_impl": "pallas",
          "hist_interpret": True, "tree_grow_policy": "wave"}


@pytest.mark.parametrize("impl,counted", [
    ("pallas", "score.lookup_rows"), ("segment_sum", "score.gather_rows")])
def test_the_counters_say_which_form_took_the_rows(impl, counted):
    X, y, _ = rows_fixture(4096)
    before = counters()
    bst = lgb.train({**PARAMS, "objective": "binary", "hist_impl": impl},
                    lgb.Dataset(X, label=(y > 0).astype(np.float32)),
                    num_boost_round=2)
    after = counters()
    assert bst.trees[0].num_leaves > 31
    other = ({"score.lookup_rows", "score.gather_rows"} - {counted}).pop()
    assert after[counted] - before[counted] == 2 * 4096
    assert after[other] == before[other]


# ------------------------------------------------- the same trees and scores
def train(params, X, y, kinds, rounds=3, gather=False, monkeypatch=None):
    """(`bst`, its training scores' bits).  `gather`: the look-up taken
    off the path, every row by `scaled[leaf_id]` as the parent did."""
    if gather:
        monkeypatch.setattr(
            booster_mod, "leaf_rows",
            lambda table, leaf_id, hist_impl, interpret=False:
            table[leaf_id])
    ds = lgb.Dataset(X, label=y, categorical_feature=kinds or "auto")
    bst = lgb.train({**PARAMS, **params}, ds, num_boost_round=rounds)
    if gather:
        monkeypatch.undo()
    return bst, bits(bst._train_score)


def model_text(bst):
    return "\n".join(line for line in bst.model_to_string().splitlines()
                     if not line.startswith("["))


@pytest.mark.parametrize("case", ["numerical", "categorical",
                                  "regression_l1", "rollback"])
def test_three_rounds_grow_the_gathers_trees_and_scores(case, monkeypatch):
    X, y, kinds = rows_fixture(cat=case == "categorical")
    params = {"objective": "regression_l1" if case == "regression_l1"
              else "regression"}
    runs = []
    for gather in (False, True):
        before = counters()
        bst, score = train(params, X, y, kinds, gather=gather,
                           monkeypatch=monkeypatch)
        if case == "rollback":
            # the kept `contrib` is subtracted, then a round grown again
            bst.rollback_one_iter()
            rolled = bits(bst._train_score)
            bst.update()
            score = np.concatenate([rolled, bits(bst._train_score)])
        runs.append((model_text(bst), score))
        assert counters()["score.gather_rows"] == before["score.gather_rows"]
        assert bst.trees[0].num_leaves == 255
        assert (bst.trees[0].num_cat > 0) == (case == "categorical")
    assert runs[0][0] == runs[1][0]
    np.testing.assert_array_equal(runs[0][1], runs[1][1])
