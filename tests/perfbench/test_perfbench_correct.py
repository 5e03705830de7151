"""`correct` comes out true for the program as it is, and false for the
control and for each fault a training cell can have.

Everything but the harness's look for a chip is driven (`run.main` with the
hooks a test may replace), at a size a test run can hold: the fixture cell
`tiny13-l31.train`, 65,536 rows of the airline13 shape.
"""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from perfbench import check, manifest, run
from perfbench.generators import tabular_codes
from perfbench.jobs.train import build_dataset
from perfbench.reference import gbdt

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "fixtures", "bench")
CELL = "tiny13-l31.train"


def drive(capsys, hooks, seed=20260930, more=()):
    hooks.require_chip = False
    hooks.compile_cache = False
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.1", "--trace", "0", "--bench-dir", BENCH, *more],
                  hooks=hooks)
    out = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.out.strip().splitlines()[-1])
    # each number beside its limit: last on the line, and on stderr
    assert list(line)[-1] == "compared"
    for k, v in line["compared"].items():
        assert f"compared: {k} " in out.err
        assert set(v) == {"value", "limit"}
    return line


def test_the_program_as_it_is_is_correct(capsys):
    line = drive(capsys, run.default_hooks())
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_rounds_per_s", "holdout_auc",
                                    "setup_s"}
    assert 0.5 < line["metrics"]["holdout_auc"]["value"] < 1.0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}


def drive_and_keep_trees(capsys, seed, more=()):
    """(the result's line, the trees that were compared, as bytes)."""
    hooks, kept = run.default_hooks(), []
    hooks.alter_trees = kept.extend
    line = drive(capsys, hooks, seed, more)
    assert line["correct"] is True and len(kept) == 3
    return line, [tuple(np.asarray(x).tobytes() for x in t) for t in kept]


@pytest.fixture(scope="module")
def population_of_the_config():
    config = manifest.config(manifest.workload(CELL, BENCH)["config"], BENCH)
    return config["data"]["population_seed"]


def test_two_seeds_grow_the_same_trees_and_score_other_rows(
        capsys, population_of_the_config):
    """`--seed` draws the hold-out rows and the checked nodes; the trees,
    and so the work of a round, are the configuration's."""
    a, trees_a = drive_and_keep_trees(capsys, population_of_the_config)
    b, trees_b = drive_and_keep_trees(capsys, 2 ** 31 + 777)
    assert trees_a == trees_b
    assert a["metrics"]["holdout_auc"]["value"] \
        != b["metrics"]["holdout_auc"]["value"]
    assert abs(a["metrics"]["holdout_auc"]["value"]
               - b["metrics"]["holdout_auc"]["value"]) < 0.05


def test_population_seed_overrides_the_configs(capsys,
                                               population_of_the_config):
    """A builder's `--population-seed`: other trees on the same hold-out
    rows; given the configuration's own value it changes nothing."""
    seed = 2 ** 31 + 777
    own, trees = drive_and_keep_trees(capsys, seed)
    same, trees_same = drive_and_keep_trees(
        capsys, seed, ["--population-seed", str(population_of_the_config)])
    other, trees_other = drive_and_keep_trees(
        capsys, seed, ["--population-seed", "77"])
    assert trees_same == trees and trees_other != trees
    assert same["metrics"]["holdout_auc"] == own["metrics"]["holdout_auc"]
    assert same["compared"] == own["compared"]
    assert other["metrics"]["holdout_auc"] != own["metrics"]["holdout_auc"]


def test_fault_a_step_that_leaves_its_state_unchanged(capsys):
    """The score update is dropped: every round sees the first gradients."""
    hooks = run.default_hooks()

    def make(lgb, params, ds):
        bst = lgb.Booster(params=params, train_set=ds)
        real = bst.update

        def update(*a, **k):
            first = bst.current_iteration() == 0
            before = bst._train_score
            out = real(*a, **k)
            if not first:       # keep boost_from_average's first state
                bst._train_score = before
            return out
        bst.update = update
        return bst
    hooks.make_booster = make
    line = drive(capsys, hooks)
    assert line["correct"] is False
    c = line["compared"]["leaf_value_gap"]
    assert c["value"] > 10 * c["limit"]


def test_fault_half_of_the_batch_left_out(capsys):
    """The booster trains on the first half of the rows only."""
    hooks = run.default_hooks()

    def make(lgb, params, ds):
        half = ds._num_data // 2
        sub = lgb.Dataset(None, free_raw_data=False)
        sub.__dict__.update(ds.__dict__)
        sub.bin_data = np.ascontiguousarray(np.asarray(ds.bin_data)[:half])
        sub._label_arr = ds._label_arr[:half]
        sub._num_data = half
        return lgb.Booster(params=params, train_set=sub)
    hooks.make_booster = make
    line = drive(capsys, hooks)
    assert line["correct"] is False
    c = line["compared"]["leaf_count_gap"]
    assert c["value"] > 0.3


@pytest.mark.parametrize("what", ["leaf_value", "threshold"])
def test_fault_an_answer_altered_where_it_is_produced(capsys, what):
    hooks = run.default_hooks()

    def alter(trees):
        t = trees[1]
        if what == "leaf_value":
            t.leaf_value[3] *= 1.02
        else:
            t.threshold[0] += 3.0
    hooks.alter_trees = alter
    line = drive(capsys, hooks)
    assert line["correct"] is False


@pytest.fixture(scope="module")
def followed():
    """Three rounds of the program on the fixture cell's rows, followed by
    the reference in float32 and, as the control, in bfloat16."""
    import lightgbm_tpu as lgb
    cell = manifest.workload(CELL, BENCH)
    config = manifest.config(cell["config"], BENCH)
    rows = tabular_codes.make(77, config["data"], config["train_rows"], 1)
    ds = build_dataset(lgb, rows["codes"], rows["label"], config["params"],
                          [c["name"] for c in config["data"]["columns"]])
    bst = lgb.Booster(params=config["params"], train_set=ds)
    for _ in range(3):
        bst.update()
    trees = [gbdt.tree_from_dump(t)
             for t in bst.dump_model(num_iteration=3)["tree_info"]]
    ref = gbdt.follow(rows["codes"], rows["label"], trees, config["params"])
    low = gbdt.follow(rows["codes"], rows["label"], trees, config["params"],
                      dtype=jnp.bfloat16)
    return cell, config, rows, trees, ref, low


def test_the_control_in_bfloat16_is_not_correct(followed):
    cell, _, _, trees, ref, low = followed
    limits = cell["traffic_params"]["limits"]
    program = check.compare(check.stated_of(trees), ref)
    control = check.compare(check.stated_by(low, trees), ref)
    assert check.verdict(program, limits)
    assert not check.verdict(control, limits)
    assert control["leaf_value_gap"] > 3 * program["leaf_value_gap"]


def test_reference_histograms_match_numpy_float64(followed):
    """The reference's own sums against a loop-free NumPy count in f64."""
    _, config, rows, trees, ref, _ = followed
    codes, label = rows["codes"], rows["label"].astype(np.float64)
    tree = trees[0]
    p = label.mean()
    g = p - label
    h = np.full_like(label, p * (1 - p))
    leaf = np.asarray(gbdt.route(jnp.asarray(codes), tree))
    n_leaves = tree.num_leaves
    count = np.bincount(leaf, minlength=n_leaves)
    assert np.array_equal(count, ref[0].leaf_count)
    sum_g = np.bincount(leaf, weights=g, minlength=n_leaves)
    sum_h = np.bincount(leaf, weights=h, minlength=n_leaves)
    step = -sum_g / sum_h * config["params"]["learning_rate"]
    scale = np.maximum(np.abs(step), np.median(np.abs(step)))
    assert np.max(np.abs(step - ref[0].leaf_step) / scale) < 2e-5
    # the root's best split by brute force over one column
    f = int(ref[0].best_split[0][0])
    col = codes[f]
    best = -np.inf
    for t in range(int(col.max())):
        left = col <= t
        if left.sum() < 20 or (~left).sum() < 20:
            continue
        gl, hl, gr, hr = g[left].sum(), h[left].sum(), g[~left].sum(), \
            h[~left].sum()
        best = max(best, gl * gl / hl + gr * gr / hr
                   - g.sum() ** 2 / h.sum())
    assert ref[0].best_gain[0] == pytest.approx(best, rel=1e-4)


def test_readings_tool_reads_program_control_and_faults(tmp_path, capsys):
    """What the limits are set from on the chip, at the fixture's size: the
    program within its limits, the control and each planted fault over."""
    from perfbench import readings
    out = tmp_path / "r.jsonl"
    rc = readings.main(["--workload", CELL, "--bench-dir", BENCH, "--seeds",
                        "4100000013", "--control-seeds", "4100000013",
                        "--allow-cpu", "--out", str(out)])
    capsys.readouterr()
    assert rc == 0
    line = json.loads(out.read_text())
    limits = manifest.workload(CELL, BENCH)["traffic_params"]["limits"]
    assert check.verdict(line["program"], limits)
    for other in ("control_bf16", "fault_half_batch",
                  "fault_state_unchanged"):
        assert not check.verdict(line[other], limits), other
    assert line["fault_state_unchanged"]["leaf_value_gap"] > \
        10 * line["program"]["leaf_value_gap"]
    assert line["fault_half_batch"]["leaf_count_gap"] > 0.3


def test_split_numbers_by_hand():
    """Two checked nodes worth 10 and 2; the stated split at the second is
    worth 1 there: it gives away half of that node, a twelfth of the tree."""
    gains = [np.full((1, 256), -np.inf), np.full((1, 256), -np.inf)]
    gains[0][0, 7] = 10.0
    gains[1][0, 3], gains[1][0, 4] = 2.0, 1.0
    three = np.ones(3)
    reading = gbdt.RoundReading(
        leaf_value=three, leaf_step=three, leaf_count=three, leaf_hess=three,
        nodes=np.array([0, 1]), best_gain=np.array([10.0, 2.0]), gains=gains,
        best_split=np.array([[0, 7], [0, 3]]))
    stated = {"leaf_value": three, "leaf_count": three, "leaf_weight": three,
              "split": np.array([[0, 7], [0, 4]])}
    got = check.compare([stated], [reading])
    assert got["leaf_value_gap"] == got["leaf_count_gap"] == 0.0
    assert got["split_gain_gap"] == pytest.approx(0.5)
    assert got["split_gain_loss"] == pytest.approx(1 / 12)
    # a stated split that the reference does not allow cannot be within any limit
    stated["split"] = np.array([[0, 7], [0, 9]])
    assert check.compare([stated], [reading])["split_gain_loss"] == np.inf
    assert not check.verdict(check.compare([stated], [reading]),
                             {"split_gain_loss": 1.0})
