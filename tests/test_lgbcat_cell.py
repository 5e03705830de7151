"""The cell `airline13-lgbcat-l255.train` (ISSUE 35): LightGBM's direct
categorical features on the airline rows, added by data files, one job
(`jobs/train_cat.py`), one reference module (`reference/gbdt_cat.py`) and
nine metric files over existing readers.

The CPU stand-in is the cell's own files at 65,536 rows through
`perfbench.run`'s hooks: `correct` against `gbdt_cat`, and not correct for
the bfloat16 control and each planted fault, the deployment's own two
among them (a categorical node routed as `code <= t`, a category dropped
from every left set).
"""
import ast
import contextlib
import importlib.util
import io
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from perfbench import check, manifest, readers, run
from perfbench.generators import tabular_codes
from perfbench.reference import gbdt, gbdt_cat

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "airline13-lgbcat-l255.train"
CONFIG = "airline13-lgbcat-l255"
TWIN_CELL = "airline13-lgbexp-l255.train"
TWIN_CONFIG = "airline13-lgbexp-l255"
OLD_CELL = "airline13-l31.train"
ROWS = 65536
SEED = 4100000013


@pytest.fixture(scope="module")
def faults():
    """scripts/lgbcat_readings.py: the faults the chip readings plant."""
    spec = importlib.util.spec_from_file_location(
        "lgbcat_readings", os.path.join(os.path.dirname(HERE), "scripts",
                                        "lgbcat_readings.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small_bench(tmp, **params):
    """The cell's files, cut to ROWS rows."""
    for d in ("configs", "workloads", "layer_metrics"):
        os.makedirs(tmp / d)
    with open(os.path.join(manifest.HERE, "peaks.json")) as f:
        (tmp / "peaks.json").write_text(f.read())
    config = manifest.config(CONFIG)
    config["train_rows"] = ROWS
    config["params"].update(params)
    (tmp / "configs" / (CONFIG + ".json")).write_text(json.dumps(config))
    cell = manifest.workload(CELL)
    cell["traffic_params"]["holdout_rows"] = 16384
    (tmp / "workloads" / (CELL + ".json")).write_text(json.dumps(cell))
    return str(tmp), config, cell


def drive(bench):
    """One run of the small cell: (result line, the trees compared, log)."""
    hooks, kept = run.default_hooks(), []
    hooks.require_chip = False
    hooks.compile_cache = False
    hooks.alter_trees = kept.extend
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                       "0.1", "--trace", "0", "--bench-dir", bench],
                      hooks=hooks)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), kept, \
        out.getvalue()


@pytest.fixture(scope="module")
def driven(tmp_path_factory):
    bench, config, cell = small_bench(tmp_path_factory.mktemp("bench"))
    line, trees, log = drive(bench)
    rows = tabular_codes.make(SEED, config["data"], ROWS, 1)
    traffic = cell["traffic_params"]
    kw = {"n_check": int(traffic["check_nodes"]), "seed": SEED,
          "categorical": gbdt_cat.declared_columns(config)}
    readings = gbdt_cat.follow(rows["codes"], rows["label"], trees,
                               config["params"], **kw)
    return {"line": line, "trees": trees, "rows": rows, "log": log,
            "readings": readings, "params": config["params"],
            "limits": traffic["limits"], "kw": kw, "config": config}


def test_the_program_is_correct_against_the_categorical_reference(driven):
    line = driven["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rounds_per_s", "holdout_auc",
                                    "setup_s"}
    assert set(line["compared"]) == {"leaf_value_gap", "leaf_count_gap",
                                     "split_gain_loss"}
    assert line["compared"]["leaf_value_gap"]["value"] < 1e-5
    assert line["compared"]["leaf_count_gap"]["value"] == 0
    assert line["compared"]["split_gain_loss"]["value"] < 1e-7
    trees = driven["trees"]
    assert len(trees) == 3
    declared = set(driven["kw"]["categorical"]["columns"])
    assert declared == {1, 2, 3, 6, 9, 10}
    for t in trees:
        # on this small fixture over a third of the splits are category sets
        assert 3 * t.is_cat.sum() > len(t.is_cat)
        assert set(t.split_feature[t.is_cat]) <= declared
        assert not set(t.split_feature[~t.is_cat]) & declared
        sizes = t.left_set.sum(axis=1)
        assert (sizes[t.is_cat] >= 1).all() and (sizes[t.is_cat] <= 32).all()
        assert (sizes[~t.is_cat] == 0).all()
    assert "categorical: [1, 2, 3, 6, 9, 10]" in driven["log"]


def _fault(driven, faults, name):
    rows, trees, params, kw = (driven[k] for k in
                               ("rows", "trees", "params", "kw"))
    codes, label = rows["codes"], rows["label"]
    if name == "control_bf16":
        return gbdt_cat.follow(codes, label, trees, params,
                               dtype=jnp.bfloat16, **kw)
    if name == "half_batch":
        return gbdt_cat.follow(
            np.ascontiguousarray(codes[:, :ROWS // 2]), label[:ROWS // 2],
            trees, params, **kw)
    if name == "state_unchanged":
        return gbdt_cat.follow(codes, label, trees, params,
                               update_scores=False, **kw)
    return gbdt_cat.follow(codes, label, faults.fault_trees(trees, name),
                           params, **kw)


@pytest.mark.parametrize("fault", ["control_bf16", "half_batch",
                                   "state_unchanged", "cat_as_threshold",
                                   "category_dropped"])
def test_the_control_and_each_fault_fail_the_cells_limits(driven, faults,
                                                          fault):
    trees, readings, limits = (driven[k] for k in
                               ("trees", "readings", "limits"))
    program = check.compare(check.stated_of(trees), readings)
    assert check.verdict(program, limits)
    numbers = check.compare(check.stated_by(_fault(driven, faults, fault),
                                            trees), readings)
    assert not check.verdict(numbers, limits), numbers
    if fault in faults.FAULTS:
        # rows on the wrong side of a categorical node: the counts say so
        assert numbers["leaf_count_gap"] > 2 * limits["leaf_count_gap"]
        assert numbers["leaf_value_gap"] > 2 * limits["leaf_value_gap"]


def test_a_dropped_category_in_the_stated_tree_is_no_candidate(driven,
                                                               faults):
    """The tree itself altered (not put in the program's place): a left
    set that lost a category from its middle is no prefix of the sorted
    order, and the reference prices it at -inf."""
    trees = faults.fault_trees(driven["trees"], "category_dropped")
    readings = gbdt_cat.follow(driven["rows"]["codes"],
                               driven["rows"]["label"], trees,
                               driven["params"], **driven["kw"])
    numbers = check.compare(check.stated_of(trees), readings)
    assert numbers["split_gain_loss"] > driven["limits"]["split_gain_loss"]


def test_min_data_per_group_binds_and_the_reference_enforces_it(
        tmp_path, driven):
    """A tree grown with the configuration's 100 differs from one grown
    with 1; every categorical node of the first is a candidate under the
    stated rule (checked at EVERY node), and the second's trees state
    sets that the rule with 100 refuses."""
    bench, config, _ = small_bench(tmp_path, min_data_per_group=1)
    _, loose, _ = drive(bench)
    strict = driven["trees"]
    assert any(not np.array_equal(a.left_set, b.left_set)
               or not np.array_equal(a.split_feature, b.split_feature)
               for a, b in zip(strict, loose))
    rows = driven["rows"]
    kw = dict(driven["kw"], n_check=1 << 30)

    def stated_gains(trees):
        out = []
        for r, t in zip(gbdt_cat.follow(rows["codes"], rows["label"], trees,
                                        driven["params"], **kw), trees):
            out += [r.gains[k][t.split_feature[n], gbdt_cat.STATED_SLOT]
                    for k, n in enumerate(r.nodes) if t.is_cat[n]]
        return np.asarray(out)

    assert np.isfinite(stated_gains(strict)).all()
    assert not np.isfinite(stated_gains(loose)).all()


def test_without_categorical_nodes_it_reads_what_gbdt_reads(driven):
    """The twin configuration's trees (no declared column): every number
    of `gbdt_cat.follow` is `gbdt.follow`'s."""
    import lightgbm_tpu as lgb
    from perfbench.jobs.train import build_dataset
    config = manifest.config(TWIN_CONFIG)
    rows = driven["rows"]
    names = [c["name"] for c in config["data"]["columns"]]
    ds = build_dataset(lgb, rows["codes"], rows["label"], config["params"],
                       names)
    bst = lgb.train(config["params"], ds, num_boost_round=2)
    dump = bst.dump_model()["tree_info"]
    kw = {"n_check": 8, "seed": SEED}
    a = gbdt.follow(rows["codes"], rows["label"],
                    [gbdt.tree_from_dump(t) for t in dump],
                    config["params"], **kw)
    trees = [gbdt_cat.tree_from_dump(t) for t in dump]
    assert not any(t.is_cat.any() for t in trees)
    b = gbdt_cat.follow(rows["codes"], rows["label"], trees,
                        config["params"], **kw)
    for x, y in zip(a, b):
        for u, v in zip(x, y):
            if isinstance(u, list):
                assert all(np.array_equal(p, q) for p, q in zip(u, v))
            else:
                np.testing.assert_array_equal(u, v)


def test_the_reference_works_out_the_programs_binning_by_the_rule(driven):
    """`kept_categories` from the sample rows against the mapper the
    public constructor fits: the same categories are bins of their own."""
    import lightgbm_tpu as lgb
    from perfbench.jobs.train_cat import build_dataset
    config, rows = driven["config"], driven["rows"]
    names = [c["name"] for c in config["data"]["columns"]]
    ds = build_dataset(lgb, rows["codes"], rows["label"], config["params"],
                       names, config["categorical_feature"])
    decl = gbdt_cat.declared_columns(config)
    assert ds._categorical_indices == sorted(decl["columns"])
    some_other = False
    for f in decl["columns"]:
        kept = gbdt_cat.kept_categories(
            rows["codes"][f, :decl["sample_rows"]], decl["max_bin"])
        mine = np.zeros(256, bool)
        mine[ds.bin_mappers[f].bin_2_categorical] = True
        np.testing.assert_array_equal(kept, mine)
        some_other |= bool((np.bincount(rows["codes"][f], minlength=256)
                            [~kept] > 0).any())
    assert some_other       # a 255-category column keeps 254 at most


def test_the_reference_imports_nothing_of_the_program():
    for name in ("gbdt_cat.py",):
        with open(os.path.join(manifest.HERE, "reference", name)) as f:
            tree = ast.parse(f.read())
        names = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names |= {a.name for a in node.names}
            elif isinstance(node, ast.ImportFrom):
                names.add(("." * node.level) + (node.module or ""))
        assert not any("lightgbm" in n for n in names), names
        assert names <= {"__future__", "typing", "numpy", "jax", "jax.numpy",
                         ".", ".gbdt"}


# ----------------------------------------------------------- the manifest
def test_four_configurations_four_cells_thirtyfour_metric_files():
    assert manifest.problems() == []
    b = manifest.benchmark()
    # the fourth configuration and cell (later PRs append their own: the
    # first four are held, not the count of the whole)
    assert [c["name"] for c in b["configs"]][3] == CONFIG
    assert len(b["configs"]) >= 4
    assert [w["name"] for w in b["workloads"]][3] == CELL
    assert [w["chips"] for w in b["workloads"]][:4] == [1, 1, 4, 1]
    # this cell's nine `cat.*` metrics, listed together and each with its
    # file (later PRs append metrics of their own: no count of the whole)
    names = [m["name"] for m in b["per_layer"]]
    at = [i for i, n in enumerate(names) if n.startswith("cat.")]
    assert len(at) == 9 and at == list(range(at[0], at[0] + 9))
    for i in at:
        assert os.path.isfile(os.path.join(
            manifest.HERE, "layer_metrics", names[i] + ".json"))
    cell = manifest.workload(CELL)
    assert cell["job"] == "train_cat" and cell["chips"] == 1
    assert manifest.config(CONFIG)["reference"] == "gbdt_cat"


def test_each_new_metric_reads_an_existing_reader_and_lists_both_cells():
    """Nine `cat.*` files: eight twins of the `.lgbexp` cell's metrics and
    the routing kernel's share.  Each lists the new cell first and also
    the first cell (`tests/perfbench/test_perfbench_trace.py::
    test_recorded_trace_reduces` holds that the manifest's first cell
    reports every metric from its fixture trace, which is why no metric
    over `scope_count_per` or `counter_delta` on a new counter could be
    added: PERF.md 7.10)."""
    new = {m["name"]: m for m in manifest.layer_metrics(CELL)
           if m["name"].startswith("cat.")}
    assert len(new) == 9
    twins = {m["name"]: m for m in manifest.layer_metrics(TWIN_CELL)}
    for name, m in new.items():
        assert m["workloads"] == [CELL, OLD_CELL]
        assert m["moves"] == "train_rounds_per_s"
        assert m["reader"] in readers.READERS
        assert "not to be read" in m["what"]
        if name == "cat.route.time_pct":
            assert m["reader"] == "scope_share"
            assert m["args"] == {"program": "^jit_grow$",
                                 "name": "^route_wave_rows",
                                 "zero_if_absent": True}
            continue
        twin = twins["l255." + name[len("cat."):]]
        for k in ("reader", "args", "layer", "unit", "better", "source"):
            assert m[k] == twin[k], (name, k)


def test_the_configuration_is_the_twins_but_for_the_declaration():
    config, twin = manifest.config(CONFIG), manifest.config(TWIN_CONFIG)
    added = {"cat_smooth": 10.0, "cat_l2": 10.0, "max_cat_threshold": 32,
             "max_cat_to_onehot": 4, "min_data_per_group": 100}
    assert config["params"] == {**twin["params"], **added}
    for k in ("data", "train_rows", "generator", "reduced", "precision"):
        assert config[k] == twin[k], k
    assert config["categorical_feature"] == [
        "Month", "DayofMonth", "DayOfWeek", "UniqueCarrier", "Origin",
        "Dest"]
    assert config["reference"] == "gbdt_cat"
    assert config["job_kind"] == "train_cat"
    for k in ("source", "reduced_why", "assumed", "rule", "departures",
              "guarantees"):
        assert config[k], k
    assert len(config["source"]) <= 200
    cell, twin_cell = manifest.workload(CELL), manifest.workload(TWIN_CELL)
    assert cell["traffic_params"] == twin_cell["traffic_params"]
