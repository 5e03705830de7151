"""The cell `criteo67-lgbpar-l255.train` (ISSUE 33): LightGBM's published
parallel experiment on one four-chip host, added by data files, one
reference module and nine metric files over existing readers.

The CPU stand-in is the cell's own files at 4 x 16,384 rows over four of
the virtual devices (`num_machines=4`), through `perfbench.run`'s hooks:
`correct` against `perfbench/reference/gbdt_parts.py`, and not correct
for the bfloat16 control and each planted fault, the deployment's own
among them: one shard's rows left out of the sums.
"""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from perfbench import check, manifest, readers, run
from perfbench.generators import tabular_codes
from perfbench.reference import gbdt, gbdt_parts

CELL = "criteo67-lgbpar-l255.train"
CONFIG = "criteo67-lgbpar-l255"
OLD_CELL = "airline13-l31.train"
SHARDS = 4
ROWS = SHARDS * 16384
SEED = 4100000013


@pytest.fixture(scope="module")
def small_bench(tmp_path_factory):
    """The cell's files, cut to ROWS rows on four devices."""
    bench = tmp_path_factory.mktemp("bench")
    for d in ("configs", "workloads", "layer_metrics"):
        os.makedirs(bench / d)
    with open(os.path.join(manifest.HERE, "peaks.json")) as f:
        (bench / "peaks.json").write_text(f.read())
    config = manifest.config(CONFIG)
    config["train_rows"] = ROWS
    config["params"]["num_machines"] = SHARDS
    (bench / "configs" / (CONFIG + ".json")).write_text(json.dumps(config))
    cell = manifest.workload(CELL)
    cell["traffic_params"]["holdout_rows"] = 16384
    (bench / "workloads" / (CELL + ".json")).write_text(json.dumps(cell))
    return str(bench), config, cell


@pytest.fixture(scope="module")
def driven(small_bench):
    """One run of the small cell: its result line, the trees that were
    compared, the rows, and the reference's reading of those trees."""
    import contextlib
    import io
    bench, config, cell = small_bench
    hooks, kept, boosters = run.default_hooks(), [], []
    hooks.require_chip = False
    hooks.compile_cache = False
    hooks.alter_trees = kept.extend
    make = hooks.make_booster

    def make_booster(lgb, params, ds):
        boosters.append(make(lgb, params, ds))
        return boosters[-1]
    hooks.make_booster = make_booster
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                       "0.1", "--trace", "0", "--bench-dir", bench],
                      hooks=hooks)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    rows = tabular_codes.make(SEED, config["data"], ROWS, 1)
    traffic = cell["traffic_params"]
    kw = {"n_check": int(traffic["check_nodes"]), "seed": SEED}
    readings = gbdt_parts.follow(rows["codes"], rows["label"], kept,
                                 config["params"], **kw)
    bst = boosters[0]
    learner = {"mesh": dict(bst._mesh.shape), "policy": bst._grow_policy,
               "row_sharding": bst._dd.row_sharding,
               "score_sharding": bst._train_score.sharding}
    return {"line": line, "trees": kept, "rows": rows, "readings": readings,
            "params": config["params"], "limits": traffic["limits"],
            "kw": kw, "learner": learner, "log": out.getvalue()}


def test_the_sharded_program_is_correct_against_the_parts_reference(driven):
    line = driven["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rounds_per_s", "holdout_auc",
                                    "setup_s"}
    assert set(line["compared"]) == {"leaf_value_gap", "leaf_count_gap",
                                     "split_gain_loss"}
    assert line["compared"]["leaf_value_gap"]["value"] < 1e-5
    assert line["compared"]["leaf_count_gap"]["value"] == 0
    assert len(driven["trees"]) == 3
    assert all(t.num_leaves == 255 for t in driven["trees"])
    # four shards grew them, the wave grower, per-row state split like
    # the rows
    learner = driven["learner"]
    assert learner["mesh"] == {"data": SHARDS}
    assert learner["policy"] == "wave"
    assert learner["row_sharding"] is not None
    assert learner["score_sharding"] == learner["row_sharding"]
    assert f"{ROWS} x 67 of population" in driven["log"]


def _fault(driven, name):
    rows, trees, params, kw = (driven[k] for k in
                               ("rows", "trees", "params", "kw"))
    codes, label = rows["codes"], rows["label"]
    if name == "control_bf16":
        return gbdt_parts.follow(codes, label, trees, params,
                                 dtype=jnp.bfloat16, **kw)
    if name == "half_batch":
        return gbdt_parts.follow(
            np.ascontiguousarray(codes[:, :ROWS // 2]), label[:ROWS // 2],
            trees, params, **kw)
    if name == "state_unchanged":
        return gbdt_parts.follow(codes, label, trees, params,
                                 update_scores=False, **kw)
    assert name == "one_shard_left_out"        # the second of the four
    keep = np.ones(ROWS, bool)
    keep[ROWS // SHARDS:2 * ROWS // SHARDS] = False
    return gbdt_parts.follow(np.ascontiguousarray(codes[:, keep]),
                             label[keep], trees, params, **kw)


@pytest.mark.parametrize("fault", ["control_bf16", "half_batch",
                                   "state_unchanged", "one_shard_left_out"])
def test_the_control_and_each_fault_fail_the_cells_limits(driven, fault):
    trees, readings, limits = (driven[k] for k in
                               ("trees", "readings", "limits"))
    program = check.compare(check.stated_of(trees), readings)
    assert check.verdict(program, limits)
    numbers = check.compare(check.stated_by(_fault(driven, fault), trees),
                            readings)
    assert not check.verdict(numbers, limits), numbers
    if fault == "one_shard_left_out":
        # a quarter of the rows is missing: at least that of the worst
        # leaf's (about 0.25 on the chip, where a leaf holds 800,000
        # rows; here one holds 250 and the worst leaf lost more)
        assert 0.25 <= numbers["leaf_count_gap"] < 0.75
    if fault == "half_batch":
        assert numbers["leaf_count_gap"] > 0.3
    if fault == "control_bf16":
        assert numbers["leaf_value_gap"] > 100 * program["leaf_value_gap"]


@pytest.mark.parametrize("parts", [1, 4])
def test_parts_reference_reads_what_the_one_device_reference_reads(
        driven, parts):
    """One part: `gbdt.follow`'s numbers bit for bit.  Four parts: the
    same to 1e-6 (float64 sums of four float32 partial sums)."""
    rows, trees, params, kw = (driven[k] for k in
                               ("rows", "trees", "params", "kw"))
    want = gbdt.follow(rows["codes"], rows["label"], trees, params, **kw)
    got = gbdt_parts.follow(rows["codes"], rows["label"], trees, params,
                            parts=parts, **kw)
    assert len(gbdt_parts.part_bounds(ROWS, 67, parts)) == parts
    assert len(got) == len(want) == 3
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.nodes, w.nodes)
        np.testing.assert_array_equal(g.leaf_count, w.leaf_count)
        for field in ("leaf_value", "leaf_step", "leaf_hess", "best_gain"):
            a, b = getattr(g, field), getattr(w, field)
            if parts == 1:
                np.testing.assert_array_equal(a, b, field)
            else:
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7,
                                           err_msg=field)
        if parts == 1:
            np.testing.assert_array_equal(g.best_split, w.best_split)
            for a, b in zip(g.gains, w.gains):
                np.testing.assert_array_equal(a, b)


def test_parts_are_cut_to_fit_a_device():
    # the cell's rows: one part a chip of a four-chip host, whole blocks
    bounds = gbdt_parts.part_bounds(201_326_592, 67, n_devices=4)
    assert bounds == [(i * 50_331_648, (i + 1) * 50_331_648)
                      for i in range(4)]
    assert all((hi - lo) % gbdt.BLOCK == 0 for lo, hi in bounds)
    assert (bounds[0][1] * (67 + 24)) <= gbdt_parts.PART_BYTES
    # the one-chip cells' rows fit one part; a ragged count ends short
    assert gbdt_parts.part_bounds(114_999_296, 13) == [(0, 114_999_296)]
    assert gbdt_parts.part_bounds(100_000, 67, parts=3) == [
        (0, 49_152), (49_152, 98_304), (98_304, 100_000)]


def test_the_reference_imports_nothing_of_the_program():
    import ast
    with open(gbdt_parts.__file__) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0] or "perfbench")
    assert "lightgbm_tpu" not in names
    assert names <= {"__future__", "typing", "numpy", "jax", "perfbench",
                     "gbdt"}


# ----------------------------------------------------------- the manifest
def test_three_configurations_three_cells_one_on_four_chips():
    """The benchmark's first three configurations and cells (PR 35
    appended a fourth, on one chip: tests/test_lgbcat_cell.py), and this
    cell's nine `par4.*` metrics, listed together and each with its file
    (later PRs append metrics of their own: no count of the whole)."""
    assert manifest.problems() == []
    b = manifest.benchmark()
    assert [c["name"] for c in b["configs"]][2] == CONFIG
    assert [w["name"] for w in b["workloads"]][2] == CELL
    assert [w["chips"] for w in b["workloads"]][:4] == [1, 1, 4, 1]
    names = [m["name"] for m in b["per_layer"]]
    at = [i for i, n in enumerate(names) if n.startswith("par4.")]
    assert len(at) == 9 and at == list(range(at[0], at[0] + 9))
    assert not any(n.startswith("cat.") for n in names[:at[-1]])
    for i in at:
        assert os.path.isfile(os.path.join(
            manifest.HERE, "layer_metrics", names[i] + ".json"))


def test_a_second_four_chip_cell_is_caught_beside_the_benchmarks_own(
        tmp_path):
    """At most a quarter of the cells, and one always, may ask for four.
    `tests/perfbench/test_perfbench_manifest.py::
    test_a_second_four_chip_cell_of_two_is_caught` plants a four-chip cell
    in a copy of the manifest and presumes the copy had none; since this
    cell it has one, so that test cannot hold as written (tests/conftest.py
    marks it, PERF.md 7 asks a benchmark PR for the repair).  The same rule
    is held here on the manifest as it is (the own four-chip cell is sound,
    a second of four cells is caught) and, with the own cell set aside, by
    that test's two steps: one four-chip cell of two passes, a second of
    three is caught."""
    import shutil
    root, bench = tmp_path / "root", tmp_path / "root" / "perfbench"
    bench.mkdir(parents=True)
    for d in ("configs", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(manifest.HERE, d), bench / d)
    shutil.copy(os.path.join(manifest.HERE, "peaks.json"), bench)

    def write(b):
        (root / "BENCHMARK.json").write_text(json.dumps(b))
        return manifest.problems(str(root), str(bench))

    b = manifest.benchmark()
    assert write(b) == []
    first = b["workloads"][0]
    planted = dict(first, name="planted.train", traffic="other", chips=4)
    w = manifest.workload(first["name"])
    w.update(name=planted["name"], traffic="other", chips=4)
    (bench / "workloads" / "planted.train.json").write_text(json.dumps(w))
    second = json.loads(json.dumps(b))
    second["workloads"].append(planted)
    assert "too many four-chip cells" in write(second)
    # the own cell set aside: the first cell on four chips is the one that
    # always may, and the planted one beside it is caught
    gone = {x["name"] for x in b["workloads"] if x["chips"] == 4}
    assert gone == {CELL}
    alone = json.loads(json.dumps(b))
    alone["workloads"] = [x for x in alone["workloads"]
                          if x["name"] not in gone]
    alone["configs"] = [c for c in alone["configs"] if c["name"] != CONFIG]
    alone["per_layer"] = [m for m in alone["per_layer"]
                          if not gone & set(m["workloads"])]
    alone["workloads"][0]["chips"] = 4
    w = manifest.workload(first["name"])
    w["chips"] = 4
    (bench / "workloads" / (first["name"] + ".json")).write_text(
        json.dumps(w))
    assert write(alone) == []
    alone["workloads"].append(planted)
    assert "too many four-chip cells" in write(alone)


def test_each_new_metric_reads_an_existing_reader_and_lists_both_cells():
    """Nine `par4.*` files: seven twins of the first cell's metrics, the
    collectives' share, and the kernel's roofline from ONE shard's rows.
    Each lists the new cell first and also the first cell
    (`tests/perfbench/test_perfbench_trace.py::test_recorded_trace_reduces`
    holds that the manifest's first cell reports every metric)."""
    new = {m["name"]: m for m in manifest.layer_metrics(CELL)
           if m["name"].startswith("par4.")}
    assert len(new) == 9
    old = {m["name"]: m for m in manifest.layer_metrics(OLD_CELL)
           if "." in m["name"] and m["name"].split(".")[0]
           not in ("l255", "par4", "cat")
           or m["name"] == "hist_kernel_roofline"}
    for name, m in new.items():
        assert m["workloads"] == [CELL, OLD_CELL]
        assert m["moves"] == "train_rounds_per_s"
        assert m["reader"] in readers.READERS
        assert "not to be read" in m["what"]
        twin = old.get(name[len("par4."):])
        if name == "par4.collective.time_pct":
            assert twin is None and m["layer"] == "multi-chip reduce"
            assert m["reader"] == "scope_share"
            assert m["args"]["zero_if_absent"] is True
            continue
        for k in ("reader", "layer", "unit", "better", "source"):
            assert m[k] == twin[k], (name, k)
        if name != "par4.hist_kernel_roofline":
            assert m["args"] == twin["args"], name
    # the roofline's work is one shard's: tied to the configuration
    from lightgbm_tpu.parallel.learner import padded_feature_count
    config = manifest.config(CONFIG)
    work = new["par4.hist_kernel_roofline"]["args"]["work_args"]
    assert work["rows"] * SHARDS == config["train_rows"]
    assert work["columns"] == padded_feature_count(
        len(config["data"]["columns"]), SHARDS) == 68
    assert (work["max_bin"], work["slots"], work["bin_bytes"]) == (255, 1, 1)


def test_the_configuration_is_the_published_shape_and_states_its_cut():
    cell = manifest.workload(CELL)
    config = manifest.config(cell["config"])
    p = config["params"]
    assert (p["tree_learner"], p["num_leaves"], p["learning_rate"],
            p["max_bin"], p["min_data_in_leaf"],
            p["min_sum_hessian_in_leaf"]) == ("data", 255, 0.1, 255, 20,
                                              1e-3)
    assert "deterministic_reduce" not in p and "num_machines" not in p
    assert "docs/Experiments.rst" in config["source"]
    assert "Parallel Experiment" in config["source"]
    assert len(config["source"]) <= 200
    assert config["reduced"] == ["train_rows"] == list(config["reduced_why"])
    assert config["train_rows"] % (SHARDS * 2048) == 0
    assert config["reference"] == "gbdt_parts"
    cols = config["data"]["columns"]
    assert len(cols) == 67
    assert [c["cardinality"] for c in cols].count(255) == 66
    assert {c["name"]: c["cardinality"] for c in cols}["I10"] == 12
    assert len(config["data"]["pairs"]) == 6
    for word in ("every row of every shard", "ONE tree", "deterministic_reduce",
                 "two-limb", "no voting"):
        assert word in config["guarantees"], word
    old = manifest.config("airline13-l31")["params"]
    for k in ("tree_grow_policy", "tpu_wave_width", "tpu_wave_gain_ratio",
              "tpu_wave_strict_tail", "objective"):
        assert p[k] == old[k]
    assert cell["chips"] == 4
    t, t_old = cell["traffic_params"], \
        manifest.workload(OLD_CELL)["traffic_params"]
    assert {k: v for k, v in t.items() if k not in ("limits", "check_nodes")} \
        == {k: v for k, v in t_old.items()
            if k not in ("limits", "check_nodes")}
    assert set(t["limits"]) == {"leaf_value_gap", "leaf_count_gap",
                                "split_gain_loss"}
