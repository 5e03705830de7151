"""The cell `airline13-lgbexp-l255.train` (ISSUE 29): added by data files
alone, and decided `correct` by limits that the bfloat16 control and both
planted faults fail.

The CPU stand-in is the fixture configuration `tiny13-lgbexp-l255`
(tests/perfbench/fixtures/bench/): the experiment settings at 65,536 rows,
where `min_sum_hessian_in_leaf=100` stops a tree near 120 leaves.
"""
import importlib.util
import json
import os

import pytest

from perfbench import check, manifest, readers, readings, run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "perfbench", "fixtures", "bench")
FIXTURE = "tiny13-lgbexp-l255.train"
CELL = "airline13-lgbexp-l255.train"
OLD_CELL = "airline13-l31.train"


@pytest.fixture(scope="module")
def correct_tests():
    """tests/perfbench/test_perfbench_correct.py, whose `drive` runs a
    fixture cell through `perfbench.run.main` without a chip."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_correct_for_lgbexp",
        os.path.join(HERE, "perfbench", "test_perfbench_correct.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_program_is_correct_under_the_experiment_settings(
        correct_tests, capsys, monkeypatch):
    monkeypatch.setattr(correct_tests, "CELL", FIXTURE)
    kept = []
    hooks = run.default_hooks()
    hooks.alter_trees = kept.extend
    line = correct_tests.drive(capsys, hooks)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rounds_per_s", "holdout_auc",
                                    "setup_s"}
    # the hessian gate decides which leaves exist: no leaf under 100 of
    # hessian (about 400 rows), so a tree stops short of 255
    assert len(kept) == 3
    for t in kept:
        assert 60 < t.num_leaves < 255
        assert t.leaf_weight.min() >= 100.0
    assert line["compared"]["leaf_value_gap"]["value"] < 1e-5


def test_the_control_and_both_faults_fail_the_fixtures_limits(tmp_path,
                                                              capsys):
    out = tmp_path / "r.jsonl"
    rc = readings.main(["--workload", FIXTURE, "--bench-dir", BENCH,
                        "--seeds", "4100000013", "--control-seeds",
                        "4100000013", "--allow-cpu", "--out", str(out)])
    capsys.readouterr()
    assert rc == 0
    line = json.loads(out.read_text())
    limits = manifest.workload(FIXTURE, BENCH)["traffic_params"]["limits"]
    assert check.verdict(line["program"], limits)
    for other in ("control_bf16", "fault_half_batch",
                  "fault_state_unchanged"):
        assert not check.verdict(line[other], limits), other
    assert line["control_bf16"]["leaf_value_gap"] > \
        1000 * line["program"]["leaf_value_gap"]
    assert line["fault_half_batch"]["leaf_count_gap"] > 0.3


# ----------------------------------------------------------- the manifest
def test_two_configurations_two_cells_sixteen_metric_files():
    """The benchmark's first two configurations and cells and their
    sixteen metric files: the old cell's eight and the new cell's eight
    `l255.*` twins, the manifest's first sixteen entries (later PRs
    append metrics of their own: tests/test_criteo67_cell.py)."""
    assert manifest.problems() == []
    b = manifest.benchmark()
    assert [c["name"] for c in b["configs"]][:2] == [
        "airline13-l31", "airline13-lgbexp-l255"]
    assert [w["name"] for w in b["workloads"]][:2] == [OLD_CELL, CELL]
    mine = [m for m in b["per_layer"][:16]]
    assert [m["workloads"] for m in mine] == [[OLD_CELL]] * 8 \
        + [[CELL, OLD_CELL]] * 8
    assert [m["name"] for m in mine[8:]] == [
        "l255." + m["name"] for m in mine[:8]]
    for m in mine:
        assert os.path.isfile(os.path.join(
            manifest.HERE, "layer_metrics", m["name"] + ".json"))
    assert b["workloads"][1]["chips"] == 1


def test_each_new_metric_is_an_old_readers_twin_and_lists_the_new_cell():
    """The new cell brings its own eight metric files over the old cell's
    readers.  Each lists the new cell first and ALSO the old one:
    `tests/perfbench/test_perfbench_trace.py::test_recorded_trace_reduces`,
    a file of the benchmark that this PR may not edit, holds that the
    manifest's FIRST cell reports every `per_layer` metric (PERF.md 7.10
    says which line a `benchmark` PR changes to let the lists part)."""
    d = os.path.join(manifest.HERE, "layer_metrics")
    new = {m["name"]: m for m in manifest.layer_metrics(CELL)
           if m["name"].startswith("l255.")}
    old = {m["name"]: m for m in manifest.layer_metrics(OLD_CELL)
           if "l255." + m["name"] in new}
    assert len(new) == len(old) == 8
    assert set(new) == {"l255." + n for n in old}
    for name, m in new.items():
        assert m["workloads"] == [CELL, OLD_CELL]
        twin = old[name[len("l255."):]]
        assert twin["workloads"] == [OLD_CELL]
        assert m["reader"] in readers.READERS
        for k in ("reader", "args", "layer", "unit", "better", "source",
                  "moves"):
            assert m[k] == twin[k], (name, k)
        assert os.path.isfile(os.path.join(d, name + ".json"))


def test_the_configuration_states_the_source_settings_and_its_cut():
    cell = manifest.workload(CELL)
    config = manifest.config(cell["config"])
    p = config["params"]
    assert (p["num_leaves"], p["learning_rate"], p["min_data_in_leaf"],
            p["min_sum_hessian_in_leaf"]) == (255, 0.1, 0, 100)
    assert config["reduced"] == ["train_rows"]
    assert config["train_rows"] == 40960 * 2048
    old = manifest.config("airline13-l31")
    assert config["data"] == old["data"]       # the source's rows, uncut
    for k in ("tree_grow_policy", "tpu_wave_width", "tpu_wave_gain_ratio",
              "tpu_wave_strict_tail", "max_bin", "objective"):
        assert p[k] == old["params"][k]
    t, t_old = cell["traffic_params"], \
        manifest.workload(OLD_CELL)["traffic_params"]
    assert {k: v for k, v in t.items() if k != "limits"} == \
        {k: v for k, v in t_old.items() if k != "limits"}
    assert set(t["limits"]) == {"leaf_value_gap", "leaf_count_gap",
                                "split_gain_loss"}
