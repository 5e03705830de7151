"""The cell `istellax220-lgbrank-l31.train`: LightGBM's lambdarank on
Istella-X's shape, added by data files, one generator
(`generators/ltr_codes.py`), one job (`jobs/train_rank.py`), one
reference module (`reference/gbdt_rank.py`) and seventeen `rank.*` metric
files over existing readers.

The CPU stand-in is the cell's own files cut to 20,480 rows in 40 queries
x 24 columns, through `perfbench.run`'s hooks: `correct` against
`gbdt_rank`, and not correct for the bfloat16 control and the faults the
chip readings plant (`scripts/lgbrank_readings.py`): the previous pair
rule, documents shuffled between queries; and one query's documents left
out, which only the leaf counts' limit of 0 has to catch.
"""
import ast
import contextlib
import io
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from perfbench import check, manifest, readers, run
from perfbench.generators import ltr_codes
from perfbench.jobs import train_rank
from perfbench.reference import gbdt_rank

CELL = "istellax220-lgbrank-l31.train"
CONFIG = "istellax220-lgbrank-l31"
ROWS, QUERIES, COLUMNS = 20480, 40, 24
SEED = 4100000013


def small_bench(tmp):
    """The cell's files, cut to ROWS rows in QUERIES queries of COLUMNS."""
    for d in ("configs", "workloads", "layer_metrics"):
        os.makedirs(tmp / d)
    with open(os.path.join(manifest.HERE, "peaks.json")) as f:
        (tmp / "peaks.json").write_text(f.read())
    config = manifest.config(CONFIG)
    config.update(train_rows=ROWS, train_queries=QUERIES)
    data = config["data"]
    data.update(queries=QUERIES, informative=12, pairs=4)
    data["columns"] = dict(data["columns"], count=COLUMNS,
                           narrow=data["columns"]["narrow"][:2])
    (tmp / "configs" / (CONFIG + ".json")).write_text(json.dumps(config))
    cell = manifest.workload(CELL)
    cell["traffic_params"]["holdout_queries"] = 8
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
    rows = ltr_codes.make(SEED, config["data"], ROWS, 1)
    traffic = cell["traffic_params"]
    kw = {"n_check": int(traffic["check_nodes"]), "seed": SEED}
    readings = gbdt_rank.follow(rows["codes"], rows["label"],
                                rows["query_boundaries"], trees,
                                config["params"], **kw)
    return {"line": line, "trees": trees, "rows": rows, "log": log,
            "readings": readings, "params": config["params"],
            "limits": traffic["limits"], "kw": kw}


def test_the_program_is_correct_against_the_ranking_reference(driven):
    line = driven["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rounds_per_s", "holdout_auc",
                                    "setup_s"}
    assert 0.5 < line["metrics"]["holdout_auc"]["value"] < 1
    assert set(line["compared"]) == {"leaf_value_gap", "leaf_count_gap",
                                     "split_gain_loss"}
    assert line["compared"]["leaf_value_gap"]["value"] < 1e-5
    assert line["compared"]["leaf_count_gap"]["value"] == 0
    assert line["compared"]["split_gain_loss"]["value"] < 1e-7
    assert len(driven["trees"]) == 2
    assert "NDCG@1,3,5" in driven["log"]
    assert '"rank.pair_slots"' in driven["log"]


def _shuffled(qb, n, seed=0):
    """Rows of each pair of neighbouring queries pooled and dealt back in
    a random order: documents in the wrong queries."""
    rng = np.random.default_rng(seed)
    perm = np.arange(n)
    for q in range(0, len(qb) - 2, 2):
        lo, hi = qb[q], qb[q + 2]
        perm[lo:hi] = lo + rng.permutation(hi - lo)
    return perm


def _left_out(rows, q=1):
    """Rows, labels and boundaries with query `q`'s documents left out:
    rows missing from the sums and the counts."""
    qb = np.asarray(rows["query_boundaries"])
    lo, hi = qb[q], qb[q + 1]
    keep = np.r_[0:lo, hi:len(rows["label"])]
    return (rows["codes"][:, keep], rows["label"][keep],
            np.concatenate([qb[:q + 1], qb[q + 2:] - (hi - lo)]))


@pytest.mark.parametrize("fault", ["control_bf16", "previous_rule",
                                   "shuffled_queries", "query_left_out"])
def test_each_fault_fails_the_cells_limits(driven, fault):
    trees, readings, limits, rows = (driven[k] for k in
                                     ("trees", "readings", "limits", "rows"))
    assert check.verdict(check.compare(check.stated_of(trees), readings),
                         limits)
    kw = dict(driven["kw"])
    data = rows["codes"], rows["label"], rows["query_boundaries"]
    if fault == "control_bf16":
        kw["dtype"] = jnp.bfloat16
    elif fault == "previous_rule":
        kw["rule"] = "per_doc_norm"
    elif fault == "shuffled_queries":
        kw["rows"] = _shuffled(rows["query_boundaries"], len(rows["label"]))
    else:
        data = _left_out(rows)
    other = gbdt_rank.follow(*data, trees, driven["params"], **kw)
    numbers = check.compare(check.stated_by(other, trees), readings)
    assert not check.verdict(numbers, limits), numbers
    if fault == "query_left_out":
        # a leaf's count is whole rows: one row missing reads above 0
        assert numbers["leaf_count_gap"] > limits["leaf_count_gap"] == 0
    else:
        assert numbers["leaf_count_gap"] == 0
        assert numbers["leaf_value_gap"] > 2 * limits["leaf_value_gap"]


def test_the_generator_keeps_the_stated_shape():
    config = manifest.config(CONFIG)
    data = config["data"]
    lengths, _ = ltr_codes.query_lengths(data, config["train_rows"])
    assert len(lengths) == config["train_queries"] == 5000
    assert lengths.sum() == config["train_rows"] == 6541 * 2048
    assert lengths.min() >= 50 and lengths.max() <= 12000
    assert config["train_rows"] / len(lengths) == pytest.approx(2679, 0.01)
    card = ltr_codes.cardinalities(data)
    assert len(card) == 220 and sum(c == 255 for c in card) == 200
    assert sorted(c for c in card if c < 255) == sorted(
        data["columns"]["narrow"])
    rows = ltr_codes.make(SEED, dict(data, queries=6), 6 * 2048, 3)
    qb, hqb = rows["query_boundaries"], rows["holdout_query_boundaries"]
    share = np.bincount(rows["label"].astype(int), minlength=5) / qb[-1]
    np.testing.assert_allclose(share, data["label_shares"], atol=0.002)
    for lo, hi in zip(qb[:-1], qb[1:]):      # every grade in every query
        assert set(np.unique(rows["label"][lo:hi])) == {0, 1, 2, 3, 4}
    assert len(hqb) == 4 and hqb[-1] == rows["holdout_codes"].shape[1]
    again = ltr_codes.make(SEED + 1, dict(data, queries=6), 6 * 2048, 3)
    np.testing.assert_array_equal(again["codes"], rows["codes"])
    assert not np.array_equal(again["holdout_label"], rows["holdout_label"])


def test_query_auc_ties_and_one_class_queries():
    label = np.asarray([1, 0, 0, 2, 0, 0, 0, 3, 1], np.float32)
    score = np.asarray([2, 1, 1, 0, 0, 5, 5, 1, 1], np.float64)
    qb = np.asarray([0, 3, 5, 7, 9])
    # q0: 1.0; q1: 0.5 (tie at the mean rank); q2: no positive; q3: one class
    assert train_rank.query_auc(label, score, qb) == pytest.approx(0.75)


# ----------------------------------------------------------- the manifest
def test_one_configuration_one_cell_ten_metric_files():
    assert manifest.problems() == []
    b = manifest.benchmark()
    assert b["configs"][-1]["name"] == CONFIG
    assert b["workloads"][-1]["name"] == CELL
    assert b["workloads"][-1]["chips"] == 1
    config = manifest.config(CONFIG)
    assert config["reduced"] == b["configs"][-1]["reduced"] == [
        "train_rows", "train_queries"]
    assert config["published"] == {"train_rows": 26791447,
                                   "train_queries": 10000}
    cell = manifest.workload(CELL)
    assert cell["job"] == "train_rank" and config["reference"] == "gbdt_rank"
    names = [m["name"] for m in b["per_layer"]]
    at = [i for i, n in enumerate(names) if n.startswith("rank.")]
    assert at == list(range(len(names) - len(at), len(names)))
    ten = ["rank." + n for n in TWINS] + ["rank.grad.time_pct",
                                         "rank.pair_fill_pct"]
    assert names[at[0]:at[0] + 10] == ten
    for m in manifest.layer_metrics(CELL):
        if m["name"] in ten:
            assert m["workloads"] == [CELL]
            assert m["reader"] in readers.READERS
            assert m["moves"] == "train_rounds_per_s"


#: the metrics of the cells before this one that the first eight of the
#: cell's ten read again
TWINS = ["entry.compiles_in_window", "round.outside_grower_pct",
         "grower.passes_per_tree", "grower.other_pct", "hist.time_pct",
         "hist_kernel_roofline", "device.idle_pct", "device.peak_hbm_pct"]
SETUP_TWINS = ["setup.import_s", "setup.booster_s", "setup.probe_s",
               "setup.place_s", "setup.trace_s", "setup.lower_s",
               "hist.c128_pass_pct"]


def test_the_set_up_and_c128_metrics_have_twins_on_the_cell():
    """The six `setup.*` metrics and `hist.c128_pass_pct` list the cells
    before this one; their `rank.*` twins read the same counters and
    spans on this cell, last in `per_layer`."""
    names = [m["name"] for m in manifest.benchmark()["per_layer"]]
    assert names[-len(SETUP_TWINS):] == ["rank." + n for n in SETUP_TWINS]
    mine = {m["name"]: m for m in manifest.layer_metrics(CELL)}
    for n in SETUP_TWINS:
        with open(os.path.join(manifest.HERE, "layer_metrics",
                               n + ".json")) as f:
            base = json.load(f)
        twin = mine["rank." + n]
        assert CELL not in base["workloads"] and twin["workloads"] == [CELL]
        assert {k: v for k, v in twin.items() if k not in (
            "name", "workloads")} == {k: v for k, v in base.items() if k
                                      not in ("name", "workloads")}


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(manifest.HERE, "reference", "gbdt_rank.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(("." * node.level) + (node.module or ""))
    assert not any("lightgbm" in n for n in names), names
    assert names <= {"__future__", "os", "concurrent.futures", "typing",
                     "numpy", "jax", "jax.numpy", ".", ".gbdt"}
