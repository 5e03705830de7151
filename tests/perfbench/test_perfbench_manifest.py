"""BENCHMARK.json and the files it names keep the contract's rules, and a
configuration, a cell or a per-layer metric is added by files alone."""
import json
import os
import shutil

import pytest

from perfbench import manifest, readers

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "bench")


def test_the_manifest_is_sound():
    assert manifest.problems() == []


def test_every_cell_finds_its_files_by_name():
    b = manifest.benchmark()
    for w in b["workloads"]:
        cell = manifest.workload(w["name"])
        config = manifest.config(cell["config"])
        assert config["name"] == w["config"]
        manifest.load_module("jobs", cell["job"])
        manifest.load_module("generators", config["generator"])
        manifest.load_module("reference", config["reference"])
        mine = {m["name"] for m in manifest.layer_metrics(w["name"])}
        listed = {m["name"] for m in b["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])}
        assert mine == listed
    for m in b["per_layer"]:
        f = json.load(open(os.path.join(manifest.HERE, "layer_metrics",
                                        m["name"] + ".json")))
        assert f["reader"] in readers.READERS


def test_no_cell_or_config_name_in_code():
    b = manifest.benchmark()
    names = [w["name"] for w in b["workloads"]] + \
        [c["name"] for c in b["configs"]]
    for dirpath, _, files in os.walk(manifest.HERE):
        for fn in files:
            if fn.endswith(".py"):
                src = open(os.path.join(dirpath, fn)).read()
                for n in names:
                    assert n not in src, (fn, n)


@pytest.fixture()
def checkout(tmp_path):
    """A copy of BENCHMARK.json and perfbench/'s data files to break."""
    root = tmp_path / "root"
    bench = root / "perfbench"
    bench.mkdir(parents=True)
    for d in ("configs", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(manifest.HERE, d), bench / d)
    shutil.copy(os.path.join(manifest.HERE, "peaks.json"), bench)
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    return root, bench


def _edit(root, fn):
    b = json.load(open(root / "BENCHMARK.json"))
    fn(b)
    json.dump(b, open(root / "BENCHMARK.json", "w"))


BREAKS = {
    "unit with a space": lambda b: b["end_to_end"][0].update(
        unit="rounds per s"),
    "name with a slash": lambda b: b["per_layer"][0].update(name="a/b"),
    "name too long": lambda b: b["workloads"][0].update(name="x" * 65),
    "greek unit": lambda b: b["per_layer"][0].update(unit="µs"),
    "bound over a tenth": lambda b: b["end_to_end"][0].update(bound=0.2),
    "moves no end-to-end metric": lambda b: b["per_layer"][0].update(
        moves="tokens_per_s"),
    "moves a metric the cell does not report": lambda b: (
        b["end_to_end"][0].update(workloads=[])),
    "extra key on a metric": lambda b: b["per_layer"][0].update(why="x"),
    "unknown source": lambda b: b["per_layer"][0].update(source="guess"),
    "why over 200 characters": lambda b: b["workloads"][0].update(
        why="y" * 201),
    "cell without a file": lambda b: b["workloads"].append(
        dict(b["workloads"][0], name="nowhere.train", traffic="other")),
    "config used by no cell": lambda b: b["configs"].append(
        dict(b["configs"][0], name="orphan")),
    "run_seconds over the limit": lambda b: b.update(run_seconds=52),
    "no setup_s": lambda b: b.update(end_to_end=[
        m for m in b["end_to_end"] if m["name"] != "setup_s"]),
    "config file outside paths": lambda b: b["configs"][0].update(
        file="bench.py"),
    "reduced differs from the file": lambda b: b["configs"][0].update(
        reduced=[]),
}


@pytest.mark.parametrize("what", sorted(BREAKS))
def test_a_broken_manifest_is_caught(checkout, what):
    root, bench = checkout
    assert manifest.problems(str(root), str(bench)) == []
    _edit(root, BREAKS[what])
    assert manifest.problems(str(root), str(bench)) != [], what


NO_POPULATION = {
    "left out": lambda data: data.pop("population_seed"),
    "a string": lambda data: data.update(population_seed="20260930"),
    "negative": lambda data: data.update(population_seed=-1),
    "a fraction": lambda data: data.update(population_seed=1.5),
    "true": lambda data: data.update(population_seed=True),
}


@pytest.mark.parametrize("what", sorted(NO_POPULATION))
def test_a_config_without_a_population_is_refused_by_name(checkout, what):
    """The training rows are the configuration's: a file that names no
    `data.population_seed` is an error, never a fall-back to `--seed`."""
    root, bench = checkout
    name = manifest.benchmark()["configs"][0]["name"]
    assert manifest.config(name, str(bench))["data"]["population_seed"] >= 0
    path = bench / "configs" / (name + ".json")
    body = json.load(open(path))
    NO_POPULATION[what](body["data"])
    json.dump(body, open(path, "w"))
    with pytest.raises(ValueError, match=f"config {name}: "
                                         "data.population_seed"):
        manifest.config(name, str(bench))
    assert any(name in p and "population_seed" in p
               for p in manifest.problems(str(root), str(bench)))


def test_every_config_file_names_its_population():
    """The parked configurations too: the PR that brings one in inherits it."""
    d = os.path.join(manifest.HERE, "configs")
    names = [fn[:-5] for fn in sorted(os.listdir(d)) if fn.endswith(".json")]
    assert len(names) >= 2
    for name in names:
        assert manifest.no_population(manifest.config(name)) == ""


def test_with_population_replaces_the_seed_and_nothing_else():
    config = manifest.config(manifest.benchmark()["configs"][0]["name"])
    kept = json.dumps(config, sort_keys=True)
    other = manifest.with_population(config, 77)
    assert other["data"]["population_seed"] == 77
    assert json.dumps(config, sort_keys=True) == kept       # not in place
    other["data"]["population_seed"] = config["data"]["population_seed"]
    assert json.dumps(other, sort_keys=True) == kept


def test_a_second_four_chip_cell_of_two_is_caught(checkout):
    """At most a quarter of the cells, and one always, may ask for four."""
    root, bench = checkout
    first = manifest.benchmark()["workloads"][0]
    for src, name in (
            (os.path.join(manifest.HERE, "workloads", first["name"] + ".json"),
             first["name"]),
            (os.path.join(FIXTURES, "workloads", "tiny13-l31.train.json"),
             "tiny13-l31.train")):
        w = json.load(open(src))
        w.update(chips=4, config=first["config"])
        json.dump(w, open(bench / "workloads" / (name + ".json"), "w"))
    _edit(root, lambda b: b["workloads"][0].update(chips=4))
    assert manifest.problems(str(root), str(bench)) == []
    _edit(root, lambda b: b["workloads"].append(
        dict(b["workloads"][0], name="tiny13-l31.train", traffic="other")))
    assert "too many four-chip cells" in manifest.problems(str(root),
                                                            str(bench))


def test_config_cell_and_metric_are_added_by_files_alone(checkout):
    """A later PR adds files and manifest entries, and edits nothing."""
    root, bench = checkout
    before = {p: open(os.path.join(dp, p)).read()
              for dp, _, fs in os.walk(bench) for p in fs}
    shutil.copy(os.path.join(FIXTURES, "configs", "tiny13-l31.json"),
                bench / "configs")
    shutil.copy(os.path.join(FIXTURES, "workloads", "tiny13-l31.train.json"),
                bench / "workloads")
    cell = "tiny13-l31.train"
    metric = {"name": "hist.wave_time_pct", "layer": "histogram kernel",
              "unit": "%", "better": "lower", "source": "device_trace",
              "moves": "train_rounds_per_s", "workloads": [cell],
              "reader": "scope_share",
              "args": {"name": "^pallas_histogram_multi"}}
    json.dump(metric, open(bench / "layer_metrics" /
                           "hist.wave_time_pct.json", "w"))

    def add(b):
        b["configs"].append({
            "name": "tiny13-l31", "source": "test fixture",
            "file": "perfbench/configs/tiny13-l31.json",
            "reduced": ["train_rows"], "why": "fixture"})
        b["workloads"].append({"name": cell, "config": "tiny13-l31",
                               "traffic": "train-tiny", "chips": 1,
                               "why": "fixture"})
        b["per_layer"].append({k: metric[k] for k in (
            "name", "unit", "better", "source", "layer", "moves",
            "workloads")})
    _edit(root, add)
    assert manifest.problems(str(root), str(bench)) == []
    assert manifest.workload(cell, str(bench))["config"] == "tiny13-l31"
    assert [m["name"] for m in manifest.layer_metrics(cell, str(bench))] \
        == ["hist.wave_time_pct"]
    # the cells that were there read exactly the metrics they read before
    old = manifest.benchmark()["workloads"][0]["name"]
    assert {m["name"] for m in manifest.layer_metrics(old, str(bench))} \
        == {m["name"] for m in manifest.layer_metrics(old)}
    after = {p: open(os.path.join(dp, p)).read()
             for dp, _, fs in os.walk(bench) for p in fs}
    assert all(after[p] == before[p] for p in before)
