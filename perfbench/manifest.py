"""Find a cell's files by name, and check the manifest against its rules.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own under `perfbench/`:

  workloads/<cell>.json        config, chips, job, traffic parameters, why
  configs/<config>.json        source, parameters, shape, reduced, assumed
  layer_metrics/<metric>.json  layer, unit, moves, workloads, reader + args
  program_metrics/<metric>.json  the same, for a metric of the program's
                               own record that `BENCHMARK.json` cannot list
                               yet (PERF.md, Open questions): the traced
                               run reports it under `program_metrics`

`BENCHMARK.json` at the root of the checkout names them.  No cell, config
or metric name appears in code.
"""
from __future__ import annotations

import importlib
import json
import os
import re
from typing import Any, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def workload(name: str, bench_dir: str = HERE) -> dict:
    if not NAME.match(name):
        raise ValueError(f"not a workload name: {name!r}")
    return _load(os.path.join(bench_dir, "workloads", name + ".json"))


def no_population(body: dict) -> str:
    """Why a configuration's file names no training population, or ""."""
    seed = body.get("data", {}).get("population_seed")
    if isinstance(seed, int) and not isinstance(seed, bool) and seed >= 0:
        return ""
    return ("data.population_seed is missing or no whole number: the "
            "training rows are the configuration's, the same in every run, "
            "and --seed is no fall-back for them")


def config(name: str, bench_dir: str = HERE) -> dict:
    if not NAME.match(name):
        raise ValueError(f"not a config name: {name!r}")
    body = _load(os.path.join(bench_dir, "configs", name + ".json"))
    why = no_population(body)
    if why:
        raise ValueError(f"config {name}: {why}")
    return body


def with_population(config: dict, population_seed: int) -> dict:
    """The configuration trained on another population's rows (a builder's
    `--population-seed`; the driver's runs never ask for it)."""
    data = dict(config["data"], population_seed=int(population_seed))
    return dict(config, data=data)


#: TEMPORARY, the one place that says so (PERF.md 7.1): the folder of the
#: program's metrics that `BENCHMARK.json` cannot list while tests outside
#: the benchmark pin its `per_layer` at 34 entries.  The change that lifts
#: the pins moves these files into `layer_metrics/`, lists them in
#: `per_layer`, and deletes in the same change this constant, the `folder`
#: argument below, `run.per_layer`'s second `_read` and the line's
#: `program_metrics` key.  Nothing else belongs to this second channel.
PROGRAM_METRICS = "program_metrics"


def layer_metrics(cell: str, bench_dir: str = HERE,
                  folder: str = "layer_metrics") -> List[dict]:
    """Every per-layer metric whose file lists the cell, by name."""
    d = os.path.join(bench_dir, folder)
    if not os.path.isdir(d):
        return []
    out = []
    for fn in sorted(os.listdir(d)):
        if fn.endswith(".json"):
            m = _load(os.path.join(d, fn))
            if cell in m.get("workloads", []):
                out.append(m)
    return out


def peaks(device_kind: str, bench_dir: str = HERE) -> dict:
    table = _load(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json; "
                       "an unknown device is an error, not a default")
    return table["devices"][device_kind]


def load_module(kind: str, name: str):
    """`perfbench/<kind>/<name>.py`, chosen by a name in a data file."""
    if not re.match(r"^[A-Za-z_][A-Za-z0-9_]*$", name):
        raise ValueError(f"not a module name: {name!r}")
    return importlib.import_module(f"perfbench.{kind}.{name}")


# ---------------------------------------------------------------- validation
def problems(root: str = ROOT, bench_dir: str = HERE) -> List[str]:
    """Every way in which BENCHMARK.json and the files it names break the
    contract's rules that can be checked without a run; [] when sound."""
    bad: List[str] = []
    b = benchmark(root)

    def name_ok(x: Any, what: str) -> None:
        if not isinstance(x, str) or not NAME.match(x):
            bad.append(f"{what}: {x!r} is not a name")

    def line_ok(x: Any, what: str) -> None:
        if not isinstance(x, str) or not 1 <= len(x) <= 200 \
                or "\n" in x or "\t" in x:
            bad.append(f"{what}: not one line of 1 to 200 characters")

    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(b) != want:
        bad.append(f"keys {sorted(set(b) ^ want)} missing or unknown")
        return bad
    for w in b["command"]:
        line_ok(w, "command word")
    if not 1 <= int(b["run_seconds"]) <= 51:
        bad.append("run_seconds outside 1..51")
    under = tuple(p.rstrip("/") + "/" for p in b["paths"])

    cfg_names = set()
    for c in b["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys")
            continue
        name_ok(c["name"], "config")
        line_ok(c["source"], f"config {c['name']} source")
        line_ok(c["why"], f"config {c['name']} why")
        cfg_names.add(c["name"])
        if not c["file"].startswith(under):
            bad.append(f"config {c['name']}: file outside paths")
        path = os.path.join(root, c["file"])
        if not os.path.isfile(path):
            bad.append(f"config {c['name']}: {c['file']} not found")
            continue
        body = _load(path)
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced")
        if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
            bad.append(f"config {c['name']}: reduced differs from its file")
        if len(c["reduced"]) > 16:
            bad.append(f"config {c['name']}: more than 16 reduced keys")
        why = no_population(body)
        if why:
            bad.append(f"config {c['name']}: {why}")

    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {}
    pairs = set()
    for w in b["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys")
            continue
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        line_ok(w["why"], f"workload {w['name']} why")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips")
        if w["config"] not in cfg_names:
            bad.append(f"workload {w['name']}: unknown config")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {w['name']}: config and traffic repeated")
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
        try:
            f = workload(w["name"], bench_dir)
        except (OSError, ValueError) as e:
            bad.append(f"workload {w['name']}: file not found ({e})")
            continue
        for k in ("config", "chips"):
            if f.get(k) != w[k]:
                bad.append(f"workload {w['name']}: {k} differs from its file")
        try:
            load_module("jobs", f["job"])
        except (ImportError, ValueError, KeyError) as e:
            bad.append(f"workload {w['name']}: job ({e})")
    if len(cells) != len(b["workloads"]):
        bad.append("workload names repeat")
    if not {c for c in cfg_names} <= {w["config"] for w in b["workloads"]}:
        bad.append("a config is used by no cell")
    if sum(w["chips"] == 4 for w in b["workloads"]) > max(
            1, len(b["workloads"]) // 4):
        bad.append("too many four-chip cells")

    def reports(metric: dict) -> set:
        return set(metric.get("workloads", cells))

    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for m in b["end_to_end"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound",
                                      "source"}:
            bad.append(f"end_to_end {m.get('name')}: keys")
            continue
        name_ok(m["name"], "end_to_end")
        if not UNIT.match(m["unit"]):
            bad.append(f"end_to_end {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"end_to_end {m['name']}: better")
        if not 0 < float(m["bound"]) <= 0.1:
            bad.append(f"end_to_end {m['name']}: bound")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end_to_end {m['name']}: source")
        if not reports(m) <= set(cells):
            bad.append(f"end_to_end {m['name']}: unknown cell")
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    if len(set(names)) != len(names):
        bad.append("metric names repeat")
    for m in b["per_layer"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "source",
                                      "layer", "moves"}:
            bad.append(f"per_layer {m.get('name')}: keys")
            continue
        name_ok(m["name"], "per_layer")
        line_ok(m["layer"], f"per_layer {m['name']} layer")
        if not UNIT.match(m["unit"]):
            bad.append(f"per_layer {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"per_layer {m['name']}: better")
        if m["source"] not in SOURCES:
            bad.append(f"per_layer {m['name']}: source")
        if m["moves"] not in e2e:
            bad.append(f"per_layer {m['name']}: moves {m['moves']!r}")
            continue
        if not reports(m) <= reports(e2e[m["moves"]]):
            bad.append(f"per_layer {m['name']}: a listed cell does not "
                       f"report {m['moves']}")
        path = os.path.join(bench_dir, "layer_metrics", m["name"] + ".json")
        if not os.path.isfile(path):
            bad.append(f"per_layer {m['name']}: no file in layer_metrics/")
            continue
        f = _load(path)
        for k in ("unit", "layer", "moves", "source", "better"):
            if f.get(k) != m[k]:
                bad.append(f"per_layer {m['name']}: {k} differs from its file")
        if sorted(f.get("workloads", [])) != sorted(reports(m)):
            bad.append(f"per_layer {m['name']}: workloads differ from its file")
    for name, w in cells.items():
        mine = [m for m in b["end_to_end"] if name in reports(m)]
        if not any(m["name"] == "setup_s" for m in mine) or len(mine) < 2:
            bad.append(f"workload {name}: needs setup_s and one more metric")
        if not any(name in reports(m) for m in b["per_layer"]):
            bad.append(f"workload {name}: no per-layer metric")
    return bad
