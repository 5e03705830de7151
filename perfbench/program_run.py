"""A traced run of one cell that keeps what it recorded: a builder's tool,
never run by the benchmark.

    python3 -m perfbench.program_run --workload <cell> --seed <n> \\
        --seconds <s> [--save DIR] [--population-seed <n>]

It is `perfbench.run ... --trace 1`, whose job records the program itself
(`jobs/train.ProgramRecord`) and whose line carries the per-layer metrics,
the `program_metrics` and the breakdown, with two additions:

  - `--save DIR` keeps the trace (gzipped) and the job's record of the
    program (`program.json`: `program`, `counters`, `units_in_window`,
    `shape`, `memory_peak_bytes`) for reduction off the chip
    (`reduce_saved`, which is `run.per_layer` again) and for test fixtures;
  - the rows that `hist.useful_row_pct` counted over the window
    (`grow.hist_rows_needed`) are checked against a count from the leaf
    counts of the window's own trees, every one of them, dumped before the
    program's state is freed.

Every other argument of `perfbench.run` is handed on to it.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

from . import manifest

#: the fields of the job's result that `--save` keeps
SAVED = ("program", "counters", "units_in_window", "shape",
         "memory_peak_bytes")


def reduce_saved(directory: str, cell: str, device_kind: str = "TPU v5 lite",
                 bench_dir: str = manifest.HERE) -> Dict[str, Any]:
    """`run.per_layer` over what `--save` kept."""
    from . import run
    with open(os.path.join(directory, "program.json")) as f:
        result = json.load(f)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "saved.xplane.pb")
        with gzip.open(os.path.join(directory, "trace.xplane.pb.gz")) as f, \
                open(path, "wb") as g:
            shutil.copyfileobj(f, g)
        return run.per_layer(cell, dict(result, trace_file=path),
                             device_kind, bench_dir)


def save(result: dict, directory: str) -> None:
    os.makedirs(directory, exist_ok=True)
    with open(result["trace_file"], "rb") as f, gzip.open(os.path.join(
            directory, "trace.xplane.pb.gz"), "wb") as g:
        shutil.copyfileobj(f, g)
    with open(os.path.join(directory, "program.json"), "w") as f:
        json.dump({k: result[k] for k in SAVED}, f)


def rows_needed_by_counts(tree) -> float:
    """`Tree.hist_rows_needed` again, from a dumped tree's leaf counts
    alone (`reference` TreeArrays): the root's rows and at every split the
    smaller child's."""
    ni = len(tree.left)
    if not ni:
        return float(tree.leaf_count[0])
    total = [0.0] * ni

    def count(ref: int) -> float:
        return total[ref] if ref >= 0 else float(tree.leaf_count[~ref])

    needed = 0.0
    for i in reversed(range(ni)):       # a child's index is above its parent's
        left, right = count(int(tree.left[i])), count(int(tree.right[i]))
        total[i] = left + right
        needed += min(left, right)
    return needed + total[0]


def main(argv: Optional[List[str]] = None,
         hooks: Optional[SimpleNamespace] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--save", default="")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--bench-dir", default=manifest.HERE)
    own, rest = ap.parse_known_args(argv)
    rest += ["--workload", own.workload, "--bench-dir", own.bench_dir,
             "--trace", "1"]
    from . import run
    hooks = hooks or run.default_hooks()

    cell = manifest.workload(own.workload, own.bench_dir)
    warmup = int(cell["traffic_params"]["warmup_rounds"])
    ref = manifest.load_module("reference", manifest.config(
        cell["config"], own.bench_dir)["reference"])
    by_counts: List[float] = []
    made: List[Any] = []            # the run's booster, until its trees are read
    make, alter = hooks.make_booster, hooks.alter_trees

    def make_booster(lgb, params, ds):
        made.append(make(lgb, params, ds))
        return made[-1]

    def alter_trees(trees):
        # every tree made so far, not the few the reference follows; the
        # booster is let go here, before the job frees the program's state
        dumped = made.pop().dump_model()["tree_info"]
        by_counts.extend(rows_needed_by_counts(ref.tree_from_dump(t))
                         for t in dumped)
        alter(trees)

    def on_result(result):
        if own.save and result.get("trace_file"):
            save(result, own.save)
        window = by_counts[warmup:warmup + int(result["attempted"])]
        counted = result["counters"].get("grow.hist_rows_needed", 0)
        run.say(f"program: grow.hist_rows_needed over the window = "
                f"{counted:.0f}; from the dumped leaf counts of "
                f"{len(window)} tree(s) = {sum(window):.0f}")

    hooks.make_booster, hooks.alter_trees = make_booster, alter_trees
    hooks.on_result = on_result
    return run.main(rest, hooks=hooks)


if __name__ == "__main__":
    sys.exit(main())
