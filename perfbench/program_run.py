"""A run of one cell with the program's own record on: a builder's tool,
never run by the benchmark.

    python3 -m perfbench.program_run --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--save DIR] [--population-seed <n>]

It is `perfbench.run` with three additions, which are what a `benchmark` PR
would write into `jobs/train.py`, `run.py` and `readers.py` to make the
program's metrics part of the driver's traced run (no other PR may edit
those files; PERF.md, Open questions, lists the lines):

  - before anything of the program runs, a `MemorySink` and the compile
    listener are attached to `lightgbm_tpu.telemetry`, so the program
    records its span tree of every round, and its counters are read when
    each round closes.  (With `--trace 0` this is all that differs from
    `perfbench.run`: the cost of the recording is the difference.)
  - the traced run's per-layer metrics gain those of
    `perfbench/program_metrics/*.json` that list the cell, read by
    `program_readers.py`, and `breakdown.idle_gaps` names each gap by the
    program span it fell under; `breakdown.host_gap_ms_by_span` and
    `breakdown.phase_s` are added.
  - in a traced run the compile cache's keys include the programs'
    metadata, so a cache that an older checkout filled cannot hand back
    executables whose device events carry that checkout's scope names (JAX
    leaves metadata out of the key by default; the first traced run on a
    cache therefore compiles every program again).

`--save DIR` keeps the trace (gzipped) and the program's record
(`program.json`) for reduction off the chip (`reduce_saved`) and for test
fixtures.  `--population-seed`, like every other argument of
`perfbench.run`, is handed on to it.  After the run the rows that
`hist.useful_row_pct` counted over the window are checked against a count
from the leaf counts of the window's own trees, every one of them, dumped
before the program's state is freed.
"""
from __future__ import annotations

import argparse
import gzip
import json
import math
import os
import shutil
import sys
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

from . import manifest, program_readers, trace as T

METRICS_DIR = "program_metrics"


def program_metrics(cell: str, bench_dir: str = manifest.HERE) -> List[dict]:
    """The program's per-layer metrics whose file lists the cell."""
    d = os.path.join(bench_dir, METRICS_DIR)
    if not os.path.isdir(d):
        return []
    found = (manifest._load(os.path.join(d, fn))
             for fn in sorted(os.listdir(d)) if fn.endswith(".json"))
    return [m for m in found if cell in m.get("workloads", [])]


def reduce(cell: str, trace: Optional[T.Trace], trace_file: Optional[str],
           program: dict, units: dict, shape: dict,
           bench_dir: str = manifest.HERE) -> Dict[str, Any]:
    """The program's metrics of one traced run, and its breakdown."""
    ctx = {"trace": trace, "trace_file": trace_file, "program": program,
           "units": units, "shape": shape}
    metrics = {}
    for m in program_metrics(cell, bench_dir):
        v = program_readers.READERS[m["reader"]](ctx, m.get("args", {}))
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out: Dict[str, Any] = {"metrics": metrics, "breakdown": {}}
    if trace is not None:
        out["breakdown"] = {
            "idle_gaps": [[k, v] for k, v in
                          program_readers.idle_gaps(ctx)],
            "host_gap_ms_by_span": {
                k: v / 1e6 / max(units.get("rounds", 0), 1) for k, v in
                sorted(program_readers.gap_ns_by_span(ctx).items())},
            "phase_s": program_readers.phase_seconds(ctx, "^jit_grow$")}
    return out


def reduce_saved(directory: str, cell: str,
                 bench_dir: str = manifest.HERE) -> Dict[str, Any]:
    """`reduce` over what `--save` kept."""
    import tempfile
    with open(os.path.join(directory, "program.json")) as f:
        saved = json.load(f)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "saved.xplane.pb")
        with gzip.open(os.path.join(directory, "trace.xplane.pb.gz")) as f, \
                open(path, "wb") as g:
            shutil.copyfileobj(f, g)
        return reduce(cell, T.load(path), path, saved["program"],
                      saved["units"], saved["shape"], bench_dir)


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
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    own, rest = ap.parse_known_args(argv)
    rest += ["--workload", own.workload, "--bench-dir", own.bench_dir,
             "--trace", str(own.trace)]
    from . import run
    hooks = hooks or run.default_hooks()

    if manifest.ROOT not in sys.path:
        sys.path.insert(0, manifest.ROOT)
    import jax
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.telemetry.recorder import install_compile_listener
    if own.trace:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)

    def counters() -> Dict[str, float]:
        snap = telemetry.REGISTRY.snapshot()
        return {**snap["counters"], **snap["gauges"]}

    class Record(telemetry.MemorySink):
        """The program's events, and its counters as each round closes."""

        def __init__(self):
            super().__init__()
            self.at_round_end: List[Dict[str, float]] = []

        def emit(self, event):
            super().emit(event)
            if event.get("ev") == "span" and event["name"] == "train.chunk":
                self.at_round_end.append(counters())

    record = telemetry.TRACER.add_sink(Record())
    install_compile_listener()
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

    hooks.make_booster, hooks.alter_trees = make_booster, alter_trees
    inner = run.per_layer

    def per_layer(cell_name, result, device_kind, bench_dir):
        out = inner(cell_name, result, device_kind, bench_dir)
        ends = record.at_round_end
        last = min(warmup + int(result["attempted"]), len(ends)) - 1
        program = {
            "spans": [e for e in record.events if e.get("ev") == "span"],
            "counters_start": ends[warmup - 1] if 0 < warmup <= len(ends)
            else {},
            "counters_end": ends[last] if last >= 0 else {}}
        path = result.get("trace_file")
        units, shape = result["units_in_window"], result["shape"]
        if own.save and path:
            os.makedirs(own.save, exist_ok=True)
            with open(path, "rb") as f, gzip.open(os.path.join(
                    own.save, "trace.xplane.pb.gz"), "wb") as g:
                shutil.copyfileobj(f, g)
            with open(os.path.join(own.save, "program.json"), "w") as f:
                json.dump({"program": program, "units": units,
                           "shape": shape}, f)
        mine = reduce(cell_name, T.load(path) if path else None, path,
                      program, units, shape, bench_dir)
        out["metrics"].update(mine["metrics"])
        out.setdefault("breakdown", {}).update(mine["breakdown"])
        window = by_counts[warmup:warmup + int(result["attempted"])]
        counted = program["counters_end"].get("grow.hist_rows_needed", 0) \
            - program["counters_start"].get("grow.hist_rows_needed", 0)
        run.say(f"program: grow.hist_rows_needed over the window = "
                f"{counted:.0f}; from the dumped leaf counts of "
                f"{len(window)} tree(s) = {sum(window):.0f}")
        return out

    run.per_layer = per_layer
    try:
        return run.main(rest, hooks=hooks)
    finally:
        run.per_layer = inner
        telemetry.TRACER.remove_sink(record)


if __name__ == "__main__":
    sys.exit(main())
