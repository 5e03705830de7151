"""Readings that a cell's limits are set from, many seeds in one process.

    python3 -m perfbench.readings --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2] [--out chiprun_out/readings.jsonl]

For each seed, at the cell's own size: the rows of THAT seed as the training
population (a limit is set from many populations' trees, not from the one
the configuration names), the booster, the cell's
`check_rounds` rounds through `Booster.update()` (no measured window: a
training cell's readings need none), then with the program's state freed
the plain reference follows the trees, and the three compared numbers are
the program's reading (a lower reading).  For the control seeds the same
trees are also read
  - by the reference computed in bfloat16 put in the program's place (the
    control: an upper reading), and
  - by the reference over the first half of the rows put in the program's
    place (the fault "half of the batch left out"), and
  - by the reference with its score update dropped put in the program's
    place (the fault "a step that returns its state unchanged").
One JSON line per seed.  The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

from . import check, manifest
from .jobs.train import make_inputs, wait_for_rounds


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--bench-dir", default=manifest.HERE)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("perfbench.readings: no TPU; nothing was run", file=sys.stderr)
        return 2
    import lightgbm_tpu as lgb
    if not args.allow_cpu:      # a CPU rehearsal leaves no cache behind
        from lightgbm_tpu.utils.env import setup_compile_cache
        setup_compile_cache()

    cell = manifest.workload(args.workload, args.bench_dir)
    config = manifest.config(cell["config"], args.bench_dir)
    traffic = cell["traffic_params"]
    ref = manifest.load_module("reference", config["reference"])
    n_rounds = int(traffic["check_rounds"])

    for seed in seeds:
        t0 = time.perf_counter()
        rows, ds, params = make_inputs(
            lgb, manifest.with_population(config, seed), seed, 1)
        booster = lgb.Booster(params=params, train_set=ds)
        t_rounds = []
        for _ in range(n_rounds):
            t = time.perf_counter()
            booster.update()
            wait_for_rounds(booster)
            t_rounds.append(time.perf_counter() - t)
        stats = jax.local_devices()[0].memory_stats() or {}
        trees = [ref.tree_from_dump(t) for t in booster.dump_model(
            num_iteration=n_rounds)["tree_info"]]
        del booster, ds
        gc.collect()
        t = time.perf_counter()
        kw = {"n_check": int(traffic["check_nodes"]), "seed": seed}
        readings = ref.follow(rows["codes"], rows["label"], trees, params,
                              **kw)
        t_ref = time.perf_counter() - t
        line = {"workload": args.workload, "seed": seed,
                "round_s": t_rounds, "reference_s": t_ref,
                "peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
                "leaves": [t.num_leaves for t in trees],
                "program": check.compare(check.stated_of(trees), readings),
                "worst": check.worst(check.stated_of(trees), readings)}
        if seed in control:
            t = time.perf_counter()
            low = ref.follow(rows["codes"], rows["label"], trees, params,
                             dtype=jnp.bfloat16, **kw)
            line["control_bf16"] = check.compare(
                check.stated_by(low, trees), readings)
            line["control_s"] = time.perf_counter() - t
            half = rows["codes"].shape[1] // 2
            part = ref.follow(np.ascontiguousarray(rows["codes"][:, :half]),
                              rows["label"][:half], trees, params, **kw)
            line["fault_half_batch"] = check.compare(
                check.stated_by(part, trees), readings)
            same = ref.follow(rows["codes"], rows["label"], trees, params,
                              update_scores=False, **kw)
            line["fault_state_unchanged"] = check.compare(
                check.stated_by(same, trees), readings)
        line["total_s"] = time.perf_counter() - t0
        del rows, readings, trees
        gc.collect()
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
