"""`perfbench.readings` for a ranking cell, with the ranking faults.

    python3 scripts/lgbrank_readings.py \
        --workload istellax220-lgbrank-l31.train --seeds 1,2 --control-seeds 1

For each seed, at the cell's own size: that seed's population as the
training queries (`jobs/train_rank.make_inputs`), the booster, the cell's
`check_rounds` rounds through `Booster.update()`, then with the program's
state freed the reference (`reference/gbdt_rank.py`) follows the trees:
the three compared numbers are the program's reading.  On the control
seeds the same trees are also read by the reference put in the program's
place
  control_bf16           the lambdas and hessians rounded to bfloat16
                         before they are summed (the next precision down)
  fault_previous_rule    the pair rule the program followed before: no
                         score distance, the norm over the docs' |lambda|
  fault_shuffled_queries the rows of each pair of neighbouring queries
                         pooled and dealt back at random: documents in
                         the wrong queries
  fault_state_unchanged  every round at score 0 (the score update dropped)
One JSON line a seed, also appended to `--out` where given.
`perfbench/readings.py` reads binary cells and is not edited, so these
live here (`tests/test_lgbrank_cell.py` plants the same faults).
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def shuffled_queries(query_boundaries, n_rows: int, seed: int = 0):
    """A permutation of the rows that pools each pair of neighbouring
    queries and deals their rows back at random."""
    rng = np.random.default_rng(seed)
    qb = np.asarray(query_boundaries, np.int64)
    perm = np.arange(n_rows)
    for q in range(0, len(qb) - 2, 2):
        lo, hi = qb[q], qb[q + 2]
        perm[lo:hi] = lo + rng.permutation(hi - lo)
    return perm


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--bench-dir", default=None)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("lgbrank_readings: no TPU; nothing was run", file=sys.stderr)
        return 2
    import lightgbm_tpu as lgb
    from perfbench import check, manifest
    from perfbench.jobs.train import wait_for_rounds
    from perfbench.jobs.train_rank import make_inputs
    if not args.allow_cpu:
        from lightgbm_tpu.utils.env import setup_compile_cache
        setup_compile_cache()
    bench = args.bench_dir or manifest.HERE
    cell = manifest.workload(args.workload, bench)
    config = manifest.config(cell["config"], bench)
    traffic = cell["traffic_params"]
    ref = manifest.load_module("reference", config["reference"])

    for seed in seeds:
        t0 = time.perf_counter()
        rows, ds, params = make_inputs(
            lgb, manifest.with_population(config, seed), seed, 1)
        booster = lgb.Booster(params=params, train_set=ds)
        round_s = []
        for _ in range(int(traffic["check_rounds"])):
            t = time.perf_counter()
            booster.update()
            wait_for_rounds(booster)
            round_s.append(time.perf_counter() - t)
        peak = int((jax.local_devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use", 0))
        trees = [ref.tree_from_dump(t) for t in booster.dump_model(
            num_iteration=len(round_s))["tree_info"]]
        del booster, ds
        gc.collect()
        codes, label, qb = (rows[k] for k in
                            ("codes", "label", "query_boundaries"))
        kw = {"n_check": int(traffic["check_nodes"]), "seed": seed}
        t = time.perf_counter()
        readings = ref.follow(codes, label, qb, trees, params, **kw)
        line = {"workload": args.workload, "seed": seed, "round_s": round_s,
                "reference_s": time.perf_counter() - t, "peak_bytes": peak,
                "leaves": [t.num_leaves for t in trees],
                "leaf_hessian_min": [float(t.leaf_weight.min())
                                     for t in trees],
                "program": check.compare(check.stated_of(trees), readings),
                "worst": check.worst(check.stated_of(trees), readings)}

        def put_in_the_programs_place(**more):
            other = ref.follow(codes, label, qb, trees, params, **kw, **more)
            return check.compare(check.stated_by(other, trees), readings)

        if seed in control:
            line["control_bf16"] = put_in_the_programs_place(
                dtype=jnp.bfloat16)
            line["fault_previous_rule"] = put_in_the_programs_place(
                rule="per_doc_norm")
            line["fault_shuffled_queries"] = put_in_the_programs_place(
                rows=shuffled_queries(qb, len(label)))
            line["fault_state_unchanged"] = put_in_the_programs_place(
                update_scores=False)
        line["total_s"] = time.perf_counter() - t0
        del rows, readings, trees, codes, label
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
