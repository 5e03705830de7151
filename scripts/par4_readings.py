"""`perfbench.readings` for a sharded cell, with the deployment's own fault.

    chiprun --chips 4 -- python3 scripts/par4_readings.py \
        --workload criteo67-lgbpar-l255.train --seeds 1,2 --control-seeds 1

What `python3 -m perfbench.readings` reads (the program on each seed's own
population; on the control seeds the bfloat16 control, half of the batch
left out, the score update dropped: its docstring), and on the control
seeds one fault more, which only a row-sharded learner can have: ONE
SHARD'S ROWS LEFT OUT of the followed sums (`--shards` contiguous row
shards, the second left out; `leaf_count_gap` then reads about 1 /
shards).  `perfbench/readings.py` is the benchmark's and is not edited, so
the fault lives here.  One JSON line a seed, also appended to `--out`.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--out", default="chiprun_out/par4_readings.jsonl")
    ap.add_argument("--bench-dir", default=None)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("par4_readings: no TPU; nothing was run", file=sys.stderr)
        return 2
    import lightgbm_tpu as lgb
    from perfbench import check, manifest
    from perfbench.jobs.train import make_inputs, wait_for_rounds
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
        peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in jax.local_devices()]
        trees = [ref.tree_from_dump(t) for t in booster.dump_model(
            num_iteration=len(round_s))["tree_info"]]
        del booster, ds
        gc.collect()
        codes, label = rows["codes"], rows["label"]
        kw = {"n_check": int(traffic["check_nodes"]), "seed": seed}
        t = time.perf_counter()
        readings = ref.follow(codes, label, trees, params, **kw)
        line = {"workload": args.workload, "seed": seed, "round_s": round_s,
                "reference_s": time.perf_counter() - t,
                "peak_bytes_by_device": peaks,
                "leaves": [t.num_leaves for t in trees],
                "program": check.compare(check.stated_of(trees), readings)}

        def put_in_the_programs_place(other):
            return check.compare(check.stated_by(other, trees), readings)

        if seed in control:
            n = codes.shape[1]
            line["control_bf16"] = put_in_the_programs_place(ref.follow(
                codes, label, trees, params, dtype=jnp.bfloat16, **kw))
            line["fault_half_batch"] = put_in_the_programs_place(ref.follow(
                np.ascontiguousarray(codes[:, :n // 2]), label[:n // 2],
                trees, params, **kw))
            line["fault_state_unchanged"] = put_in_the_programs_place(
                ref.follow(codes, label, trees, params,
                           update_scores=False, **kw))
            keep = np.ones(n, bool)
            keep[n // args.shards:2 * (n // args.shards)] = False
            line["fault_one_shard_left_out"] = put_in_the_programs_place(
                ref.follow(np.ascontiguousarray(codes[:, keep]), label[keep],
                           trees, params, **kw))
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
