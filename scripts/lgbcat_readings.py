"""`perfbench.readings` for a cell with categorical columns, with the
deployment's own two faults.

    chiprun -- python3 scripts/lgbcat_readings.py \
        --workload airline13-lgbcat-l255.train --seeds 1,2,3 --control-seeds 1

What `python3 -m perfbench.readings` reads (the program on each seed's own
population; on the control seeds the bfloat16 control, half of the batch
left out, the score update dropped: its docstring), through the cell's own
job (`jobs/train_cat.make_inputs`: the declared columns categorical) and
reference (`reference/gbdt_cat.py`), and on the control seeds the two
faults only a model with category-set splits can have:

  cat_as_threshold   every categorical node routed as `code <= t`, t the
                     node's index among the tree's categorical nodes (what
                     a dump that prints `<=` for every node states)
  category_dropped   one category, the smallest, dropped from every left
                     set

each followed by the reference and put in the program's place.
`perfbench/readings.py` is the benchmark's and is not edited, so the
faults live here (`fault_trees`; `tests/test_lgbcat_cell.py` plants the
same).  One JSON line a seed, also appended to `--out`.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

FAULTS = ("cat_as_threshold", "category_dropped")


def fault_trees(trees, fault: str):
    """The followed trees (`gbdt_cat.TreeArrays`) with `fault` planted in
    every categorical node."""
    out = []
    for t in trees:
        cat = np.nonzero(t.is_cat)[0]
        if fault == "cat_as_threshold":
            threshold = t.threshold.copy()
            threshold[cat] = np.arange(len(cat))
            out.append(t._replace(threshold=threshold,
                                  is_cat=np.zeros_like(t.is_cat),
                                  left_set=np.zeros_like(t.left_set)))
        elif fault == "category_dropped":
            left_set = t.left_set.copy()
            left_set[cat, np.argmax(left_set[cat], axis=1)] = False
            out.append(t._replace(left_set=left_set))
        else:
            raise ValueError(f"unknown fault {fault!r}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="chiprun_out/lgbcat_readings.jsonl")
    ap.add_argument("--bench-dir", default=None)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("lgbcat_readings: no TPU; nothing was run", file=sys.stderr)
        return 2
    import lightgbm_tpu as lgb
    from perfbench import check, manifest
    from perfbench.jobs.train import wait_for_rounds
    from perfbench.jobs.train_cat import make_inputs
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
        codes, label = rows["codes"], rows["label"]
        kw = {"n_check": int(traffic["check_nodes"]), "seed": seed,
              "categorical": ref.declared_columns(config)}
        t = time.perf_counter()
        readings = ref.follow(codes, label, trees, params, **kw)
        line = {"workload": args.workload, "seed": seed, "round_s": round_s,
                "reference_s": time.perf_counter() - t, "peak_bytes": peak,
                "leaves": [t.num_leaves for t in trees],
                "cat_nodes": [int(t.is_cat.sum()) for t in trees],
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
            for fault in FAULTS:
                line["fault_" + fault] = put_in_the_programs_place(
                    ref.follow(codes, label, fault_trees(trees, fault),
                               params, **kw))
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
