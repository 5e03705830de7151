"""What a lambdarank round costs at a ranking cell's shape, phase by phase.

    python3 scripts/lgbrank_probe.py \
        --workload istellax220-lgbrank-l31.train [--program <checkout>]

Builds the cell's training queries (`jobs/train_rank.make_inputs`) and a
`Booster` of the program found at `--program` (default: this checkout; a
parent's unpacked tree to read it before a change), then times, each on
the device and waited for:
  - the gradient program alone (`Booster._grad_fn` at the current score):
    the first call (compile and run) and `--calls` more, and the device's
    peak bytes after them;
  - `--rounds` rounds through `Booster.update()`;
and prints the lane plan's lanes a row, the column blocks a pass is cut
into at one and at eight slots, and the program's counters of the rounds
(kernel passes by body, routing passes and picks, waves and tail passes,
`rank.*` where the program has them).  A booster that cannot be built is
printed as such, and then the objective's gradients alone are timed (its
`grad_hess` jitted as the booster would); a gradient program that raises
(the device's memory) is printed as such; the rounds need both.  One JSON line at the end,
also appended to `--out`.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program", default=HERE)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--out", default="")
    ap.add_argument("--bench-dir", default="",
                    help="where workloads/ and configs/ are (a rehearsal's "
                         "cut copy; default perfbench/)")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    # the benchmark's modules from this checkout, the program from
    # --program (whose own perfbench/ may lack the job)
    from perfbench import manifest
    from perfbench.jobs.train_rank import make_inputs
    sys.path.insert(0, os.path.abspath(args.program))

    import jax
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("lgbrank_probe: no TPU; nothing was run", file=sys.stderr)
        return 2
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.ops import pallas_hist
    if not args.allow_cpu:      # a CPU rehearsal leaves no cache behind
        from lightgbm_tpu.utils.env import setup_compile_cache
        setup_compile_cache()

    def say(msg):
        print(msg, flush=True)

    say(f"program: {os.path.dirname(os.path.abspath(lgb.__file__))}")
    bench = args.bench_dir or manifest.HERE
    cell = manifest.workload(args.workload, bench)
    config = manifest.config(cell["config"], bench)
    rows, ds, params = make_inputs(lgb, config, args.seed, 1, say)
    line = {"workload": args.workload, "program": args.program}
    dev = jax.local_devices()[0]
    t = time.perf_counter()
    try:
        bst = lgb.Booster(params=params, train_set=ds)
    except Exception as e:      # the program under test may not build
        bst = None
        line["booster_error"] = f"{type(e).__name__}: {str(e)[:600]}"
    line["booster_s"] = time.perf_counter() - t
    if bst is not None:
        plan = bst._grower_spec.hist_lane_plan
        line["lanes_per_row"] = telemetry.REGISTRY.gauge(
            "hist.lanes_per_row").value
        line["column_blocks"] = {
            str(s): [c1 - c0 for c0, c1, _ in pallas_hist.column_blocks(
                rows["codes"].shape[0], s, int(params["max_bin"]), plan)]
            for s in (9, 72)}
        grad, score = bst._grad_fn, bst._train_score
    else:
        # the objective alone, as the booster would jit it
        from lightgbm_tpu.objectives import create_objective
        from lightgbm_tpu.utils.config import Config
        obj = create_objective(Config(params))
        obj.init_meta(rows["label"].astype(np.float64), None,
                      rows["query_boundaries"])
        label = jax.device_put(rows["label"])
        fn = jax.jit(lambda s, y: obj.grad_hess(s, y, None))
        grad = lambda s: fn(s, label)          # noqa: E731
        score = jax.numpy.zeros(len(rows["label"]), jax.numpy.float32)
    say(json.dumps(line))
    try:
        t = time.perf_counter()
        jax.block_until_ready(grad(score))
        line["grad_first_s"] = time.perf_counter() - t
        calls = []
        for _ in range(args.calls):
            t = time.perf_counter()
            jax.block_until_ready(grad(score))
            calls.append(time.perf_counter() - t)
        line["grad_s"] = calls
        line["peak_after_grad"] = int(
            (dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    except Exception as e:      # the program under test may not fit
        line["grad_error"] = f"{type(e).__name__}: {str(e)[:600]}"
    say(json.dumps(line))
    if bst is not None and "grad_error" not in line:
        before = dict(telemetry.REGISTRY.snapshot()["counters"])
        rounds = []
        for _ in range(args.rounds):
            t = time.perf_counter()
            bst.update()
            jax.block_until_ready(bst._train_score)
            rounds.append(time.perf_counter() - t)
        after = telemetry.REGISTRY.snapshot()["counters"]
        line["round_s"] = rounds
        line["counters"] = {
            k: v - before.get(k, 0) for k, v in after.items()
            if k.startswith(("grow.", "rank.", "score."))
            and v != before.get(k, 0)}
        line["peak_bytes"] = int(
            (dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    text = json.dumps(line)
    print(text, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
