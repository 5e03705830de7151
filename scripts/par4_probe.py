"""Drive `Booster.update()` with `tree_learner=data` over every local chip.

    chiprun --chips 4 -- python3 scripts/par4_probe.py [--rows-a-shard N]

ISSUE 33's step 0, item 3, and the builder's account of a sharded round
afterwards: `--rows-a-shard` rows a chip of `--config`'s columns
(`perfbench/configs/`), a `Booster` with the four-chip cell's growth
settings and `tree_learner=data`, `--rounds` rounds under the profiler.
Prints, as JSON lines: how long the booster and each round took, the
programs and the heaviest device operations the trace shows, each
device's busy share and `peak_bytes_in_use`, and the program's own
counters (`fallback.events`, `jit.recompiles`, `grow.*`, `hist.*`).

One process; exits 2 where JAX finds no TPU (`--rehearse` runs tiny on the
CPU's virtual devices: it finds faults, its times mean nothing).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def say(**kw):
    print(json.dumps(kw), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows-a-shard", type=int, default=2_097_152)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--config", default="criteo67-lgbpar-l255",
                    help="whose columns, function and population")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not a.rehearse:
        print("no TPU: nothing was run", file=sys.stderr)
        return 2
    say(device=devs[0].device_kind, count=len(devs))
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.telemetry.recorder import install_compile_listener
    from perfbench import manifest, trace as T
    from perfbench.generators import tabular_codes
    from perfbench.jobs.train import build_dataset, wait_for_rounds
    if not a.rehearse:
        from lightgbm_tpu.utils.env import setup_compile_cache
        setup_compile_cache()
    install_compile_listener()

    data = manifest.config(a.config)["data"]
    n = a.rows_a_shard * len(devs)
    t = time.perf_counter()
    codes, label = tabular_codes.generate(
        int(data["population_seed"]), data, 0, n)
    say(rows=n, columns=int(codes.shape[0]), label_mean=float(label.mean()),
        rows_s=time.perf_counter() - t)
    params = {"objective": "binary", "tree_learner": "data",
              "num_leaves": a.leaves, "learning_rate": 0.1, "max_bin": 255,
              "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
              "tree_grow_policy": "wave", "tpu_wave_width": 8,
              "tpu_wave_gain_ratio": 0, "tpu_wave_strict_tail": 16,
              "verbosity": -1}
    if a.rehearse:
        params.update(hist_impl="pallas", hist_interpret=True)
    ds = build_dataset(lgb, codes, label, params,
                       [c["name"] for c in data["columns"]])
    t = time.perf_counter()
    booster = lgb.Booster(params=params, train_set=ds)
    say(booster_s=time.perf_counter() - t, policy=booster._grow_policy,
        hist_impl=booster._grower_spec.hist_impl,
        lane_plan=booster._grower_spec.hist_lane_plan is not None,
        mesh=None if booster._mesh is None else dict(booster._mesh.shape))

    def one_round():
        t = time.perf_counter()
        booster.update()
        wait_for_rounds(booster)
        return time.perf_counter() - t

    say(warmup_round_s=one_round())
    trace_dir = os.path.join(manifest.ROOT, ".perfbench_trace")
    tracer = T.Tracer(trace_dir)
    tracer.start()
    try:
        rounds = [one_round() for _ in range(a.rounds)]
    finally:
        tracer.stop()
    say(round_s=rounds)
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        say(memory_device=d.id, peak_bytes_in_use=st.get("peak_bytes_in_use"),
            bytes_limit=st.get("bytes_limit"))
    snap = telemetry.REGISTRY.snapshot()
    flat = {}
    for kind in ("counters", "gauges"):
        for k, v in (snap.get(kind) or {}).items():
            if k.split(".")[0] in ("fallback", "jit", "grow", "hist", "mesh",
                                   "wave"):
                flat[k] = v
    say(registry=flat)
    path = tracer.file()
    if path:
        tr = T.load(path)
        lo, hi = T.window_of(tr)
        say(trace_devices=tr.devices, window_s=(hi - lo) / 1e9,
            busy_s_by_device={d: T.total(T.busy(tr, d)) / 1e9
                              for d in tr.devices},
            programs=sorted({o.program for o in tr.ops}))
        say(top_ops=T.top_ops(tr, 40))
        coll = {}
        for o in tr.ops:
            if o.opcode.startswith(("all-", "reduce-scatter",
                                    "collective-permute")):
                coll[o.opcode] = coll.get(o.opcode, 0.0) + o.dur
        say(collective_s_a_device={k: v / len(tr.devices) / 1e9
                                   for k, v in coll.items()})
        if not a.rehearse:      # a rehearsal overwrites no chip record
            out = os.path.join(manifest.ROOT, "chiprun_out")
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, "par4_probe_trace.txt"), "w") as f:
                f.write(T.describe(path, 60))
    return 0


if __name__ == "__main__":
    sys.exit(main())
