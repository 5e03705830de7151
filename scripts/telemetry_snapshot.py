"""Produce one telemetry/flight snapshot JSON for the regression sentinel.

Runs a small, fully deterministic CPU training job with the flight
recorder on and writes

    {"backend": ..., "sentinel": {"rel_tol", "timing_rel_tol"},
     "metrics": REGISTRY.snapshot(), "flight": booster.flight_summary()}

to --out (stdout by default).  Two snapshots diff via

    python -m lightgbm_tpu telemetry diff A.json B.json [--warn-timings]

CI (scripts/run_ci.sh) diffs a fresh snapshot against the checked-in
scripts/telemetry_baseline.json: counter-class drift (tree shape, split
counts, recompiles, fallback events, memory watermarks) fails the gate;
wall-clock drift only warns there (--warn-timings — CI boxes share
cores).  Regenerate the baseline with scripts/telemetry_baseline.sh
after an INTENDED change to the training mechanism.

The embedded `sentinel` block carries the tolerances the snapshot wants
to be compared under (from the telemetry_diff_rel_tol /
telemetry_diff_timing_rel_tol params); `telemetry diff` honors it when
its CLI flags are left at defaults.

Everything that feeds the counters is pinned: fixed seed, fixed sizes,
single-threaded deterministic binning, JAX_PLATFORMS=cpu (forced below
unless the caller already chose a platform).
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def build_snapshot(rounds: int, rel_tol: float,
                   timing_rel_tol: float) -> dict:
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    import jax

    rng = np.random.RandomState(1234)
    n, f = 3000, 10
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + X[:, 2] * X[:, 3]
         + rng.randn(n) * 0.4 > 0).astype(np.float64)
    Xe, ye = X[:600], y[:600]

    params = {
        "objective": "binary",
        "num_leaves": 15,
        "learning_rate": 0.2,
        "verbosity": -1,
        "flight_recorder": True,
        "telemetry_diff_rel_tol": rel_tol,
        "telemetry_diff_timing_rel_tol": timing_rel_tol,
    }
    bst = lgb.train(params, lgb.Dataset(X, label=y),
                    num_boost_round=rounds,
                    valid_sets=[lgb.Dataset(Xe, label=ye)],
                    valid_names=["holdout"])
    # external-memory segment: a short spilled training run so the
    # baseline carries the datastore.* names.  Fixed shard size (not the
    # budget heuristic) keeps shard/spill counts machine-independent;
    # prefetch hit/stall and the resident watermark stay scheduling-
    # dependent and are ignore/timing-class in diff.RULES
    lgb.train({**params, "flight_recorder": False,
               "external_memory": True, "datastore_shard_rows": 512},
              lgb.Dataset(X, label=y), num_boost_round=4)
    # streaming segment (ISSUE 16): a short shard-streamed run so the
    # baseline carries the stream.* gauges/counters and the
    # stream.pass.* attribution histograms.  Pass counts and shard
    # geometry are data-determined; the histogram percentiles are
    # wall-clock and timing-class in diff.RULES (stream.pass.*.count is
    # ignore-class, so a pass-count change only fails through the
    # stream.shard_passes counter it already fails through)
    lgb.train({**params, "flight_recorder": False,
               "external_memory": True, "datastore_shard_rows": 512,
               "streaming_train": "on"},
              lgb.Dataset(X, label=y), num_boost_round=4)
    # sharded serving segment: one pinned replica per visible device
    # (1 on the CPU CI box) so the baseline carries the
    # serve.replicas / serve.replica.<i>.* / stripe-imbalance names
    # the PR-10 sentinel rules watch.  One predict keeps every counter
    # deterministic; the latency histograms are timing-class anyway
    from lightgbm_tpu.serving import ServingClient
    client = ServingClient(bst, params={"serve_max_wait_ms": 0.0,
                                        "serve_shard_devices": 0})
    client.predict(np.ascontiguousarray(Xe, dtype=np.float64),
                   raw_score=True)
    client.close()
    # fleet segment: one append → retrain → gated hot-swap plus a tenant
    # predict, so the baseline carries the fleet.* names the PR-11
    # sentinel rules watch (swap.rejected / gate.fail / shed.slo stay
    # absent — the up_is_bad rules fire only if a later snapshot grows
    # them).  Everything is pinned: fixed rows, fixed rounds, step() is
    # synchronous; fleet timings are timing/ignore-class in diff.RULES.
    # ISSUE 12 names ride the same segment: serve_drift samples the
    # pinned predict rows and PSI-scores them against the candidate's
    # training bins (fully data-determined → the up_is_bad psi rules
    # gate hard); the tenant predict sets the fleet.slo.* gauges — the
    # SLO class is deliberately absurdly lenient (1e6 ms p99) so no
    # request can ever be over budget and budget_remaining pins at a
    # deterministic 1.0 (its down_is_bad rule is counter-class);
    # ledger.records counts every control-plane record (ignore-class)
    import shutil
    import tempfile
    from lightgbm_tpu.fleet import TrainerDaemon, TenantRegistry, \
        create_fleet_store
    fdir = tempfile.mkdtemp(prefix="fleet_snap_")
    try:
        Xf = np.asarray(X[:384], np.float64)
        yf = np.asarray(y[:384], np.float32)
        fbst = lgb.train({"objective": "binary", "num_leaves": 7,
                          "verbosity": -1},
                         lgb.Dataset(Xf, label=yf), num_boost_round=3)
        create_fleet_store(fdir, Xf, yf, shard_rows=256)
        fclient = ServingClient(fbst, params={"serve_max_wait_ms": 0.0,
                                              "serve_warmup": False})
        daemon = TrainerDaemon(
            fdir, fclient.registry, fbst,
            train_params={"objective": "binary", "num_leaves": 7,
                          "verbosity": -1},
            params={"fleet_retrain_rows": 128, "fleet_rounds": 2,
                    "fleet_shadow_rows": 128, "serve_drift": True,
                    "serve_drift_min_rows": 32})
        from lightgbm_tpu.datastore.store import ShardStore
        ShardStore.open(fdir).append_rows(Xf[:192], label=yf[:192])
        daemon.step()
        # sampled through the registry's hook by this pinned predict,
        # scored by the next poll (no new store rows → compute only)
        fclient.predict(np.ascontiguousarray(Xf[:64]))
        daemon.step()
        tenants = TenantRegistry({"fleet_slo_classes": "lax=1000000"},
                                 registry=fclient.registry)
        tenants.register("snapshot", fbst, warmup=False)
        tenants.predict(np.ascontiguousarray(Xf[:16]), tenant="snapshot")
        daemon.stop()
        fclient.close()
    finally:
        shutil.rmtree(fdir, ignore_errors=True)
    # bounded serving segment (PR 19): one pinned predict through a
    # serve_precision=bounded runtime so the baseline carries the
    # serve.bounded counter and the serve.bounded.active/bound/
    # measured_error{model=} contract gauges the sentinel rules watch
    # (bounded.active down-is-bad, error_ratio up-is-bad in the bench
    # block; serve.bounded_disabled{cause=} up-is-bad here).  The bound
    # and the probe's measured error are pure functions of the pinned
    # model + probe batch, so both gauges are deterministic
    bclient = ServingClient(bst, params={"serve_max_wait_ms": 0.0,
                                         "serve_warmup": False,
                                         "serve_precision": "bounded"})
    bclient.predict(np.ascontiguousarray(Xe[:64], dtype=np.float64),
                    raw_score=True)
    bclient.close()
    # memory segment (ISSUE 18): reconcile the device-memory ledger
    # against allocator truth so the baseline carries
    # mem.unattributed_bytes (up_is_bad — attribution rot fails the
    # gate) next to the live mem.dev0.* owner gauges the earlier
    # segments published (ignore-class workload bookkeeping).  The
    # gc.collect() first retires every dead segment's arrays so the
    # live_arrays truth source on CPU sees only deterministic
    # survivors, not cycle-held garbage with scheduler-dependent
    # lifetimes
    import gc
    gc.collect()
    telemetry.MEMLEDGER.reconcile()
    return {
        "backend": jax.devices()[0].platform,
        "sentinel": {"rel_tol": float(bst.config.telemetry_diff_rel_tol),
                     "timing_rel_tol":
                         float(bst.config.telemetry_diff_timing_rel_tol)},
        "metrics": telemetry.REGISTRY.snapshot(),
        "flight": bst.flight_summary(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="-",
                    help="output path (default: stdout)")
    # 32 rounds = 2 fused chunks (_BULK_CHUNK=16): enough for the
    # chunked-eval path AND the speculative pipeline dispatch to engage,
    # so the baseline covers train.harvest / train.pipeline.* names
    ap.add_argument("--rounds", type=int, default=32)
    ap.add_argument("--rel-tol", type=float, default=0.25)
    ap.add_argument("--timing-rel-tol", type=float, default=1.5)
    args = ap.parse_args(argv)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # deterministic by default; an explicit JAX_PLATFORMS (e.g. a TPU
    # snapshot for a hardware baseline) wins
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, repo)

    snap = build_snapshot(args.rounds, args.rel_tol, args.timing_rel_tol)
    text = json.dumps(snap, indent=1, sort_keys=True) + "\n"
    if args.out == "-":
        sys.stdout.write(text)
    else:
        with open(args.out, "w") as fh:
            fh.write(text)
        print(f"[telemetry-snapshot] wrote {args.out} "
              f"({snap['backend']}, {args.rounds} rounds)",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
