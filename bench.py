"""Benchmark: boosting rounds/sec on a Higgs-shaped binary problem, on a TPU.

ONE process: it checks that JAX's first device is a TPU (anything else is
an error — exit 2, nothing measured, nothing printed on stdout), runs the
measurement and prints ONE JSON line: {"metric", "value", "unit",
"vs_baseline"} plus the informational keys "backend", "device_kind",
"auc", "config" and the optional blocks below.  A phase that raises
fails the run: the traceback goes to stderr and the exit code is
non-zero.

`--serve` adds a `serving` block after the main measurement: closed-loop
p50/p99 + rows/s through the micro-batched runtime, per-rung splits, the
sharded plane (when several devices are visible) and the fleet hot-swap.

`--streaming` adds a `streaming` block: rounds/s through the
shard-streamed engine vs the assembled device matrix at the bench shape,
shard passes, prefetch stall ratio and the device-staging watermark
(byte identity asserted in-process).

`--soak` adds a `soak` block: the ~60 s mini-soak acceptance run
(lightgbm_tpu/soak/) — closed-loop multi-tenant traffic with an
append-triggered gated hot-swap, drift, one chaos rung-kill, the
byte-consistency oracle and the fitted step-load capacity model.
diff.py fails hard on byte_inconsistent / slo_breach / expect_fail rises
and watches the capacity throughput fields as timing metrics.

`--spool [dir]` (or BENCH_SPOOL_DIR) attaches the process to a telemetry
spool (telemetry/spool.py); render it afterwards with
`python -m lightgbm_tpu timeline <dir>`.

Baseline anchor (documented; see BASELINE.md "Our target"): the target is
the reference's **CUDA learner** on Higgs-10.5M (BASELINE.json: ">=1.5x
CUDA rounds/sec, equal AUC").  No exact public CUDA-learner table exists, so
the anchor is derived from the published chain and recorded here:
  CPU  (docs/Experiments.rst):   500 iters / 130 s = 3.85 rounds/s
  OpenCL (docs/GPU-Performance.rst): ~3.5x CPU      = ~13.5 rounds/s
  CUDA (v4 release notes, "faster than OpenCL, esp. max_bin=255"):
       assumed 1.5x OpenCL                           = ~20.2 rounds/s
  => CUDA_ANCHOR_ROUNDS_PER_SEC = 20.2 at N = 10.5M rows, 255 bins,
     31 leaves.  Scaled linearly in rows to this bench's N.
vs_baseline = ours / (anchor * 10.5e6 / N); >= 1.5 meets the north star.

Dataset: synthetic Higgs-like (N x 28 features, binary labels from a noisy
nonlinear score), fixed seed, plus a held-out slice for AUC.  Training runs
the fused device-side chunk trainer (ops/fused.py) — the TPU hot path —
and times steady-state chunks after one warmup chunk (compile excluded;
the persistent compile cache is placed by utils/env.setup_compile_cache).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks import configs_r4 as _cfg  # noqa: E402

F = 28
NUM_LEAVES = 31
MAX_BIN = 255

# documented anchor chain (see module docstring)
CUDA_ANCHOR_ROUNDS_PER_SEC = 20.2
ANCHOR_ROWS = 10_500_000

# training config the bench runs, emitted verbatim in the JSON line so a
# consumer comparing against the stock-leafwise anchor can see the policy
# difference (the emitted `auc` field keeps quality honest).  Derived
# from benchmarks/configs_r4.py's SHIPPED entry — ONE definition across
# bench/quality-sweep/family-bench.  r5: the multi-seed decider
# (PROFILE.md r5: 3 seeds at BOTH 500k and 2M) picked W=8 + strict tail
# 16 + no gain floor; the remaining mean gap to strict at 2M is -0.00275
# (same sign on 3/3 seeds) — the price of wave throughput; the default
# user policy stays `leafwise`.
BENCH_CONFIG = {"num_leaves": NUM_LEAVES, "max_bin": MAX_BIN,
                "learning_rate": 0.1,
                # pipelined chunk dispatch (explicit so the emitted JSON
                # records the schedule the number was measured under)
                "tpu_pipeline_chunks": 2,
                **_cfg.CONFIGS[_cfg.SHIPPED]}


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _require_tpu():
    """The devices, or exit 2: a number from any other backend must never
    be written under this benchmark's metric names."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[bench] FATAL: jax.devices()[0].platform is "
              f"{devs[0].platform!r} ({devs[0].device_kind}), not 'tpu' — "
              "this benchmark measures the chip and has no fallback; send "
              "it through the chip tool", file=sys.stderr, flush=True)
        sys.exit(2)
    return devs


def _spool_dir() -> str:
    """`--spool [dir]` / BENCH_SPOOL_DIR -> absolute spool dir ('' = off)."""
    spool_dir = os.environ.get("BENCH_SPOOL_DIR", "")
    if "--spool" in sys.argv:
        i = sys.argv.index("--spool")
        if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("--"):
            spool_dir = sys.argv[i + 1]
        spool_dir = spool_dir or "bench_spool"
    if not spool_dir:
        return ""
    spool_dir = os.path.abspath(spool_dir)
    os.makedirs(spool_dir, exist_ok=True)
    return spool_dir


def _make_higgs_like(n, f, seed=77):
    import numpy as np
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    score = (1.2 * X[:, 0] - 0.8 * X[:, 1] + X[:, 2] * X[:, 3]
             + 0.5 * np.sin(3 * X[:, 4]) + 0.6 * X[:, 5] ** 2
             - 0.4 * np.abs(X[:, 6]))
    y = (score + rng.randn(n) * 1.0 > 0).astype(np.float64)
    return X, y


def _emit(rounds_per_sec: float, n_rows: int, devs, auc=None, pred=None,
          telemetry=None, flight=None, pipeline=None, serving=None,
          streaming=None, memledger=None, soak=None) -> None:
    baseline = CUDA_ANCHOR_ROUNDS_PER_SEC * (ANCHOR_ROWS / n_rows)
    line = {
        "metric": f"boosting_rounds_per_sec_higgs{n_rows // 1000}k",
        "value": round(rounds_per_sec, 3),
        "unit": "rounds/s",
        "vs_baseline": float(f"{rounds_per_sec / baseline:.3g}"),
        "backend": f"{devs[0].platform}x{len(devs)}",
        "device_kind": devs[0].device_kind,
        # the anchor is stock leaf-wise growth; this run's policy/knobs
        # ride along so the throughput ratio is never read as a
        # config-identical comparison (the `auc` field keeps quality
        # honest — ADVICE r3)
        "config": BENCH_CONFIG,
    }
    if auc is not None:
        line["auc"] = round(auc, 4)
    if pred is not None:
        # batch-predict throughput (device jitted ensemble vs host walk)
        line["predict_device_rows_per_sec"] = round(pred[0])
        line["predict_host_rows_per_sec"] = round(pred[1])
    if telemetry is not None:
        # metrics registry snapshot: rounds trained, span timings,
        # fallback counters
        line["telemetry"] = telemetry
    if flight is not None:
        # flight-recorder summary: tree-shape/gain quantiles, per-phase
        # wall-clock, compile accounting; the device-memory watermarks
        # are ALSO lifted to a top-level key for grep/jq consumers
        line["flight"] = flight
        if isinstance(flight, dict) and flight.get("watermarks"):
            line["memory"] = flight["watermarks"]
    if memledger is not None:
        # device-memory ledger roll-up: attributed per-owner residency,
        # allocator-reconciled unattributed watermark, leak-sentinel
        # slope and budget-violation counts — merged into the same
        # `memory` block as the flight watermarks (copy first: `memory`
        # may alias flight["watermarks"])
        mem = dict(line.get("memory") or {})
        mem["ledger"] = memledger
        line["memory"] = mem
    if pipeline is not None:
        # pipelined-dispatch summary: configured depth, chunks run,
        # device-idle-gap estimate totals — the `telemetry diff`
        # sentinel watches the idle gauge as a timing-class metric
        line["pipeline"] = pipeline
    if serving is not None:
        # closed-loop serving bench (--serve): per-request p50/p99
        # latency + rows/s through the micro-batched runtime — diff.py
        # classes these as timing metrics
        line["serving"] = serving
    if streaming is not None:
        # streamed-vs-assembled training comparison (--streaming):
        # rounds/s both routes, shard passes, stall ratio and the
        # device-staging watermark — diff.py fails hard on a
        # peak_device_mb rise and watches the throughputs as timing
        # metrics
        line["streaming"] = streaming
    if soak is not None:
        # mini-soak acceptance run (--soak): scenario expectations,
        # byte-oracle verdict, SLO burn and the fitted capacity model —
        # diff.py fails HARD on byte_inconsistent / slo_breach /
        # expect_fail rises and watches the capacity rows/s +
        # sustainable-QPS fields as timing metrics
        line["soak"] = soak
    print(json.dumps(line, default=str), flush=True)


# --------------------------------------------------------------------------
# main measurement
# --------------------------------------------------------------------------

def main() -> None:
    import numpy as np

    from lightgbm_tpu.utils.env import setup_compile_cache
    cache_dir = setup_compile_cache()
    devs = _require_tpu()
    _log(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
         f"count={len(devs)} compile_cache={cache_dir}")

    n = int(os.environ.get("BENCH_N", 2_000_000))
    rounds_timed = int(os.environ.get("BENCH_ROUNDS", 48))
    n_eval = min(200_000, max(10_000, n // 10))

    t0 = time.time()
    X, y = _make_higgs_like(n + n_eval, F)
    X_eval, y_eval = X[n:], y[n:]
    X, y = X[:n], y[:n]
    _log(f"data {X.shape} built in {time.time() - t0:.1f}s")

    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.booster import Booster

    def _memledger_block():
        # device-memory ledger roll-up: per-device attributed bytes by
        # owner, allocator reconciliation (unattributed watermark) and
        # the leak-sentinel slope — the BENCH JSON `memory` block merges
        # this next to the flight recorder's phase watermarks
        led = telemetry.MEMLEDGER
        if not led.enabled:
            return None
        snap = led.debug_snapshot()
        blk = {"devices": {
            dev: {"attributed_mb":
                      round(d.get("attributed_bytes", 0) / 2**20, 3),
                  "peak_mb":
                      round(d.get("peak_bytes", 0) / 2**20, 3),
                  "owners": {k: round(o["bytes"] / 2**20, 3)
                             for k, o in d.get("owners", {}).items()}}
            for dev, d in snap.get("devices", {}).items()}}
        rec = snap.get("reconcile") or {}
        if rec:
            blk["unattributed_mb"] = round(
                rec.get("unattributed_bytes", 0) / 2**20, 3)
            blk["reconcile_source"] = rec.get("source")
        blk["leak_slope_mb_per_min"] = round(
            led.sentinel.slope_mb_per_min(), 4)
        blk["budget_violations"] = snap.get("budget_violations", {})
        blk["oom_dumps"] = int(snap.get("oom_dumps", 0))
        return blk

    def _pipeline_block():
        # pipelined-dispatch summary: configured depth, chunks run, and
        # the device-idle-gap estimate (booster._note_pipeline_gap) — the
        # BENCH JSON `pipeline` block the telemetry-diff sentinel watches
        reg = telemetry.REGISTRY
        idle = reg.timing("train.pipeline.idle")
        return {"depth": int(reg.gauge("train.pipeline.depth").value),
                "chunks": int(reg.counter("train.chunks").value),
                "device_idle_s_last":
                    reg.gauge("train.pipeline.device_idle_s").value,
                "device_idle_s_total": round(idle.total, 6),
                "device_idle_s_mean": round(idle.mean, 6)}

    if os.environ.get("BENCH_TELEMETRY_JSONL"):
        # full span stream (dataset.bin / train.chunk / compile_warmup /
        # predict.*)
        telemetry.TRACER.attach_jsonl(os.environ["BENCH_TELEMETRY_JSONL"])
    spool_dir = _spool_dir()
    if spool_dir:
        from lightgbm_tpu.telemetry.spool import attach_spool
        attach_spool(spool_dir, role="bench")
        _log(f"spooling telemetry to {spool_dir}")

    # TPU-first growth: wave-batched multi-leaf histograms fill the MXU's
    # 128-row LHS (PROFILE.md round 3c); BENCH_CONFIG picks the AUC-parity
    # point of the sweep and rides along in the emitted JSON line
    # flight_recorder rides along: per-round stats are derived from host
    # arrays the fused path already materializes, so the timed chunks pay
    # only the python bookkeeping (and the summary lands in BENCH JSON)
    params = {"objective": "binary", "verbosity": -1,
              "flight_recorder": True, **BENCH_CONFIG}
    if os.environ.get("BENCH_EXTERNAL_MEMORY"):
        # BENCH_EXTERNAL_MEMORY=<budget_mb> (or "1" for the default
        # budget): run the same measurement through the spilled shard
        # store — the datastore.* gauges ride the telemetry snapshot, so
        # the emitted JSON shows spill volume, shard count and the
        # prefetch residency watermark next to rounds/sec
        budget = os.environ["BENCH_EXTERNAL_MEMORY"]
        params["external_memory"] = True
        if budget not in ("1", "true"):
            params["datastore_budget_mb"] = float(budget)
        _log(f"external-memory mode: budget="
             f"{params.get('datastore_budget_mb', 'default')} MB")
    t0 = time.time()
    ds = lgb.Dataset(X, label=y)
    bst = Booster(params=params, train_set=ds)
    _log(f"dataset binned + device init in {time.time() - t0:.1f}s")

    chunk = bst._BULK_CHUNK
    t0 = time.time()
    bst.update_many(chunk)  # warmup: includes compile
    _log(f"warmup chunk ({chunk} rounds) incl. compile: "
         f"{time.time() - t0:.1f}s")

    done = 0
    total_s = 0.0
    while done < rounds_timed:
        t0 = time.time()
        bst.update_many(chunk)
        dt = time.time() - t0
        done += chunk
        total_s += dt
        _log(f"chunk {chunk} rounds: {dt:.4f}s")
    rounds_per_sec = done / total_s

    # rough effective-bandwidth estimate (see PROFILE.md)
    levels = np.log2(NUM_LEAVES) / 2 + 1
    gbps = n * (F + 16) * levels * rounds_per_sec / 1e9
    _log(f"est. effective HBM traffic ~{gbps:.1f} GB/s (analytic)")

    from lightgbm_tpu.metrics import _auc
    raw = bst.predict(X_eval, raw_score=True)
    auc = _auc(raw, y_eval, None, None)
    _log(f"held-out AUC after {bst.current_iteration()} rounds: "
         f"{auc:.4f} (n_eval={n_eval})")
    pipeline = _pipeline_block()
    serving = streaming = soak = None

    # batch-predict throughput (VERDICT r3 #6: prediction was never
    # measured): device jitted stacked-ensemble path vs the host walk
    # (ref: predictor.hpp Predictor).  Device timed on the full eval
    # slice (2 same-shape calls: compile+warm, then timed); host on a
    # bounded slice so a slow host walk can't eat the wall budget.
    ne = len(X_eval)
    bst.predict(X_eval, raw_score=True, device_predict=True)
    t0 = time.time()
    bst.predict(X_eval, raw_score=True, device_predict=True)
    dev_rps = ne / max(time.time() - t0, 1e-9)
    hs = min(20_000, ne)
    t0 = time.time()
    bst.predict(X_eval[:hs], raw_score=True)
    host_rps = hs / max(time.time() - t0, 1e-9)
    _log(f"batch predict: device {dev_rps:,.0f} rows/s, "
         f"host {host_rps:,.0f} rows/s ({dev_rps / host_rps:.1f}x)")

    # closed-loop serving bench (--serve): per-request latency through
    # the full micro-batched stack (client -> batcher -> bucketed device
    # runtime), one request in flight at a time so p50/p99 measure the
    # serving path itself, not queueing.  Warm-up compiles every bucket
    # first, so the percentiles are steady-state numbers
    if "--serve" in sys.argv:
        from lightgbm_tpu.serving import ServingClient
        batch = int(os.environ.get("BENCH_SERVE_ROWS", 256))
        iters = int(os.environ.get("BENCH_SERVE_ITERS", 50))
        Xs = X_eval[:batch]
        client = ServingClient(bst, params={"serve_max_wait_ms": 0.0})
        client.predict(Xs, raw_score=True)  # steady-state check
        lat = []
        t_all = time.time()
        for _ in range(iters):
            t0 = time.perf_counter()
            client.predict(Xs, raw_score=True)
            lat.append(time.perf_counter() - t0)
        total_s = time.time() - t_all
        client.close()
        lat_ms = np.sort(np.asarray(lat)) * 1e3
        blk = {"rows_per_request": batch, "requests": iters,
               "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
               "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
               "rows_per_sec": round(batch * iters / total_s, 1)}

        # server-side view of the same closed loop: per-rung e2e
        # percentiles from the serve.stage.e2e histograms the
        # serving stack itself filled (request_trace.py), so the
        # bench records what the server measured, not just what the
        # client timed — diff.py watches
        # serving.server.<rung>.p50_ms/p99_ms as timing metrics
        server = telemetry.server_latency_block()
        if server:
            blk["server"] = server

        # per-rung split at full 4096-row buckets: the exact
        # device-sum rung vs the slot path it replaces (same
        # workload, serve_device_sum toggled).  `active` records
        # whether the parity probe actually enabled the rung —
        # diff.py fails hard if it flips back to 0, so the slot
        # path cannot silently return
        def _rung_bench(mode, rows, n_iters, compiled="off",
                        precision="exact"):
            Xr = X_eval
            if len(Xr) < rows:
                Xr = np.tile(Xr, (-(-rows // max(len(Xr), 1)), 1))
            Xr = np.ascontiguousarray(Xr[:rows], np.float64)
            c = ServingClient(bst, params={
                "serve_max_wait_ms": 0.0, "serve_device_sum": mode,
                "serve_compiled": compiled,
                "serve_precision": precision})
            rt = c.registry.get().runtime
            d2h = telemetry.REGISTRY.counter("serve.d2h_bytes")
            d2h0 = d2h.value
            c.predict(Xr, raw_score=True)      # steady state
            rlat = []
            t_rall = time.time()
            for _ in range(n_iters):
                t0 = time.perf_counter()
                c.predict(Xr, raw_score=True)
                rlat.append(time.perf_counter() - t0)
            rtotal = time.time() - t_rall
            d2h_bytes = d2h.value - d2h0
            extra = {}
            if precision == "bounded":
                # the bounded rung's whole story in one block: is it
                # serving (active), what it costs in HBM vs the exact
                # compiled planes (plane_bytes/exact_plane_bytes), and
                # how much error headroom is left (error_ratio =
                # measured / published — diff.py fails HARD when this
                # climbs, the probe disables the rung past 1.0)
                active = bool(getattr(rt, "bounded_active", False))
                st = getattr(rt, "_state", None)
                if st is not None and st.bounded_planes is not None:
                    extra["plane_bytes"] = sum(
                        int(a.nbytes) for a in st.bounded_planes)
                if st is not None and st.plan_planes is not None:
                    extra["exact_plane_bytes"] = sum(
                        int(a.nbytes) for bucket in st.plan_planes
                        for a in bucket if a is not None)
                bound = getattr(rt, "bounded_bound", None)
                meas = getattr(rt, "bounded_measured_error", None)
                if bound:
                    extra["bound"] = bound
                    extra["measured_max_abs_error"] = meas
                    extra["error_ratio"] = round(
                        (meas or 0.0) / bound, 6)
            elif compiled != "off":
                active = bool(getattr(rt, "compiled_active", False))
                plan = getattr(rt, "_plan", None)
                if plan is not None:
                    extra["tiles"] = plan.num_tiles()
                    extra["vmem_bytes"] = plan.total_plane_bytes()
            else:
                active = bool(getattr(rt, "device_sum_active", False))
            c.close()
            rlat_ms = np.sort(np.asarray(rlat)) * 1e3
            return {
                "rows_per_request": rows, "requests": n_iters,
                "p50_ms": round(float(np.percentile(rlat_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(rlat_ms, 99)), 3),
                "rows_per_sec": round(rows * n_iters / rtotal, 1),
                "active": int(active),
                "d2h_bytes_per_row": round(
                    d2h_bytes / (rows * (n_iters + 1)), 1),
                **extra}

        rung_rows = int(os.environ.get("BENCH_SERVE_RUNG_ROWS", 4096))
        rung_iters = max(int(os.environ.get("BENCH_SERVE_RUNG_ITERS",
                                            max(iters // 5, 5))), 1)
        # the compiled rung (ISSUE 13): tile planes + fused traverse
        # kernel, probe-gated exactly like device_sum.  device_sum
        # is kept off so the measurement is the kernel alone, never
        # a silent degradation one rung down — `active` plus the
        # diff.py sentinel catch the probe flipping it back off
        blk["compiled"] = _rung_bench("off", rung_rows, rung_iters,
                                      compiled="on")
        # the bounded precision tier (serve_precision=bounded) over
        # the same compiled planes: int8 leaf codes + int32
        # accumulation instead of the software-f64 adder.  The block
        # records plane_bytes next to the exact compiled planes'
        # bytes (the ~4x cut is the tier's claim) and the measured
        # vs published error ratio the probe enforced at refresh
        blk["bounded"] = _rung_bench("off", rung_rows, rung_iters,
                                     compiled="on",
                                     precision="bounded")
        blk["device_sum"] = _rung_bench("auto", rung_rows, rung_iters)
        slot = _rung_bench("off", rung_rows, rung_iters)
        slot.pop("active")
        blk["slot_path"] = slot

        # sharded serving plane (serving/sharded.py): same closed
        # loop with one pinned replica per visible device, requests
        # wide enough to stripe (> max_batch_rows).  diff.py treats
        # replicas as down-is-bad and stripe_imbalance as
        # up-is-bad, so a mesh that silently shrinks or a scheduler
        # that stops balancing fails the gate
        import jax as _jax
        if len(_jax.devices()) > 1:
            # one max_batch_rows chunk per replica, so a single
            # closed-loop request stripes the whole mesh (the
            # batcher hands oversized requests through whole); the
            # steady-state call below compiles every replica, so
            # the full bucket-ladder warmup is skipped
            c = ServingClient(bst, params={
                "serve_max_wait_ms": 0.0, "serve_shard_devices": 0,
                "serve_warmup": False})
            rt = c.registry.get().runtime
            sh_rows = int(os.environ.get(
                "BENCH_SERVE_SHARD_ROWS",
                rt.max_batch_rows * rt.num_replicas))
            Xr = X_eval
            if len(Xr) < sh_rows:
                Xr = np.tile(Xr, (-(-sh_rows // max(len(Xr), 1)), 1))
            Xr = np.ascontiguousarray(Xr[:sh_rows], np.float64)
            rows0 = [telemetry.REGISTRY.counter(
                        f"serve.replica.{i}.rows").value
                     for i in range(rt.num_replicas)]
            c.predict(Xr, raw_score=True)      # steady state
            slat = []
            t_sall = time.time()
            for _ in range(rung_iters):
                t0 = time.perf_counter()
                c.predict(Xr, raw_score=True)
                slat.append(time.perf_counter() - t0)
            stotal = time.time() - t_sall
            routed = [telemetry.REGISTRY.counter(
                         f"serve.replica.{i}.rows").value - rows0[i]
                      for i in range(rt.num_replicas)]
            c.close()
            slat_ms = np.sort(np.asarray(slat)) * 1e3
            mean_r = sum(routed) / max(len(routed), 1)
            sh = {
                "rows_per_request": sh_rows, "requests": rung_iters,
                "replicas": rt.num_replicas,
                "p50_ms": round(float(np.percentile(slat_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(slat_ms, 99)), 3),
                "rows_per_sec": round(
                    sh_rows * rung_iters / stotal, 1),
                "rows_per_sec_per_replica": round(
                    sh_rows * rung_iters / stotal
                    / max(rt.num_replicas, 1), 1),
                "stripe_imbalance": round(
                    max(routed) / mean_r, 4) if mean_r > 0 else 1.0}
            blk["sharded"] = sh
            _log(f"sharded serving: {sh['replicas']} replicas, "
                 f"{sh['rows_per_sec']:,.0f} rows/s total "
                 f"({sh['rows_per_sec_per_replica']:,.0f}/replica), "
                 f"stripe imbalance {sh['stripe_imbalance']}")
        # continuous-training fleet: closed-loop predict latency
        # through a live gated hot-swap vs steady state — the
        # serving cost of staying fresh (append -> retrain -> gate
        # -> build-then-swap), measured from the caller's side
        import shutil
        import tempfile
        import threading
        from lightgbm_tpu.datastore.store import ShardStore
        from lightgbm_tpu.fleet import (TrainerDaemon,
                                        create_fleet_store)
        fn = int(os.environ.get("BENCH_FLEET_ROWS", 4096))
        Xf = np.ascontiguousarray(X[:fn], np.float64)
        yf = np.asarray(y[:fn], np.float32)
        fparams = {"objective": "binary", "num_leaves": 31,
                   "verbosity": -1}
        fb = lgb.train(fparams, lgb.Dataset(Xf, label=yf),
                       num_boost_round=8)
        fdir = tempfile.mkdtemp(prefix="bench_fleet_")
        create_fleet_store(fdir, Xf, yf, shard_rows=2048)
        fc = ServingClient(fb, params={"serve_max_wait_ms": 0.0,
                                       "serve_warmup": False})
        daemon = TrainerDaemon(
            fdir, fc.registry, fb, train_params=fparams,
            params={"fleet_retrain_rows": fn // 2,
                    "fleet_rounds": 4,
                    "fleet_shadow_rows": 1024})
        Xq = np.ascontiguousarray(X_eval[:256], np.float64)
        fc.predict(Xq, raw_score=True)     # steady state
        lat0 = []
        for _ in range(30):
            t0 = time.perf_counter()
            fc.predict(Xq, raw_score=True)
            lat0.append(time.perf_counter() - t0)
        lat_sw, stop_h = [], threading.Event()

        def _hammer():
            while not stop_h.is_set():
                t0 = time.perf_counter()
                fc.predict(Xq, raw_score=True)
                lat_sw.append(time.perf_counter() - t0)

        th = threading.Thread(target=_hammer)
        th.start()
        ShardStore.open(fdir).append_rows(
            Xf[:fn // 2], label=yf[:fn // 2])
        t_sw = time.perf_counter()
        daemon.step()
        swap_s = time.perf_counter() - t_sw
        stop_h.set()
        th.join()
        daemon.stop()
        fc.close()
        shutil.rmtree(fdir, ignore_errors=True)
        l0 = np.sort(np.asarray(lat0)) * 1e3
        ls = np.sort(np.asarray(lat_sw)) * 1e3
        fl = {"swaps": daemon.swaps, "rejects": daemon.rejects,
              "append_to_swap_s": round(swap_s, 3),
              "steady_p50_ms": round(
                  float(np.percentile(l0, 50)), 3),
              "steady_p99_ms": round(
                  float(np.percentile(l0, 99)), 3),
              "swap_window_p99_ms": round(
                  float(np.percentile(ls, 99)), 3)
                  if len(ls) else None,
              "requests_during_swap": len(ls)}
        blk["fleet"] = fl
        _log(f"fleet bench: append->swap {fl['append_to_swap_s']}"
             f" s ({fl['swaps']} swap, {fl['rejects']} reject), "
             f"p99 {fl['steady_p99_ms']} ms steady -> "
             f"{fl['swap_window_p99_ms']} ms through the swap "
             f"({fl['requests_during_swap']} reqs)")
        serving = blk
        _log(f"serving rungs @{rung_rows} rows: compiled "
             f"{blk['compiled']['rows_per_sec']:,.0f} rows/s "
             f"(active={blk['compiled']['active']}, "
             f"{blk['compiled'].get('tiles', 0)} tiles, "
             f"{blk['compiled'].get('vmem_bytes', 0)} B planes) "
             f"vs device_sum "
             f"{blk['device_sum']['rows_per_sec']:,.0f} rows/s "
             f"(active={blk['device_sum']['active']}, "
             f"{blk['device_sum']['d2h_bytes_per_row']} B/row D2H) "
             f"vs slot {slot['rows_per_sec']:,.0f} rows/s "
             f"({slot['d2h_bytes_per_row']} B/row D2H)")
        _log(f"bounded rung: "
             f"{blk['bounded']['rows_per_sec']:,.0f} rows/s "
             f"(active={blk['bounded']['active']}, "
             f"{blk['bounded'].get('plane_bytes', 0)} B planes vs "
             f"{blk['bounded'].get('exact_plane_bytes', 0)} B exact, "
             f"error_ratio {blk['bounded'].get('error_ratio')})")
        _log(f"serving bench: p50 {blk['p50_ms']} ms, "
             f"p99 {blk['p99_ms']} ms, "
             f"{blk['rows_per_sec']:,.0f} rows/s "
             f"({batch} rows x {iters} requests)")

    # streamed-training bench (--streaming): rounds/s through the
    # shard-streamed engine vs the assembled device matrix at the bench
    # shape, plus the pass count and the prefetch stall ratio — the
    # honest price of HBM-free training in one BENCH JSON block.  One
    # warmup round each keeps compile out of the timed window; both
    # routes ride the same spilled store so the comparison isolates
    # streaming itself.
    if "--streaming" in sys.argv:
        sr = int(os.environ.get("BENCH_STREAM_ROUNDS", 8))
        reg = telemetry.REGISTRY
        # ~8 shards whatever the bench shape: the default budget
        # would fit a small dataset in ONE shard and the
        # stream degenerates to a re-upload loop
        sp = {"objective": "binary", "verbosity": -1,
              "external_memory": True,
              "datastore_shard_rows": max(1024, len(X) // 8),
              **BENCH_CONFIG}

        def _timed_run(mode):
            b = Booster(params={**sp, "streaming_train": mode},
                        train_set=lgb.Dataset(X, label=y))
            b.update_many(1)             # warmup incl. compile
            t0 = time.time()
            b.update_many(sr)
            return b, sr / max(time.time() - t0, 1e-9)

        p0 = reg.counter("stream.shard_passes").value
        h0 = reg.counter("datastore.prefetch.hit").value
        s0 = reg.counter("datastore.prefetch.stall").value
        bst_a, a_rps = _timed_run("off")
        bst_s, s_rps = _timed_run("on")
        passes = int(reg.counter("stream.shard_passes").value - p0)
        hits = reg.counter("datastore.prefetch.hit").value - h0
        stalls = reg.counter("datastore.prefetch.stall").value - s0
        strip = (lambda t: "\n".join(
            l for l in t.splitlines() if not l.startswith("[")))
        blk = {"rounds": sr,
               "assembled_rounds_per_sec": round(a_rps, 3),
               "streamed_rounds_per_sec": round(s_rps, 3),
               "streamed_vs_assembled":
                   float(f"{s_rps / max(a_rps, 1e-9):.3g}"),
               "shard_passes": passes,
               "shards": int(reg.gauge("stream.shards").value),
               "stall_ratio":
                   round(stalls / max(hits + stalls, 1), 4),
               "peak_device_mb":
                   reg.gauge("stream.peak_device_mb").value,
               "peak_staging_mb":
                   reg.gauge("stream.peak_staging_mb").value,
               "byte_identical":
                   strip(bst_a.model_to_string())
                   == strip(bst_s.model_to_string())}
        if not blk["byte_identical"]:
            raise RuntimeError(
                "streamed bench model diverged from assembled")
        streaming = blk
        _log(f"streaming bench: {blk['streamed_rounds_per_sec']} "
             f"rounds/s streamed vs "
             f"{blk['assembled_rounds_per_sec']} assembled "
             f"({blk['streamed_vs_assembled']}x) over "
             f"{passes} shard passes, stall ratio "
             f"{blk['stall_ratio']}, peak device "
             f"{blk['peak_device_mb']} MB")
    # mini-soak acceptance run (--soak): the composed production plane
    # (datastore → daemon → gate → registry → tenancy → HTTP) under
    # closed-loop multi-tenant traffic with an append-triggered hot-swap,
    # drift injection and one chaos rung-kill, then the step-load
    # capacity ladder — the byte-oracle / SLO / expectation verdicts and
    # the fitted capacity model land in one BENCH `soak` block
    if "--soak" in sys.argv:
        from lightgbm_tpu.soak import run_mini_soak
        soak_params = {}
        if spool_dir:
            soak_params["telemetry_spool_dir"] = spool_dir
        blk = run_mini_soak(params=soak_params)
        soak = blk
        _log(f"soak bench: {blk['requests']} requests, "
             f"{blk['byte_inconsistent']} byte-inconsistent, "
             f"{blk['expect_pass']}/{blk['expect_pass'] + blk['expect_fail']}"
             f" expectations, {blk['slo_breach']} SLO breaches, "
             f"capacity "
             f"{blk.get('capacity', {}).get('rows_per_sec_peak')}"
             f" rows/s peak")
    _emit(rounds_per_sec, n, devs, auc=auc, pred=(dev_rps, host_rps),
          telemetry=telemetry.REGISTRY.snapshot(),
          flight=bst.flight_summary(), pipeline=pipeline,
          serving=serving, streaming=streaming,
          memledger=_memledger_block(), soak=soak)
    # self-contained spool entry: the registry snapshot rides the stream
    # as one `metrics` event, so aggregate() can roll this process into
    # the fleet metrics without the BENCH JSON line
    telemetry.TRACER.emit_metrics_snapshot()
    telemetry.TRACER.flush()


if __name__ == "__main__":
    main()
