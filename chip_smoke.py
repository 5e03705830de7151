"""Chip smoke: train and serve end to end on the TPU, through the entry
points a user calls, and fail loudly if anything on that path hid the
device.

    python chip_smoke.py              # on the chip, through the chip tool
    python chip_smoke.py --dry-run    # tiny shape, CPU, Pallas interpret mode

ONE process, the only one that touches JAX.  Without `--dry-run` the first
device must be a TPU or the script exits 2 before it measures or prints
anything.  Nothing selects `--dry-run` automatically.

Phases (a failed check is collected and fails the run at the end; an
exception ends it at once — no `except` here logs and carries on):

  train  `lgb.train` on the bench shape (`bench._make_higgs_like` seed 77,
         2M x 28, max_bin=255, num_leaves=31, `benchmarks/configs_r4.py`
         SHIPPED): one warm-up chunk through `lgb.train`, two timed
         16-round chunks through the `update_many` it calls, then one
         16-round chunk at the default `tree_grow_policy=leafwise`.
  serve  `ServingClient` over the booster just trained: 1, 256 and 4096
         rows, then `/predict` and `/healthz` through `make_server`.
  multichip  with >= 4 devices: the same job with `tree_learner=data` over
         4 chips, then `ShardedServingRuntime` with 4 replicas.

Every number printed is a smoke observation, not a benchmark: one run, no
repeats, tracing spans on.  The summary line ends `"claim": null`; the
last line is the device record the driver's contract asks for.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import threading
import time
import urllib.request

CHUNK = 16
#: the driver allows 1200 s: past this the script dumps every thread's
#: stack and exits 1 itself, rather than be killed without a word
DEADLINE_S = 1100
#: held-out AUC floor at the full shape after 48 wave rounds.  Source: a
#: CPU run of this repo at small size scored 0.8831 at 100k rows / 32
#: rounds (BENCH_r05.json); 2M rows / 48 rounds must not do worse.
AUC_FLOOR = 0.88
#: `--dry-run` floor (2048 rows, 7 leaves, 31 bins): the dry run on CPU
#: scores 0.861 after 48 rounds; the floor only catches a broken model.
AUC_FLOOR_DRY = 0.80
#: `predict(device_predict=True)` (f32 device sum) vs the f64 host walk
PREDICT_TOL = 1e-3
#: four-chip AUC must sit this close to the one-chip phase
MULTICHIP_AUC_TOL = 0.002

#: `fallback.*` events this run may emit, each with the reason it is
#: tolerated.  Anything else fails the smoke.
ALLOWED_FALLBACKS = {}

FAILURES = []


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> bool:
    """Record a failed expectation; the run goes on and exits 1."""
    if not ok:
        FAILURES.append(what)
        say(f"CHECK FAILED: {what}")
    return bool(ok)


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny shape on CPU with Pallas in interpret mode "
                         "(exercises the script, proves nothing about the "
                         "chip)")
    args = ap.parse_args()
    dry = args.dry_run
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if dry:
        say("=== DRY RUN === tiny shape, Pallas interpret mode: this says "
            "the script runs, nothing about the chip")
    elif device["platform"] != "tpu":
        print(f"chip_smoke: jax.devices()[0].platform is "
              f"{device['platform']!r} ({device['kind']}), not 'tpu' — "
              "nothing was run; send this script through the chip tool "
              "(or pass --dry-run to exercise it on CPU)",
              file=sys.stderr, flush=True)
        return 2

    import numpy as np

    root = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, root)
    import jaxlib
    import lightgbm_tpu as lgb
    from lightgbm_tpu import native, telemetry
    from lightgbm_tpu.metrics import _auc
    from lightgbm_tpu.utils.env import setup_compile_cache

    import bench
    from benchmarks import configs_r4

    cache_dir = setup_compile_cache()
    cache_before = cache_entries(cache_dir)
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "absent"
    say(f"device: platform={device['platform']} "
        f"device_kind={device['kind']} count={device['count']}")
    say(f"versions: jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu_version}")
    say(f"compile_cache: dir={cache_dir} entries_before={cache_before}")
    say(f"native: {'loaded' if native.get_lib() is not None else 'numpy'}")

    events = telemetry.TRACER.add_sink(telemetry.MemorySink())
    telemetry.install_compile_listener()
    reg = telemetry.REGISTRY
    recompiles = reg.counter("jit.recompiles")

    # ------------------------------------------------------------- data
    if dry:
        n, n_eval, shape = 2048, 512, {"num_leaves": 7, "max_bin": 31}
        # the kernel path a TPU picks by itself (`hist_impl=auto`) has to
        # be named off-TPU, where it runs interpreted
        kernel = {"hist_impl": "pallas", "hist_interpret": True}
        auc_floor = AUC_FLOOR_DRY
    else:
        n, n_eval = 2_000_000, 200_000
        shape = {"num_leaves": bench.NUM_LEAVES, "max_bin": bench.MAX_BIN}
        kernel = {}
        auc_floor = AUC_FLOOR
    (X, y), dt = timed(lambda: bench._make_higgs_like(n + n_eval, bench.F))
    X_eval, y_eval = X[n:], y[n:]
    X, y = X[:n], y[:n]
    say(f"data: {X.shape} + {n_eval} held out, built in {dt:.1f}s")

    base = {"objective": "binary", "learning_rate": 0.1, "verbosity": -1,
            **shape, **kernel}
    wave_params = {**base, **configs_r4.CONFIGS[configs_r4.SHIPPED]}
    ds, dt = timed(lambda: lgb.Dataset(X, label=y).construct())
    say(f"dataset: binned in {dt:.1f}s")

    # ------------------------------------------------------- train: wave
    say(f"--- train: {configs_r4.SHIPPED} ---")
    bst, warm_s = timed(lambda: lgb.train(wave_params, ds,
                                          num_boost_round=CHUNK))
    impl = bst._grower_spec.hist_impl
    say(f"train.wave: policy={bst._grow_policy} hist_impl={impl} "
        f"interpret={bst._grower_spec.hist_interpret}")
    say(f"train.wave: warm-up chunk ({CHUNK} rounds, compile included) "
        f"{warm_s:.2f}s")
    check(bst._grow_policy == "wave",
          f"tree_grow_policy=wave resolved to {bst._grow_policy!r}")
    check(impl in ("pallas", "pallas_q"),
          f"hist_impl resolved to {impl!r}, not the Pallas family")
    check(dry or not bst._grower_spec.hist_interpret,
          "the Pallas kernels ran in interpret mode on the TPU")

    # timed chunk 1: `update_many`, which ends in the device_get of the
    # chunk's trees.  timed chunk 2: the same dispatch, first waited for
    # with block_until_ready(score), then harvested — on the r3 backend
    # block_until_ready returned early (PROFILE.md r3b); ROADMAP S0 needs
    # the answer for this chip once
    compiles0 = recompiles.value
    _, chunk_get_s = timed(lambda: bst.update_many(CHUNK))
    spec = bst._make_bulk_spec()
    t0 = time.perf_counter()
    pending = bst._dispatch_chunk(spec)
    jax.block_until_ready(bst._train_score)
    chunk_bur_s = time.perf_counter() - t0
    bst._harvest_chunk(pending)
    chunk_bur_total_s = time.perf_counter() - t0
    in_window_compiles = int(recompiles.value - compiles0)
    say(f"train.wave: timed chunk 1 ({CHUNK} rounds, ended by device_get "
        f"of the trees) {chunk_get_s:.3f}s")
    say(f"train.wave: timed chunk 2 ({CHUNK} rounds) "
        f"block_until_ready(score) after {chunk_bur_s:.3f}s, trees on "
        f"host after {chunk_bur_total_s:.3f}s")
    say(f"block_until_ready: returns at {chunk_bur_s / chunk_get_s:.2f}x "
        "of a device_get-ended chunk (~1 = honest, ~0 = returns early)")
    say(f"train.wave: compilations inside the timed chunks: "
        f"{in_window_compiles}")
    check(in_window_compiles == 0,
          f"{in_window_compiles} compilation(s) inside the timed chunks")
    check(bst.current_iteration() == 3 * CHUNK,
          f"trained {bst.current_iteration()} rounds, expected {3 * CHUNK}")
    # `lgb.train` stamped best_iteration after the warm-up chunk, and
    # predict/serve stop there by default: move it past the timed chunks
    bst.best_iteration = bst.current_iteration()

    host_raw, dt = timed(lambda: bst.predict(X_eval, raw_score=True))
    auc = float(_auc(host_raw, y_eval, None, None))
    say(f"train.wave: held-out AUC {auc:.4f} after "
        f"{bst.current_iteration()} rounds (n_eval={n_eval}, floor "
        f"{auc_floor}; host walk {dt:.1f}s)")
    check(np.isfinite(host_raw).all(), "non-finite raw scores")
    check(auc >= auc_floor, f"AUC {auc:.4f} under the floor {auc_floor}")
    dev_raw = bst.predict(X_eval, raw_score=True, device_predict=True)
    dev_diff = float(np.max(np.abs(dev_raw - host_raw)))
    say(f"train.wave: device_predict vs f64 host walk max |diff| "
        f"{dev_diff:.3g} (tolerance {PREDICT_TOL})")
    check(dev_raw.shape == host_raw.shape and dev_diff <= PREDICT_TOL,
          f"device_predict differs from the host walk by {dev_diff:.3g}")

    # --------------------------------------------------- train: leafwise
    say("--- train: default tree_grow_policy (leafwise) ---")
    bst_lw, lw_s = timed(lambda: lgb.train(base, ds, num_boost_round=CHUNK))
    lw_impl = bst_lw._grower_spec.hist_impl
    lw_auc = float(_auc(bst_lw.predict(X_eval, raw_score=True), y_eval,
                        None, None))
    say(f"train.leafwise: policy={bst_lw._grow_policy} hist_impl={lw_impl} "
        f"one chunk ({CHUNK} rounds, compile included) {lw_s:.2f}s "
        f"AUC {lw_auc:.4f}")
    check(bst_lw._grow_policy == "leafwise",
          f"default policy resolved to {bst_lw._grow_policy!r}")
    check(lw_impl in ("pallas", "pallas_q"),
          f"leafwise hist_impl resolved to {lw_impl!r}")
    check(lw_auc >= auc_floor - 0.05,
          f"leafwise AUC {lw_auc:.4f} after {CHUNK} rounds")

    # ------------------------------------------------------------ serve
    say("--- serve ---")
    serving = serve_phase(bst, X_eval, dry)

    # ----------------------------------------------------------- memory
    stats = devs[0].memory_stats()
    peak = (stats or {}).get("peak_bytes_in_use")
    ledger_source = telemetry.MEMLEDGER.reconcile()["source"]
    sample = telemetry.sample_memory("smoke") or {}
    say(f"memory: device.memory_stats() peak_bytes_in_use={peak} "
        f"ledger_reconcile_source={ledger_source} "
        f"recorder_source={sample.get('source')}")
    check(dry or (peak and ledger_source == "memory_stats"
                  and sample.get("source") == "memory_stats"),
          "the memory ledger did not take its memory_stats branch on the "
          "TPU")

    # -------------------------------------------------------- multichip
    multichip = None
    if len(devs) >= 4:
        say("--- multichip: 4 devices ---")
        multichip = multichip_phase(bst, auc, wave_params, ds, X_eval,
                                    y_eval, devs[:4])
    else:
        say(f"multichip: skipped ({len(devs)} device)")

    # -------------------------------------------------------- fallbacks
    fallbacks = [e for e in events.events
                 if e.get("ev") == "event"
                 and str(e.get("name", "")).startswith("fallback.")]
    say(f"fallback.events={int(reg.counter('fallback.events').value)}")
    for e in fallbacks:
        extra = {k: v for k, v in e.items()
                 if k not in ("ev", "name", "ts", "t", "pid", "tid")}
        say(f"  {e['name']}: {json.dumps(extra, default=str)[:700]}")
        check(e["name"] in ALLOWED_FALLBACKS,
              f"unexpected fallback event {e['name']}")
    check(int(reg.counter("fallback.events").value) == len(fallbacks),
          "fallback.events counter disagrees with the events seen")

    cache_after = cache_entries(cache_dir)
    say(f"compile_cache: entries_after={cache_after} "
        f"(+{cache_after - cache_before})")

    summary = {
        "ok": not FAILURES, "dry_run": dry, "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version},
        "rows": n, "policy": bst._grow_policy, "hist_impl": impl,
        "warmup_s": round(warm_s, 2),
        "chunk_s_device_get": round(chunk_get_s, 3),
        "chunk_s_block_until_ready": round(chunk_bur_s, 3),
        "in_window_compiles": in_window_compiles,
        "auc": round(auc, 4), "leafwise_chunk_s": round(lw_s, 2),
        "serving": serving, "multichip": multichip,
        "fallbacks": sorted({e["name"] for e in fallbacks}),
        "cache": {"dir": cache_dir, "before": cache_before,
                  "after": cache_after},
        "peak_bytes_in_use": peak, "failures": FAILURES,
        "note": "smoke observations from one run, not benchmark results",
        "claim": None,
    }
    say("summary: " + json.dumps(summary, default=str))
    if FAILURES:
        say(f"FAILED: {len(FAILURES)} check(s): " + "; ".join(FAILURES))
        return 1
    if dry:
        say("=== DRY RUN passed === (no device record: nothing ran on a "
            "chip)")
        return 0
    say(json.dumps({"ok": True, "device": device}))
    return 0


def serve_phase(bst, X_eval, dry) -> dict:
    """`ServingClient` + `make_server` over `bst`: every response must be
    `bst.predict`'s bytes, answered from a device rung."""
    import numpy as np
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.serving import ServingClient
    from lightgbm_tpu.serving.http import make_server

    reg = telemetry.REGISTRY
    rung_counters = ("serve.bounded", "serve.compiled", "serve.device_sum",
                     "serve.slot_path")

    def host_walks() -> int:
        return int(sum(c.value for c in reg.counter_family(
            "serve.host_walk")))

    before = {k: reg.counter(k).value for k in rung_counters}
    walks0 = host_walks()
    client, load_s = timed(lambda: ServingClient(bst))
    try:
        say(f"serve: ServingClient loaded + warmed every bucket in "
            f"{load_s:.1f}s")
        for rows in (1, 256, 4096):
            Xr = np.ascontiguousarray(X_eval[:rows], np.float64)
            for raw in (False, True):
                got, dt = timed(lambda: client.predict(Xr, raw_score=raw))
                want = bst.predict(Xr, raw_score=raw)
                same = (got.shape == want.shape and got.dtype == want.dtype
                        and got.tobytes() == want.tobytes())
                say(f"serve: {rows} rows raw_score={raw}: "
                    f"{'bit-identical' if same else 'DIFFERENT'} "
                    f"({dt * 1e3:.1f} ms)")
                check(same, f"ServingClient.predict({rows} rows, "
                            f"raw_score={raw}) != bst.predict")

        server = make_server(client, port=0)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        try:
            url = "http://127.0.0.1:%d" % server.server_address[1]
            Xh = np.ascontiguousarray(X_eval[:64], np.float64)
            req = urllib.request.Request(
                url + "/predict",
                data=json.dumps({"rows": Xh.tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                body = json.loads(r.read())
            http_same = (np.asarray(body["predictions"], np.float64).tobytes()
                         == np.asarray(bst.predict(Xh),
                                       np.float64).tobytes())
            say(f"serve: HTTP /predict 64 rows: "
                f"{'bit-identical' if http_same else 'DIFFERENT'}")
            check(http_same, "HTTP /predict != bst.predict")
            with urllib.request.urlopen(url + "/healthz", timeout=60) as r:
                health = json.loads(r.read())
            say("serve: /healthz " + json.dumps(health)[:900])
            check(health.get("status") == "ok", "/healthz status != ok")
        finally:
            server.shutdown()
            server.server_close()
            thread.join(timeout=30)
            check(not thread.is_alive(), "HTTP server thread did not stop")

        rungs = client.status()["rungs"]["default"]
        answered = {k.split(".", 1)[1]: int(reg.counter(k).value - before[k])
                    for k in rung_counters}
        walks = host_walks() - walks0
        live = [k for k in ("bounded", "compiled", "device_sum")
                if rungs[k]]
        say(f"serve: rungs live {live}; chunks answered per rung "
            f"{answered}; host_walk {walks}")
        for rung, why in rungs["disabled"].items():
            say(f"serve: {rung}_disabled cause={why['cause']} "
                f"detail={why['detail'][:300]!r}")
        check(walks == 0, f"serve.host_walk rose by {walks}")
        check(rungs["compiled"] or rungs["device_sum"],
              "neither the compiled nor the device_sum rung is live")
        check(answered["compiled"] + answered["device_sum"] > 0
              and answered["slot_path"] == 0 and answered["bounded"] == 0,
              f"requests were not answered from compiled/device_sum: "
              f"{answered}")
        check(dry or rungs["compiled"]
              or rungs["disabled"].get("compiled", {}).get("cause")
              == "compile",
              "the compiled rung is off on the TPU for a cause other than "
              "a compile refusal: "
              f"{rungs['disabled'].get('compiled')}")
    finally:
        client.close()
    return {"rung": "compiled" if answered["compiled"] else "device_sum",
            "answered": answered, "host_walk": walks,
            "disabled": rungs["disabled"], "load_s": round(load_s, 1)}


def multichip_phase(bst1, auc1, wave_params, ds, X_eval, y_eval,
                    devs) -> dict:
    """Data-parallel training and sharded serving over four real chips."""
    import jax
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.metrics import _auc
    from lightgbm_tpu.serving import ShardedServingRuntime

    params = {**wave_params, "tree_learner": "data", "num_machines": 4}
    bst, dt = timed(lambda: lgb.train(params, ds,
                                      num_boost_round=3 * CHUNK))
    mesh = bst._mesh
    say(f"multichip.train: {3 * CHUNK} rounds in {dt:.1f}s (compile "
        f"included) policy={bst._grow_policy} "
        f"hist_impl={bst._grower_spec.hist_impl} mesh="
        f"{None if mesh is None else dict(mesh.shape)}")
    if not check(mesh is not None and mesh.devices.size == 4,
                 "tree_learner=data did not build a 4-device mesh (serial "
                 "fallback?)"):
        return {"ok": False}
    bins_devs = len(bst._train_bins.sharding.device_set)
    score_devs = len(bst._train_score.sharding.device_set)
    say(f"multichip.train: bins on {bins_devs} devices, scores on "
        f"{score_devs} devices")
    check(bins_devs == 4, f"bins sharded over {bins_devs} devices")
    check(score_devs == 4, f"scores sharded over {score_devs} devices")
    auc = float(_auc(bst.predict(X_eval, raw_score=True), y_eval, None,
                     None))

    def strip(b):
        return "\n".join(ln for ln in b.model_to_string().splitlines()
                         if not ln.startswith("["))

    identical = strip(bst) == strip(bst1)
    say(f"multichip.train: AUC {auc:.4f} (one chip {auc1:.4f}); model text "
        f"identical to one chip: {identical}")
    check(abs(auc - auc1) <= MULTICHIP_AUC_TOL,
          f"4-chip AUC {auc:.4f} vs 1-chip {auc1:.4f}")

    rt, dt = timed(lambda: ShardedServingRuntime(bst, devices=list(devs)))
    planes_on = []
    for rep in rt.replicas:
        ex = rep._state.export
        planes_on.append(sorted({d.id for a in jax.tree.leaves(
            (ex["stacked"], ex.get("value_hi")))
            if hasattr(a, "devices") for d in a.devices()}))
    say(f"multichip.serve: {rt.num_replicas} replicas in {dt:.1f}s, planes "
        f"on devices {planes_on}")
    check(rt.num_replicas == 4, f"{rt.num_replicas} serving replicas")
    check(planes_on == [[d.id] for d in devs],
          f"replica planes not one-per-device: {planes_on}")
    rows = 4 * rt.max_batch_rows
    Xr = np.ascontiguousarray(
        np.tile(X_eval, (-(-rows // len(X_eval)), 1))[:rows], np.float64)
    reg_rows = [f"serve.replica.{i}.rows" for i in range(4)]
    r0 = [telemetry.REGISTRY.counter(k).value for k in reg_rows]
    got = rt.predict(Xr)
    routed = [int(telemetry.REGISTRY.counter(k).value - a)
              for k, a in zip(reg_rows, r0)]
    want = bst.predict(Xr)
    same = got.tobytes() == want.tobytes()
    say(f"multichip.serve: {rows} rows striped {routed}; "
        f"{'bit-identical' if same else 'DIFFERENT'} to bst.predict")
    check(all(r > 0 for r in routed), f"stripe missed a replica: {routed}")
    check(same, "sharded serving != bst.predict")
    return {"ok": True, "auc": round(auc, 4), "model_identical": identical,
            "routed": routed}


if __name__ == "__main__":
    sys.exit(main())
