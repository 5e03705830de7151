"""Round-4 TPU speed sweep — one config per killable subprocess.

Each child trains the bench shape (default 2M x 28 / 255 bins / 31
leaves) with `utils.profile.timeit_rounds` (honest device_get-anchored
timing; includes warmup_compile_sec) and prints one JSON line.  The
parent enforces a per-config timeout so a hung config costs one
config, not the sweep.  Run configs ordered most-important-first for
the same reason.

Usage: python benchmarks/sweep_speed_r4.py [N] [ROUNDS] [names...]
"""
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from configs_r4 import BASE, CONFIGS  # noqa: E402 (one shared definition)

N = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000_000
ROUNDS = int(sys.argv[2]) if len(sys.argv) > 2 else 48
PER_CONFIG_TIMEOUT = float(os.environ.get("SWEEP_TIMEOUT", 420))

# speed-sweep default: the TPU-relevant head of the shared table.
# wave_w8_tail16 is the SHIPPED bench config as of r5 (multi-seed
# decider at 500k + 2M, PROFILE.md r5); the r4 floor+auto config and
# strict follow for the speed/AUC trade rows, then the wide-quant
# challengers that the int8 42-slot kernel economics motivate.
SPEED_DEFAULT = ["wave_w8_tail16", "strict", "wave_r3bench+tail",
                 "wave_w28_tail16+quant", "wave_w16_tail16+quant",
                 "wave_w8_tail_auto+quant", "wave_r3bench",
                 "strict+quant"]


def child(name: str) -> None:
    import numpy as np  # noqa: F401

    import bench
    import lightgbm_tpu as lgb
    from lightgbm_tpu.metrics import _auc
    from lightgbm_tpu.utils.profile import timeit_rounds

    import jax
    devs = jax.devices()
    n_eval = max(100_000, N // 10)
    X, y = bench._make_higgs_like(N + n_eval, bench.F)
    X_eval, y_eval = X[N:], y[N:]
    X, y = X[:N], y[:N]
    params = {**BASE, **CONFIGS[name]}
    from lightgbm_tpu.booster import Booster
    bst = Booster(params=params, train_set=lgb.Dataset(X, label=y))
    rep = timeit_rounds(bst, ROUNDS)
    auc = float(_auc(bst.predict(X_eval, raw_score=True),
                     y_eval, None, None))
    print("RESULT " + json.dumps({
        "config": name, "platform": f"{devs[0].platform}x{len(devs)}",
        "n": N, "rounds_per_sec": rep["rounds_per_sec"],
        "warmup_compile_sec": rep["warmup_compile_sec"],
        "hist_impl": rep["hist_impl"], "auc": round(auc, 5)}), flush=True)


def main() -> None:
    names = sys.argv[3:] or SPEED_DEFAULT
    unknown = set(names) - CONFIGS.keys()
    if unknown:
        sys.exit(f"unknown config name(s): {sorted(unknown)} "
                 f"(known: {sorted(CONFIGS)})")
    results = []
    for name in names:
        t0 = time.time()
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 str(N), str(ROUNDS), "--child", name],
                capture_output=True, text=True,
                timeout=PER_CONFIG_TIMEOUT, cwd=ROOT)
        except subprocess.TimeoutExpired:
            print(f"[sweep] {name}: TIMED OUT (>{PER_CONFIG_TIMEOUT:.0f}s) "
                  "— hung?", flush=True)
            continue
        line = next((ln for ln in r.stdout.splitlines()
                     if ln.startswith("RESULT ")), None)
        if line:
            res = json.loads(line[len("RESULT "):])
            results.append(res)
            print(f"[sweep] {name}: {res['rounds_per_sec']} r/s, "
                  f"auc {res['auc']}, warmup {res['warmup_compile_sec']}s "
                  f"({time.time() - t0:.0f}s total)", flush=True)
        else:
            print(f"[sweep] {name}: FAILED rc={r.returncode}: "
                  f"{r.stderr.strip()[-400:]}", flush=True)
    print("SWEEP " + json.dumps(results), flush=True)


if __name__ == "__main__":
    if "--child" in sys.argv:
        child(sys.argv[sys.argv.index("--child") + 1])
    else:
        main()
