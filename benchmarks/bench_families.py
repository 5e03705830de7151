"""Per-capability-family performance rows (VERDICT r4 #5).

The reference publishes one perf table per capability family
(docs/Experiments.rst: Higgs binary, MS-LTR lambdarank, Criteo
categorical, Epsilon GOSS/DART); this repo's bench historically
measured exactly one shape (Higgs-like binary).  This script adds one
row per family on synthetic data of the family's shape:

  lambdarank — MSLR-Web30K-like: ~136 features, graded 0-4 relevance,
      ~120-doc queries.  Prices the padded-segment ranking design.
      Reports rounds/s + NDCG@10.
  categorical_efb — Criteo-like: 13 numeric + 26 high-cardinality
      categorical columns (EFB bundles the sparse ones).  Reports
      rounds/s + AUC.
  goss / dart — Epsilon-style boosting-mode rows on the Higgs shape.
      Reports rounds/s + AUC.
  binary — the headline Higgs-like shape, same harness, for a
      same-script baseline row.

Each family runs in a KILLABLE subprocess with a per-family timeout (a
hung family costs one row, not the table), ordered
most-important-first.  CPU-measured rows are labeled by platform and
are floors, not TPU claims.

Usage: python benchmarks/bench_families.py [N] [ROUNDS] [families...]
"""
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

N = int(sys.argv[1]) if len(sys.argv) > 1 else 500_000
ROUNDS = int(sys.argv[2]) if len(sys.argv) > 2 else 32
PER_FAMILY_TIMEOUT = float(os.environ.get("SWEEP_TIMEOUT", 600))

FAMILIES = ["lambdarank", "categorical_efb", "goss", "dart", "binary"]

# the SHIPPED bench wave knobs — single-sourced from configs_r4 so the
# family rows always measure the same config as the headline bench
from configs_r4 import CONFIGS, SHIPPED  # noqa: E402

WAVE = dict(CONFIGS[SHIPPED])


def make_ranking(n_rows, n_feat=136, docs_per_query=120, seed=7):
    """MSLR-like synthetic ranking set: relevance 0-4 driven by a few
    informative columns + noise, fixed-ish query sizes."""
    import numpy as np
    rng = np.random.RandomState(seed)
    X = rng.randn(n_rows, n_feat).astype(np.float32)
    score = (X[:, 0] + 0.8 * X[:, 1] - 0.5 * X[:, 2]
             + 0.4 * X[:, 3] * X[:, 4] + 0.7 * rng.randn(n_rows))
    # graded relevance by within-dataset quantiles (skewed like LTR data)
    qs = np.quantile(score, [0.55, 0.75, 0.9, 0.97])
    y = np.digitize(score, qs).astype(np.float64)
    sizes = []
    left = n_rows
    while left > 0:
        s = min(left, max(20, int(rng.normal(docs_per_query, 25))))
        sizes.append(s)
        left -= s
    return X, y, np.asarray(sizes, dtype=np.int64)


def make_criteo_like(n_rows, seed=11):
    """13 numeric + 26 categorical columns; a few categoricals are
    high-cardinality (up to ~10k levels), most are small — the shape
    EFB + categorical splits are built for."""
    import numpy as np
    rng = np.random.RandomState(seed)
    num = rng.lognormal(0.0, 1.0, (n_rows, 13)).astype(np.float32)
    cards = [3, 4, 8, 12, 16, 24, 32, 50, 64, 100, 120, 200, 300, 400,
             500, 700, 1000, 1500, 2000, 3000, 4000, 6000, 8000, 10000,
             40, 80]
    cats = np.stack([rng.randint(0, c, n_rows) for c in cards],
                    axis=1).astype(np.float32)
    w = rng.randn(13) * 0.4
    score = num @ w
    # inject signal through a few categorical columns (hashed effect)
    for j, c in ((0, 3), (5, 24), (17, 1500)):
        eff = rng.randn(c) * 0.5
        score = score + eff[cats[:, j].astype(np.int64)]
    y = (score + rng.randn(n_rows) > np.median(score)).astype(np.float64)
    X = np.concatenate([num, cats], axis=1)
    return X, y, list(range(13, 39))


def child(family: str) -> None:
    import numpy as np

    import bench
    import lightgbm_tpu as lgb
    from lightgbm_tpu.booster import Booster
    from lightgbm_tpu.metrics import _auc
    from lightgbm_tpu.utils.profile import timeit_rounds

    import jax
    devs = jax.devices()
    plat = f"{devs[0].platform}x{len(devs)}"
    n_eval = max(50_000, N // 10)
    extra_metrics = {}

    if family == "lambdarank":
        X, y, sizes = make_ranking(N + n_eval)
        # split on a query boundary so train and eval groups stay whole
        cut_q = int(np.searchsorted(np.cumsum(sizes), N))
        if cut_q == 0 or cut_q >= len(sizes):
            sys.exit(f"lambdarank family needs N >> one query "
                     f"(~120 docs); got N={N}")
        cut = int(np.cumsum(sizes)[cut_q - 1])
        Xt, yt, gt = X[:cut], y[:cut], sizes[:cut_q]
        Xe, ye, ge = X[cut:], y[cut:], sizes[cut_q:]
        assert ge.sum() == len(ye), (ge.sum(), len(ye))
        params = {"objective": "lambdarank", "num_leaves": 31,
                  "max_bin": 255, "learning_rate": 0.1, "verbosity": -1,
                  "lambdarank_truncation_level": 30}
        ds = lgb.Dataset(Xt, label=yt, group=gt)
        bst = Booster(params=params, train_set=ds)
        rep = timeit_rounds(bst, ROUNDS)
        from lightgbm_tpu.metrics import _make_ndcg
        qb = np.concatenate([[0], np.cumsum(ge)])
        ndcg = _make_ndcg([10], [2 ** i - 1 for i in range(32)])(
            bst.predict(Xe, raw_score=True), ye, None, qb)
        extra_metrics["ndcg@10"] = round(float(ndcg[0][1]), 5)
    elif family == "categorical_efb":
        X, y, cat_idx = make_criteo_like(N + n_eval)
        Xt, yt, Xe, ye = X[:N], y[:N], X[N:], y[N:]
        params = {"objective": "binary", "num_leaves": 31,
                  "max_bin": 255, "learning_rate": 0.1, "verbosity": -1,
                  **WAVE}
        ds = lgb.Dataset(Xt, label=yt, categorical_feature=cat_idx)
        bst = Booster(params=params, train_set=ds)
        rep = timeit_rounds(bst, ROUNDS)
        extra_metrics["auc"] = round(float(_auc(
            bst.predict(Xe, raw_score=True), ye, None, None)), 5)
    else:  # goss / dart / binary on the Higgs shape
        X, y = bench._make_higgs_like(N + n_eval, bench.F)
        Xt, yt, Xe, ye = X[:N], y[:N], X[N:], y[N:]
        params = {"objective": "binary", "num_leaves": 31,
                  "max_bin": 255, "learning_rate": 0.1, "verbosity": -1,
                  **WAVE}
        if family == "goss":
            params["boosting"] = "goss"
        elif family == "dart":
            params.update(boosting="dart", drop_rate=0.1)
        ds = lgb.Dataset(Xt, label=yt)
        bst = Booster(params=params, train_set=ds)
        rep = timeit_rounds(bst, ROUNDS)
        extra_metrics["auc"] = round(float(_auc(
            bst.predict(Xe, raw_score=True), ye, None, None)), 5)

    print("RESULT " + json.dumps({
        "family": family, "platform": plat, "n": N,
        "grow_policy": bst._grow_policy,
        "rounds_per_sec": rep["rounds_per_sec"],
        "warmup_compile_sec": rep["warmup_compile_sec"],
        "hist_impl": rep["hist_impl"], **extra_metrics}), flush=True)


def main() -> None:
    names = sys.argv[3:] or FAMILIES
    unknown = set(names) - set(FAMILIES)
    if unknown:
        sys.exit(f"unknown families: {sorted(unknown)} (known: {FAMILIES})")
    results = []
    for name in names:
        t0 = time.time()
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 str(N), str(ROUNDS), "--child", name],
                capture_output=True, text=True,
                timeout=PER_FAMILY_TIMEOUT, cwd=ROOT)
        except subprocess.TimeoutExpired:
            print(f"[families] {name}: TIMED OUT "
                  f"(>{PER_FAMILY_TIMEOUT:.0f}s)", flush=True)
            continue
        line = next((ln for ln in r.stdout.splitlines()
                     if ln.startswith("RESULT ")), None)
        if line:
            res = json.loads(line[len("RESULT "):])
            results.append(res)
            print(f"[families] {name}: {res['rounds_per_sec']} r/s "
                  f"({res['platform']}, {time.time() - t0:.0f}s total)",
                  flush=True)
        else:
            print(f"[families] {name}: FAILED rc={r.returncode}: "
                  f"{r.stderr.strip()[-400:]}", flush=True)
    print("FAMILIES " + json.dumps(results), flush=True)


if __name__ == "__main__":
    if "--child" in sys.argv:
        child(sys.argv[sys.argv.index("--child") + 1])
    else:
        main()
