"""Run one cell of the benchmark once.

    python3 -m perfbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  `--seed` draws the hold-out rows and the
nodes the reference checks; the training rows are the configuration's
(`data.population_seed`), so every run grows the same trees.  A builder may
add `--population-seed <n>` to train on another population; the driver
never does.  Finds `perfbench/workloads/<name>.json`, its
configuration and (with `--trace 1`) every per-layer metric that lists the
cell; exits 2 before measuring anything where JAX finds no TPU or fewer
chips than the cell asks for.  The last line of standard output is one JSON
object with `correct`, `attempted`, `failed`, `metrics`, `device`, with
`--trace 1` also `breakdown` and `program_metrics` (the metrics of the
program's own record that `BENCHMARK.json` does not list yet), and last
`compared`: each number the comparison read beside its limit.  See
`perfbench/README.md`.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()        # process start, to the interpreter's start-up

import argparse                 # noqa: E402
import json                     # noqa: E402
import math                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

from . import manifest          # noqa: E402

BIG = 1e30                      # stands for "no finite number" in JSON


def say(msg: str) -> None:
    print(msg, flush=True)


def _finite(x: float) -> float:
    return float(x) if math.isfinite(x) else BIG


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def default_hooks() -> SimpleNamespace:
    """What a test or a builder's tool may replace: the look for a chip,
    the persistent compile cache (a test run must not create
    `<checkout>/.jax_cache`), how the booster is built, what is done to
    the trees before they are compared, and what is done with the job's
    result before it is reduced (`program_run` keeps it)."""
    return SimpleNamespace(
        require_chip=True,
        compile_cache=True,
        make_booster=lambda lgb, params, ds: lgb.Booster(params=params,
                                                         train_set=ds),
        alter_trees=lambda trees: None,
        on_result=lambda result: None)


def _read(metrics: List[dict], ctx: dict) -> Dict[str, Any]:
    from . import readers
    out = {}
    for m in metrics:
        v = readers.read(m, ctx)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def per_layer(cell_name: str, result: dict, device_kind: str,
              bench_dir: str) -> Dict[str, Any]:
    """Reduce the traced run to the cell's per-layer metrics, the metrics
    of `program_metrics/` that list it, the device's busy time and the
    breakdown: the heaviest operations, the longest idle gaps named
    `<annotation>/<innermost program span>`, device-idle ms a round by
    program span, and the grower's device seconds by phase scope."""
    from . import program_readers as P, trace as T
    path = result.get("trace_file")
    tr = T.load(path, P.span_names(result.get("program"))) if path else None
    try:
        peaks = manifest.peaks(device_kind, bench_dir)
    except KeyError:
        if tr is not None and tr.devices:
            raise
        peaks = {}
    units = result.get("units_in_window", {})
    ctx = {"trace": tr, "trace_file": path, "program": result.get("program"),
           "counters": result.get("counters", {}),
           "memory": {"peak_bytes": result.get("memory_peak_bytes")},
           "units": units, "shape": result.get("shape", {}), "peaks": peaks}
    out: Dict[str, Any] = {
        "metrics": _read(manifest.layer_metrics(cell_name, bench_dir), ctx),
        "program_metrics": _read(manifest.layer_metrics(
            cell_name, bench_dir, manifest.PROGRAM_METRICS), ctx)}
    if tr is not None:
        lo, hi = T.window_of(tr)
        rounds = max(units.get("rounds", 0), 1)
        by_span = sorted(P.gap_ns_by_span(ctx).items(), key=lambda kv: -kv[1])
        phases = sorted((P.phase_seconds(ctx, "^jit_grow$") or {}).items(),
                        key=lambda kv: -kv[1])
        out["busy_s"] = P.busy_s(ctx)
        out["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in T.top_ops(tr)],
            "idle_gaps": [[k, v] for k, v in P.idle_gaps(ctx)],
            "host_gap_ms_by_span": [[k, v / 1e6 / rounds]
                                    for k, v in by_span[:10]],
            "phase_s": [[k or "unattributed", v] for k, v in phases[:10]]}
    return out


def main(argv: Optional[List[str]] = None, hooks: Optional[SimpleNamespace]
         = None, bench_dir: str = manifest.HERE) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--population-seed", type=int, default=None,
                    help="train on this population's rows and not on the "
                         "configuration's (a builder's check that a gain "
                         "holds on other trees; the driver never passes it)")
    ap.add_argument("--bench-dir", default=bench_dir,
                    help="where workloads/, configs/ and layer_metrics/ "
                         "are (tests and fixtures; default perfbench/)")
    args = ap.parse_args(argv)
    bench_dir = args.bench_dir
    hooks = hooks or default_hooks()

    cell = manifest.workload(args.workload, bench_dir)
    config = manifest.config(cell["config"], bench_dir)
    if args.population_seed is not None:
        config = manifest.with_population(config, args.population_seed)
    job = manifest.load_module("jobs", cell["job"])

    if manifest.ROOT not in sys.path:
        sys.path.insert(0, manifest.ROOT)
    try:
        import lightgbm_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"perfbench: the program is not in this checkout ({e}); "
              "nothing was run", file=sys.stderr, flush=True)
        return 3
    t_imported = time.perf_counter()
    import jax
    import jaxlib
    devs = jax.devices()
    t_devices = time.perf_counter()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if hooks.require_chip and (device["platform"] != "tpu"
                               or len(devs) < int(cell["chips"])):
        print(f"perfbench: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX reports {device} — nothing was run",
              file=sys.stderr, flush=True)
        return 2

    cache_dir = ""
    if hooks.compile_cache:
        from lightgbm_tpu.utils.env import setup_compile_cache
        cache_dir = setup_compile_cache()
    n_cache = cache_entries(cache_dir)
    say(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    say(f"versions: jax={jax.__version__} jaxlib={jaxlib.__version__}")
    say(f"compile cache: {cache_dir} entries_before={n_cache}")
    say(f"setup: the program imported in {t_imported - T0:.2f} s, the "
        f"device found in {t_devices - t_imported:.2f} s more")
    say(f"cell: {args.workload} config={cell['config']} seed={args.seed} "
        f"population_seed={config['data']['population_seed']} "
        f"seconds={args.seconds} trace={args.trace}")

    ctx = SimpleNamespace(
        cell=cell, config=config, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t0=T0, say=say,
        trace_dir=os.path.join(manifest.ROOT, ".perfbench_trace"),
        make_booster=hooks.make_booster, alter_trees=hooks.alter_trees)
    result = job.run(ctx)
    hooks.on_result(result)
    say(f"compile cache: entries_after={cache_entries(cache_dir)} "
        f"(before {n_cache})")
    say("window: " + json.dumps(result.get("window", {})))

    device["memory_peak_bytes"] = int(result.get("memory_peak_bytes", 0))
    line: Dict[str, Any] = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"])}
    if args.trace:
        t = time.perf_counter()
        pl = per_layer(args.workload, result, device["kind"], bench_dir)
        say(f"trace: reduced in {time.perf_counter() - t:.2f} s")
        line["metrics"] = pl["metrics"]
        if "busy_s" in pl:
            device["busy_s"] = pl["busy_s"]
            device["window_s"] = pl["window_s"]
        line["device"] = device
        if "breakdown" in pl:
            line["breakdown"] = pl["breakdown"]
        if pl["program_metrics"]:     # temporary: manifest.PROGRAM_METRICS
            line["program_metrics"] = pl["program_metrics"]
    else:
        line["metrics"] = {k: {"value": _finite(v["value"]),
                               "unit": v["unit"]}
                           for k, v in result["end_to_end"].items()}
        line["device"] = device
    line["compared"] = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
                        for k, v in result["compared"].items()}
    for k, v in line["compared"].items():
        print(f"compared: {k} value={v['value']:.6g} limit={v['limit']:.6g} "
              f"{'ok' if v['value'] <= v['limit'] else 'OVER'}",
              file=sys.stderr, flush=True)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
