"""Perf-regression sentinel: compare two telemetry snapshots.

`python -m lightgbm_tpu telemetry diff <baseline.json> <current.json>`
compares two metrics/flight snapshots (the JSON written by
`scripts/telemetry_snapshot.py`, a BENCH JSON line, or a bare
`REGISTRY.snapshot()` dump) under per-metric **direction + tolerance**
rules and prints a machine-readable verdict:

 - every metric is flattened to a dotted path (`counters.train.rounds`,
   `flight.depth_max`, `timings.span.train.chunk.total_s`, ...);
 - a rule table maps path patterns to a direction (`up_is_bad`,
   `down_is_bad`, `ignore`) and a relative tolerance;
 - a delta beyond tolerance in the bad direction is a **violation**
   (exit 1); beyond tolerance in the good direction is reported as
   *improved* (exit 0); `--warn-timings` downgrades timing-class
   violations to warnings (CI runs on the CPU backend, where absolute
   wall-clock is noise but counter/shape regressions are still real).

STDLIB-ONLY and self-contained (no imports from the sibling telemetry
modules), so a jax-free process can load this file by path.
"""
from __future__ import annotations

import argparse
import fnmatch
import json
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

#: Default relative tolerances by rule class.
DEFAULT_REL_TOL = 0.25       # counters / structural stats
DEFAULT_TIMING_REL_TOL = 1.5  # wall-clock: CI boxes are noisy
ABS_FLOOR = 1e-9             # deltas below this are never violations

#: (path glob, direction, class) — first match wins.  direction:
#:   up_is_bad   — growth beyond tolerance is a regression (timings,
#:                 memory watermarks, recompiles, fallbacks)
#:   down_is_bad — shrinkage beyond tolerance is a regression
#:                 (throughput, eval quality)
#:   ignore      — bookkeeping that moves freely between runs
#: class: "timing" rules use the timing tolerance and are downgradable
#: via --warn-timings; "counter" rules always fail hard.
RULES: List[Tuple[str, str, str]] = [
    # bookkeeping / identity — never a regression by itself
    ("*.ts", "ignore", "counter"),
    ("ts", "ignore", "counter"),
    ("sentinel.*", "ignore", "counter"),
    ("*backend*", "ignore", "counter"),
    ("*monitoring_hooked", "ignore", "counter"),
    ("*samples", "ignore", "counter"),
    ("*ring_depth", "ignore", "counter"),
    ("*last_round", "ignore", "counter"),
    ("*top_features*", "ignore", "counter"),
    ("counters.event.probe.*", "ignore", "counter"),
    # quality / throughput — lower is worse
    ("*rounds_per_sec", "down_is_bad", "timing"),
    ("*est_hbm_gb_per_sec", "down_is_bad", "timing"),
    ("*est_scatter_adds_per_sec", "down_is_bad", "timing"),
    ("*predict_*_rows_per_sec", "down_is_bad", "timing"),
    ("value", "down_is_bad", "timing"),         # BENCH line: rounds/s
    ("vs_baseline", "down_is_bad", "timing"),
    ("*auc*", "down_is_bad", "counter"),
    ("*eval.*.last", "ignore", "counter"),   # direction depends on metric
    ("*eval.*.delta", "ignore", "counter"),
    ("*eval.*.first", "ignore", "counter"),
    ("*eval.*.n", "ignore", "counter"),
    # compile & memory watermarks — higher is worse
    ("*jit.recompiles", "up_is_bad", "counter"),
    ("*compile.recompiles", "up_is_bad", "counter"),
    ("*cache_entries", "up_is_bad", "counter"),
    ("*compile_total_s", "up_is_bad", "timing"),
    # device-memory ledger (ISSUE 18): unattributed bytes growing means
    # allocations escaped the owner classes (an attribution leak);
    # budget-violation counts and the leak-sentinel slope fail hard on
    # growth (slope is wall-clock-derived — timing tolerance); the
    # reconcile walk is background work, and the per-device per-owner
    # attribution gauges are workload shape, not a regression axis
    ("*mem.unattributed_bytes", "up_is_bad", "counter"),
    ("*mem.budget_violation*", "up_is_bad", "counter"),
    ("*mem.leak.slope_mb_per_min", "up_is_bad", "timing"),
    ("*mem.reconcile*", "ignore", "timing"),
    ("*mem.oom.dumps", "up_is_bad", "counter"),
    # watermarks (..peak_bytes, matched below) fail hard on growth;
    # the LIVE per-owner gauges are whatever was resident at snapshot
    # time — scheduling-dependent, not a regression axis
    ("*peak_bytes", "up_is_bad", "counter"),
    ("*mem.dev*", "ignore", "counter"),
    ("*mem.host.*", "ignore", "counter"),
    ("*mem.*", "up_is_bad", "counter"),
    # fallback / forced events — higher is worse
    ("*fallback*", "up_is_bad", "counter"),
    ("*events.*", "up_is_bad", "counter"),
    # pipelined dispatch: depth is a config knob (identity, not a
    # regression axis); the device-idle-gap gauge is wall-clock — a
    # growing gap means the overlap stopped working (the per-chunk
    # timing series under timings.train.pipeline.idle.* is covered by
    # the span rules below)
    ("*pipeline.depth", "ignore", "counter"),
    ("gauges.train.pipeline.device_idle_s", "up_is_bad", "timing"),
    # continuous-training fleet (ISSUE 11): a growing rejected-swap
    # count means candidates stopped clearing the shadow gate (drifted
    # holdout metric, diverging frozen prefix) — fail hard.  Gate
    # latency is wall-clock on the scoring path (timing class); the
    # tenant-count gauge is deployment identity, and the row/retrain/
    # sample counters are workload bookkeeping.  SLO sheds and the
    # error counters (sampler hook, daemon poll, background refresh)
    # fail hard on growth like their serve.* cousins.
    ("*fleet.swap.rejected", "up_is_bad", "counter"),
    ("*fleet.gate.latency*", "up_is_bad", "timing"),
    ("*fleet.gate.fail", "up_is_bad", "counter"),
    ("gauges.fleet.tenants", "ignore", "counter"),
    ("*fleet.shed.slo", "up_is_bad", "counter"),
    ("*fleet.sampler_errors", "up_is_bad", "counter"),
    ("*fleet.poll_errors", "up_is_bad", "counter"),
    ("*serve.auto_refresh_errors", "up_is_bad", "counter"),
    # resilience plane (ISSUE 14): a watchdog firing means a device
    # dispatch blew its deadline, a batcher worker restart means the
    # serving loop crashed, a gate error means a candidate was rejected
    # fail-closed without being scored, and retry exhaustion means a
    # swap storm starved a request — all fail hard on growth.  Breaker
    # transition/re-probe/recovered counters are the RECOVERY machinery
    # doing its job (the underlying failure already fails via
    # serve.device_errors / watchdog.fired), so they move freely.  A
    # daemon recovering cleanly (resumed / model_restored / an ignored
    # foreign state) is by design; a CORRUPT state file is a torn-write
    # bug.  413s are the body cap working, not a serving error.
    ("*serve.watchdog.fired*", "up_is_bad", "counter"),
    ("*serve.batcher.worker_restarts", "up_is_bad", "counter"),
    ("*serve.swap_retry_exhausted", "up_is_bad", "counter"),
    ("*serve.breaker.*", "ignore", "counter"),
    ("*fleet.gate.errors", "up_is_bad", "counter"),
    ("*fleet.recover.state_corrupt", "up_is_bad", "counter"),
    ("*fleet.recover.*", "ignore", "counter"),
    ("*serve.http.body_too_large", "ignore", "counter"),
    # control-plane observability (ISSUE 12): burn rate rising means a
    # tenant is eating error budget faster than its SLO allows —
    # timing class (wall-clock-derived: a plain `telemetry diff` fails,
    # the shared-core CI's --warn-timings run warns); its twin gauge
    # budget_remaining fails in the DOWN direction, counter-classed:
    # the gauge lives in [0, 1], so the timing tolerance (150% rel)
    # could never fire on a drop — and the baseline segment pins it at
    # a deterministic 1.0 (lenient SLO, no request can exceed budget).
    # Drift PSI is
    # computed from pinned data in the snapshot, so it is deterministic
    # and fails hard on growth; the drift bookkeeping gauges (sampled
    # row counts, feature indices) move freely.  Ledger record counts
    # are pure bookkeeping.  Replica skew is wall-clock-derived
    # (timing); the straggler INDEX is identity, not magnitude.
    ("*fleet.slo.burn_rate*", "up_is_bad", "timing"),
    ("*fleet.slo.budget_remaining*", "down_is_bad", "counter"),
    ("*serve.drift.psi*", "up_is_bad", "counter"),
    ("*serve.drift.max_psi", "up_is_bad", "counter"),
    ("*serve.drift.*", "ignore", "counter"),
    ("*ledger.records", "ignore", "counter"),
    # mesh skew (PR 12 within-process ratio; ISSUE 16 fleet scope): the
    # skew magnitudes are wall-clock-derived (timing class — a growing
    # lag means a device is pulling away); the straggler/device INDEX is
    # identity, not magnitude
    ("*mesh.skew.p99_ratio", "up_is_bad", "timing"),
    ("*mesh.skew.straggler", "ignore", "counter"),
    ("*mesh.skew.device", "ignore", "counter"),
    ("*mesh.skew.*", "up_is_bad", "timing"),
    ("*mesh.collective.*", "ignore", "timing"),
    # telemetry spool (ISSUE 16): pure bookkeeping — attach counts and
    # per-process spool stats move with deployment shape, never a
    # training/serving regression by themselves
    ("*spool.*", "ignore", "counter"),
    ("*fleet.tenant.*", "ignore", "counter"),
    ("*fleet.*", "ignore", "counter"),
    # serving: the bench `serving` block's latency percentiles /
    # throughput are wall-clock (timing class, CPU-fallback noise
    # warns); shed growth means overload handling regressed and fails
    # hard; queue/in-flight/model-count gauges and traffic counters are
    # load-dependent bookkeeping.  serve.host_walk{cause=} growth means
    # requests degraded all the way to the host walk — fail hard (the
    # old unlabeled serve.fallbacks was caught by the *fallback* rule
    # above); shed/device-error growth fails hard here
    ("*serving.p50_ms", "up_is_bad", "timing"),
    ("*serving.p99_ms", "up_is_bad", "timing"),
    ("*serving.rows_per_sec", "down_is_bad", "timing"),
    # device-sum rung sentinels: `active` flipping 1 -> 0 or the
    # disabled/demotion counters growing means the exact device-sum
    # path silently fell back to the slot path — fail hard.  The
    # per-rung bench stats are wall-clock (timing class); the slot-path
    # comparison block is informational (the rung we WANT to lose).
    ("*serve.device_sum_disabled", "up_is_bad", "counter"),
    ("*serve.demotions", "up_is_bad", "counter"),
    # compiled rung sentinels (ISSUE 13): same shape as device_sum —
    # `active` flipping 1 -> 0 or the per-cause disabled counters
    # growing means the tile planes silently stopped serving; host_walk
    # growth means requests fell all the way off the ladder.  Tile /
    # plane-byte counts are identity (a plan that changes shape on the
    # same model is a packer bug caught elsewhere); compile.plan.* is
    # build-time bookkeeping.
    ("*serve.host_walk*", "up_is_bad", "counter"),
    # cause=platform is the designed CPU outcome of serve_compiled=auto
    # (the rung is TPU-only by default), not a degradation
    ("*serve.compiled_disabled{cause=platform}", "ignore", "counter"),
    ("*serve.compiled_disabled*", "up_is_bad", "counter"),
    ("*serving.compiled.active", "down_is_bad", "counter"),
    ("*serving.compiled.rows_per_sec", "down_is_bad", "timing"),
    ("*serving.compiled.p50_ms", "up_is_bad", "timing"),
    ("*serving.compiled.p99_ms", "up_is_bad", "timing"),
    ("*serving.compiled.*", "ignore", "counter"),
    ("*compile.plan.*", "ignore", "counter"),
    # bounded precision tier (serve_precision=bounded): `active`
    # flipping 1 -> 0 means the quantized rung stopped serving (counter
    # class — fails hard); `error_ratio` (probe-measured / published
    # bound) climbing means the quantizer's error headroom is eroding —
    # also hard, the probe disables the rung outright past 1.0.  The
    # rung's latency/throughput are wall-clock; plane bytes and the
    # published bound are identity for a fixed model.
    ("*serve.bounded_disabled*", "up_is_bad", "counter"),
    ("*serving.bounded.active", "down_is_bad", "counter"),
    ("*serving.bounded.error_ratio", "up_is_bad", "counter"),
    ("*serving.bounded.rows_per_sec", "down_is_bad", "timing"),
    ("*serving.bounded.p50_ms", "up_is_bad", "timing"),
    ("*serving.bounded.p99_ms", "up_is_bad", "timing"),
    ("*serving.bounded.*", "ignore", "counter"),
    ("*serving.device_sum.active", "down_is_bad", "counter"),
    ("*serving.device_sum.d2h_bytes_per_row", "up_is_bad", "counter"),
    ("*serving.device_sum.rows_per_sec", "down_is_bad", "timing"),
    ("*serving.device_sum.p50_ms", "up_is_bad", "timing"),
    ("*serving.device_sum.p99_ms", "up_is_bad", "timing"),
    ("*serving.slot_path.*", "ignore", "timing"),
    # sharded serving plane (PR 10): replica latency percentiles are
    # wall-clock; the replica count shrinking means the mesh silently
    # lost devices (fail hard); stripe imbalance growing means the
    # least-outstanding-work scheduler stopped balancing (fail hard).
    # Per-replica rows/rung/outstanding series are load-dependent
    # bookkeeping
    ("*serve.replica.*.p50_s", "up_is_bad", "timing"),
    ("*serve.replica.*.p90_s", "up_is_bad", "timing"),
    ("*serve.replica.*.p99_s", "up_is_bad", "timing"),
    ("*serve.replica.*.p999_s", "up_is_bad", "timing"),
    ("*serve.replica.*", "ignore", "counter"),
    ("gauges.serve.replicas", "down_is_bad", "counter"),
    ("*serving.sharded.replicas", "down_is_bad", "counter"),
    ("*stripe_imbalance", "up_is_bad", "counter"),
    ("*serving.sharded.p50_ms", "up_is_bad", "timing"),
    ("*serving.sharded.p99_ms", "up_is_bad", "timing"),
    ("*serving.sharded.rows_per_sec*", "down_is_bad", "timing"),
    ("*serving.sharded.*", "ignore", "counter"),
    # server-side per-rung latency histograms (ISSUE 8): the
    # `serve.stage.e2e{rung=...}` percentile paths in a registry
    # snapshot, and the bench `serving.server.<rung>` block next to the
    # client-side numbers.  Wall-clock → timing class (warns on the
    # shared-core CI fallback, fails a plain `telemetry diff`); the
    # per-rung counts are load-dependent bookkeeping.
    ("*serve.stage.*.p50_s", "up_is_bad", "timing"),
    ("*serve.stage.*.p90_s", "up_is_bad", "timing"),
    ("*serve.stage.*.p99_s", "up_is_bad", "timing"),
    ("*serve.stage.*.p999_s", "up_is_bad", "timing"),
    ("*serve.stage.*", "ignore", "counter"),
    ("*serving.server.*.p50_ms", "up_is_bad", "timing"),
    ("*serving.server.*.p99_ms", "up_is_bad", "timing"),
    ("*serving.server.*", "ignore", "counter"),
    # per-cause shed split (serve.shed.queue_full / serve.shed.deadline)
    # fails on growth like the aggregate; recorder traffic stats are
    # load-dependent
    ("*serve.shed.*", "up_is_bad", "counter"),
    ("*serve.shed", "up_is_bad", "counter"),
    ("*serve.trace.*", "ignore", "counter"),
    ("*serve.device_errors", "up_is_bad", "counter"),
    ("gauges.serve.*", "ignore", "counter"),
    ("counters.serve.*", "ignore", "counter"),
    # external-memory datastore: prefetch stalls growing means the
    # read-ahead stopped hiding disk latency (timing class — thread
    # scheduling makes the exact count jittery); hits, spill volume and
    # shard count are workload bookkeeping; the resident watermark is a
    # budget signal but inherits the same scheduling jitter
    # streamed training (ISSUE 15): the device-residency watermark is
    # computed from accumulator/shard-block array SIZES (deterministic,
    # counter class — it IS the budget contract, growth fails hard);
    # stalls inherit the prefetch thread-scheduling jitter (timing
    # class); shard-pass / shards-read counts are workload bookkeeping
    # (pass count moves with tree shape), and the shard-count gauge is
    # dataset identity
    ("*stream.peak_device_mb", "up_is_bad", "counter"),
    # transient staging watermark (ISSUE 18): the double-buffer window
    # alone — deterministic array sizes, same budget-contract semantics
    ("*stream.peak_staging_mb", "up_is_bad", "counter"),
    ("*stream.stalls", "up_is_bad", "timing"),
    # streaming-pass profiler (ISSUE 16): per-stage attribution
    # histograms (prefetch-wait / H2D / device-fold / host-harvest) are
    # wall-clock — a rising prefetch_wait p99 means the read-ahead
    # stopped hiding disk latency; pass counts are workload identity
    ("*stream.pass.*.count", "ignore", "counter"),
    ("*stream.pass.prefetch_wait*", "up_is_bad", "timing"),
    ("*stream.pass.*", "up_is_bad", "timing"),
    ("*stream.shard_passes", "ignore", "counter"),
    ("*stream.shards_read", "ignore", "counter"),
    ("*stream.shards", "ignore", "counter"),
    # the bench `streaming` block (--streaming): both throughputs and
    # the streamed/assembled ratio are wall-clock; the stall ratio is
    # prefetch-scheduling jitter (timing); the device watermark is the
    # budget contract (deterministic, fails hard); pass/shard counts
    # are workload identity at a fixed bench shape
    ("streaming.*rounds_per_sec", "down_is_bad", "timing"),
    ("streaming.streamed_vs_assembled", "down_is_bad", "timing"),
    ("streaming.stall_ratio", "up_is_bad", "timing"),
    ("streaming.peak_device_mb", "up_is_bad", "counter"),
    ("streaming.*", "ignore", "counter"),
    # the bench `memory.ledger` block (ISSUE 18): the unattributed
    # watermark, violation counts and the leak slope fail hard on
    # growth (slope is wall-clock-derived — timing tolerance); the
    # per-device per-owner attribution is workload shape, not a
    # regression axis
    ("memory.ledger.unattributed_mb", "up_is_bad", "counter"),
    ("memory.ledger.budget_violations*", "up_is_bad", "counter"),
    ("memory.ledger.oom_dumps", "up_is_bad", "counter"),
    ("memory.ledger.leak_slope_mb_per_min", "up_is_bad", "timing"),
    ("memory.ledger.*", "ignore", "counter"),
    # the bench `soak` block (ISSUE 20, --soak): the invariant verdicts
    # fail HARD on any rise — a byte-inconsistent response, an SLO-class
    # budget breach, a failed scenario expectation or an unattributed
    # swap-window shed each mean a production invariant broke; the
    # fitted capacity model's throughput fields are wall-clock-derived
    # (timing class, down-is-bad — a capacity regression is the model
    # being falsified); scenario bookkeeping (request counts, versions,
    # per-step detail) is workload identity at a fixed scenario shape
    ("soak.byte_inconsistent", "up_is_bad", "counter"),
    ("soak.slo_breach", "up_is_bad", "counter"),
    ("soak.expect_fail", "up_is_bad", "counter"),
    ("soak.errors", "up_is_bad", "counter"),
    ("soak.swap_retry_exhausted", "up_is_bad", "counter"),
    ("soak.sheds.unattributed_swap", "up_is_bad", "counter"),
    ("soak.mem_budget_violations", "up_is_bad", "counter"),
    ("soak.slo.*.burn_rate", "up_is_bad", "timing"),
    ("soak.slo.*.observed_p99_ms", "up_is_bad", "timing"),
    ("soak.capacity.rows_per_sec*", "down_is_bad", "timing"),
    ("soak.capacity.service_rate_qps", "down_is_bad", "timing"),
    ("soak.capacity.capacity_qps.*", "down_is_bad", "timing"),
    ("soak.capacity.shed_onset_qps", "down_is_bad", "timing"),
    ("soak.capacity.base_ms", "up_is_bad", "timing"),
    ("soak.capacity.*", "ignore", "counter"),
    ("soak.tenants.*.p99_ms", "up_is_bad", "timing"),
    ("soak.*", "ignore", "counter"),
    # the soak run's own live counters (spool/registry snapshots)
    ("*soak.oracle.byte_inconsistent", "up_is_bad", "counter"),
    ("*soak.expect.fail", "up_is_bad", "counter"),
    ("*soak.oracle.checked", "ignore", "counter"),
    ("*soak.requests", "ignore", "counter"),
    ("*soak.shed", "ignore", "counter"),
    ("*soak.errors", "up_is_bad", "counter"),
    ("*soak.appends", "ignore", "counter"),
    ("*soak.expect.pass", "ignore", "counter"),
    ("*datastore.prefetch.stall", "up_is_bad", "timing"),
    ("*datastore.prefetch.hit", "ignore", "counter"),
    ("*datastore.spill_bytes", "ignore", "counter"),
    ("*datastore.shards", "ignore", "counter"),
    ("*datastore.h2d_bytes_saved", "ignore", "counter"),
    ("*datastore.peak_resident_mb", "up_is_bad", "timing"),
    # wall-clock spans — higher is worse, timing class
    ("*total_s", "up_is_bad", "timing"),
    ("*mean_s", "up_is_bad", "timing"),
    ("*max_s", "up_is_bad", "timing"),
    ("*min_s", "ignore", "timing"),
    ("*dur_s", "up_is_bad", "timing"),
    ("*warmup_compile_sec", "up_is_bad", "timing"),
    # everything else (tree shape stats, counters): a move in EITHER
    # direction beyond tolerance is flagged — shape drift is the
    # "unmeasured mechanism changed" signal even when the sign is
    # ambiguous
    ("*", "any_is_bad", "counter"),
]


def match_rule(path: str) -> Tuple[str, str]:
    """(direction, class) for a flattened metric path."""
    for pat, direction, klass in RULES:
        if fnmatch.fnmatch(path, pat):
            return direction, klass
    return "any_is_bad", "counter"


def flatten(obj: Any, prefix: str = "") -> Dict[str, float]:
    """Dotted-path → numeric value map; non-numeric leaves are dropped
    (strings/lists carry identity, not magnitude)."""
    out: Dict[str, float] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            key = f"{prefix}.{k}" if prefix else str(k)
            out.update(flatten(v, key))
    elif isinstance(obj, bool):
        pass
    elif isinstance(obj, (int, float)):
        out[prefix] = float(obj)
    return out


def load_snapshot(path: str) -> Dict[str, Any]:
    """Load a snapshot file: a JSON object, or a JSONL/BENCH file whose
    LAST parseable JSON-object line wins (so `bench.py ... > out.txt`
    artifacts diff directly)."""
    with open(path) as f:
        text = f.read()
    try:
        obj = json.loads(text)
        if isinstance(obj, dict):
            return obj
    except ValueError:
        pass
    last = None
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict):
            last = obj
    if last is None:
        raise ValueError(f"{path}: no JSON object found")
    return last


def diff_snapshots(base: Dict[str, Any], cur: Dict[str, Any],
                   rel_tol: float = DEFAULT_REL_TOL,
                   timing_rel_tol: float = DEFAULT_TIMING_REL_TOL,
                   warn_timings: bool = False) -> Dict[str, Any]:
    """Compare two snapshots → verdict dict (machine-readable).

    verdict: "ok" | "regression"; `violations` carry path/base/current/
    ratio/rule; `warnings` are timing violations under --warn-timings;
    `improved` are beyond-tolerance moves in the good direction;
    `missing`/`new` are metrics present on only one side (reported,
    never failing — instrumentation growth must not trip the sentinel).
    """
    a = flatten(base)
    b = flatten(cur)
    violations: List[Dict[str, Any]] = []
    warnings: List[Dict[str, Any]] = []
    improved: List[Dict[str, Any]] = []
    checked = 0
    for path in sorted(set(a) & set(b)):
        direction, klass = match_rule(path)
        if direction == "ignore":
            continue
        va, vb = a[path], b[path]
        checked += 1
        delta = vb - va
        if abs(delta) <= ABS_FLOOR:
            continue
        tol = timing_rel_tol if klass == "timing" else rel_tol
        # relative to the BASELINE value (not max(a,b), which caps |rel|
        # at 1.0 and makes any tolerance above 1 unreachable); the floor
        # keeps a 0 -> x move finite-but-huge, which is the right signal
        scale = max(abs(va), ABS_FLOOR)
        rel = delta / scale
        # drops are measured against the CURRENT value (fold-symmetric):
        # baseline-relative change caps a drop's |rel| at 1.0, which
        # made every tolerance above 1 unreachable downward — a
        # down_is_bad timing rule (tol 1.5) could never fire.  With the
        # current-relative measure a fall to 1/(1+tol) of baseline trips
        # exactly like a rise to (1+tol)x does.
        rel_down = delta / max(abs(vb), ABS_FLOOR)
        entry = {"metric": path, "base": va, "current": vb,
                 "rel_change": round(rel, 4),
                 "rule": f"{direction}/{klass}"}
        bad = (direction == "up_is_bad" and rel > tol) or \
              (direction == "down_is_bad" and -rel_down > tol) or \
              (direction == "any_is_bad"
               and (rel > tol or -rel_down > tol))
        good = (direction == "up_is_bad" and -rel_down > tol) or \
               (direction == "down_is_bad" and rel > tol)
        if bad:
            if klass == "timing" and warn_timings:
                warnings.append(entry)
            else:
                violations.append(entry)
        elif good:
            improved.append(entry)
    out = {
        "verdict": "regression" if violations else "ok",
        "checked": checked,
        "violations": violations,
        "warnings": warnings,
        "improved": improved,
        "missing": sorted(set(a) - set(b))[:50],
        "new": sorted(set(b) - set(a))[:50],
        "rel_tol": rel_tol,
        "timing_rel_tol": timing_rel_tol,
    }
    return out


def render(verdict: Dict[str, Any]) -> str:
    lines = [f"telemetry diff: {verdict['verdict'].upper()} "
             f"({verdict['checked']} metrics checked, "
             f"tol {verdict['rel_tol']:g}/"
             f"{verdict['timing_rel_tol']:g} timing)"]
    for label, key in (("VIOLATION", "violations"), ("warn", "warnings"),
                       ("improved", "improved")):
        for e in verdict[key]:
            lines.append(
                f"  {label:>9}  {e['metric']}: {e['base']:g} -> "
                f"{e['current']:g} ({e['rel_change']:+.1%}, "
                f"{e['rule']})")
    if verdict["missing"]:
        lines.append(f"  missing in current: "
                     f"{', '.join(verdict['missing'][:8])}"
                     + (" ..." if len(verdict["missing"]) > 8 else ""))
    if verdict["new"]:
        lines.append(f"  new in current: {len(verdict['new'])} metrics")
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu telemetry diff",
        description="Compare two telemetry/flight snapshots; exit 1 on "
                    "direction-violating deltas beyond tolerance.")
    p.add_argument("baseline")
    p.add_argument("current")
    # default=None so an EXPLICIT flag is distinguishable from "unset"
    # even when its value equals the built-in default — explicit flags
    # must beat the baseline's embedded sentinel contract
    p.add_argument("--rel-tol", type=float, default=None,
                   help="relative tolerance for counter-class metrics "
                        f"(default {DEFAULT_REL_TOL:g})")
    p.add_argument("--timing-rel-tol", type=float, default=None,
                   help="relative tolerance for wall-clock metrics "
                        f"(default {DEFAULT_TIMING_REL_TOL:g})")
    p.add_argument("--warn-timings", action="store_true",
                   help="downgrade timing-class violations to warnings "
                        "(CI on the CPU fallback)")
    p.add_argument("--json", action="store_true",
                   help="print the verdict as one JSON object")
    args = p.parse_args(list(argv) if argv is not None else None)
    try:
        base = load_snapshot(args.baseline)
        cur = load_snapshot(args.current)
    except (OSError, ValueError) as e:
        print(f"telemetry diff: {e}", file=sys.stderr)
        return 2
    # tolerance resolution: explicit CLI flag > the baseline's embedded
    # comparison contract (the telemetry_diff_*_tol params, written by
    # telemetry_snapshot.py as a `sentinel` block) > built-in default
    sentinel = base.get("sentinel") if isinstance(base, dict) else None
    if not isinstance(sentinel, dict):
        sentinel = {}
    rel_tol = args.rel_tol
    if rel_tol is None:
        rel_tol = float(sentinel.get("rel_tol", DEFAULT_REL_TOL))
    timing_tol = args.timing_rel_tol
    if timing_tol is None:
        timing_tol = float(sentinel.get("timing_rel_tol",
                                        DEFAULT_TIMING_REL_TOL))
    verdict = diff_snapshots(base, cur, rel_tol=rel_tol,
                             timing_rel_tol=timing_tol,
                             warn_timings=args.warn_timings)
    if args.json:
        print(json.dumps(verdict, separators=(",", ":")))
    else:
        print(render(verdict))
    return 1 if verdict["verdict"] == "regression" else 0


if __name__ == "__main__":
    sys.exit(main())
