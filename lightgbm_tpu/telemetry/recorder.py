"""Training flight recorder + compile/memory watermarks.

The flight recorder is the *semantic* layer on top of the generic spans /
metrics substrate: an opt-in (`flight_recorder=true`), ring-buffered
per-round record of what the booster actually grew — tree depth and leaf
count, split-gain distribution quantiles, top split features, grad/hess
aggregates, wave/fallback events, eval deltas, and the round's wall-clock
split across the existing span names (train.chunk / compile_warmup /
eval / predict.*).  Each round is emitted as one structured `train.round`
event through the attached sinks and the ring is summarized into
`booster.flight_summary()` — the dict `bench.py` embeds in the BENCH JSON
and `telemetry diff` compares between runs (the GPU GBDT systems this
repo reproduces diagnose their histogram/partition hot paths from exactly
these per-level gain/occupancy stats and device-memory watermarks; see
arxiv 1806.11248 §5, 2005.09148 §4).

Cost model: with `flight_recorder` off the booster never constructs a
FlightRecorder — the hot paths carry a single `is None` check and the
grown model bytes are identical either way (asserted by
tests/test_flight_recorder.py).  With it on, every stat is derived from
the HOST-side tree arrays the booster already materialized
(`Tree.from_device` / `_decode_stacked` already did the one device_get
per chunk) — recording adds **no device syncs**.

STDLIB-ONLY core (like metrics.py / sinks.py): this module never imports
jax, numpy, or lightgbm_tpu; tree stats duck-type over the host numpy
arrays (iteration + float()), and the watermark collectors reach jax only
through `sys.modules.get("jax")` — never an import — so the module stays
loadable by file path from the jax-free bench/probe processes.
"""
from __future__ import annotations

import collections
import math
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from .metrics import REGISTRY
from .sinks import make_event
from .spans import TRACER

#: span.<name> timing totals that make up a round's wall-clock split.
#: train.chunk covers the fused-chunk DISPATCH (async under pipelining),
#: train.harvest the blocking readback + decode of a dispatched chunk
#: (train.grow/train.decode are the finer per-phase splits), eval and
#: compile_warmup ride beside them (hist/split phases live device-side as
#: jax.named_scopes — visible in XProf, not in host wall-clock; see
#: docs/OBSERVABILITY.md).
PHASE_SPANS = ("train.chunk", "train.harvest", "train.grow",
               "train.decode", "eval", "compile_warmup", "predict.device",
               "predict.host")

#: registry counters whose per-round deltas ride in each record (forced /
#: fallback events: wave downgrades, pallas probe failures).
PHASE_COUNTERS = ("fallback.events", "event.fallback.wave_downgrade",
                  "event.fallback.pallas_probe", "jit.recompiles")


def quantiles(values: Sequence[float], qs: Sequence[float]) -> List[float]:
    """Linear-interpolated quantiles of `values` (pure python — numpy is
    off-limits here); returns 0.0s for an empty input."""
    vs = sorted(float(v) for v in values)
    if not vs:
        return [0.0 for _ in qs]
    out = []
    for q in qs:
        pos = (len(vs) - 1) * float(q)
        lo = int(math.floor(pos))
        hi = min(lo + 1, len(vs) - 1)
        out.append(vs[lo] + (vs[hi] - vs[lo]) * (pos - lo))
    return out


def tree_depth(left_child: Sequence[int], right_child: Sequence[int],
               num_leaves: int) -> int:
    """Max leaf depth of one host tree from its child pointers (leaves
    are encoded as `~leaf_index`, internal node 0 is the root)."""
    if num_leaves <= 1:
        return 0
    depth = 0
    stack = [(0, 1)]
    while stack:
        node, d = stack.pop()
        if node < 0:
            if d - 1 > depth:
                depth = d - 1
            continue
        if d > depth:
            depth = d
        stack.append((int(left_child[node]), d + 1))
        stack.append((int(right_child[node]), d + 1))
    return depth


def tree_stats(tree: Any) -> Dict[str, Any]:
    """Per-tree flight stats from a HOST `Tree` (duck-typed numpy arrays;
    no device access).  grad/hess aggregates are recovered from the leaf
    aggregates the device already shipped: leaf_weight is the hessian sum
    and leaf_value = -g/h * shrinkage, so per-leaf grad = -value*weight/
    shrinkage — their sums are exact, the L1 is a leaf-granularity lower
    bound on the row-level norm (good enough to catch a diverging
    objective without a device sync)."""
    nl = int(tree.num_leaves)
    ni = max(nl - 1, 0)
    gains = [float(g) for g in tree.split_gain[:ni]]
    feats = [int(f) for f in tree.split_feature[:ni]]
    shrink = float(tree.shrinkage) or 1.0
    grad_sum = 0.0
    grad_l1 = 0.0
    hess_sum = 0.0
    for v, w in zip(tree.leaf_value[:nl], tree.leaf_weight[:nl]):
        g = -float(v) * float(w) / shrink
        grad_sum += g
        grad_l1 += abs(g)
        hess_sum += float(w)
    return {
        "num_leaves": nl,
        "depth": tree_depth(tree.left_child, tree.right_child, nl),
        "gains": gains,
        "features": feats,
        "grad_sum": grad_sum,
        "grad_l1": grad_l1,
        "hess_sum": hess_sum,
    }


class FlightRecorder:
    """Ring-buffered per-round training diagnostics.

    One `record_round` call per boosting iteration (per-iteration path:
    after the K trees of the round are decoded; fused path: per chunk
    slot in `_decode_stacked`).  Eval results arrive asynchronously via
    `note_eval` (evals run after the round on both paths) and are folded
    into the eval series + the next round's record.
    """

    def __init__(self, depth: int = 128, wave: Optional[Dict] = None):
        self.depth = max(int(depth), 1)
        self.ring: collections.deque = collections.deque(maxlen=self.depth)
        self.rounds_seen = 0
        self.trees_seen = 0
        self.wave = dict(wave) if wave else None
        self._feature_counts: collections.Counter = collections.Counter()
        self._eval_series: Dict[str, List[float]] = {}
        self._phase_prev: Dict[str, float] = {}
        self._counter_prev: Dict[str, int] = {}
        self._lock = threading.Lock()

    # ---------------------------------------------------------- deltas
    def _phase_delta(self) -> Dict[str, float]:
        """Per-round wall-clock split: delta of the span timing totals
        since the previous record (fused rounds share one train.chunk
        span — the chunk's cost lands on its last decoded round, which
        is exactly how the chunk is paid for in wall-clock)."""
        out = {}
        for name in PHASE_SPANS:
            t = REGISTRY.timing(f"span.{name}")
            prev = self._phase_prev.get(name, 0.0)
            if t.total > prev:
                out[name] = round(t.total - prev, 6)
            self._phase_prev[name] = t.total
        return out

    def _counter_delta(self) -> Dict[str, int]:
        out = {}
        for name in PHASE_COUNTERS:
            v = REGISTRY.counter(name).value
            prev = self._counter_prev.get(name, 0)
            if v != prev:
                out[name] = v - prev
            self._counter_prev[name] = v
        return out

    def _eval_latest(self) -> Dict[str, Dict[str, float]]:
        out = {}
        for key, series in self._eval_series.items():
            entry = {"value": series[-1]}
            if len(series) > 1:
                entry["delta"] = series[-1] - series[-2]
            out[key] = entry
        return out

    # --------------------------------------------------------- recording
    def record_round(self, round_idx: int, trees: List[Dict[str, Any]],
                     **extra: Any) -> Dict[str, Any]:
        """Fold one boosting iteration's host-tree stats into the ring
        and emit one `train.round` event (when a sink is attached)."""
        with self._lock:
            gains: List[float] = []
            feats: List[int] = []
            for t in trees:
                gains.extend(t["gains"])
                feats.extend(t["features"])
            self._feature_counts.update(feats)
            g50, g90, gmax = quantiles(gains, (0.5, 0.9, 1.0))
            top = self._top_features(collections.Counter(feats), 3)
            rec = {
                "round": int(round_idx),
                "trees": len(trees),
                "num_leaves": sum(t["num_leaves"] for t in trees),
                "max_depth": max((t["depth"] for t in trees), default=0),
                "splits": len(gains),
                "gain_p50": round(g50, 6),
                "gain_p90": round(g90, 6),
                "gain_max": round(gmax, 6),
                "top_features": top,
                "grad_sum": round(sum(t["grad_sum"] for t in trees), 6),
                "grad_l1": round(sum(t["grad_l1"] for t in trees), 6),
                "hess_sum": round(sum(t["hess_sum"] for t in trees), 6),
            }
            if self.wave:
                # configured wave policy knobs + per-round leaf fill (how
                # much of the num_leaves capacity the wave frontier used —
                # the host-visible occupancy proxy; per-wave widths live
                # device-side)
                cap = self.wave.get("num_leaves", 0)
                rec["wave"] = {
                    **self.wave,
                    "leaf_fill": round(rec["num_leaves"] /
                                       max(cap * len(trees), 1), 4),
                }
            phases = self._phase_delta()
            if phases:
                rec["phase_s"] = phases
            counters = self._counter_delta()
            if counters:
                rec["events"] = counters
            ev = self._eval_latest()
            if ev:
                rec["eval"] = ev
            if extra:
                rec.update(extra)
            self.ring.append(rec)
            self.rounds_seen += 1
            self.trees_seen += len(trees)
        if TRACER._sinks:
            TRACER._emit(make_event("flight", "train.round", **rec))
        return rec

    def note_eval(self, data_name: str,
                  results: Sequence[Sequence[Any]]) -> None:
        """Fold one eval pass's (data, metric, value, bigger_better)
        tuples into the eval series and amend the latest round record
        in place (evals run after the round was recorded)."""
        with self._lock:
            latest: Dict[str, Dict[str, float]] = {}
            for item in results:
                key = f"{item[0]}.{item[1]}"
                series = self._eval_series.setdefault(key, [])
                series.append(float(item[2]))
                entry = {"value": series[-1]}
                if len(series) > 1:
                    entry["delta"] = series[-1] - series[-2]
                latest[key] = entry
            if latest and self.ring:
                self.ring[-1].setdefault("eval", {}).update(latest)

    def _top_features(self, counts: collections.Counter,
                      n: int) -> List[List[int]]:
        # deterministic order: count desc, feature index asc
        items = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))
        return [[int(f), int(c)] for f, c in items[:n]]

    # ----------------------------------------------------------- summary
    def summary(self) -> Dict[str, Any]:
        """Aggregate the ring into the flight summary `telemetry diff`
        compares: quantiles ACROSS rounds of the per-round stats, the
        overall top split features, phase wall-clock totals, eval
        first→last deltas, and the compile/memory watermarks."""
        with self._lock:
            recs = list(self.ring)
            depth_q = quantiles([r["max_depth"] for r in recs],
                                (0.5, 1.0))
            leaves_q = quantiles([r["num_leaves"] for r in recs],
                                 (0.5, 1.0))
            gain_q = quantiles([r["gain_p50"] for r in recs], (0.5,))
            out: Dict[str, Any] = {
                "enabled": True,
                "rounds": self.rounds_seen,
                "rounds_recorded": len(recs),
                "ring_depth": self.depth,
                "trees": self.trees_seen,
                "depth_p50": depth_q[0],
                "depth_max": depth_q[1],
                "leaves_p50": leaves_q[0],
                "leaves_max": leaves_q[1],
                "gain_p50_med": round(gain_q[0], 6),
                "top_features": self._top_features(self._feature_counts, 8),
            }
            if recs:
                out["last_round"] = recs[-1]["round"]
                out["grad_l1_last"] = recs[-1]["grad_l1"]
                out["hess_sum_last"] = recs[-1]["hess_sum"]
            if self.wave:
                fills = [r["wave"]["leaf_fill"] for r in recs
                         if "wave" in r]
                out["wave"] = {**self.wave,
                               "leaf_fill_mean": round(
                                   sum(fills) / len(fills), 4)
                               if fills else 0.0}
                # geometry the built grower actually resolved (recorded
                # by ops/grow_wave.make_wave_grower at build time; can
                # differ from the configured knobs via clamping/overgrow)
                resolved = REGISTRY.gauge("wave.width").value
                if resolved:
                    out["wave"]["resolved_width"] = int(resolved)
                    out["wave"]["grow_leaves"] = int(
                        REGISTRY.gauge("wave.grow_leaves").value)
            evals = {}
            for key, series in self._eval_series.items():
                evals[key] = {"first": series[0], "last": series[-1],
                              "delta": series[-1] - series[0],
                              "n": len(series)}
            if evals:
                out["eval"] = evals
        phases = {}
        for name in PHASE_SPANS:
            t = REGISTRY.timing(f"span.{name}")
            if t.count:
                phases[name] = {"count": t.count,
                                "total_s": round(t.total, 6),
                                "mean_s": round(t.mean, 6)}
        if phases:
            out["phase_s"] = phases
        out["compile"] = compile_stats()
        wm = memory_watermarks()
        if wm:
            out["watermarks"] = wm
        return out

    def throughput(self, num_data: int, hist_columns: int, num_leaves: int,
                   hist_impl: str, bundled: bool) -> Optional[Dict]:
        """The analytic throughput block folded in from
        utils/profile.py::training_report — rounds/sec is measured from
        the recorded `span.train.chunk` + `span.train.harvest` totals
        (dispatch + blocking readback/decode; the harvest half is zero on
        the per-iteration path, whose chunk span is synchronous) instead
        of a caller-timed interval, so the flight summary carries it for
        free."""
        t = REGISTRY.timing("span.train.chunk")
        h = REGISTRY.timing("span.train.harvest")
        total = t.total + h.total
        if not t.count or total <= 0 or not self.rounds_seen:
            return None
        return throughput_report(self.rounds_seen, total, num_data,
                                 hist_columns, num_leaves, hist_impl,
                                 bundled)


def throughput_report(rounds: int, seconds: float, num_data: int,
                      hist_columns: int, num_leaves: int, hist_impl: str,
                      bundled: bool) -> Dict:
    """Analytic throughput model (PROFILE.md): rounds/s, estimated HBM
    traffic and scatter-add rate.  Single source of truth — the
    `utils.profile.training_report` shim and `flight_summary()` both
    call this, returning the exact dict keys the shim always had."""
    levels = math.log2(max(num_leaves, 2)) / 2.0 + 1.0
    # uint8 bins + f32 (g,h,w,leaf_id) payload per row visit
    bytes_per_round = num_data * (hist_columns + 16) * levels
    rps = rounds / max(seconds, 1e-9)
    scatter_rate = num_data * hist_columns * 3 * rps * levels
    return {
        "rounds_per_sec": round(rps, 3),
        "rows": int(num_data),
        "hist_columns": int(hist_columns),
        "est_hbm_gb_per_sec": round(bytes_per_round * rps / 1e9, 1),
        "est_scatter_adds_per_sec": float(f"{scatter_rate:.3g}"),
        "hist_impl": hist_impl,
        "bundled": bool(bundled),
    }


# --------------------------------------------------------------------------
# compile & memory watermarks (jax via sys.modules mirror, NEVER imported)
# --------------------------------------------------------------------------

_compile_listener_installed = False
_compile_lock = threading.Lock()

#: the jax.monitoring time spans of JAX's compile pipeline, by the span
#: each becomes.  `backend_compile` marks one XLA computation compile or
#: its retrieval from the persistent cache, which the same event times.
_JIT_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}
#: the jax.monitoring events (not durations) of one persistent-cache miss
#: (a program compiled and written because no entry had its key) and of
#: one hit (fired inside the `backend_compile` span that loaded it).
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: jax.monitoring stamps spans with `time.time()`; spans are on
#: `perf_counter_ns`.  One offset, taken at install, converts.
_clock_offset_ns = 0
#: per thread, the wall-clock time of the last cache hit while recording
_hits = threading.local()
#: per recorded span name, the disjoint [start, end) intervals (wall
#: clock) its spans covered: the union behind `jit.trace_s` / `jit.lower_s`
_covered: Dict[str, List[List[float]]] = {}


def _cover(intervals: List[List[float]], start: float, end: float) -> float:
    """Add [start, end) to the sorted, disjoint `intervals` in place;
    returns the seconds it adds to their union.  Spans arrive as they end,
    so an enclosing span arrives after what it encloses and covers the
    last few intervals."""
    j = len(intervals)
    while j and intervals[j - 1][0] > end:       # wholly after it
        j -= 1
    k = j
    while k and intervals[k - 1][1] >= start:    # overlapping it
        k -= 1
    merged = intervals[k:j]
    lo = min([start] + [a for a, _ in merged])
    hi = max([end] + [b for _, b in merged])
    intervals[k:j] = [[lo, hi]]
    return (hi - lo) - sum(b - a for a, b in merged)


def _on_jit_span(name: str, start: float, end: float, **kw) -> None:
    """One callback for each span of JAX's compile pipeline.  Always: a
    backend compile counts in `jit.recompiles` and `jit.compile_total_s`.
    While the tracer is active: the span `jit.trace` / `jit.lower` /
    `jit.compile` under the span open on this thread, and the union
    gauges `jit.trace_s` / `jit.lower_s`."""
    span = _JIT_SPANS.get(name)
    if span == "jit.compile":
        REGISTRY.counter("jit.recompiles").inc()
        g = REGISTRY.gauge("jit.compile_total_s")
        g.set(g.value + (end - start))
    if span is None or not TRACER.active:
        return
    attrs = {"fun": str(kw.get("fun_name", ""))}
    if span == "jit.compile":
        hit = getattr(_hits, "at", None)
        attrs["cache"] = "hit" if hit is not None and start <= hit <= end \
            else "miss"
    else:
        with _compile_lock:
            g = REGISTRY.gauge(span + "_s")
            g.set(g.value + _cover(_covered.setdefault(span, []), start,
                                   end))
    TRACER.record(span, round(start * 1e9) + _clock_offset_ns,
                  round(end * 1e9) + _clock_offset_ns, start, **attrs)


def _on_jit_event(name: str, **kw) -> None:
    if name == _CACHE_MISS_EVENT:
        REGISTRY.counter("jit.cache_misses").inc()
    elif name == _CACHE_HIT_EVENT and TRACER.active:
        _hits.at = time.time()


def install_compile_listener() -> bool:
    """Hook `jax.monitoring` so every backend compile increments
    `jit.recompiles` and accumulates `jit.compile_total_s`, and every
    persistent-cache miss increments `jit.cache_misses`; while a sink is
    attached, the compile pipeline also becomes spans (`_on_jit_span`).
    Idempotent; returns False only when jax is not loaded in this process
    (this module never imports it)."""
    global _compile_listener_installed, _clock_offset_ns
    with _compile_lock:
        if _compile_listener_installed:
            return True
        jax = sys.modules.get("jax")
        if jax is None:
            return False
        _clock_offset_ns = time.perf_counter_ns() - time.time_ns()
        jax.monitoring.register_event_time_span_listener(_on_jit_span)
        jax.monitoring.register_event_listener(_on_jit_event)
        REGISTRY.counter("jit.cache_misses")    # reads 0, not absent
        _compile_listener_installed = True
        return True


def poll_jit_caches(fns: Sequence[Any]) -> int:
    """Sum the jit cache entry counts of the given jitted callables
    (`_cache_size()` on PjitFunction) into the `jit.cache_entries` gauge
    at summary time (cache entries ≠ compiles: a cache that keeps
    growing between summaries is the recompile-trap signal)."""
    total = 0
    for fn in fns:
        size = getattr(fn, "_cache_size", None)
        if size is None:
            continue
        total += int(size())
    REGISTRY.gauge("jit.cache_entries").set(total)
    return total


def compile_stats() -> Dict[str, Any]:
    return {
        "recompiles": REGISTRY.counter("jit.recompiles").value,
        "compile_total_s": round(
            REGISTRY.gauge("jit.compile_total_s").value, 3),
        "cache_entries": int(REGISTRY.gauge("jit.cache_entries").value),
        "monitoring_hooked": _compile_listener_installed,
    }


_mem_peaks: Dict[str, Dict[str, float]] = {}
_mem_lock = threading.Lock()


def sample_memory(phase: str) -> Optional[Dict[str, Any]]:
    """Record the current device-memory footprint under `phase`, keeping
    the high-water mark per phase and per device.

    TPU/GPU backends report allocator truth via `device.memory_stats()`
    (bytes_in_use / peak_bytes_in_use); the CPU backend returns None, so
    the fallback sums `jax.live_arrays()` nbytes — host-visible buffer
    bytes, not an allocator watermark, flagged via source="live_arrays".
    Surfaced as `mem.<phase>.peak_bytes` / `mem.dev<i>.peak_bytes` gauges
    and embedded in the flight summary + BENCH JSON."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        devices = jax.local_devices()
    except Exception:
        return None
    total = 0
    peak = 0
    per_dev = []
    source = "memory_stats"
    try:
        for d in devices:
            ms = None
            try:
                ms = d.memory_stats()
            except Exception:
                ms = None
            if ms:
                in_use = int(ms.get("bytes_in_use", 0))
                dev_peak = int(ms.get("peak_bytes_in_use", in_use))
            else:
                source = "live_arrays"
                in_use = dev_peak = 0
            total += in_use
            peak += dev_peak
            per_dev.append((str(getattr(d, "id", len(per_dev))), dev_peak))
        if source == "live_arrays":
            # Fallback: sum live-array nbytes per device — METADATA
            # only (materializing `addressable_shards[i].data` would
            # register aliasing views that inflate every later sample).
            # Arrays committed to a platform other than the default
            # backend (host-committed staging on a TPU run) are NOT
            # device residency: they land in the per-platform subtotals
            # instead of the device totals.  Aliasing views that already
            # exist are deduped by underlying buffer pointer.
            try:
                default_plat = str(jax.default_backend()).lower()
                by_dev: Dict[str, int] = {}
                platforms = {}
                seen: set = set()
                for a in jax.live_arrays():
                    try:
                        devs = sorted(
                            a.devices(),
                            key=lambda d: int(getattr(d, "id", 0)))
                    except Exception:
                        continue
                    if not devs:
                        continue
                    try:
                        key = ("ptr", int(a.unsafe_buffer_pointer()))
                    except Exception:
                        key = ("id", id(a))
                    if key in seen:
                        continue
                    seen.add(key)
                    nb = int(getattr(a, "nbytes", 0))
                    plat = str(getattr(devs[0], "platform",
                                       default_plat)).lower()
                    platforms[plat] = platforms.get(plat, 0) + nb
                    if plat != default_plat:
                        continue
                    replicated = bool(getattr(
                        getattr(a, "sharding", None),
                        "is_fully_replicated", len(devs) == 1))
                    per = nb if replicated \
                        else max(nb // len(devs), 0)
                    for d in devs:
                        k = str(getattr(d, "id", 0))
                        by_dev[k] = by_dev.get(k, 0) + per
                total = peak = sum(by_dev.values())
                per_dev = sorted(by_dev.items())
            except Exception:
                total = peak = sum(int(getattr(a, "nbytes", 0))
                                   for a in jax.live_arrays())
                per_dev = [("0", peak)]
                platforms = {}
    except Exception:
        return None
    with _mem_lock:
        entry = _mem_peaks.setdefault(
            phase, {"peak_bytes": 0.0, "samples": 0.0})
        entry["samples"] += 1
        if peak > entry["peak_bytes"]:
            entry["peak_bytes"] = float(peak)
        entry["source"] = source  # type: ignore[assignment]
        REGISTRY.gauge(f"mem.{phase}.peak_bytes").set(entry["peak_bytes"])
        for dev_id, dev_peak in per_dev:
            g = REGISTRY.gauge(f"mem.dev{dev_id}.peak_bytes")
            if dev_peak > g.value:
                g.set(dev_peak)
    out = {"phase": phase, "bytes_in_use": total, "peak_bytes": peak,
           "source": source}
    if source == "live_arrays":
        # per-platform subtotals tag the snapshot (returned, not folded
        # into the cross-run watermarks: the split is GC-timing noise)
        out["platforms"] = {k: platforms[k] for k in sorted(platforms)}
    return out


def memory_watermarks() -> Dict[str, Dict[str, Any]]:
    """Per-phase high-water marks recorded so far (JSON-ready)."""
    with _mem_lock:
        return {ph: {"peak_bytes": int(e["peak_bytes"]),
                     "samples": int(e["samples"]),
                     "source": e.get("source", "memory_stats")}
                for ph, e in sorted(_mem_peaks.items())}


def reset_watermarks() -> None:
    """Test hook: drop accumulated per-phase peaks (the REGISTRY gauges
    are reset separately via REGISTRY.reset())."""
    with _mem_lock:
        _mem_peaks.clear()
