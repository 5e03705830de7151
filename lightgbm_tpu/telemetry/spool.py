"""Cross-process telemetry spool: one JSONL stream per process, merged
into a clock-aligned fleet timeline.

Every participating process — a gloo training rank, the fleet trainer
daemon, the serving HTTP frontend, a bench worker — attaches a
`SpoolSink` that appends its existing telemetry event stream into a
shared *spool directory* as

    <spool_dir>/proc-<host>-<pid>-<rank>.jsonl

The first record of every spool file is a self-describing header
(`ev: "spool"`, `name: "header"`) carrying the process role, the jax
`process_index` when a distributed runtime is up, the visible device
ids, and a monotonic↔wall clock anchor pair

    {"mono": time.perf_counter(), "wall": time.time()}

taken atomically at attach time.  Events already stamp wall-clock `ts`,
so the anchors are the *alignment contract*: `wall - mono` is the
process's clock offset, and two spools whose offsets are finite can be
merged on `ts` directly (see docs/TIMELINE.md for the drift bound).

`aggregate()` merges every spool in a directory into one ordered fleet
stream plus a fleet-wide metrics roll-up, computes per-collective
per-device skew from the `mesh.collective.<name>` round events the mesh
layer stamps (mesh/placement.py `emit_collective_round`), names the
straggler device (`mesh.skew.device`), and summarizes the streaming
engine's `stream.pass` attribution.  `chrome_trace()` renders the same
merge as Chrome-trace (catapult) JSON for chrome://tracing / Perfetto.
Both back `python -m lightgbm_tpu timeline <spool_dir>` and the spool
block in `/debug/fleet` (telemetry/ops.py).

STDLIB-ONLY by design (see metrics.py): a jax-free process can load
this file by path to spool its own header, and `aggregate()`/`main()`
never need the package.  `attach_spool()` is the
one in-package helper (it touches the process-global TRACER); file-path
loaders construct `SpoolSink` directly instead.  jax is mirrored via
`sys.modules.get("jax")`, never imported.
"""
from __future__ import annotations

import json
import os
import re
import socket
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

try:
    from .sinks import JsonlSink, read_jsonl_counted
except ImportError:  # loaded by file path, outside the package
    import importlib.util as _ilu
    _spec = _ilu.spec_from_file_location(
        "_telemetry_spool_sinks",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "sinks.py"))
    _sinks = _ilu.module_from_spec(_spec)
    _spec.loader.exec_module(_sinks)
    JsonlSink = _sinks.JsonlSink
    read_jsonl_counted = _sinks.read_jsonl_counted

#: Event kinds the aggregator understands; anything else is counted and
#: skipped (forward-compat: an older reader meeting a newer writer).
KNOWN_EV_KINDS = ("span", "event", "metrics", "trace", "spool", "oom")

#: Default spool directory when `telemetry_spool=true` with no
#: `telemetry_spool_dir` (relative to the process cwd, like every other
#: relative artifact path in the params surface).
DEFAULT_SPOOL_DIR = "lgbm_tpu_spool"

#: Spool directories this process has attached to — `/debug/fleet`
#: (telemetry/ops.py) aggregates them so a `top` against a serving
#: process sees the whole fleet's spools, not just its own stream.
SPOOL_DIRS: List[str] = []  # guarded-by: _attach_lock

_ATTACHED: Dict[str, "SpoolSink"] = {}  # guarded-by: _attach_lock

#: serializes attach_spool's check-then-act: a Booster and a serving
#: daemon attaching the same dir concurrently must share ONE sink, not
#: stack two headers into two files.  A plain threading.Lock (not
#: make_lock) because this module stays file-path-loadable with zero
#: package imports at module scope; it is a leaf lock — nothing else
#: is ever acquired under it
_attach_lock = threading.Lock()


def _safe(token: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.]+", "-", str(token)).strip("-") or "x"


def _jax_identity() -> Tuple[Optional[int], Optional[List[int]]]:
    """(process_index, visible device ids) from an ALREADY-LOADED jax —
    mirrored via sys.modules, never imported, so a jax-free process is
    never dragged into it."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None, None
    try:
        pidx = int(jax.process_index())
        devs = [int(d.id) for d in jax.local_devices()]
        return pidx, devs
    except Exception:
        return None, None


class SpoolSink(JsonlSink):
    """A per-process JSONL sink inside a shared spool directory.

    The constructor writes the self-describing header record first, so
    even a process killed immediately after attach leaves a spool entry
    the aggregator can identify and clock-align.
    """

    def __init__(self, spool_dir: str, role: str,
                 rank: Optional[int] = None,
                 process_index: Optional[int] = None,
                 devices: Optional[List[int]] = None):
        host = _safe(socket.gethostname().split(".")[0])
        jax_pidx, jax_devs = _jax_identity()
        if process_index is None:
            process_index = jax_pidx
        if devices is None:
            devices = jax_devs
        if rank is None:
            rank = process_index if process_index is not None else 0
        self.role = str(role)
        self.rank = int(rank)
        self.spool_dir = os.path.abspath(spool_dir)
        path = os.path.join(self.spool_dir,
                            f"proc-{host}-{os.getpid()}-{self.rank}.jsonl")
        super().__init__(path)
        # mono/wall taken back-to-back: the pair IS the clock anchor
        mono = time.perf_counter()
        wall = time.time()
        self.emit({"ev": "spool", "name": "header",
                   "ts": round(wall, 6),
                   "role": self.role, "host": host, "pid": os.getpid(),
                   "rank": self.rank, "process_index": process_index,
                   "devices": devices,
                   "mono": round(mono, 6), "wall": round(wall, 6)})


def attach_spool(spool_dir: str, role: str,
                 rank: Optional[int] = None) -> "SpoolSink":
    """Attach a `SpoolSink` for this process to the global TRACER —
    idempotent per spool directory, so every Booster / server / daemon
    constructed with the same `telemetry_spool_dir` shares one spool
    file instead of stacking headers.  In-package only (the TRACER
    import is relative); file-path loaders build `SpoolSink` directly.
    """
    from .metrics import REGISTRY
    from .spans import TRACER
    key = os.path.abspath(spool_dir or DEFAULT_SPOOL_DIR)
    with _attach_lock:
        sink = _ATTACHED.get(key)
        if sink is None:
            sink = SpoolSink(key, role, rank=rank)
            _ATTACHED[key] = sink
            TRACER.add_sink(sink)
            if key not in SPOOL_DIRS:
                SPOOL_DIRS.append(key)
            REGISTRY.counter("spool.attached").inc()
    return sink


# ---------------------------------------------------------------- merge
def _merge_metrics(fleet: Dict[str, Any], snap: Dict[str, Any]) -> None:
    """Fold one process's registry snapshot into the fleet roll-up.

    Counters sum; gauges keep the max (watermark semantics — the only
    cross-process reduction that never understates); timings merge
    exactly (count/total sum, min/max extremes, mean recomputed);
    histogram percentiles are NOT mergeable from snapshots, so the
    roll-up keeps count/sum plus the per-process WORST percentile —
    an upper bound, flagged as such in docs/TIMELINE.md.
    """
    for name, v in (snap.get("counters") or {}).items():
        fleet["counters"][name] = fleet["counters"].get(name, 0) + v
    for name, v in (snap.get("gauges") or {}).items():
        cur = fleet["gauges"].get(name)
        fleet["gauges"][name] = v if cur is None else max(cur, v)
    for name, t in (snap.get("timings") or {}).items():
        cur = fleet["timings"].get(name)
        if cur is None:
            fleet["timings"][name] = dict(t)
            continue
        cur["count"] += t.get("count", 0)
        cur["total_s"] = round(cur["total_s"] + t.get("total_s", 0.0), 6)
        cur["min_s"] = min(cur["min_s"], t.get("min_s", cur["min_s"]))
        cur["max_s"] = max(cur["max_s"], t.get("max_s", cur["max_s"]))
        cur["mean_s"] = round(cur["total_s"] / cur["count"], 6) \
            if cur["count"] else 0.0
    for name, h in (snap.get("histograms") or {}).items():
        cur = fleet["histograms"].get(name)
        if cur is None:
            fleet["histograms"][name] = dict(h)
            continue
        cur["count"] += h.get("count", 0)
        cur["sum_s"] = round(cur["sum_s"] + h.get("sum_s", 0.0), 6)
        for k in ("max_s", "p50_s", "p90_s", "p99_s", "p999_s"):
            if k in h:
                cur[k] = max(cur.get(k, 0.0), h[k])


def _collective_skew(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Per-collective per-device skew from `mesh.collective.<name>`
    round events.

    Each participating process stamps one point event per local device
    per collective round (host-side, around the dispatch — graft-lint
    R005 keeps telemetry out of jitted code).  Within one (name, round)
    group the earliest stamp defines t0; a device's *lag* is its stamp
    minus t0.  A consistently-late device across rounds is the
    straggler — the cross-process upgrade of the within-process
    `mesh.skew.p99_ratio` gauge (PR 12).
    """
    rounds: Dict[Tuple[str, Any], List[Tuple[int, float]]] = {}
    payloads: Dict[str, int] = {}
    for ev in events:
        name = ev.get("name", "")
        if ev.get("ev") != "event" or \
                not name.startswith("mesh.collective."):
            continue
        if "device" not in ev:
            continue
        coll = name[len("mesh.collective."):]
        key = (coll, ev.get("round"))
        rounds.setdefault(key, []).append(
            (int(ev["device"]), float(ev.get("ts", 0.0))))
        if "payload_bytes" in ev:
            payloads[coll] = int(ev["payload_bytes"])
    per: Dict[str, Dict[int, Dict[str, float]]] = {}
    for (coll, _rnd), stamps in rounds.items():
        t0 = min(ts for _d, ts in stamps)
        devs = per.setdefault(coll, {})
        for dev, ts in stamps:
            d = devs.setdefault(dev, {"count": 0, "lag_total_s": 0.0,
                                      "lag_max_s": 0.0})
            lag = ts - t0
            d["count"] += 1
            d["lag_total_s"] += lag
            d["lag_max_s"] = max(d["lag_max_s"], lag)
    out: Dict[str, Any] = {}
    for coll, devs in sorted(per.items()):
        table = {}
        for dev, d in sorted(devs.items()):
            table[str(dev)] = {
                "rounds": d["count"],
                "lag_mean_s": round(d["lag_total_s"] / d["count"], 6)
                if d["count"] else 0.0,
                "lag_max_s": round(d["lag_max_s"], 6)}
        worst = max(table, key=lambda k: table[k]["lag_mean_s"])
        means = sorted(v["lag_mean_s"] for v in table.values())
        median = means[len(means) // 2]
        out[coll] = {
            "devices": table,
            "payload_bytes": payloads.get(coll),
            "straggler": int(worst),
            "lag_mean_s": table[worst]["lag_mean_s"],
            "skew_ratio": round(table[worst]["lag_mean_s"] / median, 4)
            if median > 0 else 1.0,
        }
    return out


def _stream_pass_summary(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold `stream.pass` span attrs (streaming/engine.py per-pass
    profiler) into per-stage totals: prefetch-wait vs H2D vs device-fold
    vs host-harvest, plus the pass wall time they must sum under."""
    stages = ("prefetch_wait_s", "h2d_s", "device_fold_s",
              "host_harvest_s")
    out = {"passes": 0, "wall_s": 0.0}
    out.update({s: 0.0 for s in stages})
    for ev in events:
        if ev.get("ev") != "span" or ev.get("name") != "stream.pass":
            continue
        attrs = ev.get("attrs") or {}
        if not any(s in attrs for s in stages):
            continue
        out["passes"] += 1
        out["wall_s"] += float(ev.get("dur_s", 0.0) or 0.0)
        for s in stages:
            out[s] += float(attrs.get(s, 0.0) or 0.0)
    for k, v in list(out.items()):
        if isinstance(v, float):
            out[k] = round(v, 6)
    out["attributed_s"] = round(sum(out[s] for s in stages), 6)
    return out


def aggregate(spool_dir: str, keep_events: bool = True) -> Dict[str, Any]:
    """Merge every `proc-*.jsonl` spool in `spool_dir` into one
    clock-ordered fleet view.

    Returns a dict with: `processes` (one row per spool file — header
    identity, clock offset, event/torn counts), `events` (the merged
    stream, each record annotated with its `_proc` key; omitted when
    `keep_events` is false — /debug/fleet wants the summary, not the
    firehose), `metrics` (the fleet registry roll-up), `collectives`
    (per-device skew + straggler per collective), `straggler` (the
    fleet-wide `mesh.skew.device`), `stream` (pass attribution),
    `memory_samples` (timestamped per-owner `mem.*` gauge points from
    the memory ledger's round hook — the Chrome-trace counter tracks),
    and the `torn_lines` / `unknown_ev` forward-compat counters.
    OOM forensics dumps (`{"ev": "oom"}`) ride in `events` verbatim.
    """
    spool_dir = os.path.abspath(spool_dir)
    processes: List[Dict[str, Any]] = []
    merged: List[Dict[str, Any]] = []
    torn_total = 0
    unknown: Dict[str, int] = {}
    fleet = {"counters": {}, "gauges": {}, "timings": {}, "histograms": {}}
    mem_samples: List[Dict[str, Any]] = []
    for fn in sorted(os.listdir(spool_dir)):
        if not (fn.startswith("proc-") and fn.endswith(".jsonl")):
            continue
        events, torn = read_jsonl_counted(os.path.join(spool_dir, fn))
        torn_total += torn
        header = next((e for e in events if e.get("ev") == "spool"
                       and e.get("name") == "header"), None)
        if header is not None:
            proc_key = (f"{header.get('host', '?')}-"
                        f"{header.get('pid', '?')}-"
                        f"rank{header.get('rank', '?')}")
            offset = None
            if isinstance(header.get("wall"), (int, float)) and \
                    isinstance(header.get("mono"), (int, float)):
                offset = round(header["wall"] - header["mono"], 6)
            row = {"file": fn, "role": header.get("role", "?"),
                   "host": header.get("host"), "pid": header.get("pid"),
                   "rank": header.get("rank"),
                   "process_index": header.get("process_index"),
                   "devices": header.get("devices"),
                   "clock_offset_s": offset}
        else:
            # headerless (torn at birth): identity from the filename
            proc_key = fn[len("proc-"):-len(".jsonl")]
            row = {"file": fn, "role": "?", "header_missing": True}
        snap_count = 0
        n_known = 0
        for ev in events:
            kind = ev.get("ev")
            if kind not in KNOWN_EV_KINDS:
                unknown[str(kind)] = unknown.get(str(kind), 0) + 1
                continue
            n_known += 1
            if kind == "metrics" and isinstance(ev.get("snapshot"), dict):
                snap_count += 1
                _merge_metrics(fleet, ev["snapshot"])
                if ev.get("name") == "memory":
                    # memledger round points: keep the timestamped
                    # samples too — the fold above only retains the
                    # cross-process max, but the Chrome-trace counter
                    # tracks need the series
                    mem_samples.append(
                        {"ts": float(ev.get("ts", 0.0) or 0.0),
                         "_proc": proc_key,
                         "gauges": ev["snapshot"].get("gauges") or {}})
                continue
            if kind == "spool":
                continue
            ev = dict(ev)
            ev["_proc"] = proc_key
            merged.append(ev)
        row["events"] = n_known
        row["torn_lines"] = torn
        row["metrics_snapshots"] = snap_count
        processes.append(row)
    merged.sort(key=lambda e: (float(e.get("ts", 0.0) or 0.0),
                               e.get("_proc", "")))
    collectives = _collective_skew(merged)
    straggler = None
    if collectives:
        worst = max(collectives.values(), key=lambda c: c["lag_mean_s"])
        straggler = worst["straggler"]
    out = {
        "spool_dir": spool_dir,
        "processes": processes,
        "metrics": fleet,
        "collectives": collectives,
        "straggler": straggler,
        "stream": _stream_pass_summary(merged),
        "memory_samples": sorted(mem_samples,
                                 key=lambda s: (s["ts"], s["_proc"])),
        "torn_lines": torn_total,
        "unknown_ev": unknown,
        "n_events": len(merged),
    }
    if keep_events:
        out["events"] = merged
    return out


# --------------------------------------------------------- chrome trace
def chrome_trace(agg: Dict[str, Any]) -> Dict[str, Any]:
    """Render an `aggregate()` result as Chrome-trace (catapult) JSON:
    one trace process per spool process, spans as complete (`ph: "X"`)
    events, point events as instants, memory-ledger round points as
    per-device counter (`ph: "C"`) tracks and OOM dumps as global
    instants — loadable by chrome://tracing and Perfetto.  Timestamps
    are µs relative to the earliest merged event (absolute epoch
    seconds overflow the viewer's float precision)."""
    events = agg.get("events") or []
    mem_samples = agg.get("memory_samples") or []
    t0 = min((float(e.get("ts", 0.0) or 0.0)
              for e in list(events) + list(mem_samples)),
             default=0.0)
    trace: List[Dict[str, Any]] = []
    pids: Dict[str, int] = {}
    for i, proc in enumerate(agg.get("processes", [])):
        key = (f"{proc.get('host', '?')}-{proc.get('pid', '?')}-"
               f"rank{proc.get('rank', '?')}")
        if proc.get("header_missing"):
            key = proc["file"][len("proc-"):-len(".jsonl")]
        pids[key] = i
        trace.append({"name": "process_name", "ph": "M", "pid": i,
                      "tid": 0,
                      "args": {"name": f"{proc.get('role', '?')} "
                                       f"{key}"}})
    for ev in events:
        pid = pids.get(ev.get("_proc", ""), len(pids))
        us = (float(ev.get("ts", 0.0) or 0.0) - t0) * 1e6
        kind = ev.get("ev")
        if kind == "span":
            args = dict(ev.get("attrs") or {})
            trace.append({"name": ev.get("name", "?"), "ph": "X",
                          "ts": round(us, 3),
                          "dur": round(float(ev.get("dur_s", 0.0)
                                             or 0.0) * 1e6, 3),
                          "pid": pid, "tid": int(ev.get("depth", 0)),
                          "args": args})
        elif kind == "event":
            args = {k: v for k, v in ev.items()
                    if k not in ("ev", "name", "ts", "_proc")}
            trace.append({"name": ev.get("name", "?"), "ph": "i",
                          "ts": round(us, 3), "s": "p",
                          "pid": pid, "tid": 0, "args": args})
        elif kind == "oom":
            # forensics dump: a GLOBAL instant (full-height line in the
            # viewer) carrying the attributed per-owner snapshot
            args = {k: v for k, v in ev.items()
                    if k not in ("ev", "name", "ts", "_proc")}
            trace.append({"name": f"OOM {ev.get('name', '?')}",
                          "ph": "i", "ts": round(us, 3), "s": "g",
                          "pid": pid, "tid": 0, "args": args})
    for s in mem_samples:
        pid = pids.get(s.get("_proc", ""), len(pids))
        us = (float(s.get("ts", 0.0) or 0.0) - t0) * 1e6
        per_dev: Dict[str, Dict[str, float]] = {}
        for name, v in (s.get("gauges") or {}).items():
            if not name.startswith("mem."):
                continue
            dev, _, okey = name[len("mem."):].partition(".")
            if okey:
                per_dev.setdefault(dev, {})[okey] = round(
                    float(v) / (1 << 20), 3)
        for dev, series in sorted(per_dev.items()):
            # one stacked counter track per device, series per owner
            trace.append({"name": f"mem.{dev} (MB)", "ph": "C",
                          "ts": round(us, 3), "pid": pid,
                          "args": series})
    return {"traceEvents": trace, "displayTimeUnit": "ms",
            "otherData": {"spool_dir": agg.get("spool_dir", ""),
                          "epoch_t0": t0}}


# -------------------------------------------------------------- render
def render_timeline(agg: Dict[str, Any]) -> str:
    """Fixed-width text rendering of an `aggregate()` result."""
    lines: List[str] = []
    procs = agg.get("processes", [])
    if not procs:
        lines.append(f"status: no-run (no spool files in "
                     f"{agg.get('spool_dir', '?')})")
        return "\n".join(lines)
    lines.append(f"spool: {agg.get('spool_dir')}  "
                 f"({len(procs)} processes, {agg.get('n_events', 0)} "
                 f"events)")
    lines.append(f"  {'role':<18} {'host':<12} {'pid':>7} {'rank':>4} "
                 f"{'devices':<16} {'events':>7} {'torn':>5}")
    for p in procs:
        devs = p.get("devices")
        devs_s = ",".join(str(d) for d in devs) if devs else "-"
        lines.append(
            f"  {str(p.get('role', '?')):<18} "
            f"{str(p.get('host', '?')):<12} "
            f"{str(p.get('pid', '?')):>7} {str(p.get('rank', '?')):>4} "
            f"{devs_s:<16} {p.get('events', 0):>7} "
            f"{p.get('torn_lines', 0):>5}")
    if agg.get("torn_lines"):
        lines.append(f"  skipped {agg['torn_lines']} torn line(s)")
    if agg.get("unknown_ev"):
        kinds = ", ".join(f"{k} x{n}"
                          for k, n in sorted(agg["unknown_ev"].items()))
        lines.append(f"  skipped unknown event kinds: {kinds}")
    colls = agg.get("collectives", {})
    if colls:
        lines.append("")
        lines.append("mesh collectives (per-device lag vs round start):")
        for name, c in sorted(colls.items()):
            pb = c.get("payload_bytes")
            lines.append(f"  {name}"
                         + (f"  [{pb} B/device]" if pb else ""))
            for dev, d in sorted(c["devices"].items(),
                                 key=lambda kv: int(kv[0])):
                lines.append(f"    device {dev:>3}: {d['rounds']:>5} "
                             f"rounds, lag mean "
                             f"{d['lag_mean_s'] * 1e3:8.3f} ms, max "
                             f"{d['lag_max_s'] * 1e3:8.3f} ms")
            lines.append(f"    straggler: device {c['straggler']} "
                         f"(skew ratio {c['skew_ratio']})")
        if agg.get("straggler") is not None:
            lines.append(f"  mesh.skew.device: {agg['straggler']}")
    st = agg.get("stream", {})
    if st.get("passes"):
        lines.append("")
        lines.append(f"streaming passes: {st['passes']} "
                     f"(wall {st['wall_s']:.3f}s, attributed "
                     f"{st['attributed_s']:.3f}s)")
        for stage in ("prefetch_wait_s", "h2d_s", "device_fold_s",
                      "host_harvest_s"):
            share = (100.0 * st[stage] / st["wall_s"]
                     if st["wall_s"] > 0 else 0.0)
            lines.append(f"  {stage[:-2]:<16} {st[stage]:>10.4f}s "
                         f"{share:>5.1f}%")
    cnt = (agg.get("metrics") or {}).get("counters") or {}
    if cnt:
        lines.append("")
        lines.append("fleet counters (merged):")
        for name, v in sorted(cnt.items()):
            lines.append(f"  {name:<44} {v}")
    return "\n".join(lines)


# ----------------------------------------------------------------- CLI
def main(argv: Optional[List[str]] = None) -> int:
    """`python -m lightgbm_tpu timeline <spool_dir> [--trace out.json]
    [--json]` — merge a spool directory and render the fleet timeline;
    `--trace` additionally writes the Chrome-trace export."""
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m lightgbm_tpu timeline <spool_dir> "
              "[--trace out.json] [--json]", file=sys.stderr)
        return 0 if argv else 2
    as_json = "--json" in argv
    trace_out = None
    if "--trace" in argv:
        i = argv.index("--trace")
        if i + 1 >= len(argv):
            print("timeline: --trace needs an output path",
                  file=sys.stderr)
            return 2
        trace_out = argv[i + 1]
        del argv[i:i + 2]
    argv = [a for a in argv if a != "--json"]
    spool_dir = argv[0]
    if not os.path.isdir(spool_dir):
        print(f"timeline: not a directory: {spool_dir}", file=sys.stderr)
        return 2
    agg = aggregate(spool_dir)
    if trace_out is not None:
        with open(trace_out, "w") as f:
            json.dump(chrome_trace(agg), f)
        print(f"[timeline] wrote Chrome trace to {trace_out}",
              file=sys.stderr)
    if as_json:
        slim = {k: v for k, v in agg.items() if k != "events"}
        print(json.dumps(slim, default=str))
    else:
        print(render_timeline(agg))
    return 0


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
