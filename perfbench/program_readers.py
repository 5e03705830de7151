"""Readers of what the PROGRAM records of itself: its phases on the device
(`jax.named_scope` names, through `xplane_meta`), its spans of a round on
the profiler's clock, and its counters.

A reader has the shape of `readers.py`'s: `(ctx, args) -> number or None`,
None where there is nothing to read (a program without the scopes, spans or
counters: the metric is then left out).  Beside `readers.py`'s context
(`trace`, `units`, `shape`) these read

  ctx["trace_file"]  the `.xplane.pb` the trace was loaded from
  ctx["program"]     {"spans": the program's span events of the whole run
                      (`lightgbm_tpu.telemetry` MemorySink: name, id,
                      parent_id, round, start_ns, end_ns),
                      "counters_start" / "counters_end": its counters and
                      gauges when the window opened and closed}

  phase_share       100 * device self time of the grower's operations whose
                    innermost phase scope is `phase` / busy time.
                    args: phase ("" = under none of PHASES), program
  useful_rows       100 * rows the window's trees needed histograms of
                    (counter `counter`) / (selected kernel calls * rows)
                    args: counter + the kernel's selection
  host_gap_per_round  device-idle ms inside the window under a program
                    span, per round
  first_span_s      seconds of the run's first span named `span`
  counter_at_start  counters_start[counter]

`idle_gaps` names each long gap `<benchmark annotation>/<innermost program
span>`: the program's spans are found in the trace's host planes by the
names its own record lists, so they are on the device's clock.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

from . import trace as T
from . import xplane_meta as X
from .readers import _selected

#: the device phases of a tree (lightgbm_tpu/ops/grow_wave.py, grow.py)
PHASES = ("init", "payload", "partition", "histogram", "histogram_wave",
          "hist_cache", "find_split", "prune")


def phase_of(scope: str) -> str:
    """The innermost phase on a name stack, "" where there is none."""
    for part in reversed(scope.split("/")):
        if part in PHASES:
            return part
    return ""


# ------------------------------------------------------------ device phases
def phase_seconds(ctx: dict, program: str) -> Optional[Dict[str, float]]:
    """Device seconds by phase of the window's operations of `program`
    ("" = no phase); None where the file names no scope at all."""
    tr, path = ctx.get("trace"), ctx.get("trace_file")
    if tr is None or not path:
        return None
    cache = ctx.setdefault("_phase_seconds", {})
    if program not in cache:
        if "_meta" not in ctx:
            ctx["_meta"] = X.device_meta(path)
        acc: Dict[str, float] = {}
        scoped = False
        for op in T.select(tr, program=program):
            meta = X.scope_of(ctx["_meta"], op)
            scoped = scoped or bool(meta and meta.scope)
            phase = phase_of(meta.scope) if meta else ""
            acc[phase] = acc.get(phase, 0.0) + op.dur
        n = max(len(tr.devices), 1)
        cache[program] = {k: v / n / 1e9 for k, v in acc.items()} \
            if scoped else None
    return cache[program]


def phase_share(ctx, args) -> Optional[float]:
    secs = phase_seconds(ctx, args.get("program", "^jit_grow$"))
    if not secs or args["phase"] not in secs:
        return None
    busy = T.busy_seconds(ctx["trace"])
    return 100.0 * secs[args["phase"]] / busy if busy > 0 else None


# ----------------------------------------------------------------- counters
def _counter(ctx, when: str, name: str) -> Optional[float]:
    v = (ctx.get("program") or {}).get(when, {}).get(name)
    return None if v is None else float(v)


def counter_at_start(ctx, args) -> Optional[float]:
    return _counter(ctx, "counters_start", args["counter"])


def useful_rows(ctx, args) -> Optional[float]:
    tr = ctx.get("trace")
    lo = _counter(ctx, "counters_start", args["counter"])
    hi = _counter(ctx, "counters_end", args["counter"])
    rows = ctx.get("shape", {}).get("rows")
    if tr is None or lo is None or hi is None or not rows:
        return None
    calls = len(_selected(ctx, args)) / max(len(tr.devices), 1)
    return 100.0 * (hi - lo) / (calls * rows) if calls else None


# -------------------------------------------------------------------- spans
def first_span_s(ctx, args) -> Optional[float]:
    spans = [s for s in (ctx.get("program") or {}).get("spans", [])
             if s.get("name") == args["span"]]
    if not spans:
        return None
    first = min(spans, key=lambda s: s["start_ns"])
    return (first["end_ns"] - first["start_ns"]) / 1e9


def program_lines(ctx: dict) -> List[List[T.Span]]:
    """The program's spans on the profiler's clock: per host thread, in
    order of (start, longest first), so a parent comes before its
    children."""
    if "_program_lines" in ctx:
        return ctx["_program_lines"]
    names = {s.get("name") for s in (ctx.get("program") or {})
             .get("spans", [])}
    lines: List[List[T.Span]] = []
    if names and ctx.get("trace_file"):
        from jax.profiler import ProfileData
        for plane in ProfileData.from_file(ctx["trace_file"]).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                found = sorted((T.Span(ev.name, ev.start_ns, ev.duration_ns)
                                for ev in line.events if ev.name in names),
                               key=lambda s: (s.start, -s.dur))
                if found:
                    lines.append(found)
    ctx["_program_lines"] = lines
    return lines


def self_cover(line: List[T.Span], a: float, z: float) -> Dict[str, float]:
    """ns of [a, z) under each span name, a span's cover less its direct
    children's: what the innermost spans hold of the interval."""
    cover = [max(0.0, min(z, s.start + s.dur) - max(a, s.start))
             for s in line]
    own = list(cover)
    stack: List[int] = []
    for i, s in enumerate(line):
        while stack and line[stack[-1]].start + line[stack[-1]].dur \
                <= s.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= cover[i]
        stack.append(i)
    out: Dict[str, float] = {}
    for s, ns in zip(line, own):
        if ns > 0:
            out[s.name] = out.get(s.name, 0.0) + ns
    return out


def gaps_of(tr: T.Trace) -> List[Tuple[float, float]]:
    """The first device's idle intervals inside the window."""
    if not tr.devices:
        return []
    lo, hi = T.window_of(tr)
    edges = [lo] + [x for iv in T.busy(tr, tr.devices[0]) for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def gap_ns_by_span(ctx: dict, gaps=None) -> Dict[str, float]:
    """Device-idle ns by the innermost program span, of the window's gaps
    or of the given ones."""
    acc: Dict[str, float] = {}
    for a, z in gaps_of(ctx["trace"]) if gaps is None else gaps:
        for line in program_lines(ctx):
            for name, ns in self_cover(line, a, z).items():
                acc[name] = acc.get(name, 0.0) + ns
    return acc


def host_gap_per_round(ctx, args) -> Optional[float]:
    rounds = ctx.get("units", {}).get("rounds", 0)
    if ctx.get("trace") is None or not rounds or not program_lines(ctx):
        return None
    return sum(gap_ns_by_span(ctx).values()) / 1e6 / rounds


def idle_gaps(ctx: dict, top: int = 10) -> List[Tuple[str, float]]:
    """`trace.idle_gaps` with each gap named `<benchmark annotation>/
    <innermost program span>` (the annotation alone where no program span
    covers it)."""
    tr = ctx["trace"]
    out = []
    for a, z in sorted(gaps_of(tr), key=lambda g: g[0] - g[1])[:top]:
        outer, cover = "no_annotation", 0.0
        for s in tr.spans:
            c = min(z, s.start + s.dur) - max(a, s.start)
            if c > cover:
                outer, cover = s.name, c
        own = gap_ns_by_span(ctx, [(a, z)])
        inner = max(own, key=own.get) if own else ""
        out.append((f"{outer}/{inner}" if inner else outer, (z - a) / 1e9))
    return out


READERS: Dict[str, Callable[[dict, dict], Optional[float]]] = {
    "phase_share": phase_share,
    "useful_rows": useful_rows,
    "host_gap_per_round": host_gap_per_round,
    "first_span_s": first_span_s,
    "counter_at_start": counter_at_start,
}
