"""Readers of what the PROGRAM records of itself: its phases on the device
(`jax.named_scope` names, through `xplane_meta`), its spans of a round on
the profiler's clock, and its counters.

They are part of `readers.READERS`: a reader has that module's shape,
`(ctx, args) -> number or None`, None where there is nothing to read (a
program without the scopes, spans or counters: the metric is then left
out).  Beside the trace these read what the job hands on from a traced run
(`jobs/train.ProgramRecord`):

  ctx["trace_file"]  the `.xplane.pb` the trace was loaded from
  ctx["program"]     {"spans": the program's span events of the run up to
                      the window's close (`lightgbm_tpu.telemetry`
                      MemorySink: name, id, parent_id, round, start_ns,
                      end_ns), "counters_start" / "counters_end": its
                      counters and gauges when the window opened and closed}
  ctx["counters"]    the change of every counter over the window

  phase_share       100 * device self time of the grower's operations whose
                    innermost phase scope is `phase` / busy time.
                    args: phase ("" = under none of PHASES), program
  useful_rows       100 * rows the window's trees needed histograms of
                    (the window's change of `counter`) / (selected kernel
                    calls * rows)
                    args: counter + the kernel's selection
  host_gap_per_round  device-idle ms inside the window under a program
                    span, per round
  first_span_s      seconds of the run's first span named `span`
  counter_at_start  counters_start[counter]
  counter_ratio     scale * (sum of the window's changes of the counters
                    `num`) / (sum of those of `den`, or units[per]), the
                    denominator less units[less_per] and times the gauge
                    `gauge` as the window closed (`mesh.shards`: a count
                    summed over shards, per shard).  A counter the program
                    never touched adds 0; None where it has none of the
                    named counters, the gauge is absent or the denominator
                    is not above 0.  args: num, den | per, less_per, gauge,
                    scale

`idle_gaps` names each long gap `<benchmark annotation>/<innermost program
span>`: the program's spans are found in the trace's host planes by the
names its own record lists, so they are on the device's clock.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

from . import trace as T
from . import xplane_meta as X

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


def busy_s(ctx: dict) -> float:
    """`trace.busy_seconds` of the context's trace, worked out once."""
    if "_busy_s" not in ctx:
        ctx["_busy_s"] = T.busy_seconds(ctx["trace"])
    return ctx["_busy_s"]


def phase_share(ctx, args) -> Optional[float]:
    secs = phase_seconds(ctx, args.get("program", "^jit_grow$"))
    if not secs or args["phase"] not in secs:
        return None
    busy = busy_s(ctx)
    return 100.0 * secs[args["phase"]] / busy if busy > 0 else None


# ----------------------------------------------------------------- counters
def _counter(ctx, when: str, name: str) -> Optional[float]:
    v = (ctx.get("program") or {}).get(when, {}).get(name)
    return None if v is None else float(v)


def counter_at_start(ctx, args) -> Optional[float]:
    return _counter(ctx, "counters_start", args["counter"])


def useful_rows(ctx, args) -> Optional[float]:
    tr = ctx.get("trace")
    needed = (ctx.get("counters") or {}).get(args["counter"])
    rows = ctx.get("shape", {}).get("rows")
    if tr is None or needed is None or not rows:
        return None
    calls = len(T.select_by(tr, args)) / max(len(tr.devices), 1)
    return 100.0 * needed / (calls * rows) if calls else None


def counter_ratio(ctx, args) -> Optional[float]:
    delta, units = ctx.get("counters") or {}, ctx.get("units", {})
    named = list(args["num"]) + list(args.get("den", []))
    if not any(n in delta for n in named):
        return None                 # the program records none of them

    def total(names) -> float:      # a counter never touched adds 0
        return sum(float(delta.get(n, 0.0)) for n in names)
    den = total(args["den"]) if "den" in args \
        else float(units.get(args["per"], 0))
    den -= float(units.get(args["less_per"], 0)) if "less_per" in args \
        else 0.0
    if "gauge" in args:
        den *= _counter(ctx, "counters_end", args["gauge"]) or 0.0
    if den <= 0:
        return None
    return float(args.get("scale", 1.0)) * total(args["num"]) / den


# -------------------------------------------------------------------- spans
def first_span_s(ctx, args) -> Optional[float]:
    spans = [s for s in (ctx.get("program") or {}).get("spans", [])
             if s.get("name") == args["span"]]
    if not spans:
        return None
    first = min(spans, key=lambda s: s["start_ns"])
    return (first["end_ns"] - first["start_ns"]) / 1e9


def span_names(program: Optional[dict]) -> set:
    """The names of the program's spans: `trace.load` finds them in the
    trace's host planes, in the pass that finds the annotations."""
    return {s.get("name") for s in (program or {}).get("spans", [])}


def program_lines(ctx: dict) -> List[List[T.Span]]:
    """The program's spans on the profiler's clock, one list a host thread
    (`trace.Trace.lines`)."""
    tr = ctx.get("trace")
    return tr.lines if tr is not None else []


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


def gap_ns_by_span(ctx: dict, gaps=None) -> Dict[str, float]:
    """Device-idle ns by the innermost program span, of the given gaps or
    of all the window's (worked out once a context)."""
    if gaps is None and "_gap_ns_by_span" in ctx:
        return ctx["_gap_ns_by_span"]
    acc: Dict[str, float] = {}
    for a, z in T.idle_intervals(ctx["trace"]) if gaps is None else gaps:
        for line in program_lines(ctx):
            for name, ns in self_cover(line, a, z).items():
                acc[name] = acc.get(name, 0.0) + ns
    if gaps is None:
        ctx["_gap_ns_by_span"] = acc
    return acc


def host_gap_per_round(ctx, args) -> Optional[float]:
    rounds, tr = ctx.get("units", {}).get("rounds", 0), ctx.get("trace")
    if tr is None or not tr.devices or not rounds or not program_lines(ctx):
        return None
    return sum(gap_ns_by_span(ctx).values()) / 1e6 / rounds


def idle_gaps(ctx: dict, top: int = 10) -> List[Tuple[str, float]]:
    """The first device's longest idle gaps inside the window, seconds,
    each named `<benchmark annotation>/<innermost program span>`: the
    annotation that covers most of it (`no_annotation` where none does),
    alone where no program span covers it."""
    tr = ctx["trace"]
    out = []
    for a, z in sorted(T.idle_intervals(tr),
                       key=lambda g: g[0] - g[1])[:top]:
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
    "counter_ratio": counter_ratio,
}
