"""Profiler trace of the window, and its reduction to intervals.

`Tracer` wraps `jax.profiler` around the window and finds the `.xplane.pb`
it wrote.  `load(path)` reads it with `jax.profiler.ProfileData` into a
`Trace`: the device operations of every device plane and the benchmark's
own host annotations (`update`, `between_rounds`) on the same clock.  The
readers in `perfbench/readers.py` reduce a `Trace` to metrics; nothing else
reads the file.

What the first trace of this program on a v5e showed (PR 24, by hand):
a device plane is `/device:TPU:<n>`; its line `XLA Ops` holds one event per
executed HLO instruction, whose name is the instruction's whole text
(`%pallas_histogram_multi_rows.2 = f32[13,72,255]{...} custom-call(...)`)
and whose stats hold no scope path and no module: `jax.named_scope` names
do NOT reach the device events.  Control flow (`while`, `conditional`)
appears as events that enclose their bodies' events in time.  The line
`XLA Modules` holds one event per program execution
(`jit_grow(<fingerprint>)`).  So:

  - an operation's `name` is the instruction's own name without `%`, its
    `opcode` the HLO opcode, its `program` the module event that encloses
    it in time (fingerprint cut off);
  - an operation's `dur` is its SELF time: its duration less that of the
    events nested inside it, so sums over any selection count nothing
    twice and the operations of a plane add up to its busy time;
  - `scope` stays for traces that carry one (stat `tf_op`); it is "" here.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
INSTRUCTION = re.compile(r"^%?([^\s=]+)(?: = .*?[\]\})] ([A-Za-z][\w\-]*)\()?",
                         re.S)
ANNOTATION_PREFIX = "perfbench."


class Op(NamedTuple):
    name: str        # instruction name, e.g. "pallas_histogram_multi_rows.2"
    opcode: str      # HLO opcode, e.g. "custom-call", "fusion", "while"
    scope: str       # named-scope path, "" when the trace carries none
    program: str     # jitted program it ran in, e.g. "jit_grow"
    start: float     # ns
    span: float      # ns, whole duration
    dur: float       # ns, self time: span less the events nested inside
    device: int


class Span(NamedTuple):
    name: str
    start: float
    dur: float


class Trace(NamedTuple):
    ops: List[Op]                 # device operations, every device
    modules: List[Span]           # program executions (first device)
    spans: List[Span]             # the benchmark's host annotations
    devices: List[int]
    # the program's own spans (`load`'s `names`), one list a host thread in
    # order of (start, longest first), so a parent comes before its children
    lines: List[List[Span]] = []


# ----------------------------------------------------------------- recording
class Tracer:
    """Start and stop `jax.profiler` around the window."""

    def __init__(self, directory: str):
        self.directory = directory

    def start(self) -> None:
        import jax
        os.makedirs(self.directory, exist_ok=True)
        for old in glob.glob(os.path.join(
                self.directory, "plugins", "profile", "*", "*.xplane.pb")):
            os.remove(old)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host annotations only
        jax.profiler.start_trace(self.directory, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def file(self) -> Optional[str]:
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def annotation(name: str):
    """A host span in the profiler's own trace, on the device's clock."""
    import jax
    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)


@contextlib.contextmanager
def no_annotation(name: str):
    yield


# ------------------------------------------------------------------- reading
def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except Exception:       # a stat of a type the reader cannot convert
        return {}


def _self_times(events: List[Tuple[float, float]]) -> List[float]:
    """Self time of each (start, end) event of one line, given in order of
    (start, -end): its duration less its direct children's."""
    out = [hi - lo for lo, hi in events]
    stack: List[int] = []
    for i, (lo, hi) in enumerate(events):
        while stack and events[stack[-1]][1] <= lo:
            stack.pop()
        if stack:
            out[stack[-1]] -= min(hi, events[stack[-1]][1]) - lo
        stack.append(i)
    return [max(x, 0.0) for x in out]


def load(path: str, names: Iterable[str] = ()) -> Trace:
    """The file's device operations and annotations and, in the same pass
    over the host planes, the host events named in `names`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    names = frozenset(names)
    ops: List[Op] = []
    modules: List[Span] = []
    spans: List[Span] = []
    devices: List[int] = []
    lines: List[List[Span]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(2))
            mods: List[Span] = []
            raw = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    mods = sorted(
                        (Span(re.sub(r"\(.*$", "", ev.name), ev.start_ns,
                              ev.duration_ns) for ev in line.events),
                        key=lambda s: s.start)
                elif line.name == OPS_LINE:
                    raw = sorted(line.events,
                                 key=lambda e: (e.start_ns, -e.duration_ns))
            if not raw:
                continue
            devices.append(dev)
            if not modules:
                modules = mods
            starts = [s.start for s in mods]
            selfs = _self_times([(e.start_ns, e.start_ns + e.duration_ns)
                                 for e in raw])
            for ev, self_ns in zip(raw, selfs):
                mm = INSTRUCTION.match(ev.name)
                name = mm.group(1) if mm else ev.name
                opcode = (mm.group(2) if mm else None) or ""
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                program = mods[i].name if i >= 0 and ev.start_ns < \
                    mods[i].start + mods[i].dur else ""
                scope = str(_stats(ev).get("tf_op") or "")
                ops.append(Op(name, opcode, scope, program, ev.start_ns,
                              ev.duration_ns, self_ns, dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                found = []
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        spans.append(Span(ev.name[len(ANNOTATION_PREFIX):],
                                          ev.start_ns, ev.duration_ns))
                    elif ev.name in names:
                        found.append(Span(ev.name, ev.start_ns,
                                          ev.duration_ns))
                if found:
                    lines.append(sorted(found,
                                        key=lambda s: (s.start, -s.dur)))
    return Trace(ops, modules, sorted(spans, key=lambda s: s.start),
                 sorted(devices), lines)


# ---------------------------------------------------------------- arithmetic
def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def total(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def window_of(trace: Trace) -> Tuple[float, float]:
    """The traced window on the device's clock: from the start of the
    first `update` annotation to the end of the last annotation, or the
    extent of the device operations where there is no annotation."""
    if trace.spans:
        return (min(s.start for s in trace.spans),
                max(s.start + s.dur for s in trace.spans))
    if not trace.ops:
        return (0.0, 0.0)
    return (min(o.start for o in trace.ops),
            max(o.start + o.span for o in trace.ops))


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def busy(trace: Trace, device: Optional[int] = None
         ) -> List[Tuple[float, float]]:
    """Union of the intervals in which an operation ran, inside the window."""
    lo, hi = window_of(trace)
    return clip(union((o.start, o.start + o.span) for o in trace.ops
                      if device is None or o.device == device), lo, hi)


def busy_seconds(trace: Trace) -> float:
    """Busy time averaged over the devices used, in seconds."""
    if not trace.devices:
        return 0.0
    return sum(total(busy(trace, d)) for d in trace.devices) \
        / len(trace.devices) / 1e9


def select(trace: Trace, scope: Optional[str] = None,
           name: Optional[str] = None, program: Optional[str] = None,
           opcode: Optional[str] = None, not_scope: Optional[str] = None,
           not_name: Optional[str] = None,
           not_program: Optional[str] = None) -> List[Op]:
    """Operations inside the window whose scope path, own name, opcode and
    program match the given regular expressions (`re.search`)."""
    lo, hi = window_of(trace)

    def ok(o: Op) -> bool:
        return (o.start + o.span > lo and o.start < hi
                and (scope is None or re.search(scope, o.scope))
                and (name is None or re.search(name, o.name))
                and (program is None or re.search(program, o.program))
                and (opcode is None or re.search(opcode, o.opcode))
                and (not_name is None or not re.search(not_name, o.name))
                and (not_scope is None or not re.search(not_scope, o.scope))
                and (not_program is None
                     or not re.search(not_program, o.program)))
    return [o for o in trace.ops if ok(o)]


#: the arguments of `select`, as a metric file names them
SELECT = ("scope", "name", "program", "opcode", "not_scope", "not_name",
          "not_program")


def select_by(trace: Trace, args: dict) -> List[Op]:
    """`select` with the selection arguments that a metric's `args` give."""
    return select(trace, **{k: args[k] for k in SELECT if k in args})


def op_seconds(ops: Sequence[Op], n_devices: int) -> float:
    """Device time of the operations, averaged over devices, in seconds:
    the sum of their self times, which counts nothing twice."""
    return sum(o.dur for o in ops) / max(n_devices, 1) / 1e9


def idle_intervals(trace: Trace) -> List[Tuple[float, float]]:
    """The first device's idle intervals inside the window, in ns.
    `program_readers.idle_gaps` names the longest by what the host did."""
    if not trace.devices:
        return []
    lo, hi = window_of(trace)
    edges = [lo] + [x for iv in busy(trace, trace.devices[0])
                    for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def top_ops(trace: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """Device operations by total self time, as program:instruction with
    the instruction's number cut off (or the scope's tail where there is
    one)."""
    lo, hi = window_of(trace)
    acc: Dict[str, float] = {}
    for o in trace.ops:
        if o.start + o.span <= lo or o.start >= hi:
            continue
        tail = "/".join(o.scope.split("/")[-3:]) if o.scope else ""
        key = f"{o.program}:{tail or re.sub(r'[.][0-9]+$', '', o.name)}"
        acc[key] = acc.get(key, 0.0) + o.dur
    n = max(len(trace.devices), 1)
    return [(k, v / n / 1e9) for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def describe(path: str, limit: int = 40) -> str:
    """A hand-look at a trace file: planes, lines, and for each device line
    the events that took most time with one event's stats."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(evs)}")
            if not (DEVICE_PLANE.match(plane.name)
                    or any(e.name.startswith(ANNOTATION_PREFIX)
                           for e in evs[:2000])):
                continue
            acc: Dict[str, List] = {}
            for e in evs:
                a = acc.setdefault(e.name, [0, 0.0, e])
                a[0] += 1
                a[1] += e.duration_ns
            for name, (cnt, dur, e) in sorted(
                    acc.items(), key=lambda kv: -kv[1][1])[:limit]:
                out.append(f"    {dur / 1e9:10.4f}s x{cnt:<6d} {name[:100]} "
                           f"{ {k: str(v)[:160] for k, v in _stats(e).items()} }")
    return "\n".join(out)
