"""Spans + Tracer: named, nested wall-clock phases.

`tracer.span("train.chunk", rounds=16)` is a context manager that records
a wall-clock interval, maintains per-thread nesting (depth + parent name),
and on exit emits one `{"ev": "span", ...}` event to every attached sink
and one observation into the timing registry (`span.<name>`).

A span event carries what it takes to rebuild the tree it came from: `id`
(unique in the process), `parent_id` (beside the parent's `name`),
`start_ns` / `end_ns` on ONE monotonic clock (`time.perf_counter_ns`;
`dur_s` is their difference, `ts` stays the wall-clock stamp of the start
for merging across processes) and `round`: the boosting iteration, given
by the span that opens a round (`train.chunk`, `train.harvest`) and
inherited by every span under it, so the spans of one round share it.

Two cost regimes, chosen per `span()` call:

 - **inactive** (no sink attached, not force-enabled): `span()` returns a
   shared no-op context manager — one attribute check, zero allocation —
   so the instrumentation stays compiled into production hot paths
   (booster/engine/parallel/ops) at negligible cost.
 - **active**: wall time via `perf_counter`, and the body additionally
   runs under `jax.profiler.TraceAnnotation(name)` when jax is already
   loaded, so the host-side record and the XProf/Perfetto device timeline
   carry the SAME phase names and can be cross-read.

The spans of one boosting round (`Booster.update`, booster.py):

    train.chunk                 the round (attrs: rounds, fused; `round`)
      train.gradients           objective gradients dispatched
      train.sample              bagging / GOSS weights, feature mask
      compile_warmup            first dispatch of a (re)built grower
        train.grow              the grower's dispatch (returns at once)
      train.wait                dispatch end -> the device tree is ready:
                                the round's DEVICE time, seen from the host
      train.decode              device_get of the ready tree + host Tree
      train.score               leaf-value gather, score add, valid scores
      train.bookkeeping         model version, flight recorder, ledger

The fused chunk path keeps `train.chunk` (dispatch) and `train.harvest` >
`train.decode` (readback, decode), each with the chunk's first `round`.

The spans of set-up, all on the same clock as the round's:

    setup.booster               `Booster(params, train_set)`: everything
                                after the booster's own sinks attach
      setup.probe               one kernel probe that ran (a cached
                                verdict records nothing; attrs: max_bin,
                                num_feature, multi, width, quantized)
      setup.place               one upload of training state (attr
                                `what`: bins, bundle, ones, score, label,
                                weight); a recording span waits for the
                                arrays, so it holds the transfer
        parallel.place_data     the bin matrix placed on a mesh

`setup.probe` and `setup.place` also add their seconds to the gauges
`setup.probe_s` / `setup.place_s` (`summed_span`), which a recording
`setup.booster` starts at 0.  The gauge `setup.import_s` is the package's
import (`lightgbm_tpu/__init__.py`).  JAX's own compile pipeline reaches
the record as spans too (`recorder.install_compile_listener`), nested in
whatever span was open on the thread when JAX ran it (`setup.probe`,
`compile_warmup`, `train.gradients`, ...):

    jit.trace                   Python traced to a jaxpr (attr `fun`)
    jit.lower                   the jaxpr lowered to an MLIR module
    jit.compile                 compiled, or loaded from the persistent
                                cache (attr `cache`: hit / miss)

JAX traces a nested jit inside its caller's trace, so `jit.trace` spans
of one thread may overlap; the gauges `jit.trace_s` / `jit.lower_s` hold
the seconds of their UNION while a sink was attached.

Device-side, the phases of a tree are `jax.named_scope`s inside the jitted
growers; they reach the profiler's trace as the `tf_op` stat of a device
event's METADATA (`jax.profiler.ProfileData` does not show it;
`perfbench/xplane_meta.py` reads it).  The wave grower (ops/grow_wave.py)
carries `init`, `payload`, `partition`, `histogram_wave`, `hist_cache`,
`find_split`, `prune`; the strict grower (ops/grow.py) `histogram`,
`find_split`, `partition`; the fused chunk (ops/fused.py) `grad_hess`,
`grow_tree`, `update_scores` around them.

jax is mirrored via `sys.modules.get("jax")`, NEVER imported: jax-free
supervising processes load telemetry too, and a parent that touched jax
would hold the chip its child needs.
"""
from __future__ import annotations

import contextlib
import itertools
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from .metrics import REGISTRY
from .sinks import JsonlSink, Sink, make_event


class _NoopSpan:
    """Reusable do-nothing context manager (the inactive fast path)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


#: Shared do-nothing span — also handed out directly by call sites that
#: want a span only under some condition (`span(n) if cond else NOOP`).
NOOP = _NOOP = _NoopSpan()


class Span:
    """One named wall-clock phase; records itself on exit."""

    __slots__ = ("tracer", "name", "attrs", "t0", "end_ns", "wall0",
                 "depth", "parent", "id", "parent_id", "round", "_annot")

    _ids = itertools.count(1)

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.round = attrs.pop("round", None)
        self.attrs = attrs
        self.id = next(Span._ids)
        self._annot = None

    def __enter__(self) -> "Span":
        stack = self.tracer._stack()
        above = stack[-1] if stack else None
        self.parent = above.name if above else None
        self.parent_id = above.id if above else None
        if self.round is None and above is not None:
            self.round = above.round
        self.depth = len(stack)
        stack.append(self)
        jax = sys.modules.get("jax")
        if jax is not None:
            try:
                self._annot = jax.profiler.TraceAnnotation(self.name)
                self._annot.__enter__()
            except Exception:
                self._annot = None
        self.wall0 = time.time()
        self.t0 = time.perf_counter_ns()
        return self

    def set(self, **attrs) -> "Span":
        """Attach attributes discovered mid-span (e.g. row counts known
        only after construction); emitted with the exit event."""
        self.attrs.update(attrs)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        end = self.end_ns = time.perf_counter_ns()
        if self._annot is not None:
            try:
                self._annot.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:       # unbalanced exit (generator teardown)
            stack.remove(self)
        ev = _span_event(self.name, self.id, self.depth, self.t0, end,
                         self.wall0, self.parent, self.parent_id,
                         self.round, self.attrs)
        if exc_type is not None:
            ev["error"] = getattr(exc_type, "__name__", str(exc_type))
        self.tracer._emit(ev)
        return False


def _span_event(name: str, span_id: int, depth: int, start_ns: int,
                end_ns: int, wall0: float, parent: Optional[str],
                parent_id: Optional[int], round_: Optional[int],
                attrs: Dict[str, Any]) -> Dict[str, Any]:
    """The event of one ended span; its seconds are observed into the
    timing registry (`span.<name>`)."""
    dur = (end_ns - start_ns) / 1e9
    REGISTRY.timing(f"span.{name}").observe(dur)
    ev = make_event("span", name, dur_s=round(dur, 6), depth=depth,
                    pid=os.getpid(), id=span_id, start_ns=start_ns,
                    end_ns=end_ns)
    ev["ts"] = round(wall0, 6)  # span events stamp their START
    if parent is not None:
        ev["parent"] = parent
        ev["parent_id"] = parent_id
    if round_ is not None:
        ev["round"] = round_
    if attrs:
        ev["attrs"] = attrs
    return ev


class Tracer:
    """Process-global span recorder with pluggable sinks."""

    def __init__(self):
        self._sinks: List[Sink] = []
        self._jsonl_paths: Dict[str, JsonlSink] = {}
        self._tls = threading.local()
        self._forced = False
        self._lock = threading.Lock()

    # ------------------------------------------------------------- sinks
    @property
    def active(self) -> bool:
        return bool(self._sinks) or self._forced

    def enable(self, flag: bool = True) -> None:
        """Force span recording (into the metrics registry) even with no
        sink attached — for in-process inspection via REGISTRY."""
        self._forced = bool(flag)

    def add_sink(self, sink: Sink) -> Sink:
        with self._lock:
            if sink not in self._sinks:
                self._sinks.append(sink)
        return sink

    def remove_sink(self, sink: Sink) -> None:
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)
            for p, s in list(self._jsonl_paths.items()):
                if s is sink:
                    del self._jsonl_paths[p]
        sink.close()

    def attach_jsonl(self, path: str) -> JsonlSink:
        """Attach (or reuse) a JSONL file sink — idempotent per abspath,
        so every Booster constructed with the same `telemetry_sink` param
        shares one appender instead of stacking duplicates."""
        key = os.path.abspath(path)
        with self._lock:
            sink = self._jsonl_paths.get(key)
            if sink is None:
                sink = JsonlSink(key)
                self._jsonl_paths[key] = sink
                self._sinks.append(sink)
        return sink

    def clear_sinks(self) -> None:
        with self._lock:
            sinks, self._sinks = self._sinks, []
            self._jsonl_paths.clear()
        for s in sinks:
            s.close()

    def flush(self) -> None:
        for s in list(self._sinks):
            s.flush()

    # ------------------------------------------------------------- spans
    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, **attrs: Any):
        """Context manager for a named phase; no-op when inactive."""
        if not self.active:
            return _NOOP
        return Span(self, name, attrs)

    def current_span(self) -> Optional[Span]:
        st = self._stack()
        return st[-1] if st else None

    def record(self, name: str, start_ns: int, end_ns: int,
               wall0: float, **attrs: Any) -> None:
        """Emit a span that has already ended, timed by someone else (on
        `perf_counter_ns`; `wall0` the wall-clock start), as a child of
        the span open on this thread.  It never starts before that
        parent: a start converted from another clock may read a few
        microseconds early.  No-op when inactive."""
        if not self.active:
            return
        stack = self._stack()
        above = stack[-1] if stack else None
        if above is not None:
            start_ns = max(start_ns, above.t0)
        end_ns = max(end_ns, start_ns)
        self._emit(_span_event(
            name, next(Span._ids), len(stack), start_ns, end_ns, wall0,
            above.name if above else None, above.id if above else None,
            above.round if above else None, attrs))

    # ------------------------------------------------------------ events
    def _emit(self, event: Dict[str, Any]) -> None:
        for s in list(self._sinks):
            try:
                s.emit(event)
            except Exception:
                # a dead sink (full disk, closed stream) must never take
                # down training — drop the event, keep the run alive
                pass

    def event(self, name: str, **fields: Any) -> Dict[str, Any]:
        """Emit a point event (probe attempt, fallback, ...).  Always
        counts into the registry (`event.<name>`); reaches sinks only
        when one is attached."""
        REGISTRY.counter(f"event.{name}").inc()
        ev = make_event("event", name, **fields)
        if self._sinks:
            self._emit(ev)
        return ev

    def emit_metrics_snapshot(self) -> None:
        """Write the current registry state to the sinks as one event —
        callers (engine.train end, bench worker exit) use it so a JSONL
        file is self-contained for `telemetry-report`."""
        if not self._sinks:
            return
        self._emit(make_event("metrics", "registry",
                              **{"snapshot": REGISTRY.snapshot()}))


#: The process-global tracer every instrumented path records into.
TRACER = Tracer()


def span(name: str, **attrs: Any):
    return TRACER.span(name, **attrs)


@contextlib.contextmanager
def summed_span(name: str, **attrs: Any):
    """`span(name)` whose seconds, when it records, also add up in the
    gauge `<name>_s`: a total the registry holds for whoever snapshots it
    (`setup.probe_s`, `setup.place_s`).  Inactive: the shared no-op, and
    no gauge."""
    with TRACER.span(name, **attrs) as s:
        yield s
    if s is not _NOOP:
        g = REGISTRY.gauge(name + "_s")
        g.set(g.value + (s.end_ns - s.t0) / 1e9)


def event(name: str, **fields: Any) -> Dict[str, Any]:
    return TRACER.event(name, **fields)
