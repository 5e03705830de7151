"""The fixed set of readers that turn a traced run into per-layer metrics.

A per-layer metric is a file `layer_metrics/<name>.json` that names one of
these readers and gives its arguments as data, so a new metric over an
existing reader is one new file.  A reader gets the run's context
(`trace`, `trace_file`, `program`, `counters`, `memory`, `units`, `shape`,
`peaks`: `run.per_layer` builds it) and returns a number, or None where it
finds nothing to read: the harness then leaves the metric out of the line.
No reader returns 0 for a share of a roofline.  `READERS` also holds those
of `program_readers.py`, which read what the program records of itself.

  scope_share        100 * device time of the selected operations / busy time
                     args: scope, name, program, opcode, not_scope,
                     not_name, not_program (regular expressions, any subset)
  program_share      the same; named apart for metrics that select by program
  scope_count_per    selected operations / units[per]   args: as above + per
  idle_share         100 * (1 - busy / window)
  roofline_share     100 * least time for the selected calls / their device
                     time.  args: selection + work (a function of work.py),
                     work_args (names of the run's shape fields or numbers),
                     slots_from_scope (optional regex with one group that
                     reads the call's slot count from its scope or name)
  counter_delta      counters[counter]: the counter's change over the window
  memory_peak_share  100 * peak bytes in use / the device's capacity
"""
from __future__ import annotations

import re
from typing import Callable, Dict, Optional

from . import program_readers as P
from . import trace as T
from . import work as W


def _selected(ctx, args):
    if ctx.get("trace") is None:
        return None
    return T.select_by(ctx["trace"], args)


def scope_share(ctx, args) -> Optional[float]:
    ops = _selected(ctx, args)
    if ops is None:
        return None
    busy = P.busy_s(ctx)
    if busy <= 0:
        return None
    if not ops and not args.get("zero_if_absent", False):
        return None
    return 100.0 * T.op_seconds(ops, len(ctx["trace"].devices)) / busy


def scope_count_per(ctx, args) -> Optional[float]:
    ops = _selected(ctx, args)
    units = ctx.get("units", {}).get(args.get("per", "trees"), 0)
    if not ops or not units:
        return None
    return len(ops) / max(len(ctx["trace"].devices), 1) / units


def idle_share(ctx, args) -> Optional[float]:
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return None
    lo, hi = T.window_of(tr)
    if hi <= lo:
        return None
    return 100.0 * (1.0 - P.busy_s(ctx) / ((hi - lo) / 1e9))


def roofline_share(ctx, args) -> Optional[float]:
    ops = _selected(ctx, args)
    if not ops:
        return None
    fn = W.FUNCTIONS[args["work"]]
    shape = ctx["shape"]
    fixed = {k: (shape[v] if isinstance(v, str) else v)
             for k, v in args.get("work_args", {}).items()}
    slots_re = args.get("slots_from_scope")
    least = spent = 0.0
    for o in ops:
        kw = dict(fixed)
        if slots_re:
            m = re.search(slots_re, o.scope) or re.search(slots_re, o.name)
            if m:
                kw["slots"] = int(m.group(1))
        least += W.least_seconds(fn(**kw), ctx["peaks"])["seconds"]
        spent += o.dur / 1e9
    if spent <= 0:
        return None
    return 100.0 * least / spent


def counter_delta(ctx, args) -> Optional[float]:
    v = ctx.get("counters", {}).get(args["counter"])
    return None if v is None else float(v)


def memory_peak_share(ctx, args) -> Optional[float]:
    peak = ctx.get("memory", {}).get("peak_bytes")
    cap = ctx.get("peaks", {}).get("hbm_bytes")
    if not peak or not cap:
        return None
    return 100.0 * peak / cap


READERS: Dict[str, Callable[[dict, dict], Optional[float]]] = {
    "scope_share": scope_share,
    "program_share": scope_share,
    "scope_count_per": scope_count_per,
    "idle_share": idle_share,
    "roofline_share": roofline_share,
    "counter_delta": counter_delta,
    "memory_peak_share": memory_peak_share,
    **P.READERS,
}


def read(metric: dict, ctx: dict) -> Optional[float]:
    """The metric's value from the run's context, or None."""
    return READERS[metric["reader"]](ctx, metric.get("args", {}))
