"""Unified telemetry: spans, metrics, structured event sinks.

Three always-available pieces (see ISSUE: observability tentpole):

 - `TRACER` / `span()` — named, nested wall-clock phases mirrored into
   `jax.profiler.TraceAnnotation` when jax is loaded (spans.py);
 - `REGISTRY` — process-global counters / gauges / timing accumulators
   with JSON snapshot + Prometheus text export (metrics.py);
 - sinks — JSONL event log + in-memory capture (sinks.py), summarized
   by `python -m lightgbm_tpu telemetry-report` (report.py).

This package NEVER imports jax, so jax-free processes can load the
submodules by file path.  (Importing it as `lightgbm_tpu.telemetry` runs
`lightgbm_tpu/__init__.py`, which does pull jax — jax-free callers must
use `importlib.util.spec_from_file_location` on the submodule files
(tests/test_telemetry.py::test_jax_free_import shows how).)
"""
from .metrics import (Counter, Gauge, Histogram, HISTOGRAM_BOUNDS,
                      MetricsRegistry, REGISTRY, Timing, write_prometheus)
from .sinks import (JsonlSink, MemorySink, Sink, iso_ts, make_event,
                    read_jsonl, read_jsonl_counted)
from .spans import NOOP, Span, TRACER, Tracer, event, span, summed_span
from .spool import (SpoolSink, aggregate as aggregate_spool, attach_spool,
                    chrome_trace, render_timeline)
from .report import render, summarize
from .recorder import (FlightRecorder, install_compile_listener,
                       memory_watermarks, poll_jit_caches, sample_memory,
                       throughput_report, tree_stats)
from .request_trace import (RequestTrace, SERVE_RECORDER, ServeRecorder,
                            StageClock, e2e_latency_summary, new_request_id,
                            observe_stages, server_latency_block)
from .diff import diff_snapshots, flatten, load_snapshot
from .ledger import LEDGER, Ledger, ancestry, ledger_records, rejections
from .memledger import (LeakSentinel, MemHandle, MEMLEDGER, MemoryLedger,
                        is_oom, render_memory)
from .slo import BurnRateMeter
from .ops import fleet_snapshot, render_top

__all__ = [
    "Counter", "Gauge", "Histogram", "HISTOGRAM_BOUNDS", "MetricsRegistry",
    "REGISTRY", "Timing", "write_prometheus",
    "JsonlSink", "MemorySink", "Sink", "iso_ts", "make_event", "read_jsonl",
    "read_jsonl_counted",
    "NOOP", "Span", "TRACER", "Tracer", "event", "span", "summed_span",
    "SpoolSink", "aggregate_spool", "attach_spool", "chrome_trace",
    "render_timeline",
    "render", "summarize",
    "FlightRecorder", "install_compile_listener", "memory_watermarks",
    "poll_jit_caches", "sample_memory", "throughput_report", "tree_stats",
    "RequestTrace", "SERVE_RECORDER", "ServeRecorder", "StageClock",
    "e2e_latency_summary", "new_request_id", "observe_stages",
    "server_latency_block",
    "diff_snapshots", "flatten", "load_snapshot",
    "LEDGER", "Ledger", "ancestry", "ledger_records", "rejections",
    "LeakSentinel", "MemHandle", "MEMLEDGER", "MemoryLedger", "is_oom",
    "render_memory",
    "BurnRateMeter",
    "fleet_snapshot", "render_top",
]
