"""Set-up recorded by the program itself: the spans of `Booster(params,
train_set)` (`setup.booster`, `setup.probe`, `setup.place`), JAX's compile
pipeline as spans (`jit.trace`, `jit.lower`, `jit.compile`), the gauges
that sum them, the import's gauge, and the benchmark's `setup.*` metrics
read from such a record by the readers it already has."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.ops import pallas_hist
from lightgbm_tpu.telemetry import recorder
from perfbench import manifest, readers

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PARENT_RECORD = os.path.join(HERE, "perfbench", "fixtures", "driver_round",
                             "program.json")
SETUP_METRICS = ("setup.import_s", "setup.booster_s", "setup.probe_s",
                 "setup.place_s", "setup.trace_s", "setup.lower_s")
GAUGES = ("setup.probe_s", "setup.place_s", "jit.trace_s", "jit.lower_s")


def _data(n=2000, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 5)
    return X, (X[:, 0] + 0.1 * rng.rand(n) > 0.5).astype(float)


def _gauges():
    return dict(telemetry.REGISTRY.snapshot()["gauges"])


@pytest.fixture(scope="module")
def traced():
    """A tiny booster built and trained one round under a `MemorySink`,
    the Pallas kernel in interpret mode so that its probe runs: (the span
    events, the gauges before the booster, when the round began and after
    it, and the record as the benchmark's job keeps it)."""
    assert recorder.install_compile_listener()
    X, y = _data()
    ds = lgb.Dataset(X, label=y).construct()
    saved_cache = dict(pallas_hist._PROBE_CACHE)
    pallas_hist._PROBE_CACHE.clear()
    sink = telemetry.TRACER.add_sink(telemetry.MemorySink())
    try:
        before = _gauges()
        bst = lgb.Booster({"objective": "binary", "verbosity": -1,
                           "num_leaves": 8, "hist_impl": "pallas",
                           "hist_interpret": True}, ds)
        opened = _gauges()
        bst.update()
        closed = _gauges()
    finally:
        telemetry.TRACER.remove_sink(sink)
        pallas_hist._PROBE_CACHE.clear()
        pallas_hist._PROBE_CACHE.update(saved_cache)
    spans = [e for e in sink.events if e.get("ev") == "span"]
    record = {"spans": spans, "counters_start": opened,
              "counters_end": closed}
    return spans, before, opened, closed, record


def _by_name(spans, name):
    return [e for e in spans if e["name"] == name]


def _inside(child, parent):
    return parent["start_ns"] <= child["start_ns"] <= child["end_ns"] \
        <= parent["end_ns"]


# ------------------------------------------------------ the booster's spans
def test_the_booster_records_its_probe_and_uploads_inside_its_span(traced):
    spans, before, opened, _, _ = traced
    (booster,) = _by_name(spans, "setup.booster")
    places = _by_name(spans, "setup.place")
    assert [p["attrs"]["what"] for p in places] == ["bins", "ones", "score",
                                                    "label"]
    (probe,) = _by_name(spans, "setup.probe")
    assert probe["attrs"] == {"max_bin": 255, "num_feature": 5,
                              "multi": False, "width": None,
                              "quantized": False}
    for child in places + [probe]:
        assert child["parent_id"] == booster["id"]
        assert _inside(child, booster)
    # the gauges add up the spans' seconds
    delta = {g: opened[g] - before.get(g, 0.0) for g in GAUGES}
    assert delta["setup.probe_s"] == pytest.approx(probe["dur_s"], abs=1e-5)
    assert delta["setup.place_s"] == pytest.approx(
        sum(p["dur_s"] for p in places), abs=1e-5)
    assert 0 < delta["setup.probe_s"] + delta["setup.place_s"] \
        <= booster["dur_s"]


def test_a_cached_probe_records_nothing():
    X, y = _data(600, 1)
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 4,
              "hist_impl": "pallas", "hist_interpret": True}
    lgb.Booster(params, lgb.Dataset(X, label=y))     # fills the cache
    sink = telemetry.TRACER.add_sink(telemetry.MemorySink())
    try:
        g0 = _gauges()
        lgb.Booster(params, lgb.Dataset(X, label=y))
        g1 = _gauges()
    finally:
        telemetry.TRACER.remove_sink(sink)
    names = [e["name"] for e in sink.events if e.get("ev") == "span"]
    assert "setup.booster" in names and "setup.probe" not in names
    # a recording booster starts the gauge at 0: it reads, and adds nothing
    assert g1["setup.probe_s"] == g0.get("setup.probe_s", 0.0)


# ---------------------------------------------------- JAX's compile pipeline
def test_jit_spans_carry_fun_and_nest_in_the_span_open_when_jax_ran(traced):
    spans = traced[0]
    ids = {e["id"]: e for e in spans}
    jit = [e for e in spans if e["name"].startswith("jit.")]
    assert {e["name"] for e in jit} == {"jit.trace", "jit.lower",
                                        "jit.compile"}
    for e in jit:
        assert e["attrs"]["fun"], e
        parent = ids[e["parent_id"]]
        assert parent["name"] == e["parent"]
        assert _inside(e, parent), e
    assert {e["attrs"]["cache"] for e in _by_name(jit, "jit.compile")} \
        <= {"hit", "miss"}
    # where JAX compiles: the probe, the uploads' programs, the first round
    parents = {e["parent"] for e in jit}
    assert {"setup.probe", "train.grow"} <= parents
    (probe,) = _by_name(spans, "setup.probe")
    assert any(e["parent_id"] == probe["id"]
               for e in _by_name(jit, "jit.compile"))


def test_the_union_gauges_count_nested_traces_once(traced):
    spans, before, _, closed, _ = traced
    for name in ("jit.trace", "jit.lower"):
        got = closed[name + "_s"] - before.get(name + "_s", 0.0)
        intervals = sorted((e["start_ns"], e["end_ns"])
                           for e in _by_name(spans, name))
        union, end = 0, None
        for a, z in intervals:
            if end is None or a > end:
                union += z - a
                end = z
            elif z > end:
                union += z - end
                end = z
        assert got == pytest.approx(union / 1e9, abs=1e-4), name
        assert got <= sum(z - a for a, z in intervals) / 1e9 + 1e-6


@pytest.mark.parametrize("adds,want", [
    ([(0, 10), (2, 3), (4, 6)], 10),            # nested: the outer arrives last
    ([(2, 3), (4, 6), (0, 10)], 10),
    ([(0, 4), (3, 7)], 7),                      # overlapping
    ([(5, 6), (0, 1), (2, 3)], 3),              # disjoint, out of order
    ([(0, 1), (2, 3), (0.5, 2.5)], 3),          # bridging two
    ([(1, 2), (1, 2)], 1),                      # the same twice
])
def test_cover_adds_what_an_interval_adds_to_the_union(adds, want):
    intervals, total = [], 0.0
    for a, z in adds:
        total += recorder._cover(intervals, a, z)
    assert total == pytest.approx(want)
    assert sum(z - a for a, z in intervals) == pytest.approx(want)
    assert intervals == sorted(intervals)
    assert all(intervals[i][1] < intervals[i + 1][0]
               for i in range(len(intervals) - 1))


def test_a_recorded_span_never_starts_before_its_parent():
    sink = telemetry.TRACER.add_sink(telemetry.MemorySink())
    try:
        with telemetry.span("outer") as outer:
            telemetry.TRACER.record("jit.trace", outer.t0 - 5000,
                                    outer.t0 + 1000, 0.0, fun="f")
    finally:
        telemetry.TRACER.remove_sink(sink)
    (rec,) = [e for e in sink.events if e["name"] == "jit.trace"]
    assert rec["parent_id"] == outer.id and rec["start_ns"] == outer.t0
    assert rec["end_ns"] == outer.t0 + 1000 and rec["depth"] == 1


def test_without_a_sink_the_listener_counts_as_before_and_emits_nothing(
        monkeypatch):
    import jax
    assert recorder.install_compile_listener()
    assert not telemetry.TRACER.active
    emitted = []
    monkeypatch.setattr(telemetry.TRACER, "_emit", emitted.append)
    compiles = telemetry.REGISTRY.counter("jit.recompiles")
    misses = telemetry.REGISTRY.counter("jit.cache_misses")
    total = telemetry.REGISTRY.gauge("jit.compile_total_s")
    c0, m0, t0, g0 = compiles.value, misses.value, total.value, _gauges()
    mon = jax.monitoring
    mon.record_event_time_span("/jax/core/compile/jaxpr_trace_duration",
                               10.0, 10.5, fun_name="f")
    mon.record_event_time_span(
        "/jax/core/compile/jaxpr_to_mlir_module_duration", 10.5, 10.75,
        fun_name="f")
    mon.record_event("/jax/compilation_cache/cache_hits")
    mon.record_event_time_span("/jax/core/compile/backend_compile_duration",
                               10.75, 11.0, fun_name="f")
    mon.record_event("/jax/compilation_cache/cache_misses")
    assert (compiles.value, misses.value) == (c0 + 1, m0 + 1)
    assert total.value == pytest.approx(t0 + 0.25)
    assert emitted == []
    g1 = _gauges()
    assert all(g1.get(g) == g0.get(g) for g in ("jit.trace_s",
                                                "jit.lower_s"))


def test_a_compile_that_loaded_from_the_cache_says_hit():
    import jax
    assert recorder.install_compile_listener()
    sink = telemetry.TRACER.add_sink(telemetry.MemorySink())
    mon = jax.monitoring
    now = recorder.time.time()
    try:
        mon.record_event_time_span(
            "/jax/core/compile/backend_compile_duration", now - 2, now - 1,
            fun_name="old")
        mon.record_event("/jax/compilation_cache/cache_hits")
        mon.record_event_time_span(
            "/jax/core/compile/backend_compile_duration", now - 1,
            recorder.time.time(), fun_name="loaded")
    finally:
        telemetry.TRACER.remove_sink(sink)
    got = {e["attrs"]["fun"]: e["attrs"]["cache"] for e in sink.events
           if e["name"] == "jit.compile"}
    assert got == {"old": "miss", "loaded": "hit"}


def test_untraced_set_up_makes_no_span(monkeypatch):
    """With no sink, `Booster(...)` goes through the shared no-op: not one
    `Span` is built, and no upload waits."""
    from lightgbm_tpu.telemetry import spans as spans_mod
    assert not telemetry.TRACER.active
    X, y = _data(500, 2)
    ds = lgb.Dataset(X, label=y).construct()
    made, waited = [], []
    real = spans_mod.Span.__init__

    def counting(self, *a, **kw):
        made.append(a[1])
        real(self, *a, **kw)
    monkeypatch.setattr(spans_mod.Span, "__init__", counting)
    monkeypatch.setattr("jax.block_until_ready", waited.append)
    lgb.Booster({"objective": "binary", "verbosity": -1, "num_leaves": 4},
                ds)
    assert made == [] and waited == []


def test_the_import_sets_a_positive_gauge():
    """`setup.import_s` in a fresh interpreter, as the benchmark's job
    sees it (this process's registry may have been reset by a test)."""
    code = ("import lightgbm_tpu\n"
            "from lightgbm_tpu import telemetry\n"
            "print(telemetry.REGISTRY.snapshot()['gauges']"
            "['setup.import_s'])\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert 0 < float(out.stdout.strip().splitlines()[-1]) < 300


# ---------------------------------------------- the benchmark's six metrics
def _setup_metrics():
    d = os.path.join(manifest.HERE, "layer_metrics")
    return [json.load(open(os.path.join(d, n + ".json")))
            for n in SETUP_METRICS]


def test_the_six_metrics_read_the_programs_record(traced):
    spans, before, opened, _, record = traced
    record = dict(record, counters_start=dict(
        opened, **{"setup.import_s": 4.5}))
    ctx = {"program": record, "counters": {}}
    got = {m["name"]: readers.read(m, ctx) for m in _setup_metrics()}
    assert all(v is not None and v >= 0 for v in got.values()), got
    (booster,) = _by_name(spans, "setup.booster")
    assert got["setup.booster_s"] == pytest.approx(booster["dur_s"],
                                                   abs=1e-6)
    assert got["setup.import_s"] == 4.5
    # probe + place <= booster holds for the gauges of one booster
    assert (got["setup.probe_s"] - before.get("setup.probe_s", 0.0)) \
        + (got["setup.place_s"] - before.get("setup.place_s", 0.0)) \
        <= got["setup.booster_s"]
    assert got["setup.trace_s"] > 0 and got["setup.lower_s"] > 0


def test_the_six_metrics_read_nothing_on_the_parents_record():
    """The parent's program records none of it: each metric is left out
    of the line (None), and no reader raises."""
    with open(PARENT_RECORD) as f:
        saved = json.load(f)
    ctx = {"program": saved["program"], "counters": saved["counters"]}
    for m in _setup_metrics():
        assert readers.read(m, ctx) is None, m["name"]
    assert all(readers.read(m, {"program": None, "counters": {}}) is None
               for m in _setup_metrics())


def test_the_six_metrics_are_in_the_manifest_and_move_setup():
    b = manifest.benchmark()
    entries = {m["name"]: m for m in b["per_layer"]}
    cells = [w["name"] for w in b["workloads"]]
    for m in _setup_metrics():
        e = entries[m["name"]]
        assert (e["moves"], e["unit"], e["better"], e["layer"]) == (
            "setup_s", "s", "lower", "entry")
        assert e["workloads"] == m["workloads"]
        # every cell of the time but the first, whose fixture record
        # predates them (tests/perfbench/test_perfbench_trace.py::
        # test_recorded_trace_reduces); later cells are not listed
        assert m["workloads"] == cells[1:4]
        assert m["reader"] in ("counter_at_start", "first_span_s")
    # listed together, in this order (later PRs append metrics of their own)
    names = [m["name"] for m in b["per_layer"]]
    at = names.index(SETUP_METRICS[0])
    assert names[at:at + 6] == list(SETUP_METRICS)
