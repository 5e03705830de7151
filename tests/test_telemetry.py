"""Telemetry smoke + unit coverage (ISSUE 1 tentpole acceptance).

The smoke trains 2 rounds on 512 synthetic rows with a JSONL sink
attached (conftest forces JAX_PLATFORMS=cpu) and asserts the span tree —
{dataset.bin, compile_warmup, train.chunk, eval, predict.*} with
non-negative nested durations — plus the JSONL round-trip, the
telemetry-report renderer/CLI, and the Prometheus dump.  Unit tests pin
the no-op fast path and the MetricsRegistry/sink semantics that the
jax-free bench/probe processes rely on.
"""
import json
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry import (MemorySink, MetricsRegistry, NOOP,
                                    read_jsonl, write_prometheus)
from lightgbm_tpu.telemetry.report import render, summarize

pytestmark = pytest.mark.quick


def make_binary(n=512, f=8, seed=11):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (1.2 * X[:, 0] - X[:, 1] + 0.4 * rng.randn(n) > 0).astype(np.float64)
    return X, y


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One 2-round training run with a JSONL sink; yields (events, path).

    Module-scoped: every assertion class reads the same artifact, the way
    telemetry-report consumes a real run's file.
    """
    path = str(tmp_path_factory.mktemp("telemetry") / "events.jsonl")
    X, y = make_binary(512)
    ds = lgb.Dataset(X[:384], label=y[:384])
    dv = ds.create_valid(X[384:], label=y[384:])
    try:
        bst = lgb.train({"objective": "binary", "verbosity": -1,
                         "telemetry_sink": path},
                        ds, 2, valid_sets=[dv])
        bst.predict(X)
        telemetry.TRACER.flush()
    finally:
        # the global tracer must not leak an appender into later tests
        telemetry.TRACER.clear_sinks()
    return read_jsonl(path), path


class TestSpanTree:
    def test_jsonl_round_trip(self, traced_run):
        events, path = traced_run
        assert events, "sink wrote no events"
        # every line the sink wrote is valid standalone JSON
        with open(path) as f:
            for line in f:
                assert json.loads(line)["ev"] in ("span", "event", "metrics")

    def test_required_phases_present(self, traced_run):
        events, _ = traced_run
        names = {e["name"] for e in events if e["ev"] == "span"}
        required = {"dataset.bin", "compile_warmup", "train.chunk", "eval",
                    "train.loop"}
        assert required <= names, f"missing spans: {required - names}"
        assert names & {"predict.host", "predict.device"}, \
            "no predict span recorded"

    def test_durations_non_negative(self, traced_run):
        events, _ = traced_run
        for e in events:
            if e["ev"] == "span":
                assert e["dur_s"] >= 0.0, e
                assert e["depth"] >= 0, e

    def test_parent_links(self, traced_run):
        events, _ = traced_run
        spans = [e for e in events if e["ev"] == "span"]
        names = {e["name"] for e in spans}
        by_name = {}
        for e in spans:
            by_name.setdefault(e["name"], []).append(e)
        # children reference parents that exist in the same file
        for e in spans:
            if "parent" in e:
                assert e["parent"] in names, e
                assert e["depth"] >= 1, e
        # the documented nesting of a 2-round per-iteration run
        assert by_name["setup.booster"][0]["parent"] == "train.loop"
        assert by_name["dataset.bin"][0]["parent"] == "setup.booster"
        assert by_name["train.chunk"][0]["parent"] == "train.loop"
        assert by_name["compile_warmup"][0]["parent"] == "train.chunk"
        assert by_name["train.loop"][0]["depth"] == 0
        # a nested span fits inside its parent's wall-clock interval
        chunk = by_name["train.chunk"][0]
        warm = by_name["compile_warmup"][0]
        assert chunk["ts"] <= warm["ts"]
        assert warm["dur_s"] <= chunk["dur_s"] + 1e-6

    def test_span_attrs(self, traced_run):
        events, _ = traced_run
        binned = [e for e in events
                  if e["ev"] == "span" and e["name"] == "dataset.bin"]
        assert binned[0]["attrs"]["rows"] == 384
        chunks = [e for e in events
                  if e["ev"] == "span" and e["name"] == "train.chunk"]
        assert sum(c["attrs"]["rounds"] for c in chunks) == 2

    def test_metrics_snapshot_embedded(self, traced_run):
        events, _ = traced_run
        snaps = [e for e in events if e["ev"] == "metrics"]
        assert snaps, "train() did not emit a final metrics snapshot"
        counters = snaps[-1]["snapshot"]["counters"]
        assert counters.get("train.rounds", 0) >= 2
        timings = snaps[-1]["snapshot"]["timings"]
        assert timings["span.train.chunk"]["count"] >= 2


class TestReport:
    def test_summarize(self, traced_run):
        events, _ = traced_run
        s = summarize(events)
        assert s["n_events"] == len(events)
        assert s["root_total_s"] > 0
        chunk = s["phases"]["train.chunk"]
        assert chunk["count"] >= 2
        assert chunk["min_s"] <= chunk["mean_s"] <= chunk["max_s"]
        assert "train.loop" in chunk["parents"]
        assert s["metrics"]["counters"]["train.rounds"] >= 2

    def test_render_nests_children(self, traced_run):
        events, _ = traced_run
        out = render(summarize(events))
        lines = out.splitlines()
        chunk = next(l for l in lines if l.lstrip().startswith("train.chunk"))
        warm = next(l for l in lines
                    if l.lstrip().startswith("compile_warmup"))
        indent = lambda l: len(l) - len(l.lstrip())  # noqa: E731
        assert indent(warm) > indent(chunk)

    def test_cli_subcommand(self, traced_run, capsys):
        events, path = traced_run
        from lightgbm_tpu.cli import run
        assert run(["telemetry-report", path]) == 0
        out = capsys.readouterr().out
        assert "train.chunk" in out and "dataset.bin" in out

    def test_cli_missing_file(self, tmp_path):
        from lightgbm_tpu.cli import run
        assert run(["telemetry-report", str(tmp_path / "nope.jsonl")]) == 2

    def test_read_jsonl_skips_garbage(self, tmp_path):
        p = tmp_path / "mixed.jsonl"
        p.write_text('{"ev": "span", "name": "a", "dur_s": 1}\n'
                     'not json\n\n{"ev": "event", "name": "b"}\n')
        events = read_jsonl(str(p))
        assert [e["name"] for e in events] == ["a", "b"]
        assert summarize(events)["events"] == {"b": 1}


class TestNoopFastPath:
    def test_span_is_shared_noop_when_inactive(self):
        t = telemetry.Tracer()
        assert t.span("x") is NOOP
        assert t.span("y", rows=1) is NOOP
        with t.span("z") as sp:
            assert sp is NOOP
            sp.set(rows=2)  # no-op, must not raise

    def test_global_tracer_inactive_by_default(self):
        assert not telemetry.TRACER.active
        assert telemetry.TRACER.span("anything") is NOOP

    def test_forced_enable_records_without_sink(self):
        t = telemetry.Tracer()
        t.enable(True)
        assert t.active
        before = telemetry.REGISTRY.timing("span.forced_phase").count
        with t.span("forced_phase"):
            pass
        assert telemetry.REGISTRY.timing("span.forced_phase").count \
            == before + 1
        t.enable(False)
        assert t.span("forced_phase") is NOOP


class TestTracer:
    def test_memory_sink_and_nesting(self):
        t = telemetry.Tracer()
        mem = t.add_sink(MemorySink())
        try:
            with t.span("outer"):
                with t.span("inner", k=1):
                    pass
        finally:
            t.clear_sinks()
        inner, outer = mem.events  # inner exits (and emits) first
        assert inner["name"] == "inner" and inner["parent"] == "outer"
        assert inner["depth"] == 1 and outer["depth"] == 0
        assert inner["attrs"] == {"k": 1}

    def test_attach_jsonl_idempotent(self, tmp_path):
        t = telemetry.Tracer()
        p = str(tmp_path / "t.jsonl")
        try:
            s1 = t.attach_jsonl(p)
            s2 = t.attach_jsonl(p)
            assert s1 is s2
            with t.span("once"):
                pass
        finally:
            t.clear_sinks()
        assert len(read_jsonl(p)) == 1

    def test_dead_sink_never_raises(self):
        class DeadSink(telemetry.Sink):
            def emit(self, event):
                raise OSError("disk full")

        t = telemetry.Tracer()
        mem = MemorySink()
        t.add_sink(DeadSink())
        t.add_sink(mem)
        try:
            with t.span("survives"):
                pass
        finally:
            t.clear_sinks()
        assert mem.events[0]["name"] == "survives"

    def test_error_span_tagged(self):
        t = telemetry.Tracer()
        mem = t.add_sink(MemorySink())
        try:
            with pytest.raises(ValueError):
                with t.span("boom"):
                    raise ValueError("x")
        finally:
            t.clear_sinks()
        assert mem.events[0]["error"] == "ValueError"

    def test_event_counts_without_sink(self):
        t = telemetry.Tracer()
        before = telemetry.REGISTRY.counter("event.test.ping").value
        t.event("test.ping", detail=1)
        assert telemetry.REGISTRY.counter("event.test.ping").value \
            == before + 1


class TestMetricsRegistry:
    def test_counter_gauge_timing(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        reg.counter("c").inc(4)
        reg.gauge("g").set(2.5)
        reg.timing("t").observe(0.1)
        reg.timing("t").observe(0.3)
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 5
        assert snap["gauges"]["g"] == 2.5
        t = snap["timings"]["t"]
        assert t["count"] == 2
        assert t["min_s"] == pytest.approx(0.1)
        assert t["max_s"] == pytest.approx(0.3)
        assert t["mean_s"] == pytest.approx(0.2)

    def test_thread_safety(self):
        reg = MetricsRegistry()

        def work():
            for _ in range(1000):
                reg.counter("hits").inc()

        threads = [threading.Thread(target=work) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert reg.counter("hits").value == 8000

    def test_prometheus_dump(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("train.rounds").inc(32)
        reg.gauge("queue.depth").set(3)
        reg.timing("span.eval").observe(0.25)
        text = reg.to_prometheus()
        assert "# TYPE lgbm_tpu_train_rounds counter" in text
        assert "lgbm_tpu_train_rounds 32" in text
        assert "lgbm_tpu_queue_depth 3" in text
        assert "lgbm_tpu_span_eval_seconds_count 1" in text
        p = tmp_path / "metrics.prom"
        write_prometheus(str(p), registry=reg)
        assert p.read_text() == text

    def test_prometheus_name_collision_disambiguated(self):
        """Normalization maps `train.rounds` and `train_rounds` to the
        same Prometheus name; colliding series must get a `_dupN` suffix
        instead of silently sharing one name (regression: the second
        series used to shadow the first in scrapes)."""
        reg = MetricsRegistry()
        reg.counter("train.rounds").inc(1)
        reg.counter("train_rounds").inc(2)
        reg.gauge("train:rounds").set(3)   # collides across metric kinds
        text = reg.to_prometheus()
        assert text.count("# TYPE lgbm_tpu_train_rounds counter") == 1
        assert "lgbm_tpu_train_rounds 1" in text
        assert "# TYPE lgbm_tpu_train_rounds_dup2 counter" in text
        assert "lgbm_tpu_train_rounds_dup2 2" in text
        assert "# TYPE lgbm_tpu_train_rounds_dup3 gauge" in text
        assert "lgbm_tpu_train_rounds_dup3 3" in text
        # every exposed series name is unique
        names = [ln.split()[0] for ln in text.splitlines()
                 if ln and not ln.startswith("#")]
        assert len(names) == len(set(names))

    def test_prometheus_timing_collision_disambiguated(self):
        reg = MetricsRegistry()
        reg.timing("span.eval").observe(0.1)
        reg.timing("span:eval").observe(0.2)
        text = reg.to_prometheus()
        assert "lgbm_tpu_span_eval_seconds_count 1" in text
        assert "lgbm_tpu_span_eval_seconds_dup2_count 1" in text

    def test_jax_free_import(self):
        """jax-free processes load these modules by file path — prove
        the modules don't import jax."""
        import subprocess
        import sys
        code = (
            "import importlib.util, sys, types\n"
            # recorder.py does relative sibling imports; a synthetic
            # parent package rooted at the telemetry dir resolves them
            # without executing lightgbm_tpu/__init__.py (which pulls jax)
            "pkg = types.ModuleType('tel')\n"
            "pkg.__path__ = ['lightgbm_tpu/telemetry']\n"
            "sys.modules['tel'] = pkg\n"
            "for mod in ('metrics', 'sinks', 'spans', 'request_trace', "
            "'report', 'recorder', 'diff'):\n"
            "    spec = importlib.util.spec_from_file_location(\n"
            "        'tel.' + mod, 'lightgbm_tpu/telemetry/' + mod + '.py')\n"
            "    m = importlib.util.module_from_spec(spec)\n"
            "    sys.modules['tel.' + mod] = m\n"
            "    spec.loader.exec_module(m)\n"
            # the datastore package is jax-free too (assemble.py defers
            # its jax import into the function body) — store.py's
            # `from . import format` needs format loaded first
            "dpkg = types.ModuleType('dstore')\n"
            "dpkg.__path__ = ['lightgbm_tpu/datastore']\n"
            "sys.modules['dstore'] = dpkg\n"
            "for mod in ('format', 'store', 'prefetch', 'assemble'):\n"
            "    spec = importlib.util.spec_from_file_location(\n"
            "        'dstore.' + mod, 'lightgbm_tpu/datastore/' + mod "
            "+ '.py')\n"
            "    m = importlib.util.module_from_spec(spec)\n"
            "    sys.modules['dstore.' + mod] = m\n"
            "    spec.loader.exec_module(m)\n"
            "    setattr(dpkg, mod, m)\n"
            "assert 'jax' not in sys.modules, 'jax leaked'\n"
            "rec = sys.modules['tel.recorder']\n"
            "assert rec.sample_memory('t') in (None,)  # no-jax fallback\n"
            "print('CLEAN')\n")
        r = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                           capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        assert "CLEAN" in r.stdout


# --------------------------------------------------------------------------
# ISSUE 25: one span tree per round, the no-op path, the needed-rows counter
# --------------------------------------------------------------------------
ROUND_CHILDREN = {"train.gradients", "train.sample", "compile_warmup",
                  "train.grow", "train.wait", "train.decode", "train.score",
                  "train.bookkeeping"}


@pytest.fixture(scope="module")
def recorded_rounds():
    """Three `Booster.update()` rounds of a 7-leaf wave booster into a
    MemorySink: (span events, booster, the needed-rows counter's growth)."""
    X, y = make_binary(600)
    sink = telemetry.TRACER.add_sink(MemorySink())
    before = telemetry.REGISTRY.counter("grow.hist_rows_needed").value
    try:
        bst = lgb.Booster(params={"objective": "binary", "verbosity": -1,
                                  "num_leaves": 7, "min_data_in_leaf": 5,
                                  "tree_grow_policy": "wave"},
                          train_set=lgb.Dataset(X, label=y))
        for _ in range(3):
            bst.update()
    finally:
        telemetry.TRACER.remove_sink(sink)
    grown = telemetry.REGISTRY.counter("grow.hist_rows_needed").value - before
    return [e for e in sink.events if e["ev"] == "span"], bst, grown


class TestRoundSpanTree:
    def test_every_round_is_one_tree_under_its_chunk(self, recorded_rounds):
        spans, _, _ = recorded_rounds
        chunks = [s for s in spans if s["name"] == "train.chunk"]
        assert [c["round"] for c in chunks] == [0, 1, 2]
        assert len({s["id"] for s in spans}) == len(spans)
        by_id = {s["id"]: s for s in spans}
        for c in chunks:
            below = [s for s in spans if s.get("parent_id") == c["id"]]
            names = {s["name"] for s in below}
            assert names <= ROUND_CHILDREN
            assert names >= ROUND_CHILDREN - {"compile_warmup",
                                              "train.grow"}
            # the spans of one round share its identifier ...
            assert {s["round"] for s in below} == {c["round"]}
            # ... lie inside it, and do not overlap one another
            below.sort(key=lambda s: s["start_ns"])
            assert c["start_ns"] <= below[0]["start_ns"]
            assert below[-1]["end_ns"] <= c["end_ns"]
            for a, b in zip(below, below[1:]):
                assert a["end_ns"] <= b["start_ns"], (a["name"], b["name"])
        # train.grow hangs from the chunk, or from compile_warmup in the
        # round that compiles; either way its parent is in the same round
        for g in (s for s in spans if s["name"] == "train.grow"):
            up = by_id[g["parent_id"]]
            assert up["name"] in ("train.chunk", "compile_warmup")
            assert up["round"] == g["round"]
        assert [s["name"] for s in spans
                if s["name"] == "compile_warmup"] == ["compile_warmup"]

    def test_start_and_end_are_one_clock(self, recorded_rounds):
        spans, _, _ = recorded_rounds
        for s in spans:
            assert s["end_ns"] >= s["start_ns"]
            assert s["dur_s"] == pytest.approx(
                (s["end_ns"] - s["start_ns"]) / 1e9, abs=1e-6)

    def test_wait_and_decode_split_the_old_decode_interval(
            self, recorded_rounds):
        """`train.decode` used to run from the end of the dispatch to the
        decoded tree; `train.wait` now takes the head of that interval."""
        spans, _, _ = recorded_rounds
        for rnd in (0, 1, 2):
            mine = {s["name"]: s for s in spans if s.get("round") == rnd}
            grow, wait, dec = (mine[n] for n in ("train.grow", "train.wait",
                                                 "train.decode"))
            assert grow["end_ns"] <= wait["start_ns"] <= wait["end_ns"] \
                <= dec["start_ns"] <= dec["end_ns"]
            # nothing but the two span boundaries lies between them
            assert wait["start_ns"] - grow["end_ns"] < 5e6
            assert dec["start_ns"] - wait["end_ns"] < 5e6

    def test_needed_rows_against_a_hand_count(self, recorded_rounds):
        _, bst, grown = recorded_rounds
        total = 0.0
        for tree in bst.trees:
            assert tree.num_leaves == 7
            count = {}                  # node ref -> rows, by walking down

            def rows(ref):
                if ref < 0:
                    return float(tree.leaf_count[~ref])
                if ref not in count:
                    count[ref] = rows(int(tree.left_child[ref])) \
                        + rows(int(tree.right_child[ref]))
                return count[ref]

            needed = rows(0)            # the root's histogram: every row
            assert needed == 600
            for i in range(6):
                needed += min(rows(int(tree.left_child[i])),
                              rows(int(tree.right_child[i])))
            assert tree.hist_rows_needed() == needed
            total += needed
        assert grown == total
        assert 600 * 3 < total < 600 * 3 * 4    # root + at most N/2 a level

    def test_single_leaf_tree_needs_its_root_only(self):
        from lightgbm_tpu.tree import Tree
        t = Tree(1)
        t.leaf_count = np.array([42.0])
        assert t.hist_rows_needed() == 42.0


class TestNoSpanWithoutASink:
    def test_update_creates_no_span_object(self, monkeypatch):
        """With no sink and no `enable`, a round goes through the shared
        no-op: not one `Span` is built."""
        from lightgbm_tpu.telemetry import spans as spans_mod
        assert not telemetry.TRACER.active
        X, y = make_binary(300)
        bst = lgb.Booster(params={"objective": "binary", "verbosity": -1,
                                  "num_leaves": 4},
                          train_set=lgb.Dataset(X, label=y))
        made = []
        real = spans_mod.Span.__init__

        def counting(self, *a, **kw):
            made.append(a[1] if len(a) > 1 else kw.get("name"))
            real(self, *a, **kw)

        monkeypatch.setattr(spans_mod.Span, "__init__", counting)
        bst.update()
        bst.update()
        assert made == []
        assert bst.current_iteration() == 2


class TestCompileListener:
    def test_cache_misses_are_counted_from_jaxs_event(self):
        import jax
        from lightgbm_tpu.telemetry.recorder import install_compile_listener
        assert install_compile_listener()
        misses = telemetry.REGISTRY.counter("jit.cache_misses")
        compiles = telemetry.REGISTRY.counter("jit.recompiles")
        m0, c0 = misses.value, compiles.value
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        assert (misses.value, compiles.value) == (m0 + 1, c0)
        jax.monitoring.record_event_time_span(
            "/jax/core/compile/backend_compile_duration", 100.0, 100.25,
            fun_name="f")
        assert (misses.value, compiles.value) == (m0 + 1, c0 + 1)
