"""Cross-process telemetry spool (ISSUE 16 tentpole acceptance).

Fast tier: synthetic multi-rank aggregation (clock alignment, skew /
straggler naming, Chrome-trace export, CLI), torn-file counted skips,
unknown-ev forward compat, attach idempotence, spool-on/off model byte
identity, and the streaming-pass profiler's stall-attribution invariant.

Slow tier: a REAL 2-process gloo cluster (tests/test_multihost.py
harness) where each rank spools its own stream — the merged timeline
must carry `mesh.collective.*` events from BOTH ranks with finite clock
offsets.
"""
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry import spool
from lightgbm_tpu.telemetry.report import render, summarize
from test_multihost import REPO, _spawn_cluster

pytestmark = pytest.mark.quick


@pytest.fixture(autouse=True)
def _clean_spool_state():
    """The spool attaches to the PROCESS-GLOBAL tracer — never leak a
    sink (or the attach-once registry) into later tests."""
    yield
    telemetry.TRACER.clear_sinks()
    spool._ATTACHED.clear()
    spool.SPOOL_DIRS.clear()


def _write_rank(d, rank, events, devices=None):
    s = spool.SpoolSink(str(d), role="gloo-rank", rank=rank,
                        process_index=rank, devices=devices)
    for ev in events:
        s.emit(ev)
    s.close()
    return s


def _mk_two_rank_spool(d):
    """Two synthetic ranks: 2 devices each, device 3 consistently 50 ms
    late into every ring_fold round."""
    for rank in (0, 1):
        evs = []
        for rnd in range(3):
            for dev in (rank * 2, rank * 2 + 1):
                evs.append({"ev": "event",
                            "name": "mesh.collective.ring_fold",
                            "ts": 100.0 + rnd + dev * 0.002
                            + (0.05 if dev == 3 else 0.0),
                            "device": dev, "payload_bytes": 4096,
                            "round": rnd})
        evs.append({"ev": "metrics", "name": "registry", "ts": 110.0,
                    "snapshot": {"counters": {"train.rounds": 3},
                                 "gauges": {"peak_mb": 10.0 + rank}}})
        _write_rank(d, rank, evs, devices=[rank * 2, rank * 2 + 1])


class TestAggregate:
    def test_two_rank_merge_and_straggler(self, tmp_path):
        _mk_two_rank_spool(tmp_path)
        agg = spool.aggregate(str(tmp_path))
        assert len(agg["processes"]) == 2
        assert {p["rank"] for p in agg["processes"]} == {0, 1}
        # every process row carries a finite clock anchor offset
        for p in agg["processes"]:
            assert isinstance(p["clock_offset_s"], float)
        # merged stream is ts-ordered and proc-annotated
        ts = [e["ts"] for e in agg["events"]]
        assert ts == sorted(ts)
        assert {e["_proc"] for e in agg["events"]
                if e["name"].startswith("mesh.collective.")} \
            == {p_key for p_key in
                (f"{p['host']}-{p['pid']}-rank{p['rank']}"
                 for p in agg["processes"])}
        # device 3 is the planted straggler
        c = agg["collectives"]["ring_fold"]
        assert c["straggler"] == 3
        assert c["payload_bytes"] == 4096
        assert c["devices"]["3"]["lag_mean_s"] > \
            c["devices"]["1"]["lag_mean_s"]
        assert agg["straggler"] == 3
        # metrics roll-up: counters sum, gauges keep the watermark
        assert agg["metrics"]["counters"]["train.rounds"] == 6
        assert agg["metrics"]["gauges"]["peak_mb"] == 11.0

    def test_chrome_trace_valid_and_relative(self, tmp_path):
        _mk_two_rank_spool(tmp_path)
        agg = spool.aggregate(str(tmp_path))
        tr = json.loads(json.dumps(spool.chrome_trace(agg)))
        assert tr["traceEvents"]
        # one process_name metadata record per spool process
        metas = [e for e in tr["traceEvents"] if e["ph"] == "M"]
        assert len(metas) == 2
        # instants are relative-µs (never absolute epoch seconds)
        insts = [e for e in tr["traceEvents"] if e["ph"] == "i"]
        assert insts and min(e["ts"] for e in insts) == 0.0

    def test_cli_exits_zero_and_writes_trace(self, tmp_path, capsys):
        _mk_two_rank_spool(tmp_path)
        out = str(tmp_path / "trace.json")
        assert spool.main([str(tmp_path), "--trace", out]) == 0
        rendered = capsys.readouterr().out
        assert "straggler: device 3" in rendered
        assert "mesh.skew.device: 3" in rendered
        with open(out) as f:
            assert json.load(f)["traceEvents"]

    def test_cli_rejects_missing_dir(self, tmp_path):
        assert spool.main([str(tmp_path / "nope")]) == 2

    def test_empty_dir_renders_no_run(self, tmp_path):
        agg = spool.aggregate(str(tmp_path))
        assert agg["n_events"] == 0
        assert "status: no-run" in spool.render_timeline(agg)

    def test_torn_lines_counted_not_fatal(self, tmp_path):
        _write_rank(tmp_path, 0,
                    [{"ev": "event", "name": "x", "ts": 1.0}])
        fn = next(f for f in os.listdir(tmp_path)
                  if f.startswith("proc-"))
        with open(tmp_path / fn, "a") as f:
            f.write('{"ev": "event", "name": "torn-mid-wri\n')
            f.write("not json at all\n")
        agg = spool.aggregate(str(tmp_path))
        assert agg["torn_lines"] == 2
        assert agg["processes"][0]["torn_lines"] == 2
        assert agg["n_events"] == 1     # the intact event survives
        assert "torn line(s)" in spool.render_timeline(agg)

    def test_unknown_ev_kinds_counted_skip(self, tmp_path):
        _write_rank(tmp_path, 0,
                    [{"ev": "event", "name": "x", "ts": 1.0},
                     {"ev": "hologram", "name": "y", "ts": 2.0},
                     {"ev": "hologram", "name": "z", "ts": 3.0}])
        agg = spool.aggregate(str(tmp_path))
        assert agg["unknown_ev"] == {"hologram": 2}
        assert agg["n_events"] == 1
        assert "unknown event kinds" in spool.render_timeline(agg)

    def test_headerless_file_identity_from_filename(self, tmp_path):
        with open(tmp_path / "proc-h-1-7.jsonl", "w") as f:
            f.write(json.dumps({"ev": "event", "name": "x",
                                "ts": 1.0}) + "\n")
        agg = spool.aggregate(str(tmp_path))
        assert agg["processes"][0]["header_missing"]
        assert agg["events"][0]["_proc"] == "h-1-7"


class TestAttach:
    def test_attach_idempotent_one_header(self, tmp_path):
        s1 = spool.attach_spool(str(tmp_path), role="trainer")
        s2 = spool.attach_spool(str(tmp_path), role="trainer")
        assert s1 is s2
        telemetry.TRACER.flush()
        files = [f for f in os.listdir(tmp_path)
                 if f.startswith("proc-")]
        assert len(files) == 1
        with open(tmp_path / files[0]) as f:
            headers = [l for l in f if '"header"' in l]
        assert len(headers) == 1
        assert str(tmp_path) in spool.SPOOL_DIRS

    def test_events_reach_spool(self, tmp_path):
        spool.attach_spool(str(tmp_path), role="trainer")
        telemetry.event("mesh.collective.test", device=0,
                        payload_bytes=8, round=0)
        telemetry.TRACER.flush()
        agg = spool.aggregate(str(tmp_path))
        assert agg["collectives"]["test"]["devices"]["0"]["rounds"] == 1


def _make_binary(n=400, f=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def _strip_spool_params(model: str) -> str:
    return "\n".join(l for l in model.splitlines()
                     if not l.startswith("[telemetry_spool"))


class TestByteIdentity:
    def test_spool_on_off_model_identity(self, tmp_path):
        X, y = _make_binary()
        P = {"objective": "binary", "num_iterations": 3,
             "num_leaves": 7, "verbosity": -1}
        on = lgb.train({**P, "telemetry_spool_dir": str(tmp_path)},
                       lgb.Dataset(X, label=y))
        m_on = on.model_to_string()
        p_on = on.predict(X[:64])
        telemetry.TRACER.clear_sinks()
        spool._ATTACHED.clear()
        spool.SPOOL_DIRS.clear()
        off = lgb.train(P, lgb.Dataset(X, label=y))
        # the ONLY difference is the embedded spool param line — every
        # tree byte and every prediction bit is identical
        assert _strip_spool_params(m_on) \
            == _strip_spool_params(off.model_to_string())
        np.testing.assert_array_equal(p_on, off.predict(X[:64]))
        # and the spool actually recorded the run
        agg = spool.aggregate(str(tmp_path))
        assert agg["n_events"] > 0
        assert agg["processes"][0]["role"] == "trainer"


class TestStreamingProfiler:
    def test_pass_attribution_sums_under_wall(self, tmp_path):
        X, y = _make_binary(n=300)
        P = {"objective": "binary", "num_iterations": 2, "num_leaves": 4,
             "verbosity": -1, "external_memory": True,
             "streaming_train": "on", "datastore_shard_rows": 64,
             "telemetry_spool_dir": str(tmp_path)}
        lgb.train(P, lgb.Dataset(X, label=y))
        telemetry.TRACER.flush()
        agg = spool.aggregate(str(tmp_path))
        st = agg["stream"]
        assert st["passes"] > 0
        # disjoint sub-intervals: attribution never exceeds pass wall
        assert st["attributed_s"] <= st["wall_s"] * 1.05
        # every profiled pass span carries the four stages + identity
        spans = [e for e in agg["events"]
                 if e.get("ev") == "span" and e["name"] == "stream.pass"]
        assert spans
        for sp in spans:
            attrs = sp["attrs"]
            for k in ("prefetch_wait_s", "h2d_s", "device_fold_s",
                      "host_harvest_s", "wall_s", "tree", "wave",
                      "shards"):
                assert k in attrs, f"missing {k} in {attrs}"
            stage_sum = sum(attrs[k] for k in
                            ("prefetch_wait_s", "h2d_s",
                             "device_fold_s", "host_harvest_s"))
            assert stage_sum <= attrs["wall_s"] * 1.05
        # histograms landed in the registry for the snapshot/diff plane
        snap = telemetry.REGISTRY.snapshot()
        for k in ("stream.pass.prefetch_wait", "stream.pass.h2d",
                  "stream.pass.device_fold", "stream.pass.host_harvest",
                  "stream.pass.wall"):
            assert snap["histograms"][k]["count"] > 0
        # the timeline CLI renders the attribution table
        assert "streaming passes:" in spool.render_timeline(agg)


class TestReportNoRun:
    def test_summarize_counts_unknown_kinds(self):
        s = summarize([{"ev": "event", "name": "x", "ts": 1.0},
                       {"ev": "gizmo", "name": "y", "ts": 2.0}])
        assert s["unknown"] == {"gizmo": 1}
        assert "skipped" in render(s) and "gizmo" in render(s)

    def test_render_empty_is_no_run(self):
        assert "status: no-run" in render(summarize([]))

    def test_report_cli_empty_artifact(self, tmp_path, capsys):
        from lightgbm_tpu.telemetry.report import main
        p = tmp_path / "BENCH_r01.json"
        p.write_text("")
        assert main([str(p)]) == 0
        assert "status: no-run" in capsys.readouterr().out

    def test_report_cli_multichip_skip_record(self, tmp_path, capsys):
        from lightgbm_tpu.telemetry.report import main
        p = tmp_path / "MULTICHIP_r01.json"
        p.write_text(json.dumps({"n_devices": 0, "rc": 124, "ok": False,
                                 "skipped": "no tpu",
                                 "tail": "probe timed out"}) + "\n")
        assert main([str(p)]) == 0
        out = capsys.readouterr().out
        assert "status: no-run" in out
        assert "no tpu" in out


# slow tier: two fresh gloo-joined JAX processes cost ~50 s on a shared
# box (same budget note as test_multihost.py)
@pytest.mark.slow
def test_two_process_spool_timeline(tmp_path):
    spool_dir = tmp_path / "spool"
    spool_dir.mkdir()
    rcs, outs = _spawn_cluster(
        tmp_path, port=12967,
        extra_env={"LGBM_TPU_SPOOL_DIR": str(spool_dir)})
    assert rcs == [0, 0], "\n---\n".join(outs)[-3000:]

    agg = spool.aggregate(str(spool_dir))
    assert len(agg["processes"]) == 2
    assert {p["rank"] for p in agg["processes"]} == {0, 1}
    # aligned clocks: every header carried a finite mono/wall anchor
    for p in agg["processes"]:
        assert isinstance(p["clock_offset_s"], float)
    # the merged timeline holds mesh.collective.* stamps from BOTH ranks
    colls = [e for e in agg["events"]
             if e.get("ev") == "event"
             and e["name"].startswith("mesh.collective.")]
    assert {e["_proc"] for e in colls} == {
        f"{p['host']}-{p['pid']}-rank{p['rank']}"
        for p in agg["processes"]}
    # each rank stamped its 4 LOCAL devices; together they tile the
    # 8-device mesh (global CPU device ids are process-prefixed, so
    # count them instead of assuming 0..7)
    assert len({e["device"] for e in colls}) == 8
    per_rank = {}
    for e in colls:
        per_rank.setdefault(e["_proc"], set()).add(e["device"])
    assert all(len(devs) == 4 for devs in per_rank.values())
    # both ranks rolled their registries into the fleet metrics
    assert sum(p["metrics_snapshots"] for p in agg["processes"]) == 2
    # and the rendered timeline names a straggler for the collective
    assert "mesh collectives" in spool.render_timeline(agg)
