"""Perf-regression sentinel (telemetry/diff.py): rule matching, verdict
semantics and CLI exit codes.

The diff module is stdlib-only and jax-free, so everything here runs
in-process with synthetic snapshots — no training required.
"""
import json

import pytest

from lightgbm_tpu.telemetry.diff import (diff_snapshots, flatten,
                                         load_snapshot, main as diff_main,
                                         match_rule)

pytestmark = pytest.mark.quick


def _snap(**overrides):
    """A small but rule-covering snapshot, mutated per test."""
    base = {
        "backend": "cpu",
        "ts": "2026-08-05T00:00:00Z",
        "metrics": {
            "counters": {"train.rounds": 12, "jit.recompiles": 20,
                         "event.fallback.wave_downgrade": 0},
            "gauges": {"mem.train.peak_bytes": 1_000_000,
                       "jit.cache_entries": 3},
            "timings": {"span.train.chunk":
                        {"count": 12, "total_s": 3.0, "mean_s": 0.25,
                         "min_s": 0.2, "max_s": 0.4}},
        },
        "flight": {"depth_max": 7, "leaves_p50": 15.0,
                   "gain_p50_med": 11.5,
                   "throughput": {"rounds_per_sec": 4.0}},
    }
    out = json.loads(json.dumps(base))
    for path, value in overrides.items():
        node = out
        keys = path.split("/")
        for k in keys[:-1]:
            node = node[k]
        node[keys[-1]] = value
    return out


class TestFlatten:
    def test_dotted_paths_numbers_only(self):
        flat = flatten({"a": {"b": 2, "s": "str", "ok": True},
                        "c": 1.5})
        assert flat == {"a.b": 2.0, "c": 1.5}

    def test_rule_matching(self):
        assert match_rule("metrics.counters.jit.recompiles") == \
            ("up_is_bad", "counter")
        assert match_rule("flight.throughput.rounds_per_sec") == \
            ("down_is_bad", "timing")
        assert match_rule("metrics.timings.span.eval.total_s") == \
            ("up_is_bad", "timing")
        assert match_rule("backend") == ("ignore", "counter")
        assert match_rule("flight.depth_max") == ("any_is_bad", "counter")


class TestVerdicts:
    def test_self_diff_ok(self):
        v = diff_snapshots(_snap(), _snap())
        assert v["verdict"] == "ok"
        assert not v["violations"] and not v["warnings"]
        assert v["checked"] > 0

    def test_timing_regression_beyond_tolerance(self):
        cur = _snap(**{"metrics/timings/span.train.chunk/total_s": 12.0})
        v = diff_snapshots(_snap(), cur, timing_rel_tol=1.5)
        assert v["verdict"] == "regression"
        assert any(e["metric"].endswith("total_s") for e in v["violations"])

    def test_timing_within_tolerance_passes(self):
        cur = _snap(**{"metrics/timings/span.train.chunk/total_s": 6.0})
        v = diff_snapshots(_snap(), cur, timing_rel_tol=1.5)
        assert v["verdict"] == "ok"

    def test_warn_timings_downgrades(self):
        cur = _snap(**{"metrics/timings/span.train.chunk/total_s": 12.0})
        v = diff_snapshots(_snap(), cur, warn_timings=True)
        assert v["verdict"] == "ok"
        assert v["warnings"]

    def test_counter_direction_violation_survives_warn_timings(self):
        cur = _snap(**{"metrics/counters/jit.recompiles": 40})
        v = diff_snapshots(_snap(), cur, warn_timings=True)
        assert v["verdict"] == "regression"
        assert v["violations"][0]["metric"].endswith("jit.recompiles")

    def test_memory_watermark_growth_fails(self):
        cur = _snap(**{"metrics/gauges/mem.train.peak_bytes": 2_000_000})
        v = diff_snapshots(_snap(), cur)
        assert v["verdict"] == "regression"

    def test_improvement_is_not_a_violation(self):
        cur = _snap(**{"metrics/counters/jit.recompiles": 5})
        v = diff_snapshots(_snap(), cur)
        assert v["verdict"] == "ok"
        assert any(e["metric"].endswith("recompiles")
                   for e in v["improved"])

    def test_throughput_drop_fails(self):
        cur = _snap(**{"flight/throughput/rounds_per_sec": 1.0})
        v = diff_snapshots(_snap(), cur, timing_rel_tol=0.5)
        assert v["verdict"] == "regression"

    def test_shape_drift_flags_both_directions(self):
        up = diff_snapshots(_snap(), _snap(**{"flight/depth_max": 20}))
        down = diff_snapshots(_snap(), _snap(**{"flight/depth_max": 2}))
        assert up["verdict"] == "regression"
        assert down["verdict"] == "regression"

    def test_new_and_missing_metrics_never_fail(self):
        cur = _snap()
        cur["flight"]["brand_new_stat"] = 42
        del cur["flight"]["gain_p50_med"]
        v = diff_snapshots(_snap(), cur)
        assert v["verdict"] == "ok"
        assert "flight.brand_new_stat" in v["new"]
        assert "flight.gain_p50_med" in v["missing"]

    def test_fallback_event_appearing_fails(self):
        cur = _snap(
            **{"metrics/counters/event.fallback.wave_downgrade": 1})
        v = diff_snapshots(_snap(), cur)
        assert v["verdict"] == "regression"


class TestCli:
    def _write(self, tmp_path, name, obj):
        p = tmp_path / name
        p.write_text(json.dumps(obj))
        return str(p)

    def test_self_diff_exit_zero(self, tmp_path, capsys):
        a = self._write(tmp_path, "a.json", _snap())
        assert diff_main([a, a]) == 0
        assert "OK" in capsys.readouterr().out

    def test_regression_exit_one_and_json(self, tmp_path, capsys):
        a = self._write(tmp_path, "a.json", _snap())
        b = self._write(tmp_path, "b.json",
                        _snap(**{"metrics/counters/jit.recompiles": 100}))
        assert diff_main([a, b, "--json"]) == 1
        verdict = json.loads(capsys.readouterr().out)
        assert verdict["verdict"] == "regression"

    def test_load_error_exit_two(self, tmp_path):
        a = self._write(tmp_path, "a.json", _snap())
        assert diff_main([a, str(tmp_path / "missing.json")]) == 2

    def test_embedded_sentinel_tolerances_honored(self, tmp_path):
        base = _snap(**{"metrics/timings/span.train.chunk/total_s": 1.0})
        base["sentinel"] = {"rel_tol": 0.25, "timing_rel_tol": 50.0}
        a = self._write(tmp_path, "a.json", base)
        b = self._write(tmp_path, "b.json",
                        _snap(**{"metrics/timings/span.train.chunk/"
                                 "total_s": 10.0}))
        # 10x slower, but the baseline's contract allows 50x
        assert diff_main([a, b]) == 0
        # explicit CLI flag beats the embedded contract
        assert diff_main([a, b, "--timing-rel-tol", "1.5"]) == 1

    def test_bench_jsonl_last_line_wins(self, tmp_path):
        p = tmp_path / "bench.txt"
        p.write_text("[bench] log noise\n"
                     + json.dumps({"value": 4.0, "auc": 0.9}) + "\n"
                     + json.dumps({"value": 5.0, "auc": 0.9}) + "\n")
        snap = load_snapshot(str(p))
        assert snap["value"] == 5.0

    def test_auc_drop_fails_between_bench_lines(self, tmp_path):
        a = self._write(tmp_path, "a.json", {"value": 5.0, "auc": 0.90})
        b = self._write(tmp_path, "b.json", {"value": 5.0, "auc": 0.40})
        assert diff_main([a, b]) == 1
