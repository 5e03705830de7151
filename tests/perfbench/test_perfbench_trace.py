"""The reduction from a trace to metrics: on a hand-made trace whose every
number can be worked out on paper, and on the driver's traced run of the
fixture cell `tiny13-l31.train` (65,536 rows) recorded on a TPU v5e
(`fixtures/driver_round/`, PR 37: the trace and the job's record of the
program, through `perfbench.program_run --save`)."""
import gzip
import json
import os

import pytest

from perfbench import manifest, program_readers as P, program_run, readers
from perfbench import trace as T

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "driver_round")
S = 1e9     # the trace's clock is in ns
# the fixture's (PR 37): at 65,536 rows the split scan outweighs the kernel;
# a tree makes 10.5 passes on average (1 root + 4 waves + its strict tail)
HIST_PCT, PASSES = 13.46, 10.5

# (name, opcode, program, start s, length s) as the device line holds them:
# a `while` 1..6 that encloses the grower's operations, as control flow does
EVENTS = [
    ("while.3", "while", "jit_grow", 1, 5),
    ("pallas_histogram_multi_rows.2", "custom-call", "jit_grow", 1, 2),
    ("pallas_histogram_multi_rows.2", "custom-call", "jit_grow", 4, 1),
    ("fusion.7", "fusion", "jit_grow", 5, 1),
    ("fusion.9", "fusion", "jit__grad", 8, 1),
]


@pytest.fixture()
def handmade():
    """Window 0..10 s by the annotations.  Device 0: the grower's `while`
    1..6 around a histogram kernel 1..3 and 4..5 and a fusion 5..6 (so the
    loop's own time is 3..4), an operation of another program 8..9.
    Busy 1..6 and 8..9 = 6 s; idle 0..1, 6..8, 9..10 = 4 s."""
    selfs = T._self_times([(a * S, (a + d) * S) for _, _, _, a, d in EVENTS])
    ops = [T.Op(n, oc, "", prog, a * S, d * S, self_ns, 0)
           for (n, oc, prog, a, d), self_ns in zip(EVENTS, selfs)]
    spans = [T.Span("update", 0 * S, 6.5 * S),
             T.Span("between_rounds", 6.5 * S, 1.0 * S),
             T.Span("update", 7.5 * S, 2.5 * S)]
    return T.Trace(ops, [], spans, [0])


def test_union_merges_overlaps():
    assert T.union([(3, 5), (1, 2), (4, 7), (7, 8)]) == [(1, 2), (3, 8)]
    assert T.total(T.union([(0, 2), (1, 3)])) == 3


def test_self_times_take_the_nested_events_out():
    # a loop 0..10 around a body 1..4 (which holds a kernel 2..3) and 6..9
    ev = [(0, 10), (1, 4), (2, 3), (6, 9)]
    assert T._self_times(ev) == [4, 2, 1, 3]
    assert sum(T._self_times(ev)) == 10     # nothing counted twice


def test_busy_is_the_union_inside_the_window(handmade):
    assert T.window_of(handmade) == (0.0, 10 * S)
    assert T.busy_seconds(handmade) == pytest.approx(6.0)
    # self times add up to the busy time: the loop keeps only 3..4
    assert T.op_seconds(handmade.ops, 1) == pytest.approx(6.0)
    assert handmade.ops[0].dur == pytest.approx(1.0 * S)


def test_shares_counts_idle_and_roofline(handmade):
    ctx = {"trace": handmade, "counters": {}, "memory": {},
           "units": {"trees": 2},
           "shape": {"rows": 1000, "columns": 4, "max_bin": 16},
           "peaks": {"hbm_bytes_per_s": 1e4, "f32_flops_per_s": 1e12}}
    hist = {"name": "^pallas_histogram", "opcode": "custom-call"}
    assert readers.scope_share(ctx, hist) == pytest.approx(100 * 3 / 6)
    assert readers.scope_share(
        ctx, {"program": "^jit_grow$", "not_name": "^pallas_histogram"}
    ) == pytest.approx(100 * 2 / 6)
    assert readers.scope_share(
        ctx, {"not_program": "^jit_grow$"}) == pytest.approx(100 * 1 / 6)
    assert readers.scope_count_per(
        ctx, dict(hist, per="trees")) == pytest.approx(1.0)
    assert readers.idle_share(ctx, {}) == pytest.approx(40.0)
    # work of one pass: 4000 + 16000 + 1*4*16*12 = 20768 B -> 2.0768 s at
    # 1e4 B/s; two calls took 3 s: 100 * 4.1536 / 3
    r = readers.roofline_share(ctx, dict(
        hist, work="histogram_pass",
        work_args={"rows": "rows", "columns": "columns",
                   "max_bin": "max_bin", "slots": 1}))
    assert r == pytest.approx(100 * 2 * 2.0768 / 3)
    # a reader that finds nothing to read returns nothing, never 0
    assert readers.scope_share(ctx, {"name": "no_such_kernel"}) is None
    assert readers.roofline_share(ctx, dict(
        name="no_such_kernel", work="histogram_pass", work_args={})) is None
    assert readers.scope_share(
        ctx, {"name": "no_such_kernel", "zero_if_absent": True}) == 0.0


def test_idle_gaps_are_named_by_what_the_host_was_doing(handmade):
    """Without the program's spans a gap is named by the annotation."""
    gaps = P.idle_gaps({"trace": handmade})
    assert sorted(round(g[1], 6) for g in gaps) == [1.0, 1.0, 2.0]
    assert gaps[0][1] == pytest.approx(2.0)
    # 6..8 lies 0.5 s under `update` and 1.0 s under `between_rounds`
    assert gaps[0][0] == "between_rounds"
    assert {g[0] for g in gaps[1:]} == {"update"}


def test_top_ops_sum_by_program_and_instruction(handmade):
    top = T.top_ops(handmade)
    assert top[0] == ("jit_grow:pallas_histogram_multi_rows",
                      pytest.approx(3.0))
    assert dict(top)["jit_grow:while"] == pytest.approx(1.0)


def test_instruction_text_is_split_into_name_and_opcode():
    m = T.INSTRUCTION.match(
        "%pallas_histogram_multi_rows.2 = f32[13,72,255]{2,1,0:T(8,128)S(1)} "
        "custom-call(u8[13,65536]{1,0:T(8,128)(4,1)} %p0), custom_call_target"
        '="tpu_custom_call"')
    assert m.group(1) == "pallas_histogram_multi_rows.2"
    assert m.group(2) == "custom-call"
    m = T.INSTRUCTION.match(
        "%while.204 = (f32[31,13,255,3]{2,1,3,0:T(8,128)}, f32[31]{0:T(128)}) "
        "while((f32[31,13,255,3]{2,1,3,0:T(8,128)}, f32[31]{0}) %tuple.1)")
    assert (m.group(1), m.group(2)) == ("while.204", "while")
    assert T.INSTRUCTION.match("fusion.9").group(1) == "fusion.9"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(trace, the job's saved result, `run.per_layer`'s reduction of both
    for the manifest's first cell)."""
    path = tmp_path_factory.mktemp("trace") / "round.xplane.pb"
    with gzip.open(os.path.join(FIXTURE, "trace.xplane.pb.gz"), "rb") as f:
        path.write_bytes(f.read())
    with open(os.path.join(FIXTURE, "program.json")) as f:
        saved = json.load(f)
    cell = manifest.benchmark()["workloads"][0]["name"]
    return T.load(str(path)), saved, program_run.reduce_saved(FIXTURE, cell)


def test_recorded_trace_reduces(recorded):
    """Every metric that lists the manifest's first cell reads on its
    fixture: those of `BENCHMARK.json`'s `per_layer` and those of
    `program_metrics/`."""
    tr, saved, out = recorded
    rounds = saved["units_in_window"]["rounds"]
    assert tr.devices == [0]
    lo, hi = T.window_of(tr)
    busy = T.busy_seconds(tr)
    assert 0 < busy <= (hi - lo) / 1e9
    assert sum(s.name == "update" for s in tr.spans) == rounds
    assert sum(s.name == "between_rounds" for s in tr.spans) == rounds
    # every operation found its program, and the grower's is the heaviest:
    # at 65,536 rows its split scan's `reduce-window` outweighs the kernel
    # (`test_the_program_metrics_read_the_recorded_run`)
    assert all(o.program for o in tr.ops)
    assert T.top_ops(tr)[0][0] == "jit_grow:reduce-window"
    assert "jit_grow:pallas_histogram_multi_rows_full" in dict(T.top_ops(tr))
    first = manifest.benchmark()["workloads"][0]["name"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(got) == {m["name"] for m in manifest.benchmark()["per_layer"]
                        if first in m.get("workloads", [first])}
    assert set(out["program_metrics"]) == {
        m["name"] for m in manifest.layer_metrics(
            first, folder=manifest.PROGRAM_METRICS)}
    # the parts of device-busy time add up to all of it
    parts = ["round.outside_grower_pct", "grower.other_pct", "hist.time_pct"]
    assert all(0 <= got[k] <= 100 for k in parts), got
    assert sum(got[k] for k in parts) == pytest.approx(100.0, abs=0.01)
    assert got["hist.time_pct"] == pytest.approx(HIST_PCT, abs=0.5)
    assert 0 < got["hist_kernel_roofline"] < 100
    assert got["grower.passes_per_tree"] == PASSES
    assert got["grower.passes_per_tree"] == pytest.approx(
        out["program_metrics"]["grower.hist_passes_per_tree"]["value"],
        abs=0.01)
    assert 0 <= got["device.idle_pct"] < 100
    assert got["entry.compiles_in_window"] == 0
    assert len(out["breakdown"]["idle_gaps"]) == 10
