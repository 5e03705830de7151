"""What the program records of itself, read by `perfbench/xplane_meta.py`
and by the readers of `program_readers.py` (part of `readers.READERS`) in
the driver's traced run: on the trace PR 24 recorded
(`fixtures/round.xplane.pb.gz`, a program without the phase scopes), on a
traced run recorded on a TPU v5e through the job's own record
(`fixtures/driver_round/`, PR 37: `perfbench.program_run --save` on the
fixture cell `tiny13-l31.train`, 65,536 rows, one warm-up round and a
window of rounds; `program.json` holds the job's `program`, `counters`,
`units_in_window`, `shape` and `memory_peak_bytes`), and on hand-made
contexts and spans whose every number can be worked out on paper."""
import gzip
import json
import os
import shutil

import pytest

from perfbench import manifest, program_readers as P, program_run, readers
from perfbench import run, trace as T, xplane_meta as X

HERE = os.path.dirname(os.path.abspath(__file__))
OLD = os.path.join(HERE, "fixtures", "round.xplane.pb.gz")
DRIVER = os.path.join(HERE, "fixtures", "driver_round")
CELL = manifest.benchmark()["workloads"][0]["name"]
S = 1e9


def _unzipped(path, tmp_path_factory):
    out = str(tmp_path_factory.mktemp("xplane") / "fixture.xplane.pb")
    with gzip.open(path, "rb") as f, open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    return out


def program_metrics(cell):
    return manifest.layer_metrics(cell, folder=manifest.PROGRAM_METRICS)


@pytest.fixture(scope="module")
def old(tmp_path_factory):
    path = _unzipped(OLD, tmp_path_factory)
    return T.load(path), X.device_meta(path)


@pytest.fixture(scope="module")
def new(tmp_path_factory):
    """(context of the readers, the reduction of `run.per_layer`)."""
    path = _unzipped(os.path.join(DRIVER, "trace.xplane.pb.gz"),
                     tmp_path_factory)
    with open(os.path.join(DRIVER, "program.json")) as f:
        saved = json.load(f)
    ctx = {"trace": T.load(path, P.span_names(saved["program"])),
           "trace_file": path,
           "program": saved["program"], "counters": saved["counters"],
           "units": saved["units_in_window"], "shape": saved["shape"]}
    return ctx, run.per_layer(CELL, dict(saved, trace_file=path),
                              "TPU v5 lite", manifest.HERE)


# ------------------------------------------------------------- xplane_meta
def test_wire_walker_reads_varints_and_nested_messages():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed32
    buf = bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62,
                 0x1D, 1, 0, 0, 0])
    assert list(X.fields(buf)) == [(1, 300), (2, b"ab"),
                                   (3, bytes([1, 0, 0, 0]))]
    with pytest.raises(ValueError):
        list(X.fields(bytes([0x0B])))       # wire type 3: not an xplane


def test_old_fixture_carries_a_scope_for_every_kernel_call(old):
    """PR 24 read "no scope reaches the device events": the names are in
    the events' metadata, which `ProfileData` does not show."""
    tr, meta = old
    assert all(o.scope == "" for o in tr.ops)       # what trace.py sees
    kernels = [o for o in tr.ops
               if o.name.startswith("pallas_histogram_multi_rows")]
    assert kernels
    for o in kernels:
        m = X.scope_of(meta, o)
        assert m is not None and "/histogram_wave/" in m.scope
        assert m.scope.startswith("jit(grow)/")
        assert "/lightgbm_tpu/ops/" in m.source
        assert m.program_id
    # every traced operation has its metadata; the only phase is the
    # kernel's: that program wrapped nothing else
    assert all(X.scope_of(meta, o) is not None for o in tr.ops)
    phases = {P.phase_of(X.scope_of(meta, o).scope) for o in tr.ops}
    assert phases == {"", "histogram_wave"}


def test_phase_of_takes_the_innermost_phase():
    assert P.phase_of("jit(grow)/while/body/partition/while/body/"
                      "find_split/jit(_where)/select_n:") == "find_split"
    assert P.phase_of("jit(grow)/while/body/cond/branch_0_fun/"
                      "histogram_wave/jit(pallas_histogram_multi_rows)/"
                      "pallas_call:") == "histogram_wave"
    assert P.phase_of("jit(grow)/while/cond/reduce_max:") == ""
    assert P.phase_of("") == ""
    assert P.phase_of("jit(grow)/my_partition_helper/add:") == ""


# -------------------------------------------------- the recorded new trace
def test_each_phase_selects_device_time_and_they_add_up(new):
    """The six phases of a tree without overgrow (`prune` exists only with
    it), the kernel under `histogram_wave`, and what is under no phase add
    up to the grower's share of the device's busy time."""
    ctx, _ = new
    secs = P.phase_seconds(ctx, "^jit_grow$")
    for phase in ("init", "payload", "partition", "histogram_wave",
                  "hist_cache", "find_split"):
        assert secs.get(phase, 0.0) > 0.0, phase
    assert "prune" not in secs
    tr = ctx["trace"]
    grower = T.op_seconds(T.select(tr, program="^jit_grow$"),
                          len(tr.devices))
    assert sum(secs.values()) == pytest.approx(grower, rel=1e-9)
    shares = [P.phase_share(ctx, {"phase": p}) for p in secs]
    whole = readers.scope_share(ctx, {"program": "^jit_grow$"})
    assert sum(shares) == pytest.approx(whole, rel=1e-9)
    # the kernel's time lies under histogram_wave
    kernel = T.op_seconds(T.select(tr, name="^pallas_histogram",
                                   opcode="custom-call"), len(tr.devices))
    assert 0 < kernel <= secs["histogram_wave"]


def test_the_program_metrics_read_the_recorded_run(new):
    """Every metric of `program_metrics/` that lists the first cell reads
    on the driver's traced run, and each says what its parts say."""
    ctx, out = new
    m = {k: v["value"] for k, v in out["program_metrics"].items()}
    assert set(m) == {x["name"] for x in program_metrics(CELL)}
    assert len(m) == 14
    for name in ("split.time_pct", "partition.time_pct",
                 "grower.unattributed_pct"):
        assert 0 < m[name] < 100
    # what is under no phase is, before all, the `reduce-window` that XLA
    # makes of the split scan's cumsum without a name stack (at 65,536 rows
    # it outweighs the phases; at the cell's size it is 0.015% of the device)
    left, device_meta = {}, X.device_meta(ctx["trace_file"])
    for o in T.select(ctx["trace"], program="^jit_grow$"):
        meta = X.scope_of(device_meta, o)
        if not P.phase_of(meta.scope if meta else ""):
            base = o.name.split(".")[0]
            left[base] = left.get(base, 0.0) + o.dur
    assert max(left, key=left.get) == "reduce-window"
    assert 0 < m["hist.useful_row_pct"] < 100
    # a tree makes its passes and needs its root and some smaller children
    passes = readers.scope_count_per(ctx, {
        "name": "^pallas_histogram", "opcode": "custom-call",
        "program": "^jit_grow$", "per": "trees"})
    needed = ctx["counters"]["grow.hist_rows_needed"]
    assert needed == (ctx["program"]["counters_end"]["grow.hist_rows_needed"]
                      - ctx["program"]["counters_start"][
                          "grow.hist_rows_needed"])
    assert m["hist.useful_row_pct"] == pytest.approx(
        100 * needed / (passes * ctx["units"]["trees"]
                        * ctx["shape"]["rows"]))
    # the program counts the kernel calls the device trace shows (one
    # column block a pass), and the rows it contracts are fewer than the
    # rows it streams
    assert m["grower.hist_passes_per_tree"] == pytest.approx(passes,
                                                             abs=0.01)
    assert 0 < m["hist.full_pass_pct"] <= 100
    assert m["hist.useful_row_pct"] <= m["hist.mxu_useful_row_pct"] <= 100
    c = ctx["counters"]
    assert m["route.picks_per_pass"] == pytest.approx(
        c["grow.route_picks"] / c["grow.route_passes"])
    assert m["grower.leaves_per_tree"] == 31
    assert m["score.lookup_row_pct"] == 100     # PR 36's pass on the chip
    assert m["setup.first_round_s"] > 0
    assert m["setup.compile_s"] > 0
    assert m["setup.cache_misses"] >= 0
    assert m["entry.host_gap_ms_per_round"] > 0


def test_every_long_gap_is_named_by_a_program_span(new):
    ctx, out = new
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps and len(gaps) <= 10
    names = {s["name"] for s in ctx["program"]["spans"]}
    for name, seconds in gaps:
        outer, _, inner = name.partition("/")
        assert outer in ("update", "between_rounds", "no_annotation")
        assert seconds > 0
        if outer == "update":
            assert inner in names, name
    longest = max(gaps, key=lambda g: g[1])[0]
    assert longest.startswith("update/train."), longest
    # the split by span adds up to the metric, a list of [span, ms] pairs
    by_span = out["breakdown"]["host_gap_ms_by_span"]
    assert 0 < len(by_span) <= 10 and all(len(x) == 2 for x in by_span)
    assert sum(ms for _, ms in by_span) == pytest.approx(
        out["program_metrics"]["entry.host_gap_ms_per_round"]["value"])
    # the grower's seconds by phase add up to its device time
    phases = dict(out["breakdown"]["phase_s"])
    assert "histogram_wave" in phases and "unattributed" in phases
    assert sum(phases.values()) == pytest.approx(T.op_seconds(
        T.select(ctx["trace"], program="^jit_grow$"), 1))
    # the program's spans are on the profiler's clock, inside the window
    lo, hi = T.window_of(ctx["trace"])
    waits = [s for line in P.program_lines(ctx) for s in line
             if s.name == "train.wait"]
    assert len(waits) == ctx["units"]["rounds"]
    assert all(lo <= s.start and s.start + s.dur <= hi for s in waits)


def test_the_existing_readers_do_not_see_the_programs_spans(new):
    """`trace.load` keeps the benchmark's annotations only, so the window
    and with it the eight metrics of PR 24 read as before."""
    ctx, _ = new
    assert {s.name for s in ctx["trace"].spans} == {"update",
                                                    "between_rounds"}


# ------------------------------------------------------ hand-made spans
def test_self_cover_gives_an_interval_to_the_innermost_span():
    line = [T.Span("train.chunk", 0 * S, 10 * S),
            T.Span("train.grow", 1 * S, 1 * S),
            T.Span("train.wait", 2 * S, 5 * S),
            T.Span("train.decode", 7 * S, 1 * S),
            T.Span("train.chunk", 11 * S, 4 * S)]
    # 6.5..9: 0.5 under wait, 1 under decode, 1 under the chunk itself
    assert P.self_cover(line, 6.5 * S, 9 * S) == {
        "train.wait": 0.5 * S, "train.decode": 1 * S, "train.chunk": 1 * S}
    # 10..11 lies between two rounds: under no span
    assert P.self_cover(line, 10 * S, 11 * S) == {}
    assert P.self_cover(line, 12 * S, 13 * S) == {"train.chunk": 1 * S}


def test_gaps_and_their_names_on_a_handmade_trace():
    """Device busy 1..6 and 8..9 of a window 0..10: gaps 0..1, 6..8, 9..10."""
    ops = [T.Op("fusion.1", "fusion", "", "jit_grow", 1 * S, 5 * S, 5 * S,
                0),
           T.Op("fusion.2", "fusion", "", "jit_add", 8 * S, 1 * S, 1 * S, 0)]
    tr = T.Trace(ops, [], [T.Span("update", 0, 9.5 * S),
                           T.Span("between_rounds", 9.5 * S, 0.5 * S)], [0],
                 [[T.Span("train.chunk", 0.2 * S, 9.2 * S),
                   T.Span("train.gradients", 0.2 * S, 0.7 * S),
                   T.Span("train.wait", 1 * S, 5.5 * S),
                   T.Span("train.decode", 6.5 * S, 1.0 * S),
                   T.Span("train.score", 7.5 * S, 1.8 * S)]])
    assert T.idle_intervals(tr) == [(0, 1 * S), (6 * S, 8 * S),
                                    (9 * S, 10 * S)]
    ctx = {"trace": tr, "units": {"rounds": 2}}
    assert P.idle_gaps(ctx) == [
        ("update/train.decode", 2.0), ("update/train.gradients", 1.0),
        ("update/train.score", 1.0)]
    by_span = P.gap_ns_by_span(ctx)
    assert by_span == pytest.approx({
        "train.gradients": 0.7 * S, "train.wait": 0.5 * S,
        "train.decode": 1.0 * S, "train.score": 0.8 * S,
        "train.chunk": 0.2 * S})
    # 0..0.2 and 9.4..10 lie under no program span: 3.2 s of 4.0 s idle
    assert P.host_gap_per_round(ctx, {}) == pytest.approx(3200.0 / 2)


def test_readers_return_nothing_where_the_program_records_nothing(
        old, tmp_path_factory):
    """The parent of PR 25: no spans, no counters, one scope.  A reader
    then returns None (the metric is left out) and does not raise."""
    tr, _ = old
    ctx = {"trace": tr, "trace_file": None, "program": None,
           "counters": {"jit.recompiles": 0},
           "units": {"rounds": 2, "trees": 2},
           "shape": {"rows": 65536, "columns": 13, "max_bin": 255}}
    for m in program_metrics(CELL):
        assert readers.read(m, ctx) is None, m["name"]
    result = {"trace_file": _unzipped(OLD, tmp_path_factory),
              "counters": ctx["counters"], "units_in_window": ctx["units"],
              "shape": ctx["shape"]}
    out = run.per_layer(CELL, result, "TPU v5 lite", manifest.HERE)
    # with the file, the one scope it names makes all else "unattributed"
    # (test_old_programs_phases_read_as_unattributed)
    assert set(out["program_metrics"]) == {"grower.unattributed_pct"}
    # without the program's spans a gap keeps the benchmark's name
    gaps = out["breakdown"]["idle_gaps"]
    assert len(gaps) == 10 and {n for n, _ in gaps} <= {
        "update", "between_rounds", "no_annotation"}
    assert out["breakdown"]["host_gap_ms_by_span"] == []


def test_old_programs_phases_read_as_unattributed(old, tmp_path_factory):
    """On the old executable's names everything outside the kernel is
    under no phase: `grower.unattributed_pct` then reads what
    `grower.other_pct` reads, which is how stale names show."""
    tr, _ = old
    path = _unzipped(OLD, tmp_path_factory)
    ctx = {"trace": tr, "trace_file": path}
    assert P.phase_share(ctx, {"phase": "find_split"}) is None
    assert P.phase_share(ctx, {"phase": "partition"}) is None
    other = readers.scope_share(ctx, {"program": "^jit_grow$",
                                      "not_name": "^pallas_histogram"})
    kernel_side = P.phase_share(ctx, {"phase": "histogram_wave"})
    whole = readers.scope_share(ctx, {"program": "^jit_grow$"})
    assert P.phase_share(ctx, {"phase": ""}) == pytest.approx(
        whole - kernel_side)
    assert P.phase_share(ctx, {"phase": ""}) <= other


# ----------------------------------------------------------- the data files
def test_program_metric_files_are_ready_for_the_manifest():
    """Each file in `program_metrics/` has what a `layer_metrics/` file and
    a `per_layer` entry need, lists only cells of the manifest that report
    what it moves, and names a reader of `readers.READERS`: a later PR
    moves the files and lists them, and edits nothing else (PERF.md 7)."""
    b = manifest.benchmark()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    taken = {m["name"] for m in b["per_layer"]} | set(e2e)
    d = os.path.join(manifest.HERE, manifest.PROGRAM_METRICS)
    files = sorted(f for f in os.listdir(d) if f.endswith(".json"))
    assert len(files) == 15
    for fn in files:
        m = json.load(open(os.path.join(d, fn)))
        assert m["name"] + ".json" == fn
        assert manifest.NAME.match(m["name"]) and m["name"] not in taken
        assert manifest.UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in manifest.SOURCES
        assert m["moves"] in e2e
        assert m["reader"] in readers.READERS
        assert set(m["workloads"]) <= cells and m["workloads"]
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get(
            "workloads", cells))
        assert 1 <= len(m["layer"]) <= 200
    listed = {m["name"] for m in program_metrics(CELL)}
    assert len(listed) == 14 and "cat.split.cat_split_pct" not in listed


CHIP = {"grow.hist_passes_full": 9, "grow.hist_passes_c256": 28,
        "grow.hist_passes_c512": 3, "grow.leaves": 510, "grow.cat_splits": 84,
        "grow.route_picks": 11, "grow.route_passes": 2}


@pytest.mark.parametrize("shards,want", [(1, 20.0), (4, 5.0)])
def test_counter_ratio_divides_by_trees_and_shards(shards, want):
    """Two trees of 40 kernel calls summed over the shards: 20 a tree on
    one chip, 5 a tree a shard on four (`mesh.shards` as the window
    closed)."""
    ctx = {"counters": dict(CHIP), "units": {"trees": 2, "rounds": 2},
           "program": {"counters_end": {"mesh.shards": float(shards)}}}
    args = {"num": ["grow.hist_passes_full", "grow.hist_passes_c256",
                    "grow.hist_passes_c512"],
            "per": "trees", "gauge": "mesh.shards"}
    assert P.counter_ratio(ctx, args) == pytest.approx(want)
    # without the gauge (a program that does not set it) nothing is read
    assert P.counter_ratio(dict(ctx, program={}), args) is None


def test_counter_ratio_sums_scales_and_subtracts():
    ctx = {"counters": dict(CHIP), "units": {"trees": 2}}
    full = {"num": ["grow.hist_passes_full"],
            "den": ["grow.hist_passes_full", "grow.hist_passes_c256",
                    "grow.hist_passes_c512"], "scale": 100}
    assert P.counter_ratio(ctx, full) == pytest.approx(100 * 9 / 40)
    assert P.counter_ratio(ctx, {"num": ["grow.route_picks"],
                                 "den": ["grow.route_passes"]}) == 5.5
    # categorical splits over all splits: leaves less one a tree
    assert P.counter_ratio(ctx, {
        "num": ["grow.cat_splits"], "den": ["grow.leaves"],
        "less_per": "trees", "scale": 100}) == pytest.approx(100 * 84 / 508)
    # a counter the program never touched adds 0: no look-up by the pass
    # reads 0%, not nothing; where it has none of the named counters, or
    # the denominator is 0, nothing is read
    assert P.counter_ratio(ctx, {
        "num": ["grow.route_picks"],
        "den": ["grow.route_passes", "score.gather_rows"]}) == 5.5
    gather = dict(ctx, counters={"score.gather_rows": 65536})
    lookup = {"num": ["score.lookup_rows"],
              "den": ["score.lookup_rows", "score.gather_rows"]}
    assert P.counter_ratio(gather, lookup) == 0.0
    assert P.counter_ratio(ctx, lookup) is None
    assert P.counter_ratio(ctx, {"num": ["grow.leaves"], "per": "rounds"}) \
        is None
    assert P.counter_ratio({"counters": {"a": 0, "b": 0}},
                           {"num": ["a"], "den": ["b"]}) is None


def test_rows_needed_by_counts_on_a_tree_worked_by_hand():
    """Root 100 rows -> (60 | 40); the 60 -> (45 | 15); the 40 -> (10 | 30):
    100 + 40 + 15 + 10."""
    from perfbench.reference import gbdt
    import numpy as np
    tree = gbdt.TreeArrays(
        split_feature=np.zeros(3, np.int32), threshold=np.zeros(3),
        left=np.array([1, ~0, ~1], np.int32),
        right=np.array([2, ~2, ~3], np.int32),
        leaf_value=np.zeros(4), leaf_count=np.array([45., 10., 15., 30.]),
        split_gain=np.zeros(3), leaf_weight=np.zeros(4))
    assert program_run.rows_needed_by_counts(tree) == 165.0
    stump = gbdt.TreeArrays(*(np.zeros(0),) * 4, np.zeros(1),
                            np.array([7.0]), np.zeros(0), np.zeros(1))
    assert program_run.rows_needed_by_counts(stump) == 7.0


def _fixture_cell(tmp_path, min_window_rounds=None):
    bench = str(tmp_path / "bench")
    shutil.copytree(os.path.join(HERE, "fixtures", "bench"), bench)
    cell = "tiny13-l31.train"
    path = os.path.join(bench, "workloads", cell + ".json")
    with open(path) as f:
        body = json.load(f)
    if min_window_rounds:
        body["traffic_params"]["min_window_rounds"] = min_window_rounds
    with open(path, "w") as f:
        json.dump(body, f)
    hooks = run.default_hooks()
    hooks.require_chip = hooks.compile_cache = False
    return bench, cell, body, hooks


def test_the_row_check_counts_every_tree_of_the_window(capsys, tmp_path):
    """`program_run` on the CPU, the fixture cell, a window of more rounds
    than the reference follows: the rows the program counted over the
    window equal the count from the leaf counts of ALL the window's trees
    (the check used to see the followed trees only: 2 of a 4-round window).
    The job's record is in the result, and its sink is off the program's
    tracer once the window has closed."""
    import re
    from lightgbm_tpu import telemetry
    bench, cell, body, hooks = _fixture_cell(tmp_path, 5)
    followed = body["traffic_params"]["check_rounds"]
    sinks = list(telemetry.TRACER._sinks)
    rc = program_run.main(
        ["--workload", cell, "--seed", "5", "--seconds", "0.1",
         "--bench-dir", bench, "--save", str(tmp_path / "saved")],
        hooks=hooks)
    out = capsys.readouterr().out
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] >= 5 > followed
    said = re.search(r"program: grow\.hist_rows_needed over the window = "
                     r"(\d+); from the dumped leaf counts of (\d+) tree\(s\) "
                     r"= (\d+)", out)
    assert said, out[-2000:]
    counted, trees, by_counts = map(int, said.groups())
    assert trees == line["attempted"]
    assert counted == by_counts > 0
    assert telemetry.TRACER._sinks == sinks
    with open(tmp_path / "saved" / "program.json") as f:
        kept = json.load(f)
    assert set(kept) == set(program_run.SAVED)
    spans = {e["name"] for e in kept["program"]["spans"]}
    assert {"train.chunk", "train.grow", "train.decode"} <= spans
    # every counter's change over the window, not jit.recompiles alone
    assert kept["counters"]["grow.leaves"] == 31 * line["attempted"]
    assert kept["counters"]["grow.hist_rows_needed"] == counted
    assert kept["counters"]["jit.recompiles"] == 0


def test_an_untraced_run_attaches_nothing(capsys, tmp_path, monkeypatch):
    """`--trace 0` runs the parent's code: no sink on the program's tracer,
    no `program` in the result, and `counters` holds `jit.recompiles`
    alone."""
    from lightgbm_tpu import telemetry
    bench, cell, _, hooks = _fixture_cell(tmp_path)
    results, seen = [], []
    real_add = telemetry.TRACER.add_sink

    def add_sink(sink):
        seen.append(sink)
        return real_add(sink)
    hooks.on_result = results.append
    monkeypatch.setattr(telemetry.TRACER, "add_sink", add_sink)
    sinks = list(telemetry.TRACER._sinks)
    rc = run.main(["--workload", cell, "--seed", "6", "--seconds", "0.1",
                   "--trace", "0", "--bench-dir", bench], hooks=hooks)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert seen == [] and telemetry.TRACER._sinks == sinks
    (result,) = results
    assert "program" not in result
    assert set(result["counters"]) == {"jit.recompiles"}
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
