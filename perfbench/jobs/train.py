"""The `train` job: closed-loop boosting rounds through `Booster.update()`.

Set-up (counted as `setup_s`): the configuration's training rows
(`data.population_seed`: the same in every run, so every run grows the same
trees) and the hold-out rows of `--seed`, both in code space, bin
mappers fitted through the public `Dataset(...).construct()` on a sample,
the whole matrix handed to a constructed `Dataset` the way
`Dataset.load_binary` builds one, the booster, `warmup_rounds` rounds.
Window: `update()` until a call returns with the clock at or past
`--seconds`, at least `min_window_rounds` rounds.  After the window, outside
the timing: the peak memory is read, the hold-out rows are scored by the
model of exactly `quality_rounds` rounds, the program's state is freed, and
the plain reference follows the first `check_rounds` trees
(`perfbench/check.py` decides `correct`).

A traced run (`ctx.trace`) also records the program itself
(`ProgramRecord`): its spans from before the rows are made, and its
counters and gauges at the window's two edges.  The result then carries
`program`, and `counters` is the window's change of every counter; an
untraced run attaches nothing and counts `jit.recompiles` alone.
"""
from __future__ import annotations

import gc
import hashlib
import json
import math
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .. import check, trace as tracelib
from ..manifest import load_module


# ----------------------------------------------------------------- helpers
def auc(label: np.ndarray, score: np.ndarray) -> float:
    """Area under the ROC curve by ranks, ties at their mean rank."""
    label = np.asarray(label) > 0.5
    n_pos = int(label.sum())
    n_neg = len(label) - n_pos
    if not n_pos or not n_neg:
        return float("nan")
    _, group, size = np.unique(np.asarray(score, np.float64),
                               return_inverse=True, return_counts=True)
    mean_rank = np.cumsum(size) - (size - 1) / 2.0
    pos_ranks = mean_rank[group[label]].sum()
    return float((pos_ranks - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def build_dataset(lgb, codes: np.ndarray, label: np.ndarray, params: dict,
                  names: List[str], sample_rows: int = 200_000):
    """A constructed `Dataset` over all rows without binning them one by
    one: mappers from the public constructor on a sample (raw value =
    code), then code -> bin by a table per column.  `bin_data` is the
    transposed view of the feature-major [F, N] bins, so the program's own
    feature-major copy is no copy."""
    n_feat, n = codes.shape
    take = min(n, sample_rows)
    bin_params = {k: params[k] for k in ("max_bin", "min_data_in_bin")
                  if k in params}
    sample = lgb.Dataset(
        np.ascontiguousarray(codes[:, :take].T).astype(np.float64),
        label=label[:take], feature_name=list(names),
        params=bin_params).construct()
    mappers = sample.bin_mappers
    bins = codes
    for f, m in enumerate(mappers):
        card = int(codes[f, :take].max()) + 1
        table = np.asarray(m.values_to_bins(np.arange(256, dtype=np.float64)))
        if len(np.unique(table[:card])) != card:
            raise RuntimeError(
                f"column {names[f]}: the fitted mapper merges codes "
                f"({card} codes -> {len(np.unique(table[:card]))} bins); "
                "the reference compares in code space and needs one bin "
                "per code")
        if not np.array_equal(table[:card], np.arange(card)):
            if bins is codes:
                bins = codes.copy()
            bins[f] = table.astype(np.uint8)[codes[f]]
    ds = lgb.Dataset(None, free_raw_data=False)
    ds.bin_mappers = mappers
    ds.bin_data = bins.T
    ds._num_data, ds._num_feature = n, n_feat
    ds.num_total_bin = sum(m.num_bin for m in mappers)
    ds._feature_names = list(names)
    ds._categorical_indices = []
    ds._label_arr = np.asarray(label, np.float32)
    ds._handle_constructed = True
    return ds


def make_inputs(lgb, config: dict, seed: int, holdout_rows: int,
                say: Callable[[str], None] = lambda msg: None):
    """The configuration's training rows, the hold-out rows of `seed`, and
    the constructed data set over the training rows: (rows, data set, the
    program's parameters)."""
    data = config["data"]
    gen = load_module("generators", config["generator"])
    t = time.perf_counter()
    rows = gen.make(seed, data, int(config["train_rows"]), holdout_rows)
    say(f"setup: rows made in {time.perf_counter() - t:.2f} s "
        f"({rows['codes'].shape[1]} x {rows['codes'].shape[0]} of population "
        f"{data['population_seed']}, label mean {rows['label'].mean():.4f}; "
        f"{rows['holdout_codes'].shape[1]} hold-out rows of seed {seed})")
    params = dict(config["params"])
    t = time.perf_counter()
    ds = build_dataset(lgb, rows["codes"], rows["label"], params,
                       [c["name"] for c in data["columns"]])
    say(f"setup: data set in {time.perf_counter() - t:.2f} s")
    return rows, ds, params


def round_failed(booster, rounds_before: int) -> Optional[str]:
    """Why the round just made does not count, or None."""
    if booster.current_iteration() != rounds_before + 1:
        return "no round was added"
    tree = booster.dump_model(start_iteration=rounds_before,
                              num_iteration=1)["tree_info"][0]
    if int(tree["num_leaves"]) < 2:
        return "empty tree"
    stack, vals = [tree["tree_structure"]], []
    while stack:
        node = stack.pop()
        if "leaf_value" in node:
            vals.append(node["leaf_value"])
        else:
            stack += [node["left_child"], node["right_child"]]
    if not np.all(np.isfinite(vals)):
        return "non-finite leaf value"
    return None


class ProgramRecord:
    """What the program records of itself in a traced run: a `MemorySink`
    on `lightgbm_tpu.telemetry.TRACER` and the compile listener, attached
    when made (before anything of the program runs), and its counters and
    gauges when the window opens and closes; the sink comes off at the
    close."""

    def __init__(self):
        from lightgbm_tpu import telemetry
        from lightgbm_tpu.telemetry.recorder import install_compile_listener
        self.telemetry = telemetry
        self.sink = telemetry.TRACER.add_sink(telemetry.MemorySink())
        install_compile_listener()
        self.start: Dict[str, Dict[str, float]] = {}
        self.end: Dict[str, Dict[str, float]] = {}

    def _snapshot(self) -> Dict[str, Dict[str, float]]:
        snap = self.telemetry.REGISTRY.snapshot()
        return {"counters": snap["counters"], "gauges": snap["gauges"]}

    def open(self) -> None:
        self.start = self._snapshot()

    def close(self) -> None:
        self.end = self._snapshot()
        self.telemetry.TRACER.remove_sink(self.sink)

    def fields(self) -> Dict[str, Any]:
        """The result's `program`, and `counters` as the window's change
        of every counter."""
        before = self.start["counters"]
        return {
            "program": {
                "spans": [e for e in self.sink.events
                          if e.get("ev") == "span"],
                "counters_start": {**before, **self.start["gauges"]},
                "counters_end": {**self.end["counters"],
                                 **self.end["gauges"]}},
            "counters": {k: v - before.get(k, 0)
                         for k, v in self.end["counters"].items()}}


def wait_for_rounds(booster) -> None:
    """Block until the device work of the rounds made so far is done (the
    score update is dispatched after the tree has been decoded)."""
    import jax
    score = getattr(booster, "_train_score", None)
    if score is not None:
        jax.block_until_ready(score)


def run_window(update: Callable[[], Any], seconds: float, min_rounds: int,
               clock: Callable[[], float] = time.perf_counter,
               on_round: Optional[Callable[[int, float], None]] = None,
               finish: Optional[Callable[[], None]] = None) -> Dict[str, Any]:
    """Closed loop: the next round starts when the last has returned.
    Ends when a call returns with the clock at or past `seconds` and at
    least `min_rounds` rounds are made.  The rate is all rounds completed
    over the time from the window's start to the last completion."""
    t0 = clock()
    attempted = failed = 0
    ends: List[float] = []
    while True:
        attempted += 1
        try:
            why = update()
        except Exception as e:      # a round that raises is a failed round
            why = f"raised {type(e).__name__}: {e}"
        now = clock() - t0
        if why:
            failed += 1
        else:
            ends.append(now)
        if on_round is not None:
            on_round(attempted, now, why)
        if now >= seconds and attempted >= min_rounds:
            break
        if failed >= 3:             # a broken program must not spin
            break
    if finish is not None:
        finish()
        if ends:
            ends[-1] = clock() - t0
    done = len(ends)
    return {"attempted": attempted, "failed": failed, "completed": done,
            "window_s": ends[-1] if ends else clock() - t0,
            "round_ends_s": ends,
            "rounds_per_s": done / ends[-1] if ends else 0.0}


# --------------------------------------------------------------------- run
def run(ctx) -> Dict[str, Any]:
    """Drive one run of a training cell; returns the result's fields."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.telemetry.recorder import install_compile_listener

    cell, config, say = ctx.cell, ctx.config, ctx.say
    traffic = cell["traffic_params"]
    annotate = tracelib.annotation if ctx.trace else tracelib.no_annotation

    record = ProgramRecord() if ctx.trace else None
    rows, ds, params = make_inputs(lgb, config, ctx.seed,
                                   int(traffic["holdout_rows"]), say)
    install_compile_listener()
    t = time.perf_counter()
    booster = ctx.make_booster(lgb, params, ds)
    say(f"setup: booster in {time.perf_counter() - t:.2f} s")

    def one_round():
        """The window's own call; the warm-up rounds go through it too."""
        with annotate("update"):
            before = booster.current_iteration()
            booster.update()
        with annotate("between_rounds"):
            return round_failed(booster, before)

    t = time.perf_counter()
    for _ in range(int(traffic["warmup_rounds"])):
        why = one_round()
        if why:
            raise RuntimeError(f"warm-up round failed: {why}")
    wait_for_rounds(booster)
    say(f"setup: {traffic['warmup_rounds']} warm-up round(s) in "
        f"{time.perf_counter() - t:.2f} s")
    compiles = telemetry.REGISTRY.counter("jit.recompiles")
    counters_before = {"jit.recompiles": compiles.value}

    # ------------------------------------------------------------ window
    setup_s = time.perf_counter() - ctx.t0
    tracer = tracelib.Tracer(ctx.trace_dir) if ctx.trace else None
    if tracer:
        record.open()
        tracer.start()
    try:
        window = run_window(
            one_round, ctx.seconds, int(traffic["min_window_rounds"]),
            on_round=lambda i, now, why: say(
                f"window: round {i} returned at {now:.3f} s"
                + (f" FAILED: {why}" if why else "")),
            finish=lambda: wait_for_rounds(booster))
    finally:
        if tracer:
            t = time.perf_counter()
            tracer.stop()
            record.close()
            say(f"window: trace written in {time.perf_counter() - t:.2f} s")
    counters_after = {"jit.recompiles": compiles.value}

    # ----------------------------------------------- after the window closes
    dev = jax.local_devices()[0]
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    limit = int(stats.get("bytes_limit", 0))
    say(f"memory: peak_bytes_in_use={peak} bytes_limit={limit}")

    quality_rounds = int(traffic["quality_rounds"])
    while booster.current_iteration() < quality_rounds:
        booster.update()            # outside the timing
    hold_raw = np.ascontiguousarray(rows["holdout_codes"].T).astype(
        np.float64)
    t = time.perf_counter()
    hold_score = np.asarray(booster.predict(
        hold_raw, num_iteration=quality_rounds, raw_score=True))
    holdout_auc = auc(rows["holdout_label"], hold_score)
    say(f"quality: hold-out AUC after {quality_rounds} rounds = "
        f"{holdout_auc:.6f} (scored in {time.perf_counter() - t:.2f} s)")

    check_rounds = int(traffic["check_rounds"])
    ref = load_module("reference", config["reference"])
    dump = booster.dump_model(num_iteration=check_rounds)
    say(f"check: the first {len(dump['tree_info'])} tree(s) as dumped, sha256 "
        + hashlib.sha256(json.dumps(dump["tree_info"], sort_keys=True)
                         .encode()).hexdigest())
    trees = [ref.tree_from_dump(t) for t in dump["tree_info"]]
    ctx.alter_trees(trees)
    # free the program's state before the reference takes the device
    del booster, ds, dump
    gc.collect()
    t = time.perf_counter()
    readings = ref.follow(rows["codes"], rows["label"], trees, params,
                          n_check=int(traffic["check_nodes"]), seed=ctx.seed)
    numbers = check.compare(check.stated_of(trees), readings)
    say(f"check: reference followed {len(trees)} round(s) in "
        f"{time.perf_counter() - t:.2f} s; worst leaves: "
        + json.dumps(check.worst(check.stated_of(trees), readings)))
    limits = traffic["limits"]
    ok = check.verdict(numbers, limits) and window["failed"] == 0 \
        and window["completed"] >= int(traffic["min_window_rounds"]) \
        and math.isfinite(holdout_auc)

    out = {
        "correct": bool(ok),
        "attempted": window["attempted"], "failed": window["failed"],
        "window": window,
        "end_to_end": {
            "train_rounds_per_s": {"value": window["rounds_per_s"],
                                   "unit": "rounds/s"},
            "holdout_auc": {"value": holdout_auc, "unit": "auc"},
            "setup_s": {"value": setup_s, "unit": "s"}},
        "memory_peak_bytes": peak,
        "counters": {k: counters_after[k] - counters_before[k]
                     for k in counters_after},
        "units_in_window": {"trees": window["completed"],
                            "rounds": window["completed"]},
        "shape": {"rows": int(rows["codes"].shape[1]),
                  "columns": int(rows["codes"].shape[0]),
                  "max_bin": int(params.get("max_bin", 255)),
                  "num_leaves": int(params["num_leaves"])},
        "compared": check.compared_lines(numbers, limits),
        "trace_file": tracer.file() if tracer else None,
    }
    if record:
        out.update(record.fields())
    return out
