"""The `train_cat` job: `jobs/train.py`'s closed loop over a data set whose
declared columns are CATEGORICAL.

The configuration lists `categorical_feature`; the public constructor fits
the mappers on the first `sample_rows` training rows with those columns
declared, so a declared column is binned by count (the most frequent
category is bin 1; rare categories, and what the sample never saw, share
bin 0, "other") and the program searches category sets on it.  A declared
column's codes go to bins by the mapper's own table: several codes may
share bin 0, which the numerical columns' "one bin per code" demand
(`jobs.train.build_dataset`) would refuse.  The reference
(`reference/gbdt_cat.py`) works in code space and takes nothing of this: it
works out which categories are "other" from the same rows by the rule the
configuration states.

Set-up, window, quality and check are `jobs/train.run`'s, call for call:
`run_window`, `auc`, `round_failed` and `wait_for_rounds` are imported from
there and nothing of the timing differs, and a traced run records the
program by `jobs.train.ProgramRecord`.  An untraced run's `counters` also
carry the window's change of `grow.cat_splits`, `grow.route_passes` and
`grow.route_picks` (0 where the program under test has no such counter).
"""
from __future__ import annotations

import gc
import hashlib
import json
import math
import time
from typing import Any, Callable, Dict, List

import numpy as np

from .. import check, trace as tracelib
from ..manifest import load_module
from .train import (ProgramRecord, auc, round_failed, run_window,
                    wait_for_rounds)

COUNTERS = ("jit.recompiles", "grow.cat_splits", "grow.route_passes",
            "grow.route_picks")


def build_dataset(lgb, codes: np.ndarray, label: np.ndarray, params: dict,
                  names: List[str], categorical: List[str],
                  sample_rows: int = 200_000):
    """`jobs.train.build_dataset` with the columns of `categorical`
    declared: mappers from the public constructor on a sample (raw value =
    code), then code -> bin by a table per column.  A numerical column
    still needs one bin per code; a declared one takes the mapper's
    table as it is."""
    n_feat, n = codes.shape
    take = min(n, sample_rows)
    bin_params = {k: params[k] for k in ("max_bin", "min_data_in_bin")
                  if k in params}
    sample = lgb.Dataset(
        np.ascontiguousarray(codes[:, :take].T).astype(np.float64),
        label=label[:take], feature_name=list(names),
        categorical_feature=list(categorical),
        params=bin_params).construct()
    mappers = sample.bin_mappers
    declared = [names.index(c) for c in categorical]
    bins = codes
    for f, m in enumerate(mappers):
        card = int(codes[f, :take].max()) + 1
        table = np.asarray(m.values_to_bins(np.arange(256, dtype=np.float64)))
        if f not in declared and len(np.unique(table[:card])) != card:
            raise RuntimeError(
                f"column {names[f]}: the fitted mapper merges codes "
                f"({card} codes -> {len(np.unique(table[:card]))} bins); "
                "the reference compares in code space and needs one bin "
                "per code of a numerical column")
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
    ds._categorical_indices = sorted(declared)
    ds._label_arr = np.asarray(label, np.float32)
    ds._handle_constructed = True
    return ds


def make_inputs(lgb, config: dict, seed: int, holdout_rows: int,
                say: Callable[[str], None] = lambda msg: None):
    """The configuration's training rows, the hold-out rows of `seed`, and
    the constructed data set over the training rows with the declared
    columns categorical: (rows, data set, the program's parameters)."""
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
                       [c["name"] for c in data["columns"]],
                       list(config["categorical_feature"]),
                       int(config.get("sample_rows", 200_000)))
    say(f"setup: data set in {time.perf_counter() - t:.2f} s (bins a "
        f"column: {[int(m.num_bin) for m in ds.bin_mappers]}; categorical: "
        f"{ds._categorical_indices})")
    return rows, ds, params


def run(ctx) -> Dict[str, Any]:
    """Drive one run of a categorical training cell; returns the result's
    fields (those of `jobs.train.run`)."""
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
    counters = {k: telemetry.REGISTRY.counter(k) for k in COUNTERS}
    counters_before = {k: c.value for k, c in counters.items()}

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
    counters_after = {k: c.value for k, c in counters.items()}
    say("window: counters " + json.dumps(
        {k: counters_after[k] - counters_before[k] for k in COUNTERS}))

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
    say("check: categorical nodes a tree: "
        + json.dumps([int(np.sum(t.is_cat)) for t in trees]))
    ctx.alter_trees(trees)
    # free the program's state before the reference takes the device
    del booster, ds, dump
    gc.collect()
    t = time.perf_counter()
    readings = ref.follow(rows["codes"], rows["label"], trees, params,
                          n_check=int(traffic["check_nodes"]), seed=ctx.seed,
                          categorical=ref.declared_columns(config))
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
