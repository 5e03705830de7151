"""The `train_rank` job: `jobs/train.py`'s closed loop over a data set of
QUERY GROUPS trained with a ranking objective.

The configuration's generator makes rows grouped by query
(`generators/ltr_codes.py`: codes, graded labels, query boundaries); the
data set is `jobs.train.build_dataset`'s, with the training queries bound
as `Dataset._query_boundaries` (what `Dataset(group=...)` stores).

Set-up, window and check are `jobs/train.run`'s, call for call:
`run_window`, `round_failed` and `wait_for_rounds` are imported from there
and nothing of the timing differs, and a traced run records the program by
`jobs.train.ProgramRecord`.  The quality (`holdout_auc`) is the mean, over
the hold-out queries that hold both a document of grade 0 and one above,
of the within-query AUC of grade > 0 against grade 0 (ties at their mean
rank), scored by the model of exactly `quality_rounds` rounds; NDCG@1, 3,
5 of the same scores are a log line.  The reference
(`reference/gbdt_rank.py`) follows the first `check_rounds` trees with its
own float64 lambdas.  An untraced run's `counters` also carry the
window's change of `rank.pair_slots` and `rank.pair_positions`.
"""
from __future__ import annotations

import gc
import hashlib
import json
import math
import time
from typing import Any, Callable, Dict

import numpy as np

from .. import check, trace as tracelib
from ..manifest import load_module
from .train import (ProgramRecord, auc, build_dataset, round_failed,
                    run_window, wait_for_rounds)

COUNTERS = ("jit.recompiles", "rank.pair_slots", "rank.pair_positions")


def query_auc(label: np.ndarray, score: np.ndarray,
              query_boundaries: np.ndarray) -> float:
    """Mean within-query AUC of label > 0 against label 0 over the queries
    that hold both; nan where none does."""
    qb = np.asarray(query_boundaries, np.int64)
    vals = [auc(label[lo:hi] > 0, score[lo:hi])
            for lo, hi in zip(qb[:-1], qb[1:])]
    vals = [v for v in vals if math.isfinite(v)]
    return float(np.mean(vals)) if vals else float("nan")


def query_ndcg(label: np.ndarray, score: np.ndarray,
               query_boundaries: np.ndarray, k: int) -> float:
    """Mean NDCG@k with gains 2^label - 1 (ties in row order), over the
    queries whose best DCG is above 0."""
    qb = np.asarray(query_boundaries, np.int64)
    out = []
    for lo, hi in zip(qb[:-1], qb[1:]):
        gain = 2.0 ** label[lo:hi] - 1.0
        disc = 1.0 / np.log2(np.arange(2, min(k, hi - lo) + 2))
        best = np.sort(gain)[::-1][:k] @ disc
        if best > 0:
            top = gain[np.argsort(-score[lo:hi], kind="stable")][:k]
            out.append(float(top @ disc / best))
    return float(np.mean(out)) if out else float("nan")


def make_inputs(lgb, config: dict, seed: int, holdout_queries: int,
                say: Callable[[str], None] = lambda msg: None):
    """The configuration's training queries, the hold-out queries of
    `seed`, and the constructed data set over the training rows with its
    query boundaries bound: (rows, data set, the program's parameters)."""
    data = config["data"]
    gen = load_module("generators", config["generator"])
    t = time.perf_counter()
    rows = gen.make(seed, data, int(config["train_rows"]), holdout_queries)
    qb, hqb = rows["query_boundaries"], rows["holdout_query_boundaries"]
    say(f"setup: rows made in {time.perf_counter() - t:.2f} s "
        f"({rows['codes'].shape[1]} x {rows['codes'].shape[0]} in "
        f"{len(qb) - 1} queries of {int(np.diff(qb).min())} .. "
        f"{int(np.diff(qb).max())} of population {data['population_seed']}, "
        f"grade shares {np.round(np.bincount(rows['label'].astype(int)) / len(rows['label']), 4).tolist()}; "
        f"{rows['holdout_codes'].shape[1]} hold-out rows in {len(hqb) - 1} "
        f"queries of seed {seed})")
    params = dict(config["params"])
    t = time.perf_counter()
    ds = build_dataset(lgb, rows["codes"], rows["label"], params,
                       gen.column_names(data))
    ds._query_boundaries = np.asarray(qb, np.int64)
    say(f"setup: data set in {time.perf_counter() - t:.2f} s")
    return rows, ds, params


def run(ctx) -> Dict[str, Any]:
    """Drive one run of a ranking cell; returns the result's fields (those
    of `jobs.train.run`)."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.telemetry.recorder import install_compile_listener

    cell, config, say = ctx.cell, ctx.config, ctx.say
    traffic = cell["traffic_params"]
    annotate = tracelib.annotation if ctx.trace else tracelib.no_annotation

    record = ProgramRecord() if ctx.trace else None
    rows, ds, params = make_inputs(lgb, config, ctx.seed,
                                   int(traffic["holdout_queries"]), say)
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
    hqb = rows["holdout_query_boundaries"]
    holdout_auc = query_auc(rows["holdout_label"], hold_score, hqb)
    say(f"quality: hold-out mean query AUC after {quality_rounds} rounds = "
        f"{holdout_auc:.6f}; NDCG@1,3,5 = " + ", ".join(
            f"{query_ndcg(rows['holdout_label'], hold_score, hqb, k):.6f}"
            for k in (1, 3, 5))
        + f" (scored in {time.perf_counter() - t:.2f} s)")

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
    readings = ref.follow(rows["codes"], rows["label"],
                          rows["query_boundaries"], trees, params,
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
