"""Command-line entry point: `python -m lightgbm_tpu config=train.conf`.

TPU-native re-design of the reference's CLI Application
(ref: src/main.cpp `main`; src/application/application.cpp
`Application::{LoadData,InitTrain,Train,Predict,ConvertModel}`; config-file
`key=value` parsing in src/io/config.cpp `Config::Set`).

Accepts the same `key=value` argument and conf-file syntax: a `config=` arg
names a conf file whose lines are `key = value` (with `#` comments);
command-line pairs override file pairs.  Tasks: train, predict, refit.
Data files are CSV/TSV/LibSVM, auto-detected like src/io/parser.cpp
`Parser::CreateParser`.
"""
from __future__ import annotations

import sys
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .basic import Dataset
from .booster import Booster
from .engine import train as engine_train
from .utils import log
from .utils.config import Config
from .utils.log import LightGBMError


def parse_conf_file(path: str) -> Dict[str, str]:
    """ref: Application config-file parsing (key=value lines, # comments)."""
    out: Dict[str, str] = {}
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line or "=" not in line:
                continue
            k, v = line.split("=", 1)
            out[k.strip()] = v.strip()
    return out


def parse_args(argv: List[str]) -> Dict[str, str]:
    params: Dict[str, str] = {}
    for arg in argv:
        if "=" not in arg:
            raise LightGBMError(f"Unknown argument format: {arg!r} "
                                f"(expect key=value)")
        k, v = arg.split("=", 1)
        params[k.strip()] = v.strip()
    if "config" in params and params["config"]:
        file_params = parse_conf_file(params["config"])
        # command-line pairs override conf-file pairs (ref: Application ctor)
        file_params.update(params)
        params = file_params
    return params


def _sniff_format(path: str) -> Tuple[str, bool]:
    """Detect csv/tsv/space/libsvm + header (ref: parser.cpp
    auto-detection).  Space is a first-class delimiter — the classic
    LibSVM layout is space-delimited, and sniffing it as one tsv token
    would silently dense-parse 'idx:val' fields as bare numbers."""
    with open(path) as f:
        first = f.readline()
    commas, tabs, spaces = (first.count(c) for c in (",", "\t", " "))
    if commas >= tabs and commas >= spaces:
        sep, fmt = ",", "csv"
    elif tabs >= spaces:
        sep, fmt = "\t", "tsv"
    else:
        sep, fmt = " ", "space"
    tokens = first.strip().split(sep)
    if any(":" in t for t in tokens[1:3] if t):
        return "libsvm", False
    def _is_num(t):
        try:
            float(t)
            return True
        except ValueError:
            return False
    has_header = not all(_is_num(t) for t in tokens if t != "")
    return fmt, has_header


def parse_column_spec(spec: str, what: str) -> Optional[int]:
    """Column-role param → index (ref: dataset_loader.cpp label_idx /
    weight_idx / group_idx resolution).  'name:' forms need header-name
    plumbing we don't do — raise with the workaround."""
    if spec == "":
        return None
    if spec.startswith("name:"):
        raise LightGBMError(
            f"{what}=name: requires header parsing; use column index "
            f"form (e.g. {what}=0)")
    return int(spec)


def column_roles(config: Config):
    """(label, weight, group, drop-list) FILE column indexes from config
    (ref: config.h + docs/Parameters.rst: `label_column` counts all file
    columns, but `weight_column`/`group_column`/`ignore_column` indexes
    "don't count the label column" — e.g. label at column_0 + weight at
    file column_1 is written `weight_column=0`).  `drop` is the sorted
    set of file columns to remove from the feature matrix — the ONE
    place that set is computed (whole-file and streaming ingest must
    drop identical columns)."""
    label = parse_column_spec(config.label_column, "label_column") or 0

    def skip_label(idx):
        return idx if idx is None or idx < label else idx + 1

    weight = skip_label(parse_column_spec(config.weight_column,
                                          "weight_column"))
    group = skip_label(parse_column_spec(config.group_column,
                                         "group_column"))
    drop = {label}
    if config.ignore_column:
        for tok in str(config.ignore_column).split(","):
            tok = tok.strip()
            if tok:
                drop.add(skip_label(parse_column_spec(tok,
                                                      "ignore_column")))
    if weight is not None:
        drop.add(weight)
    if group is not None:
        drop.add(group)
    return label, weight, group, sorted(drop)


def group_ids_to_sizes(ids: np.ndarray) -> np.ndarray:
    """Per-row query ids (contiguous) → group sizes (ref: metadata.cpp
    Metadata::SetQuery from query ids)."""
    if len(ids) == 0:
        return np.zeros(0, np.int64)
    change = np.nonzero(np.diff(ids))[0] + 1
    bounds = np.concatenate([[0], change, [len(ids)]])
    return np.diff(bounds)


def load_data_file(path: str, config: Config
                   ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Load a training/prediction text file → (X, label or None).
    Column-role extras (weight/group/ignored) via `load_data_file_full`.

    ref: src/io/parser.cpp CSVParser/TSVParser/LibSVMParser;
    label_column handling in dataset_loader.cpp.
    """
    X, y, _ = load_data_file_full(path, config)
    return X, y


def load_data_file_full(path: str, config: Config):
    """(X, label, extras) where extras holds 'weight' and 'group'
    (sizes) when weight_column/group_column are configured; ignored
    columns are dropped from X (ref: dataset_loader.cpp column roles)."""
    fmt, has_header = _sniff_format(path)
    if config.header:
        has_header = True
    from .native import parse_dense, parse_libsvm
    if fmt == "libsvm":
        try:
            data = parse_libsvm(path)  # index base auto-detected
        except ValueError:
            data = None  # malformed for the strict parser → sklearn
        if data is not None:
            return data[:, 1:].copy(), data[:, 0].copy(), {}
        from sklearn.datasets import load_svmlight_file
        X, y = load_svmlight_file(path)
        return np.asarray(X.todense(), dtype=np.float64), y, {}
    try:
        native = parse_dense(path)
    except ValueError:
        # e.g. text cells mid-file — genfromtxt maps those to NaN
        native = None
    if native is not None:
        data, native_skipped_header = native
        if (has_header or config.header) and not native_skipped_header:
            # the user declared a header the numeric sniff didn't catch
            data = data[1:]
    else:
        sep = {"tsv": "\t", "space": None}.get(fmt, ",")  # None = any ws
        data = np.genfromtxt(path, delimiter=sep,
                             skip_header=1 if has_header else 0,
                             dtype=np.float64)
    if data.ndim == 1:
        data = data.reshape(-1, 1)
    label_col, weight_col, group_col, drop = column_roles(config)
    y = data[:, label_col].copy()
    extras = {}
    if weight_col is not None:
        extras["weight"] = data[:, weight_col].copy()
    if group_col is not None:
        extras["group"] = group_ids_to_sizes(data[:, group_col])
    X = np.delete(data, drop, axis=1)
    return X, y, extras


def _snapshot_callback(freq: int, output_model: str):
    """Periodic mid-training snapshots (ref: application.cpp
    `Application::Train` — every `snapshot_freq` iterations the model so
    far is saved to `<output_model>.snapshot_iter_<n>`).  `n` counts
    TOTAL trees (`current_iteration`), so resumed runs continue the
    numbering of the run they resume.  Not `chunk_safe`: the engine must
    drive it per-iteration so each snapshot is the exact model at that
    iteration."""
    def _callback(env) -> None:
        it = env.model.current_iteration()
        if it % freq == 0:
            path = f"{output_model}.snapshot_iter_{it}"
            env.model.save_model(path)
            log.info(f"Saved snapshot to {path}")

    # BEFORE early_stopping (order 30): its EarlyStopException aborts the
    # callback chain, which would silently drop a snapshot due on the
    # stopping (or final) iteration
    _callback.order = 25  # type: ignore
    return _callback


def _compile_plan_main(argv: List[str]) -> int:
    """`compile-plan <model> [serve_tile_vmem_kb=...] [--json]`: print
    the serving compiler's tile plan — tiles, trees per tile, node
    words, palette sizes, VMEM bytes per tile and the tree permutation
    — for offline inspection without a device."""
    import json
    args = [a for a in argv if a != "--json"]
    as_json = "--json" in argv
    if not args:
        print("usage: python -m lightgbm_tpu compile-plan <model_file>"
              " [serve_tile_vmem_kb=...] [--json]", file=sys.stderr)
        return 2
    vmem = 512.0
    for a in args[1:]:
        if a.startswith("serve_tile_vmem_kb="):
            vmem = float(a.split("=", 1)[1])
        else:
            raise LightGBMError(f"unknown compile-plan arg: {a}")
    from .booster import Booster
    from .compiler import PlanNotCompilable, build_plan, plan_summary
    booster = Booster(model_file=args[0])
    try:
        plan = build_plan(booster.export_predict_arrays(),
                          tile_vmem_kb=vmem)
    except PlanNotCompilable as e:
        print(f"not compilable: {e}", file=sys.stderr)
        return 1
    s = plan_summary(plan)
    if as_json:
        print(json.dumps(s, indent=2))
        return 0
    print(f"trees: {s['trees']}  num_class: {s['num_class']}  "
          f"tiles: {s['tiles']}  tile_vmem_kb: {s['tile_vmem_kb']:g}")
    print(f"total plane bytes: {s['total_plane_bytes']}")
    ti = 0
    for b in s["buckets"]:
        for tile in b["tiles"]:
            st = s["tile_stats"][ti]
            print(f"  tile {ti}: depth={b['depth']} trees={len(tile)} "
                  f"node_words={st['nodes']} palette={st['palette']} "
                  f"vmem_bytes={st['bytes']}")
            ti += 1
    perm = s["permutation"]
    print(f"permutation: {perm if len(perm) <= 64 else perm[:64]}"
          f"{' ...' if len(perm) > 64 else ''}")
    return 0


def run(argv: List[str]) -> int:
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m lightgbm_tpu config=train.conf [key=value ...]\n"
              "tasks: train | predict | refit | convert_model\n"
              "       python -m lightgbm_tpu telemetry-report <events.jsonl>\n"
              "       python -m lightgbm_tpu telemetry diff <A.json> <B.json>"
              " [--warn-timings]\n"
              "       python -m lightgbm_tpu lint [--race]"
              " [--format json|text] [--update-baseline]\n"
              "       python -m lightgbm_tpu serve model=<model_file>"
              " [serve_port=...] [serve_trace=...]\n"
              "       python -m lightgbm_tpu fleet model=<model_file>"
              " store=<datastore_dir> [fleet_retrain_rows=...]\n"
              "       python -m lightgbm_tpu lineage <events.jsonl>"
              " [model=default] [n=5] [--json]\n"
              "       python -m lightgbm_tpu top [url=http://host:port]"
              " [n=8] [--json]\n"
              "       python -m lightgbm_tpu timeline <spool_dir>"
              " [--trace out.json] [--json]\n"
              "       python -m lightgbm_tpu memory"
              " [url | spool_dir] [--json]\n"
              "       python -m lightgbm_tpu compile-plan <model_file>"
              " [serve_tile_vmem_kb=...] [--json]\n"
              "       python -m lightgbm_tpu soak <scenario>"
              " [--minutes N] [--capacity] [--json]",
              file=sys.stderr)
        return 0
    if argv[0] == "compile-plan":
        # offline serving-compiler plan inspection (compiler/plan.py is
        # numpy-only, so this never touches a device)
        return _compile_plan_main(argv[1:])
    if argv[0] == "soak":
        # production soak harness (soak/): closed-loop multi-tenant
        # traffic + chaos scenario + byte-oracle/SLO invariants
        from .soak import main as soak_main
        return soak_main(argv[1:])
    if argv[0] == "serve":
        # prediction-serving HTTP frontend (serving/http.py): stdlib
        # server over the micro-batched device runtime
        from .serving.http import main as serve_main
        return serve_main(argv[1:])
    if argv[0] == "fleet":
        # continuous-training fleet (fleet/daemon.py): HTTP serving +
        # the datastore-tailing trainer daemon in one process
        from .fleet.daemon import main as fleet_main
        return fleet_main(argv[1:])
    if argv[0] == "lineage":
        # model-lineage report (telemetry/ledger.py): reconstruct the
        # serving model's ancestry + rejections from a JSONL sink file
        from .telemetry.ledger import main as lineage_main
        return lineage_main(argv[1:])
    if argv[0] == "top":
        # one-shot fleet ops report (telemetry/ops.py): fetches
        # /debug/fleet from a running serving process
        from .telemetry.ops import main as top_main
        return top_main(argv[1:])
    if argv[0] == "timeline":
        # cross-process spool aggregation (telemetry/spool.py): merged
        # fleet timeline + optional Chrome-trace export
        from .telemetry.spool import main as timeline_main
        return timeline_main(argv[1:])
    if argv[0] == "memory":
        # attributed device-memory report (telemetry/memledger.py):
        # /debug/memory from a serving process or a spool-dir roll-up
        from .telemetry.memledger import main as memory_main
        return memory_main(argv[1:])
    if argv[0] == "telemetry-report":
        # subcommand, not a key=value task — handled before parse_args
        from .telemetry.report import main as report_main
        return report_main(argv[1:])
    if argv[0] == "telemetry":
        # `telemetry diff A B` (regression sentinel) / `telemetry report F`
        action = argv[1] if len(argv) > 1 else ""
        if action == "diff":
            from .telemetry.diff import main as diff_main
            return diff_main(argv[2:])
        if action == "report":
            from .telemetry.report import main as report_main
            return report_main(argv[2:])
        print("usage: python -m lightgbm_tpu telemetry "
              "{diff <A.json> <B.json> | report <events.jsonl>}",
              file=sys.stderr)
        return 2
    if argv[0] == "lint":
        # graft-lint static analysis (stdlib-only, no jax backend use)
        from .analysis.cli import main as lint_main
        return lint_main(argv[1:])
    params = parse_args(argv)
    config = Config(params)
    task = config.task

    if task == "train":
        if not config.data:
            raise LightGBMError("No training data file (set data=...)")
        # the PATH goes straight into Dataset: construct() applies the
        # column roles itself and, under two_round=true, streams the file
        # without materializing the raw float64 matrix — loading it here
        # would defeat exactly that (CLI is two_round's primary interface)
        train_set = Dataset(config.data, params=dict(params))
        valid_sets = []
        valid_names = []
        for i, vf in enumerate(config.valid):
            valid_sets.append(train_set.create_valid(vf))
            valid_names.append(f"valid_{i}")
        from .callback import log_evaluation
        callbacks = [log_evaluation(max(config.metric_freq, 1))]
        if config.snapshot_freq > 0:
            callbacks.append(_snapshot_callback(config.snapshot_freq,
                                                config.output_model))
        booster = engine_train(
            dict(params), train_set, num_boost_round=config.num_iterations,
            valid_sets=valid_sets or None, valid_names=valid_names or None,
            # continued training: a killed job resumes from its last
            # snapshot via input_model= (ref: application.cpp InitTrain —
            # task=train + input_model loads then continues boosting)
            init_model=config.input_model or None,
            callbacks=callbacks)
        booster.save_model(config.output_model)
        log.info(f"Finished training; model saved to {config.output_model}")
        return 0

    if task in ("predict", "prediction", "test"):
        if not config.input_model:
            raise LightGBMError("No input model (set input_model=...)")
        booster = Booster(model_file=config.input_model)
        X, _ = load_data_file(config.data, config)
        out = booster.predict(
            X, raw_score=config.predict_raw_score,
            pred_leaf=config.predict_leaf_index,
            pred_contrib=config.predict_contrib,
            start_iteration=config.start_iteration_predict,
            num_iteration=(None if config.num_iteration_predict < 0
                           else config.num_iteration_predict))
        np.savetxt(config.output_result, np.atleast_2d(out.T).T, fmt="%.10g",
                   delimiter="\t")
        log.info(f"Finished prediction; results saved to "
                 f"{config.output_result}")
        return 0

    if task == "convert_model":
        # ref: application.cpp task=convert_model → Tree::ToIfElse
        if not config.input_model:
            raise LightGBMError("task=convert_model requires "
                                "input_model=...")
        from .convert import convert_model
        booster = Booster(model_file=config.input_model)
        convert_model(booster, config.convert_model,
                      config.convert_model_language)
        return 0

    if task == "refit":
        # ref: application.cpp task=refit (input_model + data → output_model)
        if not config.input_model:
            raise LightGBMError("task=refit requires input_model=...")
        if not config.data:
            raise LightGBMError("task=refit requires data=...")
        booster = Booster(model_file=config.input_model,
                          params=dict(params))
        X, y = load_data_file(config.data, config)
        refit_bst = booster.refit(X, y,
                                  decay_rate=config.refit_decay_rate)
        out = config.output_model or "LightGBM_model.txt"
        refit_bst.save_model(out)
        log.info(f"Finished refit; model saved to {out}")
        return 0
    raise LightGBMError(f"Unknown task: {task}")


def main() -> None:
    # the process entry point places the persistent compile cache (a
    # process-global JAX setting); `run` stays free of it for in-process
    # callers
    from .utils.env import setup_compile_cache
    setup_compile_cache()
    sys.exit(run(sys.argv[1:]))
