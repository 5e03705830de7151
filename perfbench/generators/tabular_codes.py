"""Rows of a tabular data set, generated in code space from two seeds.

A column is a small integer code `0 .. cardinality-1` (a calendar field, a
carrier id, a binned time), drawn uniformly.  The label is Bernoulli with
log-odds `scale * z(row) + offset`, where `z` is a fixed non-linear
function of the codes: one effect table per column plus products of two
tables for the listed pairs.  The tables depend on the configuration's
`function_seed` only.

The TRAINING rows are the configuration's, not the run's: they are the
stream of `population_seed`, so every run of a configuration trains on the
same rows against the same function, grows the same trees and does the
same work (NVIDIA/gbm-bench trains on one airline file).  How many
histogram passes a tree costs depends on the order its leaves split in;
rows that changed with `--seed` made the rate a property of the seed.
What `--seed` draws is the HOLD-OUT rows (and, in the reference, the nodes
that are checked): the quality is scored on rows that differ from run to
run and that no run has trained on.

Rows come in fixed chunks of `CHUNK` rows, each from its own generator
keyed by (seed, chunk index); the stream does not depend on how many
threads fill it.  Training rows are chunks `0 ..` of the population's
stream; hold-out rows are the chunks of the `--seed` stream AFTER as many
chunks as the training rows take, so a hold-out chunk is never a training
chunk, also where the two seeds are equal.

Parameters (the configuration file's `data` object):
  columns          [{"name", "cardinality", "effect": "smooth"|"iid", "weight"}]
  pairs            [[column, column, weight], ...]
  function_seed    seed of the effect tables
  population_seed  seed of the training rows
  scale, offset    log-odds = scale * z + offset
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Tuple

import numpy as np

CHUNK = 1 << 20


def effect_tables(data: dict) -> Tuple[List[np.ndarray], List[tuple]]:
    """Per-column effect tables [cardinality] f32 and the pair terms
    (i, j, table_i, table_j), all from `function_seed`.  Each table has
    mean 0 and unit variance under uniform codes, times its weight."""
    cols = data["columns"]
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(data["function_seed"]), 0x7AB1E])))

    def table(card: int, kind: str) -> np.ndarray:
        if kind == "smooth":
            x = np.arange(card) / max(card - 1, 1)
            t = sum(rng.normal() / (k + 1) *
                    np.sin(2 * np.pi * ((k + 1) * x / 2 + rng.random()))
                    for k in range(4))
        elif kind == "iid":
            t = rng.normal(size=card)
        else:
            raise ValueError(f"unknown effect kind {kind!r}")
        t = t - t.mean()
        sd = t.std()
        return (t / sd if sd > 0 else t).astype(np.float64)

    main = [float(c.get("weight", 1.0)) * table(int(c["cardinality"]),
                                                c.get("effect", "smooth"))
            for c in cols]
    names = [c["name"] for c in cols]
    pairs = []
    for a, b, w in data.get("pairs", []):
        i, j = names.index(a), names.index(b)
        pairs.append((i, j,
                      float(w) * table(int(cols[i]["cardinality"]), "smooth"),
                      table(int(cols[j]["cardinality"]), "smooth")))
    return ([t.astype(np.float32) for t in main],
            [(i, j, ti.astype(np.float32), tj.astype(np.float32))
             for i, j, ti, tj in pairs])


def log_odds(codes: np.ndarray, data: dict, tables=None) -> np.ndarray:
    """`scale * z + offset` of rows given as codes [F, n]; f32 [n]."""
    main, pairs = tables if tables is not None else effect_tables(data)
    z = np.zeros(codes.shape[1], np.float32)
    for f, t in enumerate(main):
        z += t[codes[f]]
    for i, j, ti, tj in pairs:
        z += ti[codes[i]] * tj[codes[j]]
    z *= np.float32(data["scale"])
    z += np.float32(data["offset"])
    return z


def _fill_chunk(seed: int, chunk: int, codes: np.ndarray, label: np.ndarray,
                data: dict, tables) -> None:
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), int(chunk)])))
    n = codes.shape[1]
    for f, c in enumerate(data["columns"]):
        codes[f] = rng.integers(0, int(c["cardinality"]), size=n,
                                dtype=np.uint8)
    u = rng.random(n, dtype=np.float32)
    z = log_odds(codes, data, tables)
    np.negative(z, out=z)
    np.exp(z, out=z)
    z += 1.0
    np.reciprocal(z, out=z)
    label[:] = u < z


def generate(seed: int, data: dict, first_row: int, n_rows: int,
             threads: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Rows `first_row .. first_row + n_rows` of the seed's stream:
    codes [F, n_rows] uint8 (feature-major, C order) and labels [n_rows]
    f32 in {0, 1}.  `first_row` is a multiple of CHUNK."""
    if first_row % CHUNK:
        raise ValueError(f"first_row must be a multiple of {CHUNK}")
    cols = data["columns"]
    if any(not 1 <= int(c["cardinality"]) <= 256 for c in cols):
        raise ValueError("cardinalities must fit a uint8 code")
    tables = effect_tables(data)
    codes = np.empty((len(cols), n_rows), np.uint8)
    label = np.empty(n_rows, np.float32)
    c0 = first_row // CHUNK
    spans = [(c0 + k, lo, min(lo + CHUNK, n_rows))
             for k, lo in enumerate(range(0, n_rows, CHUNK))]

    def work(span):
        chunk, lo, hi = span
        buf = np.empty((len(cols), hi - lo), np.uint8)
        lab = np.empty(hi - lo, np.float32)
        _fill_chunk(seed, chunk, buf, lab, data, tables)
        codes[:, lo:hi] = buf
        label[lo:hi] = lab

    threads = threads or max(1, min(12, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(work, spans))
    return codes, label


def make(seed: int, data: dict, train_rows: int, holdout_rows: int
         ) -> Dict[str, np.ndarray]:
    """The cell's training rows, from `data["population_seed"]`, and the
    run's hold-out rows, from `seed`."""
    hold_first = -(-train_rows // CHUNK) * CHUNK
    codes, label = generate(int(data["population_seed"]), data, 0, train_rows)
    hcodes, hlabel = generate(seed, data, hold_first, holdout_rows)
    return {"codes": codes, "label": label,
            "holdout_codes": hcodes, "holdout_label": hlabel}
