"""Query-grouped rows of a learning-to-rank set, generated in code space.

A row is one candidate document of one query: `F` columns of small integer
codes (a feature already binned), drawn uniformly, and a graded label.
Queries are contiguous runs of rows.  The label of a document is its
grade WITHIN its query: a latent score `scale * z(codes) + noise` is ranked
inside the query and the top `label_shares[4]` of the documents get grade
4, the next `label_shares[3]` grade 3, ... the rest grade 0, so every
query of at least 50 documents holds every grade.  `z` is a fixed function
of the informative columns' codes (`tabular_codes.effect_tables`: one
effect table a column plus products of two tables for some pairs), from
`function_seed` alone.

Query lengths are log-normal (`length_sigma`), clipped to
[`length_min`, `length_max`] and scaled so that `queries` lengths sum to
the configuration's `train_rows`.  The TRAINING rows are the stream of
`population_seed` (every run trains on the same queries, as
`tabular_codes` does); `--seed` draws the cell's hold-out queries, of the
same length law (the training scale), after the training chunks, so a
hold-out row is never a training row.

Rows come in chunks of `tabular_codes.CHUNK` rows, each from a generator
keyed by (seed, chunk index); the stream does not depend on how many
threads fill it.

Parameters (the configuration file's `data` object):
  columns          {"count": F, "cardinality": codes of a wide column,
                    "narrow": [codes of each narrow column], "narrow_every":
                    every k-th column (from k-1) is the next narrow one}
  informative      how many columns (evenly spread) carry an effect
  pairs            how many pair products, each of two informative columns
  function_seed    seed of the columns' effects and the pairs
  population_seed  seed of the training rows
  queries, length_sigma, length_min, length_max
  label_shares     shares of grades 0 .. 4 inside a query
  scale, noise     latent = scale * z + noise * N(0, 1)
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

from .tabular_codes import CHUNK, effect_tables, log_odds


def cardinalities(data: dict) -> List[int]:
    """Codes of each column, in column order."""
    spec = data["columns"]
    narrow = list(spec["narrow"])
    every = int(spec["narrow_every"])
    out = []
    for f in range(int(spec["count"])):
        if (f + 1) % every == 0 and narrow:
            out.append(int(narrow.pop(0)))
        else:
            out.append(int(spec["cardinality"]))
    return out


def column_names(data: dict) -> List[str]:
    return [f"f{f:03d}" for f in range(int(data["columns"]["count"]))]


def informative(data: dict) -> np.ndarray:
    """The columns that carry an effect, evenly spread."""
    n = int(data["columns"]["count"])
    return np.unique(np.linspace(0, n - 1, int(data["informative"]))
                     .round().astype(np.int64))


def _effects(data: dict) -> dict:
    """`tabular_codes`' parameters over the informative columns: their
    cardinalities, alternating smooth and iid effects of weights drawn
    from `function_seed`, and the pairs."""
    card = cardinalities(data)
    cols = informative(data)
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(data["function_seed"]), 0x1742])))
    weight = np.exp(rng.normal(-0.5, 0.7, size=len(cols)))
    columns = [{"name": str(c), "cardinality": card[c],
                "effect": "smooth" if k % 2 == 0 else "iid",
                "weight": float(w)}
               for k, (c, w) in enumerate(zip(cols, weight))]
    pick = rng.choice(len(cols), size=(int(data["pairs"]), 2), replace=True)
    pairs = [[str(cols[a]), str(cols[b]), 0.5] for a, b in pick if a != b]
    return {"columns": columns, "pairs": pairs,
            "function_seed": int(data["function_seed"]),
            "scale": float(data["scale"]), "offset": 0.0}


def length_scale(data: dict, raw: np.ndarray, total: int) -> float:
    """The factor c with sum(clip(c * raw)) = total (bisection)."""
    lo, hi = 1e-3, 1e7
    for _ in range(200):
        mid = (lo * hi) ** 0.5
        got = np.clip(mid * raw, data["length_min"], data["length_max"]).sum()
        lo, hi = (mid, hi) if got < total else (lo, mid)
    return (lo * hi) ** 0.5


def _raw_lengths(seed: int, n: int, data: dict, stream: int) -> np.ndarray:
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), 0x5EED, stream])))
    return np.exp(float(data["length_sigma"]) * rng.normal(size=n))


def _integer_lengths(x: np.ndarray, data: dict, total: int = 0
                     ) -> np.ndarray:
    """Whole lengths within the clip; with `total`, summing to it (the
    remainder goes one a query to the largest fractional parts)."""
    x = np.clip(x, data["length_min"], data["length_max"])
    n = np.floor(x).astype(np.int64)
    if total:
        short = int(total - n.sum())
        room = np.flatnonzero(n < int(data["length_max"]))
        take = room[np.argsort(-(x - n)[room], kind="stable")][:short]
        n[take] += 1
        if n.sum() != total:
            raise ValueError("query lengths cannot reach the row count")
    return n


def query_lengths(data: dict, train_rows: int):
    """(the training queries' lengths summing to `train_rows`, the
    length law's scale)."""
    raw = _raw_lengths(data["population_seed"], int(data["queries"]), data,
                       0)
    c = length_scale(data, raw, train_rows)
    return _integer_lengths(c * raw, data, train_rows), c


def _codes(seed: int, first_row: int, n_rows: int, card: List[int],
           threads: int) -> np.ndarray:
    """Uniform codes [F, n_rows] of rows `first_row ..` of the seed's
    stream (`first_row` a multiple of CHUNK)."""
    codes = np.empty((len(card), n_rows), np.uint8)
    c0 = first_row // CHUNK
    spans = [(c0 + k, lo, min(lo + CHUNK, n_rows))
             for k, lo in enumerate(range(0, n_rows, CHUNK))]

    def work(span):
        chunk, lo, hi = span
        rng = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([int(seed), int(chunk)])))
        for f, k in enumerate(card):
            codes[f, lo:hi] = rng.integers(0, k, size=hi - lo,
                                           dtype=np.uint8)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(work, spans))
    return codes


def _labels(seed: int, first_row: int, codes: np.ndarray,
            lengths: np.ndarray, data: dict, threads: int) -> np.ndarray:
    """Grades 0 .. 4 [n] f32 by the within-query quantile of the latent."""
    eff = _effects(data)
    tables = effect_tables(eff)
    cols = informative(data)
    n = codes.shape[1]
    latent = np.empty(n, np.float32)
    spans = [(first_row // CHUNK + k, lo, min(lo + CHUNK, n))
             for k, lo in enumerate(range(0, n, CHUNK))]

    def work(span):
        chunk, lo, hi = span
        rng = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([int(seed), int(chunk), 0x7A7E])))
        latent[lo:hi] = log_odds(codes[cols, lo:hi], eff, tables) \
            + np.float32(data["noise"]) * rng.standard_normal(
                hi - lo, dtype=np.float32)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(work, spans))
    cut = np.cumsum(data["label_shares"])[:-1]
    label = np.empty(n, np.float32)
    bounds = np.concatenate([[0], np.cumsum(lengths)])

    def grade(q):
        lo, hi = bounds[q], bounds[q + 1]
        rank = np.empty(hi - lo, np.int64)
        rank[np.argsort(latent[lo:hi], kind="stable")] = np.arange(hi - lo)
        label[lo:hi] = np.searchsorted(cut, (rank + 0.5) / (hi - lo))

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(grade, range(len(lengths))))
    return label


def make(seed: int, data: dict, train_rows: int, holdout_queries: int
         ) -> Dict[str, np.ndarray]:
    """The cell's training rows and query boundaries, from
    `data["population_seed"]`, and the run's `holdout_queries` hold-out
    queries' rows and boundaries, from `seed`."""
    threads = max(1, min(12, (os.cpu_count() or 2) - 1))
    card = cardinalities(data)
    lengths, c = query_lengths(data, train_rows)
    pop = int(data["population_seed"])
    codes = _codes(pop, 0, train_rows, card, threads)
    label = _labels(pop, 0, codes, lengths, data, threads)
    hold_lengths = _integer_lengths(
        c * _raw_lengths(seed, int(holdout_queries), data, 1), data)
    hold_first = -(-train_rows // CHUNK) * CHUNK
    hold_rows = int(hold_lengths.sum())
    hcodes = _codes(seed, hold_first, hold_rows, card, threads)
    hlabel = _labels(seed, hold_first, hcodes, hold_lengths, data, threads)
    return {"codes": codes, "label": label,
            "query_boundaries": np.concatenate([[0], np.cumsum(lengths)]),
            "holdout_codes": hcodes, "holdout_label": hlabel,
            "holdout_query_boundaries": np.concatenate(
                [[0], np.cumsum(hold_lengths)])}
