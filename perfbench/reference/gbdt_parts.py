"""The plain reference of `gbdt.py`, over rows taken in PARTS.

`gbdt.follow` puts every row on one device (`jnp.asarray(codes)`): at
201M rows x 67 columns that is 13.5 GB of codes beside 20 B a row of
score, gradients, leaf id and label, more than a chip holds.  This module
follows the same trees with the same arithmetic — `gbdt`'s own functions:
plain `jax.numpy`, float32 at `highest`, compensated sums, nothing of the
program imported — but cuts the rows into contiguous parts that each fit a
device beside their per-row state:

  per part   gradients at the part's own scores, the rows routed through
             the tree, the per-leaf sums and the checked nodes'
             histograms, exactly as `gbdt.follow` computes them over all
             rows (float32, compensated across the part's blocks); the
             scores stay with their part
  across     the parts' leaf sums, counts and histograms are added on the
             host in float64, in part order

It places one part on each local device where there are several, with
plain `jax.device_put`, and knows nothing of meshes, shards or
`shard_map`: where the program under test splits its rows is not the
reference's business.  On one part every number is `gbdt.follow`'s bit
for bit (`tests/test_criteo67_cell.py`).

`tree_from_dump`, `follow(codes, label, trees, params, n_check=, seed=,
dtype=, update_scores=)` keep `gbdt.py`'s signatures, `RoundReading` and
`TreeArrays` are its types: `jobs/train.py`, `check.py` and `readings.py`
take this module as they take that one.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import gbdt
from .gbdt import (BLOCK, RoundReading, TreeArrays,  # noqa: F401
                   tree_from_dump)

# bytes of codes and per-row state (score, gradient, hessian, leaf id,
# label, one temporary: 24 B a row) one part may take: a 16 GB device
# holds it beside a histogram block's temporaries
PART_BYTES = 5 << 30


def part_bounds(n_rows: int, n_columns: int, parts: Optional[int] = None,
                n_devices: int = 1) -> List[tuple]:
    """[(first row, end row), ...]: as few parts as keep each within
    `PART_BYTES`, a whole number a device where there is more than one
    part, each but the last a whole number of histogram blocks.  `parts`
    forces their number."""
    if parts is None:
        parts = max(1, -(-n_rows * (n_columns + 24) // PART_BYTES))
        if parts > 1:
            parts = -(-parts // n_devices) * n_devices
    rows = -(-n_rows // parts)
    rows = -(-rows // BLOCK) * BLOCK
    return [(lo, min(lo + rows, n_rows)) for lo in range(0, n_rows, rows)]


def follow(codes, label, trees: List[TreeArrays], params: dict,
           dtype=jnp.float32, n_check: int = 1 << 30, seed: int = 0,
           update_scores: bool = True, parts: Optional[int] = None
           ) -> List[RoundReading]:
    """`gbdt.follow` (its docstring) over row parts.  `codes` [F, N] uint8
    and `label` [N] f32 are host arrays; each part's rows and per-row
    state stay on that part's device."""
    codes = np.asarray(codes)
    label = np.asarray(label)
    devices = jax.local_devices()
    bounds = part_bounds(codes.shape[1], codes.shape[0], parts, len(devices))
    on = [devices[i % len(devices)] for i in range(len(bounds))]
    part_codes = [jax.device_put(np.ascontiguousarray(codes[:, lo:hi]), d)
                  for (lo, hi), d in zip(bounds, on)]
    part_label = [jax.device_put(label[lo:hi], d)
                  for (lo, hi), d in zip(bounds, on)]
    # the mean of all labels from the parts' float32 means, weighted by
    # their rows in float64 (one part: that part's mean, untouched)
    means = [float(jnp.mean(lab.astype(jnp.float32))) for lab in part_label]
    rows = [hi - lo for lo, hi in bounds]
    bias = gbdt.init_score(
        sum(m * n for m, n in zip(means, rows)) / sum(rows))
    scores = [jnp.full(lab.shape, bias, jnp.float32, device=d)
              for lab, d in zip(part_label, on)]
    out = []
    for r, tree in enumerate(trees):
        nodes = gbdt.nodes_to_check(tree, n_check, seed, r)
        member = gbdt.leaves_under(tree)[nodes].astype(np.float32)
        leaves, pending = [], []
        for c, lab, score, d in zip(part_codes, part_label, scores, on):
            # every part is dispatched before any is read back, so the
            # devices work side by side
            g, h = gbdt.grad_hess(score, lab, dtype)
            leaf = gbdt.route(c, tree)
            sums, count = gbdt.leaf_sums(leaf, g, h, tree.num_leaves, dtype)
            hists = gbdt.node_histograms(
                c, leaf, g, h, jax.device_put(member, d), dtype) \
                if len(nodes) else None
            leaves.append(leaf)
            pending.append((sums, count, hists))
        got = jax.device_get(pending)
        if len(got) == 1:
            sums, count, hists = got[0]
            gh, cnt = hists if hists is not None else (np.zeros((0,)),) * 2
        else:
            sums = sum(np.asarray(s, np.float64) for s, _, _ in got)
            count = sum(np.asarray(c, np.float64) for _, c, _ in got)
            gh = cnt = np.zeros((0,))
            if len(nodes):
                gh = sum(np.asarray(hs[0], np.float64) for _, _, hs in got)
                cnt = sum(np.asarray(hs[1], np.float64) for _, _, hs in got)
        reading = gbdt.read_round(sums, count, gh, cnt, nodes, params,
                                  bias if r == 0 else 0.0)
        out.append(reading)
        if update_scores:
            scores = [gbdt.add_leaf_values(
                score, leaf, jax.device_put(
                    np.asarray(reading.leaf_step, np.float32), d))
                for score, leaf, d in zip(scores, leaves, on)]
    return out
