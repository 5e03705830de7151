"""Frozen wave-grower models for the strict tail's schedule (ISSUE 26).

The strict tail of `ops/grow_wave.py` was rescheduled (speculated
smaller-child histograms, a split whose child histogram is cached costs
no pass) under the promise that the trees do not change.  The models in
`wave_tail_goldens.json` were dumped by this script from the commit
BEFORE that change (d3c9bb9, the one-pass-per-tail-split schedule);
`tests/test_wave.py::TestSpeculativeTail::test_golden_models` imports
`CASES` / `train_case` from here and holds the current grower to them
byte for byte.

Regenerate (only ever from a commit whose trees are the intended ones):

    JAX_PLATFORMS=cpu python tests/data/make_wave_tail_goldens.py
"""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDENS = os.path.join(HERE, "wave_tail_goldens.json")
ROUNDS = 3
ROWS = 1500


def numerical(seed=11):
    rng = np.random.RandomState(seed)
    X = rng.randn(ROWS, 6).astype(np.float32)
    score = X[:, 0] + X[:, 1] * X[:, 2] + 0.5 * np.sin(3 * X[:, 3])
    y = (score + 0.5 * rng.randn(ROWS) > 0).astype(np.float64)
    return X, y, {}


def categorical(seed=12):
    rng = np.random.RandomState(seed)
    cat_a = rng.randint(0, 12, ROWS)
    cat_b = rng.randint(0, 5, ROWS)
    num = rng.randn(ROWS, 2).astype(np.float32)
    score = (cat_a % 3 == 0) + 0.7 * (cat_b == 2) + 0.5 * num[:, 0] \
        - 0.3 * num[:, 1] * (cat_a > 6)
    y = (score + 0.4 * rng.randn(ROWS) > 0.5).astype(np.float64)
    X = np.column_stack([cat_a, cat_b, num]).astype(np.float32)
    return X, y, {"categorical_feature": [0, 1]}


def missing(seed=13):
    X, y, kw = numerical(seed)
    rng = np.random.RandomState(seed + 100)
    X = X.copy()
    # missing at random in two columns, missing-by-value in a third
    X[rng.rand(ROWS) < 0.25, 0] = np.nan
    X[rng.rand(ROWS) < 0.10, 2] = np.nan
    X[X[:, 3] > 1.0, 3] = np.nan
    return X, y, kw


DATA = {"numerical": numerical, "categorical": categorical,
        "missing": missing}

# (case id, data set, num_leaves, tpu_wave_strict_tail, extra params)
CASES = [(f"{d}-l{leaves}-t{tail}", d, leaves, tail, {})
         for d in DATA for leaves in (8, 31, 63) for tail in (4, 16, -1)]
# the benchmark cell's growth settings through the Pallas kernel
# (interpret mode): the slot a leaf's rows ride in must not move its sums
CASES.append(("numerical-l31-t16-pallas-w8", "numerical", 31, 16,
              {"hist_impl": "pallas", "hist_interpret": True,
               "tpu_wave_width": 8}))
# the same through a categorical column's many-vs-rest search
CASES.append(("categorical-l31-t16-pallas-w8", "categorical", 31, 16,
              {"hist_impl": "pallas", "hist_interpret": True,
               "tpu_wave_width": 8}))


def train_case(data, leaves, tail, extra):
    """The case's model text, parameter echo lines left out (they name
    the growth knobs, not the trees)."""
    import lightgbm_tpu as lgb
    X, y, kw = DATA[data]()
    bst = lgb.train({"objective": "binary", "num_leaves": leaves,
                     "verbosity": -1, "tree_grow_policy": "wave",
                     "tpu_wave_strict_tail": tail,
                     "tpu_wave_gain_ratio": 0, "tpu_wave_overgrow": 0,
                     "min_data_in_leaf": 5, "seed": 7, **extra},
                    lgb.Dataset(X, label=y, **kw), num_boost_round=ROUNDS)
    assert bst._grow_policy == "wave"
    return "\n".join(ln for ln in bst.model_to_string().splitlines()
                     if not ln.startswith("["))


def main():
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    out = {}
    for cid, data, leaves, tail, extra in CASES:
        out[cid] = train_case(data, leaves, tail, extra)
        print(cid, len(out[cid]), "bytes", flush=True)
    with open(GOLDENS, "w") as fh:
        json.dump(out, fh, indent=0, sort_keys=True)
        fh.write("\n")


if __name__ == "__main__":
    main()
