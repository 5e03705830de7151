"""The row generator: seeded, chunked, hold-out apart, cardinalities kept;
the training rows are the configuration's and the hold-out rows the run's."""
import hashlib

import numpy as np
import pytest

from perfbench import manifest
from perfbench.generators import tabular_codes as gen

SEED = 2 ** 31 + 12345
DATA = dict(manifest.config("airline13-l31")["data"], population_seed=SEED)
N = gen.CHUNK + 4096          # one whole chunk and a part of the next

# what `make(SEED, ...)` gave at PR 26, when one seed drew all the rows
STREAM_AT_PR26 = {
    "codes": "4be0863ae855b73e51c7a7f9fdcfb8300edf6a78a382f3b7e63ea49c6028e0a6",
    "label": "de4679b6b453afd8aa66933bf9c899e27c0f60d2bf68b261369f9d026083e4cd",
    "holdout_codes":
        "6752c98602921d3ad796fc82f9153b14568044639ced78c686f07cb660e5ad75",
    "holdout_label":
        "cc4113258d488752a09f9c78402c4df4f63f39a8aa37da15ae5639b7a7025881"}


@pytest.fixture(scope="module")
def rows():
    """The run whose `--seed` is the population's."""
    return gen.make(SEED, DATA, N, 8192)


def test_same_seed_same_rows(rows):
    again = gen.make(SEED, DATA, N, 8192)
    for k in rows:
        assert np.array_equal(rows[k], again[k]), k


def test_other_seed_other_rows(rows):
    other = gen.make(SEED + 1, DATA, N, 8192)
    assert not np.array_equal(rows["holdout_codes"], other["holdout_codes"])
    assert not np.array_equal(rows["holdout_label"], other["holdout_label"])


def test_other_seed_same_training_rows(rows):
    """`--seed` draws the hold-out only: every run trains on the same bytes."""
    for seed in (SEED + 1, 7, 2 ** 31 + 2 ** 30):
        other = gen.make(seed, DATA, N, 8192)
        assert other["codes"].tobytes() == rows["codes"].tobytes()
        assert other["label"].tobytes() == rows["label"].tobytes()


def test_other_population_other_training_rows_same_holdout(rows):
    other = gen.make(SEED, dict(DATA, population_seed=SEED + 1), N, 8192)
    assert not np.array_equal(rows["codes"], other["codes"])
    assert not np.array_equal(rows["label"], other["label"])
    assert np.array_equal(rows["holdout_codes"], other["holdout_codes"])
    assert np.array_equal(rows["holdout_label"], other["holdout_label"])


@pytest.mark.parametrize("part", sorted(STREAM_AT_PR26))
def test_the_population_is_the_stream_its_seed_always_gave(rows, part):
    """`make(seed=population_seed)` is byte for byte what `make(seed)` was
    before the two seeds were told apart: a cell whose population is X
    measures the program on the rows that `--seed X` used to give."""
    assert hashlib.sha256(rows[part].tobytes()).hexdigest() \
        == STREAM_AT_PR26[part]


def test_a_config_without_a_population_is_no_fallback_to_the_seed():
    data = {k: v for k, v in DATA.items() if k != "population_seed"}
    with pytest.raises(KeyError, match="population_seed"):
        gen.make(SEED, data, N, 8192)


def test_threads_do_not_change_the_stream():
    a = gen.generate(5, DATA, 0, N, threads=1)
    b = gen.generate(5, DATA, 0, N, threads=5)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_holdout_rows_are_chunks_after_the_training_rows(rows):
    first = -(-N // gen.CHUNK) * gen.CHUNK
    codes, label = gen.generate(SEED, DATA, first, 8192)
    assert np.array_equal(codes, rows["holdout_codes"])
    assert np.array_equal(label, rows["holdout_label"])
    # and they are not a copy of any training rows of the same length
    assert not np.array_equal(rows["holdout_codes"], rows["codes"][:, :8192])


def test_holdout_chunks_are_no_training_chunks_when_the_seeds_are_equal(rows):
    """Here `--seed` IS the population's seed: the hold-out is still the
    chunk after the training rows', and the head of no training chunk."""
    assert DATA["population_seed"] == SEED
    n_train_chunks = -(-N // gen.CHUNK)
    for chunk in range(n_train_chunks):
        lo = chunk * gen.CHUNK
        n = min(8192, N - lo)
        assert not np.array_equal(rows["holdout_codes"][:, :n],
                                  rows["codes"][:, lo:lo + n]), chunk
    # the population's own chunk at the hold-out's place is the hold-out:
    # that chunk is beyond the training rows, so it is never trained on
    codes, _ = gen.generate(SEED, DATA, n_train_chunks * gen.CHUNK, 8192)
    assert np.array_equal(codes, rows["holdout_codes"])


def test_layout_and_cardinalities(rows):
    codes = rows["codes"]
    assert codes.dtype == np.uint8 and codes.flags.c_contiguous
    assert codes.shape == (len(DATA["columns"]), N)
    for f, c in enumerate(DATA["columns"]):
        assert codes[f].max() == c["cardinality"] - 1, c["name"]
        assert len(np.unique(codes[f])) == c["cardinality"], c["name"]
    assert set(np.unique(rows["label"])) == {0.0, 1.0}
    assert 0.3 < rows["label"].mean() < 0.7


def test_label_function_does_not_depend_on_the_seed(rows):
    z = gen.log_odds(rows["holdout_codes"], DATA)
    p = 1 / (1 + np.exp(-z.astype(np.float64)))
    # the labels follow the fixed function: calibration within sampling noise
    assert abs(p.mean() - rows["holdout_label"].mean()) < 0.02
    assert z.std() > 0.5


def test_first_row_must_be_a_chunk_boundary():
    with pytest.raises(ValueError):
        gen.generate(1, DATA, 17, 100)
