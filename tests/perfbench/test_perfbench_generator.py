"""The row generator: seeded, chunked, hold-out apart, cardinalities kept."""
import numpy as np
import pytest

from perfbench import manifest
from perfbench.generators import tabular_codes as gen

DATA = manifest.config("airline13-l31")["data"]
N = gen.CHUNK + 4096          # one whole chunk and a part of the next


@pytest.fixture(scope="module")
def rows():
    return gen.make(2 ** 31 + 12345, DATA, N, 8192)


def test_same_seed_same_rows(rows):
    again = gen.make(2 ** 31 + 12345, DATA, N, 8192)
    for k in rows:
        assert np.array_equal(rows[k], again[k]), k


def test_other_seed_other_rows(rows):
    other = gen.make(2 ** 31 + 12346, DATA, N, 8192)
    assert not np.array_equal(rows["codes"], other["codes"])
    assert not np.array_equal(rows["label"], other["label"])


def test_threads_do_not_change_the_stream():
    a = gen.generate(5, DATA, 0, N, threads=1)
    b = gen.generate(5, DATA, 0, N, threads=5)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_holdout_rows_are_chunks_after_the_training_rows(rows):
    first = -(-N // gen.CHUNK) * gen.CHUNK
    codes, label = gen.generate(2 ** 31 + 12345, DATA, first, 8192)
    assert np.array_equal(codes, rows["holdout_codes"])
    assert np.array_equal(label, rows["holdout_label"])
    # and they are not a copy of any training rows of the same length
    assert not np.array_equal(rows["holdout_codes"], rows["codes"][:, :8192])


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
