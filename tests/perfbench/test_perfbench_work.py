"""The work-counting function against hand-worked shapes, and the readers
that need no trace."""
import pytest

from perfbench import manifest, readers, work


def test_histogram_pass_by_hand():
    # 1000 rows x 4 columns, 16 bins, 2 slots, one byte a bin:
    # 4000 B of bins + 16000 B of per-row inputs + 2*4*16*12 = 1536 B written
    w = work.histogram_pass(rows=1000, columns=4, max_bin=16, slots=2)
    assert w["bytes"] == 4000 + 16000 + 1536
    assert w["flops"] == 3 * 1000 * 4


def test_histogram_pass_at_cell_size():
    w = work.histogram_pass(rows=114_999_296, columns=13, max_bin=255)
    assert w["bytes"] == 114_999_296 * 13 + 16 * 114_999_296 + 13 * 255 * 12
    peaks = manifest.peaks("TPU v5 lite")
    least = work.least_seconds(w, peaks)
    assert least["bound"] == "hbm"
    assert least["seconds"] == pytest.approx(w["bytes"] / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        manifest.peaks("TPU v9 imaginary")


def test_readers_without_a_trace_return_nothing():
    ctx = {"trace": None, "counters": {"jit.recompiles": 0}, "units": {},
           "memory": {"peak_bytes": 2 ** 32}, "shape": {},
           "peaks": manifest.peaks("TPU v5 lite")}
    assert readers.scope_share(ctx, {"scope": "x"}) is None
    assert readers.idle_share(ctx, {}) is None
    assert readers.roofline_share(ctx, {"work": "histogram_pass"}) is None
    assert readers.counter_delta(ctx, {"counter": "jit.recompiles"}) == 0.0
    assert readers.counter_delta(ctx, {"counter": "absent"}) is None
    assert readers.memory_peak_share(ctx, {}) == 25.0
