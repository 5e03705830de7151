"""Work a call needs, counted from its logical arguments, never from how it
is implemented.  A per-layer metric file names one of these functions and
gives its parameters as data; `roofline_share` divides the least time the
chip could take for that work by the device time the trace shows.
"""
from __future__ import annotations

from typing import Dict


def histogram_pass(rows: int, columns: int, max_bin: int, slots: int = 1,
                   bin_bytes: int = 1) -> Dict[str, float]:
    """One histogram call over all rows: for each of `slots` leaves, the
    sums of gradient, hessian and count per (column, bin).

    bytes  read  rows*columns*bin_bytes   the bin matrix, once
           read  16*rows                  gradient, hessian, count weight
                                          (f32 each) and the row's leaf id
           write slots*columns*max_bin*12 three f32 sums per cell
    flops  3*rows*columns                 one addition per row, column and
                                          sum; the one-hot matmul's
                                          multiply-adds are not work
    """
    return {
        "bytes": float(rows) * columns * bin_bytes + 16.0 * rows
        + float(slots) * columns * max_bin * 12,
        "flops": 3.0 * rows * columns,
    }


def least_seconds(work: Dict[str, float], peaks: dict) -> Dict[str, object]:
    """The least time the chip could take, and which peak bounds it."""
    by_bytes = work["bytes"] / float(peaks["hbm_bytes_per_s"])
    by_flops = work["flops"] / float(peaks["f32_flops_per_s"])
    return {"seconds": max(by_bytes, by_flops),
            "bound": "hbm" if by_bytes >= by_flops else "compute"}


FUNCTIONS = {"histogram_pass": histogram_pass}
