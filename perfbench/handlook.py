"""Look at one trace by hand before trusting a reader's pattern.

    python3 -m perfbench.handlook <trace dir or .xplane.pb> <out dir>

Writes `<out dir>/describe.txt`: every plane and line of the trace and, for
device lines and the benchmark's annotations, the events that took most
time with one event's stats (scope path, program).  Copies the trace file
beside it where it is under 24 MiB.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys

from . import trace


def main(argv) -> int:
    src, out = argv[0], argv[1]
    if os.path.isdir(src):
        found = sorted(glob.glob(os.path.join(
            src, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            print(f"no .xplane.pb under {src}", file=sys.stderr)
            return 1
        src = found[-1]
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "describe.txt"), "w") as f:
        f.write(f"{src} {os.path.getsize(src)} bytes\n")
        f.write(trace.describe(src))
        f.write("\n")
    if os.path.getsize(src) < 24 * 2 ** 20:
        shutil.copy(src, os.path.join(out, "trace.xplane.pb"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
