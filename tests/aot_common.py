"""What the ahead-of-time compile tests read off a compiled program's
text (`tests/test_tpu_aot.py`, `tests/test_criteo67_aot.py`)."""
import re


def tpu_kernels(text):
    """Names of the Pallas custom-calls of a compiled program."""
    return [line.split("=")[0].strip().lstrip("%").rsplit(".", 1)[0]
            for line in text.splitlines()
            if "custom-call(" in line and "tpu_custom_call" in line]


def row_array_copies(text, n):
    """The compiled program's copies of an array over all `n` rows ([n],
    [1, n], [F, n]) that stay in HBM: what XLA inserts where it cannot
    update such an array in place.  (A `copy-start` into the alternate
    memory space, `S(1)` in the result's layout, is a prefetch that the
    memory-space assignment chose, not a duplicate.)"""
    found = []
    for line in text.splitlines():
        name, _, rest = line.partition(" = ")
        if not re.match(r"\(?\w+\[(\d+,)?%d\]" % n, rest):
            continue
        op = re.search(r"\b(copy|copy-start)\(", rest)
        if op and "S(1)" not in rest[:op.start()]:
            found.append(line.strip()[:200])
    return found
