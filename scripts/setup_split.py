"""Split one benchmark run's set-up into its parts, from the run's log and
the program's own record of it.

    python3 scripts/setup_split.py --log RUN.out [--saved DIR]

`--log` is the standard output of `perfbench.run` or
`perfbench.program_run`: its `setup:` lines time the import, the device,
the rows, the data set, the booster and the warm-up round(s), and an
untraced run's last line carries `setup_s`.  `--saved` is what
`program_run --save` kept (`program.json`): the spans and gauges of a
traced run split the booster into its kernel probes (`setup.probe`), its
uploads (`setup.place`) and the rest, and each warm-up round into JAX's
tracing, lowering and compiling or cache loading (`jit.*`, counted once
where they nest), the device (`train.wait`) and the rest; the window's
first round (`in_window`) is split the same way for comparison, and the
set-up and compile spans are checked to lie inside their parents.  Prints
one JSON object.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Dict, List

LINES = {
    "import_and_device": r"setup: the program imported in ([\d.]+) s, the "
                         r"device found in ([\d.]+) s more",
    "rows": r"setup: rows made in ([\d.]+) s",
    "data_set": r"setup: data set in ([\d.]+) s",
    "booster": r"setup: booster in ([\d.]+) s",
    "warmup": r"setup: \d+ warm-up round\(s\) in ([\d.]+) s",
}


def from_log(text: str) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for key, pattern in LINES.items():
        m = re.search(pattern, text)
        if not m:
            continue
        if key == "import_and_device":
            out["import"], out["device"] = float(m.group(1)), float(
                m.group(2))
        else:
            out[key] = float(m.group(1))
    last = [ln for ln in text.splitlines() if ln.startswith("{")]
    if last:
        setup = json.loads(last[-1]).get("metrics", {}).get("setup_s")
        if setup:
            out["setup_s"] = setup["value"]
    return out


def union_s(spans: List[dict], lo: int, hi: int) -> float:
    """Seconds of [lo, hi) under at least one of the spans."""
    cut = sorted((max(s["start_ns"], lo), min(s["end_ns"], hi))
                 for s in spans)
    total, end = 0, lo
    for a, z in cut:
        if z <= a:
            continue
        a = max(a, end)
        if z > a:
            total += z - a
            end = z
    return total / 1e9


def split(program: dict, window_rounds: int) -> Dict[str, object]:
    """The booster and the warm-up rounds (the `train.chunk` spans before
    the window's `window_rounds`) of a traced run's record."""
    spans = program["spans"]
    named: Dict[str, List[dict]] = {}
    for s in spans:
        named.setdefault(s["name"], []).append(s)
    out: Dict[str, object] = {"gauges": {
        k: v for k, v in program["counters_start"].items()
        if k.startswith("setup.") or k in ("jit.trace_s", "jit.lower_s",
                                           "jit.compile_total_s",
                                           "jit.cache_misses",
                                           "jit.recompiles")}}

    def inside(lo: int, hi: int) -> Dict[str, float]:
        part = {name: union_s(named.get(name, []), lo, hi)
                for name in ("jit.trace", "jit.lower")}
        compiles = [s for s in named.get("jit.compile", [])
                    if lo <= s["start_ns"] and s["end_ns"] <= hi]
        for cache in ("hit", "miss"):
            mine = [s for s in compiles if s["attrs"]["cache"] == cache]
            part[f"jit.compile.{cache}"] = union_s(mine, lo, hi)
            part[f"jit.compile.{cache}_n"] = len(mine)
        return part

    booster = min(named.get("setup.booster", []),
                  key=lambda s: s["start_ns"], default=None)
    if booster:
        lo, hi = booster["start_ns"], booster["end_ns"]
        whole = (hi - lo) / 1e9
        probe = sum(s["dur_s"] for s in named.get("setup.probe", [])
                    if lo <= s["start_ns"] < hi)
        place: Dict[str, float] = {}
        for s in named.get("setup.place", []):
            if lo <= s["start_ns"] < hi:
                what = s["attrs"]["what"]
                place[what] = place.get(what, 0.0) + s["dur_s"]
        # JAX's work in the booster outside its probes and uploads
        held = [s for s in named.get("setup.probe", [])
                + named.get("setup.place", []) if lo <= s["start_ns"] < hi]
        free = [s for s in spans if s["name"].startswith("jit.")
                and lo <= s["start_ns"] < hi and not any(
                    h["start_ns"] <= s["start_ns"] < h["end_ns"]
                    for h in held)]
        rest = whole - probe - sum(place.values())
        out["booster"] = {"seconds": whole, "probe": probe,
                          "probes": len(named.get("setup.probe", [])),
                          "place": place, "rest": rest,
                          "rest_jit": union_s(free, lo, hi),
                          "jit": inside(lo, hi)}
    rounds = sorted(named.get("train.chunk", []),
                    key=lambda s: s["start_ns"])
    jit_all = [s for s in spans if s["name"].startswith("jit.")]
    warm = []
    for i, chunk in enumerate(rounds):
        if i > len(rounds) - window_rounds:
            break                   # the window's second round onward
        lo, hi = chunk["start_ns"], chunk["end_ns"]
        part = inside(lo, hi)
        wait = sum(s["dur_s"] for s in named.get("train.wait", [])
                   if lo <= s["start_ns"] < hi)
        seconds = (hi - lo) / 1e9
        jit = union_s(jit_all, lo, hi)      # counted once where they nest
        warm.append({"round": chunk.get("round"),
                     "in_window": i == len(rounds) - window_rounds,
                     "seconds": seconds,
                     "device_wait": wait, **part,
                     "rest": seconds - wait - jit})
    out["rounds"] = warm
    # every set-up and compile span lies inside its parent's interval
    ids = {s["id"]: s for s in spans}
    kids = [s for s in spans if s["name"].startswith(("setup.", "jit."))
            and s.get("parent_id") in ids]
    out["outside_parent"] = sum(
        not ids[s["parent_id"]]["start_ns"] <= s["start_ns"] <= s["end_ns"]
        <= ids[s["parent_id"]]["end_ns"] for s in kids)
    out["nested_checked"] = len(kids)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--log", required=True)
    ap.add_argument("--saved", default="")
    args = ap.parse_args(argv)
    with open(args.log) as f:
        out: Dict[str, object] = {"log": from_log(f.read())}
    if args.saved:
        with open(f"{args.saved}/program.json") as f:
            saved = json.load(f)
        out.update(split(saved["program"],
                         int(saved["units_in_window"]["rounds"])))
    json.dump(out, sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
