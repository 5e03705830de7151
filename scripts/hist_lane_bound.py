"""Time one histogram kernel call on the chip at the benchmark cells' shape.

    chiprun -- python3 scripts/hist_lane_bound.py [--rows N] [--reps K]

Prints one JSON line a variant: seconds a call (median of K, after a
warm-up call) and ns a (row, column).  Variants: `mb256` / `mb128` (every
column its own `max_bin` lanes, bins drawn below 128 for both, so only the
contracted lanes differ: ISSUE 30 step 0), `airline_trivial` and
`airline_packed` (the thirteen airline bin counts at max_bin 255, without
and with the lane plan); with `--parent <checkout>` that checkout's kernel
too, and whether the airline sums equal its sums bit for bit.

    chiprun -- python3 scripts/hist_lane_bound.py --active-share 0.04,0.08 \
        --capacity 256,512,1024

is ISSUE 32's step 0: the packed airline call with a `leaf_id` that puts a
share p of iid rows into the S slots, through the full body, through each
compacting capacity that holds the fullest tile (forced: `body=`), and as
the dispatch picks (`auto`); each line carries the largest |difference|
of its sums from the full body's, relative to the largest sum.

    python3 scripts/hist_lane_bound.py --rows 83886080 \
        --active-share 0.01,0.02,0.035,0.06 --capacity 64,128

times the capacities below 256 the same way at the 255-leaf cells' rows
(40,960 tiles), where the deep waves' fullest tile holds 25-170 active
rows: `c128` is kept where its call is at most 0.8x of `c256`'s.

    chiprun -- python3 scripts/hist_lane_bound.py --num-bins 255x9,12,255x57,1 \
        --blocks 1,2,3,4

is ISSUE 33's step 0: the call at other columns than the airline's (here
the 68 a shard of `criteo67-lgbpar-l255` sees), in 1, 2, 3 and 4 column
blocks (`pallas_hist.column_blocks`, forced through `feat_tile`), without
and with the lane plan, through each body (`full` over rows that are all
in a slot's reach as the waves' are, `c512` / `c256` at 18% / 8% of the
rows active); a line says whether its sums equal the first line's of its
share bit for bit, or carries the compiler's refusal.

Exits 2 where JAX finds no TPU: a CPU time is not a device number.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.ops import pallas_hist as ph

AIRLINE_NUM_BIN = (22, 12, 31, 7, 255, 255, 29, 255, 255, 255, 255, 255, 2)


def _time(fn, args, reps):
    jax.block_until_ready(fn(*args))                 # compile + warm up
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return statistics.median(out), out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=2048 * 2048)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true", help="run off the "
                    "TPU in interpret mode (tiny --rows): finds faults, "
                    "its times mean nothing")
    ap.add_argument("--parent", default="", help="checkout of another "
                    "commit whose kernel is timed and compared bitwise")
    ap.add_argument("--active-share", default="", help="comma-separated "
                    "shares of rows in a slot: time the compacting bodies")
    ap.add_argument("--capacity", default="", help="comma-separated "
                    "capacities to time beside pallas_hist.COMPACT_CAPS")
    ap.add_argument("--num-bins", default="", help="the columns' bin "
                    "counts, comma-separated, `255x9` for nine of 255: "
                    "time the call at these columns by column blocks")
    ap.add_argument("--blocks", default="1,2,3,4")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.rehearse:
        print("no TPU: a time from this machine is not a device number",
              file=sys.stderr)
        return 2
    print(json.dumps({"device": dev.device_kind, "rows": a.rows,
                      "slots": a.slots, "reps": a.reps}))
    if a.num_bins:
        return column_blocks(a)
    if a.active_share:
        return compaction(a)
    rng = np.random.RandomState(0)
    n, f = a.rows, len(AIRLINE_NUM_BIN)
    pw9 = ph._split_payload9(jnp.asarray(
        np.abs(rng.randn(n, 3)).astype(np.float32)))
    lid = jnp.asarray(rng.randint(0, 4 * a.slots, n).astype(np.int32))
    slots = jnp.arange(a.slots, dtype=jnp.int32)
    low = jnp.asarray(rng.randint(0, 128, (f, n)).astype(np.uint8))
    air = jnp.asarray(np.stack(
        [rng.randint(0, nb, n) for nb in AIRLINE_NUM_BIN]).astype(np.uint8))
    variants = [("mb256", ph, low, 256, None), ("mb128", ph, low, 128, None)]
    if a.parent:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "lightgbm_tpu.ops._parent_pallas_hist",
            os.path.join(a.parent, "lightgbm_tpu", "ops", "pallas_hist.py"))
        parent = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = parent
        spec.loader.exec_module(parent)
        variants += [("parent_mb256", parent, low, 256, None),
                     ("parent_mb128", parent, low, 128, None),
                     ("airline_parent", parent, air, 255, None)]
    variants += [("airline_trivial", ph, air, 255, None),
                 ("airline_packed", ph, air, 255,
                  ph.lane_plan(AIRLINE_NUM_BIN, 255))]
    ref = None
    for name, mod, bins, mb, plan in variants:
        kw = {} if plan is None else {"plan": plan}

        def call(b, p, l, s, mb=mb, kw=kw, mod=mod):
            return mod.pallas_histogram_multi_rows(
                b, p, l, s, mb, interpret=a.rehearse, **kw)
        med, all_s = _time(call, (bins, pw9, lid, slots), a.reps)
        line = {"variant": name, "max_bin": mb, "call_s": med,
                "ns_per_row_col": med / (n * f) * 1e9, "all_s": all_s}
        if name.startswith("airline"):
            got = np.asarray(call(bins, pw9, lid, slots))
            if ref is None:
                ref = got
            else:
                line["bit_equal_to_first"] = bool(np.array_equal(got, ref))
        print(json.dumps(line), flush=True)
    return 0


def column_blocks(a):
    """ISSUE 33 step 0: one line a (body, plan, blocks)."""
    num_bins = []
    for part in a.num_bins.split(","):
        nb, _, times = part.partition("x")
        num_bins += [int(nb)] * int(times or 1)
    rng = np.random.RandomState(0)
    n, f = a.rows, len(num_bins)
    row_tile = min(n, ph.ROW_TILE)
    pw9 = ph._split_payload9(jnp.asarray(
        np.abs(rng.randn(n, 3)).astype(np.float32)))
    bins = jnp.asarray(np.stack(
        [rng.randint(0, nb, n) for nb in num_bins]).astype(np.uint8))
    slots = jnp.arange(a.slots, dtype=jnp.int32)
    for body, share in (("full", 0.5), ("c512", 0.18), ("c256", 0.08)):
        lid = jnp.asarray(np.where(
            rng.rand(n) < share, rng.randint(0, a.slots, n),
            a.slots + rng.randint(0, 3 * a.slots, n)).astype(np.int32))
        ref = None
        for plan in (None, ph.lane_plan(num_bins, 255)):
            for k in (int(b) for b in a.blocks.split(",")):
                feat_tile = -(-f // k)
                line = {"columns": f, "body": body, "active_share": share,
                        "plan": plan is not None, "blocks": len(
                            ph.column_blocks(f, a.slots * 9, 255, plan,
                                             feat_tile))}

                def call(b, p, l, s, plan=plan, feat_tile=feat_tile):
                    return ph.pallas_histogram_multi_rows(
                        b, p, l, s, 255, plan=plan, feat_tile=feat_tile,
                        interpret=a.rehearse, row_tile=row_tile, body=body)
                try:
                    med, all_s = _time(call, (bins, pw9, lid, slots), a.reps)
                except Exception as e:      # the compiler's refusal
                    line["refused"] = f"{type(e).__name__}: {e}"[:600]
                    print(json.dumps(line), flush=True)
                    continue
                got = np.asarray(call(bins, pw9, lid, slots))
                ref = got if ref is None else ref
                line.update(call_s=med, all_s=all_s,
                            ps_per_row_lane=med / n / (
                                ph.plan_lanes(plan) if plan else f * 256)
                            * 1e12,
                            bit_equal_to_first=bool(np.array_equal(got, ref)))
                print(json.dumps(line), flush=True)
    return 0


def compaction(a):
    """ISSUE 32 step 0: one line a (share, body)."""
    caps = sorted(set(ph.COMPACT_CAPS)
                  | {int(c) for c in a.capacity.split(",") if c})
    ph.COMPACT_CAPS = tuple(caps)        # read where a call is traced
    rng = np.random.RandomState(0)
    n = a.rows
    plan = ph.lane_plan(AIRLINE_NUM_BIN, 255)
    pw9 = ph._split_payload9(jnp.asarray(
        np.abs(rng.randn(n, 3)).astype(np.float32)))
    air = jnp.asarray(np.stack(
        [rng.randint(0, nb, n) for nb in AIRLINE_NUM_BIN]).astype(np.uint8))
    slots = jnp.arange(a.slots, dtype=jnp.int32)
    row_tile = min(n, ph.ROW_TILE)

    def call(lid, body):
        return ph.pallas_histogram_multi_rows(
            air, pw9, lid, slots, 255, plan=plan, interpret=a.rehearse,
            row_tile=row_tile, body=body, count_bodies=True)

    for p in (float(v) for v in a.active_share.split(",")):
        lid_np = np.where(rng.rand(n) < p, rng.randint(0, a.slots, n),
                          a.slots + rng.randint(0, 3 * a.slots, n))
        fullest = int((lid_np < a.slots).reshape(-1, row_tile).sum(1).max())
        lid = jnp.asarray(lid_np.astype(np.int32))
        ref = None
        for body in ["full"] + [f"c{c}" for c in caps if fullest <= c
                                and c < row_tile] + [None]:
            med, all_s = _time(call, (lid, body), a.reps)
            limbs, calls = call(lid, body)
            limbs = np.asarray(limbs)
            sums = np.asarray(ph.hist_value(jnp.asarray(limbs)), np.float64)
            if ref is None:
                ref, ref_limbs = sums, limbs
            print(json.dumps({
                "active_share": p, "fullest_tile": fullest,
                "body": body or "auto", "call_s": med,
                "ran": dict(zip([nm for nm, _ in ph.hist_bodies(row_tile)],
                                np.asarray(calls).tolist())),
                "rel_diff_from_full": float(
                    np.abs(sums - ref).max() / np.abs(ref).max()),
                "limbs_bit_equal_to_full": bool(
                    np.array_equal(limbs.view(np.uint32),
                                   ref_limbs.view(np.uint32))),
                "all_s": all_s}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
