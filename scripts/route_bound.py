"""Time one wave's routing alone on the chip, in each form.

    chiprun -- python3 scripts/route_bound.py [--shapes 13x83886080,...]

ISSUE 34's step 0, beside `scripts/hist_lane_bound.py`.  At each `[F, N]`
uint8 shape, eight picks over leaves that hold 1/2, 1/4, .. 1/256 of the
rows, routed by:

  a       today's pick loop: a `fori_loop` whose every turn makes one
          `split_go_left` mask pass and one `leaf_id` rewrite (what
          `ops/grow_wave.py`'s `ibody` does a pick)
  b       `ops/route.route_wave_rows`, the Pallas pass over all columns
  c       `ops/route.route_rows_xla`, the same in plain `jax.numpy`, the
          eight picks unrolled into what XLA makes of them
  a_spec / b_spec / c_spec   the speculation's `slot_of_row` (default -1)
  a_cat   ISSUE 35's step 0: five of the eight picks categorical (left
          sets of 25 of 254 bins), today's pick loop, whose categorical
          picks gather `cat_mask[bins]` at [N]
  b_cat / c_cat   the same picks as records with their left sets
          (`ops/route.left_sets`) through the Pallas pass / the XLA unroll
          (PR 35 also timed the pass with a left set as a 128-lane row
          gathered along the lanes, 7.6 ms, and as a 0/1 table contracted
          on the MXU, 64 ms, for the kept selects' 6.3 ms: PERF.md 6)

One JSON line a form: ms a pass (median of `--reps` after a warm-up
call), ps a row, the pass's `(F + 8)`-byte floor at 819 GB/s, and whether
its integers equal form a's.  `--tiles` / `--chunks` time form b at other
rows a grid step / a compute chunk than the module's.

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

from lightgbm_tpu.ops import route as rt
from lightgbm_tpu.ops.split import MISSING_NAN, bin_goes_left

HBM_BYTES_PER_S = 819e9
K = 8


def make_inputs(f, n, seed=0):
    """Bins of 255 codes a column (the last column 2, as the airline's),
    `leaf_id` geometric over 16 leaves (leaf j holds 2**-(j+1) of the
    rows), eight picks on leaves 0..7 with pick 5 a pad slot's twin (a
    leaf with no rows: 40)."""
    @jax.jit
    def gen(key):
        kb, kl = jax.random.split(key)
        nb = jnp.full((f, 1), 255, jnp.uint8).at[f - 1].set(2)
        bins = jax.random.bits(kb, (f, n), jnp.uint8) % nb
        u = jax.random.bits(kl, (n,), jnp.uint32)
        lid = jnp.minimum(jax.lax.clz(u), 15).astype(jnp.int32)
        return bins, lid
    bins, lid = gen(jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    nb = np.full(f, 255, np.int32)
    nb[-1] = 2
    feat = rng.randint(0, f, K)
    feat[0] = f - 1
    rec = dict(
        live=np.ones(K, bool), leaf=np.arange(K), feature=feat,
        thr=np.array([rng.randint(0, nb[c]) for c in feat]),
        default_left=rng.randint(0, 2, K).astype(bool),
        new=16 + np.arange(K), small_is_left=rng.randint(0, 2, K) > 0)
    rec["leaf"][5] = 40
    # ISSUE 35: picks 0, 2, 3, 5, 6 categorical, 25 of the column's bins
    # (never bin 0) in each left set
    rec["is_cat"] = np.isin(np.arange(K), (0, 2, 3, 5, 6))
    rec["cat_mask"] = np.zeros((K, 255), bool)
    for k in range(K):
        top = max(int(nb[feat[k]]) - 1, 1)
        rec["cat_mask"][k, 1 + rng.choice(top, min(25, top),
                                          replace=False)] = True
    missing = np.where(np.arange(f) % 2 == 0, MISSING_NAN, 0).astype(np.int32)
    return bins, lid, {k: jnp.asarray(v) for k, v in rec.items()}, \
        jnp.asarray(nb), jnp.asarray(missing)


def sets_of(r, nb, missing):
    return rt.left_sets(r["feature"], r["thr"], r["default_left"], nb,
                        missing, r["is_cat"], r["cat_mask"])


def records(r, nb, missing, spec):
    k = jnp.arange(K)
    if spec:
        return rt.pick_records(r["live"], r["leaf"], r["feature"], r["thr"],
                               r["default_left"], nb, missing,
                               jnp.where(r["small_is_left"], k, -1),
                               jnp.where(r["small_is_left"], -1, k))
    return rt.pick_records(r["live"], r["leaf"], r["feature"], r["thr"],
                           r["default_left"], nb, missing, r["leaf"],
                           r["new"])


def form_a(spec, cat=False):
    """The grower's per-pick routing as it stands: one pick a loop turn
    (`cat`: as a model with categorical columns routes, the categorical
    picks by a gather in their mask)."""
    def run(bins, lid, r, nb, missing):
        def go_left(k):
            f = r["feature"][k]
            fbins = jnp.take(bins, f, axis=0).astype(jnp.int32)
            if cat:
                return bin_goes_left(fbins, nb[f], missing[f], r["thr"][k],
                                     r["default_left"][k], r["is_cat"][k],
                                     r["cat_mask"][k])
            return bin_goes_left(fbins, nb[f], missing[f], r["thr"][k],
                                 r["default_left"][k])

        def pick(k, leaf_id):
            in_leaf = r["live"][k] & (leaf_id == r["leaf"][k])
            return jnp.where(in_leaf & ~go_left(k), r["new"][k], leaf_id)

        def slot(k, slot_of_row):
            return jnp.where(
                r["live"][k] & (lid == r["leaf"][k])
                & (go_left(k) == r["small_is_left"][k]), k, slot_of_row)
        if spec:
            return jax.lax.fori_loop(0, K, slot, jnp.full_like(lid, -1))
        return jax.lax.fori_loop(0, K, pick, lid)
    return run


def form_b(spec, interpret, cat=False):
    def run(bins, lid, r, nb, missing):
        return rt.route_wave_rows.__wrapped__(
            bins, lid, records(r, nb, missing, spec),
            fill=-1 if spec else None, interpret=interpret,
            sets=sets_of(r, nb, missing) if cat else None)
    return run


def form_c(spec, cat=False):
    def run(bins, lid, r, nb, missing):
        return rt.route_rows_xla(bins, lid, records(r, nb, missing, spec),
                                 fill=-1 if spec else None,
                                 sets=sets_of(r, nb, missing) if cat
                                 else None)
    return run


def timed(fn, bins, lid, rest, reps, donate):
    """(median s, first call's result on the host).  A form that rewrites
    `leaf_id` is handed its own copy every call and donates it, as the
    grower's loop carries it."""
    jfn = jax.jit(fn, donate_argnums=(1,) if donate else ())
    first = np.asarray(jfn(bins, jnp.copy(lid), *rest))
    out = []
    for _ in range(reps):
        arg = jax.block_until_ready(jnp.copy(lid))
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(bins, arg, *rest))
        out.append(time.perf_counter() - t0)
    return statistics.median(out), first


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="13x83886080,13x114999296,"
                    "68x50331648", help="FxN, comma-separated")
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--forms", default="a,b,c,a_spec,b_spec,c_spec")
    ap.add_argument("--tiles", default="", help="rows a grid step of form "
                    "b, comma-separated (default: the module's)")
    ap.add_argument("--chunks", default="", help="rows a compute chunk")
    ap.add_argument("--rehearse", action="store_true", help="run off the "
                    "TPU in interpret mode (tiny shapes): finds faults, "
                    "its times mean nothing")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.rehearse:
        print("no TPU: a time from this machine is not a device number",
              file=sys.stderr)
        return 2
    print(json.dumps({"device": dev.device_kind, "reps": a.reps, "picks": K}))
    tiles = [int(t) for t in a.tiles.split(",") if t] or [rt.ROUTE_TILE]
    chunks = [int(c) for c in a.chunks.split(",") if c] or [rt.ROUTE_CHUNK]
    ok = True
    for shape in a.shapes.split(","):
        f, n = (int(v) for v in shape.split("x"))
        bins, lid, r, nb, missing = make_inputs(f, n)
        rest = (r, nb, missing)
        floor_s = (f + 8) * n / HBM_BYTES_PER_S
        ref = {}
        for form in a.forms.split(","):
            spec = form.endswith("_spec")
            cat = "_cat" in form
            variants = [(None, None)]
            if form[0] == "b":
                variants = [(t, c) for t in tiles for c in chunks]
            for tile, chunk in variants:
                line = {"shape": [f, n], "form": form}
                if tile:
                    rt.ROUTE_TILE, rt.ROUTE_CHUNK = tile, chunk
                    line.update(tile=tile, chunk=chunk)
                fn = {"a": form_a(spec, cat),
                      "b": form_b(spec, a.rehearse, cat),
                      "c": form_c(spec, cat)}[form[0]]
                try:
                    med, got = timed(fn, bins, lid, rest, a.reps,
                                     donate=not spec)
                except Exception as e:       # the compiler's refusal
                    line["refused"] = str(e)[:300]
                    print(json.dumps(line), flush=True)
                    continue
                ref.setdefault((spec, cat), got)
                equal = bool(np.array_equal(got, ref[spec, cat]))
                ok &= equal
                line.update(ms_a_pass=med * 1e3, ps_a_row=med / n * 1e12,
                            floor_ms=floor_s * 1e3,
                            times_floor=med / floor_s,
                            equal_to_first=equal,
                            rows_moved=int((got != np.asarray(lid)).sum())
                            if not spec else int((got >= 0).sum()))
                print(json.dumps(line), flush=True)
        del bins, lid
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
