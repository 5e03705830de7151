"""Time the score update's look-up `table[leaf_id]` alone on the chip, in
each form.

    chiprun -- python3 scripts/leaf_rows_bound.py [--rows 83886080,...]

ISSUE 36's step 0, beside `scripts/route_bound.py`.  At each `[N]` of
int32 leaf ids drawn like a grown tree's (leaf j holds the rows with
`L u**3` in [j, j + 1): leaf 0 a sixth of them at 255 leaves, the last
leaves a thousandth each) and each `[L]` f32 table, the per-row values
by:

  a       today's `table[leaf_id]`, what XLA makes of the gather
  b       a Pallas pass over `[1, tile]` id blocks (`route_wave_rows`'
          frame), the value by a select TREE over the id's bits, the
          table's entries read as scalars from SMEM
  b_s     the same tree over `[tile / 128, 128]` id blocks (the ids
          viewed as 128-lane rows)
  c       the pass with the table as `[L / 128, 128]` f32 rows: a lane
          gather by `id & 127` in each row and a select by `id >> 7`,
          over `[tile / 128, 128]` id blocks
  c_r     the lane gather in `route_wave_rows`' frame, each `[1, chunk]`
          of ids reshaped to 128-lane rows inside the kernel: the
          package's kernel (`ops/leaf_rows._leaf_rows_kernel`) at the
          script's tile and chunk
  d       the one-hot of the ids against the table's three bf16 limbs on
          the MXU, the limbs added in f32
  e       plain XLA: compare every id with every entry and sum (the form
          XLA finds by itself at 31 leaves)
  e16     plain XLA, two levels: sixteen entries by `id & 15`, then
          sixteen rows by `id >> 4`
  e_tree  plain XLA: form b's select tree, one fusion
  kept    `ops/leaf_rows.leaf_rows` as the booster calls it on the Pallas
          family (form c_r at the module's tile and chunk; a table over
          `LEAF_MAX_ENTRIES`: the gather)

Read on a v5e at [83,886,080], 255 entries (PERF.md section 6, PR 36): a
819.0 ms, b 6.96, b_s 6.87, c 2.58, c_r 2.57 (1.95 at the module's tile
and chunk), d 8.87 and not a's bits, e 68.3, e16 35.5, e_tree 7.11.

One JSON line a form: ms a pass (median of `--reps` after a warm-up
call), ps a row, the pass's 8-byte floor at 819 GB/s, and whether its
BITS equal form a's (compared on the device).  `--tiles` / `--chunks`
time the Pallas forms at other rows a grid step / a compute chunk.

Exits 2 where JAX finds no TPU: a CPU time is not a device number.
"""
import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.ops import leaf_rows as lr

HBM_BYTES_PER_S = 819e9
LANE = 128


def make_inputs(n, leaves, seed=0):
    """`[n]` ids skewed like a grown tree's leaves and a `[leaves]` table
    of distinct values of both signs (sixteen significant bits and more,
    so that the three bf16 limbs of form d all carry something)."""
    @jax.jit
    def gen(key):
        u = jax.random.uniform(key, (n,), jnp.float32)
        return jnp.minimum((leaves * u * u * u).astype(jnp.int32),
                           leaves - 1)
    rng = np.random.RandomState(seed + leaves)
    table = (rng.standard_normal(leaves) * 0.0371).astype(np.float32)
    return gen(jax.random.PRNGKey(seed)), jnp.asarray(table)


def id_bits(leaves):
    return max(int(leaves - 1).bit_length(), 1)


def padded(table, to):
    """The table with zeros up to a multiple of `to` entries."""
    return jnp.pad(table, (0, -table.shape[0] % to))


def select_tree(ids, entry, bits):
    """The entry at `ids`' low `bits` bits by a binary tree of selects,
    depth first (`entry(j)`: entry j, a scalar); 0.0 where an id has
    higher bits set."""
    masks = [((ids >> k) & 1) == 1 for k in range(bits)]

    def rec(lo, bit):
        if bit < 0:
            return entry(lo)
        return jnp.where(masks[bit], rec(lo + (1 << bit), bit - 1),
                         rec(lo, bit - 1))
    return jnp.where((ids >> bits) == 0, rec(0, bits - 1), 0.0)


# ------------------------------------------------------------ Pallas forms
def _rows_loop(ids_ref, out_ref, chunk, value_of):
    """`value_of` over a `[1, tile]` block, `chunk` rows at a time."""
    def one(c, carry):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        out_ref[:, rows] = value_of(ids_ref[:, rows])
        return carry
    jax.lax.fori_loop(0, ids_ref.shape[1] // chunk, one, 0)


def _lanes_loop(ids_ref, out_ref, chunk, value_of):
    """`value_of` over a `[tile / 128, 128]` block, `chunk` rows (`chunk /
    128` sublane rows) at a time."""
    sub = chunk // LANE

    def one(c, carry):
        rows = pl.ds(pl.multiple_of(c * sub, sub), sub)
        out_ref[rows, :] = value_of(ids_ref[rows, :])
        return carry
    jax.lax.fori_loop(0, ids_ref.shape[0] // sub, one, 0)


def _tree_kernel(tab_ref, ids_ref, out_ref, *, bits, chunk, loop):
    loop(ids_ref, out_ref, chunk,
         lambda ids: select_tree(ids, lambda j: tab_ref[j], bits))


def _gather_lanes_kernel(tab_ref, ids_ref, out_ref, *, chunk):
    """The package's look-up over ids that arrive as 128-lane rows."""
    tab = tab_ref[:]
    _lanes_loop(ids_ref, out_ref, chunk,
                lambda ids: lr.lane_rows_lookup(tab, ids))


def _mxu_kernel(limbs_ref, ids_ref, out_ref, *, chunk):
    limbs = limbs_ref[:]                                 # [8, Lp] bf16

    def value_of(ids):                                   # [1, chunk]
        hot = (jax.lax.broadcasted_iota(
            jnp.int32, (limbs.shape[1], ids.shape[1]), 0) == ids)
        got = jax.lax.dot_general(
            limbs, hot.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [8, chunk]
        return (got[0:1] + got[1:2]) + got[2:3]
    _rows_loop(ids_ref, out_ref, chunk, value_of)


def bf16_limbs(table):
    """[8, Lp] bf16: rows 0..2 the table's three limbs (they add back to
    it in f32 without rounding), the rest zeros."""
    hi = table.astype(jnp.bfloat16)
    mid = (table - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    lo = (table - hi.astype(jnp.float32)
          - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.pad(jnp.stack([hi, mid, lo]), ((0, 5), (0, 0)))


def pallas_form(form, ids, table, tile, chunk, interpret):
    n = ids.shape[0]
    bits = id_bits(table.shape[0])
    chunk = min(chunk, -(-n // 1024) * 1024)
    tile = min(tile, -(-n // chunk) * chunk)
    params = dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel",)), interpret=interpret)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    if form in ("b", "c_r", "d"):                        # [1, tile] blocks
        row = pl.BlockSpec((1, tile), lambda r: (0, r))
        shape = jax.ShapeDtypeStruct((1, n), jnp.float32)
        if form == "b":
            kernel = functools.partial(_tree_kernel, bits=bits, chunk=chunk,
                                       loop=_rows_loop)
            first, spec = padded(table, 1 << bits), smem
        elif form == "c_r":
            first = padded(table, LANE).reshape(-1, LANE)
            kernel = functools.partial(lr._leaf_rows_kernel, chunk=chunk)
            spec = pl.BlockSpec(first.shape, lambda r: (0, 0))
        else:
            first = bf16_limbs(padded(table, LANE))
            kernel = functools.partial(_mxu_kernel, chunk=chunk)
            spec = pl.BlockSpec(first.shape, lambda r: (0, 0))
        return pl.pallas_call(
            kernel, grid=(pl.cdiv(n, tile),), in_specs=[spec, row],
            out_specs=row, out_shape=shape, name=f"leaf_rows_{form}",
            **params)(first, ids[None, :])[0]
    assert n % LANE == 0, "the 128-lane view needs whole rows"
    lanes = pl.BlockSpec((tile // LANE, LANE), lambda r: (r, 0))
    shape = jax.ShapeDtypeStruct((n // LANE, LANE), jnp.float32)
    if form == "b_s":
        kernel = functools.partial(_tree_kernel, bits=bits, chunk=chunk,
                                   loop=_lanes_loop)
        first, spec = padded(table, 1 << bits), smem
    else:
        first = padded(table, LANE).reshape(-1, LANE)
        kernel = functools.partial(_gather_lanes_kernel, chunk=chunk)
        spec = pl.BlockSpec(first.shape, lambda r: (0, 0))
    return pl.pallas_call(
        kernel, grid=(pl.cdiv(n // LANE, tile // LANE),),
        in_specs=[spec, lanes], out_specs=lanes, out_shape=shape,
        name=f"leaf_rows_{form}", **params)(
            first, ids.reshape(-1, LANE)).reshape(-1)


# --------------------------------------------------------------- XLA forms
def form_a(ids, table):
    return table[ids]


def form_e(ids, table):
    j = jnp.arange(table.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where(ids[:, None] == j, table, 0.0), axis=1)


def form_e16(ids, table):
    t = padded(table, 16).reshape(-1, 16)                # [rows, 16]
    j = jnp.arange(16, dtype=jnp.int32)
    by_lo = jnp.sum(jnp.where(((ids & 15)[:, None] == j)[:, None, :],
                              t[None], 0.0), axis=2)     # [N, rows]
    r = jnp.arange(t.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where((ids >> 4)[:, None] == r, by_lo, 0.0), axis=1)


def form_e_tree(ids, table):
    bits = id_bits(table.shape[0])
    t = padded(table, 1 << bits)
    return select_tree(ids, lambda j: t[j], bits)


XLA_FORMS = {"a": form_a, "e": form_e, "e16": form_e16, "e_tree": form_e_tree}


def timed(fn, ids, table, reps):
    """(median s, the first call's result, left on the device)."""
    jfn = jax.jit(fn)
    first = jax.block_until_ready(jfn(ids, table))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(ids, table))
        out.append(time.perf_counter() - t0)
    return statistics.median(out), first


@jax.jit
def same_bits(x, y):
    as_int = functools.partial(jax.lax.bitcast_convert_type,
                               new_dtype=jnp.int32)
    return jnp.all(as_int(x) == as_int(y))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="83886080,114999296,50331648")
    ap.add_argument("--leaves", default="255,31")
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--forms", default="a,b,b_s,c,c_r,d,e,e16,e_tree,kept")
    ap.add_argument("--tiles", default="", help="rows a grid step of the "
                    "Pallas forms, comma-separated (default: the module's)")
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
    print(json.dumps({"device": dev.device_kind, "reps": a.reps}))
    tiles = [int(t) for t in a.tiles.split(",") if t] or [lr.LEAF_TILE]
    chunks = [int(c) for c in a.chunks.split(",") if c] or [lr.LEAF_CHUNK]
    ok = True
    for n in (int(v) for v in a.rows.split(",")):
        floor_s = 8 * n / HBM_BYTES_PER_S
        for leaves in (int(v) for v in a.leaves.split(",")):
            ids, table = make_inputs(n, leaves)
            ref = None
            for form in a.forms.split(","):
                variants = [(None, None)]
                if form not in XLA_FORMS and form != "kept":
                    variants = [(t, c) for t in tiles for c in chunks]
                for tile, chunk in variants:
                    line = {"rows": n, "leaves": leaves, "form": form}
                    if form in XLA_FORMS:
                        fn = XLA_FORMS[form]
                    elif form == "kept":
                        def fn(ids, table):
                            return lr.leaf_rows.__wrapped__(
                                table, ids, "pallas", a.rehearse)
                    else:
                        line.update(tile=tile, chunk=chunk)
                        fn = functools.partial(
                            pallas_form, form, tile=tile, chunk=chunk,
                            interpret=a.rehearse)
                    try:
                        med, got = timed(fn, ids, table, a.reps)
                    except Exception as e:   # the compiler's refusal
                        line["refused"] = str(e)[:300]
                        print(json.dumps(line), flush=True)
                        continue
                    if ref is None:
                        ref = got
                    equal = bool(same_bits(got, ref))
                    ok &= equal or form in ("d", "e", "e16")
                    line.update(ms_a_pass=med * 1e3,
                                ps_a_row=med / n * 1e12,
                                floor_ms=floor_s * 1e3,
                                times_floor=med / floor_s,
                                bits_equal_to_first=equal)
                    print(json.dumps(line), flush=True)
                    del got
            del ids, ref
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
