"""A tree's value for every row: `table[leaf_id]` at a routing pass's price.

The score update adds the new tree's shrunken leaf values to the training
scores: one look-up a row in a table of `num_leaves` f32 entries.  What
XLA makes of `table[leaf_id]` on a TPU v5e is a gather at 9.8 ns a row at
255 leaves (819 ms alone at [83,886,080]: a fifth of a 255-leaf round);
the same look-up inside a Pallas pass over the ids reads 1.9-2.6 ms, 2.4
to 3.1 times the 8 bytes a row it moves.

The pass walks `[1, tile]` blocks of the ids in
`ops/route.route_wave_rows`' frame (nothing padded or copied, a short
last step) with the table as `[ceil(L / 128), 128]` f32 rows resident in
VMEM: a chunk of ids is read as 128-lane rows, each table row is
gathered along the lanes by `id & 127`, and `id >> 7` selects among the
rows.  No arithmetic touches a value, so the output is `table[leaf_id]`
to the BIT (-0.0, denormals and NaN payloads included) for every id in
`[0, L)`; an id outside it (a mesh's pad rows carry -1) reads 0.0.
The other forms read at [83,886,080] and 255 entries (ms a pass alone,
`scripts/leaf_rows_bound.py`; PERF.md section 6, PR 36): a select tree
over the id's bits 6.96 in the pass and 7.11 as one XLA fusion, a one-hot
against the table's three bf16 limbs on the MXU 8.87 and NOT the gather's
bits, XLA's compare-and-reduce 68.3, two levels of sixteen 35.5.

`leaf_rows` is the one function the boosters call: the Pallas pass where
the Pallas family runs and the table has at most `LEAF_MAX_ENTRIES`
entries, `table[leaf_id]` elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_hist import LANE

Array = jax.Array

# rows a grid step moves and rows a compute chunk inside it works on at
# once (`ops/route.py`'s frame; one pass alone at [83,886,080] and 255
# entries, ms: tile 32,768 at chunk 4,096 / 8,192 / 16,384: 3.04 / 2.58 /
# 2.40; tile 131,072: 2.94 / 2.13 / 1.95; tile 524,288: 2.92 / 2.11 /
# 1.87, for 8 MiB of VMEM: PERF.md section 6, PR 36)
LEAF_TILE = 131072
LEAF_CHUNK = 16384

# entries up to which the pass was read against the gather: one lane
# gather and one select a 128-entry table row, 0.33 ms a row at
# [83,886,080] (1,023 entries 4.35 ms, 4,095 entries 12.90 ms, for the
# gather's 566 and 722: PERF.md section 6, PR 36); beyond, the gather
LEAF_MAX_ENTRIES = 4096


def lane_rows_lookup(tab: Array, ids: Array) -> Array:
    """`tab` [T, 128] f32 (entry j at row j // 128, lane j % 128) looked
    up at `ids` [S, 128] int32: one lane gather a table row by the id's
    low seven bits, the row chosen by the bits above; 0.0 for an id in no
    row."""
    lane, hi = ids & (LANE - 1), ids >> 7
    out = jnp.zeros(ids.shape, jnp.float32)
    for r in range(tab.shape[0]):
        got = jnp.take_along_axis(
            jnp.broadcast_to(tab[r:r + 1, :], ids.shape), lane, axis=1,
            mode="promise_in_bounds")
        out = jnp.where(hi == r, got, out)
    return out


def _leaf_rows_kernel(tab_ref, ids_ref, out_ref, *, chunk: int):
    """One grid step, `chunk` ids at a time as `[chunk / 128, 128]`."""
    tab = tab_ref[:]                                     # [T, LANE]

    def one(c, carry):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        ids = ids_ref[:, rows].reshape(chunk // LANE, LANE)
        out_ref[:, rows] = lane_rows_lookup(tab, ids).reshape(1, chunk)
        return carry

    jax.lax.fori_loop(0, ids_ref.shape[1] // chunk, one, 0)


def _leaf_rows_pass(table: Array, leaf_id: Array, interpret: bool) -> Array:
    """The look-up as one Pallas pass over `[1, tile]` id blocks."""
    n = leaf_id.shape[0]
    tab = jnp.pad(table.astype(jnp.float32),
                  (0, -table.shape[0] % LANE)).reshape(-1, LANE)
    # nothing is padded or copied: the last step (the only one of a small
    # table) may hold fewer rows than a tile; what it reads beyond the
    # rows is not defined, and what it writes there is dropped
    chunk = min(LEAF_CHUNK, -(-n // 1024) * 1024)
    tile = min(LEAF_TILE, -(-n // chunk) * chunk)
    row_spec = pl.BlockSpec((1, tile), lambda r: (0, r))
    out = pl.pallas_call(
        functools.partial(_leaf_rows_kernel, chunk=chunk),
        grid=(pl.cdiv(n, tile),),
        in_specs=[pl.BlockSpec(tab.shape, lambda r: (0, 0)), row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="leaf_rows",
    )(tab, leaf_id.astype(jnp.int32)[None, :])
    return out[0]


def pass_serves(entries: int, hist_impl: str) -> bool:
    """Whether `leaf_rows` looks a table of `entries` up in the Pallas
    pass: static facts, the family that runs and the table's size."""
    return hist_impl in ("pallas", "pallas_q") and \
        entries <= LEAF_MAX_ENTRIES


@functools.partial(jax.jit, static_argnames=("hist_impl", "interpret"))
def leaf_rows(table: Array, leaf_id: Array, hist_impl: str,
              interpret: bool = False) -> Array:
    """[N] f32, `table[leaf_id]`: every row's value of a tree whose leaf
    values are `table` [L] f32, `leaf_id` [N] int32 (`hist_impl`,
    `interpret` as the grower's spec has them).  Where the pass serves,
    an id outside `[0, L)` reads 0.0; elsewhere it reads what JAX's
    indexing reads (a negative id counts from the end, any other is
    clamped)."""
    if pass_serves(len(table), hist_impl):
        return _leaf_rows_pass(table, leaf_id, interpret)
    return table[leaf_id]
