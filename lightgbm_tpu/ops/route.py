"""Route the rows of several splits in ONE pass over the rows.

A wave's picks are distinct leaves that were ready at the wave's start,
so no row is in two of them: the K routings are independent, and one pass
that reads every bin column and `leaf_id` once does what K passes of
`ops/grow.split_go_left` + a `leaf_id` rewrite do (`ops/grow_wave.py`,
phase `partition`).  A speculating tail pass is the same computation with
another value a side: slot k where the row falls on the smaller side,
-1 elsewhere.

A pick is a RECORD of `REC_FIELDS` int32s (`pick_records`); a row in no
record's leaf takes the default.  The routing rule is
`ops/split.bin_goes_left`: where the model has no categorical column, its
numerical one (threshold, the NaN bin under `default_left`), which the pass
applies with one compare; where it has one, a pick of either kind is handed
over as its LEFT SET over all 256 bins (`left_sets`: `bin_goes_left`
itself, evaluated on every bin, `SET_WORDS` 32-bit words a pick) and the
pass looks each row's bin up in its record's set (`in_left_set`: the word
by a chain of selects, the bit by a shift: 6.3 ms a pass alone at
[13, 83,886,080] for the numerical rule's 4.3; a lane gather in a 128-word
row read 7.6 ms, a 0/1 table contracted on the MXU 64 ms, the per-pick
loop's [N] gathers 3.3 s; PERF.md section 6, PR 35).  Bundled columns
route by another computation and keep the per-pick loop.

Two forms of the one function, chosen like the histogram's by the family
that runs: `route_wave_rows` (a Pallas TPU kernel; `interpret` off the
chip) and `route_rows_xla` (plain `jax.numpy`, for the XLA histogram
families).  Both return the integers the per-pick loop returns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_hist import LANE
from .split import MISSING_NAN, bin_goes_left

Array = jax.Array

# a record's fields: the leaf whose rows it routes (NO_LEAF: none), the
# split column with its bin count and missing type, the threshold bin,
# `default_left`, the value a row takes on the left and on the right
REC_FIELDS = 8
_LEAF, _FEAT, _NB, _MISSING, _THR, _DL, _IF_LEFT, _IF_RIGHT = range(8)
# matches no row: leaves count from 0, a mesh's pad rows carry -1
NO_LEAF = -2
# a left set over 256 bins: bit j of word w says whether bin 32 w + j goes
# left
SET_WORDS = 8
SET_BINS = 32 * SET_WORDS

# rows a grid step moves (one DMA a block; the last step may be short),
# and rows a compute chunk inside it works on at once.  A step costs
# about 0.35 us whatever it moves and a chunk's chain from the bins to the
# stored ids is latency that only the next columns of the same chunk hide
# (one pass alone at [13, 83,886,080], ms: tile 8,192 / 16,384 / 32,768
# at chunk 2,048: 8.26 / 7.25 / 6.93; at chunk 4,096: 7.15 / 5.52 / 4.82;
# tile 32,768 at chunk 8,192 / 16,384: 4.31 / 4.32; PERF.md section 6,
# PR 34); a tile of 65,536 rows x 128 columns is refused (VMEM)
ROUTE_TILE = 32768
ROUTE_CHUNK = 8192

# columns up to which one pass over ALL columns beats the per-pick loop's
# one bin row a pick (PERF.md section 6, PR 34: from 68 columns up the
# pass costs about 1.6 ps a row a column, a pick 44 ps a row, and a pass
# routes 5.5 picks on average: it turns near 140 columns)
ROUTE_MAX_COLUMNS = 128


def pick_records(live, leaf, feature, thr, default_left, nb, missing,
                 if_left, if_right) -> Array:
    """[K, REC_FIELDS] int32 records of K picks ([K] arrays; `nb`,
    `missing` the per-column tables): pick k routes the rows of `leaf[k]`
    where `live[k]`, by column `feature[k]`."""
    cols = [jnp.where(live, leaf, NO_LEAF), feature, nb[feature],
            missing[feature], thr, default_left, if_left, if_right]
    return jnp.stack([jnp.asarray(c).astype(jnp.int32) for c in cols],
                     axis=1)


def left_sets(feature, thr, default_left, nb, missing, is_cat,
              cat_mask) -> Array:
    """[K, SET_WORDS] int32: each pick's left set over all `SET_BINS`
    bins, by the ONE rule `bin_goes_left` evaluated on every bin ([K]
    arrays a field, `cat_mask` [K, MB] bool; `nb`, `missing` the
    per-column tables).  What the pass then does with a row is a look-up,
    for a numerical pick as for a categorical one."""
    b = jnp.arange(SET_BINS, dtype=jnp.int32)
    mask = jnp.pad(cat_mask, ((0, 0), (0, SET_BINS - cat_mask.shape[1])))

    def one(f, t, dl, c, m):
        return bin_goes_left(b, nb[f], missing[f], t, dl, c, m)

    go = jax.vmap(one)(feature, thr, default_left, is_cat, mask)
    bits = go.reshape(-1, SET_WORDS, 32).astype(jnp.uint32)
    return jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=2,
                   dtype=jnp.uint32).astype(jnp.int32)


def in_left_set(words: Array, b: Array) -> Array:
    """bool like `b` (int32 bins): `left_sets`' answer for each bin;
    `words` [..., SET_WORDS] against `b` [..., n] (one pick's words and
    its rows' bins, or [K, SET_WORDS] and [K, n]).  Elementwise: no
    table is gathered."""
    word = jnp.zeros_like(b)
    for w in range(SET_WORDS):
        word = jnp.where((b >> 5) == w,
                         jax.lax.slice_in_dim(words, w, w + 1, axis=-1),
                         word)
    return ((word >> (b & 31)) & 1) == 1


def batched_route_applies(bins_fm: Array) -> bool:
    """Whether one pass over all columns serves these bins: codes exact
    in bf16 (the kernel selects a pick's bin row on the MXU) and few
    enough columns that reading them all beats a bin row a pick."""
    return bins_fm.dtype == jnp.uint8 and \
        bins_fm.shape[0] <= ROUTE_MAX_COLUMNS


def route_rows_xla(bins_fm: Array, leaf_id: Array, rec: Array,
                   fill=None, sets=None) -> Array:
    """[N] int32: a row of `rec[k]`'s leaf takes the record's value of
    its side, any other row `fill` (None: its `leaf_id`).  A static
    unroll over the records, elementwise over [N]: one fusion.  With
    `sets` (`left_sets`) a row's side is looked up in its record's set."""
    out = leaf_id if fill is None else jnp.full_like(leaf_id, fill)
    for k in range(rec.shape[0]):
        r = rec[k]
        b = jnp.take(bins_fm, r[_FEAT], axis=0).astype(jnp.int32)
        go_left = bin_goes_left(b, r[_NB], r[_MISSING], r[_THR],
                                r[_DL] != 0) if sets is None \
            else in_left_set(sets[k], b)
        out = jnp.where(leaf_id == r[_LEAF],
                        jnp.where(go_left, r[_IF_LEFT], r[_IF_RIGHT]), out)
    return out


def _route_kernel(rec_ref, *refs, fill, chunk: int):
    """One grid step: `chunk` rows at a time, the K records' bin rows by
    ONE one-hot [K, F] x [F, chunk] product (a cell is one code times 1
    plus zeros, and a uint8 code is exact in bf16), so that every compare
    after it works on whole [K, chunk] registers and not on one sublane
    of eight; then the records' values summed over K: at most one record
    holds a row, the others add 0.  `refs`: the bins, the ids and the
    output, behind the records' left sets where the call has them."""
    sets_ref = refs[0] if len(refs) == 4 else None
    bins_ref, lid_ref, out_ref = refs[-3:]
    rec = rec_ref[:]                                     # [K, REC_FIELDS]
    k_n, f_n = rec.shape[0], bins_ref.shape[0]

    def col(j):
        return rec[:, j:j + 1]                           # [K, 1]

    sel = (col(_FEAT) == jax.lax.broadcasted_iota(
        jnp.int32, (k_n, f_n), 1)).astype(jnp.float32)  # [K, F]
    # a row a record holds has that record's leaf as its id, so what the
    # record adds to the default is a constant a side
    leaf = col(_LEAF)
    held = leaf if fill is None else fill
    add_left, add_right = col(_IF_LEFT) - held, col(_IF_RIGHT) - held
    if sets_ref is None:
        # `split.bin_goes_left`'s rule on the product's f32 codes: the
        # NaN bin (the column's last, where its missing type is NaN)
        # follows `default_left`, so its rows take a code left of every
        # threshold or right of all of them and one compare routes every
        # row
        thr = col(_THR).astype(jnp.float32)
        nan_bin = jnp.where(col(_MISSING) == MISSING_NAN, col(_NB) - 1,
                            -1).astype(jnp.float32)
        nan_code = jnp.where(col(_DL) != 0, -1.0, 256.0)

    def one(c, carry):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        bins = bins_ref[:, rows].astype(jnp.int32).astype(jnp.float32)
        b = jax.lax.dot_general(
            sel, bins, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
        lid = lid_ref[:, rows]                           # [1, chunk]
        if sets_ref is None:
            b = jnp.where(b == nan_bin, nan_code, b)
            go_left = b <= thr
        else:
            go_left = in_left_set(sets_ref[:], b.astype(jnp.int32))
        add = jnp.where(go_left, add_left, add_right)
        base = lid if fill is None else jnp.full_like(lid, fill)
        out_ref[:, rows] = base + jnp.sum(
            jnp.where(lid == leaf, add, 0), axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, lid_ref.shape[1] // chunk, one, 0)


@functools.partial(jax.jit, static_argnames=("fill", "interpret"))
def route_wave_rows(bins_fm: Array, leaf_id: Array, rec: Array, fill=None,
                    interpret: bool = False, sets=None) -> Array:
    """`route_rows_xla` as one Pallas pass over `[F, tile]` bin blocks
    (uint8 bins): (F + 8) bytes a row for K picks.  With `fill` None the
    new ids are written over `leaf_id` (aliased).  Without `sets` the
    call has no such operand: a model without categorical columns runs
    the program it ran before there were any."""
    f, n = bins_fm.shape
    leaf_id = leaf_id.astype(jnp.int32)
    # nothing is padded or copied: the last step (the only one of a small
    # table) may hold fewer rows than a tile; what it reads beyond the
    # rows is not defined, and what it writes there is dropped
    chunk = min(ROUTE_CHUNK, -(-n // LANE) * LANE)
    tile = min(ROUTE_TILE, -(-n // chunk) * chunk)
    row_spec = pl.BlockSpec((1, tile), lambda r: (0, r))
    whole = [rec] if sets is None else [rec, sets]
    out = pl.pallas_call(
        functools.partial(_route_kernel, fill=fill, chunk=chunk),
        grid=(pl.cdiv(n, tile),),
        in_specs=[pl.BlockSpec(a.shape, lambda r: (0, 0)) for a in whole]
        + [pl.BlockSpec((f, tile), lambda r: (0, r)), row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        input_output_aliases={len(whole) + 1: 0} if fill is None else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="route_wave_rows",
    )(*whole, bins_fm, leaf_id[None, :])
    return out[0]
