"""Pallas TPU histogram kernel — the flagship hot op.

TPU-native replacement for the reference's histogram constructors
(ref: src/io/dense_bin.hpp `DenseBin::ConstructHistogram` [CPU, per-thread
buffers]; src/treelearner/cuda/cuda_histogram_constructor.cu
`CUDAConstructHistogramKernel` [shared-memory block histograms + atomics]).

TPUs have no atomics, and XLA lowers a 256-segment scatter-add to a SERIAL
update loop.  So scatter-add becomes dense compute the MXU can chew: for
each (row-tile, lane group) the kernel materialises a one-hot comparison of
the bin column against the bin axis and contracts it with the payload in
ONE default-precision bf16 matmul.  Per-tile accumulators live in VMEM and
revisit across the row-tile grid axis, exactly the role of the CUDA
kernel's shared-memory histograms (grid-level reduction replaces
atomicAdd).

What a call costs (PERF.md sections 6 and 7.5, PR 30 and 32; one TPU
v5e).  Not bytes: a call runs at 1.8 % of its HBM bound.  Until PR 30 the
one-hot was built as `bins[:, None] == lane iota`, which spreads every
bin row from lanes to sublanes with one XLU permute per 8 rows and
column: a flat 0.57 ns per (row, column) whatever the bin count (128
lanes cost what 256 did).  The operand is now built TRANSPOSED,
`bins[None, :] == sublane iota` ([lanes, N_t]; a bin row never leaves the
lanes it arrives on), and the dot contracts both operands' last axis,
the MXU loading the 0/1 tile transposed.  That left the MXU's slots as
the bound — 0.74 ps per (row, contracted lane) plus 1.0 us a 2048-row
tile (4.19M rows: 1,664 lanes 7.26 ms, 3,328 lanes 12.44 ms; the packed
1,920 lanes 9.24 ms, 1.15 ps all in) — so the lanes count, and the LANE
PLAN (`lane_plan`) packs the columns of few bins into shared 128-lane
multi-hot groups: the airline table contracts 1,920 lanes a row, not
3,328.  Same products, same order: sums bit-equal throughout.  At S = 8
that is 70 % of the MXU's bf16 peak on a 72-row LHS, so what is left is
the ROWS: past a tree's first waves most rows of a pass are in no slot
(73 % / 90 % of all rows contracted in the benchmark's cells).  A pass
whose every tile holds at most 128, 256 or 512 active rows
(`COMPACT_CAPS`) lands them in that many columns with one selection
matmul — a one-hot like any other — and contracts those for the tile's
2048 (`_hist_kernel_multi_compact`): the same call takes 3.2 ms / 4.9 ms
for 9.4 ms at 256 / 512 whatever the share of active rows, the ranking
fusion included, and over 83,886,080 rows a 128 call 0.72x of a 256 one.
Both the selection one-hot ([cap, N_t]) and the histogram dot (cap
contracted columns) grow with the capacity, so most of a compacting
call's price does; below 128 columns the per-tile rest is what is left
(64 read 0.95x of 128), so 128 is the smallest (PERF.md section 6).

Precision design (replaces the old Precision.HIGHEST formulation, which
cost 3-6 MXU passes): the one-hot operand is {0,1} — exact in bf16 at any
precision — and each f32 payload channel is split into THREE bf16 terms
(p = p1 + p2 + p3, each the bf16 rounding of the residual), giving >= f32
accuracy from a single matmul: the LHS is
[9, N_t] = (g1, g2, g3, h1, h2, h3, w1, w2, w3), and the MXU processes up
to 128 LHS rows per pass, so the 3-way splits cost nothing over an
unsplit payload.

Across row tiles the f32 kernels keep TWO limbs a cell (hi + lo): a plain
VMEM accumulator takes `FLUSH_TILES` tiles (8-bit terms over 2048 rows and
16 tiles add up in 23 bits: no rounding yet), then is folded error-free
into the (hi, lo) outputs.  One f32 accumulator over all tiles drifted by
N/2 ulps on near-equal addends (+861 on a 10.4M hessian sum over 40,960
tiles), which sibling subtraction handed down to the smallest leaves.

The quantized variant (`pallas_histogram_quantized`) feeds the integer
gradient lattice of `use_quantized_grad` (ref:
cuda_gradient_discretizer.cu + the packed 32-bit histogram atomics of the
CUDA kernel) directly: LHS [3, N_t] = (gq·w, hq·w, w) — small integers,
exact in bf16 — one matmul, rescaled to (Σg, Σh, count) afterwards.

Layouts (all chosen for the (sublane, lane=128) tiling):
 - bins stay uint8 [F, N] in HBM, rows on lanes; the kernel compares
   them where they lie (see above).
 - the payload rows are passed pre-split+masked [R, N] as f32 refs whose
   VALUES are bf16-representable (see the in-kernel comment: real bf16
   refs make Mosaic round the RESULT to bf16).
 - the kernel writes [F, R, MB] (lane dim = bins), or under a lane plan
   [R, L]: one 128-aligned lane slice a group, a packed column at its
   lane offset inside its group's slice (`_columns_of_plan` cuts the
   columns back out to [F, R, MB]); the wrapper recombines the split
   rows to the [F, MB, 3] the split finder expects.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.spans import summed_span
from ..utils.log import LightGBMError
from .histogram import hist_value, limb_add

Array = jax.Array

ROW_TILE = 2048

def _split3(x: Array):
    """f32 -> three f32 terms with bf16-REPRESENTABLE values and
    x == x1 + x2 + x3 to >= f32 accuracy (each term is the bf16 rounding
    of the remaining residual; bf16 keeps 8 mantissa bits, so three terms
    carry ~27).  `reduce_precision` rather than astype round-trips: XLA's
    TPU simplifier elides f32->bf16->f32 conversion pairs, which would
    silently feed RAW f32 into the kernel's truncating DEFAULT-precision
    dot (observed: ~2^-9-relative histogram error)."""
    x1 = jax.lax.reduce_precision(x, 8, 7)
    r1 = x - x1
    x2 = jax.lax.reduce_precision(r1, 8, 7)
    x3 = jax.lax.reduce_precision(r1 - x2, 8, 7)
    return x1, x2, x3


# row tiles a plain f32 accumulator takes between two-limb folds
FLUSH_TILES = 16


LANE = 128


def lane_plan(num_bins, max_bin: int):
    """The f32 kernel's static lane plan, from the bin count of every
    column it will see (the mappers' `num_bin`; under EFB the bundle
    columns' widths): which columns share one one-hot operand.

    The kernel's price is flat per row and CONTRACTED LANE, whatever a
    column's bin count, so a column of 2 bins on `max_bin` lanes of its
    own pays for 256.  First-fit in column order: a column of at most
    128 bins joins the open 128-lane group at the next free lane offset
    if its bins fit, else opens a new group; a wider column is a group of
    its own on `max_bin` lanes (rounded up to whole 128-lane tiles), as
    without a plan.  A group's operand is a multi-hot: one hot lane a
    member, at disjoint offsets, so every cell is still the sum of the
    same products in the same order (bit-equal sums).

    Returns a hashable tuple of groups `(lanes, members)`, a member
    `(column, lane_offset, num_bin)`; or None — today's program, every
    column on its own `max_bin` lanes — where that saves no lane (no
    column of 128 bins or fewer) or bins are uint16 (`max_bin` > 256).
    No parameter: the layout depends only on the observed bin counts."""
    if max_bin > 2 * LANE:
        return None
    wide = -(-max_bin // LANE) * LANE
    groups, open_members, free = [], None, 0     # free: of the open group
    for col, nb in enumerate(num_bins):
        nb = max(int(nb), 1)
        if nb > LANE:
            groups.append((wide, ((col, 0, nb),)))
            continue
        if nb > free:
            open_members, free = [], LANE
            groups.append((LANE, open_members))
        open_members.append((col, LANE - free, nb))
        free -= nb
    plan = tuple((lanes, tuple(members)) for lanes, members in groups)
    if plan_lanes(plan) == len(num_bins) * wide:     # no lane saved
        return None
    return plan


def plan_lanes(plan) -> int:
    """Lanes a row contracted under `plan` (gauge `hist.lanes_per_row`)."""
    return sum(lanes for lanes, _ in plan)


def plan_columns(plan) -> list:
    """`plan`'s columns in column order: (column, first lane in the
    kernel's [.., L] sums, num_bin)."""
    cols, lane0 = [], 0
    for lanes, members in plan:
        cols += [(c, lane0 + off, nb) for c, off, nb in members]
        lane0 += lanes
    return sorted(cols)


# Scoped VMEM a Mosaic kernel gets by default, and what one call needs of
# it (`_vmem_need`): its sums block ([S*R0 rows, lanes] f32, rows padded to
# 8 sublanes) up to three times — the accumulator scratch and both limbs'
# output blocks (XLA keeps an output in VMEM itself where it finds room, so
# a larger block may compile alone and be refused inside a grower) — and a
# tile's operands (the masked LHS, the one-hot, a compacting body's
# selection), which grow with the LHS's rows.  Read off the v5e compiler
# (PERF.md section 6, PR 33: "scoped allocation with size .. and limit
# 16.00M"): beside three blocks it took 0.5-1.3 MiB at S = 8 (72 rows)
# and 2.9 MiB at S = 14 (126 rows): 24 KiB a row covers both.  At S = 8 the
# 68 columns of a four-shard Criteo table (4.7 MiB packed) compile as one
# block in every body; 86 columns (6.0 MiB) are refused.  A call of ONE
# slot (9 rows, 16 with the sublane padding) takes far more a lane than
# three 16-row blocks: 21.3 MiB at 40,960 lanes and 29.1 MiB at 56,320
# (535 B a lane, read off the v5e compiler; the single-leaf probe of 220
# columns of 255 bins under their plan, 51,712 lanes, was refused at 26.3
# MiB as one block), so a block is priced at no fewer than
# `VMEM_MIN_ROWS` rows (576 B a lane).
VMEM_BYTES = 16 << 20
VMEM_MIN_ROWS = 48


def _vmem_need(sum_rows: int, lanes: int) -> int:
    rows = -(-sum_rows // 8) * 8
    return 3 * max(rows, VMEM_MIN_ROWS) * lanes * 4 + rows * (24 << 10)


def column_blocks(num_col: int, sum_rows: int, max_bin: int, plan=None,
                  feat_tile: int = 0):
    """The column blocks one histogram pass is made in, a kernel call
    each: `((first column, end column, lane plan of the block), ...)`.

    One block wherever a call over all columns fits the scoped VMEM
    (`_vmem_need` <= `VMEM_BYTES`; then `plan` itself is the block's);
    else the fewest contiguous blocks of near-equal column counts that
    each fit.  A lane
    group never spans blocks: each block's plan is `lane_plan` of its own
    columns' bin counts (read back off `plan`), numbered from the block's
    first column, or None where that saves the block no lane.  Any
    grouping sums the same products in the same order, so the blocks'
    sums are bit-equal to one block's.  No parameter: `feat_tile` > 0
    forces blocks of at most that many columns (timing a call alone,
    tests)."""
    wide = -(-max_bin // LANE) * LANE
    num_bins = None if plan is None else \
        [nb for _, _, nb in plan_columns(plan)]

    def blocks_of(k):
        base, extra = divmod(num_col, k)
        out, c0 = [], 0
        for j in range(k):
            c1 = c0 + base + (j < extra)
            sub = plan if k == 1 or plan is None else \
                lane_plan(num_bins[c0:c1], max_bin)
            out.append((c0, c1, sub))
            c0 = c1
        return tuple(out)

    def fits(blocks):
        return all(_vmem_need(
            sum_rows, (c1 - c0) * wide if sub is None else plan_lanes(sub))
            <= VMEM_BYTES for c0, c1, sub in blocks)

    if feat_tile > 0:
        return blocks_of(-(-num_col // min(feat_tile, num_col)))
    for k in range(1, num_col):
        blocks = blocks_of(k)
        if fits(blocks):
            return blocks
    return blocks_of(max(num_col, 1))


def assert_bins_in_plan(bins_fm: Array, plan) -> None:
    """Debug check (`GrowerSpec.debug_checks`): every bin below its
    column's `num_bin`.  Without a plan an out-of-range bin lands in its
    own column's dead lanes; packed it would alias a neighbour's cells.
    A host callback, like `quantized_lattice_rows`' weight check."""
    columns = plan_columns(plan)

    def _check(top):
        bad = [(c, int(top[c]), nb) for c, _, nb in columns if top[c] >= nb]
        if bad:
            raise FloatingPointError(
                "histogram lane plan precondition violated: (column, "
                f"largest bin, num_bin) {bad} — a bin at or over its "
                "column's num_bin would be summed into a neighbouring "
                "column's cells of the packed lane group")
    jax.debug.callback(_check, bins_fm.max(axis=1))


def _lane_groups(plan, f_t: int, mb: int):
    """(groups, cells) of a kernel body: the lane groups it contracts (a
    group `(lanes, members)` as in `lane_plan`; without a plan every
    column its own group of `mb` lanes) and each group's cell of the sums
    block ([F_t, S*R0, MB] without a plan, [S*R0, L] with one)."""
    if plan is None:
        return (tuple((mb, ((f, 0, mb),)) for f in range(f_t)),
                tuple(range(f_t)))
    cells, lane0 = [], 0
    for lanes, _ in plan:
        cells.append((slice(None), slice(lane0, lane0 + lanes)))
        lane0 += lanes
    return plan, tuple(cells)


def _zero_sums_first(r, hi_ref, lo_ref, acc_ref):
    @pl.when(r == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        hi_ref[:] = jnp.zeros_like(hi_ref)
        lo_ref[:] = jnp.zeros_like(lo_ref)


def _contract_groups(acc_ref, lhs, bin_row, n_c: int, groups, cells):
    """One dot a lane group into `acc_ref`: `lhs` [S*R0, n_c] against the
    group's multi-hot of the bin rows `bin_row(f)` ([1, n_c] i32)."""
    bin_ids = {}
    for (lanes, members), cell in zip(groups, cells):    # static unroll
        if lanes not in bin_ids:
            bin_ids[lanes] = jax.lax.broadcasted_iota(
                jnp.int32, (lanes, n_c), 0)
        # the operand is built TRANSPOSED, [lanes, n_c]: a bin row stays
        # on the lanes it arrives on and is compared against a sublane
        # iota; the dot contracts both operands' last axis (the MXU loads
        # the transposed tile natively).  `b[:, None] == lane iota` cost
        # one XLU permute per 8 rows and column to spread the row over
        # sublanes — the whole price of a call before PR 30
        hot = None
        for f, off, _ in members:
            b = bin_row(f)                               # [1, n_c]
            if off:
                b = b + off
            one = b == bin_ids[lanes]
            hot = one if hot is None else hot | one
        acc_ref[cell] += jax.lax.dot_general(
            lhs, hot.astype(jnp.float32), (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)


def _fold_sums(r, n_rt: int, hi_ref, lo_ref, acc_ref, cells):
    @pl.when(((r + 1) % FLUSH_TILES == 0) | (r == n_rt - 1))
    def _flush():
        for cell in cells:                           # a group at a time
            hi_ref[cell], lo_ref[cell] = limb_add(hi_ref[cell], lo_ref[cell],
                                                  acc_ref[cell])
        acc_ref[:] = jnp.zeros_like(acc_ref)


def _hist_kernel_multi(bins_ref, pw_ref, lid_ref, slots_ref, hi_ref, lo_ref,
                       acc_ref, *, mb: int, n_rt: int, plan=None,
                       cols=None):
    """Multi-leaf grid cell with IN-KERNEL leaf masking — THE production
    kernel: every public f32 entry point (single-leaf included, via a
    mask-derived leaf id) lowers to this body or its compacting twin
    (`_hist_kernel_multi_compact`, the same group loop over fewer rows),
    so `probe()` gates exactly the code that training runs.

    bins_ref: [F, N_t], every column, of which this call sums the block
    `cols` = (first, end) (`column_blocks`; None: all, F_t = F);
    pw_ref: [R0, N_t] base payload rows (9
    f32-split); lid_ref: [1, N_t] i32 row→leaf; slots_ref: [1, S] i32 leaf
    slots.  One dot a lane GROUP (`lane_plan`, over the block's columns
    numbered from its first): without a plan every
    column is its own group of `mb` lanes and hi_ref, lo_ref are
    [F_t, S*R0, MB] two-limb sums; with one they are [S*R0, L], each
    group a static 128-aligned lane slice, its operand the OR of its
    members' one-hots at their lane offsets.  acc_ref (VMEM scratch, the
    same block) takes every tile's dot plainly: after each `FLUSH_TILES`
    tiles, and after the last, it is added error-free into (hi_ref,
    lo_ref) and cleared.

    The payload rides f32 refs whose VALUES are bf16-representable:
    DEFAULT precision on TPU truncates f32 operands to bf16 for the MXU
    (one pass) — exact here by construction — and accumulates f32.
    (Passing actual bf16 refs makes Mosaic emit a bf16 RESULT despite
    preferred_element_type, which rounds the sums.)

    Building the [S*R0, N_t] masked LHS in VMEM (instead of materialising
    it in HBM as the first multi formulation did) removes ~5.5 ms of
    reshape/pad/select HBM traffic per 1M-row pass — the mask compare and
    select are VPU work overlapping the MXU dots.
    """
    r = pl.program_id(1)
    _zero_sums_first(r, hi_ref, lo_ref, acc_ref)
    n_t = bins_ref.shape[1]
    c0, c1 = cols or (0, bins_ref.shape[0])
    groups, cells = _lane_groups(plan, c1 - c0, mb)
    pw = pw_ref[:]                                   # [R0, N_t]
    lid = lid_ref[0, :]                              # [N_t] i32
    lhs = jnp.concatenate(
        [jnp.where((lid == slots_ref[0, s])[None, :], pw, 0.0)
         for s in range(slots_ref.shape[1])], axis=0)    # [S*R0, N_t]
    _contract_groups(
        acc_ref, lhs,
        lambda f: bins_ref[c0 + f:c0 + f + 1, :].astype(jnp.int32),
        n_t, groups, cells)
    _fold_sums(r, n_rt, hi_ref, lo_ref, acc_ref, cells)


# a row's code for the compacting body: its rank among its tile's active
# rows times 2**SLOT_BITS, plus its slot's index + 1 (MULTI_CHUNK < 16);
# -1 for a row in no slot
SLOT_BITS = 4


def _compact_rows(x: Array, code: Array, cap: int) -> Array:
    """[R, N_t] f32 rows x [1, N_t] i32 codes -> [R, cap]: the columns of
    `x` whose code is not negative, each at column `code >> SLOT_BITS`
    (its rank among them), zeros beyond.  One matmul against the
    selection one-hot, built transposed as the histogram's is."""
    pt = (code >> SLOT_BITS) == jax.lax.broadcasted_iota(
        jnp.int32, (cap, x.shape[1]), 0)             # [cap, N_t]
    return jax.lax.dot_general(
        x, pt.astype(jnp.float32), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)


def _hist_kernel_multi_compact(bins_ref, pw_ref, code_ref, hi_ref, lo_ref,
                               acc_ref, *, mb: int, n_rt: int, plan,
                               cap: int, s_n: int, cols=None):
    """`_hist_kernel_multi` over the tile's ACTIVE rows only (rows in one
    of the call's slots), for a pass whose every tile holds at most `cap`
    of them (`_run_kernel_multi` sees to that).  A selection matrix is a
    one-hot, and one-hots transposed are what this kernel contracts
    best: with `dest` a row's rank among its tile's active rows (-1 in
    no slot), `PT = (dest[None, :] == sublane iota)` is [cap, N_t], and
    `X . PT^T` over X = (payload rows, slot index, bin rows) lands the
    active rows, in order, in `cap` columns.  Every cell of that product
    is ONE product plus zeros and every value of X is exact in bf16
    (uint8 bins, the split terms by construction, a slot index under
    16), so the compaction is exact.  Then today's body over `cap`
    columns for N_t: the unfilled ones are all zero (payload 0, slot 0)
    and add nothing.

    code_ref: [1, N_t] i32, `_row_codes`' (dest << SLOT_BITS | slot + 1),
    -1 where inactive (the ranks are XLA's: computed in the kernel, a
    matmul and four sublane roll-adds a tile, the chain from ids to PT
    is exposed latency — a C256 call took 0.0557 s for 0.0417 s, against
    0.0055 s for the fusions that write the codes; PERF.md section 6,
    PR 32); the sums blocks and the folds are `_hist_kernel_multi`'s."""
    r = pl.program_id(1)
    _zero_sums_first(r, hi_ref, lo_ref, acc_ref)
    r0 = pw_ref.shape[0]
    c0, c1 = cols or (0, bins_ref.shape[0])
    groups, cells = _lane_groups(plan, c1 - c0, mb)
    code = code_ref[:]                               # [1, N_t]
    slot = (code & ((1 << SLOT_BITS) - 1)).astype(jnp.float32)
    bins = bins_ref[:].astype(jnp.int32).astype(jnp.float32)
    if (c0, c1) != (0, bins_ref.shape[0]):           # this call's block
        bins = bins[c0:c1]
    comp = _compact_rows(jnp.concatenate([pw_ref[:], slot, bins], axis=0),
                         code, cap)                  # [R0 + 1 + F_t, cap]
    pw_c, slot_c = comp[:r0], comp[r0:r0 + 1]
    bins_c = comp[r0 + 1:].astype(jnp.int32)         # [F_t, cap]
    lhs = jnp.concatenate(
        [jnp.where(slot_c == s + 1.0, pw_c, 0.0) for s in range(s_n)],
        axis=0)                                      # [S*R0, cap]
    _contract_groups(acc_ref, lhs, lambda f: bins_c[f:f + 1, :], cap,
                     groups, cells)
    _fold_sums(r, n_rt, hi_ref, lo_ref, acc_ref, cells)


def _combine_terms(hi: Array, lo: Array):
    """[.., 3 terms, MB] limbs of the three bf16 split terms -> the
    channel's two limbs [.., MB]: one error-free chain, term 1 first."""
    h, l = limb_add(hi[..., 0, :], lo[..., 0, :], hi[..., 1, :],
                    lo[..., 1, :])
    return limb_add(h, l, hi[..., 2, :], lo[..., 2, :])


# Active rows a tile that a compacting body holds, ascending: a pass runs
# the smallest that holds its fullest tile, else the full body.  A
# capacity is kept where one call alone on the chip (PERF.md section 6)
# takes at most 0.8x of the next larger body's at the largest share of
# active rows it admits (the full body's, above 512).
COMPACT_CAPS = (128, 256, 512)


def hist_bodies(row_tile: int = ROW_TILE):
    """The f32 kernel's bodies in the order `pallas_histogram_multi_rows(
    .., count_bodies=True)` counts them: (name, rows contracted a tile).
    "full" first, then a "c<capacity>" each compacting capacity."""
    return (("full", row_tile),) + tuple(
        (f"c{c}", c) for c in COMPACT_CAPS if c < row_tile)


def _slot_of_rows(leaf_id: Array, slots: Array):
    """(slot, ambiguous): [N] i32, 1 + the index of the slot a row's leaf
    is listed in (0: in none), and whether some row's leaf is listed
    twice (the full body then sums it into both slots; a row has one
    code).  A static unroll over the S <= MULTI_CHUNK slots, elementwise
    over [N]: it fuses into whatever reads it."""
    slot = jnp.zeros(leaf_id.shape, jnp.int32)
    listed = jnp.zeros(leaf_id.shape, jnp.int32)
    for s in range(slots.shape[0]):
        hit = leaf_id == slots[s]
        slot = jnp.where(hit, s + 1, slot)
        listed = listed + hit
    return slot, jnp.max(listed, initial=0) > 1


def _row_codes(leaf_id: Array, slots: Array, row_tile: int) -> Array:
    """[N] i32 codes for `_hist_kernel_multi_compact` (N a multiple of
    `row_tile`, `row_tile` of LANE): (rank among the tile's active rows)
    << SLOT_BITS | (slot index + 1), -1 for a row in no slot.  The ranks
    are an exclusive prefix count within each tile: within each 128-row
    chunk by one upper-triangular bf16 matmul (0/1 operands, counts to
    128: exact), the chunks' offsets by a cumsum over row_tile/128
    totals."""
    slot, _ = _slot_of_rows(leaf_id, slots)
    active = slot > 0
    k = jnp.arange(LANE)
    upper = (k[:, None] <= k[None, :]).astype(jnp.bfloat16)
    seen = jnp.dot(active.reshape(-1, LANE).astype(jnp.bfloat16), upper,
                   preferred_element_type=jnp.float32)  # inclusive, a chunk
    seen = seen.astype(jnp.int32).reshape(-1, row_tile // LANE, LANE)
    chunk = seen[:, :, -1]                               # [tiles, chunks]
    dest = seen - 1 + (jnp.cumsum(chunk, axis=1) - chunk)[:, :, None]
    return jnp.where(active, (dest.reshape(-1) << SLOT_BITS) | slot, -1)


def _run_kernel_multi(bins_fm: Array, pw0: Array, leaf_id: Array,
                      slots: Array, max_bin: int, row_tile: int,
                      feat_tile: int, interpret: bool, plan=None,
                      body: str = None):
    """pallas_call driver for the in-kernel-masked multi-leaf kernel:
    [F, N] bins x [R0, N] payload x [N] leaf ids x [S] slots ->
    (hi, lo, body): the sums, each [F, S*R0, MB] f32, and the index in
    `hist_bodies(row_tile)` of the body that ran.  With a lane `plan` the
    kernel sums into [S*R0, L] and each column's `[offset, offset +
    num_bin)` lanes are sliced back out here and zero-padded to MB, so
    callers see one layout.

    COLUMN BLOCKS (`column_blocks`): a pass is one kernel call while the
    sums of all columns fit the kernel's scoped VMEM, else a call a column
    block, each over its own columns of the same row tiles with its own
    lane plan; every call reads the whole [F, N_t] bin tile (a few KB a
    column) and sums only its block, so nothing is sliced or copied in
    HBM.  The body is chosen once a pass, the row codes and ranks of a
    compacting pass are made once and shared by the blocks, and every
    sum is bit-equal to one block's.

    WHICH BODY is read off the call's own rows: the fullest tile's count
    of active rows (rows whose leaf is in `slots`) picks the smallest
    compacting capacity that holds it, else the full body — no tile can
    overflow, so a table in an adversarial row order simply runs the
    full body, and nothing is approximate.  Statically full: uint16 bins
    (not exact in bf16), a `row_tile` that is no multiple of 128.  ONE
    body executes (`lax.switch`), its calls named
    `pallas_histogram_multi_rows_*`: trace readers select the kernel's
    custom-calls by that prefix.
    `body` (a name of `hist_bodies`) forces one body, `feat_tile` > 0
    column blocks of at most that many columns: for timing a call alone
    (`scripts/hist_lane_bound.py`) and for tests."""
    f, n = bins_fm.shape
    r0 = pw0.shape[0]
    s_n = slots.shape[0]
    n_pad = (-n) % row_tile
    if n_pad:
        pw0 = jnp.pad(pw0, ((0, 0), (0, n_pad)))
        bins_fm = jnp.pad(bins_fm, ((0, 0), (0, n_pad)))
        # padded rows carry leaf -1: matches no slot, contributes nothing
        leaf_id = jnp.pad(leaf_id, (0, n_pad), constant_values=-1)
    leaf_id = leaf_id.astype(jnp.int32)
    n_rt = (n + n_pad) // row_tile
    if plan is not None:
        planned = [c for c, _, _ in plan_columns(plan)]
        if planned != list(range(f)):
            raise ValueError(f"lane plan over columns {planned} for {f} "
                             "columns")
    blocks = column_blocks(f, s_n * r0, max_bin, plan, feat_tile)
    whole = len(blocks) == 1
    row_spec = pl.BlockSpec((1, row_tile), lambda j, r: (0, r))

    def call(kernel, row_args, row_specs):
        """`kernel` once a column block: the blocks' (hi, lo), flat."""
        out = ()
        for c0, c1, sub in blocks:
            if sub is None:
                shape = (c1 - c0, s_n * r0, max_bin)
                sums_spec = pl.BlockSpec(shape, lambda j, r: (j, 0, 0))
            else:
                shape = (s_n * r0, plan_lanes(sub))
                sums_spec = pl.BlockSpec(shape, lambda j, r: (0, 0))
            sums = jax.ShapeDtypeStruct(shape, jnp.float32)
            out += tuple(pl.pallas_call(
                functools.partial(kernel, plan=sub,
                                  cols=None if whole else (c0, c1)),
                grid=(1, n_rt),     # the sums block revisited a row tile
                in_specs=[
                    pl.BlockSpec((f, row_tile), lambda j, r: (j, r)),
                    pl.BlockSpec((r0, row_tile), lambda j, r: (0, r)),
                ] + row_specs,
                out_specs=[sums_spec, sums_spec],
                out_shape=[sums, sums],
                scratch_shapes=[pltpu.VMEM(shape, jnp.float32)],
                interpret=interpret,
            )(bins_fm, pw0, *row_args))
        return out

    def full(bins_fm, pw0, leaf_id, slots):
        return call(
            functools.partial(_hist_kernel_multi, mb=max_bin, n_rt=n_rt),
            [leaf_id[None, :], slots[None, :]],
            [row_spec, pl.BlockSpec((1, s_n), lambda j, r: (0, 0))])

    def compact(cap):
        def run(bins_fm, pw0, leaf_id, slots):
            return call(
                functools.partial(_hist_kernel_multi_compact, mb=max_bin,
                                  n_rt=n_rt, cap=cap, s_n=s_n),
                [_row_codes(leaf_id, slots, row_tile)[None, :]], [row_spec])
        return run

    bodies = hist_bodies(row_tile)
    if bins_fm.dtype != jnp.uint8 or row_tile % LANE:
        bodies = bodies[:1]
    names = [name for name, _ in bodies]
    caps = [rows for _, rows in bodies[1:]]
    runs = [full] + [compact(cap) for cap in caps]
    args = (bins_fm, pw0, leaf_id, slots)
    if body is None and not caps:
        body = "full"
    if body is not None:
        k = names.index(body)
        which = jnp.int32(k)
        out = runs[k](*args)
    else:
        slot, ambiguous = _slot_of_rows(leaf_id, slots)
        fullest = jnp.max(jnp.sum((slot > 0).reshape(n_rt, row_tile),
                                  axis=1, dtype=jnp.int32))
        fits = jnp.array(caps) >= fullest
        which = jnp.where(jnp.any(fits) & ~ambiguous,
                          1 + jnp.argmax(fits), 0).astype(jnp.int32)
        # a named call a body, for the trace: an HLO instruction takes
        # the name of the function it is traced in (a bare switch branch
        # would call the kernel `branch_1_fun`)
        out = jax.lax.switch(
            which, [jax.named_call(fn, name="pallas_histogram_multi_rows_"
                                   + nm) for fn, nm in zip(runs, names)],
            *args)
    his, los = [], []
    for (_, _, sub), hi, lo in zip(blocks, out[0::2], out[1::2]):
        if sub is not None:
            hi = _columns_of_plan(hi, sub, max_bin)
            lo = _columns_of_plan(lo, sub, max_bin)
        his.append(hi)
        los.append(lo)
    hi, lo = (his[0], los[0]) if whole else \
        (jnp.concatenate(his), jnp.concatenate(los))
    if all(sub is None for _, _, sub in blocks):
        return hi, lo, which
    # the barrier hands callers two materialised arrays, as the kernel
    # does without a plan: XLA then compiles what follows as it did,
    # whereas fused into the unpacking a grower's f32 reductions over
    # the bin axis associated differently (an internal node's stated
    # hessian sum moved by one ulp on the chip)
    return jax.lax.optimization_barrier((hi, lo)) + (which,)


def _columns_of_plan(sums: Array, plan, max_bin: int) -> Array:
    """Packed kernel sums [S*R0, L] -> [F, S*R0, MB]: each column's own
    lanes, zero beyond its `num_bin` as its dead lanes are unpacked."""
    return jnp.stack([jnp.pad(sums[:, lane:lane + nb],
                              ((0, 0), (0, max_bin - nb)))
                      for _, lane, nb in plan_columns(plan)])


def _hist_kernel_multi_i8(bins_ref, pw_ref, lid_ref, slots_ref, out_ref, *,
                          mb: int):
    """int8 variant of `_hist_kernel_multi` for the quantized lattice:
    int8 x int8 -> int32 MXU dots run at 2x the bf16 rate (v5e: 394 vs
    197 TOPS), and the lattice values (|gq|, hq <= num_grad_quant_bins
    <= 15, w in {0,1}) are exact in int8.  Measured 8.4 ms vs ~15 ms per
    1M x 28 x 256 pass.  int32 accumulation is exact up to ~134M rows
    per shard (2^31 / 16)."""
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    f_t, n_t = bins_ref.shape
    pw = pw_ref[:]                                   # [3, N_t] int8
    lid = lid_ref[0, :]                              # [N_t] i32
    s_n = slots_ref.shape[1]
    lhs = jnp.concatenate(
        [jnp.where((lid == slots_ref[0, s])[None, :], pw, 0)
         .astype(jnp.int8) for s in range(s_n)], axis=0)
    bin_ids = jax.lax.broadcasted_iota(jnp.int32, (n_t, mb), 1)
    for f in range(f_t):                             # static unroll
        b = bins_ref[f, :].astype(jnp.int32)
        onehot = (b[:, None] == bin_ids).astype(jnp.int8)
        out_ref[f] += jax.lax.dot_general(
            lhs, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)


def _run_kernel_multi_i8(bins_fm: Array, pw0: Array, leaf_id: Array,
                         slots: Array, max_bin: int, row_tile: int,
                         feat_tile: int, interpret: bool) -> Array:
    """int8 driver: [F, N] bins x [3, N] int8 lattice x [N] leaf ids x
    [S] slots -> [F, S*3, MB] int32."""
    f, n = bins_fm.shape
    r0 = pw0.shape[0]
    s_n = slots.shape[0]
    n_pad = (-n) % row_tile
    if n_pad:
        pw0 = jnp.pad(pw0, ((0, 0), (0, n_pad)))
        bins_fm = jnp.pad(bins_fm, ((0, 0), (0, n_pad)))
        leaf_id = jnp.pad(leaf_id, (0, n_pad), constant_values=-1)
    if feat_tile <= 0 or feat_tile > f:
        feat_tile = f
    f_pad = (-f) % feat_tile
    if f_pad:
        bins_fm = jnp.pad(bins_fm, ((0, f_pad), (0, 0)))
    n_rt = (n + n_pad) // row_tile
    n_ft = (f + f_pad) // feat_tile

    out = pl.pallas_call(
        functools.partial(_hist_kernel_multi_i8, mb=max_bin),
        grid=(n_ft, n_rt),
        in_specs=[
            pl.BlockSpec((feat_tile, row_tile), lambda j, r: (j, r)),
            pl.BlockSpec((r0, row_tile), lambda j, r: (0, r)),
            pl.BlockSpec((1, row_tile), lambda j, r: (0, r)),
            pl.BlockSpec((1, s_n), lambda j, r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((feat_tile, s_n * r0, max_bin),
                               lambda j, r: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((f + f_pad, s_n * r0, max_bin),
                                       jnp.int32),
        interpret=interpret,
    )(bins_fm, pw0, leaf_id.astype(jnp.int32)[None, :], slots[None, :])
    return out[:f]


@functools.partial(jax.jit, static_argnames=("max_bin", "impl", "row_tile",
                                             "feat_tile", "interpret",
                                             "plan", "body"))
def pallas_histogram(bins_fm: Array, payload: Array, row_mask: Array,
                     max_bin: int, *, impl: str = "onehot",
                     row_tile: int = ROW_TILE, feat_tile: int = 0,
                     interpret: bool = False, plan=None,
                     body: str = None) -> Array:
    """Drop-in replacement for histogram.leaf_histogram (same contract).

    Single-leaf = the f32 multi driver with a mask-derived leaf id
    (slot 0 = in-leaf, -1 = masked out) — the SAME `_hist_kernel_multi`
    block the strict grower runs (ops/grow.py `hist_of`, S=1), so the
    `probe()` that exercises this function gates the production kernel,
    not a legacy single-leaf body.

    Args:
      bins_fm: [F, N] uint8/uint16 bin matrix, feature-major.
      payload: [N, 3] f32 (grad*w, hess*w, w).
      row_mask: [N] bool leaf membership.
      max_bin: padded bin-axis size MB.
      impl: kept for call-site compatibility; every path now runs the
        single-pass split-bf16 multi kernel.
      plan: the columns' `lane_plan` (None: every column its own lanes).
      body: force one kernel body (`pallas_histogram_multi_rows`).
    Returns: [F, MB, 3] f32 — matches the segment-sum path to >= f32
      accuracy (the 3-term bf16 split carries ~27 mantissa bits per
      payload element, every sum two limbs).
    """
    del impl
    lid = jnp.where(row_mask, 0, -1).astype(jnp.int32)
    return hist_value(pallas_histogram_multi_rows(
        bins_fm, _split_payload9(payload), lid,
        jnp.zeros((1,), jnp.int32), max_bin, row_tile=row_tile,
        feat_tile=feat_tile, interpret=interpret, plan=plan, body=body)[0])


# MXU LHS capacity is 128 rows; leaves per kernel pass at 9 / 3 rows each
MULTI_CHUNK = 14        # f32 split-payload path: 14 * 9 = 126 rows
MULTI_CHUNK_Q = 42      # quantized path:         42 * 3 = 126 rows


def _split_payload9(payload: Array) -> Array:
    """[N, 3] f32 payload -> [9, N] bf16-representable carrier rows
    (g1..g3, h1..h3, w1..w3) — the split step of `pallas_histogram`,
    hoisted so multi-leaf callers split once and mask per leaf."""
    p3 = payload.T.astype(jnp.float32)
    g1, g2, g3 = _split3(p3[0])
    h1, h2, h3 = _split3(p3[1])
    w1, w2, w3 = _split3(p3[2])
    return jnp.stack([g1, g2, g3, h1, h2, h3, w1, w2, w3])


@functools.partial(jax.jit, static_argnames=("max_bin", "row_tile",
                                             "feat_tile", "interpret",
                                             "plan", "count_bodies"))
def pallas_histogram_multi(bins_fm: Array, payload: Array, leaf_id: Array,
                           slots: Array, max_bin: int, *,
                           row_tile: int = ROW_TILE, feat_tile: int = 0,
                           interpret: bool = False, plan=None,
                           count_bodies: bool = False):
    """Histograms of up to `len(slots)` leaves, filling the MXU.

    The economics that make this THE wave-grower kernel: the one-hot
    tile is the MXU's stationary operand and loading it is what a pass
    pays for (16 pushes a 128x128 tile against 9 streamed LHS vregs at
    S = 8), so up to 128 LHS rows ride at little more than the cost of 9:
    `MULTI_CHUNK` leaves' masked payloads in one [126, N_t] LHS give 14
    histograms for about the price of one — the reference's CUDA learner
    amortizes differently (per-leaf row subsets); on TPU amortizing
    across leaves in the M axis is the native form.  The reference's row
    subsets come back as the compacting bodies: where the call's slots
    hold few rows of every tile, a tile's one-hot is loaded for 256 or
    512 columns of active rows and not for its 2048 (one call alone on a
    v5e, 4.19M rows, S = 8: 9.4 ms full, 4.9 ms at 512, 3.2 ms at 256;
    1,024 would be 7.8 ms and is not kept).

    Masking AFTER the 3-way split is exact: each split term is zeroed or
    kept whole, so per-leaf sums still reconstruct >= f32 accuracy.

    Args:
      slots: [S] i32 leaf ids; pad entries (any value absent from
        leaf_id, canonically num_leaves) produce zero histograms.
    Returns: [S, F, MB, 3] f32 (with `count_bodies`, and the kernel's
      calls by body, as `pallas_histogram_multi_rows`).
    """
    out, calls = pallas_histogram_multi_rows(
        bins_fm, _split_payload9(payload), leaf_id, slots, max_bin,
        row_tile=row_tile, feat_tile=feat_tile, interpret=interpret,
        plan=plan, count_bodies=True)
    return (hist_value(out), calls) if count_bodies else hist_value(out)


def _limbs_from_terms(hi: Array, lo: Array, c: int, max_bin: int) -> Array:
    """Kernel sums [F, c*9, MB] (rows (channel, split-term) major) ->
    [c, F, MB, 6] limbs: the three terms of a channel chained into two."""
    f = hi.shape[0]
    h, l = _combine_terms(hi.reshape(f, c, 3, 3, max_bin),
                          lo.reshape(f, c, 3, 3, max_bin))  # [F, c, 3, MB]
    return jnp.concatenate([h, l], axis=2).transpose(1, 0, 3, 2)


@functools.partial(jax.jit, static_argnames=("max_bin", "row_tile",
                                             "feat_tile", "interpret",
                                             "plan", "count_bodies",
                                             "body"))
def pallas_histogram_multi_rows(bins_fm: Array, pw9: Array, leaf_id: Array,
                                slots: Array, max_bin: int, *,
                                row_tile: int = ROW_TILE,
                                feat_tile: int = 0,
                                interpret: bool = False,
                                plan=None, count_bodies: bool = False,
                                body: str = None):
    """`pallas_histogram_multi` with the payload ALREADY split to [9, N]
    carrier rows (`_split_payload9`) — the wave grower prepares the rows
    once per tree and reuses them for every wave's call, instead of
    re-splitting the loop-invariant payload inside the while_loop body.
    Returns [S, F, MB, 6], both limbs of every sum, for
    `ops/histogram.hist_sub` to carry through the subtractions
    (`hist_value` for the [.., 3] sums).  `plan` (static, `lane_plan` of
    the columns' bin counts; None = every column its own `max_bin`
    lanes) changes the lanes the kernel contracts, not one bit of what
    is returned.  Which rows it contracts the call reads off its own
    `leaf_id` (`_run_kernel_multi`): the same rows summed whatever body
    runs.  With `count_bodies` it returns (limbs, [len(hist_bodies())]
    i32 kernel calls by the body that ran) for the growers' counters;
    `body` forces one (timing, tests)."""
    S = slots.shape[0]
    outs = []
    calls = jnp.zeros((len(hist_bodies(row_tile)),), jnp.int32)
    for c0 in range(0, S, MULTI_CHUNK):
        c1 = min(S, c0 + MULTI_CHUNK)
        hi, lo, which = _run_kernel_multi(
            bins_fm, pw9, leaf_id, slots[c0:c1], max_bin, row_tile,
            feat_tile, interpret, plan, body)        # [F, (c1-c0)*9, MB]
        outs.append(_limbs_from_terms(hi, lo, c1 - c0, max_bin))
        calls = calls.at[which].add(1)
    out = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
    return (out, calls) if count_bodies else out


@functools.partial(jax.jit, static_argnames=("max_bin", "row_tile",
                                             "feat_tile", "interpret",
                                             "debug"))
def pallas_histogram_multi_quantized(bins_fm: Array, payload: Array,
                                     leaf_id: Array, slots: Array,
                                     max_bin: int, s_g: Array, s_h: Array,
                                     *, row_tile: int = ROW_TILE,
                                     feat_tile: int = 0,
                                     interpret: bool = False,
                                     debug: bool = False) -> Array:
    """Multi-leaf quantized histogram: up to 42 leaves x 3 integer rows
    fill one MXU pass (see `pallas_histogram_quantized` for the lattice
    invariants, `pallas_histogram_multi` for the batching economics).

    Returns: [S, F, MB, 3] f32.
    """
    return pallas_histogram_multi_quantized_rows(
        bins_fm, quantized_lattice_rows(payload, s_g, s_h, debug=debug),
        leaf_id, slots, max_bin, s_g, s_h, row_tile=row_tile,
        feat_tile=feat_tile, interpret=interpret)


def quantized_lattice_rows(payload: Array, s_g: Array, s_h: Array, *,
                           debug: bool = False) -> Array:
    """[N, 3] quantized payload -> [3, N] int8 lattice rows: |gq|, hq <=
    num_grad_quant_bins (booster-gated <= 15), w in {0, 1} — exact in
    int8, 2x MXU rate vs bf16.

    PRECONDITION: payload[:, 2] ∈ {0, 1} (see pallas_histogram_quantized)
    — fractional weights are binarized, corrupting the count channel.
    `debug=True` (booster: tpu_debug_nans) enforces it with a host
    callback: eager callers get the FloatingPointError directly; under
    jit it surfaces as a runtime error at the next sync point with the
    same "precondition violated" message (verified for the jitted path
    in test_debug_mode.py)."""
    if debug:
        def _check_w(w):
            import numpy as np
            bad = int(np.count_nonzero((w != 0.0) & (w != 1.0)))
            if bad:
                raise FloatingPointError(
                    f"quantized histogram precondition violated: {bad} "
                    "weight(s) outside {0, 1} — the int8 lattice "
                    "binarizes the count channel; quantized grads "
                    "require binary bagging weights (the Booster's "
                    "quant_ok gate excludes fractional-weight modes)")
        jax.debug.callback(_check_w, payload[:, 2])
    gq = jnp.round(payload[:, 0] / s_g).astype(jnp.int8)
    hq = jnp.round(payload[:, 1] / s_h).astype(jnp.int8)
    w = (payload[:, 2] != 0).astype(jnp.int8)
    return jnp.stack([gq, hq, w])


@functools.partial(jax.jit, static_argnames=("max_bin", "row_tile",
                                             "feat_tile", "interpret"))
def pallas_histogram_multi_quantized_rows(
        bins_fm: Array, pw3: Array, leaf_id: Array, slots: Array,
        max_bin: int, s_g: Array, s_h: Array, *, row_tile: int = ROW_TILE,
        feat_tile: int = 0, interpret: bool = False) -> Array:
    """Quantized multi with the int8 lattice ALREADY prepared
    (`quantized_lattice_rows`) — per-tree prep, per-wave calls."""
    S = slots.shape[0]
    outs = []
    for c0 in range(0, S, MULTI_CHUNK_Q):
        c1 = min(S, c0 + MULTI_CHUNK_Q)
        out = _run_kernel_multi_i8(bins_fm, pw3, leaf_id, slots[c0:c1],
                                   max_bin, row_tile, feat_tile,
                                   interpret)        # [F, (c1-c0)*3, MB]
        f = out.shape[0]
        out = out.reshape(f, c1 - c0, 3, max_bin).astype(jnp.float32)
        outs.append(out.transpose(1, 0, 3, 2))           # [c, F, MB, 3]
    out = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
    return jnp.stack([out[..., 0] * s_g, out[..., 1] * s_h, out[..., 2]],
                     axis=-1)


@functools.partial(jax.jit, static_argnames=("max_bin", "row_tile",
                                             "feat_tile", "interpret",
                                             "debug"))
def pallas_histogram_quantized(bins_fm: Array, payload: Array,
                               row_mask: Array, max_bin: int,
                               s_g: Array, s_h: Array, *,
                               row_tile: int = ROW_TILE, feat_tile: int = 0,
                               interpret: bool = False,
                               debug: bool = False) -> Array:
    """Quantized-gradient histogram: ONE bf16 matmul, integer-exact.

    Same contract as histogram.leaf_histogram_packed: payload carries
    (gq·s_g·w, hq·s_h·w, w) with integer gq/hq on the quantization lattice
    and w ∈ {0, 1}.

    PRECONDITION: the weight channel MUST be {0, 1} (bagging in/out).
    The lattice binarizes it (`w != 0 -> 1`), so a fractional weight
    (GOSS amplification, sample weights) would silently turn the count
    channel into row counts instead of weight sums — the Booster's
    `quant_ok` gate excludes those modes before routing here; direct
    callers must do the same.

    The integers are recovered exactly by division, fed
    to the MXU as bf16 (|gq| ≤ 2^8 — exactly representable), and the three
    (Σgq, Σhq, count) rows come out of a single [3, N_t]x[N_t, MB] pass
    (ref: the packed 32-bit atomics of cuda_histogram_constructor.cu — one
    operation covering grad+hess; here one matmul covers all three).
    """
    # single-leaf = the int8 multi driver with a mask-derived leaf id
    # (slot 0 = in-leaf, -1 = masked out): the lattice is exact in int8
    # and the int8 x int8 -> int32 dot runs at 2x the bf16 MXU rate
    pw = quantized_lattice_rows(payload, s_g, s_h,
                                debug=debug)         # [3, N] int8
    lid = jnp.where(row_mask, 0, -1).astype(jnp.int32)
    out = _run_kernel_multi_i8(bins_fm, pw, lid,
                               jnp.zeros((1,), jnp.int32), max_bin,
                               row_tile, feat_tile, interpret)
    out = out.reshape(out.shape[0], 3, max_bin).transpose(0, 2, 1)\
        .astype(jnp.float32)                         # [F, MB, 3]
    return jnp.stack([out[..., 0] * s_g, out[..., 1] * s_h, out[..., 2]],
                     axis=-1)


@dataclasses.dataclass(frozen=True)
class ProbeResult:
    """Verdict of a kernel probe; truthy iff the kernel may be used.
    `cause` is "" (ok), "compile" (the kernel raised — lowering, Mosaic
    or execution) or "mismatch" (it ran and disagreed with the
    reference); `detail` carries the compiler's message or the numbers,
    so a degradation event can say WHY."""
    ok: bool
    cause: str = ""
    detail: str = ""

    def __bool__(self) -> bool:
        return self.ok


_PROBE_OK = ProbeResult(True)
_PROBE_CACHE = {}


def probe_cached(max_bin: int = 256, num_feature: int = 28,
                 multi: bool = False, width: int = None,
                 quantized: bool = None, interpret: bool = False,
                 plan=None) -> ProbeResult:
    """probe(), memoised per (backend platform, shape, multi params,
    lane plan); a probe that runs is one `setup.probe` span."""
    key = (jax.devices()[0].platform, max_bin, num_feature, multi,
           width, quantized, interpret, plan)
    if key not in _PROBE_CACHE:
        with summed_span("setup.probe", max_bin=max_bin,
                         num_feature=num_feature, multi=multi, width=width,
                         quantized=quantized):
            _PROBE_CACHE[key] = probe(interpret=interpret, max_bin=max_bin,
                                      num_feature=num_feature, multi=multi,
                                      width=width, quantized=quantized,
                                      plan=plan)
    return _PROBE_CACHE[key]


def _refused(e: Exception) -> ProbeResult:
    return ProbeResult(False, "compile", f"{type(e).__name__}: {e}")


def _mismatch(what: str, got, want) -> ProbeResult:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return ProbeResult(False, "mismatch",
                           f"{what}: shape {got.shape} != {want.shape}")
    diff = np.abs(got - want)
    return ProbeResult(
        False, "mismatch",
        f"{what}: max |diff| {np.nanmax(diff):.6g} at "
        f"{np.unravel_index(np.nanargmax(diff), diff.shape)}, "
        f"{int(np.count_nonzero(got != want))}/{got.size} elements differ")


def probe(interpret: bool = False, max_bin: int = 256,
          num_feature: int = 28, multi: bool = False, width: int = None,
          quantized: bool = None, plan=None) -> ProbeResult:
    """Runtime check that the kernel compiles and matches a host count on
    the current backend — used by Booster to gate the TPU histogram path.
    On a real TPU (`interpret=False`) a kernel that RAISES is an
    error, not a degradation: `LightGBMError` carries Mosaic's message
    instead of training on segment-sum behind a log line.  A
    kernel that runs but disagrees numerically still returns a falsy
    result, with the numbers in `detail`.
    Probes at the PRODUCTION bin count / feature count / ROW_TILE (Mosaic
    regressions are usually shape-specific, so a toy-shape probe would
    pass and the real call would still crash), with a single row tile to
    keep the probe cheap.

    `multi=False` covers the single-leaf block shapes gating `hist_impl`
    (`quantized` False / True: the f32 / the int8 kernel alone — the
    booster names the family it will run, since the int8 kernel, which
    has no column blocks, is refused from 67 columns up where the f32
    one is not: PERF.md section 6, PR 33; None: both);
    `multi=True` covers ONLY the multi-leaf shapes gating the wave policy
    — kept separate so a wave-shape regression degrades the wave policy,
    not every strict-policy user's histogram path.  The wave grower runs
    exactly ONE multi block shape per spec (its root pass pads to the
    wave width), so pass `width` = min(wave_width, num_leaves - 1) and
    `quantized` = (hist_impl == 'pallas_q') to probe that exact shape;
    the defaults probe a full chunk of both families.  `plan` = the
    `lane_plan` the f32 kernel will run with: the probe compiles and
    compares THAT program (bins drawn below each column's `num_bin`) in
    place of the all-`max_bin` one.  The f32 program holds a kernel body
    a capacity (`hist_bodies`): the multi-leaf probe runs and compares
    each, on rows sparse enough for the dispatch to pick it, and reads
    back that it did.  The single-leaf probe compiles the full body alone
    (`body="full"`: a kernel compile is most of a probe's time, and every
    TPU booster pays for this one): the strict grower's compacting bodies
    are the wave's at one slot, compiled for the chip at that width by
    `tests/test_tpu_aot.py` and by the first round, compared with the
    full body's sums by `tests/test_pallas_hist_compact.py`."""
    try:
        return _probe_base(interpret, max_bin, num_feature, multi, width,
                           quantized, plan)
    except Exception as e:
        if not interpret and jax.devices()[0].platform == "tpu":
            raise LightGBMError(
                f"the Pallas histogram kernel (max_bin={max_bin}, "
                f"num_feature={num_feature}, multi={multi}, width={width}, "
                f"quantized={quantized}) failed on this TPU — "
                f"{type(e).__name__}: {e}") from e
        return _refused(e)


def _probe_base(interpret: bool, max_bin: int, num_feature: int,
                multi: bool, width: int, quantized: bool,
                plan=None) -> ProbeResult:
    """`probe`'s compile-and-compare body; any
    exception the kernel raises propagates to `probe`.  The reference is
    a float64 count on the host: nothing but the kernel compiles here."""
    import numpy as np

    rng = np.random.RandomState(0)
    n = ROW_TILE if not interpret else 128
    bins_np = rng.randint(0, max_bin, (num_feature, n))
    if plan is not None:
        # every column below its own num_bin, as the plan presumes
        bins_np %= np.array([nb for _, _, nb in plan_columns(plan)])[:, None]
    bins_np = bins_np.astype(np.uint8 if max_bin <= 256 else np.uint16)
    payload_np = rng.randn(n, 3).astype(np.float32)
    s = jnp.float32(0.25)
    pq_np = np.stack([np.round(payload_np[:, 0] * 8) * 0.25,
                      np.abs(np.round(payload_np[:, 1] * 8)) * 0.25,
                      np.ones(n)], axis=1).astype(np.float32)
    bins, payload, pq = (jnp.asarray(a) for a in (
        bins_np, payload_np, pq_np))
    row_tile = min(n, ROW_TILE)

    def some_rows(count):
        rows = np.zeros(n, bool)
        rows[rng.choice(n, count, replace=False)] = True
        return rows

    def host_hist(pay, rows):
        """[F, MB, 3] sums over `rows` (bool [n]), in float64."""
        return np.stack([np.stack([np.bincount(
            col[rows], weights=pay[rows, c].astype(np.float64),
            minlength=max_bin) for c in range(3)], axis=-1)
            for col in bins_np])

    def close(what, got, want):
        # explicit sync (device_get) — the probe compares on host by
        # design; bool(jnp.allclose(...)) would hide the same transfer
        # as an implicit block (graft-lint R001)
        got, want = jax.device_get((got, want))
        if np.allclose(got, want, rtol=1e-4, atol=1e-4):
            return _PROBE_OK
        return _mismatch(what, got, want)

    if multi:
        # active rows that make the dispatch run each f32 body: most rows
        # for the full one, 7/8 of its capacity for a compacting one
        actives = [(name, int(0.7 * n) if name == "full" else rows * 7 // 8)
                   for name, rows in hist_bodies(row_tile)]
        if max_bin > 256:
            actives = actives[:1]            # uint16 bins: the full body
        # the wave grower's multi-leaf block shapes, at the exact
        # production width when the caller supplies one
        if quantized is None:
            fams = [(False, width or MULTI_CHUNK),
                    (True, width or MULTI_CHUNK_Q)]
        else:
            fams = [(quantized,
                     width or (MULTI_CHUNK_Q if quantized
                               else MULTI_CHUNK))]
        for quant_f, wdt in fams:
            slots = jnp.arange(wdt, dtype=jnp.int32)
            k = min(3, wdt)
            for b, (body, count) in enumerate(actives[:1] if quant_f
                                              else actives):
                lid_np = np.where(some_rows(count), rng.randint(0, wdt, n),
                                  wdt + 1).astype(np.int32)
                lid = jnp.asarray(lid_np)
                if quant_f:
                    got = pallas_histogram_multi_quantized(
                        bins, pq, lid, slots, max_bin, s, s,
                        row_tile=row_tile, interpret=interpret)
                    ref_payload = pq_np
                else:
                    got, calls = pallas_histogram_multi(
                        bins, payload, lid, slots, max_bin,
                        row_tile=row_tile, interpret=interpret,
                        plan=plan, count_bodies=True)
                    ref_payload = payload_np
                    # one call a chunk of MULTI_CHUNK slots, all `body`
                    if jax.device_get(calls)[b] != -(-wdt // MULTI_CHUNK):
                        return ProbeResult(
                            False, "mismatch", f"the dispatch did not run "
                            f"body {body} on {count} active rows of {n}: "
                            f"calls by body {calls}")
                want = np.stack([host_hist(ref_payload, lid_np == sl)
                                 for sl in range(k)])
                res = close(f"multi-leaf kernel (quantized={quant_f}, "
                            f"width={wdt}, body={body}) vs a float64 "
                            "count", got[:k], want)
                if not res:
                    return res
        return _PROBE_OK
    mask_np = some_rows(int(0.7 * n))
    if quantized is not True:
        got = pallas_histogram(bins, payload, jnp.asarray(mask_np), max_bin,
                               row_tile=row_tile, interpret=interpret,
                               plan=plan, body="full")
        res = close("single-leaf f32 kernel vs a float64 count", got,
                    host_hist(payload_np, mask_np))
        if not res:
            return res
    if quantized is False:
        return _PROBE_OK
    # the quantized kernel runs DIFFERENT block shapes (3-row payload)
    # — probe it too, or a Mosaic regression there would crash the
    # pallas_q path that this probe is supposed to gate
    gotq = pallas_histogram_quantized(bins, pq, jnp.asarray(mask_np),
                                      max_bin, s, s, row_tile=row_tile,
                                      interpret=interpret)
    return close("single-leaf int8 kernel vs a float64 count", gotq,
                 host_hist(pq_np, mask_np))
