"""Histogram construction — the hottest kernel of GBDT training.

TPU-native re-design of the reference's histogram path
(ref: src/io/dense_bin.hpp `DenseBin::ConstructHistogram`;
src/treelearner/cuda/cuda_histogram_constructor.cu
`CUDAConstructHistogramKernel`).

Reference design: per-thread/per-block partial histograms with atomic adds.
TPUs have no atomics; the XLA formulation here is a batched segment-sum
(scatter-add) over a feature-major bin matrix.  A Pallas kernel with per-tile
VMEM-private histograms replaces this on the perf-critical path (ops/pallas
milestone); both produce identical [F, MB, 3] (sum_grad, sum_hess, count)
accumulators.

Layout notes:
 - bins are FEATURE-MAJOR [F, N] on device so each feature's column is
   contiguous for both the scatter and future Pallas row-tiling.
 - the (g, h, 1) payload is masked by bagging weights once per tree and by
   leaf membership per call; count is the masked row count (float), which is
   what min_data_in_leaf compares against under bagging.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..analysis.contracts import contract

Array = jax.Array


# ---------------------------------------------------------------------------
# Two-limb sums
# ---------------------------------------------------------------------------
# A float32 running sum over N near-equal addends drifts by up to N/2 ulps
# (equal addends round the same way every time), and sibling subtraction
# hands the drift of a 100M-row root down to a 100-row leaf unchanged.  So
# the f32 histogram families carry every (feature, bin) sum as TWO f32
# limbs, hi + lo, with |lo| <= ulp(hi)/2: channels-last [.., MB, 6] =
# (g_hi, h_hi, c_hi, g_lo, h_lo, c_lo).  Limbs are added and subtracted
# error-free (TwoSum); a consumer reads `hist_value`.  The quantized
# families keep [.., MB, 3]: their integer sums are exact already.

HIST_TILE = 8192        # rows summed plainly between two-limb folds


def _two_sum(a: Array, b: Array):
    """s = fl(a + b) and the rounding error e, so that a + b == s + e."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def limb_add(hi: Array, lo: Array, x_hi: Array, x_lo=None):
    """(hi, lo) + (x_hi, x_lo), renormalised so the new lo stays under an
    ulp of the new hi."""
    s, e = _two_sum(hi, x_hi)
    e = e + (lo if x_lo is None else lo + x_lo)
    hi2 = s + e
    return hi2, e - (hi2 - s)


def hist_value(hist: Array) -> Array:
    """[.., 3] float32 value of a histogram ([.., 6] limbs, or [.., 3])."""
    if hist.shape[-1] == 6:
        return hist[..., :3] + hist[..., 3:]
    return hist


def hist_add(a: Array, b: Array) -> Array:
    """a + b, limb-wise where the histograms carry limbs."""
    if a.shape[-1] != 6:
        return a + b
    hi, lo = limb_add(a[..., :3], a[..., 3:], b[..., :3], b[..., 3:])
    return jnp.concatenate([hi, lo], axis=-1)


def hist_sub(a: Array, b: Array) -> Array:
    """a - b: the larger child from its parent and the smaller child,
    keeping both limbs, so that no sum is rounded at the parent's size."""
    return hist_add(a, -b)


@contract(bins_fm="[F, N] int", payload="[N, 3] f32",
          row_mask="[N] bool", max_bin="static:MB",
          ret="[F, MB, 3] f32")
def leaf_histogram(bins_fm: Array, payload: Array, row_mask: Array,
                   max_bin: int) -> Array:
    """Accumulate (Σgrad, Σhess, Σcount) per (feature, bin) over masked rows.

    Args:
      bins_fm: [F, N] integer bin matrix, feature-major.
      payload: [N, 3] float32 — (grad*w, hess*w, w) with bagging weight w.
      row_mask: [N] bool — leaf membership.
      max_bin: padded bin-axis size MB.

    Returns: [F, MB, 3] float32, each sum right to the round-off of the
    SUM (`leaf_histogram_limbs` keeps both limbs).
    """
    return hist_value(leaf_histogram_limbs(bins_fm, payload, row_mask,
                                           max_bin))


def leaf_histogram_limbs(bins_fm: Array, payload: Array, row_mask: Array,
                         max_bin: int) -> Array:
    """`leaf_histogram` as [F, MB, 6] limbs: the multi-leaf builder on a
    mask-derived leaf id (slot 0 = in the leaf, -1 = dropped)."""
    lid = jnp.where(row_mask, 0, -1).astype(jnp.int32)
    return leaf_histogram_multi_limbs(
        bins_fm, payload, lid, jnp.zeros((1,), jnp.int32), max_bin)[0]


@contract(bins_fm="[F, N] int", payload="[N, 3] f32",
          max_bin="static:MB", ret="[F, MB, 3] f32")
def root_histogram(bins_fm: Array, payload: Array, max_bin: int) -> Array:
    """Histogram over all (bagging-weighted) rows — the root pass."""
    n = bins_fm.shape[1]
    return leaf_histogram(bins_fm, payload,
                          jnp.ones((n,), dtype=bool), max_bin)


def slot_positions(leaf_id: Array, slots: Array) -> Array:
    """[N] position of each row's leaf in `slots`, or S for rows whose
    leaf is not listed (histogram-drop sentinel).  `slots` may carry the
    pad value L (matches no leaf_id) for unused wave entries."""
    eq = slots[:, None] == leaf_id[None, :]          # [S, N]
    return jnp.where(eq.any(axis=0), jnp.argmax(eq, axis=0),
                     slots.shape[0])


@contract(bins_fm="[F, N] int", payload="[N, 3] f32",
          leaf_id="[N] int", slots="[S] int", max_bin="static:MB",
          ret="[S, F, MB, 3] f32")
def leaf_histogram_multi(bins_fm: Array, payload: Array, leaf_id: Array,
                         slots: Array, max_bin: int) -> Array:
    """Histograms of SEVERAL leaves in one sweep over the bin matrix.

    The wave grower's batched analog of `leaf_histogram`: rows are keyed by
    `slot_index * MB + bin` and one scatter-add per (feature, channel part)
    accumulates every listed leaf at once — the bin matrix is read ONCE for
    the whole wave instead of once per leaf (ref: the reference's
    `ConstructHistograms` loops leaves serially; on TPU one sweep is the
    only formulation that amortizes the scatter).

    Args:
      bins_fm: [F, N] integer bin matrix, feature-major.
      payload: [N, 3] f32 (grad*w, hess*w, w).
      leaf_id: [N] i32 current row→leaf assignment.
      slots: [S] i32 leaf slots to histogram; entries that match no row
        (e.g. the pad value num_leaves) yield all-zero histograms.
      max_bin: padded bin-axis size MB.

    Returns: [S, F, MB, 3] f32 (`leaf_histogram_multi_limbs`: both limbs).
    """
    return hist_value(leaf_histogram_multi_limbs(bins_fm, payload, leaf_id,
                                                 slots, max_bin))


# a one-pass build folds at most this many bytes of open tiles at a time
_ONE_PASS_TILE_BYTES = 64 << 20


def leaf_histogram_multi_limbs(bins_fm: Array, payload: Array,
                               leaf_id: Array, slots: Array,
                               max_bin: int) -> Array:
    """`leaf_histogram_multi` as [S, F, MB, 6] limbs: the streamed carry
    (`hist_stream_*`, below) folded over all rows at once, in chunks of
    whole tiles so that the open tiles stay a bounded array.  Chunking
    cannot change a bit: the carry's sequence of additions is fixed by
    the rows' positions alone."""
    F, N = bins_fm.shape
    S = slots.shape[0]
    acc = hist_stream_init(F, S, max_bin)
    plane = 5 * F * (S + 1) * max_bin * 4
    chunk = HIST_TILE * max(1, min(32, _ONE_PASS_TILE_BYTES // plane - 1))

    def fold(acc, lo, n):
        return hist_stream_update(
            acc, jax.lax.dynamic_slice_in_dim(bins_fm, lo, n, axis=1),
            jax.lax.dynamic_slice_in_dim(payload, lo, n, axis=0),
            jax.lax.dynamic_slice_in_dim(leaf_id, lo, n, axis=0),
            slots, max_bin)

    whole = N // chunk if N > chunk else 0
    if whole:
        acc = jax.lax.fori_loop(
            0, whole, lambda i, a: fold(a, i * chunk, chunk), acc)
    if N - whole * chunk:
        acc = fold(acc, whole * chunk, N - whole * chunk)
    return hist_stream_finalize(acc, F, S, max_bin)


PACKED_TILE = 2048  # rows per int16-field accumulation tile
# largest num_grad_quant_bins whose per-tile hess-field sum stays below
# 2^15 (no carry into the packed grad field); the booster gate imports
# this so the two can never drift apart
PACKED_MAX_QUANT_BINS = (2 ** 15 - 1) // PACKED_TILE


@contract(bins_fm="[F, N] int", payload="[N, 3] f32",
          row_mask="[N] bool", max_bin="static:MB", s_g="[] float",
          s_h="[] float", const_hess_level="static int",
          ret="[F, MB, 3] f32")
def leaf_histogram_packed(bins_fm: Array, payload: Array, row_mask: Array,
                          max_bin: int, s_g: Array, s_h: Array,
                          const_hess_level: int = 0) -> Array:
    """Quantized-gradient histogram with packed integer accumulation
    (ref: cuda_gradient_discretizer.cu + the int16/int32 packed histogram
    of v4 `use_quantized_grad`; the CUDA kernel packs (grad, hess) into one
    32-bit word so one atomic covers both — here one SCATTER covers both).

    Requires `payload[:, 0] = gq·s_g·w`, `payload[:, 1] = hq·s_h·w` with
    integer gq/hq from `quantize_gradients` and w ∈ {0, 1} (plain bagging;
    GOSS weights break integrality, the booster gates that off).  The
    quantized integers are recovered exactly by division, packed as
    (gq << 16) + hq, and scatter-added per ≤PACKED_TILE-row tile — the
    hess field stays < 2^15 per tile, so field carries cannot corrupt the
    grad field.  Two scatter sweeps per feature (packed + count) instead
    of the f32 path's three.

    `const_hess_level > 0` declares every live row's hq equal to that
    level (unit-hessian objectives — L2/L1/Huber/Quantile — with no
    dataset weights quantize to exactly hq = num_grad_quant_bins): the
    count scatter is DROPPED and counts derive as hess_field / level,
    leaving ONE scatter sweep over the bin matrix per histogram.

    Returns the same [F, MB, 3] f32 (Σg, Σh, Σcount) as `leaf_histogram`,
    bit-identical-or-better: integer sums are exact where long f32 chains
    round.
    """
    F, N = bins_fm.shape
    d = jnp.where(row_mask[:, None], payload, 0.0)
    gq = jnp.round(d[:, 0] / s_g).astype(jnp.int32)
    hq = jnp.round(d[:, 1] / s_h).astype(jnp.int32)
    if const_hess_level > 0:
        # declared-constant hessian: the quantizer left hess UNQUANTIZED
        # with s_h = 1/level, so round(h/s_h) is exactly the level for
        # every live row; the clamp is a defensive no-op that keeps the
        # count derivation exact no matter what upstream feeds in
        hq = jnp.where(hq > 0, const_hess_level, 0)
    packed = (gq << 16) + hq

    T = -(-N // PACKED_TILE)
    pad = T * PACKED_TILE - N
    cols = bins_fm.astype(jnp.int32)
    if pad:
        packed = jnp.pad(packed, (0, pad))
        cols = jnp.pad(cols, ((0, 0), (0, pad)))
    pt = packed.reshape(T, PACKED_TILE)
    wt = None
    if const_hess_level == 0:       # count channel only when scattered
        w = d[:, 2].astype(jnp.int32)
        if pad:
            w = jnp.pad(w, (0, pad))
        wt = w.reshape(T, PACKED_TILE)

    def per_feature(colf: Array) -> Array:             # [T, tile]
        def per_tile(ids, vals):
            return jax.ops.segment_sum(vals, ids, num_segments=max_bin)
        ph = jax.vmap(per_tile)(colf, pt)              # [T, MB] packed i32
        h_f = ph & 0xFFFF                              # < 2^15 per tile
        g_f = (ph - h_f) >> 16
        h_sum = h_f.sum(axis=0)
        if const_hess_level > 0:
            cnt = h_sum // const_hess_level            # exact: hq ≡ level
        else:
            cnt = jax.vmap(per_tile)(colf, wt).sum(axis=0)  # [MB]
        return jnp.stack([g_f.sum(axis=0).astype(jnp.float32) * s_g,
                          h_sum.astype(jnp.float32) * s_h,
                          cnt.astype(jnp.float32)], axis=-1)   # [MB, 3]

    return jax.vmap(per_feature)(cols.reshape(F, T, PACKED_TILE))


@contract(bins_fm="[F, N] int", payload="[N, 3] f32",
          leaf_id="[N] int", slots="[S] int", max_bin="static:MB",
          s_g="[] float", s_h="[] float", const_hess_level="static int",
          ret="[S, F, MB, 3] f32")
def leaf_histogram_packed_multi(bins_fm: Array, payload: Array,
                                leaf_id: Array, slots: Array, max_bin: int,
                                s_g: Array, s_h: Array,
                                const_hess_level: int = 0) -> Array:
    """Multi-leaf variant of `leaf_histogram_packed` (see there for the
    packing invariants): rows are keyed `slot_index * MB + bin` so one
    packed scatter sweep accumulates every wave leaf at once.  The
    per-tile hess-field bound is unchanged — segment COUNT grows with S,
    per-segment tile sums do not.

    Returns [S, F, MB, 3] f32.
    """
    S = slots.shape[0]
    F, N = bins_fm.shape
    NS = (S + 1) * max_bin
    pos = slot_positions(leaf_id, slots)               # [N] in [0, S]
    gq = jnp.round(payload[:, 0] / s_g).astype(jnp.int32)
    hq = jnp.round(payload[:, 1] / s_h).astype(jnp.int32)
    if const_hess_level > 0:
        hq = jnp.where(hq > 0, const_hess_level, 0)
    packed = (gq << 16) + hq

    T = -(-N // PACKED_TILE)
    pad = T * PACKED_TILE - N
    cols = bins_fm.astype(jnp.int32) + (pos * max_bin)[None, :]
    if pad:
        # padded rows key to the dropped S-th block
        packed = jnp.pad(packed, (0, pad))
        cols = jnp.pad(cols, ((0, 0), (0, pad)),
                       constant_values=S * max_bin)
    pt = packed.reshape(T, PACKED_TILE)
    wt = None
    if const_hess_level == 0:
        w = payload[:, 2].astype(jnp.int32)
        if pad:
            w = jnp.pad(w, (0, pad))
        wt = w.reshape(T, PACKED_TILE)

    def per_feature(colf: Array) -> Array:             # [T, tile]
        def per_tile(ids, vals):
            return jax.ops.segment_sum(vals, ids, num_segments=NS)
        ph = jax.vmap(per_tile)(colf, pt)              # [T, NS] packed i32
        h_f = ph & 0xFFFF
        g_f = (ph - h_f) >> 16
        h_sum = h_f.sum(axis=0)
        if const_hess_level > 0:
            cnt = h_sum // const_hess_level
        else:
            cnt = jax.vmap(per_tile)(colf, wt).sum(axis=0)
        return jnp.stack([g_f.sum(axis=0).astype(jnp.float32) * s_g,
                          h_sum.astype(jnp.float32) * s_h,
                          cnt.astype(jnp.float32)], axis=-1)   # [NS, 3]

    out = jax.vmap(per_feature)(cols.reshape(F, T, PACKED_TILE))
    return out.reshape(F, S + 1, max_bin, 3)[:, :S].transpose(1, 0, 2, 3)


# ---------------------------------------------------------------------------
# Streamed carry accumulation (init / update / finalize)
# ---------------------------------------------------------------------------
# These decompose the one-pass builders above so a host loop can fold
# datastore shards into a wave histogram without materialising the full
# [F, N] bin matrix.  Bitwise contract with the one-pass builders:
#
#   * f32 family — the carry is (sum, cur, n): `cur` takes the rows of
#     the open tile by in-order scatter-add (plain f32, at most HIST_TILE
#     rows, and those split into an 11-bit head whose sums stay exact and
#     a tail 2^-11 as large), and whenever the rows seen so far pass a
#     multiple of HIST_TILE it is folded into the two-limb `sum` and
#     cleared.  Every addition is fixed by a row's POSITION in the stream,
#     so shards of any size, folded in pinned order, perform exactly the
#     additions of `leaf_histogram_multi_limbs` over the concatenated rows
#     (which is this fold over one shard).
#   * packed family — carries are int32; modular integer addition is
#     fully associative, so any shard/tile grouping yields identical
#     totals as long as each tile keeps the 16-bit hessian lane from
#     overflowing (the same PACKED_TILE bound the one-pass builder uses).
#
# `finalize` applies the identical trailing conversion expressions, so
# equal carries produce bit-equal [S, F, max_bin, .] histograms.


def ring_ordered_sum(local: Array, axis_name, n_shards: int) -> Array:
    """Every shard's `local`, summed in ASCENDING shard order —
    ((x0 + x1) + x2) + ... — and returned on every shard: a ppermute
    chain in place of psum's reduction tree, so the float result does not
    depend on how the backend shapes that tree.  The deterministic
    reduction of the Pallas histogram family: each shard's kernel runs
    over its own rows at once and only the S partial histograms are
    chained, limb-wise (the XLA families instead chain the scatter-add
    itself, `hist_stream_*`, which keeps them bitwise equal to one
    shard)."""
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    carry = local
    for _ in range(n_shards - 1):
        carry = hist_add(jax.lax.ppermute(carry, axis_name, perm), local)
    return jax.lax.all_gather(carry, axis_name)[n_shards - 1]


def ring_fold(fold, carry: dict, axis_name, n_shards: int) -> dict:
    """A streamed carry folded shard by shard around the ring, in ascending
    shard order: shard t applies `fold` (its own rows) to what shard t-1
    hands it, and every shard gets the last shard's carry back.  The
    `ring_fold` scope pairs the device trace with the host-side
    mesh.collective.ring_fold dispatch events (per-device collective
    timeline)."""
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    with jax.named_scope("ring_fold"):
        for t in range(n_shards):
            carry = fold(carry)
            if t < n_shards - 1:
                carry = {k: jax.lax.ppermute(v, axis_name, perm)
                         for k, v in carry.items()}
        return {k: jax.lax.all_gather(v, axis_name)[n_shards - 1]
                for k, v in carry.items()}


def hist_stream_init(F: int, slots_n: int, max_bin: int) -> dict:
    """Zero carry of the f32 family over NS = (S+1)*max_bin cells a
    feature: `sum` [2, 3, F, NS] (limb, channel), `cur` [5, F, NS] (the
    open tile: g head, g tail, h head, h tail, count), `n` rows seen."""
    NS = (slots_n + 1) * max_bin
    return {"sum": jnp.zeros((2, 3, F, NS), jnp.float32),
            "cur": jnp.zeros((5, F, NS), jnp.float32),
            "n": jnp.int32(0)}


def _fold_tile(total: Array, tile: Array) -> Array:
    """One closed tile [5, F, NS] into the two-limb sums [2, 3, F, NS]."""
    heads = jnp.stack([tile[0], tile[2], tile[4]])
    tails = jnp.stack([tile[1], tile[3], jnp.zeros_like(tile[4])])
    hi, lo = limb_add(total[0], total[1], heads)
    hi, lo = limb_add(hi, lo, tails)
    return jnp.stack([hi, lo])


def hist_stream_update(acc: dict, bins_fm: Array, payload: Array,
                       leaf_id: Array, slots: Array, max_bin: int) -> dict:
    """Fold one shard's rows into the f32 carry.

    ``bins_fm``/``payload``/``leaf_id`` hold the shard's rows only; the
    shard's internal row order plus the caller's pinned shard order
    reproduce the additions of ``leaf_histogram_multi_limbs``.
    """
    F, n = bins_fm.shape
    NS = (slots.shape[0] + 1) * max_bin
    K = -(-n // HIST_TILE) + 1          # tiles a shard of n rows can touch
    pos = slot_positions(leaf_id, slots)               # [n] in [0, S]
    off = acc["n"] % HIST_TILE
    tile_of_row = (off + jnp.arange(n, dtype=jnp.int32)) // HIST_TILE
    cols = bins_fm.astype(jnp.int32) \
        + (pos * max_bin + tile_of_row * NS)[None, :]  # [F, n] in [0, K*NS)

    def head_tail(v: Array):
        # 11 significant bits: HIST_TILE of them add up exactly in f32
        head = jax.lax.reduce_precision(v, 8, 10)
        return head, v - head

    parts = head_tail(payload[:, 0]) + head_tail(payload[:, 1]) \
        + (payload[:, 2],)

    def scatter(cur_p: Array, vals: Array) -> Array:
        # the open tile goes on from where the last shard left it
        start = jnp.pad(cur_p, ((0, 0), (0, (K - 1) * NS)))
        return jax.vmap(lambda a_f, col: a_f.at[col].add(vals))(start, cols)

    tiles = jnp.stack([scatter(acc["cur"][p], v)
                       for p, v in enumerate(parts)]).reshape(5, F, K, NS)
    closed = (off + n) // HIST_TILE                    # in [0, K - 1]
    total = jax.lax.fori_loop(
        0, closed,
        lambda j, t: _fold_tile(t, jax.lax.dynamic_index_in_dim(
            tiles, j, axis=2, keepdims=False)),
        acc["sum"])
    cur = jax.lax.dynamic_index_in_dim(tiles, closed, axis=2,
                                       keepdims=False)
    return {"sum": total, "cur": cur, "n": acc["n"] + n}


def hist_stream_finalize(acc: dict, F: int, slots_n: int,
                         max_bin: int) -> Array:
    """Carry -> [S, F, max_bin, 6], matching leaf_histogram_multi_limbs."""
    S = slots_n
    total = _fold_tile(acc["sum"], acc["cur"])          # [2, 3, F, NS]
    out = jnp.moveaxis(total.reshape(6, F, S + 1, max_bin), 0, -1)
    return out[:, :S].transpose(1, 0, 2, 3)


def hist_stream_packed_init(F: int, slots_n: int, max_bin: int,
                            const_hess_level: int = 0) -> dict:
    """Zero int32 carries for the packed family."""
    NS = (slots_n + 1) * max_bin
    acc = {"g": jnp.zeros((F, NS), jnp.int32),
           "h": jnp.zeros((F, NS), jnp.int32)}
    if const_hess_level == 0:
        acc["c"] = jnp.zeros((F, NS), jnp.int32)
    return acc


def hist_stream_packed_update(acc: dict, bins_fm: Array, payload: Array,
                              leaf_id: Array, slots: Array, max_bin: int,
                              s_g: float, s_h: float,
                              const_hess_level: int = 0) -> dict:
    """Fold one shard's rows into the packed int32 carries.

    Tiles within the shard exactly like the one-pass builder so the
    16-bit hessian lane can never overflow mid-tile; the per-tile
    unpacked g/h sums are then exact integers, and integer addition
    across shards is association-free.
    """
    F, n = bins_fm.shape
    S = slots.shape[0]
    NS = (S + 1) * max_bin
    pos = slot_positions(leaf_id, slots)
    gq = jnp.round(payload[:, 0] / s_g).astype(jnp.int32)
    hq = jnp.round(payload[:, 1] / s_h).astype(jnp.int32)
    if const_hess_level > 0:
        hq = jnp.where(hq > 0, const_hess_level, 0)
    packed = (gq << 16) + hq

    T = -(-n // PACKED_TILE)
    pad = T * PACKED_TILE - n
    cols = bins_fm.astype(jnp.int32) + (pos * max_bin)[None, :]
    if pad:
        packed = jnp.pad(packed, (0, pad))
        cols = jnp.pad(cols, ((0, 0), (0, pad)),
                       constant_values=S * max_bin)
    pt = packed.reshape(T, PACKED_TILE)
    wt = None
    if const_hess_level == 0:
        w = payload[:, 2].astype(jnp.int32)
        if pad:
            w = jnp.pad(w, (0, pad))
        wt = w.reshape(T, PACKED_TILE)

    def per_feature(colf: Array):
        def per_tile(ids, vals):
            return jax.ops.segment_sum(vals, ids, num_segments=NS)
        ph = jax.vmap(per_tile)(colf, pt)              # [T, NS] packed i32
        h_f = ph & 0xFFFF
        g_f = (ph - h_f) >> 16
        if const_hess_level == 0:
            cnt = jax.vmap(per_tile)(colf, wt).sum(axis=0)
        else:
            cnt = jnp.zeros((NS,), jnp.int32)
        return g_f.sum(axis=0), h_f.sum(axis=0), cnt

    g_s, h_s, c_s = jax.vmap(per_feature)(cols.reshape(F, T, PACKED_TILE))
    out = {"g": acc["g"] + g_s, "h": acc["h"] + h_s}
    if const_hess_level == 0:
        out["c"] = acc["c"] + c_s
    return out


def hist_stream_packed_finalize(acc: dict, F: int, slots_n: int,
                                max_bin: int, s_g: float, s_h: float,
                                const_hess_level: int = 0) -> Array:
    """Packed carries -> [S, F, max_bin, 3], matching the one-pass builder."""
    S = slots_n
    h_sum = acc["h"]
    if const_hess_level > 0:
        cnt = h_sum // const_hess_level
    else:
        cnt = acc["c"]
    out = jnp.stack([acc["g"].astype(jnp.float32) * s_g,
                     h_sum.astype(jnp.float32) * s_h,
                     cnt.astype(jnp.float32)], axis=-1)  # [F, NS, 3]
    return out.reshape(F, S + 1, max_bin, 3)[:, :S].transpose(1, 0, 2, 3)
