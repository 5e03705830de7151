"""The f32 histogram kernel's compacting bodies (ISSUE 32), interpret mode.

A pass whose every row tile holds at most C active rows (rows whose leaf
is in one of the call's slots) lands them in C columns by a selection
matmul and contracts C rows a tile for `row_tile`.  Same rows summed: with
integer payloads (every f32 sum exact) both limbs equal the full body's
bit for bit; with real payloads the sums agree with a float64 count inside
the two-limb tolerance of `tests/test_limb_sums.py`.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.ops import pallas_hist as ph
from perfbench import manifest, readers
from perfbench.generators import tabular_codes
from perfbench.jobs.train import build_dataset

AIRLINE_NUM_BIN = (22, 12, 31, 7, 255, 255, 29, 255, 255, 255, 255, 255, 2)
TILE = ph.ROW_TILE
S = 8


def _case(n, active, seed, integer, num_bins=AIRLINE_NUM_BIN,
          dtype=np.uint8):
    """Bins, carrier rows, leaf ids with `active` (bool [n]) rows in the
    S slots, the slots; the [n, 3] payload too."""
    rng = np.random.RandomState(seed)
    bins = np.stack([rng.randint(0, nb, n) for nb in num_bins])
    pay = rng.randn(n, 3) * 16
    pay = np.round(pay) if integer else pay
    pay = pay.astype(np.float32)
    lid = np.where(active, rng.randint(0, S, n),
                   S + rng.randint(0, 3 * S, n)).astype(np.int32)
    return (jnp.asarray(bins.astype(dtype)),
            ph._split_payload9(jnp.asarray(pay)), jnp.asarray(lid),
            jnp.arange(S, dtype=jnp.int32)), bins, pay, lid


def _rows(args, mb=255, **kw):
    out = ph.pallas_histogram_multi_rows(*args, mb, interpret=True,
                                         count_bodies=True, **kw)
    return np.asarray(out[0]), dict(zip(
        [name for name, _ in ph.hist_bodies(kw.get("row_tile", TILE))],
        np.asarray(out[1]).tolist()))


def _float64_sums(bins, pay, lid, mb=255):
    """[S, F, mb, 3] by numpy, float64."""
    return np.stack([np.stack([np.stack([np.bincount(
        col[lid == s], weights=pay[lid == s, c].astype(np.float64),
        minlength=mb) for c in range(3)], axis=-1) for col in bins])
        for s in range(S)])


# --------------------------------------------------- (a) the compaction alone
@pytest.mark.parametrize("cap,count", [(128, 0), (128, 97), (128, 128),
                                       (256, 0), (256, 97), (256, 256),
                                       (512, 257), (512, 512)])
def test_compaction_lands_the_active_rows_in_order(cap, count):
    rng = np.random.RandomState(cap + count)
    active = np.zeros(TILE, bool)
    active[rng.choice(TILE, count, replace=False)] = True
    args, bins, _, lid = _case(TILE, active, 1, integer=False)
    code = ph._row_codes(args[2], args[3], TILE)[None, :]
    code_np = np.asarray(code)[0]
    assert np.array_equal(code_np >= 0, active)
    assert np.array_equal(code_np[active] >> ph.SLOT_BITS, np.arange(count))
    assert np.array_equal(code_np[active] & 15, lid[active] + 1)
    x = np.concatenate([np.asarray(args[1]), (code_np & 15)[None, :],
                        bins]).astype(np.float32)   # as the kernel stacks
    comp = np.asarray(ph._compact_rows(jnp.asarray(x), code, cap))
    assert comp.shape == (9 + 1 + 13, cap)
    assert np.array_equal(comp[:, :count], x[:, active])
    assert np.all(comp[:, count:] == 0)
    # every value the selection matmul multiplies is exact in bfloat16
    assert np.array_equal(x, np.asarray(
        jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)))


# ------------------------------------- (b) integer payloads: the same bits
@pytest.mark.parametrize("planned", [False, True])
@pytest.mark.parametrize("body,share", [("c128", 0.035), ("c256", 0.08),
                                        ("c512", 0.18)])
def test_integer_payload_limbs_equal_the_full_bodys(body, share, planned):
    """More tiles than one fold takes (`FLUSH_TILES`) and a ragged last
    tile; the dispatch picks the same body the test forces."""
    n = TILE * (ph.FLUSH_TILES + 1) + 700
    rng = np.random.RandomState(3)
    args, *_ = _case(n, rng.rand(n) < share, 2, integer=True)
    plan = ph.lane_plan(AIRLINE_NUM_BIN, 255) if planned else None
    want, ran = _rows(args, plan=plan, body="full")
    assert ran["full"] == 1
    got, ran = _rows(args, plan=plan, body=body)
    assert ran[body] == 1
    assert want.shape == got.shape == (S, 13, 255, 6)
    np.testing.assert_array_equal(got, want)
    picked, ran = _rows(args, plan=plan)
    assert ran[body] == 1 and sum(ran.values()) == 1
    np.testing.assert_array_equal(picked, want)
    assert np.abs(want).sum() > 0


# ---------------------- (c) real payloads: the same sums, to the two limbs
@pytest.mark.parametrize("body,share", [("full", 0.08), ("c128", 0.035),
                                        ("c256", 0.08), ("c512", 0.18)])
def test_real_payload_sums_against_float64(body, share):
    n = TILE * 3
    rng = np.random.RandomState(5)
    args, bins, pay, lid = _case(n, rng.rand(n) < share, 4, integer=False)
    got, _ = _rows(args, plan=ph.lane_plan(AIRLINE_NUM_BIN, 255), body=body)
    got = got.astype(np.float64)
    got = got[..., :3] + got[..., 3:]
    want = _float64_sums(bins, pay, lid)
    assert np.max(np.abs(got - want) / np.maximum(1.0, np.abs(want))) < 1e-6


# --------------------------------------------------------- (d) the dispatch
def _one_tile_with(count, n_tiles=3):
    """`count` active rows in the middle tile, 40 in the others."""
    active = np.zeros((n_tiles, TILE), bool)
    active[:, :40] = True
    active[1, :count] = True
    return active.reshape(-1)


@pytest.mark.parametrize("case,body", [
    ("all_rows_active", "full"), ("no_row_active", "c128"),
    ("a_tile_at_128", "c128"), ("a_tile_at_129", "c256"),
    ("a_tile_at_256", "c256"), ("a_tile_at_257", "c512"),
    ("a_tile_at_512", "c512"), ("a_tile_at_513", "full"),
    ("uint16_bins", "full"), ("split_feat_tile", "c256"),
    ("a_leaf_listed_twice", "full"), ("row_tile_of_100", "full")])
def test_dispatch_runs_the_body_the_rule_says(case, body):
    n = 3 * TILE
    active = {"all_rows_active": np.ones(n, bool),
              "no_row_active": np.zeros(n, bool)}.get(case)
    if case.startswith("a_tile_at_"):
        active = _one_tile_with(int(case.rsplit("_", 1)[1]))
    if active is None:      # every tile over 128 active rows, under 256
        active = np.random.RandomState(6).rand(n) < 0.08
    kw, mb, num_bins, dtype = {}, 255, AIRLINE_NUM_BIN, np.uint8
    if case == "uint16_bins":
        mb, num_bins, dtype = 300, (300, 40, 7), np.uint16
    elif case == "split_feat_tile":
        kw = {"feat_tile": 4}
    elif case == "row_tile_of_100":
        kw = {"row_tile": 100}
    args, bins, pay, lid = _case(n, active, 7, integer=True,
                                 num_bins=num_bins, dtype=dtype)
    if case == "a_leaf_listed_twice":
        args = args[:3] + (args[3].at[S - 1].set(0),)
    got, ran = _rows(args, mb, **kw)
    assert ran[body] == 1 and sum(ran.values()) == 1, ran
    statically_full = case in ("uint16_bins", "row_tile_of_100")
    assert len(ran) == (1 if case == "row_tile_of_100" else 4)
    if not statically_full:
        want, _ = _rows(args, mb, body="full", **kw)
        np.testing.assert_array_equal(got, want)
    if case == "a_leaf_listed_twice":       # both slots hold leaf 0's sums
        np.testing.assert_array_equal(got[S - 1], got[0])
        assert np.abs(got[0]).sum() > 0
        return
    got = got.astype(np.float64)
    np.testing.assert_array_equal(got[..., :3] + got[..., 3:],
                                  _float64_sums(bins, pay, lid, mb))
    if case == "no_row_active":
        assert np.all(got == 0)


def test_more_slots_than_a_chunk_count_a_call_a_chunk():
    n = TILE
    rng = np.random.RandomState(8)
    bins = jnp.asarray(rng.randint(0, 16, (2, n)).astype(np.uint8))
    pw9 = ph._split_payload9(jnp.asarray(
        np.round(rng.randn(n, 3) * 8).astype(np.float32)))
    lid = jnp.asarray(rng.randint(0, 200, n).astype(np.int32))
    slots = jnp.arange(ph.MULTI_CHUNK + 3, dtype=jnp.int32)
    got, calls = ph.pallas_histogram_multi_rows(
        bins, pw9, lid, slots, 16, interpret=True, count_bodies=True)
    # a full chunk of slots holds ~143 rows a tile, the last three ~31
    assert np.asarray(calls).tolist() == [0, 1, 1, 0]
    want = ph.pallas_histogram_multi_rows(bins, pw9, lid, slots, 16,
                                          interpret=True, body="full")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------ (d2) the column blocks
# the 68 columns one of four shards sees of the Criteo-shaped table: 67
# padded to the shard count (perfbench/configs/criteo67-lgbpar-l255.json)
CRITEO_NUM_BIN = (255,) * 9 + (12,) + (255,) * 57 + (1,)
_ONE_BLOCK = {}


def _wide_case():
    """Two row tiles of 640 rows, the second ragged; 12% of the rows in
    the slots, so a tile holds under 128 of them."""
    if not _ONE_BLOCK:
        n = 640 + 300
        rng = np.random.RandomState(11)
        args, *_ = _case(n, rng.rand(n) < 0.12, 12, integer=True,
                         num_bins=CRITEO_NUM_BIN)
        want, ran = _rows(args, row_tile=640, body="full")
        assert ran["full"] == 1 and np.abs(want).sum() > 0
        _ONE_BLOCK.update(args=args, want=want)
    return _ONE_BLOCK["args"], _ONE_BLOCK["want"]


@pytest.mark.parametrize("body", ["full", "c128", "c256", "c512"])
@pytest.mark.parametrize("planned", [False, True])
@pytest.mark.parametrize("n_blocks", [1, 2, 4])
def test_column_blocks_sum_the_same_bits(n_blocks, planned, body):
    """68 columns in 1, 2 and 4 column blocks, with and without the lane
    plan, through each body: both limbs equal one block's full body's."""
    args, want = _wide_case()
    plan = ph.lane_plan(CRITEO_NUM_BIN, 255) if planned else None
    feat_tile = -(-68 // n_blocks)     # forced: 68 columns force one
    blocks = ph.column_blocks(68, S * 9, 255, plan, feat_tile)
    assert len(blocks) == n_blocks
    assert [c0 for c0, _, _ in blocks][1:] == [c1 for _, c1, _ in blocks][:-1]
    if planned:     # the 12-bin column and the pad column share no block
        packed = [len(sub) < c1 - c0 if sub else False
                  for c0, c1, sub in blocks]
        assert any(packed) == (n_blocks == 1)
    got, ran = _rows(args, row_tile=640, plan=plan, body=body,
                     feat_tile=feat_tile)
    assert ran[body] == 1 and sum(ran.values()) == 1
    assert got.shape == want.shape == (S, 68, 255, 6)
    np.testing.assert_array_equal(got, want)
    if body == "c128" and planned and n_blocks == 4:
        # and as the dispatch picks: the same body
        picked, ran = _rows(args, row_tile=640, plan=plan,
                            feat_tile=feat_tile)
        assert ran == {"full": 0, "c128": 1, "c256": 0, "c512": 0}
        np.testing.assert_array_equal(picked, want)


@pytest.mark.parametrize("columns,slots,n_blocks", [
    (13, 8, 1), (68, 8, 1), (256, 8, 4), (68, 14, 2), (256, 14, 8),
    (220, 1, 2)])
def test_chosen_column_blocks_fit_the_vmem_budget(columns, slots, n_blocks):
    """As few blocks as fit: the 68 columns a shard of the four-chip cell
    sees are ONE block at the cell's eight slots under their plan (without
    one, all 68 on 256 lanes each, they are two); a single-leaf call over
    220 columns (the ranking cell's probe) is priced at VMEM_MIN_ROWS rows
    and cut in two."""
    num_bins = (CRITEO_NUM_BIN * 4)[:columns]
    for plan in (None, ph.lane_plan(num_bins, 255)):
        blocks = ph.column_blocks(columns, slots * 9, 255, plan)
        assert len(blocks) == n_blocks + (
            (columns, slots, plan) == (68, 8, None))
        assert blocks[0][0] == 0 and blocks[-1][1] == columns
        for (c0, c1, sub), nxt in zip(blocks, blocks[1:] + ((columns,),)):
            assert c1 == nxt[0] and c1 > c0
            lanes = (c1 - c0) * 256 if sub is None else ph.plan_lanes(sub)
            assert ph._vmem_need(slots * 9, lanes) <= ph.VMEM_BYTES
            if sub is not None:
                assert [c for c, _, _ in ph.plan_columns(sub)] \
                    == list(range(c1 - c0))
        if len(blocks) == 1:
            assert blocks == ((0, columns, plan),)


# ------------------------------------------- (e) a tree through the grower
ROWS = 65_536


def _grow(monkeypatch, caps):
    """Three rounds of the `airline13-l31` settings at 65,536 rows on the
    interpreted kernel, with the compacting capacities `caps`."""
    monkeypatch.setattr(ph, "COMPACT_CAPS", caps)
    jax.clear_caches()          # the capacities are read where it traces
    config = manifest.config("airline13-l31")
    rows = tabular_codes.make(3000000021, config["data"], ROWS, 1)
    params = dict(config["params"], hist_impl="pallas", hist_interpret=True)
    names = [c["name"] for c in config["data"]["columns"]]
    ds = build_dataset(lgb, rows["codes"], rows["label"], params, names)
    bst = lgb.Booster(params=params, train_set=ds)
    before = telemetry.REGISTRY.snapshot()["counters"]
    for _ in range(3):
        bst.update()
    after = telemetry.REGISTRY.snapshot()["counters"]
    grown = {k: after[k] - before.get(k, 0) for k in after
             if k.startswith("grow.")}
    return bst, grown


def test_wave_grower_grows_the_same_trees_and_counts_its_bodies(
        monkeypatch):
    today, grown0 = _grow(monkeypatch, ())
    new, grown = _grow(monkeypatch, (128, 256, 512))
    jax.clear_caches()
    assert today._grow_policy == new._grow_policy == "wave"
    for a, b in zip(today.trees, new.trees):
        assert a.num_leaves == b.num_leaves == 31
        for field in ("split_feature", "threshold_bin", "left_child",
                      "right_child", "leaf_count", "internal_count"):
            np.testing.assert_array_equal(getattr(a, field),
                                          getattr(b, field), field)
        np.testing.assert_allclose(b.leaf_value, a.leaf_value, rtol=0,
                                   atol=1e-6)
    # a kernel call a pass, by the body that ran
    passes = grown["grow.wave_passes"] + grown["grow.tail_passes"] + 3
    by_body = {k[len("grow.hist_passes_"):]: v for k, v in grown.items()
               if k.startswith("grow.hist_passes_")}
    assert set(by_body) == {"full", "c128", "c256", "c512"}
    assert sum(by_body.values()) == passes
    assert by_body["full"] >= 3 and by_body["c128"] + by_body["c256"] \
        + by_body["c512"] > 0
    tiles = ROWS // TILE
    assert grown["grow.hist_rows_contracted"] == tiles * (
        by_body["full"] * TILE + by_body["c512"] * 512
        + by_body["c256"] * 256 + by_body["c128"] * 128)
    assert grown["grow.hist_rows_needed"] \
        <= grown["grow.hist_rows_contracted"] < passes * ROWS
    # today's bodies: every pass full, every row contracted
    assert grown0["grow.hist_passes_full"] == passes
    assert grown0["grow.hist_rows_contracted"] == passes * ROWS
    assert grown0["grow.hist_rows_needed"] == grown["grow.hist_rows_needed"]


# --------------------------------- (f) the benchmark's reading of the bodies
METRIC = "hist.c128_pass_pct"
PARENT_RECORD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "perfbench", "fixtures", "driver_round",
                             "program.json")


def _metric():
    with open(os.path.join(manifest.HERE, "layer_metrics",
                           METRIC + ".json")) as f:
        return json.load(f)


def test_c128_pass_pct_is_listed_for_the_255_leaf_cells():
    """In the manifest with its file, over every body's counter in the
    order `hist_bodies` counts them; listed for the cells whose trees have
    255 leaves (`.l31`'s fixture record predates the body)."""
    assert manifest.problems() == []
    b = manifest.benchmark()
    entry, = [m for m in b["per_layer"] if m["name"] == METRIC]
    assert (entry["layer"], entry["moves"], entry["unit"], entry["better"],
            entry["source"]) == ("histogram kernel", "train_rounds_per_s",
                                 "%", "higher", "program_counter")
    deep = [w["name"] for w in b["workloads"] if manifest.config(
        w["config"])["params"]["num_leaves"] == 255]
    m = _metric()
    assert entry["workloads"] == m["workloads"] == deep and len(deep) == 3
    assert m["reader"] == "counter_ratio"
    assert m["args"]["num"] == ["grow.hist_passes_c128"]
    assert m["args"]["den"] == [f"grow.hist_passes_{name}"
                                for name, _ in ph.hist_bodies()]


@pytest.mark.parametrize("record,want", [
    ("parent_record", 0.0), ("a_255_leaf_window", 100 * 20 / 38),
    ("no_counters", None)])
def test_c128_pass_pct_reads_the_programs_counters(record, want):
    """0 on a record of the program before the body (the recorded traced
    fixture run), the body's share of the calls on one that has it, and
    nothing where the program counted no kernel call."""
    counters = {"no_counters": {"grow.leaves": 255},
                "a_255_leaf_window": {
                    "grow.hist_passes_full": 6, "grow.hist_passes_c128": 20,
                    "grow.hist_passes_c256": 8, "grow.hist_passes_c512": 4}
                }.get(record)
    if counters is None:
        with open(PARENT_RECORD) as f:
            counters = json.load(f)["counters"]
    got = readers.read(_metric(), {"program": None, "counters": counters})
    assert got == (pytest.approx(want) if want else want)
