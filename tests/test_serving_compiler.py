"""Serving compiler (lightgbm_tpu/compiler/): plan/quantize unit
coverage + the compiled ladder rung end-to-end.

The compiled rung's contract is the same as every other rung's —
byte-identical to `booster.predict` — but its machinery (tile packing,
node-word quantization, the fused Pallas traverse kernel, the
boosting-order slot gather) is all new, so this file holds it to the
same three invariants tests/test_serving.py holds the device-sum rung
to: golden-family byte parity (raw AND converted), bounded compiles
under ragged sizes, and probe-gated degradation that leaves the live
model untouched.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))

import lightgbm_tpu as lgb
import lightgbm_tpu.serving.runtime as srt
from golden_common import GOLDEN_CASES, make_case_data
from lightgbm_tpu import telemetry
from lightgbm_tpu.booster import Booster
from lightgbm_tpu.cli import run as cli_run
from lightgbm_tpu.compiler import (PlanNotCompilable, build_plan,
                                   plan_summary)
from lightgbm_tpu.serving import MicroBatcher, ModelRegistry, ServingRuntime
from lightgbm_tpu.serving.sharded import ShardedServingRuntime

pytestmark = pytest.mark.quick


def _golden(name):
    bst = Booster(model_file=f"tests/data/golden_{name}.model.txt")
    X, _ = make_case_data(GOLDEN_CASES[name])
    return bst, X


def _recompiles():
    assert telemetry.install_compile_listener()
    return telemetry.REGISTRY.counter("jit.recompiles").value


# ------------------------------------------------------------- plan unit
def test_plan_permutation_and_inverse():
    bst, _ = _golden("binary")
    plan = build_plan(bst.export_predict_arrays(), tile_vmem_kb=1)
    T = plan.n_trees
    # perm covers every tree exactly once
    assert sorted(plan.perm.tolist()) == list(range(T))
    # gather_idx is the inverse through the padded flat layout: walking
    # the buckets/tiles in compiled order must find tree `perm[k]` at
    # the flat position gather_idx[perm[k]] — and padded positions are
    # never claimed by any tree
    claimed = set(plan.gather_idx.tolist())
    assert len(claimed) == T
    pos = 0
    k = 0
    for bucket in plan.buckets:
        tt = max(len(t) for t in bucket.tiles)
        for tile in bucket.tiles:
            for j in range(tt):
                if j < len(tile):
                    assert plan.perm[k] == tile[j]
                    assert plan.gather_idx[tile[j]] == pos
                    k += 1
                else:
                    assert pos not in claimed
                pos += 1
    # depth buckets are powers of two, ascending
    depths = [b.depth for b in plan.buckets]
    assert depths == sorted(depths)
    assert all(d & (d - 1) == 0 for d in depths)


def test_plan_tile_budget_and_summary():
    bst, _ = _golden("regression_l2")
    ex = bst.export_predict_arrays()
    small = build_plan(ex, tile_vmem_kb=1)
    large = build_plan(ex, tile_vmem_kb=4096)
    assert small.num_tiles() > large.num_tiles()
    s = plan_summary(small)
    assert s["tiles"] == small.num_tiles() == len(s["tile_stats"])
    for st in s["tile_stats"]:
        assert st["bytes"] <= max(1024, st["bytes"])   # shape sanity
        assert st["trees"] >= 1 and st["palette"] >= 1
    # every tile but oversized single-tree ones respects the budget
    multi = [st for st in s["tile_stats"] if st["trees"] > 1]
    assert all(st["bytes"] <= 1024 for st in multi)
    assert s["total_plane_bytes"] == small.total_plane_bytes()


def test_plan_refuses_unstackable_models():
    bst, _ = _golden("binary")
    ex = dict(bst.export_predict_arrays())
    ex["stacked"] = None
    with pytest.raises(PlanNotCompilable):
        build_plan(ex)
    ex2 = dict(bst.export_predict_arrays())
    ex2["average_factor"] = 4
    with pytest.raises(PlanNotCompilable):
        build_plan(ex2)


def test_quantize_node_words_decode_losslessly():
    # the packed planes must decode to exactly the stacked traversal
    # planes: palette-decoded thresholds bitwise, children exactly,
    # decision bits exactly — quantization is asserted lossless
    bst, _ = _golden("categorical")
    ex = bst.export_predict_arrays()
    plan = build_plan(ex, tile_vmem_kb=4)
    trees = ex["trees"]
    for bucket, planes in zip(plan.buckets, plan.planes):
        words = planes["words"]
        kids = planes["kids"]
        pal = planes["pal"].view(np.uint32)
        for ti, tile in enumerate(bucket.tiles):
            for j, i in enumerate(tile):
                t = trees[i]
                k = max(t.num_leaves - 1, 0)
                if k == 0:
                    continue
                w = words[ti, j, :k].view(np.uint32)
                dt = t.decision_type[:k]
                assert np.array_equal((w >> 31) & 1, dt & 1)
                assert np.array_equal((w >> 29) & 3, (dt >> 2) & 3)
                assert np.array_equal((w >> 28) & 1, (dt >> 1) & 1)
                assert np.array_equal((w >> 16) & 0xFFF,
                                      t.split_feature[:k])
                num = (dt & 1) == 0
                code = (w & 0xFFFF).astype(np.int64)
                want_bits = np.float32(t.threshold[:k]).view(np.uint32)
                assert np.array_equal(pal[ti][code[num]], want_bits[num])
                kd = kids[ti, j, :k]
                left = kd >> 16
                right = ((kd & 0xFFFF) ^ 0x8000) - 0x8000
                assert np.array_equal(left, t.left_child[:k])
                assert np.array_equal(right, t.right_child[:k])


# -------------------------------------------------- golden byte parity
@pytest.mark.parametrize("name", sorted(GOLDEN_CASES))
@pytest.mark.parametrize("raw", [True, False])
def test_compiled_golden_family_byte_parity(name, raw):
    # serve_compiled="on": CPU allowed, still probe-gated — the probe
    # must actually PASS on every golden family (multiclass and
    # transformed outputs included), and the bytes must come off the
    # compiled rung, not a silent degradation
    bst, X = _golden(name)
    rt = ServingRuntime(bst, compiled="on", tile_vmem_kb=4)
    assert rt.compiled_active, f"{name}: compiled parity probe failed"
    cc = telemetry.REGISTRY.counter("serve.compiled")
    before = cc.value
    got = rt.predict(X[:700], raw_score=raw)
    want = bst.predict(X[:700], raw_score=raw)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got, want), \
        f"{name} raw={raw}: compiled rung != booster.predict"
    assert cc.value > before


def test_compiled_padded_tail_rows_exact():
    bst, X = _golden("multiclass")
    rt = ServingRuntime(bst, compiled="on")
    assert rt.compiled_active
    for n in (1, 3, 33):
        assert np.array_equal(rt.predict(X[:n]), bst.predict(X[:n]))


# ---------------------------------------------------- bounded compiles
def test_compiled_ragged_sizes_bounded_compiles():
    # ragged 1..4097 through the micro-batcher on the compiled rung:
    # one compiled program per ROW bucket no matter how many depth
    # buckets/tiles the plan has, so total compiles stay within the
    # padding bound (PR 3 recompile listener)
    bst, _ = _golden("binary")
    rng = np.random.RandomState(11)
    sizes = [1, 2, 3, 5, 1023, 4096, 4097] + \
        [int(s) for s in rng.randint(1, 4098, 13)]
    X = rng.randn(4097, bst.num_feature())
    wants = {n: bst.predict(X[:n], raw_score=True) for n in set(sizes)}
    before = _recompiles()
    # force: skip the probe so the only compiles measured are the
    # serving programs themselves; device_sum off for the same reason
    rt = ServingRuntime(bst, compiled="force", device_sum="off")
    b = MicroBatcher(rt, max_wait_ms=0.0)
    try:
        for n in sizes:
            got = b.predict(X[:n], raw_score=True, timeout=120)
            assert np.array_equal(got, wants[n])
    finally:
        b.close()
    compiled = telemetry.REGISTRY.counter("jit.recompiles").value - before
    # compiled raw program + slot program (the probe batch path is
    # dormant here but _raw warms nothing): one each per bucket at most
    assert compiled <= 2 * len(rt.buckets()), \
        f"{compiled} compiles for ragged sizes (buckets: " \
        f"{len(rt.buckets())}) — compiled-rung padding bound is broken"


def test_compiled_warmup_precompiles_buckets():
    bst, X = _golden("binary")
    rt = ServingRuntime(bst, compiled="on", device_sum="off",
                        max_batch_rows=8)
    assert rt.compiled_active
    rt.warmup()
    before = _recompiles()
    for n in (1, 2, 3, 6, 8):
        assert np.array_equal(rt.predict(X[:n], raw_score=True),
                              bst.predict(X[:n], raw_score=True))
    after = telemetry.REGISTRY.counter("jit.recompiles").value
    assert after == before, \
        "compiled-rung request after warmup paid a compile"


# ------------------------------------------------------ probe-gate fence
def test_compiled_probe_gate_corrupted_node_word(monkeypatch):
    # a plan whose packed planes misroute (one doctored child word) must
    # be rejected by the refresh-time parity probe: the rung degrades
    # with cause=probe, the live model's other rungs keep serving
    # byte-identical results, and zero requests error
    bst, X = _golden("binary")
    orig_build = srt.build_plan

    def doctored(ex, **kw):
        plan = orig_build(ex, **kw)
        plan.planes[0]["kids"][0, 0, 0] = (3 << 16) | 3   # reroute root
        return plan

    monkeypatch.setattr(srt, "build_plan", doctored)
    dis = telemetry.REGISTRY.counter("serve.compiled_disabled",
                                     cause="probe")
    cc = telemetry.REGISTRY.counter("serve.compiled")
    before, before_cc = dis.value, cc.value
    rt = ServingRuntime(bst, compiled="on")     # probe runs here
    assert not rt.compiled_active
    assert dis.value == before + 1
    for raw in (True, False):
        assert np.array_equal(rt.predict(X[:100], raw_score=raw),
                              bst.predict(X[:100], raw_score=raw))
    assert cc.value == before_cc, "doctored plan must never serve"


def test_compiler_refusal_is_cause_compile(monkeypatch):
    # a kernel the compiler refuses (Pallas lowering / Mosaic) must land
    # in serve.compiled_disabled{cause=compile} WITH the compiler's
    # message — at the refresh probe and at a bucket the probe did not
    # compile — permanently (no breaker re-probe), without tainting the
    # ladder as `probe_fail`, and `rung_status()` must say why
    from lightgbm_tpu.resilience.breaker import PERMANENT
    bst, X = _golden("binary")
    msg = ("Unimplemented primitive in Pallas TPU lowering for "
           "KernelType.TC: cumsum")
    orig = srt.compiled_predict

    def refuse(*a, **k):
        raise NotImplementedError(msg)

    dis = telemetry.REGISTRY.counter("serve.compiled_disabled",
                                     cause="compile")
    before = dis.value
    monkeypatch.setattr(srt, "compiled_predict", refuse)
    rt = ServingRuntime(bst, compiled="on")     # probe runs here
    assert not rt.compiled_active
    assert dis.value == before + 1
    why = rt.rung_status()["disabled"]["compiled"]
    assert why["cause"] == "compile" and msg in why["detail"]
    assert rt._breakers["compiled"].state == PERMANENT
    assert not rt._state.probe_failed
    assert np.array_equal(rt.predict(X[:100]), bst.predict(X[:100]))

    # refused only at serve time: the probe's bucket compiles, a later
    # one does not — the rung retires with the same cause
    monkeypatch.setattr(srt, "compiled_predict", orig)
    rt = ServingRuntime(bst, compiled="on")
    assert rt.compiled_active and "compiled" not in \
        rt.rung_status()["disabled"]
    monkeypatch.setattr(srt, "compiled_predict", refuse)
    assert np.array_equal(rt.predict(X[:100]), bst.predict(X[:100]))
    assert not rt.compiled_active
    assert dis.value == before + 2
    assert rt.rung_status()["disabled"]["compiled"]["cause"] == "compile"


def test_compiled_auto_stays_off_on_cpu():
    # serve_compiled="auto" requires a TPU backend: on CPU the rung
    # reports cause=platform and the pre-existing ladder is untouched
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("TPU backend — auto legitimately enables")
    bst, X = _golden("binary")
    dis = telemetry.REGISTRY.counter("serve.compiled_disabled",
                                     cause="platform")
    before = dis.value
    rt = ServingRuntime(bst)
    assert not rt.compiled_active
    assert dis.value == before + 1
    assert np.array_equal(rt.predict(X[:50]), bst.predict(X[:50]))


def test_compiled_device_error_degrades_one_rung(monkeypatch):
    # the compiled program wedging mid-serve must hand over to the
    # device-sum rung (not the host walk) with the exact same bytes
    bst, X = _golden("binary")
    rt = ServingRuntime(bst, compiled="on")
    assert rt.compiled_active and rt.device_sum_active

    def boom(*a, **k):
        raise RuntimeError("kernel wedged")

    monkeypatch.setattr(srt, "compiled_predict", boom)
    de = telemetry.REGISTRY.counter("serve.device_errors")
    ds = telemetry.REGISTRY.counter("serve.device_sum")
    hw_before = sum(c.value for c in
                    telemetry.REGISTRY.counter_family("serve.host_walk"))
    before_de, before_ds = de.value, ds.value
    got = rt.predict(X[:64], raw_score=True)
    assert np.array_equal(got, bst.predict(X[:64], raw_score=True))
    assert de.value > before_de and ds.value > before_ds
    assert sum(c.value for c in
               telemetry.REGISTRY.counter_family("serve.host_walk")) \
        == hw_before


# ------------------------------------------- atomic publish + degrade
def test_refresh_never_serves_mixed_state(monkeypatch):
    # refresh() runs on a background thread while predict() keeps
    # serving: a request landing mid-refresh (new export produced, the
    # compiled probe still running) must compute with ONE whole model —
    # never the old plan's tiles over the new export's leaf values
    rng = np.random.RandomState(3)
    X = rng.randn(600, 5)
    y = (X[:, 0] - X[:, 1] + 0.3 * rng.randn(600) > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, lgb.Dataset(X, label=y),
                    num_boost_round=5)
    rt = ServingRuntime(bst, compiled="on")
    assert rt.compiled_active
    old = bst.predict(X[:64], raw_score=True)
    assert np.array_equal(rt.predict(X[:64], raw_score=True), old)
    bst.update()                               # same tree shapes (refit-
    bst.best_iteration = -1                    # style hot refresh)
    new = bst.predict(X[:64], raw_score=True)
    assert not np.array_equal(old, new)
    seen = {}
    orig_build = srt.build_plan

    def mid_refresh_probe(ex, **kw):
        seen["mid"] = rt.predict(X[:64], raw_score=True)
        return orig_build(ex, **kw)

    monkeypatch.setattr(srt, "build_plan", mid_refresh_probe)
    rt.refresh()
    # mid-refresh bytes are EXACTLY one model's output (the rung-less
    # phase-1 bundle serves the new export via the slot path)
    assert np.array_equal(seen["mid"], new), \
        "mid-refresh request mixed old plan with new export"
    assert rt.compiled_active
    assert np.array_equal(rt.predict(X[:64], raw_score=True), new)


def test_compiled_odd_max_batch_rows_pads_row_block(monkeypatch):
    # serve_max_batch_rows need not divide the kernel's ROW_BLOCK: the
    # clamped top bucket (300 rows) pads on up to a multiple inside the
    # compiled path, so load-time warmup and predict both serve instead
    # of raising out of the pallas_call driver
    bst, X = _golden("binary")
    rt = ServingRuntime(bst, compiled="on", max_batch_rows=300)
    assert rt.compiled_active
    # warm ONLY the clamped bucket — the one size that can trip the
    # kernel's row-block check (power-of-two buckets always divide);
    # full-ladder warmup coverage lives in the precompile test above
    monkeypatch.setattr(rt, "buckets", lambda: [300])
    assert rt.warmup() == 1
    assert rt.compiled_active, "clamped-bucket warmup dropped the rung"
    cc = telemetry.REGISTRY.counter("serve.compiled")
    before = cc.value
    assert np.array_equal(rt.predict(X[:280], raw_score=True),
                          bst.predict(X[:280], raw_score=True))
    assert cc.value > before


def test_warmup_compiled_failure_degrades_not_errors(monkeypatch):
    # a compiled rung that cannot even warm must not fail the model
    # load (registry.load calls warmup() with no predict-path guard
    # around it): the rung retires with cause=warmup_error and the
    # surviving ladder serves byte-identical results
    bst, X = _golden("binary")
    rt = ServingRuntime(bst, compiled="force", max_batch_rows=8)
    assert rt.compiled_active

    def boom(*a, **k):
        raise RuntimeError("compile wedged")

    monkeypatch.setattr(srt, "compiled_predict", boom)
    dis = telemetry.REGISTRY.counter("serve.compiled_disabled",
                                     cause="warmup_error")
    before = dis.value
    assert rt.warmup() > 0
    assert dis.value == before + 1
    assert not rt.compiled_active
    assert np.array_equal(rt.predict(X[:8], raw_score=True),
                          bst.predict(X[:8], raw_score=True))


def test_quantize_refuses_tile_at_leaf_slot_capacity():
    # leaf slots run 0..ni, encoded ~slot: ni == 2^15 would wrap the
    # deepest leaf's ~32768 to +32767 (an internal-node index) in the
    # kids word's int16 half — the packer must refuse AT the boundary
    from lightgbm_tpu.compiler.plan import TileBucket
    from lightgbm_tpu.compiler.quantize import MAX_TILE_NODES, pack_bucket
    bst, _ = _golden("binary")
    trees = bst.export_predict_arrays()["trees"]
    bucket = TileBucket(depth=2)
    bucket.tiles = [[0]]
    bucket.max_nodes = MAX_TILE_NODES
    with pytest.raises(PlanNotCompilable):
        pack_bucket(trees, bucket, 0)
    bucket.max_nodes = MAX_TILE_NODES - 1      # -(ni+1) = -32768 fits
    planes, _ = pack_bucket(trees, bucket, 0)
    assert planes["words"].shape[-1] == MAX_TILE_NODES - 1


# ------------------------------------------------- host-walk cause labels
def test_host_walk_cause_probe_fail(monkeypatch):
    # a runtime whose refresh-time parity probe FAILED that then hits a
    # device error must attribute its host walk to probe_fail — the
    # smoking-gun label for a miscompiling device
    bst, X = _golden("binary")
    orig = bst.export_predict_arrays

    def bad_export(*a, **k):
        ex = dict(orig(*a, **k))
        hi = np.asarray(ex["value_hi"])
        ex["value_hi"] = srt.jnp.asarray(hi ^ np.uint32(1 << 12))
        return ex

    monkeypatch.setattr(bst, "export_predict_arrays", bad_export)
    rt = ServingRuntime(bst)                    # device-sum probe fails
    assert not rt.device_sum_active

    def boom(*a, **k):
        raise RuntimeError("device wedged")

    monkeypatch.setattr(srt, "_LEAF_JIT", boom)
    pf = telemetry.REGISTRY.counter("serve.host_walk", cause="probe_fail")
    before = pf.value
    got = rt.predict(X[:32], raw_score=True)
    assert np.array_equal(got, bst.predict(X[:32], raw_score=True))
    assert pf.value == before + 1


def test_host_walk_cause_linear_tree():
    rng = np.random.RandomState(9)
    X = rng.randn(400, 4)
    y = X[:, 0] * 2.0 + X[:, 1]
    bst = lgb.train({"objective": "regression", "linear_tree": True,
                     "num_leaves": 7, "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=3)
    lt = telemetry.REGISTRY.counter("serve.host_walk", cause="linear_tree")
    before = lt.value
    rt = ServingRuntime(bst)
    assert np.array_equal(rt.predict(X[:40]), bst.predict(X[:40]))
    assert lt.value == before + 1


# ----------------------------------------------- accounting + wire-through
def test_device_bytes_accounts_plan_planes_and_demote_drops():
    bst, _ = _golden("binary")
    base = ServingRuntime(bst, compiled="off").device_bytes()
    rt = ServingRuntime(bst, compiled="on")
    assert rt.compiled_active
    with_plan = rt.device_bytes()
    assert with_plan > base, "plan planes missing from VRAM accounting"
    assert with_plan - base == sum(
        int(a.nbytes) for bucket in rt._plan_planes
        for a in bucket if a is not None)
    freed = rt.demote()
    assert freed == with_plan
    assert not rt.compiled_active and rt.device_bytes() == 0
    rt.refresh()                                # promotion re-probes
    assert rt.compiled_active
    assert rt.device_bytes() == with_plan


@pytest.mark.slow          # tier-1 keeps the single-runtime coverage;
def test_sharded_replicas_pin_their_own_plan():  # full tier runs this
    bst, X = _golden("binary")
    sh = ShardedServingRuntime(bst, shard_devices=0, compiled="on")
    assert sh.compiled_active
    for rep in sh.replicas:
        assert rep.compiled_active and rep._plan_planes is not None
    clock = telemetry.StageClock()
    got = sh.predict(X[:200], clock=clock)
    assert clock.rung == "compiled"
    assert np.array_equal(got, bst.predict(X[:200]))


def test_registry_serve_compiled_param():
    reg = ModelRegistry({"serve_compiled": "on", "serve_warmup": False})
    try:
        reg.load("m", "tests/data/golden_binary.model.txt")
        assert reg.get("m").runtime.compiled_active
    finally:
        reg.close()


# ------------------------------------------------------------------- CLI
def test_compile_plan_cli(capsys):
    assert cli_run(["compile-plan",
                    "tests/data/golden_multiclass.model.txt",
                    "serve_tile_vmem_kb=2"]) == 0
    out = capsys.readouterr().out
    assert "tiles:" in out and "permutation:" in out
    assert cli_run(["compile-plan",
                    "tests/data/golden_binary.model.txt", "--json"]) == 0
    import json
    s = json.loads(capsys.readouterr().out)
    assert s["trees"] == 10 and s["tiles"] >= 1
    assert sorted(s["permutation"]) == list(range(10))
