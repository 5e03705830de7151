"""Serving subsystem: bucketed runtime, micro-batcher, registry, HTTP.

The load-bearing claims (ISSUE acceptance criteria):

* BYTE-identity — `ServingRuntime.predict` must equal
  `booster.predict` bit-for-bit on every golden family, raw and
  transformed, on EVERY ladder rung: the device-sum rung (software
  binary64 accumulation on device), the slot rung (device slots + host
  f64 gather/sum in tree order), and the host walk.
* PROBE gate — a device-sum rung that cannot bit-match the host
  reference must degrade to the slot path at refresh time (counted in
  `serve.device_sum_disabled`), never serve wrong bytes.
* D2H — the device-sum rung moves N*K scores per request, not T*N
  slots, measured through `serve.d2h_bytes`.
* BOUNDED compiles — 50 ragged request sizes through the micro-batcher
  may compile at most one program per power-of-two bucket, asserted
  through the PR 3 `jax.monitoring` recompile listener.
* BUDGET — a load exceeding `serve_vram_budget_mb` demotes LRU entries
  and, still over, is rejected while loaded models keep serving.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.serving.runtime as srt
from golden_common import GOLDEN_CASES, make_case_data
from lightgbm_tpu import telemetry
from lightgbm_tpu.booster import Booster
from lightgbm_tpu.serving import (MicroBatcher, ModelRegistry,
                                  ServingClient, ServingOverloadError,
                                  ServingRuntime, bucket_rows)
from lightgbm_tpu.serving.http import make_server

pytestmark = pytest.mark.quick


def _golden(name):
    bst = Booster(model_file=f"tests/data/golden_{name}.model.txt")
    X, _ = make_case_data(GOLDEN_CASES[name])
    return bst, X


def _recompiles():
    """Process-wide compile counter."""
    assert telemetry.install_compile_listener()
    return telemetry.REGISTRY.counter("jit.recompiles").value


# --------------------------------------------------------------- buckets
def test_bucket_rows_math():
    assert [bucket_rows(n) for n in (0, 1, 2, 3, 4, 5, 7, 8, 9)] == \
        [1, 1, 2, 4, 4, 8, 8, 8, 16]
    assert bucket_rows(4096) == 4096
    assert bucket_rows(4097) == 4096          # caller chunks above cap
    assert bucket_rows(10, max_rows=8) == 8
    rt = ServingRuntime(_golden("binary")[0], max_batch_rows=8)
    assert rt.buckets() == [1, 2, 4, 8]


# ---------------------------------------------------- golden byte-parity
@pytest.mark.parametrize("name", sorted(GOLDEN_CASES))
@pytest.mark.parametrize("raw", [True, False])
@pytest.mark.parametrize("mode", ["auto", "off"])
def test_golden_family_byte_parity(name, raw, mode):
    # mode="auto" exercises the device-sum rung (probe-gated),
    # mode="off" pins the slot rung — both must be byte-identical
    bst, X = _golden(name)
    rt = ServingRuntime(bst, device_sum=mode)
    ds = telemetry.REGISTRY.counter("serve.device_sum")
    sp = telemetry.REGISTRY.counter("serve.slot_path")
    ds0, sp0 = ds.value, sp.value
    got = rt.predict(X, raw_score=raw)
    want = bst.predict(X, raw_score=raw)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got, want), \
        f"{name} raw={raw} mode={mode}: serving != booster.predict"
    if mode == "auto":
        # the probe must actually PASS on every golden family — the
        # fast path silently never engaging would also "pass" parity
        assert rt.device_sum_active, f"{name}: device-sum probe failed"
        assert ds.value > ds0 and sp.value == sp0
    else:
        assert not rt.device_sum_active
        assert ds.value == ds0 and sp.value > sp0


def test_padded_tail_rows_exact():
    # the rows that force padding (n not a power of two) must still be
    # bitwise equal — row independence under the vmap'd while_loop
    bst, X = _golden("multiclass")
    rt = ServingRuntime(bst)
    for n in (1, 3, 5, 33, 1023):
        assert np.array_equal(rt.predict(X[:n]), bst.predict(X[:n]))


# ------------------------------------------------- device-sum probe gate
def test_probe_gate_degrades_on_bad_leaf_planes(monkeypatch):
    # a device-sum rung that cannot bit-match the host reference must
    # NOT serve: corrupt the hi bit plane the device program sums from
    # (the slot path's f64 table stays intact) and the refresh-time
    # probe has to catch the mismatch, count it, and degrade — with
    # predictions still byte-identical through the slot rung
    bst, X = _golden("binary")
    orig = bst.export_predict_arrays

    def bad_export(*a, **k):
        ex = dict(orig(*a, **k))
        hi = np.asarray(ex["value_hi"])
        ex["value_hi"] = srt.jnp.asarray(hi ^ np.uint32(1 << 12))
        return ex

    monkeypatch.setattr(bst, "export_predict_arrays", bad_export)
    dis = telemetry.REGISTRY.counter("serve.device_sum_disabled")
    before = dis.value
    rt = ServingRuntime(bst)                   # probe runs here
    assert not rt.device_sum_active
    assert dis.value == before + 1
    for raw in (True, False):
        assert np.array_equal(rt.predict(X[:100], raw_score=raw),
                              bst.predict(X[:100], raw_score=raw))


def test_probe_rungs_share_routing_not_required_for_rf():
    # average_factor != 1 (random-forest averaging) stays off the
    # device-sum rung by construction — no probe, no disabled counter
    rng = np.random.RandomState(5)
    X = rng.randn(400, 4)
    y = (X[:, 0] + 0.2 * rng.randn(400) > 0).astype(float)
    bst = lgb.train({"objective": "binary", "boosting": "rf",
                     "bagging_freq": 1, "bagging_fraction": 0.6,
                     "feature_fraction": 0.8, "num_leaves": 7,
                     "verbosity": -1}, lgb.Dataset(X, label=y),
                    num_boost_round=4)
    dis = telemetry.REGISTRY.counter("serve.device_sum_disabled")
    before = dis.value
    rt = ServingRuntime(bst)
    assert not rt.device_sum_active
    assert dis.value == before, "RF exclusion is silent, not a failure"
    assert np.array_equal(rt.predict(X), bst.predict(X))


# ------------------------------------------------------- D2H accounting
def test_d2h_bytes_scores_not_slots():
    # the point of the device-sum rung: D2H shrinks from T*N slot words
    # to N*K finished scores (8 B raw hi/lo pair, 4 B converted f32)
    bst, X = _golden("binary")
    rt = ServingRuntime(bst)                   # probe traffic excluded
    assert rt.device_sum_active
    c = telemetry.REGISTRY.counter("serve.d2h_bytes")
    b = bucket_rows(300)                       # 512, K == 1

    before = c.value
    rt.predict(X[:300], raw_score=True)
    assert c.value - before == b * 8           # u32 hi + u32 lo planes

    before = c.value
    rt.predict(X[:300], raw_score=False)
    assert c.value - before == b * 4           # f32 scores

    off = ServingRuntime(bst, device_sum="off")
    T = len(off._export["trees"])
    before = c.value
    off.predict(X[:300], raw_score=True)
    slot_bytes = c.value - before
    assert slot_bytes == T * b * 4             # [T, N] i32 slots
    assert b * 8 < slot_bytes, "device-sum must move fewer bytes"


# ------------------------------------------------------ bounded compiles
def test_bounded_compiles_under_ragged_load():
    bst, _ = _golden("binary")
    before = _recompiles()
    # slot rung pinned: the device-sum probe compiles its own programs
    # at construction, which would double-count against the slot bound
    # (the device-sum bound gets its own test below)
    rt = ServingRuntime(bst, device_sum="off")
    b = MicroBatcher(rt, max_wait_ms=0.0)
    rng = np.random.RandomState(7)
    sizes = [1, 2, 3, 5, 4095, 4096, 4097] + \
        [int(s) for s in rng.randint(1, 4098, 43)]
    assert len(sizes) == 50
    try:
        for n in sizes:
            X = rng.randn(n, bst.num_feature())
            got = b.predict(X, raw_score=True, timeout=120)
            assert np.array_equal(got, bst.predict(X, raw_score=True))
    finally:
        b.close()
    compiled = telemetry.REGISTRY.counter("jit.recompiles").value - before
    assert compiled <= len(rt.buckets()), \
        f"{compiled} compiles for 50 ragged sizes (buckets: " \
        f"{len(rt.buckets())}) — padding bound is broken"


def test_warmup_precompiles_every_bucket():
    bst, X = _golden("binary")
    rt = ServingRuntime(bst, max_batch_rows=8)
    assert rt.warmup() == 4                    # buckets 1, 2, 4, 8
    before = _recompiles()
    for n in (1, 2, 3, 6, 8):
        assert np.array_equal(rt.predict(X[:n], raw_score=True),
                              bst.predict(X[:n], raw_score=True))
    after = telemetry.REGISTRY.counter("jit.recompiles").value
    assert after == before, "request after warmup paid a compile"


def test_device_sum_compiles_bounded_and_warmed():
    # the device-sum rung gets the same padding bound: after warmup
    # (which also warms the eager convert_output per bucket), ragged
    # requests — raw AND transformed — pay zero compiles, and the whole
    # runtime lifetime stays within buckets * programs
    bst, X = _golden("binary")
    sizes = (1, 2, 3, 5, 17, 33, 63, 64)
    # reference predictions first: booster.predict compiles its own
    # unpadded per-N programs, which must not count against serving
    wants = {(n, raw): bst.predict(X[:n], raw_score=raw)
             for n in sizes for raw in (True, False)}
    before = _recompiles()
    rt = ServingRuntime(bst, max_batch_rows=64)
    assert rt.device_sum_active
    rt.warmup()
    warmed = telemetry.REGISTRY.counter("jit.recompiles").value
    # slot + exact-raw + exact-converted + eager convert, one compile
    # each per bucket at most (construction probe shares bucket shapes)
    assert warmed - before <= 4 * len(rt.buckets())
    for (n, raw), want in wants.items():
        assert np.array_equal(rt.predict(X[:n], raw_score=raw), want)
    after = telemetry.REGISTRY.counter("jit.recompiles").value
    assert after == warmed, \
        "ragged device-sum request after warmup paid a compile"


# -------------------------------------------- export cache invalidation
def _train(rounds=5):
    rng = np.random.RandomState(3)
    X = rng.randn(600, 5)
    y = (X[:, 0] - X[:, 1] + 0.3 * rng.randn(600) > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, lgb.Dataset(X, label=y),
                    num_boost_round=rounds)
    return bst, X, y


def test_export_cache_hit_and_version():
    bst, _, _ = _train()
    ex1 = bst.export_predict_arrays()
    ex2 = bst.export_predict_arrays()
    assert ex1 is ex2, "unchanged model must hit the export cache"


def test_rollback_invalidates_export():
    bst, X, _ = _train()
    rt = ServingRuntime(bst)
    rt.predict(X[:8])
    n_trees = len(rt._export["trees"])
    bst.rollback_one_iter()
    assert rt.stale(), "rollback must bump the model version"
    rt.refresh()
    assert len(rt._export["trees"]) == n_trees - 1
    assert np.array_equal(rt.predict(X), bst.predict(X))


def test_tree_slice_key_survives_id_reuse():
    # rollback + retrain to the same length: list ids can be reused by
    # the allocator, so the key must also carry the version counter
    bst, _, _ = _train()
    key1 = bst._tree_slice_key(bst.trees)
    bst.rollback_one_iter()
    bst.update()
    key2 = bst._tree_slice_key(bst.trees)
    assert len(bst.trees) and key1 != key2


def test_continued_training_invalidates_export():
    bst, X, _ = _train()
    rt = ServingRuntime(bst)
    p_old = rt.predict(X, raw_score=True)      # f64 raw: any new tree shows
    bst.update()
    bst.best_iteration = -1    # unpin predict from the pre-update round
    assert rt.stale()
    rt.refresh()
    p_new = rt.predict(X, raw_score=True)
    assert np.array_equal(p_new, bst.predict(X, raw_score=True))
    assert not np.array_equal(p_old, p_new)


def test_refit_booster_serves_fresh_values():
    bst, X, y = _train()
    new = bst.refit(X, y, decay_rate=0.5)
    assert np.array_equal(ServingRuntime(new).predict(X),
                          new.predict(X))


# --------------------------------------------------------- micro-batcher
def test_batcher_coalesces_concurrent_requests():
    bst, X = _golden("binary")
    rt = ServingRuntime(bst)
    inner = rt.predict
    rt.predict = lambda Xq, raw_score=False, clock=None: (
        time.sleep(0.03), inner(Xq, raw_score=raw_score, clock=clock))[1]
    before = telemetry.REGISTRY.counter("serve.batches").value
    with MicroBatcher(rt, max_wait_ms=50.0) as b:
        reqs = [b.submit(X[i * 4:(i + 1) * 4]) for i in range(12)]
        outs = [r.wait(60) for r in reqs]
    for i, out in enumerate(outs):
        assert np.array_equal(out, bst.predict(X[i * 4:(i + 1) * 4]))
    batches = telemetry.REGISTRY.counter("serve.batches").value - before
    assert batches < 12, "12 tiny requests should coalesce"


def test_batcher_mixed_raw_and_prob_groups():
    bst, X = _golden("binary")
    with MicroBatcher(ServingRuntime(bst), max_wait_ms=20.0) as b:
        r1 = b.submit(X[:16], raw_score=True)
        r2 = b.submit(X[16:32], raw_score=False)
        assert np.array_equal(r1.wait(60),
                              bst.predict(X[:16], raw_score=True))
        assert np.array_equal(r2.wait(60), bst.predict(X[16:32]))


def test_batcher_sheds_on_full_queue():
    bst, X = _golden("binary")
    rt = ServingRuntime(bst)
    inner = rt.predict
    rt.predict = lambda Xq, raw_score=False, clock=None: (
        time.sleep(0.2), inner(Xq, raw_score=raw_score, clock=clock))[1]
    shed = 0
    with MicroBatcher(rt, max_wait_ms=0.0, queue_depth=1) as b:
        b.submit(X[:2])
        for _ in range(20):
            try:
                b.submit(X[:2])
            except ServingOverloadError:
                shed += 1
    assert shed >= 1, "bounded queue must reject at submit under load"


def test_queue_full_sheds_attributed_to_swap_window():
    # a shed while a registry build-then-swap is in flight must land in
    # `serve.shed.swap_window` (swap-cost), while the same shed outside
    # any window must NOT — the soak harness's "zero unattributed sheds
    # during swap windows" invariant rests on this attribution
    from lightgbm_tpu.serving import registry as registry_mod
    bst, X = _golden("binary")
    reg = telemetry.REGISTRY

    def _flood():
        rt = ServingRuntime(bst)
        inner = rt.predict
        rt.predict = lambda Xq, raw_score=False, clock=None: (
            time.sleep(0.2), inner(Xq, raw_score=raw_score,
                                   clock=clock))[1]
        shed = 0
        with MicroBatcher(rt, max_wait_ms=0.0, queue_depth=1) as b:
            b.submit(X[:2])
            for _ in range(20):
                try:
                    b.submit(X[:2])
                except ServingOverloadError:
                    shed += 1
        return shed

    swap_ctr = reg.counter("serve.shed.swap_window")
    base = swap_ctr.value
    with registry_mod._swap_window():
        assert reg.gauge("serve.swap_windows").value >= 1
        shed_in_window = _flood()
    assert shed_in_window >= 1
    assert swap_ctr.value - base == shed_in_window, \
        "every shed during a swap window must be attributed"
    assert reg.gauge("serve.swap_windows").value == 0
    base = swap_ctr.value
    shed_outside = _flood()
    assert shed_outside >= 1
    assert swap_ctr.value == base, \
        "steady-state load sheds must NOT count as swap-window sheds"


def test_batcher_deadline_shedding():
    bst, X = _golden("binary")
    rt = ServingRuntime(bst)
    inner = rt.predict
    rt.predict = lambda Xq, raw_score=False, clock=None: (
        time.sleep(0.05), inner(Xq, raw_score=raw_score, clock=clock))[1]
    before = telemetry.REGISTRY.counter("serve.shed").value
    with MicroBatcher(rt, max_wait_ms=0.0, deadline_ms=5.0) as b:
        reqs = [b.submit(X[:4]) for _ in range(5)]
        shed = 0
        for r in reqs:
            try:
                r.wait(30)
            except ServingOverloadError:
                shed += 1
    assert shed >= 1
    assert telemetry.REGISTRY.counter("serve.shed").value > before


def test_device_error_falls_back_to_host_walk(monkeypatch):
    # wedge BOTH device programs after the probe passed: the ladder
    # must walk device-sum -> slot -> host and still return the exact
    # bytes, counting each degradation
    bst, X = _golden("binary")
    rt = ServingRuntime(bst)
    assert rt.device_sum_active
    # the probes passed, so the host walk must be attributed to the
    # device error — not probe_fail — in the labeled cause counter
    fb = telemetry.REGISTRY.counter("serve.host_walk",
                                    cause="device_error")
    de = telemetry.REGISTRY.counter("serve.device_errors")
    before_fb, before_de = fb.value, de.value

    def boom(*a, **k):
        raise RuntimeError("device wedged")

    monkeypatch.setattr(srt, "_EXACT_JIT", boom)
    monkeypatch.setattr(srt, "_LEAF_JIT", boom)
    got = rt.predict(X[:32], raw_score=True)
    assert np.array_equal(got, bst.predict(X[:32], raw_score=True))
    assert fb.value > before_fb
    assert de.value >= before_de + 2           # one per wedged rung


def test_device_sum_error_degrades_one_rung_only(monkeypatch):
    # only the device-sum program wedged: the slot rung (not the host
    # walk) takes over, and no host fallback is counted
    bst, X = _golden("binary")
    rt = ServingRuntime(bst)
    assert rt.device_sum_active
    fb = telemetry.REGISTRY.counter("serve.host_walk",
                                    cause="device_error")
    sp = telemetry.REGISTRY.counter("serve.slot_path")
    before_fb, before_sp = fb.value, sp.value

    def boom(*a, **k):
        raise RuntimeError("device wedged")

    monkeypatch.setattr(srt, "_EXACT_JIT", boom)
    got = rt.predict(X[:32], raw_score=True)
    assert np.array_equal(got, bst.predict(X[:32], raw_score=True))
    assert sp.value > before_sp and fb.value == before_fb


# -------------------------------------------------------------- registry
def test_registry_load_swap_unload():
    b1, X1 = _golden("binary")
    b2, X2 = _golden("goss_bagging")
    reg = ModelRegistry({"serve_warmup": False})
    try:
        reg.load("m", "tests/data/golden_binary.model.txt")
        assert reg.names() == ["m"]
        assert np.array_equal(reg.predict(X1[:16], model="m"),
                              b1.predict(X1[:16]))
        reg.load("m", b2)                       # atomic hot-swap
        assert np.array_equal(reg.predict(X2[:16], model="m"),
                              b2.predict(X2[:16]))
        with pytest.raises(lgb.LightGBMError, match="no model"):
            reg.predict(X1[:2], model="ghost")
    finally:
        reg.close()
    assert reg.names() == []


def _device_bytes(path):
    # size an export without engaging the probe (device_bytes is
    # mode-independent)
    return ServingRuntime(Booster(model_file=path),
                          device_sum="off").device_bytes()


def test_registry_budget_lru_demotes_then_serves():
    small_p = "tests/data/golden_binary.model.txt"
    big_p = "tests/data/golden_multiclass.model.txt"
    b_small, b_big = _device_bytes(small_p), _device_bytes(big_p)
    # budget fits either model alone, never both
    budget_mb = max(b_small, b_big) / float(1 << 20)
    dem = telemetry.REGISTRY.counter("serve.demotions")
    before = dem.value
    reg = ModelRegistry({"serve_warmup": False,
                         "serve_vram_budget_mb": budget_mb})
    try:
        reg.load("small", small_p)
        reg.load("big", big_p)                 # LRU-demotes "small"
        assert dem.value == before + 1
        st = reg.status()
        assert st["models"] == ["big", "small"]
        assert st["demoted"] == ["small"]
        assert st["device_bytes"]["small"] == 0
        assert st["device_bytes"]["big"] == b_big
        # a demoted entry keeps serving bit-identical results
        bs, Xs = _golden("binary")
        bb, Xb = _golden("multiclass")
        assert np.array_equal(reg.predict(Xs[:64], model="small"),
                              bs.predict(Xs[:64]))
        assert np.array_equal(reg.predict(Xb[:64], model="big"),
                              bb.predict(Xb[:64]))
        # refresh re-promotes the demoted entry
        reg.get("small").runtime.refresh()
        assert reg.status()["demoted"] == []
    finally:
        reg.close()


def test_registry_budget_rejects_unfittable_load():
    fams = {"binary": "tests/data/golden_binary.model.txt",
            "multiclass": "tests/data/golden_multiclass.model.txt"}
    sizes = {f: _device_bytes(p) for f, p in fams.items()}
    small = min(sizes, key=sizes.get)
    big = max(sizes, key=sizes.get)
    assert sizes[small] < sizes[big]
    # budget fits the small model but can NEVER fit the big one
    budget_mb = ((sizes[small] + sizes[big]) // 2) / float(1 << 20)
    reg = ModelRegistry({"serve_warmup": False,
                         "serve_vram_budget_mb": budget_mb})
    try:
        reg.load("small", fams[small])
        with pytest.raises(lgb.LightGBMError,
                           match="keep serving"):
            reg.load("big", fams[big])
        # the failed load demoted "small" trying to make room, but
        # never touched availability — it still serves, exactly
        assert reg.names() == ["small"]
        bs, Xs = _golden(small)
        assert np.array_equal(reg.predict(Xs[:64], model="small"),
                              bs.predict(Xs[:64]))
    finally:
        reg.close()


def test_registry_staleness_and_auto_refresh():
    bst, X, _ = _train()
    reg = ModelRegistry({"serve_warmup": False,
                         "serve_auto_refresh": True})
    ar = telemetry.REGISTRY.counter("serve.auto_refresh")
    before = ar.value
    try:
        reg.load("m", bst)
        assert reg.status()["stale"] == []
        bst.update()
        bst.best_iteration = -1    # unpin predict from the old round
        assert reg.status()["stale"] == ["m"]
        assert telemetry.REGISTRY.gauge("serve.stale").value == 1
        # auto-refresh re-exports on the next predict, OFF the request
        # thread: the stale export keeps serving until the new one is
        # swapped in, so wait for the swap before comparing
        reg.predict(X, model="m", raw_score=True)
        assert ar.value == before + 1
        deadline = time.monotonic() + 60.0
        while reg.status()["stale"] and time.monotonic() < deadline:
            time.sleep(0.02)
        assert reg.status()["stale"] == []
        got = reg.predict(X, model="m", raw_score=True)
        assert ar.value == before + 1
        assert np.array_equal(got, bst.predict(X, raw_score=True))
        assert telemetry.REGISTRY.gauge("serve.stale").value == 0
    finally:
        reg.close()


def test_registry_warmup_on_load():
    # (no lower-bound assert on the load itself: the jit cache is
    # process-wide, so another test may have warmed these shapes first)
    reg = ModelRegistry({"serve_max_batch_rows": 8})
    _recompiles()                               # ensure listener, or skip
    try:
        reg.load("w", "tests/data/golden_binary.model.txt")
        bst, X = _golden("binary")
        after_load = telemetry.REGISTRY.counter("jit.recompiles").value
        assert np.array_equal(reg.predict(X[:5], model="w",
                                          raw_score=True),
                              bst.predict(X[:5], raw_score=True))
        assert telemetry.REGISTRY.counter("jit.recompiles").value == \
            after_load, "first request after warm load paid a compile"
    finally:
        reg.close()


# ------------------------------------------------------------------ HTTP
def _serve(client):
    srv = make_server(client, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req, timeout=60).read())


def test_http_predict_healthz_metrics():
    bst, X = _golden("binary")
    client = ServingClient(bst, params={"serve_warmup": False})
    srv, base = _serve(client)
    try:
        resp = _post(f"{base}/predict",
                     {"rows": X[:32].tolist(), "raw_score": True})
        assert resp["model"] == "default" and resp["rows"] == 32
        assert np.array_equal(np.asarray(resp["predictions"]),
                              bst.predict(X[:32], raw_score=True))
        hz = json.loads(urllib.request.urlopen(
            f"{base}/healthz", timeout=30).read())
        assert hz["status"] == "ok" and hz["models"] == ["default"]
        assert hz["stale"] == [] and hz["demoted"] == []
        assert hz["device_bytes"]["default"] > 0
        metrics = urllib.request.urlopen(
            f"{base}/metrics", timeout=30).read().decode()
        assert "lgbm_tpu" in metrics and "serve" in metrics
    finally:
        srv.shutdown()
        srv.server_close()
        client.close()


def test_http_error_codes():
    bst, X = _golden("binary")
    client = ServingClient(bst, params={"serve_warmup": False})
    srv, base = _serve(client)
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/predict", {"oops": 1})
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/predict",
                  {"rows": X[:2].tolist(), "model": "ghost"})
        assert e.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{base}/nowhere", timeout=30)
        assert e.value.code == 404
    finally:
        srv.shutdown()
        srv.server_close()
        client.close()
