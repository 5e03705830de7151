"""Bounded-error quantized serving tier (`serve_precision=bounded`) +
the quantized histogram-training default (`hist_impl`).

The bounded rung's contract is different from every exact rung's — it
promises |served - exact| <= the bound PUBLISHED AT EXPORT, not byte
parity — so this file holds it to exactly that contract on all five
golden families (raw and converted outputs), and to the two invariants
the tier must never compromise:

 * the exact ladder underneath stays byte-identical to
   `booster.predict` (bounded is ADDITIVE — losing it costs latency,
   never correctness);
 * the refresh probe is load-bearing: a doctored quantization plane
   whose real error exceeds the published bound disables exactly the
   bounded rung (cause-labeled), and the model keeps serving.

The training half pins the `hist_impl` request surface: explicit
int-lattice impls are byte-identical to auto, ineligible requests
degrade with PRICED fallback events (degrade-don't-error), and the
interpret plumbing lets the Pallas family run on CPU for parity checks.
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
from lightgbm_tpu.serving import ServingRuntime
from lightgbm_tpu.serving.client import ServingClient

pytestmark = pytest.mark.quick


def _golden(name):
    bst = Booster(model_file=f"tests/data/golden_{name}.model.txt")
    X, _ = make_case_data(GOLDEN_CASES[name])
    return bst, X


# --------------------------------------------------- the error contract
@pytest.mark.parametrize("name", sorted(GOLDEN_CASES))
def test_bounded_golden_family_within_bound(name):
    # every golden family must quantize, serve off the bounded rung, and
    # land inside the bound published at export — raw AND converted (the
    # shipped converts are 1-Lipschitz in the sup norm: identity,
    # sigmoid, softmax — so the raw-score bound covers both surfaces)
    bst, X = _golden(name)
    rt = ServingRuntime(bst, precision="bounded")
    assert rt.precision == "bounded"
    assert rt.bounded_active, f"{name}: bounded tier failed to enable"
    bound = rt.bounded_bound
    assert bound is not None and np.isfinite(bound) and bound > 0
    # the probe already measured the refresh batch against the bound
    assert rt.bounded_measured_error is not None
    assert rt.bounded_measured_error <= bound
    cc = telemetry.REGISTRY.counter("serve.bounded")
    before = cc.value
    for raw in (True, False):
        got = rt.predict(X[:700], raw_score=raw)
        want = bst.predict(X[:700], raw_score=raw)
        assert got.shape == want.shape
        err = float(np.max(np.abs(np.asarray(got, np.float64) - want)))
        assert err <= bound, \
            f"{name} raw={raw}: bounded error {err} > published {bound}"
    assert cc.value > before, f"{name}: requests did not use the rung"


@pytest.mark.parametrize("name", sorted(GOLDEN_CASES))
def test_exact_default_stays_byte_identical(name):
    # serve_precision defaults to exact: the bounded tier must be
    # invisible — no rung active, bytes identical to booster.predict
    bst, X = _golden(name)
    rt = ServingRuntime(bst)
    assert rt.precision == "exact"
    assert not rt.bounded_active
    for raw in (True, False):
        assert np.array_equal(rt.predict(X[:300], raw_score=raw),
                              bst.predict(X[:300], raw_score=raw))


def test_exact_ladder_beneath_bounded_is_byte_identical():
    # on a bounded runtime, blocking the bounded breaker must drop the
    # request onto the EXACT ladder — byte-identical, not merely within
    # the bound (losing the tier costs latency, never correctness)
    bst, X = _golden("binary")
    rt = ServingRuntime(bst, precision="bounded")
    assert rt.bounded_active
    rt._breakers["bounded"].allow_request = lambda: False
    for raw in (True, False):
        assert np.array_equal(rt.predict(X[:200], raw_score=raw),
                              bst.predict(X[:200], raw_score=raw))


def test_bounded_kernel_and_stacked_paths_agree():
    # with compiled planes the bounded rung traverses via the Pallas
    # kernel; without, via the stacked XLA scan — both share
    # accumulate_slots_bounded, so their f32 scores must be
    # byte-identical (same codes, same combine order)
    bst, X = _golden("multiclass")
    rt_plan = ServingRuntime(bst, precision="bounded", compiled="on")
    rt_scan = ServingRuntime(bst, precision="bounded")
    assert rt_plan.compiled_active and rt_plan.bounded_active
    assert not rt_scan.compiled_active and rt_scan.bounded_active
    for raw in (True, False):
        a = rt_plan.predict(X[:500], raw_score=raw)
        b = rt_scan.predict(X[:500], raw_score=raw)
        assert np.array_equal(a, b)


def test_bounded_plane_bytes_under_a_third_of_compiled():
    # the tier's whole reason to exist: int8 leaf planes cut the
    # compiled rung's resident plane bytes by >= 3x (acceptance floor)
    bst, _ = _golden("binary")
    rt = ServingRuntime(bst, precision="bounded", compiled="on")
    assert rt.bounded_active and rt.compiled_active
    st = rt._state
    bounded_bytes = sum(int(a.nbytes) for a in st.bounded_planes)
    compiled_bytes = sum(int(a.nbytes) for bucket in st.plan_planes
                         for a in bucket if a is not None)
    assert bounded_bytes <= compiled_bytes / 3, \
        f"bounded planes {bounded_bytes}B vs compiled {compiled_bytes}B"


def test_bounded_ledger_owner_row():
    # plane bytes are attributed to the serve.<model> owner under the
    # rung=bounded tag, so the memory ledger can answer "what does the
    # bounded tier cost me"
    from lightgbm_tpu.telemetry.memledger import MEMLEDGER
    was = MEMLEDGER.enabled
    MEMLEDGER.configure(enabled=True, reconcile_ms=0.0)
    try:
        bst, _ = _golden("binary")
        rt = ServingRuntime(bst, precision="bounded", name="ledgermodel")
        assert rt.bounded_active
        snap = MEMLEDGER.snapshot()
        key = "serve.ledgermodel.planes{rung=bounded}"
        total = sum(d["owners"].get(key, {}).get("bytes", 0)
                    for d in snap["devices"].values())
        assert total > 0, f"no ledger row under {key}"
        rt._ledger_release()
    finally:
        MEMLEDGER.configure(enabled=was)


# ------------------------------------------------- probe is load-bearing
def test_doctored_scale_plane_disables_only_bounded(monkeypatch):
    # a quantization plane whose REAL error exceeds the published bound
    # (scales silently x4, bound left as exported) must flunk the
    # refresh probe: cause=bound, only the bounded rung disabled, zero
    # requests served off it, and the live model keeps serving exact
    bst, X = _golden("binary")
    orig = srt.pack_bounded

    def doctored(*a, **kw):
        out = orig(*a, **kw)
        out["scales"] = out["scales"] * np.float32(4.0)
        return out

    monkeypatch.setattr(srt, "pack_bounded", doctored)
    dis = telemetry.REGISTRY.counter("serve.bounded_disabled",
                                     cause="bound")
    cc = telemetry.REGISTRY.counter("serve.bounded")
    before, before_cc = dis.value, cc.value
    rt = ServingRuntime(bst, precision="bounded")   # probe runs here
    assert not rt.bounded_active
    assert dis.value == before + 1
    # the measurement that convicted the plane stays visible
    assert rt.bounded_measured_error is not None
    assert rt.bounded_measured_error > 0
    for raw in (True, False):
        assert np.array_equal(rt.predict(X[:200], raw_score=raw),
                              bst.predict(X[:200], raw_score=raw))
    assert cc.value == before_cc, "doctored plane must never serve"


def test_unquantizable_model_degrades_cause_labeled(monkeypatch):
    # pack_bounded refusing a model (PlanNotCompilable) is a clean
    # cause-labeled degradation, not an error
    from lightgbm_tpu.compiler import PlanNotCompilable
    bst, X = _golden("regression_l2")

    def refuse(*a, **kw):
        raise PlanNotCompilable("synthetic refusal")

    monkeypatch.setattr(srt, "pack_bounded", refuse)
    dis = telemetry.REGISTRY.counter("serve.bounded_disabled",
                                     cause="not_quantizable")
    before = dis.value
    rt = ServingRuntime(bst, precision="bounded")
    assert not rt.bounded_active
    assert dis.value == before + 1
    assert np.array_equal(rt.predict(X[:100]), bst.predict(X[:100]))


def test_bad_precision_value_rejected():
    bst, _ = _golden("binary")
    with pytest.raises(Exception, match="serve_precision"):
        ServingRuntime(bst, precision="fuzzy")


# ------------------------------------------------------ registry surface
def test_registry_publishes_bound_in_status():
    bst, X = _golden("binary")
    client = ServingClient(params={"serve_precision": "bounded",
                                   "verbosity": -1})
    try:
        client.load("m", bst)
        st = client.status()
        blk = st["bounded"]["m"]
        assert blk["active"] is True
        assert blk["measured_max_abs_error"] <= blk["bound"]
        p = client.predict(X[:100], model="m")
        err = float(np.max(np.abs(np.asarray(p, np.float64)
                                  - bst.predict(X[:100]))))
        assert err <= blk["bound"]
        fleet = telemetry.fleet_snapshot()
        assert "m" in fleet.get("bounded", {})
    finally:
        client.close()


# ----------------------------------------- hist_impl training request
def _hist_train(X, y, **extra):
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "use_quantized_grad": True, "num_grad_quant_bins": 8}
    p.update(extra)
    return lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=4)


def _trees(bst):
    s = bst.model_to_string()
    return s[:s.index("\nparameters:")]         # the echo names the knobs


@pytest.fixture(scope="module")
def hist_data():
    rng = np.random.RandomState(11)
    X = rng.randn(500, 6)
    return X, (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)


def test_hist_impl_auto_promotes_lattice(hist_data):
    X, y = hist_data
    bst = _hist_train(X, y)
    assert bst._grower_spec.hist_impl == "packed"
    assert _trees(_hist_train(X, y, hist_impl="packed")) == _trees(bst)


def test_hist_impl_pallas_q_interpret_byte_identical(hist_data):
    # the explicit Pallas lattice impl runs on CPU under hist_interpret
    # and must produce byte-identical trees to the packed default (the
    # backend-parity contract, now assertable without a TPU)
    X, y = hist_data
    base = _hist_train(X, y)
    b = _hist_train(X, y, hist_impl="pallas_q", hist_interpret=True)
    assert b._grower_spec.hist_impl == "pallas_q"
    assert _trees(b) == _trees(base)


def test_hist_impl_pallas_q_wave_interpret_byte_identical(hist_data):
    # the same contract under the wave policy (the multi-leaf int8
    # kernel): trees byte-identical to the auto choice trained under the
    # same policy
    X, y = hist_data
    b = _hist_train(X, y, hist_impl="pallas_q",
                    hist_interpret=True, tree_grow_policy="wave")
    assert b._grower_spec.hist_impl == "pallas_q"
    assert b._grow_policy == "wave"
    auto = _hist_train(X, y, tree_grow_policy="wave")
    assert _trees(b) == _trees(auto)


def test_hist_impl_ineligible_request_priced(hist_data):
    # pallas_q without a Pallas backend (CPU, no interpret) degrades to
    # the auto path with exactly ONE priced fallback event — and the
    # model is byte-identical to auto (degradation changes speed only)
    X, y = hist_data
    ev = telemetry.REGISTRY.counter("fallback.events")
    before = ev.value
    b = _hist_train(X, y, hist_impl="pallas_q")
    assert b._grower_spec.hist_impl == "packed"
    assert ev.value == before + 1
    assert _trees(b) == _trees(_hist_train(X, y))


def test_hist_impl_quantized_disqualified_priced(hist_data):
    # use_quantized_grad=True + GOSS: the lattice cannot apply — the
    # auto path must say so with a priced event, not fall back silently
    X, y = hist_data
    ev = telemetry.REGISTRY.counter("fallback.events")
    before = ev.value
    b = _hist_train(X, y, boosting="goss")
    assert b._grower_spec.hist_impl == "segment_sum"
    assert ev.value == before + 1


def test_hist_impl_unknown_value_raises(hist_data):
    X, y = hist_data
    with pytest.raises(Exception, match="hist_impl"):
        _hist_train(X, y, hist_impl="bogus")
