"""Fused Pallas histogram+split kernel (r6: hist_impl='pallas_fused' /
'pallas_fused_q', `tpu_fused_split`).

The load-bearing claims (ISSUE acceptance criteria), all checked in
interpret mode so they run on CPU:

* the fused kernel's histogram is BITWISE the multi kernel's, and its
  compact candidate tensor decides the same split as `find_best_split`
  field-for-field — so fused wave models are byte-identical to the
  `pallas`/`pallas_q` models they replace;
* the scan-only companion (`pallas_split_scan`, sibling-subtracted
  histograms) emits bitwise-interchangeable candidates;
* ineligible configurations degrade silently to the base impl (grower)
  or never upgrade (booster `_maybe_fuse_hist_impl`);
* repeated waves share one compiled program (PR 3 recompile listener).
"""
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.ops import pallas_hist as ph
from lightgbm_tpu.ops.grow import GrowerSpec
from lightgbm_tpu.ops.grow_wave import make_wave_grower
from lightgbm_tpu.ops.histogram import hist_value
from lightgbm_tpu.ops.split import fused_numerical_candidates

pytestmark = pytest.mark.quick

SCAN_KW = dict(l1=0.0, l2=1.0, min_data_in_leaf=5.0,
               min_sum_hessian=1e-3, min_gain_to_split=0.0)


def _kernel_case(seed=0, n=512, f=6, mb=32, width=4, quantized=False):
    """bins + payload + leaf assignment with short bin counts and all
    three missing types — the metadata mix the in-kernel scan gates on."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, mb, (f, n)).astype(np.int32)
    nb = np.full(f, mb, np.int32)
    nb[1] = 17
    bins[1] %= 17
    missing = np.zeros(f, np.int32)
    missing[2] = 2                                   # NaN bin
    missing[4] = 1                                   # zero-as-missing
    if quantized:
        payload = np.stack([rng.randint(-15, 16, n) * 0.25,
                            rng.randint(1, 16, n) * 0.125,
                            np.ones(n)], axis=1).astype(np.float32)
    else:
        payload = rng.randn(n, 3).astype(np.float32)
        payload[:, 2] = 1.0
    lid = rng.randint(0, width + 2, n).astype(np.int32)
    parent = np.stack([
        np.bincount(np.clip(lid, 0, width), weights=payload[:, c],
                    minlength=width + 1)[:width] for c in range(3)],
        axis=1).astype(np.float32)
    return (jnp.asarray(bins), jnp.asarray(payload), jnp.asarray(lid),
            jnp.arange(width, dtype=jnp.int32), jnp.asarray(nb),
            jnp.asarray(missing), jnp.asarray(parent), mb)


def _xla_candidates(hist, nb, miss, parent):
    """[S, F, MB, 3] -> [S, 2, F, 8] via the shared XLA reduction."""
    ref = fused_numerical_candidates(
        jnp.transpose(jnp.asarray(hist), (1, 0, 2, 3)), nb, miss,
        parent, **SCAN_KW)
    return np.transpose(np.asarray(ref), (1, 2, 0, 3))


# ------------------------------------------------- kernel-level parity
def test_fused_kernel_hist_and_candidates_exact():
    bins, pj, lid, slots, nb, miss, parent, mb = _kernel_case()
    want_h = np.asarray(ph.pallas_histogram_multi(
        bins, pj, lid, slots, mb, row_tile=256, interpret=True))
    got_h, cand = ph.pallas_fused_hist_split_rows(
        bins, ph._split_payload9(pj), lid, slots, nb, miss, parent, mb,
        row_tile=256, interpret=True, **SCAN_KW)
    # the f32 family hands back both limbs of every sum; its scan read
    # their value, which is the unfused kernel's histogram bit for bit
    assert got_h.shape[-1] == 6
    np.testing.assert_array_equal(np.asarray(hist_value(got_h)), want_h)
    np.testing.assert_array_equal(
        np.asarray(got_h), np.asarray(ph.pallas_histogram_multi_rows(
            bins, ph._split_payload9(pj), lid, slots, mb, row_tile=256,
            interpret=True)))
    np.testing.assert_array_equal(
        np.asarray(cand), _xla_candidates(want_h, nb, miss, parent))


def test_fused_quantized_kernel_hist_and_candidates_exact():
    bins, pj, lid, slots, nb, miss, parent, mb = _kernel_case(
        seed=3, quantized=True)
    s_g, s_h = jnp.float32(0.25), jnp.float32(0.125)
    want_h = np.asarray(ph.pallas_histogram_multi_quantized(
        bins, pj, lid, slots, mb, s_g, s_h, row_tile=256, interpret=True))
    got_h, cand = ph.pallas_fused_hist_split_quantized_rows(
        bins, ph.quantized_lattice_rows(pj, s_g, s_h), lid, slots, nb,
        miss, parent, mb, s_g, s_h, row_tile=256, interpret=True,
        **SCAN_KW)
    np.testing.assert_array_equal(np.asarray(got_h), want_h)
    np.testing.assert_array_equal(
        np.asarray(cand), _xla_candidates(want_h, nb, miss, parent))


def test_scan_only_kernel_matches_xla_reduction():
    # sibling-subtracted histograms never pass through the fused kernel;
    # the scan-only companion must still emit bitwise-equal candidates
    rng = np.random.RandomState(9)
    s, f, mb = 4, 6, 32
    hist = rng.randn(s, f, mb, 3).astype(np.float32)
    hist[..., 1] = np.abs(hist[..., 1])
    hist[..., 2] = rng.randint(0, 50, (s, f, mb))
    nb = jnp.asarray(np.array([32, 17, 32, 9, 32, 32], np.int32))
    miss = jnp.asarray(np.array([0, 1, 2, 0, 1, 2], np.int32))
    parent = jnp.asarray(hist.sum(axis=(1, 2))[:, :3] / f)
    cand = ph.pallas_split_scan(jnp.asarray(hist), nb, miss, parent,
                                interpret=True, **SCAN_KW)
    np.testing.assert_array_equal(
        np.asarray(cand), _xla_candidates(hist, nb, miss, parent))


def test_fused_probe_exact_parity_interpret():
    # the booster's upgrade gate, run in interpret mode: both families
    # must certify on the CPU reference lowering
    assert ph._probe_fused(True, 32, 6, 4, False)
    assert ph._probe_fused(True, 32, 6, 4, True)


# --------------------------------------------- wave-model byte-identity
def _wave_case(seed=7, n=3000, f=6, mb=32):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, mb, (f, n)).astype(np.int32)
    nb = np.full(f, mb, np.int32)
    nb[1] = 17
    bins[1] %= 17
    missing = np.zeros(f, np.int32)
    missing[2] = 2
    grad = rng.randn(n).astype(np.float32)
    hess = (0.1 + rng.rand(n)).astype(np.float32)
    sw = np.ones(n, np.float32)
    feat = dict(nb=jnp.asarray(nb), missing=jnp.asarray(missing),
                default=jnp.zeros(f, jnp.int32),
                is_cat=jnp.zeros(f, bool), mono=jnp.zeros(f, jnp.int32))
    return bins, grad, hess, sw, feat, jnp.ones(f, bool)


def _grow(impl, bins, grad, hess, sw, feat, allowed, mb=32, **spec_kw):
    kw = dict(num_leaves=15, max_depth=0, max_bin=mb, lambda_l1=0.0,
              lambda_l2=1.0, min_data_in_leaf=5.0,
              min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
              max_delta_step=0.0, hist_impl=impl, wave_width=4,
              has_cat=False, hist_interpret=True)
    kw.update(spec_kw)
    grow = make_wave_grower(GrowerSpec(**kw))
    return grow(jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
                jnp.asarray(sw), feat, allowed)


def _assert_trees_equal(a, b, ctx=""):
    for name, x, y in zip(a._fields, a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            f"{ctx}: field {name} differs"


@pytest.mark.parametrize("has_cat", [False, True])
def test_wave_model_byte_identical(has_cat):
    bins, grad, hess, sw, feat, allowed = _wave_case()
    if has_cat:
        feat = dict(feat, is_cat=jnp.asarray(
            np.array([0, 0, 0, 1, 0, 0], bool)))
    a = _grow("pallas", bins, grad, hess, sw, feat, allowed,
              has_cat=has_cat)
    b = _grow("pallas_fused", bins, grad, hess, sw, feat, allowed,
              has_cat=has_cat)
    assert int(a.n_splits) > 0
    _assert_trees_equal(a, b, f"has_cat={has_cat}")


def test_wave_model_byte_identical_quantized():
    bins, _, _, sw, feat, allowed = _wave_case(seed=11)
    rng = np.random.RandomState(11)
    n = len(sw)
    s_g, s_h = np.float32(0.25), np.float32(0.125)
    grad = (rng.randint(-15, 16, n) * s_g).astype(np.float32)
    hess = (rng.randint(1, 16, n) * s_h).astype(np.float32)
    feat = dict(feat, qscales=jnp.asarray(np.stack([s_g, s_h])))
    a = _grow("pallas_q", bins, grad, hess, sw, feat, allowed)
    b = _grow("pallas_fused_q", bins, grad, hess, sw, feat, allowed)
    assert int(a.n_splits) > 0
    _assert_trees_equal(a, b, "quantized")


def test_wave_fused_ineligible_config_degrades_to_base():
    # path_smooth forces the given-output gain branch — the grower must
    # silently run the base impl, producing the base model unchanged
    bins, grad, hess, sw, feat, allowed = _wave_case(seed=13)
    a = _grow("pallas", bins, grad, hess, sw, feat, allowed,
              path_smooth=1.0)
    b = _grow("pallas_fused", bins, grad, hess, sw, feat, allowed,
              path_smooth=1.0)
    _assert_trees_equal(a, b, "path_smooth fallback")


def test_strict_grower_normalizes_fused_to_base():
    from lightgbm_tpu.ops.grow import make_grower
    kw = dict(num_leaves=7, max_depth=0, max_bin=32, lambda_l1=0.0,
              lambda_l2=1.0, min_data_in_leaf=5.0,
              min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
              max_delta_step=0.0, hist_impl="pallas_fused",
              has_cat=False, hist_interpret=True)
    bins, grad, hess, sw, feat, allowed = _wave_case(seed=17)
    a = make_grower(GrowerSpec(**kw))(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(sw), feat, allowed)
    b = make_grower(GrowerSpec(**dict(kw, hist_impl="pallas")))(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(sw), feat, allowed)
    _assert_trees_equal(a, b, "strict normalization")


# ------------------------------------------------------ booster gating
def test_base_hist_impl_mapping():
    assert ph.base_hist_impl("pallas_fused") == "pallas"
    assert ph.base_hist_impl("pallas_fused_q") == "pallas_q"
    for impl in ("xla", "packed", "pallas", "pallas_q", "segment_sum"):
        assert ph.base_hist_impl(impl) == impl


def _mini_booster(**extra):
    rng = np.random.RandomState(0)
    X = rng.randn(400, 5)
    y = (X[:, 0] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 8, "verbosity": -1}
    params.update(extra)
    return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=1)


def test_maybe_fuse_hist_impl_gating(monkeypatch):
    bst = _mini_booster()
    monkeypatch.setattr(ph, "probe_cached", lambda *a, **k: True)
    bst._grow_policy = "wave"
    bst._grower_spec = bst._grower_spec._replace(hist_impl="pallas")
    bst._maybe_fuse_hist_impl()
    assert bst._grower_spec.hist_impl == "pallas_fused"
    bst._grower_spec = bst._grower_spec._replace(hist_impl="pallas_q")
    bst._maybe_fuse_hist_impl()
    assert bst._grower_spec.hist_impl == "pallas_fused_q"
    # idempotent: an already-fused impl is left alone
    bst._maybe_fuse_hist_impl()
    assert bst._grower_spec.hist_impl == "pallas_fused_q"

    # each booster-side disqualifier blocks the upgrade
    bst._grower_spec = bst._grower_spec._replace(hist_impl="pallas")
    bst.config.tpu_fused_split = False
    bst._maybe_fuse_hist_impl()
    assert bst._grower_spec.hist_impl == "pallas"
    bst.config.tpu_fused_split = True

    bst._grow_policy = "strict"
    bst._maybe_fuse_hist_impl()
    assert bst._grower_spec.hist_impl == "pallas"
    bst._grow_policy = "wave"

    bst.config.monotone_constraints = [1, 0, 0, 0, 0]
    bst._maybe_fuse_hist_impl()
    assert bst._grower_spec.hist_impl == "pallas"
    bst.config.monotone_constraints = []

    monkeypatch.setattr(ph, "probe_cached", lambda *a, **k: False)
    bst._maybe_fuse_hist_impl()
    assert bst._grower_spec.hist_impl == "pallas"


def test_fused_split_param_alias_roundtrip():
    bst = _mini_booster(fused_split=False)
    assert bst.config.tpu_fused_split is False
    assert _mini_booster().config.tpu_fused_split is True


# ------------------------------------------------------ recompile bound
def test_fused_wave_recompile_bound():
    assert telemetry.install_compile_listener()
    bins, grad, hess, sw, feat, allowed = _wave_case(seed=19)
    kw = dict(num_leaves=15, max_depth=0, max_bin=32, lambda_l1=0.0,
              lambda_l2=1.0, min_data_in_leaf=5.0,
              min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
              max_delta_step=0.0, hist_impl="pallas_fused", wave_width=4,
              has_cat=False, hist_interpret=True)
    grow = make_wave_grower(GrowerSpec(**kw))
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(sw), feat, allowed)
    jax_block(grow(*args))                           # warm: compiles
    before = telemetry.REGISTRY.counter("jit.recompiles").value
    bins2, grad2, hess2, sw2, feat2, allowed2 = _wave_case(seed=23)
    jax_block(grow(jnp.asarray(bins2), jnp.asarray(grad2),
                   jnp.asarray(hess2), jnp.asarray(sw2), feat2,
                   allowed2))
    after = telemetry.REGISTRY.counter("jit.recompiles").value
    assert after == before, \
        f"second same-shape wave tree recompiled ({after - before} new)"


def jax_block(tree):
    import jax
    return jax.block_until_ready(jax.tree_util.tree_map(jnp.asarray,
                                                        tuple(tree)))
