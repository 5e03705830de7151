"""Test config: run JAX on a virtual 8-device CPU mesh.

Tests and verification run on the CPU backend; the chip is reached only
through the chip tool (`python chip_smoke.py` is the first command to
send).  The distributed (data-parallel tree learner) tests validate
sharding semantics on 8 virtual CPU devices, and the driver separately
dry-run-compiles the multi-chip path via
`__graft_entry__.dryrun_multichip`.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lightgbm_tpu.utils.env import cleaned_cpu_env  # noqa: E402

_env = cleaned_cpu_env(os.environ, 8)
os.environ.update({k: _env[k] for k in ("JAX_PLATFORMS", "XLA_FLAGS")})
# NOTE: x64 deliberately NOT enabled — tests must exercise the same f32
# accumulation behavior the real TPU path uses.


# --------------------------------------------------------------------------
# quick tier (`pytest -m quick`, scripts/run_ci.sh quick): one fast
# representative per subsystem so every layer gets smoke coverage in
# minutes, not the full suite's ~30.  Tests added here by nodeid prefix;
# new test files can also mark themselves with @pytest.mark.quick.
# --------------------------------------------------------------------------
import pytest  # noqa: E402

_QUICK_NODE_PREFIXES = (
    "test_binning.py",                                  # binning (host)
    "test_dataset.py",                                  # Dataset semantics
    "test_native.py",                                   # C++ parser/binner
    "test_efb.py::TestFindBundles",                     # EFB bundling
    "test_engine_basic.py::TestRegression::test_l2_learns",
    "test_engine_basic.py::TestBinary::test_auc_and_logloss",
    "test_boosting_modes.py::TestDART::test_dart_learns",
    "test_boosting_modes.py::TestRF::test_rf_requires_bagging",
    "test_boosting_modes.py::TestRanking::test_ranking_requires_group",
    "test_boosting_modes.py::TestSklearnAPI::test_sklearn_clone",
    "test_categorical.py::TestCategorical::test_unseen_category_goes_right",
    "test_constraints.py::TestMonotone::"
    "test_advanced_downgrades_to_intermediate",
    "test_cegb.py::TestCEGB::test_no_warning_anymore",
    "test_distributed.py::TestShardedGrower::test_eight_devices_available",
    "test_distributed.py::TestShardedGrower::test_sharded_matches_single[2]",
    "test_quantized_grad.py::TestPackedHistogram::test_op_matches_f32_path",
    "test_refit_renew.py::TestRefit::test_refit_decay_one_is_identity",
    "test_linear_tree.py::TestLinearTree::test_no_warning_anymore",
    "test_ingest_predict.py::TestSequenceIngest",
    "test_pallas_hist.py::TestPallasHistogram::"
    "test_matches_segment_sum[512-4-16-onehot]",
    "test_golden.py::TestGolden::test_matches_frozen_model[binary]",
    "test_inert_param_warning.py::test_inert_param_warns",
    "test_stock_parity.py",                             # skip-or-activate
)


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_programs():
    """Free each test module's compiled programs when it is done.  Left
    to pile up over the whole suite, the live executables end in a
    SIGSEGV inside XLA's CPU compiler about halfway through tier-1
    (seen at test_pipeline.py::test_multi_valid_eval_path, which passes
    alone); modules share almost no programs, so little is recompiled."""
    yield
    import gc

    import jax
    jax.clear_caches()
    gc.collect()


def pytest_collection_finish(session):
    """Start `chip_smoke.py --dry-run` now when its test was selected: a
    one-minute subprocess that then runs beside the first test files
    instead of inside its own test, which keeps that minute out of the
    time-boxed tier-1 run.  tests/test_chip_smoke.py collects the
    result."""
    if any(item.nodeid.endswith(
            "test_chip_smoke.py::test_dry_run_passes_and_cache_follows_env")
           for item in session.items) \
            and not session.config.option.collectonly:
        from test_chip_smoke import start_dry_run
        session.config._chip_smoke_dry_run = start_dry_run()


def pytest_sessionfinish(session):
    run = getattr(session.config, "_chip_smoke_dry_run", None)
    if run is not None:
        run.cleanup()


# The benchmark's own test of the four-chip rule plants a four-chip cell in
# a copy of the manifest and asserts the copy is sound, which holds only
# while the accepted manifest has no four-chip cell of its own.  Since
# `criteo67-lgbpar-l255.train` it has one, and `tests/perfbench/` is the
# benchmark's to edit, not a program PR's: the test is expected to fail
# until a benchmark PR sets the manifest's own four-chip cells aside first
# (PERF.md section 7 has the patch).  The rule itself stays tested, on the
# manifest as it is: tests/test_criteo67_cell.py::
# test_a_second_four_chip_cell_is_caught_beside_the_benchmarks_own.
_PRESUMES_NO_FOUR_CHIP_CELL = (
    "test_perfbench_manifest.py::test_a_second_four_chip_cell_of_two_is_caught")


def _manifest_has_a_four_chip_cell() -> bool:
    import json
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return any(w["chips"] == 4 for w in json.load(f)["workloads"])


def pytest_collection_modifyitems(config, items):
    four = _manifest_has_a_four_chip_cell()
    for item in items:
        nid = item.nodeid.split("/")[-1]
        if any(nid.startswith(p) for p in _QUICK_NODE_PREFIXES):
            item.add_marker(pytest.mark.quick)
        if four and nid == _PRESUMES_NO_FOUR_CHIP_CELL:
            item.add_marker(pytest.mark.xfail(
                reason="presumes a manifest with no four-chip cell of its "
                       "own; a benchmark PR has to repair it (PERF.md 7)",
                strict=False))
