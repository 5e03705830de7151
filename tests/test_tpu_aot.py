"""Ahead-of-time compiles for a TPU v5e, run in a sandbox that has none.

`jax.experimental.topologies.get_topology_desc("v5e:2x2")` describes four
`TPU v5 lite` devices to the installed libtpu, so
`jit(f).lower(<ShapeDtypeStructs placed on them>).compile()` gives Mosaic's
and XLA:TPU's real verdict on a program — whether it COMPILES for the
chip, not whether it runs right (only `chip_smoke.py` on the chip says
that).  The programs are the ones `chip_smoke.py` drives: the histogram
kernels, the fused train chunks, the serving programs.

Kernels the compiler still refuses are `xfail(strict=True)` with its
message quoted, so the repair that makes one compile flips its test.
Skips when the topology cannot be built (no libtpu).
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import lightgbm_tpu as lgb
from lightgbm_tpu.booster import Booster
from lightgbm_tpu.ops import pallas_hist as ph
from lightgbm_tpu.ops import leaf_rows as lr
from lightgbm_tpu.ops import route as rt
from lightgbm_tpu.ops.fused import make_bulk_trainer

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
import configs_r4  # noqa: E402
from aot_common import row_array_copies, tpu_kernels  # noqa: E402

pytestmark = pytest.mark.slow

F, MB, N = 28, 256, 65_536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # no libtpu / unknown topology: nothing to ask
        pytest.skip(f"cannot describe a v5e topology here: {e}")


def _placer(sharding):
    def sds(a):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sharding)
    return sds


def _one(topo):
    """Placeholder factory for arrays on the topology's first chip."""
    sharding = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return sds, _placer(sharding)


def _unwrap(fn):
    """The jitted function under a `@contract` wrapper."""
    return getattr(fn, "__wrapped__", fn)


# ---------------------------------------------------------- hist kernels
@pytest.mark.parametrize("width", [1, 8])
def test_hist_kernel_f32(topo, width):
    sds, _ = _one(topo)
    ph.pallas_histogram_multi.lower(
        sds((F, N), jnp.uint8), sds((N, 3), jnp.float32),
        sds((N,), jnp.int32), sds((width,), jnp.int32), MB).compile()


# the benchmark cells' thirteen airline columns (perfbench/configs)
AIRLINE_NUM_BIN = (22, 12, 31, 7, 255, 255, 29, 255, 255, 255, 255, 255, 2)


@pytest.mark.parametrize("width", [1, 8])
def test_hist_kernel_f32_lane_plan(topo, width):
    """The packed plan: six few-bin columns in one 128-lane multi-hot
    group, a [S*9, 1920] accumulator sliced at 128-aligned lanes."""
    sds, _ = _one(topo)
    plan = ph.lane_plan(AIRLINE_NUM_BIN, 255)
    assert ph.plan_lanes(plan) == 1920
    ph.pallas_histogram_multi.lower(
        sds((13, N), jnp.uint8), sds((N, 3), jnp.float32),
        sds((N,), jnp.int32), sds((width,), jnp.int32), 255,
        plan=plan).compile()


@pytest.mark.parametrize("body", [None, "full", "c128", "c256", "c512"])
@pytest.mark.parametrize("width", [1, 8])
def test_hist_kernel_f32_compacting_bodies(topo, width, body):
    """Each compacting body alone (forced) and the dispatching program
    (`body=None`: the four kernels under one `lax.switch`), at the cells'
    columns and plan.  Each kernel's custom-call is named
    `pallas_histogram_multi_rows*`: the benchmark's trace readers select
    the histogram kernel by that prefix."""
    sds, _ = _one(topo)
    plan = ph.lane_plan(AIRLINE_NUM_BIN, 255)
    text = ph.pallas_histogram_multi_rows.lower(
        sds((13, N), jnp.uint8), sds((9, N), jnp.float32),
        sds((N,), jnp.int32), sds((width,), jnp.int32), 255,
        plan=plan, body=body, count_bodies=True).compile().as_text()
    assert sorted(tpu_kernels(text)) == (
        ["pallas_histogram_multi_rows"] if body else
        ["pallas_histogram_multi_rows_" + name
         for name, _ in sorted(ph.hist_bodies())])


@pytest.mark.parametrize("fill", [None, -1], ids=["leaf_id", "slot_of_row"])
@pytest.mark.parametrize("shape", [(13, 83_886_080), (68, 50_331_648)],
                         ids=["lgbexp", "criteo67_shard"])
def test_route_wave_rows_compiles_at_cell_size(topo, shape, fill):
    """The wave's routing pass at the cells' real columns and rows.  Its
    custom-call is NOT named `pallas_histogram*`: the benchmark's readers
    select the histogram kernel by that prefix, and the routing belongs
    to `grower.other_pct`."""
    sds, _ = _one(topo)
    f, n = shape
    compiled = rt.route_wave_rows.lower(
        sds((f, n), jnp.uint8), sds((n,), jnp.int32),
        sds((8, rt.REC_FIELDS), jnp.int32), fill=fill).compile()
    assert tpu_kernels(compiled.as_text()) == ["route_wave_rows"]
    # no [8, N] operand or result of the one-hot product reaches HBM
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * n


@pytest.mark.parametrize("rows,leaves", [
    (83_886_080, 255), (114_999_296, 31), (83_886_080, lr.LEAF_MAX_ENTRIES)],
    ids=["lgbexp", "l31", "widest"])
def test_leaf_rows_compiles_at_cell_size(topo, rows, leaves):
    """The score update's look-up (ISSUE 36) at the one-chip cells' rows
    and tree sizes, and at the widest table it serves: one Pallas call
    that is NOT named `pallas_histogram*` (the readers reckon a
    histogram's work for every such name), no gather, and nothing over
    all rows but the ids read and the values written."""
    sds, _ = _one(topo)
    compiled = lr.leaf_rows.lower(sds((leaves,), jnp.float32),
                                  sds((rows,), jnp.int32),
                                  "pallas").compile()
    text = compiled.as_text()
    assert tpu_kernels(text) == ["leaf_rows"]
    assert " gather(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
    assert row_array_copies(text, rows) == []


@pytest.mark.parametrize("name", ["airline13-l31", "airline13-lgbexp-l255"])
def test_wave_grower_routes_in_place_at_cell_size(topo, name):
    """`jit_grow` of the one-chip configurations at full size: the wave
    body and the speculation route through `route_wave_rows`, and no
    array over all rows is copied in HBM anywhere in the program (the
    ids are rewritten in place: by the pass in a wave, by the pick behind
    the barrier in the tail)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "perfbench"))
    from test_perfbench_aot import grower_for_chip
    from perfbench import manifest
    sds, _ = _one(topo)
    config = manifest.config(name)
    bst = grower_for_chip(config)
    n = int(config["train_rows"])
    n_feat = len(config["data"]["columns"])
    feat = jax.tree.map(lambda a: sds(np.shape(a), a.dtype), bst._feat)
    text = bst._grower.lower(
        sds((n_feat, n), jnp.uint8), sds((n,), jnp.float32),
        sds((n,), jnp.float32), sds((n,), jnp.float32), feat,
        sds((n_feat,), jnp.bool_)).compile().as_text()
    kernels = tpu_kernels(text)
    assert kernels.count("route_wave_rows") == 2
    assert all(k == "route_wave_rows" or k.startswith("pallas_histogram")
               for k in kernels)
    assert row_array_copies(text, n) == []


@pytest.mark.parametrize("width", [1, 8])
def test_hist_kernel_int8(topo, width):
    sds, _ = _one(topo)
    ph.pallas_histogram_multi_quantized.lower(
        sds((F, N), jnp.uint8), sds((N, 3), jnp.float32),
        sds((N,), jnp.int32), sds((width,), jnp.int32), MB,
        sds((), jnp.float32), sds((), jnp.float32)).compile()


# ---------------------------------------------------------- train chunks
def _data(n=N):
    rng = np.random.RandomState(0)
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    return X, y


def _booster(extra):
    """A CPU-built booster whose grower spec names the compiled (not
    interpreted) Pallas kernel — what `hist_impl=auto` resolves to on
    the chip."""
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 255,
              "verbosity": -1, "hist_impl": "pallas",
              "hist_interpret": True, **extra}
    bst = Booster(params=params, train_set=lgb.Dataset(X, label=y))
    bst._grower_spec = bst._grower_spec._replace(hist_interpret=False)
    bst._boost_from_average()
    return bst


def _chunk_args(bst, place, bins):
    return (place(bst._train_score), (), place(jnp.int32(0)),
            place(bst._rng_key0), place(bst._ff_key0),
            place(bst._grad_key0), bins, jax.tree.map(place, bst._feat),
            place(bst._dd.base_allowed_dev), ())


@pytest.mark.parametrize("policy", ["wave", "leafwise"])
def test_train_chunk(topo, policy):
    extra = configs_r4.CONFIGS[configs_r4.SHIPPED] if policy == "wave" \
        else {}
    bst = _booster(extra)
    assert bst._grow_policy == policy
    bst._grower = bst._make_serial_grower()
    trainer = bst._bulk_trainer(bst._make_bulk_spec())
    bst._ensure_train_bins()
    _, place = _one(topo)
    trainer.lower(*_chunk_args(bst, place,
                               place(bst._train_bins))).compile()


def _four_chip_chunk(topo, det_reduce):
    from lightgbm_tpu.parallel.learner import (make_distributed_grower,
                                               padded_feature_count,
                                               padded_row_count)
    bst = _booster(configs_r4.CONFIGS[configs_r4.SHIPPED])
    mesh = Mesh(np.array(topo.devices), ("data",))
    grow = make_distributed_grower(bst._grower_spec, mesh, "data", F, N,
                                   wave=True, det_reduce=det_reduce)
    trainer = make_bulk_trainer(bst._make_bulk_spec(), bst._grad_fn, None,
                                grow)
    bins = jax.ShapeDtypeStruct(
        (padded_feature_count(F, 4), padded_row_count(N, 4)), jnp.uint8,
        sharding=NamedSharding(mesh, P(None, "data")))
    place = _placer(NamedSharding(mesh, P()))
    return trainer.lower(*_chunk_args(bst, place, bins)).compile()


def test_train_chunk_four_chips(topo):
    """`tree_learner=data` over the 2x2 mesh, default
    `deterministic_reduce` (ring-chained ppermute fold): the Pallas
    kernel inside `shard_map` compiles and scores come back row-sharded."""
    compiled = _four_chip_chunk(topo, det_reduce=True)
    assert compiled.output_shardings[0].spec == P("data")


@pytest.mark.xfail(strict=True, reason=(
    "XLA:TPU (libtpu 0.0.34) on the psum_scatter path of "
    "deterministic_reduce=false: 'INTERNAL: during context "
    "[post-optimization]: ... Bitcast cannot have different shape sizes "
    "of output (21504) and operand (172032)'; not the default — "
    "ROADMAP S9"))
def test_train_chunk_four_chips_reduce_scatter(topo):
    _four_chip_chunk(topo, det_reduce=False)


# -------------------------------------------------------------- serving
@pytest.fixture(scope="module")
def served():
    from lightgbm_tpu.serving.runtime import ServingRuntime
    X, y = _data(4000)
    bst = lgb.train({"objective": "binary", "num_leaves": 31,
                     "verbosity": -1}, lgb.Dataset(X, label=y),
                    num_boost_round=48)
    return ServingRuntime(bst, compiled="force")


@pytest.mark.parametrize("rows", [1, 256, 4096])
def test_device_sum_and_slot_programs(topo, served, rows):
    from lightgbm_tpu.serving import runtime as rt_mod
    sds, place = _one(topo)
    ex = served._state.export
    arrays = {k: v for k, v in ex["stacked"].items()
              if k not in ("min_features", "value")}
    X = sds((rows, F), jnp.float32)
    rt_mod._LEAF_JIT.lower(jax.tree.map(place, arrays), X).compile()
    arrays = dict(arrays, value_hi=ex["value_hi"], value_lo=ex["value_lo"])
    rt_mod._EXACT_JIT.lower(jax.tree.map(place, arrays), X,
                            n_class=1, convert=None).compile()


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic (jax 0.9.0): 'Not implemented: 1) rhs non contracting dims "
    "must be an infix/suffix of the shape or 2) the contracting dim of "
    "lhs/rhs must be the last dim and rhs must be vector-like' — the "
    "int32 one-hot dot_general in compiler/kernel.py _gather_bits; with "
    "that contraction rewritten the next refusal is 'infer-vector-layout: "
    "unsupported shape cast' on the flat<->(TT, NI) reshapes; "
    "ROADMAP S8/D2"))
def test_compiled_traverse_kernel(topo, served):
    from lightgbm_tpu.compiler.kernel import compiled_predict
    sds, place = _one(topo)
    st = served._state
    ex = st.export
    _unwrap(compiled_predict).lower(
        sds((256, F), jnp.float32), jax.tree.map(place, st.plan_planes),
        place(st.plan_gidx), place(ex["value_hi"]), place(ex["value_lo"]),
        None, meta=st.plan_meta, n_class=1, convert=None,
        interpret=False).compile()
