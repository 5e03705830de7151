"""The grower, the single-leaf histogram kernel and the lambdarank
gradient program of `istellax220-lgbrank-l31` compile for a TPU v5e at
the cell's real rows, columns, queries and tree size, and fit the chip's
memory.

The benchmark's own `tests/perfbench/test_perfbench_aot.py` builds every
configuration of job kind `train` through `jobs.train.build_dataset` with
no query groups; this configuration is a ranking job (`jobs.train_rank`),
so its programs are compiled here.  At 220 columns a histogram pass is
four column blocks (a kernel call each) and the routing keeps one pass a
pick (more than `ops/route.py`'s 128 columns).  The single-leaf call a
booster probes before it trains (one slot over all 220 columns) is two
column blocks: as one it asks 26.3 MiB of the 16 MiB of scoped VMEM.  The
gradient program's temporaries are a block of queries'
(`rank_objective.PAIR_BLOCK_BYTES`) beside one `[N]` float32 copy: it is
compiled at Istella-X's full 10,000 queries, and the cell's 5,000 make the
same scan over blocks no larger.  Nothing runs; the tests skip where the
topology cannot be described.  The kernels' probes are answered as
passed: they would run the kernel in the CPU's interpreter, and the
compile below asks the chip's compiler itself.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import pallas_hist
from lightgbm_tpu.rank_objective import LambdarankNDCG
import lightgbm_tpu.rank_objective as ro
from lightgbm_tpu.utils.config import Config
from perfbench import manifest
from perfbench.generators import ltr_codes
from perfbench.jobs.train import build_dataset

from aot_common import tpu_kernels

CONFIG = "istellax220-lgbrank-l31"
HBM_BYTES = 16 * 2 ** 30
#: the gradient program's temporaries, all of them
GRAD_TEMP_BUDGET = 1 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # no libtpu / unknown topology: nothing to ask
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def test_the_one_chip_compile_test_skips_this_configuration():
    assert manifest.config(CONFIG)["job_kind"] != "train"


def test_the_grower_compiles_at_cell_size(sds, monkeypatch):
    monkeypatch.setattr(pallas_hist, "probe_cached",
                        lambda *a, **k: pallas_hist.ProbeResult(True))
    config = manifest.config(CONFIG)
    data = dict(config["data"], queries=4)
    rows = ltr_codes.make(7, data, 8192, 1)
    params = {**config["params"], "hist_impl": "pallas",
              "hist_interpret": True}
    ds = build_dataset(lgb, rows["codes"], rows["label"], params,
                       ltr_codes.column_names(data))
    ds._query_boundaries = rows["query_boundaries"]
    bst = lgb.Booster(params=params, train_set=ds)
    assert bst._grow_policy == "wave"
    bst._grower_spec = bst._grower_spec._replace(hist_interpret=False)
    bst._grower = bst._make_serial_grower()
    n, n_feat = int(config["train_rows"]), 220
    feat = jax.tree.map(lambda a: sds(np.shape(a), a.dtype), bst._feat)
    compiled = bst._grower.lower(
        sds((n_feat, n), jnp.uint8), sds((n,), jnp.float32),
        sds((n,), jnp.float32), sds((n,), jnp.float32), feat,
        sds((n_feat,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(json.dumps({"config": CONFIG, "rows": n, "program": "grow",
                      "argument": mem.argument_size_in_bytes,
                      "temp": mem.temp_size_in_bytes, "total": total}))
    assert total < HBM_BYTES
    kernels = tpu_kernels(compiled.as_text())
    # every pass in four column blocks of 55: each body four calls
    hist = [k for k in kernels if k.startswith("pallas_histogram")]
    assert {k: hist.count(k) for k in set(hist)} == {
        "pallas_histogram_multi_rows": 4,
        "pallas_histogram_multi_rows_full": 8,
        "pallas_histogram_multi_rows_c128": 8,
        "pallas_histogram_multi_rows_c256": 8,
        "pallas_histogram_multi_rows_c512": 8}
    assert [c1 - c0 for c0, c1, _ in pallas_hist.column_blocks(
        n_feat, 72, 255, bst._grower_spec.hist_lane_plan)] == [55] * 4
    # over 128 columns no pick is routed by the wave's one pass
    assert "route_wave_rows" not in kernels


def test_the_single_leaf_kernel_compiles_at_220_columns(sds):
    """What `Booster` probes (`pallas_hist.probe`, multi=False): the full
    body at one slot over every row tile, with the cell's lane plan."""
    config = manifest.config(CONFIG)
    plan = pallas_hist.lane_plan(ltr_codes.cardinalities(config["data"]),
                                 255)
    blocks = pallas_hist.column_blocks(220, 9, 255, plan)
    assert [c1 - c0 for c0, c1, _ in blocks] == [110, 110]
    n = 4 * pallas_hist.ROW_TILE
    compiled = jax.jit(lambda b, p, m: pallas_hist.pallas_histogram(
        b, p, m, 255, plan=plan, body="full")).lower(
        sds((220, n), jnp.uint8), sds((n, 3), jnp.float32),
        sds((n,), jnp.bool_)).compile()
    assert tpu_kernels(compiled.as_text()).count(
        "pallas_histogram_multi_rows") == 2


def _maps(queries, rows):
    """The gradient objective and its query maps at `queries` queries of
    the cell's length law over `rows` rows."""
    config = manifest.config(CONFIG)
    data = dict(config["data"], queries=queries)
    lengths, _ = ltr_codes.query_lengths(data, rows)
    qb = np.concatenate([[0], np.cumsum(lengths)])
    label = np.zeros(rows)
    label[qb[:-1]] = 1.0
    obj = LambdarankNDCG(Config(config["params"]))
    obj.init_meta(label, None, qb)
    return obj, obj.device_maps()


FULL = (10000, 26791447)


@pytest.fixture(scope="module")
def full_program(sds):
    """The gradient program compiled at Istella-X's full 10,000 queries:
    its block shapes and memory (one compile: each bucket's `argsort`
    costs the v5e compiler several seconds)."""
    queries, rows = FULL
    obj, maps = _maps(queries, rows)
    vec = sds((rows,), jnp.float32)
    compiled = jax.jit(obj.lambdarank).lower(
        vec, vec, None, jax.tree.map(lambda a: sds(a.shape, a.dtype),
                                     maps)).compile()
    mem = compiled.memory_analysis()
    print(json.dumps({"config": CONFIG, "queries": queries, "rows": rows,
                      "program": "lambdarank",
                      "blocks": [b["gather"].shape for b in maps],
                      "argument": mem.argument_size_in_bytes,
                      "temp": mem.temp_size_in_bytes}))
    return {"blocks": [b["gather"].shape for b in maps], "mem": mem,
            "map_bytes": sum(a.nbytes for a in jax.tree.leaves(maps))}


@pytest.mark.parametrize("queries,rows", [(5000, 13395968), FULL])
def test_the_gradient_program_stays_within_its_budget(full_program, queries,
                                                      rows):
    """Compiled at 10,000 queries, the maps are arguments and the
    temporaries are one [N] copy and a block's.  At 5,000 (the cell) the
    program is the same scan over blocks no larger, and fewer rows, so its
    temporaries are no more than the compiled ones."""
    _, maps = _maps(queries, rows)
    blocks = [b["gather"].shape for b in maps]
    for _, per, P in blocks:        # a block's pair tensor within budget
        assert 4 * per * min(30, P) * P <= ro.PAIR_BLOCK_BYTES
    assert len(blocks) == len(full_program["blocks"])
    for (_, per, P), (_, full_per, full_P) in zip(blocks,
                                                  full_program["blocks"]):
        assert per * P <= full_per * full_P
    assert rows <= FULL[1]
    mem = full_program["mem"]
    assert mem.argument_size_in_bytes >= full_program["map_bytes"] \
        + 8 * FULL[1]
    assert mem.temp_size_in_bytes <= 4 * FULL[1] + 2 * ro.PAIR_BLOCK_BYTES
    assert mem.temp_size_in_bytes < GRAD_TEMP_BUDGET
