"""The data-parallel grower of `criteo67-lgbpar-l255` compiles for a v5e
2x2 host at the cell's real rows, columns and tree size, and what ONE
device holds fits the chip's memory.

The benchmark's own `tests/perfbench/test_perfbench_aot.py` compiles a
configuration's SERIAL grower for one chip; this configuration's rows need
four (its file says `job_kind: train_sharded`, which that test skips), so
its program is compiled here: `make_distributed_grower` over the described
topology's four devices.  Nothing runs; the test skips where the topology
cannot be described.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import lightgbm_tpu as lgb
from lightgbm_tpu.parallel.learner import (make_distributed_grower,
                                           padded_feature_count)
from perfbench import manifest
from perfbench.generators import tabular_codes
from perfbench.jobs.train import build_dataset

from aot_common import row_array_copies, tpu_kernels

CONFIG = "criteo67-lgbpar-l255"
HBM_BYTES = 16 * 2 ** 30
CHIPS = 4           # of the described v5e:2x2 host


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # no libtpu / unknown topology: nothing to ask
        pytest.skip(f"cannot describe a v5e topology here: {e}")


def booster_for_chip(config: dict):
    """A CPU-built booster over a few rows of the configuration's data, on
    four of the virtual devices, whose grower names the compiled (not
    interpreted) Pallas kernel: what `hist_impl=auto` resolves to on the
    chip."""
    data = config["data"]
    codes, label = tabular_codes.generate(7, data, 0, 8192)
    params = {**config["params"], "hist_impl": "pallas",
              "hist_interpret": True, "num_machines": CHIPS}
    ds = build_dataset(lgb, codes, label, params,
                       [c["name"] for c in data["columns"]])
    bst = lgb.Booster(params=params, train_set=ds)
    bst._grower_spec = bst._grower_spec._replace(hist_interpret=False)
    return bst


def test_the_one_chip_compile_test_skips_this_configuration():
    assert manifest.config(CONFIG)["job_kind"] != "train"


def test_sharded_grower_compiles_at_cell_size(topo):
    config = manifest.config(CONFIG)
    bst = booster_for_chip(config)
    assert bst._grow_policy == config["params"]["tree_grow_policy"]
    assert bst._mesh.devices.size == CHIPS
    assert bst._grower_spec.hist_lane_plan is not None
    n = int(config["train_rows"])
    n_feat = len(config["data"]["columns"])
    mesh = Mesh(np.array(topo.devices), ("data",))
    assert mesh.devices.size == CHIPS
    grow = make_distributed_grower(
        bst._grower_spec, mesh, "data", n_feat, n,
        wave=bst._grow_policy == "wave",
        det_reduce=bool(bst.config.deterministic_reduce))
    rows = NamedSharding(mesh, P("data"))
    whole = NamedSharding(mesh, P())

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    row = sds((n,), jnp.float32, rows)
    compiled = grow.jitted.lower(
        sds((padded_feature_count(n_feat, CHIPS), n), jnp.uint8,
            NamedSharding(mesh, P(None, "data"))), row, row, row,
        jax.tree.map(lambda a: sds(np.shape(a), a.dtype, whole), bst._feat),
        sds((n_feat,), jnp.bool_, whole)).compile()
    mem = compiled.memory_analysis()        # of one device's program
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(json.dumps({"config": CONFIG, "rows": n,
                      "argument": mem.argument_size_in_bytes,
                      "output": mem.output_size_in_bytes,
                      "temp": mem.temp_size_in_bytes, "total": total}))
    assert total < HBM_BYTES
    # a shard routes a wave's picks and a speculation's slots in one pass
    # each (68 columns a shard), and rewrites its ids in place
    text = compiled.as_text()
    assert tpu_kernels(text).count("route_wave_rows") == 2
    assert row_array_copies(text, n // CHIPS) == []


def test_sharded_look_up_compiles_at_cell_size(topo):
    """The score update's look-up over the four row shards (ISSUE 36):
    `make_distributed_grower`'s `leaf_rows` at the cell's 201,326,592
    rows is one Pallas call a device, no gather and no collective, and
    its values come back split like the ids."""
    config = manifest.config(CONFIG)
    bst = booster_for_chip(config)
    n = int(config["train_rows"])
    mesh = Mesh(np.array(topo.devices), ("data",))
    grow = make_distributed_grower(
        bst._grower_spec, mesh, "data", len(config["data"]["columns"]), n,
        wave=bst._grow_policy == "wave",
        det_reduce=bool(bst.config.deterministic_reduce))
    compiled = grow.leaf_rows.lower(
        jax.ShapeDtypeStruct((int(config["params"]["num_leaves"]),),
                             jnp.float32,
                             sharding=NamedSharding(mesh, P())),
        jax.ShapeDtypeStruct((n,), jnp.int32,
                             sharding=NamedSharding(mesh, P("data")))
    ).compile()
    text = compiled.as_text()
    assert tpu_kernels(text) == ["leaf_rows"]
    assert " gather(" not in text
    for collective in ("all-gather", "all-reduce", "collective-permute",
                       "all-to-all"):
        assert collective not in text
    assert compiled.output_shardings.spec == P("data")
    assert row_array_copies(text, n // CHIPS) == []
