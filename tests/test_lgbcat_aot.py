"""The grower of `airline13-lgbcat-l255` (six columns declared
categorical) compiles for a TPU v5e at the cell's real rows, columns and
tree size, fits the chip's memory, and routes every pick in the one pass.

The benchmark's own `tests/perfbench/test_perfbench_aot.py` builds every
`train` configuration through `jobs.train.build_dataset`, which declares
no categorical column: it would compile the numerical grower under this
configuration's name (the file says `job_kind: train_cat`, which that test
skips).  Here the data set is the cell's own (`jobs.train_cat`), so the
spec has `has_cat` and the program is the one the cell runs.  Nothing
runs; the test skips where the topology cannot be described.
"""
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import lightgbm_tpu as lgb
from perfbench import manifest
from perfbench.generators import tabular_codes
from perfbench.jobs.train_cat import build_dataset

from aot_common import row_array_copies, tpu_kernels

CONFIG = "airline13-lgbcat-l255"
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # no libtpu / unknown topology: nothing to ask
        pytest.skip(f"cannot describe a v5e topology here: {e}")


def test_the_one_chip_compile_test_skips_this_configuration():
    assert manifest.config(CONFIG)["job_kind"] != "train"


def test_the_categorical_grower_compiles_at_cell_size(topo):
    config = manifest.config(CONFIG)
    data = config["data"]
    codes, label = tabular_codes.generate(7, data, 0, 8192)
    params = {**config["params"], "hist_impl": "pallas",
              "hist_interpret": True}
    ds = build_dataset(lgb, codes, label, params,
                       [c["name"] for c in data["columns"]],
                       config["categorical_feature"])
    bst = lgb.Booster(params=params, train_set=ds)
    assert bst._grower_spec.has_cat and bst._grow_policy == "wave"
    bst._grower_spec = bst._grower_spec._replace(hist_interpret=False)
    bst._grower = bst._make_serial_grower()
    sharding = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    n = int(config["train_rows"])
    n_feat = len(data["columns"])
    feat = jax.tree.map(lambda a: sds(np.shape(a), a.dtype), bst._feat)
    compiled = bst._grower.lower(
        sds((n_feat, n), jnp.uint8), sds((n,), jnp.float32),
        sds((n,), jnp.float32), sds((n,), jnp.float32), feat,
        sds((n_feat,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(json.dumps({"config": CONFIG, "rows": n,
                      "argument": mem.argument_size_in_bytes,
                      "output": mem.output_size_in_bytes,
                      "temp": mem.temp_size_in_bytes, "total": total}))
    assert total < HBM_BYTES
    text = compiled.as_text()
    # a wave's picks, the tail's pick and a speculation's slots: one
    # routing pass each, ids rewritten in place
    assert tpu_kernels(text).count("route_wave_rows") == 3
    assert row_array_copies(text, n) == []
    # no pick gathers its mask at [N] any more (the parent's program held
    # three `pred[83886080] gather`s)
    gathers = [line.strip()[:160] for line in text.splitlines()
               if re.search(r"= \w+\[%d\]\S* gather\(" % n, line)]
    assert gathers == []
