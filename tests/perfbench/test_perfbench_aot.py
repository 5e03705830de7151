"""The grower program of each configuration compiles for a TPU v5e at the
cell's real rows, columns and tree size, and fits the chip's memory.

Nothing runs: `jax.experimental.topologies` describes the chip to the
installed libtpu, and `lower(...).compile()` gives XLA:TPU's and Mosaic's
verdict on the program `Booster.update()` dispatches once a round.  The
topology is described inside a module-scoped fixture (never at import), and
the tests skip where it cannot be.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import lightgbm_tpu as lgb
from perfbench import manifest
from perfbench.generators import tabular_codes
from perfbench.jobs.train import build_dataset

HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # no libtpu / unknown topology: nothing to ask
        pytest.skip(f"cannot describe a v5e topology here: {e}")


def _config_names():
    d = os.path.join(manifest.HERE, "configs")
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


def grower_for_chip(config: dict):
    """A CPU-built booster over a few rows of the configuration's data whose
    grower names the compiled (not interpreted) Pallas kernel: what
    `hist_impl=auto` resolves to on the chip."""
    data = config["data"]
    codes, label = tabular_codes.generate(7, data, 0, 8192)
    params = {**config["params"], "hist_impl": "pallas",
              "hist_interpret": True, "tpu_fused_split": False}
    ds = build_dataset(lgb, codes, label, params,
                          [c["name"] for c in data["columns"]])
    bst = lgb.Booster(params=params, train_set=ds)
    bst._grower_spec = bst._grower_spec._replace(hist_interpret=False)
    bst._grower = bst._make_serial_grower()
    return bst


@pytest.mark.parametrize("name", _config_names())
def test_grower_compiles_at_cell_size(topo, name):
    config = manifest.config(name)
    if config.get("job_kind", "train") != "train":
        pytest.skip("not a training configuration")
    bst = grower_for_chip(config)
    assert bst._grow_policy == config["params"].get("tree_grow_policy",
                                                    bst._grow_policy)
    sharding = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    n = int(config["train_rows"])
    n_feat = len(config["data"]["columns"])
    feat = jax.tree.map(lambda a: sds(np.shape(a), a.dtype), bst._feat)
    compiled = bst._grower.lower(
        sds((n_feat, n), jnp.uint8), sds((n,), jnp.float32),
        sds((n,), jnp.float32), sds((n,), jnp.float32), feat,
        sds((n_feat,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(json.dumps({"config": name, "rows": n,
                      "argument": mem.argument_size_in_bytes,
                      "output": mem.output_size_in_bytes,
                      "temp": mem.temp_size_in_bytes, "total": total}))
    assert total < HBM_BYTES
