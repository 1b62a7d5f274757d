"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, installed beside JAX, compiles each kernel
for a chip that is described and not attached, at the widths of the
paper's workloads.  A block that breaks the (8, 128) tiling, a cast or
gather Mosaic cannot lower, or a kernel over its VMEM budget fails here,
with no chip.  The topology is described inside a fixture (never at
import), so only the worker running this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.api import ExecutionPlan
from repro.core import GBDTConfig
from repro.core.binning import PackedCodes
from repro.distributed.trainer import (_distributed_round_step,
                                       _trainer_kernel_plan)
from repro.kernels import histogram, partition, traversal
from repro.kernels.ref import TreeArrays

HIGGS_N, IOT_N = 10_000_000, 7_000_000      # paper Table III records


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the kernel is there
    return compiled


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n,fields,bins,nibble", [
    (HIGGS_N, 28, 256, False),     # higgs at the estimator's 256 bins
    (HIGGS_N, 28, 16, True),       # 16 bins: the nibble-packed layout
    (IOT_N, 115, 256, False),      # iot, the widest paper dataset
])
def test_histogram_compiles(one_chip, n, fields, bins, nibble):
    if nibble:
        codes = PackedCodes(_spec(one_chip, (n, (fields + 1) // 2),
                                  jnp.uint8), fields)
    else:
        codes = _spec(one_chip, (n, fields), jnp.uint8)
    stat = _spec(one_chip, (n,), jnp.float32)
    nid = _spec(one_chip, (n,), jnp.int32)

    def fn(c, g, h, node):
        return histogram.histogram_pallas(
            c, g, h, node, n_nodes=32, n_bins=bins, records_per_block=512,
            fields_per_block=8, packed=False, interpret=False)

    out = jax.eval_shape(fn, codes, stat, stat, nid)
    assert out.shape == (32, fields, bins, 2)
    _compile(fn, codes, stat, stat, nid)


def test_partition_compiles(one_chip):
    n, nn = HIGGS_N, 32
    col = _spec(one_chip, (nn,), jnp.int32)

    def fn(node, codes, f, t, c, d):
        return partition.partition_pallas(node, codes, f, t, c, d,
                                          missing_bin=255, interpret=False)

    _compile(fn, _spec(one_chip, (n,), jnp.int32),
             _spec(one_chip, (n, nn), jnp.uint8), col, col, col, col)


@pytest.mark.parametrize("n_trees", [200, 500])
def test_batch_traversal_compiles(one_chip, n_trees):
    depth, n = 6, 4096
    words = _spec(one_chip, (n_trees, 2 ** depth - 1), jnp.int32)
    trees = TreeArrays(words, words, words, words,
                       _spec(one_chip, (n_trees, 2 ** depth), jnp.float32))

    def fn(t, codes):
        return traversal.predict_ensemble_pallas(
            t, codes, missing_bin=255, depth=depth, interpret=False)

    _compile(fn, trees, _spec(one_chip, (n, 28), jnp.uint8))


def test_sharded_round_compiles(topo):
    """One data-parallel boosting round on a 4-chip ("data",) mesh: the
    histogram and partition kernels run inside shard_map at every level,
    and the per-level reductions are all-reduces."""
    depth, n, fields, bins = 6, 4 * 16384, 28, 256
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    plan = _trainer_kernel_plan(ExecutionPlan(
        hist_strategy="pallas_grouped", partition_strategy="pallas",
        traversal_strategy="pallas", interpret=False))
    cfg = GBDTConfig(n_trees=1, max_depth=depth,
                     objective="binary:logistic")
    step = _distributed_round_step(cfg, plan, mesh, ("data",), n, n, fields,
                                   bins, None)

    def spec(shape, dtype, pspec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, pspec))

    text = step.lower(
        spec((n,), jnp.float32, P()), spec((n,), jnp.float32, P()),
        spec((2,), jnp.uint32, P()),
        spec((n, fields), jnp.uint8, P("data")),
        spec((fields, n), jnp.uint8, P(None, "data")),
        spec((fields,), jnp.bool_, P())).compile().as_text()
    kernels = re.findall(r"%([\w.]+) = .*"
                         r'custom_call_target="tpu_custom_call"', text)
    for name in ("histogram_pallas", "partition_pallas"):
        assert sum(name in k for k in kernels) == depth, (name, kernels)
    assert "all-reduce" in text
