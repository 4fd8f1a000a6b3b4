"""Ahead-of-time compiles of the chip programs for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
``v5e:2x2`` topology that is described, not attached.  What it refuses here
(a block shape Mosaic cannot tile, a program that does not fit in HBM, a
mesh step without its collective) it would refuse on the chip.  Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a module-scoped fixture — never at import —
so that under several test workers only the one given this file loads the
TPU library; where it cannot be described the tests skip.  JAX's persistent
compilation cache is off around the compiles (an entry compiled for a
described chip cannot be read back without one).
"""
import os

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import tatp
from repro.core.datastructs import hashtable as ht
from repro.kernels import hash_probe as hp

HBM_BYTES = 16 * 2**30          # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _with_sharding(shapes, node_sh, rep_sh):
    """tx_step argument shapes placed on the described devices: the PRNG key
    (last) replicated, everything else split on the node axis."""
    *node, key = shapes
    put = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
    return (*jax.tree.map(lambda s: put(s, node_sh), tuple(node)),
            put(key, rep_sh))


def test_one_chip_tx_step_fits_in_hbm(topo):
    """chip_smoke's one-chip step: 8 SimTransport nodes, 125,000
    subscribers' table each, 64 lanes, max_rounds=4."""
    cl = tatp.Cluster(8)
    one = SingleDeviceSharding(topo.devices[0])
    args = _with_sharding(cl.tx_arg_shapes(), one, one)
    mem = cl.tx_step().lower(*args).compile().memory_analysis()
    state_bytes = 8 * ht.build_layout(cl.cfg).total_words * 4
    assert mem.argument_size_in_bytes >= state_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    # the serial folds must not copy the cluster arena around: vmapped over
    # the nodes they needed about 16x the state in temporaries
    assert mem.temp_size_in_bytes < 2 * state_bytes


def test_mesh_tx_step_exchanges_with_all_to_all(topo):
    """chip_smoke --chips 4: one MeshTransport node per chip under
    shard_map; the exchange must lower to ICI all-to-alls."""
    mesh = Mesh(np.array(topo.devices), ("node",))
    cl = tatp.Cluster(4, mesh=mesh)
    args = _with_sharding(cl.tx_arg_shapes(), NamedSharding(mesh, P("node")),
                          NamedSharding(mesh, P()))
    compiled = cl.tx_step().lower(*args).compile()
    assert "all-to-all" in compiled.as_text()
    mem = compiled.memory_analysis()    # bytes per device
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("width", [1, 2, 4])
def test_hash_probe_compiles_to_a_mosaic_kernel(topo, width):
    cfg = ht.HashTableConfig(n_nodes=8, n_buckets=131072 // width,
                             bucket_width=width, n_overflow=65536)
    words = ht.build_layout(cfg).total_words
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    B = 4096
    fn = jax.jit(lambda a, b, lo, hi: hp.hash_probe(a, b, lo, hi, width=width))
    text = fn.lower(S((words,), np.uint32), S((B,), np.int32),
                    S((B,), np.uint32), S((B,), np.uint32)).compile().as_text()
    assert "tpu_custom_call" in text
