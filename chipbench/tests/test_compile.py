"""Ahead-of-time compiles of every cell's programs at their real sizes for
a described TPU v5e (no chip attached; nothing runs).  What the TPU
compiler refuses here it would refuse on the chip.  The topology is
described in a module fixture, never at import, and JAX's persistent cache
is off around the compiles."""
import os
import re

import jax
import pytest
from conftest import ROOT
from jax.sharding import SingleDeviceSharding

from chipbench.harness import spec as S

SPEC = S.load_spec(ROOT)
HBM = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    except Exception as e:      # noqa: BLE001 — any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def deployment(workload):
    cell = S.cell(SPEC, workload)
    system, _ = S.system(S.config(SPEC, cell["config"], ROOT)["system"])
    return system.Deployment(S.config(SPEC, cell["config"], ROOT),
                             S.traffic(cell["traffic"]))


def compile_for(sharding, fn, shapes):
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
    return fn.lower(*jax.tree.map(put, shapes)).compile()


@pytest.mark.parametrize("workload", [c["name"] for c in SPEC["workloads"]])
def test_cell_step_compiles_and_fits(one_chip, workload):
    dep = deployment(workload)
    compiled = compile_for(one_chip, *dep.step_program())
    mem = compiled.memory_analysis()
    print(f"{workload} step: arguments {mem.argument_size_in_bytes}, "
          f"temporaries {mem.temp_size_in_bytes}, "
          f"outputs {mem.output_size_in_bytes} bytes")
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM
    # the serial handler is a scan over an inbox's cells inside the
    # node-by-node map inside tx_loop's scan over rounds: operations three
    # loops deep.  A step with no write slot has none.
    serial = re.findall(r"while/body/(?:closed_call/)?while/body/"
                        r"(?:closed_call/)?while/body", compiled.as_text())
    assert bool(serial) == (dep.wr > 0)


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_load_step_compiles_and_fits(one_chip, config):
    cell = next(c for c in SPEC["workloads"] if c["config"] == config)
    dep = deployment(cell["name"])
    mem = compile_for(one_chip, *dep.load_program()).memory_analysis()
    print(f"{config} load step: arguments {mem.argument_size_in_bytes}, "
          f"temporaries {mem.temp_size_in_bytes} bytes")
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM
