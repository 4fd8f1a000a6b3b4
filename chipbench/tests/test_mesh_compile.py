"""Ahead-of-time compiles of the mesh cells' programs at their real sizes
for a described four-chip TPU v5e host (``v5e:2x2``; no chip attached,
nothing runs).  ``test_compile.py`` compiles for one described chip, which
a program under ``jax.shard_map`` over four devices cannot be given; here
the deployment's mesh is built from the described devices.  The topology
is described in a module fixture, never at import, and JAX's persistent
cache is off around the compiles."""
import os

import jax
import numpy as np
import pytest
from conftest import ROOT
from jax.sharding import Mesh

from chipbench.harness import spec as S

SPEC = S.load_spec(ROOT)
HBM = 16 * 10**9
MESH_CELLS = [c["name"] for c in SPEC["workloads"] if c["chips"] > 1]


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield Mesh(np.array(topo.devices), ("node",))
    except Exception as e:      # noqa: BLE001 — any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.mark.parametrize("workload", MESH_CELLS)
def test_mesh_cell_programs_compile_and_fit(four_chips, workload,
                                            monkeypatch):
    cell = S.cell(SPEC, workload)
    conf = S.config(SPEC, cell["config"], ROOT)
    system, _ = S.system(conf["system"])
    # the deployment's mesh is the described host's, not this host's
    monkeypatch.setattr(system.jax, "make_mesh", lambda *a, **k: four_chips)
    monkeypatch.setattr(system.jax, "devices", lambda: list(
        four_chips.devices.flat))
    dep = system.Deployment(conf, S.traffic(cell["traffic"]))
    for name, (fn, shapes) in (("step", dep.step_program()),
                               ("load", dep.load_program())):
        compiled = fn.lower(*shapes).compile()
        mem = compiled.memory_analysis()          # bytes per chip
        print(f"{workload} {name}: arguments {mem.argument_size_in_bytes}, "
              f"temporaries {mem.temp_size_in_bytes} bytes a chip")
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM
        assert "all-to-all" in compiled.as_text()
