"""The TATP subscriber deployment on a mesh: one Storm node per chip, every
exchange a ``lax.all_to_all`` between the chips.

It is ``systems/tatp.py``'s deployment with the cluster laid out over a
mesh of ``nodes`` devices (``jax.make_mesh``, axis "node") as
``tatp.Cluster(mesh=...)`` lays it out: node-axis arrays are split over the
chips, the PRNG key is on every chip, and each program runs under
``jax.shard_map`` with ``MeshTransport``.

* Load (f = 0): ``tatp.Cluster.load`` over ``Cluster.load_step`` on the
  mesh, every chip inserting its client's rows at once.
* The window's step: ``tatp.Cluster.tx_step`` (state donated, the
  configuration's ``max_rounds``), whose
  counts are the cluster's: psum'd over "node", the bytes the exchanges put
  on the inter-chip links (``ici_bytes``) among them, and the round trips
  that carried traffic anywhere in the cluster.
* Read-back: ``replication.failover_lookup`` on the mesh.

Replicated (f > 0) deployments are not built here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench.systems import tatp as sim
from repro import tatp
from repro.core import replication as repl
from repro.core.datastructs import hashtable as ht

NODE, REP = P("node"), P()


class Deployment(sim.Deployment):
    """One TATP deployment over a mesh of ``nodes`` devices, holding its
    table's state split over them."""

    def __init__(self, conf: dict, traffic: dict):
        super().__init__(conf, traffic)
        if self.f:
            raise ValueError("the mesh deployment holds one copy (f = 0)")
        N, have = self.n_nodes, len(jax.devices())
        if have < N:
            raise RuntimeError(f"the mesh deployment needs {N} devices, "
                               f"JAX reports {have}")
        self.mesh = jax.make_mesh((N,), ("node",))
        self.cl = tatp.Cluster(N, mesh=self.mesh, cfg=self.cfg)
        self.node_sh = NamedSharding(self.mesh, NODE)
        self.rep_sh = NamedSharding(self.mesh, REP)
        # build (not compile) the step now: a program whose Cluster.tx_step
        # cannot give the cluster's counts fails here, before any compile
        self._tx(self.f)

    def _place(self, shapes, sharding):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), shapes)

    # --- programs ---------------------------------------------------------
    def _tx(self, f, capacity=None, max_rounds=None):
        if f or capacity is not None or max_rounds is not None:
            raise ValueError("the mesh deployment builds the unreplicated "
                             "window step only")
        return self.cl.tx_step(max_rounds=self.max_rounds)

    def _shapes(self, lanes, rd, wr):
        *node, key = super()._shapes(lanes, rd, wr)
        return (*self._place(tuple(node), self.node_sh),
                self._place(key, self.rep_sh))

    def load_program(self):
        fn, shapes = super().load_program()
        return fn, self._place(shapes, self.node_sh)

    # --- the window's path --------------------------------------------------
    def put(self, table, b):
        """Device arrays of one batch, each node's lanes on its chip."""
        rk = np.stack([table.klo[b["rrow"]], table.khi[b["rrow"]]], -1)
        wk = np.stack([table.klo[b["wrow"]], table.khi[b["wrow"]]], -1)
        return (*self.cl.put((rk, wk, b["wval"], b["ren"], b["wen"])),
                self.cl.put_replicated(b["key"]))

    # --- after the window ---------------------------------------------------
    def _readback_fn(self):
        N, L = self.n_nodes, sim.READBACK_LANES
        cl, rep = self.cl, repl.ReplicaConfig(N, self.f)

        def rb(state, klo, khi, alive, en):
            out = repl.failover_lookup(cl.t, state, klo, khi, cl.cfg,
                                       cl.layout, rep, alive, enabled=en)
            return out["found"], out["value"], out["node"]

        S, u32 = jax.ShapeDtypeStruct, jnp.uint32
        state = jax.eval_shape(lambda: ht.init_cluster_state(self.cfg))
        node = self._place((state, S((N, L), u32), S((N, L), u32)),
                           self.node_sh)
        return cl.jit(rb, (NODE,) * 3 + (REP, NODE), (NODE,) * 3).lower(
            *node, self._place(S((N,), jnp.bool_), self.rep_sh),
            self._place(S((N, L), jnp.bool_), self.node_sh)).compile()

