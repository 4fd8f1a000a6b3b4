"""TATP's subscriber table on the Storm dataplane, on one chip or a mesh.

Sizing: the TATP benchmark populates its Subscriber table in units of
100,000 rows; 1,000,000 rows is scale factor 10.  The rows are spread
``PER_NODE`` = 125,000 to a node over the cluster (8 simulated nodes
on one chip, or one node per chip on a four-chip host) and each node stores
its share in a MICA hash table of 131,072 one-slot buckets plus 65,536
overflow slots (about 25 MB of arena per node).

Traffic: fig6's grouping of the TATP mix (``draw_mix``) — 80% read
transactions (half read one row, half two), 16% updates, 4% inserts and
deletes — one transaction per lane of a ``txloop.tx_loop`` batch with
``RD`` = 2 read keys and ``WR`` = 1 write key.

``Cluster`` holds one layout of the deployment and builds its jitted steps:

  load_step    one batch of OP_INSERT write-based RPCs (``load`` drives it
               to completion, re-issuing lanes dropped by back-pressure)
  lookup_step  one-two-sided hybrid lookups (read-only)
  tx_step      one tx_loop batch: per-lane results and per-round counts

The same step code runs under ``SimTransport`` (``mesh=None``: every node's
arrays carry a leading node axis on one device) and under ``MeshTransport``
inside ``jax.shard_map`` (one node per device).  Under the mesh the
per-round counts are psum'd over the node axis (scope ``storm.allreduce``)
so they equal the simulator's cluster totals; the round trips each shard
counts are the cluster's already (``Transport.exchange_mask``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import hybrid as hy
from repro.core import rpc as R
from repro.core import slots as sl
from repro.core import txloop as txl
from repro.core.datastructs import hashtable as ht
from repro.core.transport import MeshTransport, SimTransport
from repro.testing.workloads import distinct_uint32

PER_NODE = 125_000          # x 8 nodes = TATP scale factor 10
LANES = 64                  # transaction lanes per node
RD, WR = 2, 1               # static read / write set sizes (masked per mix)
MAX_ROUNDS = 4              # tx_loop's bounded retry
LOAD_LANES = 4096           # insert lanes per node per load call

NODE, REP = P("node"), P()


def table_config(n_nodes: int) -> ht.HashTableConfig:
    """Per-node hash table that stores PER_NODE subscribers (load factor
    0.95 over the buckets; the colliding ~45,000 go to overflow chains)."""
    return ht.HashTableConfig(n_nodes=n_nodes, n_buckets=131072,
                              bucket_width=1, n_overflow=65536, max_chain=12)


def make_subscribers(seed: int, n_nodes: int, per_node: int):
    """Distinct subscriber keys and their row values, from ``seed``.

    Returns numpy (key_lo (N, n), key_hi (N, n), values (N, n, VALUE_WORDS))
    uint32; row (i, j) is loaded by node i's client."""
    rng = np.random.RandomState(seed)
    n = n_nodes * per_node
    klo = distinct_uint32(rng, n).reshape(n_nodes, per_node)
    khi = rng.randint(0, 2**31, (n_nodes, per_node)).astype(np.uint32)
    vals = rng.randint(0, 2**32, (n_nodes, per_node, sl.VALUE_WORDS),
                       dtype=np.uint64).astype(np.uint32)
    return klo, khi, vals


def draw_mix(rng, n_nodes: int, per_node: int, lanes: int):
    """One batch of fig6's 80/16/4 TATP mix as row coordinates.

    Returns ((read_node, read_row) (N, lanes, RD), (write_node, write_row)
    (N, lanes, WR), read_enabled, write_enabled): row (read_node, read_row)
    is the subscriber that lane reads.  Read transactions read one row
    (GET_SUBSCRIBER_DATA-like) or two (GET_NEW_DESTINATION-like); updates
    and inserts/deletes read one row and write one."""
    def pick(n):
        s = rng.randint(0, n_nodes, (n_nodes, lanes, n))
        i = rng.randint(0, per_node, (n_nodes, lanes, n))
        return s, i
    reads = pick(RD)
    writes = pick(WR)
    kind = rng.rand(n_nodes, lanes)
    is_read = kind < 0.80                 # read-only tx
    two_reads = kind < 0.40               # GET_NEW_DESTINATION-like
    read_en = np.ones((n_nodes, lanes, RD), bool)
    read_en[..., 1] = two_reads
    read_en[~is_read, 1] = False          # updates read 1 row
    write_en = np.repeat((~is_read)[..., None], WR, axis=-1)
    return reads, writes, read_en, write_en


def load_capacity(lanes: int, n_nodes: int) -> int:
    """Per-destination send-queue budget for a load call: the expected
    lanes per destination plus three standard deviations."""
    m = lanes / n_nodes
    return int(m + 3 * math.sqrt(m)) + 1


@dataclasses.dataclass
class Cluster:
    """One layout of the deployment: ``n_nodes`` nodes simulated on one
    device (``mesh=None``) or one node per device of ``mesh`` (axis
    "node")."""
    n_nodes: int
    mesh: Optional[Mesh] = None
    cfg: Optional[ht.HashTableConfig] = None

    def __post_init__(self):
        if self.cfg is None:
            self.cfg = table_config(self.n_nodes)
        self.layout = ht.build_layout(self.cfg)
        self.t = (SimTransport(self.n_nodes) if self.mesh is None
                  else MeshTransport(self.n_nodes, axis_name="node"))

    def put(self, tree):
        """Place host arrays: node-axis arrays split over the mesh."""
        if self.mesh is None:
            return jax.tree.map(jnp.asarray, tree)
        sh = NamedSharding(self.mesh, NODE)
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    def put_replicated(self, x):
        """Place a host array whole on every device of the layout."""
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(x, NamedSharding(self.mesh, REP))

    def init_state(self):
        return self.put(ht.init_cluster_state(self.cfg))

    def jit(self, fn, in_specs, out_specs, donate_argnums=()):
        if self.mesh is not None:
            fn = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
        return jax.jit(fn, donate_argnums=donate_argnums)

    def tx_arg_shapes(self):
        """Shapes of tx_step's arguments, state first."""
        N, L = self.n_nodes, LANES
        S, u32, b = jax.ShapeDtypeStruct, jnp.uint32, jnp.bool_
        state = jax.eval_shape(lambda: ht.init_cluster_state(self.cfg))
        return (state, S((N, L, RD, 2), u32), S((N, L, WR, 2), u32),
                S((N, L, WR, sl.VALUE_WORDS), u32), S((N, L, RD), b),
                S((N, L, WR), b), S((2,), u32))

    # --- steps ------------------------------------------------------------
    def load_step(self, capacity: int):
        """(state, key_lo, key_hi, values, enabled) -> (state, status):
        status is reply word 0 per lane (ST_DROPPED when back-pressured)."""
        cfg, layout, t = self.cfg, self.layout, self.t
        h = ht.make_rpc_handler(cfg, layout)

        def step(state, klo, khi, vals, enabled):
            node, _, _ = ht.lookup_start(cfg, layout, klo, khi)
            recs = ht.make_record(R.OP_INSERT, klo, khi, value=vals)
            state, rep, _, _ = R.rpc_call(t, state, node, recs, h,
                                          capacity=capacity, enabled=enabled)
            return state, rep[..., 0]
        return self.jit(step, (NODE,) * 5, (NODE, NODE), donate_argnums=0)

    def lookup_step(self):
        """(state, key_lo, key_hi, enabled) -> (found, value)."""
        cfg, layout, t = self.cfg, self.layout, self.t

        def step(state, klo, khi, enabled):
            _, _, found, value, *_ = hy.hybrid_lookup(
                t, state, klo, khi, cfg, layout, enabled=enabled)
            return found, value
        return self.jit(step, (NODE,) * 4, (NODE, NODE))

    def tx_step(self, max_rounds: int = MAX_ROUNDS):
        """(state, read_keys, write_keys, write_values, read_enabled,
        write_enabled, prng_key) -> (state, lanes, counts).

        ``counts`` are the cluster's: the per-round transaction counts, the
        bytes the batch's exchanges put on the inter-chip links
        (``ici_bytes``, 0 on one device) and ``round_trips``."""
        cfg, layout, t = self.cfg, self.layout, self.t

        def step(state, rk, wk, wv, ren, wen, key):
            state, _, res = txl.tx_loop(
                t, state, cfg, layout, read_keys=rk, write_keys=wk,
                write_values=wv, read_enabled=ren, write_enabled=wen,
                max_rounds=max_rounds, key=key)
            lanes = dict(committed=res.committed,
                         commit_round=res.commit_round,
                         read_found=res.read_found,
                         read_values=res.read_values)
            counts = dict(committed=res.round_committed,
                          attempts=res.round_attempts,
                          abort_lock=res.round_abort_lock,
                          abort_validate=res.round_abort_validate,
                          abort_overflow=res.round_abort_overflow,
                          ici_bytes=res.metrics.wire.ici_bytes)
            if self.mesh is not None:
                with jax.named_scope("storm.allreduce"):
                    counts = jax.tree.map(lambda x: lax.psum(x, "node"),
                                          counts)
            counts["round_trips"] = res.round_trips
            return state, lanes, counts
        return self.jit(step, (NODE,) * 6 + (REP,), (NODE, NODE, REP),
                        donate_argnums=0)

    # --- drivers -------------------------------------------------------
    def load(self, step, state, klo, khi, vals, lanes: int):
        """Insert every row of (klo, khi, vals) through the compiled
        ``load_step``, ``lanes`` per node per call, re-issuing lanes dropped
        by back-pressure.  Any status but ST_OK / ST_DROPPED raises.

        Returns (state, calls, reissued)."""
        N, n = klo.shape
        todo = [np.arange(n) for _ in range(N)]
        calls = reissued = 0
        while any(len(q) for q in todo):
            idx = np.zeros((N, lanes), np.int64)
            en = np.zeros((N, lanes), bool)
            for i, q in enumerate(todo):
                k = min(lanes, len(q))
                idx[i, :k], en[i, :k] = q[:k], True
            rows = np.arange(N)[:, None]
            state, status = step(state, *self.put(
                (klo[rows, idx], khi[rows, idx], vals[rows, idx], en)))
            status = np.asarray(status)
            calls += 1
            dropped = en & (status == R.ST_DROPPED)
            bad = en & ~dropped & (status != R.ST_OK)
            if bad.any():
                raise RuntimeError(
                    f"load: {int(bad.sum())} inserts failed, statuses "
                    f"{sorted(set(status[bad].tolist()))}")
            if not (en & ~dropped).any():
                raise RuntimeError("load: a call delivered no insert")
            reissued += int(dropped.sum())
            for i, q in enumerate(todo):
                k = min(lanes, len(q))
                todo[i] = np.concatenate([q[k:], idx[i, :k][dropped[i, :k]]])
        return state, calls, reissued
