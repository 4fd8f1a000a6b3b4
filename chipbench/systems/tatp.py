"""The TATP subscriber deployments on the program under test: this
builds the cluster, loads the table through the program's insert paths,
compiles the step the window drives and reads the table back after it.

The step is ``repro.core.txloop.tx_loop`` jitted as ``tatp.Cluster.tx_step``
jits it (state donated), with ``rep=ReplicaConfig(f)`` where the
configuration keeps f > 0 backups.  Loading:

* f = 0: ``tatp.Cluster.load`` over ``Cluster.load_step`` (write-based
  RPC inserts, back-pressured lanes sent again);
* f > 0: write-only insert transactions through ``tx_loop`` at f, one
  protocol round a call, uncommitted lanes sent again here, so that every
  row is on its primary and its f ring backups as a committed write puts
  it.

Read-back is ``replication.failover_lookup``: copy i of the rows homed at
node p is read with nodes p .. p+i-1 marked dead in the alive mask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import tatp
from repro.core import replication as repl
from repro.core import txloop as txl
from repro.core.datastructs import hashtable as ht

READBACK_LANES = 512         # keys a node's client reads back per call


class Deployment:
    """One TATP deployment on one device, holding its table's state."""

    def __init__(self, conf: dict, traffic: dict):
        N = conf["nodes"]
        if conf["subscribers"] % N:
            raise ValueError("subscribers must split evenly over the nodes")
        self.n_nodes, self.f = N, conf["replication_f"]
        self.per_node = conf["subscribers"] // N
        self.cfg = ht.HashTableConfig(n_nodes=N, **conf["table"])
        self.cl = tatp.Cluster(N, cfg=self.cfg)
        self.lanes = traffic["lanes"]
        self.rd, self.wr = traffic["static_reads"], traffic["static_writes"]
        self.words = conf["value_words"]
        self.max_rounds = conf["max_rounds"]
        self.load_lanes = min(conf["load_lanes"], self.per_node)
        self.load_cap = tatp.load_capacity(self.load_lanes, N)
        self.state = self.readback_fn = None

    # --- programs ---------------------------------------------------------
    def _rep(self, f):
        return repl.ReplicaConfig(self.n_nodes, f) if f else None

    def _tx(self, f, capacity=None, max_rounds=None):
        cl, rep = self.cl, self._rep(f)
        rounds = self.max_rounds if max_rounds is None else max_rounds

        def step(state, rk, wk, wv, ren, wen, key):
            state, _, res = txl.tx_loop(
                cl.t, state, cl.cfg, cl.layout, read_keys=rk, write_keys=wk,
                write_values=wv, read_enabled=ren, write_enabled=wen,
                capacity=capacity, max_rounds=rounds, key=key, rep=rep)
            lanes = dict(committed=res.committed,
                         commit_round=res.commit_round,
                         read_found=res.read_found,
                         read_values=res.read_values)
            counts = dict(committed=res.round_committed,
                          attempts=res.round_attempts,
                          abort_lock=res.round_abort_lock,
                          abort_validate=res.round_abort_validate,
                          abort_overflow=res.round_abort_overflow,
                          round_trips=res.round_trips)
            return state, lanes, counts
        return jax.jit(step, donate_argnums=0)

    def _shapes(self, lanes, rd, wr):
        N, S, u32 = self.n_nodes, jax.ShapeDtypeStruct, jnp.uint32
        state = jax.eval_shape(lambda: ht.init_cluster_state(self.cfg))
        return (state, S((N, lanes, rd, 2), u32), S((N, lanes, wr, 2), u32),
                S((N, lanes, wr, self.words), u32),
                S((N, lanes, rd), jnp.bool_), S((N, lanes, wr), jnp.bool_),
                S((2,), u32))

    def step_program(self, f=None):
        """(jitted step, its argument shapes) for the cell's traffic."""
        f = self.f if f is None else f
        return self._tx(f), self._shapes(self.lanes, self.rd, self.wr)

    def load_program(self):
        N, L, S = self.n_nodes, self.load_lanes, jax.ShapeDtypeStruct
        if self.f == 0:
            state = jax.eval_shape(lambda: ht.init_cluster_state(self.cfg))
            u32 = jnp.uint32
            return self.cl.load_step(self.load_cap), (
                state, S((N, L), u32), S((N, L), u32),
                S((N, L, self.words), u32), S((N, L), jnp.bool_))
        return (self._tx(self.f, capacity=self.load_cap, max_rounds=1),
                self._shapes(L, 0, 1))

    def compile(self):
        """AOT-compile the load step and the window's step."""
        fn, shapes = self.load_program()
        self.load_fn = fn.lower(*shapes).compile()
        fn, shapes = self.step_program()
        self.step_fn = fn.lower(*shapes).compile()

    # --- load ---------------------------------------------------------------
    def load(self, table) -> dict:
        """Insert every row of ``table``; node i's client loads rows
        i*per_node .. (i+1)*per_node-1.  Returns the load's counts."""
        N = self.n_nodes
        klo = table.klo.reshape(N, self.per_node)
        khi = table.khi.reshape(N, self.per_node)
        vals = table.vals.reshape(N, self.per_node, self.words)
        state = self.cl.init_state()
        if self.f == 0:
            state, calls, resent = self.cl.load(
                self.load_fn, state, klo, khi, vals, self.load_lanes)
        else:
            state, calls, resent = self._load_replicated(
                state, klo, khi, vals)
        self.state = jax.block_until_ready(state)
        return dict(calls=calls, resent=resent)

    def _load_replicated(self, state, klo, khi, vals):
        N, L, n = self.n_nodes, self.load_lanes, self.per_node
        todo = [np.arange(n) for _ in range(N)]
        rows = np.arange(N)[:, None]
        z = np.zeros((N, L, 0, 2), np.uint32)
        calls = resent = 0
        while any(len(q) for q in todo):
            idx = np.zeros((N, L), np.int64)
            en = np.zeros((N, L), bool)
            for i, q in enumerate(todo):
                k = min(L, len(q))
                idx[i, :k], en[i, :k] = q[:k], True
            wk = np.stack([klo[rows, idx], khi[rows, idx]], -1)[:, :, None]
            args = (z, wk, vals[rows, idx][:, :, None], z[..., 0] > 0,
                    en[..., None], np.array([calls, 0x10AD], np.uint32))
            state, lanes, _ = self.load_fn(state, *map(jnp.asarray, args))
            done = np.asarray(lanes["committed"]) & en
            calls += 1
            if not done.any():
                raise RuntimeError("replicated load: a call committed no row")
            redo = en & ~done
            resent += int(redo.sum())
            for i, q in enumerate(todo):
                k = min(L, len(q))
                todo[i] = np.concatenate([q[k:], idx[i, :k][redo[i, :k]]])
        return state, calls, resent

    # --- the window's path --------------------------------------------------
    def put(self, table, b):
        """Device arrays of one batch (keys looked up from its rows)."""
        rk = np.stack([table.klo[b["rrow"]], table.khi[b["rrow"]]], -1)
        wk = np.stack([table.klo[b["wrow"]], table.khi[b["wrow"]]], -1)
        return jax.device_put((rk, wk, b["wval"], b["ren"], b["wen"],
                               b["key"]))

    def dispatch(self, args):
        self.state, lanes, counts = self.step_fn(self.state, *args)
        return lanes, counts

    @staticmethod
    def fetch(out):
        """Wait for a batch's results and bring them to the host."""
        return jax.device_get(jax.block_until_ready(out))

    # --- after the window ---------------------------------------------------
    def _readback_fn(self):
        N, L = self.n_nodes, READBACK_LANES
        cl, rep = self.cl, repl.ReplicaConfig(N, self.f)

        def rb(state, klo, khi, alive, en):
            out = repl.failover_lookup(cl.t, state, klo, khi, cl.cfg,
                                       cl.layout, rep, alive, enabled=en)
            return out["found"], out["value"], out["node"]

        S, u32 = jax.ShapeDtypeStruct, jnp.uint32
        return jax.jit(rb).lower(
            jax.eval_shape(lambda: ht.init_cluster_state(self.cfg)),
            S((N, L), u32), S((N, L), u32), S((N,), jnp.bool_),
            S((N, L), jnp.bool_)).compile()

    def readback(self, table, rows):
        """What the program holds for ``rows`` on each of the 1 + f
        copies: (found (copies, n), values (copies, n, words))."""
        N, L = self.n_nodes, READBACK_LANES
        if self.readback_fn is None:
            self.readback_fn = self._readback_fn()
        fn = self.readback_fn
        klo, khi = table.klo[rows], table.khi[rows]
        home = np.asarray(ht.home_of(self.cfg, jnp.asarray(klo),
                                     jnp.asarray(khi))[0])
        n, per = rows.size, N * L
        found = np.zeros((self.f + 1, n), bool)
        values = np.zeros((self.f + 1, n, self.words), np.uint32)
        for c0 in range(0, n, per):
            sl_ = slice(c0, min(n, c0 + per))
            k = sl_.stop - c0
            pad = lambda x: np.pad(x, (0, per - k)).reshape(N, L)
            lo, hi, h = pad(klo[sl_]), pad(khi[sl_]), pad(home[sl_])
            live = pad(np.ones(k, bool))
            for c in range(self.f + 1):
                # copy c of the rows homed at p: nodes p .. p+c-1 dead
                homes = [None] if c == 0 else range(N)
                for p in homes:
                    alive = np.ones(N, bool)
                    en = live.copy()
                    if p is not None:
                        alive[[(p + j) % N for j in range(c)]] = False
                        en &= h == p
                    f_, v_, node = jax.device_get(fn(
                        self.state, lo, hi, alive, en))
                    ok = en & f_ & (node == (h + c) % N)
                    sel = en.reshape(-1)[:k]
                    found[c, sl_][sel] = ok.reshape(-1)[:k][sel]
                    values[c, sl_][sel] = v_.reshape(per, -1)[:k][sel]
        return found, values
