"""Multi-round transaction engine: bounded retry with backoff (Storm §5.4).

``tx.run_transactions`` is single shot: a lane that loses a lock race, fails
OCC validation, or is dropped by send-queue back-pressure simply reports
failure.  Storm's dataplane instead *retries* aborted transactions — under
contention the batch converges instead of silently dropping work.  ``tx_loop``
drives that retry:

  * a ``lax.scan`` over ``max_rounds`` protocol rounds, all shapes static;
    a round after the first runs only while some lane of the cluster is
    still live (``Transport.any_live``), so a batch that commits in round 0
    pays for one round, not ``max_rounds``;
  * per-round lane re-enable masks: lanes that committed are parked (their
    reads/writes are disabled, so they cost no handler work, no send-queue
    capacity and no wire bytes — see transport.route_by_dest's enabled mask);
    lanes that aborted for ANY cause (lock conflict, validation conflict,
    overflow) stay live and re-execute the full OCC protocol;
  * randomized-slot backoff: each round >= 1 permutes the surviving lanes'
    send-queue slots with a per-round PRNG draw, which re-randomizes the lock
    serialization order so one pathological ordering cannot starve the same
    lane round after round (the batched analogue of randomized exponential
    backoff).

Because committed lanes release send-queue capacity, a workload that
overflows a small per-destination capacity drains across rounds — every lane
is eventually delivered (see tests/test_txloop.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import hybrid as hy
from repro.core import placement as pl
from repro.core import slots as sl
from repro.core import telemetry as T
from repro.core import tx as txm
from repro.core.datastructs import hashtable as ht
from repro.core.transport import Transport


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TxLoopResult:
    committed: jnp.ndarray            # (N, B) bool — committed in ANY round
    commit_round: jnp.ndarray         # (N, B) int32 — round of commit, -1 if never
    read_found: jnp.ndarray           # (N, B, R) bool — from the lane's last attempt
    read_values: jnp.ndarray          # (N, B, R, VALUE_WORDS)
    # --- per-round metrics, each (max_rounds,) int32 -----------------------
    round_committed: jnp.ndarray      # lanes that committed in round r
    round_attempts: jnp.ndarray       # live lanes entering round r
    round_retries: jnp.ndarray        # live lanes in round r > 0 (re-attempts)
    round_abort_lock: jnp.ndarray     # aborts by cause, per round
    round_abort_validate: jnp.ndarray
    round_abort_overflow: jnp.ndarray
    round_abort_stale: jnp.ndarray    # stale placement routes, per round
    metrics: hy.HybridMetrics         # totals across all rounds
    round_trips: jnp.ndarray          # scalar
    rounds_run: jnp.ndarray           # scalar int32 — round bodies executed


def _perm_lanes(x, perm):
    """Permute the lane axis (axis 1) of (N, B, ...) by perm (N, B)."""
    idx = perm.reshape(perm.shape + (1,) * (x.ndim - 2))
    return jnp.take_along_axis(x, idx, axis=1)


def _backoff_perm(t: Transport, key, B: int):
    """Per-node backoff permutation of the B lane slots, (n_local, B) int32.
    Node i draws from key i of the global split, so a shard of the cluster
    (MeshTransport, one node per device) draws what the simulator's node i
    draws — not a copy of node 0's permutation."""
    keys = jax.random.split(key, t.n_nodes)[t.node_ids()]
    return jax.vmap(lambda k: jax.random.permutation(k, B))(keys).astype(
        jnp.int32)


@jax.named_scope("storm.txloop")
def tx_loop(t: Transport, state, cfg: ht.HashTableConfig, layout, *,
            read_keys, write_keys, write_values, read_enabled=None,
            write_enabled=None, cache=None, use_onesided: bool = True,
            capacity: Optional[int] = None, max_rounds: int = 4, key=None,
            fused: bool = True, nic=None, rep=None, ptable=None, pcfg=None,
            telemetry: Optional[T.TelemetryConfig] = None):
    """Run a batch of transactions to convergence (bounded by max_rounds).

    Arguments mirror tx.run_transactions; additionally:
      max_rounds: static retry bound (>= 1).  Round 0 is identical to the
                  single-shot protocol; each later round re-runs only the
                  still-aborted lanes with permuted send-queue slots, and
                  is skipped whole once no lane of the cluster is live
                  (its counts and WireStats read 0; ``rounds_run`` counts
                  the rounds that ran).
      key:        optional jax PRNG key for the backoff permutation (split
                  once per round, skipped or not).
      fused:      run each protocol round on the fused 3-4-exchange schedule
                  (default) or the per-phase 5-round reference.
      nic:        optional repro.core.nic.ConnTable (connection mode +
                  emulated cluster scale); the aggregated metrics.wire then
                  reports the modeled NIC-cache hit rate / per-op penalty.
      rep:        optional repro.core.replication.ReplicaConfig — every
                  committing round installs the write set on all f+1 copies
                  (backup writes fused into the commit round, zero extra
                  exchange rounds); a backup write dropped by back-pressure
                  aborts its lane (cause: overflow), which THIS loop retries.
      ptable/pcfg: optional placement.PlacementTable + PlacementConfig —
                  every round routes through the table, and a retry round
                  entered with stale-route aborts (``aborted_stale``, i.e.
                  some owner answered ST_WRONG_EPOCH) first REFRESHES the
                  table with one one-sided read of the coordinator's routing
                  region, mirroring scan_loop's separator-directory refresh.
                  Epoch-stable rounds never refresh — the read is
                  enabled-gated off, so the steady-state round-trip schedule
                  is EXACTLY the pre-placement one (bench-gated).
      telemetry:  optional telemetry.TelemetryConfig — thread a flight
                  recorder through every exchange round (one event per fused
                  round + one summary per protocol round) and accumulate the
                  modeled per-lane latency.  ``None`` (default) is
                  bit-identical and round-identical to a recorder-free build.

    Returns (state, cache, TxLoopResult) — plus a ``telemetry.TelemetryOut``
    as a fourth element when ``telemetry`` is enabled.

    The loop's own work (backoff, lane permutes, per-round counts, the
    round gate, the scan itself) runs under the named scope
    ``storm.txloop`` (the gate's cross-chip sum under ``storm.allreduce``);
    each protocol round's work carries its ``storm.occ.*`` and
    ``storm.round.*`` scopes inside it.
    """
    N, B, Rd = read_keys.shape[:3]
    if read_enabled is None:
        read_enabled = jnp.ones(read_keys.shape[:3], bool)
    if write_enabled is None:
        write_enabled = jnp.ones(write_keys.shape[:3], bool)
    if key is None:
        key = jax.random.PRNGKey(0x5707)
    use_pl = ptable is not None
    if use_pl and pcfg is None:
        raise ValueError("tx_loop: ptable requires pcfg (PlacementConfig)")
    use_tel = telemetry is not None
    ident = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[None], (N, B))

    def protocol_round(carry, sub, rnd):
        state, cache, ptab, stale_in, done, commit_round, rfound, rvals, \
            tb, lat = carry
        rec = T.Recorder(telemetry, tb) if use_tel else None
        if use_tel:
            rec.set_round(rnd)
            n0 = rec.buf.n
        perm = _backoff_perm(t, sub, B)
        perm = jnp.where(rnd == 0, ident, perm)     # round 0 == single shot
        inv = jnp.argsort(perm, axis=1)
        active = ~done
        p = lambda x: _perm_lanes(x, perm)
        u = lambda x: _perm_lanes(x, inv)
        act_p = p(active)

        # a retry round entered with stale-route aborts refreshes the cached
        # placement table first (one one-sided read of the coordinator's
        # routing region); epoch-stable rounds gate the read OFF — zero wire,
        # zero round trips — so the fast-path schedule stays untouched
        s_ref = hy.WireStats.zero()
        if use_pl:
            want = (rnd > 0) & stale_in
            ptab_new, s_r = pl.refresh_table(t, state, layout, pcfg, ptab,
                                             enabled=want, nic=nic,
                                             telemetry=rec)
            ptab = jax.tree.map(
                lambda new, old: jnp.where(want, new, old), ptab_new, ptab)
            s_ref = jax.tree.map(
                lambda x: jnp.where(want, x, jnp.zeros_like(x)), s_r)

        state, cache, res = txm.run_transactions(
            t, state, cfg, layout,
            read_keys=p(read_keys), write_keys=p(write_keys),
            write_values=p(write_values),
            read_enabled=p(read_enabled) & act_p[..., None],
            write_enabled=p(write_enabled) & act_p[..., None],
            cache=cache, use_onesided=use_onesided, capacity=capacity,
            fused=fused, nic=nic, rep=rep,
            ptable=ptab if use_pl else None, telemetry=rec)
        # fully-masked (parked) lanes report committed=True — gate on active
        newly = u(res.committed) & active
        done = done | newly
        commit_round = jnp.where(newly, rnd.astype(jnp.int32), commit_round)
        rfound = jnp.where(active[..., None], u(res.read_found), rfound)
        rvals = jnp.where(active[..., None, None], u(res.read_values), rvals)
        count = lambda x: jnp.sum(x.astype(jnp.int32))
        stale_out = jnp.any(u(res.aborted_stale) & active)
        m = res.metrics
        stats = dict(
            committed=count(newly),
            attempts=count(active),
            retries=jnp.where(rnd > 0, count(active), 0),
            abort_lock=count(u(res.aborted_lock) & active),
            abort_validate=count(u(res.aborted_validate) & active),
            abort_overflow=count(u(res.aborted_overflow) & active),
            abort_stale=count(u(res.aborted_stale) & active),
            metrics=hy.HybridMetrics(m.onesided_success, m.rpc_fallback,
                                     m.total, m.wire + s_ref),
            round_trips=res.round_trips + s_ref.round_trips,
        )
        if use_tel:
            # every lane still live this round accumulates the round's
            # modeled latency; the summary row carries the abort vector
            lat = lat + rec.round_cost_us(n0) * active.astype(jnp.float32)
            rec.summary(committed=stats["committed"],
                        attempts=stats["attempts"],
                        abort_lock=stats["abort_lock"],
                        abort_validate=stats["abort_validate"],
                        abort_overflow=stats["abort_overflow"],
                        abort_stale=stats["abort_stale"])
            tb = rec.buf
        return (state, cache, ptab, stale_out, done, commit_round, rfound,
                rvals, tb, lat), stats

    def body(carry, rnd):
        rc, key = carry                     # rc: protocol_round's carry
        key, sub = jax.random.split(key)    # every round draws, run or not
        done = rc[4]
        # a round runs only while some lane of the cluster is live (round 0
        # always: no lane is done yet); a skipped round leaves the carry as
        # an empty one would and counts nothing — no work, no wire bytes
        run = (rnd == 0) | t.any_live(~done)
        stats = jax.eval_shape(protocol_round, rc, sub, rnd)[1]
        skip = lambda rc, sub, rnd: (rc, jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), stats))
        rc, stats = lax.cond(run, protocol_round, skip, rc, sub, rnd)
        return (rc, key), (stats, run)

    init = (
        state, cache,
        ptable if use_pl else jnp.zeros(()),
        jnp.zeros((), bool),
        jnp.zeros((N, B), bool),
        jnp.full((N, B), -1, jnp.int32),
        jnp.zeros(read_enabled.shape, bool),
        jnp.zeros(read_enabled.shape + (sl.VALUE_WORDS,), jnp.uint32),
        (T.make_buffer(t.n_nodes, T.loop_capacity(telemetry, max_rounds))
         if use_tel else jnp.zeros(())),
        jnp.zeros((N, B), jnp.float32) if use_tel else jnp.zeros(()),
    )
    ((state, cache, _, _, done, commit_round, rfound, rvals, tb, lat), _), \
        (ys, ran) = lax.scan(body, (init, key), jnp.arange(max_rounds))

    metrics = jax.tree.map(lambda x: jnp.sum(x, axis=0), ys["metrics"])
    result = TxLoopResult(
        committed=done,
        commit_round=commit_round,
        read_found=rfound,
        read_values=rvals,
        round_committed=ys["committed"],
        round_attempts=ys["attempts"],
        round_retries=ys["retries"],
        round_abort_lock=ys["abort_lock"],
        round_abort_validate=ys["abort_validate"],
        round_abort_overflow=ys["abort_overflow"],
        round_abort_stale=ys["abort_stale"],
        metrics=metrics,
        round_trips=jnp.sum(ys["round_trips"]),
        rounds_run=jnp.sum(ran.astype(jnp.int32)),
    )
    if use_tel:
        return state, cache, result, T.TelemetryOut(trace=tb,
                                                    lane_latency_us=lat)
    return state, cache, result


# ===========================================================================
# Bounded-retry loop for RANGE-SCAN transactions (tx.run_scan_transactions).
#
# Same engine shape as tx_loop — committed lanes park, aborted lanes re-run
# with randomized-slot backoff — plus one ordered-index-specific move: every
# retry round REFRESHES the cached separator directory first (one one-sided
# read per node, its wire cost accounted), so lanes that aborted on a stale
# plan (a leaf split underneath the scan: fence-chain gap -> cause
# `validate`) converge instead of replaying the same stale route — the
# retry-loop analogue of chasing a B-link right-pointer.  `truncated` lanes
# (range needs more than cfg.max_scan_leaves leaves) are parked and REPORTED:
# retrying cannot help and a silent clip is never returned as success.
# ===========================================================================
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ScanLoopResult:
    committed: jnp.ndarray            # (N, B) bool — committed in ANY round
    commit_round: jnp.ndarray         # (N, B) int32 — round of commit, -1 never
    truncated: jnp.ndarray            # (N, B) bool — parked: range > S leaves
    scan_keys: jnp.ndarray            # (N, B, S, LW) — from the last attempt
    scan_values: jnp.ndarray          # (N, B, S, LW, VALUE_WORDS)
    scan_mask: jnp.ndarray            # (N, B, S, LW) bool
    # --- per-round metrics, each (max_rounds,) int32 -----------------------
    round_committed: jnp.ndarray
    round_attempts: jnp.ndarray
    round_retries: jnp.ndarray
    round_abort_lock: jnp.ndarray
    round_abort_validate: jnp.ndarray
    round_abort_overflow: jnp.ndarray
    round_abort_stale: jnp.ndarray    # stale placement routes, per round
    metrics: hy.HybridMetrics         # totals across rounds (+ meta refresh)
    round_trips: jnp.ndarray          # scalar


@jax.named_scope("storm.txloop")
def scan_loop(t: Transport, state, cfg, layout, *, scan_lo, scan_hi,
              meta=None, write_keys=None, write_values=None,
              scan_enabled=None, write_enabled=None,
              capacity: Optional[int] = None, max_rounds: int = 4, key=None,
              fused: bool = True, nic=None, rep=None, refresh: bool = True,
              ptable=None, pcfg=None,
              telemetry: Optional[T.TelemetryConfig] = None):
    """Run a batch of range-scan transactions to convergence.

    Arguments mirror tx.run_scan_transactions (cfg is a btree.BTreeConfig);
    additionally:
      meta:       initial cached separator directory; None fetches one up
                  front (wire cost counted).
      refresh:    refresh the directory before every RETRY round (default) —
                  stale-plan aborts then converge; refresh=False replays the
                  initial meta (useful to demonstrate the livelock it avoids).
      ptable/pcfg: optional placement table + config — lock-class routing
                  goes through the table; a retry round entered with
                  stale-route aborts refreshes it first (enabled-gated read,
                  zero wire on epoch-stable rounds — same idiom as the
                  separator-directory refresh above).
      telemetry:  optional telemetry.TelemetryConfig — same flight recorder
                  as tx_loop's (``None`` = bit-identical, round-identical).
    Returns (state, meta, ScanLoopResult) — plus a ``telemetry.TelemetryOut``
    as a fourth element when ``telemetry`` is enabled.  Named scopes as in
    tx_loop."""
    from repro.core.datastructs import btree as bt

    N, B = scan_lo.shape
    S, LW = cfg.max_scan_leaves, cfg.leaf_width
    if write_keys is None:
        write_keys = jnp.zeros((N, B, 0), jnp.uint32)
        write_values = jnp.zeros((N, B, 0, sl.VALUE_WORDS), jnp.uint32)
    Wr = write_keys.shape[2]
    if scan_enabled is None:
        scan_enabled = jnp.ones((N, B), bool)
    if write_enabled is None:
        write_enabled = jnp.ones((N, B, Wr), bool)
    if key is None:
        key = jax.random.PRNGKey(0x5C0A)
    use_pl = ptable is not None
    if use_pl and pcfg is None:
        raise ValueError("scan_loop: ptable requires pcfg (PlacementConfig)")
    use_tel = telemetry is not None
    tb0 = (T.make_buffer(t.n_nodes, T.loop_capacity(telemetry, max_rounds))
           if use_tel else jnp.zeros(()))
    init_wire = hy.WireStats.zero()
    if meta is None:
        meta, s0 = bt.refresh_meta(t, state, cfg, layout, nic=nic)
        init_wire = init_wire + s0
        if use_tel:
            # up-front directory fetch: one event stamped "round -1" (per-dest
            # tails: the refresh is a uniform all-to-all, scalar split evenly)
            rec0 = T.Recorder(telemetry, tb0)
            rec0.set_round(-1)
            nd = t.n_nodes
            rec0.record(
                T.PH_REFRESH, s0,
                per_dest_msgs=jnp.full((nd,), s0.messages / nd),
                per_dest_bytes=jnp.full(
                    (nd,), (s0.req_bytes + s0.reply_bytes) / nd))
            tb0 = rec0.buf
    ident = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[None], (N, B))

    def body(carry, rnd):
        (state, meta, ptab, stale_in, done, trunc, commit_round, skeys, svals,
         smask, key, tb, lat) = carry
        rec = T.Recorder(telemetry, tb) if use_tel else None
        if use_tel:
            rec.set_round(rnd)
            n0 = rec.buf.n
        key, sub = jax.random.split(key)
        perm = _backoff_perm(t, sub, B)
        perm = jnp.where(rnd == 0, ident, perm)     # round 0 == single shot
        inv = jnp.argsort(perm, axis=1)
        active = ~done
        p = lambda x: _perm_lanes(x, perm)
        u = lambda x: _perm_lanes(x, inv)
        act_p = p(active)

        s_ref = hy.WireStats.zero()
        if refresh:
            meta_new, s_r = bt.refresh_meta(t, state, cfg, layout, nic=nic)
            use = rnd > 0
            meta = jax.tree.map(
                lambda new, old: jnp.where(use, new, old), meta_new, meta)
            s_ref = jax.tree.map(
                lambda x: jnp.where(use, x, jnp.zeros_like(x)), s_r)
            if use_tel:
                # the directory read itself is issued unconditionally but
                # ACCOUNTED only on retry rounds — record the gated view so
                # the trace matches the wire accounting exactly; the refresh
                # is a uniform all-to-all (every node reads every node), so
                # the per-dest tails are the scalar split evenly
                nd = t.n_nodes
                rec.record(
                    T.PH_REFRESH, s_ref,
                    per_dest_msgs=jnp.full((nd,), s_ref.messages / nd),
                    per_dest_bytes=jnp.full(
                        (nd,), (s_ref.req_bytes + s_ref.reply_bytes) / nd))
        if use_pl:
            # placement-table refresh, gated exactly like tx_loop's: only a
            # retry round entered with stale-route aborts pays the read
            want = (rnd > 0) & stale_in
            ptab_new, s_p = pl.refresh_table(t, state, layout, pcfg, ptab,
                                             enabled=want, nic=nic,
                                             telemetry=rec)
            ptab = jax.tree.map(
                lambda new, old: jnp.where(want, new, old), ptab_new, ptab)
            s_ref = s_ref + jax.tree.map(
                lambda x: jnp.where(want, x, jnp.zeros_like(x)), s_p)

        state, res = txm.run_scan_transactions(
            t, state, cfg, layout,
            scan_lo=p(scan_lo), scan_hi=p(scan_hi), meta=meta,
            write_keys=p(write_keys), write_values=p(write_values),
            scan_enabled=p(scan_enabled) & act_p,
            write_enabled=p(write_enabled) & act_p[..., None],
            capacity=capacity, fused=fused, nic=nic, rep=rep,
            ptable=ptab if use_pl else None, telemetry=rec)
        newly = u(res.committed) & active
        newly_trunc = u(res.truncated) & active
        done = done | newly | newly_trunc           # truncation cannot retry
        trunc = trunc | newly_trunc
        commit_round = jnp.where(newly, rnd.astype(jnp.int32), commit_round)
        upd = active[..., None, None]
        skeys = jnp.where(upd, u(res.scan_keys), skeys)
        smask = jnp.where(upd, u(res.scan_mask), smask)
        svals = jnp.where(upd[..., None], u(res.scan_values), svals)
        count = lambda x: jnp.sum(x.astype(jnp.int32))
        stale_out = jnp.any(u(res.aborted_stale) & active)
        m = res.metrics
        stats = dict(
            committed=count(newly),
            attempts=count(active),
            retries=jnp.where(rnd > 0, count(active), 0),
            abort_lock=count(u(res.aborted_lock) & active),
            abort_validate=count(u(res.aborted_validate) & active),
            abort_overflow=count(u(res.aborted_overflow) & active),
            abort_stale=count(u(res.aborted_stale) & active),
            metrics=hy.HybridMetrics(m.onesided_success, m.rpc_fallback,
                                     m.total, m.wire + s_ref),
            round_trips=res.round_trips + s_ref.round_trips,
        )
        if use_tel:
            lat = lat + rec.round_cost_us(n0) * active.astype(jnp.float32)
            rec.summary(committed=stats["committed"],
                        attempts=stats["attempts"],
                        abort_lock=stats["abort_lock"],
                        abort_validate=stats["abort_validate"],
                        abort_overflow=stats["abort_overflow"],
                        abort_stale=stats["abort_stale"])
            tb = rec.buf
        return (state, meta, ptab, stale_out, done, trunc, commit_round,
                skeys, svals, smask, key, tb, lat), stats

    init = (
        state, meta,
        ptable if use_pl else jnp.zeros(()),
        jnp.zeros((), bool),
        jnp.zeros((N, B), bool),
        jnp.zeros((N, B), bool),
        jnp.full((N, B), -1, jnp.int32),
        jnp.zeros((N, B, S, LW), jnp.uint32),
        jnp.zeros((N, B, S, LW, sl.VALUE_WORDS), jnp.uint32),
        jnp.zeros((N, B, S, LW), bool),
        key,
        tb0,
        jnp.zeros((N, B), jnp.float32) if use_tel else jnp.zeros(()),
    )
    (state, meta, _, _, done, trunc, commit_round, skeys, svals, smask,
     _, tb, lat), ys = lax.scan(body, init, jnp.arange(max_rounds))

    metrics = jax.tree.map(lambda x: jnp.sum(x, axis=0), ys["metrics"])
    metrics = hy.HybridMetrics(metrics.onesided_success, metrics.rpc_fallback,
                               metrics.total, metrics.wire + init_wire)
    result = ScanLoopResult(
        committed=done & ~trunc,
        commit_round=commit_round,
        truncated=trunc,
        scan_keys=skeys, scan_values=svals, scan_mask=smask,
        round_committed=ys["committed"],
        round_attempts=ys["attempts"],
        round_retries=ys["retries"],
        round_abort_lock=ys["abort_lock"],
        round_abort_validate=ys["abort_validate"],
        round_abort_overflow=ys["abort_overflow"],
        round_abort_stale=ys["abort_stale"],
        metrics=metrics,
        round_trips=jnp.sum(ys["round_trips"]) + init_wire.round_trips,
    )
    if use_tel:
        return state, meta, result, T.TelemetryOut(trace=tb,
                                                   lane_latency_us=lat)
    return state, meta, result
