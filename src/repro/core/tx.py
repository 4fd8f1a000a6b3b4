"""Storm transactional protocol (§5.4, Fig. 3): OCC + 2PC optimized for the
dataplane's two primitives.

Per transaction lane:
  EXECUTE   read-set via one-two-sided hybrid lookups (reads buffered
            locally), write-set read-for-update + LOCK via write-based RPC
            (the paper locks intended writes during execution).
  VALIDATE  re-read read-set slot versions with ONE-SIDED reads (Storm keeps
            the remote offsets of every read-set object).
  COMMIT    write-based RPCs install values, bump versions to even, unlock.
  ABORT     unlock / roll back placeholder inserts for lanes whose locks
            failed or whose validation detected a concurrent writer.

Shapes are static: each lane has exactly R read keys and W write keys; lanes
are batched B per node ("coroutines").

Two schedules share every phase's records, handlers and decision logic:

  * ``run_transactions(fused=False)`` — the per-phase reference: FIVE
    exchange rounds (one-sided read, RPC fallback, lock, validate, commit),
    one phase per all-to-all, exactly Figure 3 drawn naively.
  * ``run_transactions(fused=True)`` (default) — the fused schedule built on
    roundsched.fused_round.  The read-set RPC fallback is independent of
    LOCK, and the validate re-read of every lane whose slot address the
    one-sided read already learned only needs to observe the post-lock
    state — so both ride the lock round:

        round 1  one-sided read of the read set
        round 2  fallback lookups ∥ LOCK ∥ validate(one-sided hits)
        round 3  validate(addresses learned via RPC)      [empty on the
                 one-sided fast path — costs no round trip]
        round 4  commit / abort

    i.e. **4 exchange rounds in the general case, 3 when every read-set
    lookup is satisfied one-sided** — versus 5 for the reference, with
    bit-identical committed state, abort causes and delivered-request counts
    (see tests/test_tx_fused_equivalence.py).

Aborts are classified by cause — lock conflict, validation conflict, or
overflow/back-pressure — which is what the retry loop (txloop.tx_loop) and
the contention benchmarks report.

With a ``rep=replication.ReplicaConfig(f > 0)``, COMMIT installs the write
set on all f+1 copies: the backup writes ride the commit fused round as
extra traffic classes (zero additional exchange rounds, wider commit
fan-out; see commit_or_abort).

Public API: ``run_transactions`` (single shot) + ``TxResult``, and the
per-phase functions ``execute_read_set`` / ``lock_write_set`` /
``validate_read_set`` / ``commit_or_abort`` the reference schedule is built
from.  Invariants: ``fused=True`` is round-count-only (committed state,
abort causes and WireStats.ops are bit-identical to ``fused=False``);
``rep=None`` and ``rep.f == 0`` are bit-identical to each other.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import hybrid as hy
from repro.core import onesided as osd
from repro.core import placement as pl
from repro.core import regions as rg
from repro.core import replication as repl
from repro.core import roundsched as rs
from repro.core import rpc as R
from repro.core import telemetry as T
from repro.core import wireproto as W
from repro.core import slots as sl
from repro.core.datastructs import btree as bt
from repro.core.datastructs import hashtable as ht
from repro.core.transport import Transport


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TxResult:
    committed: jnp.ndarray        # (N, B) bool
    read_found: jnp.ndarray       # (N, B, R) bool
    read_values: jnp.ndarray      # (N, B, R, VALUE_WORDS)
    locked_values: jnp.ndarray    # (N, B, W, VALUE_WORDS) read-for-update values
    aborted_lock: jnp.ndarray     # (N, B) bool — lost a lock race
    aborted_validate: jnp.ndarray  # (N, B) bool — read-set changed underfoot
    aborted_overflow: jnp.ndarray  # (N, B) bool — back-pressure / no space
    aborted_stale: jnp.ndarray    # (N, B) bool — routed by a stale placement
                                  # table (ST_WRONG_EPOCH): refresh + retry
    metrics: hy.HybridMetrics
    round_trips: jnp.ndarray      # scalar


# ---------------------------------------------------------------------------
# Shared request construction / reply parsing.  Both schedules build records
# and decode replies through these helpers, so they are equivalent by
# construction at the record level.
# ---------------------------------------------------------------------------
def _lock_requests(t: Transport, cfg: ht.HashTableConfig, layout, *,
                   write_keys, write_enabled, ptable=None):
    """Flatten the write set and build the OP_LOCK records (+ unique tags).

    With a ``ptable`` (placement.PlacementTable), lock-class ops route to the
    partition OWNER — never a backup, so a lane can never fake a grant at a
    replica: a dead owner parks the lane (dest -1 -> ST_DROPPED -> abort
    overflow) until repair promotes a backup.  The lane stays ENABLED —
    masking it instead would make the all-locks-held conjunction vacuously
    true and commit an unlocked write set."""
    N, B, Wr = write_keys.shape[:3]
    wk_lo = write_keys[..., 0].reshape(N, B * Wr)
    wk_hi = write_keys[..., 1].reshape(N, B * Wr)
    en = write_enabled.reshape(N, B * Wr)
    part = ht.part_of(cfg, wk_lo, wk_hi)
    if ptable is None:
        wnode, _, _ = ht.lookup_start(cfg, layout, wk_lo, wk_hi, None)
    else:
        wnode = pl.owner_dest(ptable, part)
    # unique nonzero lock tag per (node, lane)
    lane = jnp.arange(B * Wr, dtype=jnp.uint32) // jnp.uint32(max(Wr, 1))
    tag = (t.node_ids().astype(jnp.uint32)[:, None] * jnp.uint32(B)
           + lane[None, :] + jnp.uint32(1))
    recs = ht.make_record(W.OP_LOCK, wk_lo, wk_hi, aux=tag)
    return dict(key_lo=wk_lo, key_hi=wk_hi, enabled=en, node=wnode, tag=tag,
                part=part), recs


def _parse_lock_replies(lk, lrep, lovf, N, B, Wr):
    """Decode the LOCK round's replies into the lock context dict."""
    status = lrep[..., 0]
    en = lk["enabled"]
    lock_ok = (status == W.ST_OK) & ~lovf & en
    return dict(
        lk,
        lock_ok=lock_ok, lock_slot=lrep[..., 1],
        # version at lock time (even, also for lock-inserted placeholders) —
        # the committed version every copy will carry is (lock_ver | 1) + 1,
        # which is what the backup fan-out installs (replication module)
        lock_ver=lrep[..., 2],
        locked_values=lrep[..., 3:].reshape(N, B, Wr, sl.VALUE_WORDS),
        lock_fail=(status == W.ST_LOCK_FAIL) & en,
        # the routing table this lane used is stale: the addressed node no
        # longer owns the key's partition (abort cause stale_route — txloop
        # refreshes the table and retries)
        stale=(status == W.ST_WRONG_EPOCH) & en,
        # overflow-class outcomes: dropped by back-pressure (retryable) or
        # table full (ST_NO_SPACE, delivered) — both abort with cause overflow
        no_space=((status == W.ST_NO_SPACE) | (status == W.ST_DROPPED)
                  | lovf) & en,
        overflow=lovf & en)


def _validate_from_bytes(read_ctx, vbuf, vovf):
    """Shared VALIDATE decision: compare re-read slot bytes against the
    execute-phase observation.  Absent reads validate trivially
    (repeatable-read of a miss is NOT guaranteed — documented limitation,
    same as the paper's protocol sketch)."""
    cur_ver = vbuf[..., sl.VERSION]
    cur_klo = vbuf[..., sl.KEY_LO]
    cur_lock = vbuf[..., sl.LOCK]
    unchanged = ((cur_ver == read_ctx["versions"])
                 & (cur_klo == read_ctx["key_lo"]) & (cur_lock == 0) & ~vovf)
    issued = read_ctx["enabled"] & read_ctx["found"]
    return dict(valid=unchanged | ~read_ctx["found"], overflow=vovf & issued)


# ---------------------------------------------------------------------------
# Phase functions (the per-phase reference schedule).  Each takes/returns
# cluster state plus a plain dict of per-item arrays; lane axes are flattened
# to (N, B*K) like the wire sees them.
# ---------------------------------------------------------------------------
@jax.named_scope("storm.occ.read")
def execute_read_set(t: Transport, state, cfg: ht.HashTableConfig, layout, *,
                     read_keys, read_enabled, cache=None,
                     use_onesided: bool = True, capacity: Optional[int] = None,
                     nic=None, ptable=None, telemetry=None):
    """EXECUTE phase, read half: one-two-sided lookups of the read set.

    read_keys: (N, B, Rd, 2); read_enabled: (N, B, Rd) bool.
    Returns (state, cache, ctx) where ctx holds the flattened (N, B*Rd)
    found/values/versions/owner/slot arrays the later phases need.
    """
    N, B, Rd = read_keys.shape[:3]
    rk_lo = read_keys[..., 0].reshape(N, B * Rd)
    rk_hi = read_keys[..., 1].reshape(N, B * Rd)
    en = read_enabled.reshape(N, B * Rd)
    state, cache, found, rvals, rvers, rnode, rslot, rovf, m = hy.hybrid_lookup(
        t, state, rk_lo, rk_hi, cfg, layout, cache=cache,
        use_onesided=use_onesided, rpc_serial=False, capacity=capacity,
        enabled=en, nic=nic, ptable=ptable, telemetry=telemetry)
    return state, cache, dict(
        key_lo=rk_lo, key_hi=rk_hi, enabled=en, found=found, values=rvals,
        versions=rvers, node=rnode, slot=rslot, overflow=rovf, metrics=m)


@jax.named_scope("storm.occ.lock")
def lock_write_set(t: Transport, state, cfg: ht.HashTableConfig, layout,
                   serial_h, *, write_keys, write_enabled,
                   capacity: Optional[int] = None, nic=None, ptable=None,
                   telemetry=None):
    """EXECUTE phase, write half: LOCK + read-for-update the write set.

    write_keys: (N, B, Wr, 2); write_enabled: (N, B, Wr) bool.
    """
    N, B, Wr = write_keys.shape[:3]
    lk, lock_recs = _lock_requests(t, cfg, layout, write_keys=write_keys,
                                   write_enabled=write_enabled, ptable=ptable)
    state, lrep, lovf, s_lock = R.rpc_call(
        t, state, lk["node"], lock_recs, serial_h, capacity=capacity,
        enabled=lk["enabled"], nic=nic, telemetry=telemetry, phase=T.PH_LOCK)
    lctx = _parse_lock_replies(lk, lrep, lovf, N, B, Wr)
    lctx["wire"] = s_lock
    return state, lctx


@jax.named_scope("storm.occ.validate")
def validate_read_set(t: Transport, state, layout, read_ctx, *,
                      capacity: Optional[int] = None, nic=None,
                      offset_of=None, telemetry=None):
    """VALIDATE phase: one-sided re-read of every read-set slot version.

    ``offset_of(layout, slot_idx)`` maps a read-set slot index to its arena
    word offset (default: the hash table's ``slots`` region; the ordered
    index validates leaf HEADER slots in its ``leaves`` region instead).
    Returns a dict with per-item `valid` plus the overflow mask and wire
    stats."""
    # absent reads validate trivially, so only found reads are re-read — dead
    # validation reads would waste per-destination send-queue capacity and
    # could overflow a found lane's re-read for nothing
    issued = read_ctx["enabled"] & read_ctx["found"]
    if offset_of is None:
        offset_of = ht.slot_idx_offset
    voff = offset_of(layout, read_ctx["slot"])
    vbuf, vovf, s_val = osd.remote_read(
        t, state["arena"], read_ctx["node"], voff, length=sl.SLOT_WORDS,
        capacity=capacity, enabled=issued, nic=nic, telemetry=telemetry,
        phase=T.PH_VALIDATE)
    vctx = _validate_from_bytes(read_ctx, vbuf, vovf)
    vctx["wire"] = s_val
    return vctx


def _backup_dest(lock_ctx, rep, i, ptable):
    """Destination of backup copy ``i`` for each write item.

    Without a placement table this is the ring rotation off the LOCK
    destination (the pre-placement dataplane, bit-identical).  With one, the
    copy list comes from the table's row for the item's PARTITION — which is
    what keeps the commit fan-out correct after a migration or repair has
    re-homed the partition.  A dead or absent copy slot routes to -1: the
    transport parks the record, the lane aborts (cause overflow) and retries
    until repair re-points the copy — never a silent under-replication."""
    if ptable is None:
        return rep.replica_of(lock_ctx["node"], i)
    cand = pl.copy_nodes(ptable, lock_ctx["part"])[..., i]
    ok = (cand >= 0) & ptable.alive[
        jnp.clip(cand, 0, ptable.alive.shape[0] - 1)]
    return jnp.where(ok, cand, -1).astype(jnp.int32)


def commit_or_abort(t: Transport, state, serial_h, lock_ctx, *, commit_lane,
                    write_values, capacity: Optional[int] = None, nic=None,
                    rep=None, ptable=None, telemetry=None):
    """COMMIT / ABORT phase: lanes that hold locks either install their values
    (version += 2, unlock) or roll back.  commit_lane: (N, B) bool;
    write_values: anything reshapeable to (N, B*Wr, VALUE_WORDS).

    With replication (rep = replication.ReplicaConfig, f > 0), each of the f
    backup copies rides this SAME fused round as an extra OP_BACKUP_WRITE
    traffic class headed for replica_of(primary, i) — the commit round fans
    out wider (more (src, dst) pairs on the wire) but the schedule gains ZERO
    exchange rounds.  Aborting lanes release their locks and install nothing
    anywhere.

    The primary class cannot overflow: its enabled set (lock holders) is a
    subset of the lanes the lock round DELIVERED, to the same destinations in
    the same lane order at the same capacity, so every enabled lane's
    send-queue rank can only shrink.  That invariant is what guarantees an
    acquired lock is always released.  The ring-rotation backup classes
    inherit it — the rotation is a bijection on destinations, so no backup
    destination receives more records than some primary destination did — but
    a non-bijective placement (or a future placement change) CAN overflow, so
    every backup class's per-lane overflow mask (and any delivered-but-full
    ST_NO_SPACE reply) is folded into the abort classification: a dropped
    backup write aborts its lane (cause: overflow) for txloop to retry,
    never silently degrading the record to fewer than f+1 copies.

    Documented limitation of the single-round fan-out: the primary cannot
    observe its backups' outcome within the round, so a commit whose backup
    write failed has ALREADY installed the primary copy (lock released) when
    the lane reports aborted_overflow.  The retry reinstalls the same value
    idempotently and the lane converges to committed as soon as the backup
    accepts (tests/test_replication.py exercises the drain); only a
    PERMANENTLY full backup table leaves the lane reporting aborted with its
    primary copy visible — the capacity-exhaustion regime ST_NO_SPACE exists
    to signal, to be provisioned for exactly like the primary tables (whose
    exhaustion aborts cleanly at LOCK time)."""
    N, B = commit_lane.shape
    Wr = lock_ctx["key_lo"].shape[1] // max(B, 1)
    commit_item = jnp.repeat(commit_lane, Wr, axis=-1)  # (N, B*Wr)
    op = jnp.where(commit_item, jnp.uint32(W.OP_COMMIT_UNLOCK),
                   jnp.uint32(W.OP_ABORT_UNLOCK))
    # the key_lo word carries the lock tag: the owner releases a lock only
    # for the exact tag that acquired it (hashtable's unlock ownership check)
    cm_recs = ht.make_record(
        op, lock_ctx["tag"], lock_ctx["key_hi"], aux=lock_ctx["lock_slot"],
        value=write_values.reshape(N, B * Wr, sl.VALUE_WORDS))
    # only lanes that actually HOLD a lock must unlock/commit
    classes = [rs.rpc_class(lock_ctx["node"], cm_recs, serial_h,
                            enabled=lock_ctx["lock_ok"], capacity=capacity)]
    bk_en = None
    if rep is not None and rep.f > 0:
        bk_recs = repl.backup_write_records(lock_ctx, write_values)
        # only COMMITTING lock holders install backups (aborts touch nothing)
        bk_en = commit_item & lock_ctx["lock_ok"]
        for i in range(1, rep.f + 1):
            classes.append(rs.rpc_class(
                _backup_dest(lock_ctx, rep, i, ptable), bk_recs, serial_h,
                enabled=bk_en, capacity=capacity))
    # the primary class's replies are never read (its lanes hold their
    # locks); only backup writes report back
    state, results, s_cm = rs.fused_round(t, state, classes, nic=nic,
                                          telemetry=telemetry,
                                          phase=T.PH_COMMIT,
                                          want_replies=bk_en is not None)
    overflow = results[0][1] & lock_ctx["lock_ok"]
    for brep, bovf in results[1:]:
        overflow = overflow | ((bovf | (brep[..., 0] == W.ST_NO_SPACE))
                               & bk_en)
    return state, dict(overflow=overflow, wire=s_cm)


# ---------------------------------------------------------------------------
# Shared tail: commit decision, abort classification, result packing.
# ---------------------------------------------------------------------------
@jax.named_scope("storm.occ.commit")
def _decide_and_finish(t, state, serial_h, *, N, B, Rd, Wr, write_enabled,
                       write_values, rctx, lctx, vctx, read_wire,
                       onesided_success, rpc_fallback, total,
                       capacity, nic=None, rep=None, ptable=None,
                       telemetry=None):
    lane_locks_ok = jnp.all(
        (lctx["lock_ok"] | ~lctx["enabled"]).reshape(N, B, Wr), axis=-1)
    lane_valid = jnp.all(
        (vctx["valid"] | ~rctx["enabled"]).reshape(N, B, Rd), axis=-1)
    # a read dropped by back-pressure is NOT a miss: the lane must abort
    # (cause: overflow) and retry, never commit against an unread read set
    lane_reads_ok = ~jnp.any(rctx["overflow"].reshape(N, B, Rd), axis=-1)

    # ---------------- COMMIT / ABORT (write-based RPCs) --------------------
    commit_lane = lane_locks_ok & lane_valid & lane_reads_ok    # (N, B)
    state, cctx = commit_or_abort(
        t, state, serial_h, lctx, commit_lane=commit_lane,
        write_values=write_values, capacity=capacity, nic=nic, rep=rep,
        ptable=ptable, telemetry=telemetry)

    has_writes = jnp.any(write_enabled, axis=-1)
    # commit RPCs provably never overflow (see commit_or_abort); the gate is
    # defense in depth so a lost commit could never masquerade as success
    commit_delivered = ~jnp.any(cctx["overflow"].reshape(N, B, Wr), axis=-1)
    committed = jnp.where(has_writes, commit_lane & commit_delivered,
                          lane_valid & lane_reads_ok)

    # -------- abort causes (priority: overflow > stale > lock > validate) --
    lane_ovf = (~lane_reads_ok
                | jnp.any(lctx["no_space"].reshape(N, B, Wr), axis=-1)
                | jnp.any(vctx["overflow"].reshape(N, B, Rd), axis=-1)
                | jnp.any(cctx["overflow"].reshape(N, B, Wr), axis=-1))
    lane_stale = jnp.any(lctx["stale"].reshape(N, B, Wr), axis=-1)
    lane_lock_fail = jnp.any(lctx["lock_fail"].reshape(N, B, Wr), axis=-1)
    aborted = ~committed
    aborted_overflow = aborted & lane_ovf
    aborted_stale = aborted & ~lane_ovf & lane_stale
    aborted_lock = aborted & ~lane_ovf & ~lane_stale & lane_lock_fail
    aborted_validate = (aborted & ~lane_ovf & ~lane_stale & ~lane_lock_fail
                        & ~lane_valid)

    wire = read_wire + lctx["wire"] + vctx["wire"] + cctx["wire"]
    metrics = hy.HybridMetrics(
        onesided_success=onesided_success,
        rpc_fallback=rpc_fallback,
        total=total,
        wire=wire,
    )
    rts = (read_wire.round_trips + lctx["wire"].round_trips
           + vctx["wire"].round_trips + cctx["wire"].round_trips)
    return state, TxResult(
        committed=committed,
        read_found=rctx["found"].reshape(N, B, Rd),
        read_values=rctx["values"].reshape(N, B, Rd, sl.VALUE_WORDS),
        locked_values=lctx["locked_values"],
        aborted_lock=aborted_lock,
        aborted_validate=aborted_validate,
        aborted_overflow=aborted_overflow,
        aborted_stale=aborted_stale,
        metrics=metrics,
        round_trips=rts,
    )


# ---------------------------------------------------------------------------
# The fused schedule (roundsched.fused_round): 3-4 exchange rounds.
# ---------------------------------------------------------------------------
def _run_transactions_fused(t: Transport, state, cfg, layout, *, read_keys,
                            write_keys, write_values, write_enabled,
                            read_enabled, cache, use_onesided, capacity,
                            nic=None, rep=None, ptable=None, telemetry=None):
    N, B, Rd = read_keys.shape[:3]
    Wr = write_keys.shape[2]
    serial_h = ht.make_rpc_handler(cfg, layout)

    # ---- round 1: one-sided read of the read set --------------------------
    with jax.named_scope("storm.occ.read"):
        rk_lo = read_keys[..., 0].reshape(N, B * Rd)
        rk_hi = read_keys[..., 1].reshape(N, B * Rd)
        ren = read_enabled.reshape(N, B * Rd)
        probe = hy.onesided_probe(t, state, rk_lo, rk_hi, cfg, layout,
                                  cache=cache, use_onesided=use_onesided,
                                  capacity=capacity, enabled=ren, nic=nic,
                                  ptable=ptable, telemetry=telemetry)
        counts = dict(
            onesided_success=jnp.sum(probe["success"].astype(jnp.float32)),
            rpc_fallback=jnp.sum(probe["need_rpc"].astype(jnp.float32)),
            total=jnp.sum(ren.astype(jnp.float32)))

    # ---- round 2: read-set RPC fallback ∥ LOCK ∥ validate(one-sided hits) -
    # The fallback is independent of LOCK (different key sets, the lookup is
    # read-only and observes the round's pre-handler state); the validate
    # re-read of a lane whose slot address round 1 already learned only needs
    # to observe the post-lock state, which the fused round's gather-last
    # ordering provides.  Under an explicit capacity bound the validate phase
    # keeps its own round instead, so its send-queue back-pressure policy
    # stays bit-identical to the reference's single validate round.
    fuse_v1 = capacity is None and Rd > 0
    with jax.named_scope("storm.occ.lock"):
        lk, lock_recs = _lock_requests(t, cfg, layout, write_keys=write_keys,
                                       write_enabled=write_enabled,
                                       ptable=ptable)
        lookup_recs = ht.make_record(W.OP_LOOKUP, rk_lo, rk_hi)
        vector_h = ht.make_lookup_handler_vector(cfg, layout)
        classes = [
            rs.rpc_class(probe["node"], lookup_recs, vector_h,
                         enabled=probe["need_rpc"], capacity=capacity),
            rs.rpc_class(lk["node"], lock_recs, serial_h,
                         enabled=lk["enabled"], capacity=capacity),
        ]
        if fuse_v1:
            classes.append(rs.read_class(
                probe["node"], ht.slot_idx_offset(layout, probe["slot_idx"]),
                length=sl.SLOT_WORDS, enabled=ren & probe["success"]))
        state, results, s2 = rs.fused_round(t, state, classes, nic=nic,
                                            telemetry=telemetry,
                                            phase=T.PH_LOCK)
        lookup_rep, lookup_ovf = results[0]
        lrep, lovf = results[1]

        lctx = _parse_lock_replies(lk, lrep, lovf, N, B, Wr)
        mg = hy.merge_rpc_fallback(probe, lookup_rep, lookup_ovf)
        cache = hy.update_lookup_cache(cfg, cache, rk_lo, rk_hi,
                                       probe["node"], mg["slot_idx"],
                                       mg["found"])
        rctx = dict(key_lo=rk_lo, key_hi=rk_hi, enabled=ren,
                    found=mg["found"], values=mg["value"],
                    versions=mg["version"], node=probe["node"],
                    slot=mg["slot_idx"], overflow=mg["overflow"])

    # ---- round 3: validate re-reads whose address came from the RPC -------
    # (empty — and therefore free of wire cost — on the one-sided fast path)
    if fuse_v1:
        with jax.named_scope("storm.occ.validate"):
            v1buf = results[2][0]
            v2buf, _, s3 = osd.remote_read(
                t, state["arena"], probe["node"],
                ht.slot_idx_offset(layout, mg["slot_idx"]),
                length=sl.SLOT_WORDS, enabled=ren & mg["rpc_ok"], nic=nic,
                telemetry=telemetry, phase=T.PH_VALIDATE)
            vbuf = jnp.where(probe["success"][..., None], v1buf, v2buf)
            # without a capacity bound neither validate sub-round can
            # overflow
            vctx = _validate_from_bytes(rctx, vbuf,
                                        jnp.zeros((N, B * Rd), bool))
        vctx["wire"] = s3
    else:
        vctx = validate_read_set(t, state, layout, rctx, capacity=capacity,
                                 nic=nic, telemetry=telemetry)

    # the lock round's wire is fused into s2; attribute the whole fused round
    # to the lock slot of the accounting so totals stay exact
    lctx["wire"] = s2

    state, res = _decide_and_finish(
        t, state, serial_h, N=N, B=B, Rd=Rd, Wr=Wr,
        write_enabled=write_enabled, write_values=write_values,
        rctx=rctx, lctx=lctx, vctx=vctx, read_wire=probe["wire"], **counts,
        capacity=capacity, nic=nic, rep=rep, ptable=ptable,
        telemetry=telemetry)
    return state, cache, res


def run_transactions(t: Transport, state, cfg: ht.HashTableConfig, layout, *,
                     read_keys, write_keys, write_values, write_enabled=None,
                     read_enabled=None, cache=None, use_onesided: bool = True,
                     capacity: Optional[int] = None, fused: bool = True,
                     nic=None, rep=None, ptable=None, telemetry=None):
    """Execute a batch of transactions, one per lane (single shot — aborted
    lanes report their cause and stop; see txloop.tx_loop for bounded retry).

    read_keys:    (N, B, Rd, 2) uint32 (lo, hi)
    write_keys:   (N, B, Wr, 2) uint32
    write_values: (N, B, Wr, VALUE_WORDS) uint32
    *_enabled:    optional masks (N, B, Rd/Wr) for ragged sets.
    fused:        True (default) runs the fused 3-4-round schedule;
                  False runs the per-phase 5-round reference.  Both produce
                  identical committed state, abort causes and delivered
                  request counts — the fused schedule just puts fewer
                  exchanges on the wire.
    nic:          optional repro.core.nic.ConnTable describing the connection
                  mode / emulated cluster scale; every round's WireStats then
                  carries the modeled NIC-cache hit rate and per-op
                  connection-state penalty (protocol results are unaffected).
    rep:          optional repro.core.replication.ReplicaConfig.  With f > 0,
                  COMMIT installs the write set on all f+1 copies — the f
                  backup writes ride the commit fused round as extra traffic
                  classes (zero additional exchange rounds; only the commit
                  round's (src, dst) fan-out widens).  rep=None and f=0 are
                  bit-identical to the unreplicated dataplane.
    ptable:       optional repro.core.placement.PlacementTable — ALL routing
                  (read probes, lock-class ops, commit backup fan-out) goes
                  through the epoch-stamped table instead of static
                  home/ring math.  Reads go to the first LIVE copy,
                  lock-class ops to the OWNER only; a stale table surfaces
                  as ``aborted_stale`` (owner-side ST_WRONG_EPOCH) for
                  txloop to refresh-and-retry.  The identity table with all
                  nodes up is bit-identical to ptable=None.

    Read/write sets are assumed disjoint per lane (read-for-update goes in the
    write set — its LOCK reply returns the current value, Fig. 3).
    """
    N, B, Rd = read_keys.shape[:3]
    Wr = write_keys.shape[2]
    if read_enabled is None:
        read_enabled = jnp.ones((N, B, Rd), bool)
    if write_enabled is None:
        write_enabled = jnp.ones((N, B, Wr), bool)

    if fused:
        return _run_transactions_fused(
            t, state, cfg, layout, read_keys=read_keys, write_keys=write_keys,
            write_values=write_values, write_enabled=write_enabled,
            read_enabled=read_enabled, cache=cache, use_onesided=use_onesided,
            capacity=capacity, nic=nic, rep=rep, ptable=ptable,
            telemetry=telemetry)

    serial_h = ht.make_rpc_handler(cfg, layout)

    # ---------------- EXECUTE: read set (hybrid one-two-sided) -------------
    state, cache, rctx = execute_read_set(
        t, state, cfg, layout, read_keys=read_keys, read_enabled=read_enabled,
        cache=cache, use_onesided=use_onesided, capacity=capacity, nic=nic,
        ptable=ptable, telemetry=telemetry)
    m = rctx["metrics"]

    # ---------------- EXECUTE: lock + read-for-update the write set --------
    state, lctx = lock_write_set(
        t, state, cfg, layout, serial_h, write_keys=write_keys,
        write_enabled=write_enabled, capacity=capacity, nic=nic,
        ptable=ptable, telemetry=telemetry)

    # ---------------- VALIDATE: one-sided re-read of read-set versions -----
    vctx = validate_read_set(t, state, layout, rctx, capacity=capacity,
                             nic=nic, telemetry=telemetry)

    state, res = _decide_and_finish(
        t, state, serial_h, N=N, B=B, Rd=Rd, Wr=Wr,
        write_enabled=write_enabled, write_values=write_values,
        rctx=rctx, lctx=lctx, vctx=vctx, read_wire=m.wire,
        onesided_success=m.onesided_success, rpc_fallback=m.rpc_fallback,
        total=m.total, capacity=capacity, nic=nic, rep=rep, ptable=ptable,
        telemetry=telemetry)
    return state, cache, res


# ===========================================================================
# Transactional RANGE SCANS over the ordered index (datastructs.btree).
#
# A scan transaction's READ SET is a run of B-link LEAVES: the client plans
# the (node, leaf) sequence covering [lo, hi] from its cached separator
# directory, reads each leaf with ONE one-sided read, and OCC-validates the
# leaf HEADER versions exactly like point transactions validate record slots
# (every record or structural change bumps the leaf version, so a validated
# scan is serializable at its validation point).  Writes lock whole leaves
# (OP_BT_LOCK pre-splits full leaves so OP_BT_COMMIT always has room).
#
# Two schedules, same phase records/handlers/decisions (mirroring
# run_transactions):
#
#   * fused=False — the 5-round reference: leaf reads, scan-RPC fallback,
#     LOCK, validate, COMMIT — one phase per all-to-all.
#   * fused=True (default) — the fallback rides the LOCK round and the
#     validate re-read of every leaf the one-sided read already resolved
#     rides it too (gathers observe the post-lock state):
#
#         round 1  one-sided reads of the planned leaves
#         round 2  scan fallback ∥ LOCK ∥ validate(one-sided-resolved)
#         round 3  validate(RPC-resolved leaves)   [empty on the fast path]
#         round 4  COMMIT / ABORT (+ OP_BT_BACKUP fan-out at rep.f > 0)
#
#     i.e. the fast-path scan costs EXACTLY the point-lookup schedule's
#     exchange rounds: 2 for a pure scan, 3 with writes — zero extra rounds
#     (asserted by benchmarks/range_scan.py and the bench gate).
#
# Stale separators (a leaf split since the last refresh) surface as a GAP in
# the fence chain: the lane aborts with cause `validate` and the retry loop
# (txloop.scan_loop) refreshes the directory — the round-trip analogue of
# chasing the B-link right-pointer.  `truncated` lanes (range needs more
# than cfg.max_scan_leaves leaves) are reported, parked, and never silently
# clipped.  Invariants mirror run_transactions: fused=True is
# round-count-only; rep=None ≡ rep.f == 0 bit-identical.
# ===========================================================================
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ScanTxResult:
    committed: jnp.ndarray        # (N, B) bool
    scan_keys: jnp.ndarray        # (N, B, S, leaf_width) uint32
    scan_values: jnp.ndarray      # (N, B, S, leaf_width, VALUE_WORDS)
    scan_mask: jnp.ndarray        # (N, B, S, leaf_width) bool — in [lo, hi]
    scan_complete: jnp.ndarray    # (N, B) bool — fence chain covered [lo, hi]
    truncated: jnp.ndarray        # (N, B) bool — range needs > S leaves
    locked_values: jnp.ndarray    # (N, B, Wr, VALUE_WORDS)
    aborted_lock: jnp.ndarray     # (N, B) bool
    aborted_validate: jnp.ndarray
    aborted_overflow: jnp.ndarray
    aborted_stale: jnp.ndarray    # (N, B) bool — stale placement table
    metrics: hy.HybridMetrics
    round_trips: jnp.ndarray      # scalar


def _bt_lock_requests(t: Transport, cfg: bt.BTreeConfig, *, write_keys,
                      write_enabled, ptable=None):
    """Flatten the btree write set and build OP_BT_LOCK records (leaf-grain
    locks; unique nonzero tag per (node, lane) like the hash-table path).
    With a ``ptable``, lock-class ops route to the partition OWNER only
    (see _lock_requests — same dead-owner parking, same stale-epoch
    rejection owner-side)."""
    N, B, Wr = write_keys.shape
    wk = write_keys.reshape(N, B * Wr)
    en = write_enabled.reshape(N, B * Wr)
    part = bt.part_of(cfg, wk)
    wnode = part if ptable is None else pl.owner_dest(ptable, part)
    lane = jnp.arange(B * Wr, dtype=jnp.uint32) // jnp.uint32(max(Wr, 1))
    tag = (t.node_ids().astype(jnp.uint32)[:, None] * jnp.uint32(B)
           + lane[None, :] + jnp.uint32(1))
    recs = bt.make_record(W.OP_BT_LOCK, wk, jnp.zeros_like(wk), aux=tag)
    return dict(key_lo=wk, key_hi=jnp.zeros_like(wk), enabled=en, node=wnode,
                tag=tag, part=part), recs


def _bt_leaf_offset_of(layout, slot_idx):
    """Validation-offset hook: btree read-set entries are header slots in
    the `leaves` region."""
    return rg.slot_offset(layout["leaves"], slot_idx)


def _bt_commit_or_abort(t: Transport, state, serial_h, lock_ctx, *,
                        commit_lane, write_values,
                        capacity: Optional[int] = None, nic=None, rep=None,
                        ptable=None, telemetry=None):
    """COMMIT/ABORT for btree write sets.  Record layout: key in key_lo, the
    lock TAG in the (otherwise unused) key_hi word, the locked leaf's header
    slot in aux — the owner verifies the exact tag and installs the upsert
    (never splitting: OP_BT_LOCK pre-split, and the lock froze the leaf).

    With rep.f > 0, OP_BT_BACKUP classes ride this SAME fused round (zero
    extra exchange rounds — the PR-4 backup fan-out, logically replicated for
    the ordered index).  A backup write that is dropped, finds the backup
    leaf arena full (ST_NO_SPACE) or the backup leaf locked (ST_LOCK_FAIL)
    aborts its lane with cause overflow for the loop to retry — never a
    silent under-replication."""
    N, B = commit_lane.shape
    Wr = lock_ctx["key_lo"].shape[1] // max(B, 1)
    commit_item = jnp.repeat(commit_lane, Wr, axis=-1)
    op = jnp.where(commit_item, jnp.uint32(W.OP_BT_COMMIT),
                   jnp.uint32(W.OP_BT_ABORT))
    cm_recs = bt.make_record(
        op, lock_ctx["key_lo"], lock_ctx["tag"], aux=lock_ctx["lock_slot"],
        value=write_values.reshape(N, B * Wr, sl.VALUE_WORDS))
    classes = [rs.rpc_class(lock_ctx["node"], cm_recs, serial_h,
                            enabled=lock_ctx["lock_ok"], capacity=capacity)]
    bk_en = None
    if rep is not None and rep.f > 0:
        bk_recs = repl.btree_backup_records(lock_ctx, write_values)
        bk_en = commit_item & lock_ctx["lock_ok"]
        for i in range(1, rep.f + 1):
            classes.append(rs.rpc_class(
                _backup_dest(lock_ctx, rep, i, ptable), bk_recs, serial_h,
                enabled=bk_en, capacity=capacity))
    # the primary class's replies are never read (its lanes hold their
    # locks); only backup writes report back
    state, results, s_cm = rs.fused_round(t, state, classes, nic=nic,
                                          telemetry=telemetry,
                                          phase=T.PH_COMMIT,
                                          want_replies=bk_en is not None)
    overflow = results[0][1] & lock_ctx["lock_ok"]
    for brep, bovf in results[1:]:
        bst = brep[..., 0]
        overflow = overflow | ((bovf | (bst == W.ST_NO_SPACE)
                                | (bst == W.ST_LOCK_FAIL)) & bk_en)
    return state, dict(overflow=overflow, wire=s_cm)


def _scan_chain(cfg: bt.BTreeConfig, fence_lo, fence_hi, lo, hi, en,
                resolved):
    """Client-side coverage check over the merged leaf run (all (N, B, S)).

    complete  — every enabled position resolved, fences contiguous
                (fence_lo[j] == fence_hi[j-1] + 1), the first leaf covers lo
                and some leaf reaches hi: the union of validated leaves IS
                [lo, hi] with no gap a concurrent split could hide a key in.
    truncated — the chain is sound but exhausts all S positions before
                reaching hi: the range genuinely needs > max_scan_leaves
                leaves (reported, never silently clipped)."""
    all_resolved = jnp.all(resolved | ~en, axis=-1)
    first_ok = fence_lo[..., 0] <= lo
    cont = fence_lo[..., 1:] == fence_hi[..., :-1] + 1
    cont_ok = jnp.all(cont | ~en[..., 1:], axis=-1)
    reach = jnp.any(en & (fence_hi >= hi[..., None]), axis=-1)
    has_scan = jnp.any(en, axis=-1)
    sound = all_resolved & first_ok & cont_ok
    complete = ~has_scan | (sound & reach)
    truncated = has_scan & en[..., -1] & sound & ~reach
    return complete, truncated


def run_scan_transactions(t: Transport, state, cfg: bt.BTreeConfig, layout, *,
                          scan_lo, scan_hi, meta, write_keys=None,
                          write_values=None, write_enabled=None,
                          scan_enabled=None, capacity: Optional[int] = None,
                          fused: bool = True, nic=None, rep=None,
                          ptable=None, telemetry=None):
    """Execute a batch of range-scan transactions over the ordered index,
    one per lane (single shot; see txloop.scan_loop for bounded retry).

    scan_lo/hi:   (N, B) uint32 INCLUSIVE key ranges (lo > hi scans nothing —
                  a pure-write lane).
    meta:         cached separator directory ({"sep", "nleaf"} from
                  btree.refresh_meta / local_meta) — the client-side inner
                  nodes every plan walks locally.
    write_keys:   (N, B, Wr) uint32 btree keys upserted on commit (None = no
                  writes); write_values (N, B, Wr, VALUE_WORDS).
    Limitations (btree module docstring): a lane's write keys must land on
    distinct leaves, and a lane must not write into leaves its own scan
    reads (leaf-grain self-conflict aborts forever).

    Returns (state, ScanTxResult).  fused/nic/rep/capacity as in
    run_transactions — fused changes ROUND COUNTS only, rep=None ≡ f=0.
    ptable routes the LOCK phase and commit backup fan-out through the
    placement table (scan reads stay a primary-tree protocol planned from
    ``meta``; stale routes abort ``aborted_stale`` for scan_loop to refresh
    both the table AND the separator directory)."""
    N, B = scan_lo.shape
    S = cfg.max_scan_leaves
    if write_keys is None:
        write_keys = jnp.zeros((N, B, 0), jnp.uint32)
        write_values = jnp.zeros((N, B, 0, sl.VALUE_WORDS), jnp.uint32)
    Wr = write_keys.shape[2]
    if write_enabled is None:
        write_enabled = jnp.ones((N, B, Wr), bool)
    if scan_enabled is None:
        scan_enabled = jnp.ones((N, B), bool)
    serial_h = bt.make_rpc_handler(cfg, layout)
    scan_h = bt.make_scan_handler_vector(cfg, layout)

    # ---- round 1: one-sided reads of the planned leaves -------------------
    with jax.named_scope("storm.occ.read"):
        # client-side plan from the cached inner nodes (meta has a leading
        # client axis; each node plans its own lanes)
        plan = jax.vmap(
            lambda sep, nl, lo, hi: bt.scan_plan(cfg, sep, nl, lo, hi)
        )(meta["sep"], meta["nleaf"], scan_lo, scan_hi)
        en = plan["enabled"] & scan_enabled[..., None]          # (N, B, S)
        en_f = en.reshape(N, B * S)
        dest = plan["node"].reshape(N, B * S)
        pleaf = plan["leaf"].reshape(N, B * S)
        pfence = plan["fence"].reshape(N, B * S)
        buf, ovf1, s1 = osd.remote_read(
            t, state["arena"], dest, bt.leaf_offset(cfg, layout, pleaf),
            length=cfg.leaf_words, capacity=capacity, enabled=en_f, nic=nic,
            telemetry=telemetry, phase=T.PH_READ)
        p1 = bt.parse_leaf(cfg, buf)
        # a position is resolved one-sided iff the image is stable and its
        # immutable low fence matches the plan (stale separators can only
        # MISS leaves, never mis-assign fences)
        pos_ok = (en_f & ~ovf1 & (p1["version"] % 2 == 0) & (p1["lock"] == 0)
                  & (p1["fence_lo"] == pfence))
        need = en_f & ~pos_ok

    fuse_v1 = fused and capacity is None and S > 0
    with jax.named_scope("storm.occ.lock"):
        scan_recs = bt.make_record(W.OP_BT_SCAN, pfence,
                                   jnp.zeros_like(pfence))
        lk, lock_recs = _bt_lock_requests(t, cfg, write_keys=write_keys,
                                          write_enabled=write_enabled,
                                          ptable=ptable)
        if fused:
            # ---- round 2: scan fallback ∥ LOCK ∥ validate(one-sided) -----
            classes = [
                rs.rpc_class(dest, scan_recs, scan_h, enabled=need,
                             capacity=capacity),
                rs.rpc_class(lk["node"], lock_recs, serial_h,
                             enabled=lk["enabled"], capacity=capacity),
            ]
            if fuse_v1:
                classes.append(rs.read_class(
                    dest,
                    _bt_leaf_offset_of(layout, bt.header_slot(cfg, pleaf)),
                    length=sl.SLOT_WORDS, enabled=pos_ok))
            state, results, s2 = rs.fused_round(t, state, classes, nic=nic,
                                                telemetry=telemetry,
                                                phase=T.PH_LOCK)
            scan_rep, scan_ovf = results[0]
            lrep, lovf = results[1]
            s_fallback = None
        else:
            # ---- reference rounds 2 and 3: fallback, then LOCK ------------
            state, scan_rep, scan_ovf, s_fallback = R.rpc_call(
                t, state, dest, scan_recs, scan_h, capacity=capacity,
                enabled=need, nic=nic, telemetry=telemetry,
                phase=T.PH_FALLBACK)
            state, lrep, lovf, s2 = R.rpc_call(
                t, state, lk["node"], lock_recs, serial_h, capacity=capacity,
                enabled=lk["enabled"], nic=nic, telemetry=telemetry,
                phase=T.PH_LOCK)
        lctx = _parse_lock_replies(lk, lrep, lovf, N, B, Wr)

        # merge the authoritative fallback leaf images over the one-sided
        # reads
        rpc_ok = need & (scan_rep[..., 0] == W.ST_OK) & ~scan_ovf
        mbuf = jnp.where(rpc_ok[..., None], scan_rep[..., 2:], buf)
        mslot = jnp.where(rpc_ok, scan_rep[..., 1],
                          bt.header_slot(cfg, pleaf))
        p = bt.parse_leaf(cfg, mbuf)
        resolved = pos_ok | rpc_ok
        rctx = dict(key_lo=p["fence_lo"],
                    key_hi=jnp.zeros_like(p["fence_lo"]), enabled=en_f,
                    found=resolved, versions=p["version"], node=dest,
                    slot=mslot, overflow=need & scan_ovf)

    # ---- validate the leaf read set (headers) -----------------------------
    if fuse_v1:
        with jax.named_scope("storm.occ.validate"):
            v1 = results[2][0]
            v2, _, s3 = osd.remote_read(
                t, state["arena"], dest, _bt_leaf_offset_of(layout, mslot),
                length=sl.SLOT_WORDS, enabled=rpc_ok, nic=nic,
                telemetry=telemetry, phase=T.PH_VALIDATE)
            vbuf = jnp.where(pos_ok[..., None], v1, v2)
            vctx = _validate_from_bytes(rctx, vbuf,
                                        jnp.zeros((N, B * S), bool))
        vctx["wire"] = s3
    else:
        vctx = validate_read_set(t, state, layout, rctx, capacity=capacity,
                                 nic=nic, offset_of=_bt_leaf_offset_of,
                                 telemetry=telemetry)
    read_wire = s1 if s_fallback is None else s1 + s_fallback
    lctx["wire"] = s2

    # ---- decide, commit / abort, classify ---------------------------------
    with jax.named_scope("storm.occ.commit"):
        complete, truncated = _scan_chain(
            cfg, p["fence_lo"].reshape(N, B, S),
            p["fence_hi"].reshape(N, B, S), scan_lo, scan_hi, en,
            resolved.reshape(N, B, S))
        lane_locks_ok = jnp.all(
            (lctx["lock_ok"] | ~lctx["enabled"]).reshape(N, B, Wr), axis=-1)
        lane_valid = jnp.all(
            (vctx["valid"] | ~en_f).reshape(N, B, S), axis=-1) & complete
        lane_reads_ok = ~jnp.any(
            (rctx["overflow"] | vctx["overflow"]).reshape(N, B, S), axis=-1)

        commit_lane = lane_locks_ok & lane_valid & lane_reads_ok
        state, cctx = _bt_commit_or_abort(
            t, state, serial_h, lctx, commit_lane=commit_lane,
            write_values=write_values, capacity=capacity, nic=nic, rep=rep,
            ptable=ptable, telemetry=telemetry)

        has_writes = jnp.any(write_enabled, axis=-1)
        commit_delivered = ~jnp.any(cctx["overflow"].reshape(N, B, Wr),
                                    axis=-1)
        committed = jnp.where(has_writes, commit_lane & commit_delivered,
                              lane_valid & lane_reads_ok)

        lane_ovf = (~lane_reads_ok
                    | jnp.any(lctx["no_space"].reshape(N, B, Wr), axis=-1)
                    | jnp.any(cctx["overflow"].reshape(N, B, Wr), axis=-1))
        lane_stale = jnp.any(lctx["stale"].reshape(N, B, Wr), axis=-1)
        lane_lock_fail = jnp.any(lctx["lock_fail"].reshape(N, B, Wr),
                                 axis=-1)
        aborted = ~committed
        aborted_overflow = aborted & lane_ovf
        aborted_stale = aborted & ~lane_ovf & lane_stale
        aborted_lock = aborted & ~lane_ovf & ~lane_stale & lane_lock_fail
        aborted_validate = (aborted & ~lane_ovf & ~lane_stale
                            & ~lane_lock_fail & ~lane_valid)

        # ---- scan payload: records of validated leaves inside [lo, hi] ----
        keys = p["keys"].reshape(N, B, S, cfg.leaf_width)
        values = p["values"].reshape(N, B, S, cfg.leaf_width, sl.VALUE_WORDS)
        live = p["live"].reshape(N, B, S, cfg.leaf_width)
        in_range = (live & (keys >= scan_lo[..., None, None])
                    & (keys <= scan_hi[..., None, None])
                    & (resolved.reshape(N, B, S) & en)[..., None])

        wire = read_wire + lctx["wire"] + vctx["wire"] + cctx["wire"]
        metrics = hy.HybridMetrics(
            onesided_success=jnp.sum(pos_ok.astype(jnp.float32)),
            rpc_fallback=jnp.sum(need.astype(jnp.float32)),
            total=jnp.sum(en_f.astype(jnp.float32)),
            wire=wire)
        rts = (read_wire.round_trips + lctx["wire"].round_trips
               + vctx["wire"].round_trips + cctx["wire"].round_trips)
    return state, ScanTxResult(
        committed=committed,
        scan_keys=keys, scan_values=values, scan_mask=in_range,
        scan_complete=complete, truncated=truncated,
        locked_values=lctx["locked_values"],
        aborted_lock=aborted_lock, aborted_validate=aborted_validate,
        aborted_overflow=aborted_overflow, aborted_stale=aborted_stale,
        metrics=metrics, round_trips=rts)
