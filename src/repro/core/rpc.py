"""Write-based RPC (Storm §5.2).

Storm implements RPC with ``rdma_write_with_imm``: the request is WRITTEN into
a receive ring at the callee, a completion with an immediate header pops out
of ONE shared completion queue, the handler runs, and the reply is written
back the same way.  Our realization:

  * request records are written into per-owner INBOX buffers by an all-to-all
    (= the one-sided write of the request),
  * the cell coordinates (src, slot) play the role of the immediate header
    identifying sender and coroutine lane,
  * ONE fused validity mask per inbox = the single completion queue,
  * the owner runs the registered handler over its inbox, then replies are
    written back by the mirror all-to-all.

Handlers come in two flavours:
  * ``serial``  — mutating ops.  Records are folded sequentially through the
    node state (lax.scan), which gives genuine mutual-exclusion semantics for
    locks/inserts: the order of the scan is the serialization order.
  * ``vector``  — read-only ops (lookups): vectorized across the inbox.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

from repro.core import roundsched as rs
from repro.core.roundsched import serial_apply, vector_apply  # noqa: F401  (re-export)
from repro.core.transport import Transport, WireStats  # noqa: F401  (re-export)

# Opcodes and reply statuses live in core/wireproto.py — the single
# registration point for every data structure's wire contract.  They are
# re-exported here so the historical ``R.OP_*`` / ``R.ST_*`` spelling keeps
# working everywhere.
from repro.core.wireproto import (  # noqa: F401  (re-export)
    OP_ABORT_UNLOCK, OP_BACKUP_WRITE, OP_BT_ABORT, OP_BT_BACKUP, OP_BT_COMMIT,
    OP_BT_DELETE, OP_BT_INSERT, OP_BT_LOCK, OP_BT_LOOKUP, OP_BT_SCAN,
    OP_COMMIT_UNLOCK, OP_DELETE, OP_INSERT, OP_LOCK, OP_LOOKUP, OP_NOP,
    OP_PL_INSTALL, OP_READ_VERSION, OP_UPDATE, ST_BAD_OP, ST_DROPPED,
    ST_LOCK_FAIL, ST_NOT_FOUND, ST_NO_SPACE, ST_OK, ST_WRONG_EPOCH)


@dataclasses.dataclass(frozen=True)
class Handler:
    """A registered rpc_handler (Storm Table 3)."""
    fn: Callable            # see roundsched serial/vector signatures
    reply_words: int
    serial: bool = True


def rpc_call(t: Transport, state, dest, records, handler: Handler, *,
             capacity: Optional[int] = None, enabled=None, nic=None,
             telemetry=None, phase: int = 0):
    """Batched write-based RPC round (one round trip for B lanes/node) — a
    single-class fused round (see roundsched.fused_round).

    state:   pytree with leading node axis (N_local, ...)
    dest:    (N_local, B) int32
    records: (N_local, B, W) uint32 (word 0 must be the opcode)
    enabled: optional (N_local, B) bool — lanes that actually issue the RPC.
             Disabled lanes are parked by route_by_dest (no send-queue cell,
             no capacity consumed, no wire bytes).
    capacity: per-destination send-queue budget.  ``None`` means B (a full
             batch always fits); 0 is honoured as "deliver nothing" (every
             enabled lane back-pressured), negative values are rejected.

    Returns (state, replies (N_local, B, R), overflow (N_local, B), WireStats).
    Overflowed and parked lanes carry ST_DROPPED in reply word 0 so a lane
    that issued no request can never be mistaken for success — or for a
    handler-returned ST_NO_SPACE, which means the request WAS delivered but
    storage is full (not retryable).
    """
    state, ((out, ovf),), stats = rs.fused_round(
        t, state,
        [rs.rpc_class(dest, records, handler, enabled=enabled,
                      capacity=capacity)], nic=nic, telemetry=telemetry,
        phase=phase)
    return state, out, ovf, stats
