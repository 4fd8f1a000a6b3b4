"""One-sided remote reads and writes (Storm §4.2, §5.1).

The defining property of a one-sided op is that the OWNER RUNS NO APPLICATION
LOGIC: the initiator names (node, offset, length) and the owner side is pure
data movement.  Here the owner-side computation is exactly an address
translation (flat or paged) plus a gather/scatter — the work an RDMA NIC does
in hardware — and nothing else.  Contrast with rpc.py, where the owner runs a
registered handler (pointer chasing, lock logic, ...).

All ops are batched: each node issues B lanes per round (the coroutine
pipeline).  One round = ONE network round trip for every lane in flight.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import regions as rg
from repro.core import roundsched as rs
from repro.core import telemetry as T
from repro.core.transport import Transport, route_by_dest, wire_for


def remote_read(t: Transport, arenas, dest, offsets, *, length: int,
                capacity: Optional[int] = None,
                mode: rg.AddressMode | None = None, page_tables=None,
                enabled=None, nic=None, telemetry=None, phase: int = 0):
    """Batched one-sided READ — a single-class fused round (see
    roundsched.fused_round; the owner side is translation + gather ONLY).

    arenas:  (N_local, arena_words) uint32 — this shard's node states
    dest:    (N_local, B) int32  — target node of each lane
    offsets: (N_local, B) uint32 — word offset inside the target arena
    length:  static words per read (e.g. a 128B slot = 32 words)
    enabled: optional (N_local, B) bool — disabled lanes issue nothing and
             read back zeros (no capacity, no wire bytes).
    capacity: per-destination budget; ``None`` means B, 0 back-pressures
             every lane, negative values are rejected.

    Returns (data (N_local, B, length), overflow (N_local, B) bool, WireStats).
    """
    _, ((out, ovf),), stats = rs.fused_round(
        t, {"arena": arenas},
        [rs.read_class(dest, offsets, length=length, enabled=enabled,
                       capacity=capacity, mode=mode, page_tables=page_tables)],
        nic=nic, telemetry=telemetry, phase=phase)
    return out, ovf, stats


def remote_write(t: Transport, arenas, dest, offsets, values, *,
                 capacity: Optional[int] = None,
                 mode: rg.AddressMode | None = None, page_tables=None,
                 enabled=None, nic=None):
    """Batched one-sided WRITE (no reply payload — transport-level ack only).

    values: (N_local, B, L) uint32; enabled: optional (N_local, B) bool.
    Returns (new_arenas, overflow, WireStats).  Its own exchange runs under
    ``storm.round.other``, its parts under fused_round's part scopes (the
    owner scatter under ``storm.gather``).
    """
    with jax.named_scope(rs.round_scope(T.PH_OTHER)):
        return _remote_write(t, arenas, dest, offsets, values,
                             capacity=capacity, mode=mode,
                             page_tables=page_tables, enabled=enabled,
                             nic=nic)


def _remote_write(t, arenas, dest, offsets, values, *, capacity, mode,
                  page_tables, enabled, nic):
    B = dest.shape[-1]
    L = values.shape[-1]
    # capacity=0 must mean "deliver nothing", never silently "unbounded"
    cap = B if capacity is None else int(capacity)
    if cap < 0:
        raise ValueError(f"per-destination capacity must be >= 0, got {cap}")
    with jax.named_scope("storm.pack"):
        if enabled is None:
            enabled = jnp.ones(dest.shape, bool)
        payload = jnp.concatenate(
            [offsets[..., None].astype(jnp.uint32), values.astype(jnp.uint32)],
            axis=-1)
        # disabled lanes are parked at the routing layer: no cell, no capacity
        buf, mask, pos, ovf = jax.vmap(
            lambda d, p, e: route_by_dest(d, p, t.n_nodes, cap, e)
        )(dest, payload, enabled)
    with jax.named_scope("storm.exchange"):
        inbox = t.exchange(buf)
        inbox_mask, sent, mask_bytes = t.exchange_mask(mask)

    def owner_scatter(a, recs, msk, pt):
        off = recs[..., 0]
        val = recs[..., 1:]
        return rg.arena_write(a, off, val, mode=mode, page_table=pt,
                              enabled=msk)

    with jax.named_scope("storm.gather"):
        if mode is not None and mode.kind == "paged":
            arenas = jax.vmap(owner_scatter)(arenas, inbox, inbox_mask,
                                             page_tables)
        else:
            arenas = jax.vmap(lambda a, r, m: owner_scatter(a, r, m, None))(
                arenas, inbox, inbox_mask)
    with jax.named_scope("storm.unpack"):
        stats = rs.cluster_stats(
            wire_for(mask, req_words=1 + L, reply_words=0, nic=nic), sent,
            t.ici_bytes(buf) + mask_bytes)
    return arenas, ovf, stats
