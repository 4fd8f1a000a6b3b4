"""Transport layer: the "reliable connected" fabric (Storm §4.2).

Storm's transport decisions map onto TPU as follows: RC connections between
sibling threads become the *static, compiler-scheduled collective* between
SPMD ranks — reliability, ordering and congestion control are properties of
the ICI fabric and the XLA schedule, exactly the "offload it to the NIC"
argument the paper makes for RC.  There is no QP-sharing lock anywhere: every
rank owns its send/recv buffers (Storm's lock-free sibling connections).

The single exchange primitive is dest-major -> source-major:

    exchange(x): x[dst, c, ...] (what THIS node wants delivered to `dst`)
             ->  y[src, c, ...] (what `src` delivered to THIS node)

which is precisely an all-to-all.  Two implementations:

  * SimTransport  — an N-node cluster simulated on one device (one TPU chip,
    or the CPU in tests): cluster arrays carry a leading node axis; exchange
    is a transpose in device memory.
  * MeshTransport — one node per device: runs inside ``jax.shard_map`` over
    a mesh axis; exchange is ``lax.all_to_all`` (ICI on a TPU host).
    ``chip_smoke.py --chips 4`` runs it on a four-chip v5e host against
    SimTransport(4).

Protocol code is written once at cluster level: node-state arrays have one
leading node axis (N, ...); in mesh mode that axis is the per-device shard
(length N/devices, typically 1), so the identical `jax.vmap` per-node code
serves both.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


class Transport:
    n_nodes: int  # global node count

    def exchange(self, x):
        raise NotImplementedError

    def node_ids(self):
        """Global ids of the nodes in this shard: (n_local,) int32."""
        raise NotImplementedError

    @property
    def n_local(self) -> int:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class SimTransport(Transport):
    """Whole cluster on one device; leading axis = node."""
    n_nodes: int

    def exchange(self, x):
        # x: (N_this, N_dst, C, ...) -> (N_this, N_src, C, ...)
        assert x.shape[0] == self.n_nodes and x.shape[1] == self.n_nodes, x.shape
        return jnp.swapaxes(x, 0, 1)

    def node_ids(self):
        return jnp.arange(self.n_nodes, dtype=jnp.int32)

    @property
    def n_local(self) -> int:
        return self.n_nodes


@dataclasses.dataclass(frozen=True)
class MeshTransport(Transport):
    """Inside shard_map over `axis_name`, one node per device (n_local == 1).
    Local arrays: (1, N, C, ...)."""
    n_nodes: int
    axis_name: str = "node"

    def exchange(self, x):
        # x: (1, N_dst, C, ...) dest-major.  tiled all_to_all splits axis 1
        # into axis_size chunks (each (1, 1, C, ...)), sends chunk i to rank
        # i, concatenates received chunks on axis 0 -> (N, 1, C, ...).  The
        # swap restores the (n_local=1, N_src, C, ...) source-major layout.
        y = lax.all_to_all(x, self.axis_name, split_axis=1, concat_axis=0, tiled=True)
        return jnp.swapaxes(y, 0, 1)

    def node_ids(self):
        i = lax.axis_index(self.axis_name)
        return jnp.asarray(i, jnp.int32)[None]

    @property
    def n_local(self) -> int:
        return 1


# ---------------------------------------------------------------------------
# Client-side routing: pack per-lane requests into the dest-major send buffer.
# This is the coroutine scheduler's doorbell batching: B outstanding lanes per
# node, sorted by destination, with a fixed per-destination capacity C
# (overflowed lanes report failure and retry at the app level — the same
# back-pressure a real send queue applies).  Everything headed for one
# destination shares ONE contiguous buffer chunk, so the exchange puts one
# coalesced message per live (src, dst) pair on the wire (Storm's doorbell
# batching); wire_for accounts accordingly.
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnums=(2, 3))
def route_by_dest(dest, payload, n_dst: int, capacity: int, enabled=None):
    """dest: (B,) int32 in [0, n_dst); payload: (B, W) uint32.

    enabled: optional (B,) bool — lanes that actually issue a request this
    round.  Disabled lanes are parked in the trash column and, crucially, do
    NOT consume destination capacity, so a retry round that re-enables only
    the previously-overflowed lanes can always make progress.

    A dest outside [0, n_dst) is parked exactly like a disabled lane: the
    placement layer (core/placement.py) encodes "no reachable copy" as
    dest = -1, and a parked lane reads back ST_DROPPED — an unreachable
    partition surfaces as retryable back-pressure, never as a wrapped-around
    delivery to some arbitrary node.

    Returns:
      buf      (n_dst, capacity, W) uint32 — dest-major send buffer
      mask     (n_dst, capacity)    bool   — which cells hold live requests
      pos      (B,)                 int32  — cell index of each lane (for reply
                                            pickup; == capacity for parked lanes)
      overflow (B,)                 bool   — enabled lanes dropped by capacity
    """
    B = dest.shape[0]
    dest = dest.astype(jnp.int32)
    live = jnp.ones((B,), bool) if enabled is None else enabled
    # out-of-range dests (placement's "unreachable" sentinel -1) are parked
    live = live & (dest >= 0) & (dest < n_dst)
    dest = jnp.clip(dest, 0, n_dst - 1)
    # rank of each lane within its destination group (stable order, live only)
    onehot = ((dest[:, None] == jnp.arange(n_dst, dtype=jnp.int32)[None, :])
              & live[:, None])
    pos = (jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1)[jnp.arange(B), dest]
    overflow = live & (pos >= capacity)
    # overflowed and disabled lanes land in a trash column that is sliced off,
    # so they can never clobber live cells (the send queue's back-pressure
    # drop).  pick_replies recognizes pos == capacity as "no cell".
    pos = jnp.where(live & ~overflow, pos, capacity)
    buf = jnp.zeros((n_dst, capacity + 1, payload.shape[-1]), jnp.uint32)
    buf = buf.at[dest, pos].set(payload.astype(jnp.uint32))
    mask = jnp.zeros((n_dst, capacity + 1), bool)
    mask = mask.at[dest, pos].set(live)
    return buf[:, :capacity], mask[:, :capacity], pos, overflow


def placement_dest(copies, alive, part):
    """Resolve a partition to its first LIVE copy under a placement table.

    copies: (n_parts, K) int32 — copy list per partition, column 0 = owner,
            -1 = no copy in that slot (core/placement.py's PlacementTable).
    alive:  (n_nodes,) bool.
    part:   int32, any batch shape.

    Returns (dest, reachable): dest is the first copy (owner-priority order)
    whose node is alive, or -1 when every copy is dead — which route_by_dest
    parks, so an unreachable partition becomes ST_DROPPED back-pressure.
    This one scan is THE failover rule: replication.failover_dest and the
    read-side failover paths all reduce to it.
    """
    row = copies[part]                                   # (..., K)
    ok = (row >= 0) & alive[jnp.clip(row, 0, alive.shape[0] - 1)]
    idx = jnp.argmax(ok, axis=-1)                        # first live slot
    reachable = jnp.any(ok, axis=-1)
    dest = jnp.take_along_axis(row, idx[..., None], axis=-1)[..., 0]
    return jnp.where(reachable, dest, -1).astype(jnp.int32), reachable


def route_by_placement(table, part, payload, n_dst: int, capacity: int,
                       enabled=None):
    """route_by_dest with the destination resolved THROUGH a placement table
    instead of supplied by static partition math.

    table: anything with ``.copies`` (n_parts, K) int32 and ``.alive``
    (n_nodes,) bool — i.e. a core/placement.py PlacementTable.  part: (B,)
    int32 partition of each lane.  Lanes whose partition has no live copy
    route to -1 and are parked (ST_DROPPED).

    Returns (dest, reachable, buf, mask, pos, overflow) — the extra leading
    pair lets callers thread dest into reply pickup and surface
    ``dead_route = enabled & ~reachable``.
    """
    dest, reachable = placement_dest(table.copies, table.alive, part)
    buf, mask, pos, overflow = route_by_dest(dest, payload, n_dst, capacity,
                                             enabled)
    return dest, reachable, buf, mask, pos, overflow


def pick_replies(replies, dest, pos, overflow):
    """replies: (n_dst, C, W) dest-major reply buffer (post-exchange);
    returns per-lane replies (B, W).  Lanes without a live cell (overflowed or
    parked at pos >= C) read back zeros — callers are responsible for not
    treating those as real replies (rpc.rpc_call stamps ST_DROPPED)."""
    C = replies.shape[1]
    if C == 0:
        # zero-capacity round: no cell was ever live, every lane reads zeros
        # (a capacity=0 configuration back-pressures everything, not nothing)
        return jnp.zeros(dest.shape + (replies.shape[-1],), replies.dtype)
    invalid = overflow | (pos >= C)
    out = replies[dest, jnp.where(invalid, 0, pos)]
    return jnp.where(invalid[:, None], jnp.zeros_like(out), out)


# ---------------------------------------------------------------------------
# Wire accounting — the hardware-independent metrics the benchmarks report
# (round trips / messages / bytes per op), mirroring the quantities Storm
# reasons about in §4.4-4.5.  When a connection table (core.nic.ConnTable) is
# supplied, every round additionally carries the modeled NIC-cache hit rate
# and per-op connection-state penalty of the transport configuration it ran
# under (§2.2/Fig. 7) — both stored ops-weighted so stats stay additive.
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class WireStats:
    round_trips: jnp.ndarray   # scalar f32 — network round trips issued
    messages: jnp.ndarray      # scalar f32 — coalesced messages on the wire
    ops: jnp.ndarray           # scalar f32 — application-level requests (IOPS)
    req_bytes: jnp.ndarray     # scalar f32
    reply_bytes: jnp.ndarray   # scalar f32
    # NIC connection-state model (ops-weighted so `+` stays exact):
    nic_hit_ops: jnp.ndarray = dataclasses.field(     # sum(ops * cache_hit)
        default_factory=lambda: jnp.zeros((), jnp.float32))
    nic_penalty_us: jnp.ndarray = dataclasses.field(  # sum(ops * penalty_us)
        default_factory=lambda: jnp.zeros((), jnp.float32))

    @staticmethod
    def zero():
        # field-driven so the NEXT added field is zeroed automatically instead
        # of silently breaking a positional constructor (regression-tested by
        # tests/test_telemetry.py::test_wirestats_zero_roundtrips_every_field)
        return WireStats(**{f.name: jnp.zeros((), jnp.float32)
                            for f in dataclasses.fields(WireStats)})

    def __add__(self, o):
        return WireStats(**{f.name: getattr(self, f.name) + getattr(o, f.name)
                            for f in dataclasses.fields(WireStats)})

    @property
    def total_bytes(self):
        return self.req_bytes + self.reply_bytes

    @property
    def nic_hit_rate(self):
        """Ops-weighted modeled NIC-cache hit rate (1.0 when no ConnTable
        was threaded through — an un-modeled fabric misses nothing)."""
        return jnp.where(self.ops > 0,
                         self.nic_hit_ops / jnp.maximum(self.ops, 1.0), 1.0)

    @property
    def nic_penalty_us_per_op(self):
        """Ops-weighted modeled per-op connection-state penalty (us)."""
        return jnp.where(self.ops > 0,
                         self.nic_penalty_us / jnp.maximum(self.ops, 1.0), 0.0)


def _nic_terms(ops, nic):
    """ops-weighted (hit, penalty) terms for one round; nic is a static
    core.nic.ConnTable (or None = perfect, penalty-free NIC)."""
    if nic is None:
        return ops, jnp.zeros((), jnp.float32)
    return ops * nic.cache_hit, ops * nic.penalty_us_per_op


def wire_for(mask, req_words: int, reply_words: int, header_words: int = 1,
             nic=None):
    """Stats for one exchange round given the live-cell mask (..., n_dst, C).

    Requests headed for the same destination ride ONE coalesced wire message
    per live (src, dst) pair — Storm's doorbell batching — and likewise for
    the replies coming back, so `messages` counts live pairs (both ways) while
    `ops` keeps the per-request count the paper reports as IOPS.  Each
    coalesced message pays the header once; each record pays its payload.

    The single header word is the immediate: it packs the (src, slot) reply
    coordinates AND the sender's placement-table epoch (core/placement.py).
    Epoch bumps therefore add zero bytes per record — staleness is detected
    owner-side against the published routing region and surfaced as
    ST_WRONG_EPOCH, so the epoch-stable wire format is unchanged.
    """
    live = jnp.sum(mask.astype(jnp.float32))
    pairs = jnp.sum(jnp.any(mask, axis=-1).astype(jnp.float32))
    reply_pairs = pairs if reply_words > 0 else jnp.zeros((), jnp.float32)
    hit_ops, penalty_us = _nic_terms(live, nic)
    return WireStats(
        # a round with no live (src, dst) pair puts nothing on the wire and
        # therefore costs no round trip (e.g. a fully-parked retry round)
        round_trips=(pairs > 0).astype(jnp.float32),
        messages=pairs + reply_pairs,
        ops=live,
        req_bytes=live * 4.0 * req_words + pairs * 4.0 * header_words,
        reply_bytes=live * 4.0 * reply_words + reply_pairs * 4.0 * header_words,
        nic_hit_ops=hit_ops,
        nic_penalty_us=penalty_us,
    )


def wire_for_classes(masks, req_words, reply_words, header_words: int = 1,
                     nic=None):
    """Coalesced stats for ONE fused exchange round carrying several traffic
    classes (roundsched.fused_round).

    masks: list of live-cell masks, each (..., n_dst, C_k); req_words /
    reply_words: per-class word counts.  All classes headed for one
    destination ride the SAME coalesced wire message — a (src, dst) pair is
    counted ONCE no matter how many classes it carries (the true
    doorbell-batching accounting), while `ops` still counts every delivered
    application-level request.

    This is also how the replicated commit is priced: its backup-write
    classes widen the round's (src, dst) fan-out and add delivered requests
    (each paying the nic model's per-op connection-state penalty) without
    adding a round trip — `round_trips` stays 1 for the whole fused round.
    """
    f32 = jnp.float32
    zero = jnp.zeros((), f32)
    live = [jnp.sum(m.astype(f32)) for m in masks]
    ops = sum(live, zero)
    pair_live = None
    reply_pair_live = None
    for m, rw in zip(masks, reply_words):
        a = jnp.any(m, axis=-1)
        pair_live = a if pair_live is None else (pair_live | a)
        if rw > 0:
            reply_pair_live = a if reply_pair_live is None else (reply_pair_live | a)
    pairs = zero if pair_live is None else jnp.sum(pair_live.astype(f32))
    reply_pairs = (zero if reply_pair_live is None
                   else jnp.sum(reply_pair_live.astype(f32)))
    req_bytes = sum((l * 4.0 * w for l, w in zip(live, req_words)), zero)
    reply_bytes = sum((l * 4.0 * w for l, w in zip(live, reply_words)), zero)
    hit_ops, penalty_us = _nic_terms(ops, nic)
    return WireStats(
        round_trips=(pairs > 0).astype(f32),
        messages=pairs + reply_pairs,
        ops=ops,
        req_bytes=req_bytes + pairs * 4.0 * header_words,
        reply_bytes=reply_bytes + reply_pairs * 4.0 * header_words,
        nic_hit_ops=hit_ops,
        nic_penalty_us=penalty_us,
    )


def per_dest_wire(masks, req_words, reply_words, header_words: int = 1):
    """Per-DESTINATION view of :func:`wire_for_classes` for one fused round.

    masks: list of live-cell masks, each (N_src, n_dst, C_k).  Returns
    ``(msgs, bytes)`` — two (n_dst,) float32 vectors counting the coalesced
    wire messages addressed to / replied by each destination and their total
    bytes (both directions), with the same coalescing rules as the scalar
    accounting: summing either vector over destinations reproduces the
    round's ``WireStats.messages`` / ``total_bytes`` exactly (asserted by
    tests/test_telemetry.py).  Consumed by the flight recorder's per-dest
    event-row tails (core/telemetry.py).
    """
    f32 = jnp.float32
    n_dst = masks[0].shape[-2]
    zero = jnp.zeros((n_dst,), f32)
    live = [jnp.sum(m.astype(f32), axis=(0, -1)) for m in masks]   # (n_dst,)
    pair_live = None
    reply_pair_live = None
    for m, rw in zip(masks, reply_words):
        a = jnp.any(m, axis=-1)                                    # (N, n_dst)
        pair_live = a if pair_live is None else (pair_live | a)
        if rw > 0:
            reply_pair_live = a if reply_pair_live is None else (reply_pair_live | a)
    pairs = zero if pair_live is None else jnp.sum(pair_live.astype(f32), axis=0)
    reply_pairs = (zero if reply_pair_live is None
                   else jnp.sum(reply_pair_live.astype(f32), axis=0))
    req_bytes = sum((l * 4.0 * w for l, w in zip(live, req_words)), zero)
    reply_bytes = sum((l * 4.0 * w for l, w in zip(live, reply_words)), zero)
    msgs = pairs + reply_pairs
    byts = (req_bytes + reply_bytes + pairs * 4.0 * header_words
            + reply_pairs * 4.0 * header_words)
    return msgs, byts
