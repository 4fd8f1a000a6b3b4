"""Multi-class fused round scheduler (Storm §4.5 doorbell batching, Fig. 3).

Storm's latency argument is round trips: independent protocol phases have no
business occupying separate all-to-alls.  ``fused_round`` is the one exchange
primitive everything else is built on: it takes several *traffic classes* in a
single call — each class = (dest, payload, reply shape, owner-side action) —
packs them into ONE dest-major send buffer, performs ONE all-to-all each way,
runs each class's owner action over its sub-inbox, and returns per-class
replies and overflow masks plus a single coalesced :class:`WireStats`.

Traffic classes:

  * ``read_class``  — one-sided read: the payload is a word offset, the owner
    action is pure address translation + gather (no application logic).
  * ``rpc_class``   — write-based RPC: the payload is a request record, the
    owner runs the registered handler (serial = mutating fold, vector =
    read-only map).

Owner-side ordering inside one fused round is fixed and documented, because
it is what makes fusing OCC phases legal:

  1. **vector handlers** observe the round's PRE-handler state (a read-only
     RPC fused with a mutating class sees the state as if it ran in its own
     earlier round — how tx fuses the read-set lookup fallback with LOCK);
  2. **serial handlers** fold through node state in class order, each with
     genuine serialization semantics (scan order = lock order);
  3. **one-sided gathers** run LAST, on the post-handler state (the owner
     drains its RPC inbox before serving the round's reads — how tx fuses
     VALIDATE re-reads into the same round as the locks they must observe).

Buffer layout: each class reserves its own per-destination sub-budget
(``capacity``, defaulting to its lane count), and the shared send buffer is
the concatenation of the class segments — so the per-destination budget of
the fused message is the sum of the class budgets, each class's overflow
behaviour is identical to the round it replaced, and every class's sub-inbox
is a contiguous slice.  All classes headed for one destination still ride ONE
coalesced wire message per live (src, dst) pair each way; ``wire_for_classes``
accounts accordingly.

``rpc.rpc_call`` and ``onesided.remote_read`` are thin single-class wrappers
over this primitive; ``tx.run_transactions(fused=True)`` is the multi-class
user that cuts the OCC transaction from 5 exchange rounds to 3-4, and the
replicated commit adds its backup-write classes to the same round.

Public API: ``fused_round`` (the primitive), the class constructors
``read_class`` / ``rpc_class``, the handler applicators ``serial_apply`` /
``vector_apply``, and the transport-level ``ST_DROPPED`` status.  Invariant:
``fused=True`` schedules change ROUND COUNTS only — per-class replies,
overflow masks and delivered-request counts are bit-identical to running each
class in its own round (tests/test_tx_fused_equivalence.py).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import regions as rg
from repro.core.transport import (Transport, per_dest_wire, pick_replies,
                                  route_by_dest, wire_for_classes)
# Transport-level "request never delivered" status stamped into reply word 0
# of overflowed/parked RPC lanes (registered with every other status in
# core/wireproto.py; rpc.py re-exports it too).
from repro.core.wireproto import ST_DROPPED  # noqa: F401  (re-export)


# ---------------------------------------------------------------------------
# Handler application (moved here from rpc.py so the scheduler has no import
# cycle; rpc.py re-exports both names).
# ---------------------------------------------------------------------------
def serial_apply(handler_fn, state, records, mask, reply_words: int):
    """Fold records through node state in a fixed serialization order.

    handler_fn(state, record (W,), valid) -> (state, reply (reply_words,))
    records: (S, C, W); mask: (S, C) -> replies (S, C, reply_words)
    """
    S, C, W = records.shape
    flat_r = records.reshape(S * C, W)
    flat_m = mask.reshape(S * C)

    def step(st, rm):
        rec, valid = rm
        st, rep = handler_fn(st, rec, valid)
        return st, rep

    state, flat_rep = lax.scan(step, state, (flat_r, flat_m))
    return state, flat_rep.reshape(S, C, reply_words)


def vector_apply(handler_fn, state, records, mask, reply_words: int):
    """handler_fn(state, records (S,C,W), mask) -> replies (S,C,reply_words).
    State is read-only on this path."""
    return state, handler_fn(state, records, mask)


# ---------------------------------------------------------------------------
# Traffic-class constructors
# ---------------------------------------------------------------------------
def read_class(dest, offsets, *, length: int, enabled=None,
               capacity: Optional[int] = None,
               mode: "rg.AddressMode | None" = None, page_tables=None):
    """One-sided READ class: owner action is translation + gather only."""
    return dict(kind="read", dest=dest,
                payload=offsets[..., None].astype(jnp.uint32),
                length=length, enabled=enabled, capacity=capacity,
                mode=mode, page_tables=page_tables)


def rpc_class(dest, records, handler, *, enabled=None,
              capacity: Optional[int] = None):
    """Write-based RPC class: owner runs ``handler`` over the sub-inbox."""
    return dict(kind="rpc", dest=dest, payload=records, handler=handler,
                enabled=enabled, capacity=capacity)


def _pad_words(x, width):
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def fused_round(t: Transport, state, classes: Sequence[dict], *,
                arena_key: str = "arena", nic=None, telemetry=None,
                phase: int = 0):
    """Run one fused exchange round carrying several traffic classes.

    state: pytree with leading node axis; read classes gather from
    ``state[arena_key]``.  Every class's ``dest`` is (N_local, B_k); rpc
    payloads are (N_local, B_k, W_k) uint32, read payloads are built from the
    (N_local, B_k) offsets by :func:`read_class`.

    Returns ``(state, results, stats)`` where ``results[k]`` is a
    ``(reply (N_local, B_k, R_k), overflow (N_local, B_k))`` pair aligned with
    ``classes`` and ``stats`` is ONE coalesced :class:`WireStats` for the
    whole round.  ``nic`` (an optional :class:`repro.core.nic.ConnTable`)
    stamps the modeled NIC-cache hit rate / connection-state penalty of the
    transport configuration into the stats (None = perfect NIC).
    Overflowed/parked rpc lanes carry ST_DROPPED in reply word 0
    (never aliasing ST_OK or a handler-returned status); overflowed/parked
    read lanes read back zeros.

    ``telemetry`` (an optional :class:`repro.core.telemetry.Recorder`)
    appends ONE flight-recorder event for this round — phase tag, class
    count, the WireStats snapshot, per-destination message/byte counts —
    into the recorder's TraceBuffer.  Recording only READS round values:
    ``telemetry=None`` (the default) is bit-identical.
    """
    n_dst = t.n_nodes
    specs = []
    for c in classes:
        dest = c["dest"]
        B_k = dest.shape[-1]
        cap = c.get("capacity")
        cap = B_k if cap is None else int(cap)
        if cap < 0:
            raise ValueError(f"per-destination capacity must be >= 0, got {cap}")
        payload = c["payload"]
        R_k = c["length"] if c["kind"] == "read" else c["handler"].reply_words
        en = c.get("enabled")
        if en is not None:
            buf, mask, pos, ovf = jax.vmap(
                lambda d, p, e: route_by_dest(d, p, n_dst, cap, e)
            )(dest, payload, en)
        else:
            buf, mask, pos, ovf = jax.vmap(
                lambda d, p: route_by_dest(d, p, n_dst, cap))(dest, payload)
        specs.append(dict(cls=c, cap=cap, W=payload.shape[-1], R=R_k,
                          buf=buf, mask=mask, pos=pos, ovf=ovf))

    c_total = sum(s["cap"] for s in specs)
    if c_total == 0:
        # nothing can be delivered this round: no exchange, no wire traffic
        stats = wire_for_classes([s["mask"] for s in specs],
                                 [s["W"] for s in specs],
                                 [s["R"] for s in specs], nic=nic)
        results = [(_dropped_replies(s), s["ovf"]) for s in specs]
        _record_round(telemetry, phase, specs, stats)
        return state, results, stats

    w_max = max(s["W"] for s in specs)
    r_max = max(s["R"] for s in specs)
    send = jnp.concatenate([_pad_words(s["buf"], w_max) for s in specs], axis=2)
    mask_all = jnp.concatenate([s["mask"] for s in specs], axis=2)
    inbox = t.exchange(send)            # (N_local, n_src, C_total, w_max)
    inbox_mask = t.exchange(mask_all)

    seg = []
    base = 0
    for s in specs:
        seg.append((base, base + s["cap"]))
        base += s["cap"]

    replies = [None] * len(specs)
    # 1) vector (read-only) handlers observe the round's pre-handler state
    for i, s in enumerate(specs):
        c = s["cls"]
        if c["kind"] == "rpc" and not c["handler"].serial and s["cap"] > 0:
            h = c["handler"]
            s0, s1 = seg[i]
            recs = inbox[:, :, s0:s1, :s["W"]]
            msk = inbox_mask[:, :, s0:s1]
            _, replies[i] = jax.vmap(
                lambda st, r, m, fn=h.fn, rw=h.reply_words:
                    vector_apply(fn, st, r, m, rw)
            )(state, recs, msk)
    # 2) serial (mutating) handlers fold through node state in class order.
    # The nodes' folds are independent and run one node after another
    # (lax.map): vmapped, the fold's per-node slot reads/writes and its
    # fixed-offset word writes asked for opposite layouts of the (N, words)
    # cluster arena, and the TPU compiler relaid the whole arena out twice
    # per inbox cell
    for i, s in enumerate(specs):
        c = s["cls"]
        if c["kind"] == "rpc" and c["handler"].serial and s["cap"] > 0:
            h = c["handler"]
            s0, s1 = seg[i]
            recs = inbox[:, :, s0:s1, :s["W"]]
            msk = inbox_mask[:, :, s0:s1]
            state, replies[i] = lax.map(
                lambda a, fn=h.fn, rw=h.reply_words: serial_apply(fn, *a, rw),
                (state, recs, msk))
    # 3) one-sided gathers run last, on the post-handler state
    arena = None
    for i, s in enumerate(specs):
        c = s["cls"]
        if c["kind"] == "read" and s["cap"] > 0:
            if arena is None:
                arena = state[arena_key]
            s0, s1 = seg[i]
            offs = inbox[:, :, s0:s1, 0]
            mode = c.get("mode")
            length = c["length"]
            if mode is not None and mode.kind == "paged":
                replies[i] = jax.vmap(
                    lambda a, pt, off, m=mode, ln=length:
                        rg.arena_read(a, off, ln, m, pt)
                )(arena, c["page_tables"], offs)
            else:
                replies[i] = jax.vmap(
                    lambda a, off, ln=length: rg.arena_read(a, off, ln)
                )(arena, offs)

    back = t.exchange(jnp.concatenate(
        [_pad_words(replies[i].astype(jnp.uint32), r_max)
         if replies[i] is not None
         else jnp.zeros(inbox.shape[:2] + (0, r_max), jnp.uint32)
         for i in range(len(specs))], axis=2))

    results = []
    for i, s in enumerate(specs):
        if s["cap"] == 0:
            results.append((_dropped_replies(s), s["ovf"]))
            continue
        s0, s1 = seg[i]
        out = jax.vmap(pick_replies)(
            back[:, :, s0:s1, :s["R"]], s["cls"]["dest"], s["pos"], s["ovf"])
        results.append((_finalize_reply(s, out), s["ovf"]))

    stats = wire_for_classes([s["mask"] for s in specs],
                             [s["W"] for s in specs],
                             [s["R"] for s in specs], nic=nic)
    _record_round(telemetry, phase, specs, stats)
    return state, results, stats


def _record_round(telemetry, phase, specs, stats):
    """Append this round's flight-recorder event (no-op when disabled)."""
    if telemetry is None:
        return
    pd_msgs, pd_bytes = per_dest_wire([s["mask"] for s in specs],
                                      [s["W"] for s in specs],
                                      [s["R"] for s in specs])
    telemetry.record(phase, stats, n_classes=len(specs),
                     per_dest_msgs=pd_msgs, per_dest_bytes=pd_bytes)


def _dropped_replies(s):
    """All-dropped reply block for a class that could deliver nothing."""
    shape = s["cls"]["dest"].shape + (s["R"],)
    out = jnp.zeros(shape, jnp.uint32)
    return _finalize_reply(s, out, all_dropped=True)


def _finalize_reply(s, out, all_dropped: bool = False):
    """Stamp ST_DROPPED into undelivered rpc lanes' status word (a zeroed
    reply's word 0 would alias ST_OK)."""
    c = s["cls"]
    if c["kind"] != "rpc":
        return out
    if all_dropped:
        no_reply = jnp.ones(c["dest"].shape, bool)
    else:
        # pos == cap is route_by_dest's "no live cell": capacity overflow,
        # disabled lanes, AND enabled lanes parked by an out-of-range dest
        # (placement's unreachable sentinel -1) — the last would otherwise
        # read back zeros and alias ST_OK
        no_reply = s["pos"] >= s["cap"]
    return out.at[..., 0].set(
        jnp.where(no_reply, jnp.uint32(ST_DROPPED), out[..., 0]))
