"""Flight-recorder telemetry for the dataplane (observability layer).

Storm's authors diagnosed RDMA scalability by *watching counters* — NIC cache
hit rates, per-op round trips, abort causes — evolve over a run.  This module
is the repo's equivalent: a scan-safe flight recorder that can be threaded
through every exchange round without perturbing the protocol.

Three pieces:

  * :class:`TraceBuffer` + :class:`Recorder` — a fixed-capacity buffer of
    fixed-width DEVICE-SIDE event rows, appended inside the ``lax.scan``
    bodies of ``txloop.tx_loop`` / ``txloop.scan_loop`` and inside
    ``roundsched.fused_round``.  One row per fused exchange round (round
    index, phase tag, class count, WireStats snapshot incl. the modeled NIC
    hit-rate terms, per-destination message/byte counts) plus one SUMMARY row
    per protocol round (committed / attempts / abort-cause vector).  All
    shapes are static and every append is pure array arithmetic, so recording
    is legal anywhere in a traced computation.

  * a modeled per-lane LATENCY accumulator: each protocol round's recorded
    events are priced with the same constants the benchmarks' ``ModelFabric``
    uses (one-sided vs RPC base round trip, link serialization of the round's
    bytes, the ``nic.ConnTable`` per-op connection-state penalty), and every
    lane still live in that round accumulates the cost.  The result is a
    latency *sample per lane* — histograms (p50/p90/p99 per abort-retry
    path), not means.

  * export layers: :func:`export_trace` renders the buffer as Chrome/Perfetto
    trace-event JSON (one track per destination node, one slice per
    round x class, counter tracks for aborts), and :class:`MetricsRegistry`
    collects named host-side counters into a flat ``metrics.json``.

The discipline every other optional subsystem follows (``nic=``, ``rep=``,
``ptable=``) applies here too: ``telemetry=None`` (the default everywhere) is
BIT-IDENTICAL and round-identical to a build without this module — recording
only ever *reads* protocol values (tests/test_telemetry.py asserts this; the
bench gate pins the round-trip schedule).

The threading idiom is a MUTABLE HOLDER, not a return value: a
:class:`Recorder` passed down the call tree accumulates the traced
:class:`TraceBuffer` value by assignment during tracing (the dataplane's
``jax.named_scope`` layers are names only, so no trace boundary is
crossed), and the loop body that created it threads ``recorder.buf`` back
into its scan carry.  That keeps every dataplane function's return
signature unchanged.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.transport import WireStats

# ---------------------------------------------------------------------------
# Phase tags.  An event's phase names the protocol work its exchange round
# carried and decides its latency pricing: READ / VALIDATE / REFRESH rounds
# are one-sided (rt_onesided_us), FALLBACK / LOCK / COMMIT rounds run RPC
# handlers (rt_rpc_us).  SUMMARY rows carry the per-protocol-round abort
# vector and are not priced.
# ---------------------------------------------------------------------------
PH_OTHER = 0      # unclassified single rounds (direct rpc_call/remote_read)
PH_READ = 1       # one-sided read-set probe (hybrid phase 2)
PH_FALLBACK = 2   # read-set RPC fallback in its own round (unfused schedule)
PH_LOCK = 3       # LOCK round; under the fused schedule this single
                  # exchange also carries the fallback + validate classes
PH_VALIDATE = 4   # one-sided validate re-read
PH_COMMIT = 5     # COMMIT/ABORT round (+ backup fan-out classes at f > 0)
PH_REFRESH = 6    # metadata refresh (placement table / separator directory)
PH_SUMMARY = 7    # per-protocol-round summary (abort-cause vector)

PHASE_NAMES = {
    PH_OTHER: "other", PH_READ: "read", PH_FALLBACK: "fallback",
    PH_LOCK: "lock", PH_VALIDATE: "validate", PH_COMMIT: "commit",
    PH_REFRESH: "refresh", PH_SUMMARY: "summary",
}
# phases whose exchange is one-sided (priced at rt_onesided_us)
_ONESIDED_PHASES = (PH_READ, PH_VALIDATE, PH_REFRESH)

# ---------------------------------------------------------------------------
# Event-row schema.  A row is (EV_WORDS + 2 * n_dst) float32: the fixed
# columns below, then per-destination message counts, then per-destination
# byte counts (both directions, coalesced wire accounting — summing either
# tail over destinations reproduces the scalar WireStats of the round).
# ---------------------------------------------------------------------------
EV_ROUND = 0          # protocol round index (txloop's scan counter)
EV_PHASE = 1          # phase tag above
EV_CLASSES = 2        # traffic classes fused into this exchange round
EV_RT = 3             # round trips (0 for an empty / fully-parked round)
EV_MSGS = 4           # coalesced wire messages (both directions)
EV_OPS = 5            # delivered application-level requests
EV_REQ_BYTES = 6
EV_REPLY_BYTES = 7
EV_NIC_HIT_OPS = 8    # ops-weighted modeled NIC-cache hits (snapshot)
EV_NIC_PENALTY = 9    # ops-weighted modeled connection-state penalty (us)
EV_COMMITTED = 10     # SUMMARY rows only: lanes committed this round ...
EV_ATTEMPTS = 11      # ... lanes live entering the round,
EV_AB_LOCK = 12       # and the abort-cause vector
EV_AB_VALIDATE = 13
EV_AB_OVERFLOW = 14
EV_AB_STALE = 15
EV_WORDS = 16         # fixed columns; per-dest tails follow


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Static (trace-time) flight-recorder configuration.

    capacity: event rows in the buffer (None = sized by the loop from its
              ``max_rounds``); a full buffer drops further events and counts
              them in ``TraceBuffer.dropped`` — never an error, never a
              dynamic shape.
    rt_onesided_us / rt_rpc_us / link_gbps: the latency-pricing constants,
              defaulting to the benchmarks' ``ModelFabric`` fabric.
    """
    capacity: Optional[int] = None
    rt_onesided_us: float = 1.8
    rt_rpc_us: float = 2.7
    link_gbps: float = 100.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TraceBuffer:
    """Fixed-width device-side event log (a pytree; scan-carry friendly)."""
    rows: jnp.ndarray      # (capacity, EV_WORDS + 2 * n_dst) float32
    n: jnp.ndarray         # () int32 — rows written
    rnd: jnp.ndarray       # () int32 — current protocol round register
    dropped: jnp.ndarray   # () int32 — events dropped at capacity

    @property
    def capacity(self) -> int:
        return self.rows.shape[0]

    @property
    def n_dst(self) -> int:
        return (self.rows.shape[1] - EV_WORDS) // 2


def make_buffer(n_dst: int, capacity: int) -> TraceBuffer:
    """Fresh empty buffer with per-destination tails for ``n_dst`` nodes."""
    return TraceBuffer(
        rows=jnp.zeros((capacity, EV_WORDS + 2 * n_dst), jnp.float32),
        n=jnp.zeros((), jnp.int32),
        rnd=jnp.zeros((), jnp.int32),
        dropped=jnp.zeros((), jnp.int32))


class Recorder:
    """Mutable holder threading a :class:`TraceBuffer` through a call tree.

    Dataplane functions take ``telemetry: Recorder | None = None`` and call
    :meth:`record` — the holder swaps in the new traced buffer value, so no
    return signature changes.  The creating loop body reads ``.buf`` back
    into its scan carry after the call tree returns.  Valid within one trace
    scope (a single ``lax.scan`` body iteration), which is exactly where the
    loops construct it.
    """

    __slots__ = ("config", "buf")

    def __init__(self, config: TelemetryConfig, buf: TraceBuffer):
        self.config = config
        self.buf = buf

    # -- appends ------------------------------------------------------------
    def set_round(self, rnd):
        """Stamp the protocol round index subsequent events belong to."""
        self.buf = dataclasses.replace(
            self.buf, rnd=jnp.asarray(rnd, jnp.int32))

    def _append(self, fixed, per_dest_msgs=None, per_dest_bytes=None):
        b = self.buf
        n_dst = b.n_dst
        zero_d = jnp.zeros((n_dst,), jnp.float32)
        pd_m = zero_d if per_dest_msgs is None else per_dest_msgs.astype(
            jnp.float32)
        pd_b = zero_d if per_dest_bytes is None else per_dest_bytes.astype(
            jnp.float32)
        row = jnp.concatenate([jnp.stack(fixed).astype(jnp.float32),
                               pd_m, pd_b])
        ok = b.n < b.capacity
        idx = jnp.minimum(b.n, b.capacity - 1)
        rows = b.rows.at[idx].set(jnp.where(ok, row, b.rows[idx]))
        self.buf = TraceBuffer(
            rows=rows,
            n=b.n + ok.astype(jnp.int32),
            rnd=b.rnd,
            dropped=b.dropped + (~ok).astype(jnp.int32))

    def record(self, phase: int, stats: WireStats, *, n_classes: int = 1,
               per_dest_msgs=None, per_dest_bytes=None):
        """Append one exchange-round event (called by fused_round)."""
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        z = jnp.zeros((), jnp.float32)
        self._append(
            [f32(self.buf.rnd), f32(phase), f32(n_classes),
             f32(stats.round_trips), f32(stats.messages), f32(stats.ops),
             f32(stats.req_bytes), f32(stats.reply_bytes),
             f32(stats.nic_hit_ops), f32(stats.nic_penalty_us),
             z, z, z, z, z, z],
            per_dest_msgs=per_dest_msgs, per_dest_bytes=per_dest_bytes)

    def summary(self, *, committed, attempts, abort_lock, abort_validate,
                abort_overflow, abort_stale):
        """Append one per-protocol-round SUMMARY row (abort-cause vector)."""
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        z = jnp.zeros((), jnp.float32)
        self._append(
            [f32(self.buf.rnd), f32(PH_SUMMARY), z, z, z, z, z, z, z, z,
             f32(committed), f32(attempts), f32(abort_lock),
             f32(abort_validate), f32(abort_overflow), f32(abort_stale)])

    # -- modeled latency ----------------------------------------------------
    def round_cost_us(self, n0):
        """Modeled latency (us) of the events appended since row ``n0``.

        Per event: a base round trip when the round actually went on the wire
        (one-sided vs RPC by phase tag), plus link serialization of the
        round's coalesced bytes, plus the modeled per-op connection-state
        penalty — the per-round analogue of ``ModelFabric``'s pricing.
        SUMMARY rows cost nothing (rt = 0, bytes = 0).
        """
        cfg = self.config
        b = self.buf
        idx = jnp.arange(b.capacity)
        win = (idx >= n0) & (idx < b.n)
        phase = b.rows[:, EV_PHASE]
        onesided = jnp.zeros((b.capacity,), bool)
        for p in _ONESIDED_PHASES:
            onesided = onesided | (phase == p)
        base = jnp.where(onesided, cfg.rt_onesided_us, cfg.rt_rpc_us)
        live = b.rows[:, EV_RT] > 0
        ops = jnp.maximum(b.rows[:, EV_OPS], 1.0)
        penalty = b.rows[:, EV_NIC_PENALTY] / ops
        byts = b.rows[:, EV_REQ_BYTES] + b.rows[:, EV_REPLY_BYTES]
        ser = byts * 8.0e-3 / cfg.link_gbps
        cost = jnp.where(win & live, base + penalty, 0.0) + \
            jnp.where(win, ser, 0.0)
        return jnp.sum(cost)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TelemetryOut:
    """What a loop returns when ``telemetry=`` is enabled."""
    trace: TraceBuffer
    lane_latency_us: jnp.ndarray   # (N, B) f32 — modeled latency to commit
    #                                (or to the final abort) of every lane


def loop_capacity(tel: TelemetryConfig, max_rounds: int) -> int:
    """Buffer capacity for a retry loop: the worst round appends <= 9 events
    (two refreshes, five phase rounds on the unfused schedule, summary)."""
    if tel.capacity is not None:
        return int(tel.capacity)
    return max_rounds * 10 + 4


# ---------------------------------------------------------------------------
# Host-side views + percentile summaries
# ---------------------------------------------------------------------------
def events(buf: TraceBuffer) -> np.ndarray:
    """The written rows as a host array (n, EV_WORDS + 2 * n_dst)."""
    return np.asarray(buf.rows)[: int(buf.n)]


def summarize(latencies) -> dict:
    """Percentile summary of a latency sample: {p50, p90, p99, mean} floats.

    THE percentile helper — benchmarks re-export it from
    ``benchmarks/common.py``; report distributions with it, never bare means.
    Empty samples summarize to NaNs (callers usually skip those groups).
    """
    a = np.asarray(latencies, np.float64).ravel()
    if a.size == 0:
        nan = float("nan")
        return dict(p50=nan, p90=nan, p99=nan, mean=nan)
    return dict(p50=float(np.percentile(a, 50)),
                p90=float(np.percentile(a, 90)),
                p99=float(np.percentile(a, 99)),
                mean=float(a.mean()))


def latency_by_path(lane_latency_us, committed, commit_round) -> dict:
    """Latency histograms per abort-retry path.

    Groups the per-lane modeled latency sample by outcome: committed lanes by
    the round they committed in (``retry0`` = first attempt, ``retryK`` =
    K-th re-execution), plus ``committed`` (all of them) and ``aborted``
    (lanes that never committed — their latency is time burned to the final
    abort).  Returns {group: summarize(...)} with empty groups omitted.
    """
    lat = np.asarray(lane_latency_us, np.float64).ravel()
    com = np.asarray(committed, bool).ravel()
    cr = np.asarray(commit_round, np.int64).ravel()
    out = {}
    if com.any():
        out["committed"] = summarize(lat[com])
    if (~com).any():
        out["aborted"] = summarize(lat[~com])
    for k in sorted({int(k) for k in cr[com]}):
        out[f"retry{k}"] = summarize(lat[com & (cr == k)])
    return out


# ---------------------------------------------------------------------------
# MetricsRegistry — named host-side counters -> flat metrics.json
# ---------------------------------------------------------------------------
class MetricsRegistry:
    """Named counters the benchmarks publish to ``metrics.json``.

    Plain host-side floats (increments happen after a traced computation
    returns, from its results) — the device-side complement is the
    TraceBuffer.  ``observe`` stores a whole latency distribution under
    dotted percentile keys, so the gate can pin p50/p99 by name.
    """

    def __init__(self):
        self._vals: dict = {}

    def incr(self, name: str, value=1.0):
        self._vals[name] = float(self._vals.get(name, 0.0)) + float(value)

    def set(self, name: str, value):
        self._vals[name] = float(value)

    def observe(self, name: str, latencies):
        for k, v in summarize(latencies).items():
            self._vals[f"{name}.{k}"] = v

    def get(self, name: str, default=0.0) -> float:
        return float(self._vals.get(name, default))

    def as_dict(self) -> dict:
        return dict(sorted(self._vals.items()))

    def write(self, path: str):
        with open(path, "w") as f:
            json.dump(self.as_dict(), f, indent=2, sort_keys=True)
            f.write("\n")


# ---------------------------------------------------------------------------
# Chrome/Perfetto trace-event export
# ---------------------------------------------------------------------------
def export_trace(buf: TraceBuffer, *, config: TelemetryConfig = None,
                 path: Optional[str] = None, label: str = "storm") -> dict:
    """Render the flight recorder as Chrome trace-event JSON.

    Layout: one PROCESS (track group) per destination node; within it, one
    slice per round x phase carrying that node's share of the round's
    messages/bytes in its args; a synthetic ``cluster`` process carries the
    per-round abort-cause counter tracks.  Timestamps are the MODELED
    timeline: events are laid end-to-end at their priced durations, so slice
    width in the UI is modeled round latency.  Loads directly in
    https://ui.perfetto.dev ("Open trace file") or chrome://tracing.
    """
    cfg = config or TelemetryConfig()
    ev = events(buf)
    n_dst = buf.n_dst
    out = []
    for d in range(n_dst):
        out.append(dict(ph="M", name="process_name", pid=d,
                        args=dict(name=f"node {d}")))
    cluster_pid = n_dst
    out.append(dict(ph="M", name="process_name", pid=cluster_pid,
                    args=dict(name=f"{label} cluster")))
    t_us = 0.0
    for row in ev:
        phase = int(row[EV_PHASE])
        rnd = int(row[EV_ROUND])
        pname = PHASE_NAMES.get(phase, str(phase))
        if phase == PH_SUMMARY:
            out.append(dict(ph="C", name="aborts", pid=cluster_pid,
                            ts=t_us, args=dict(
                                lock=float(row[EV_AB_LOCK]),
                                validate=float(row[EV_AB_VALIDATE]),
                                overflow=float(row[EV_AB_OVERFLOW]),
                                stale=float(row[EV_AB_STALE]))))
            out.append(dict(ph="C", name="progress", pid=cluster_pid,
                            ts=t_us, args=dict(
                                committed=float(row[EV_COMMITTED]),
                                attempts=float(row[EV_ATTEMPTS]))))
            continue
        base = (cfg.rt_onesided_us if phase in _ONESIDED_PHASES
                else cfg.rt_rpc_us)
        live = bool(row[EV_RT] > 0)
        penalty = float(row[EV_NIC_PENALTY]) / max(float(row[EV_OPS]), 1.0)
        ser = float(row[EV_REQ_BYTES] + row[EV_REPLY_BYTES]) * 8.0e-3 / \
            cfg.link_gbps
        dur = (base + penalty if live else 0.0) + ser
        name = f"r{rnd}/{pname}"
        hit_rate = (row[EV_NIC_HIT_OPS] / row[EV_OPS]
                    if row[EV_OPS] > 0 else 1.0)
        for d in range(n_dst):
            msgs = row[EV_WORDS + d]
            byts = row[EV_WORDS + n_dst + d]
            if msgs <= 0 and not live:
                continue
            out.append(dict(
                ph="X", name=name, cat=pname, pid=d, tid=phase,
                ts=t_us, dur=max(dur, 0.001), args=dict(
                    round=rnd, classes=int(row[EV_CLASSES]),
                    msgs=float(msgs), bytes=float(byts),
                    ops=float(row[EV_OPS]),
                    nic_hit_rate=float(hit_rate))))
        t_us += dur
    doc = dict(traceEvents=out, displayTimeUnit="ms",
               otherData=dict(
                   source=label, n_nodes=n_dst,
                   events=int(buf.n), dropped=int(buf.dropped),
                   modeled_span_us=t_us))
    if path is not None:
        with open(path, "w") as f:
            json.dump(doc, f)
            f.write("\n")
    return doc
