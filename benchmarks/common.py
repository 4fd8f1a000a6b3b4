"""Shared harness for the paper-figure benchmarks.

All figure benchmarks run on the N-node cluster SIMULATOR (SimTransport on
whatever single device JAX offers; they name no device and are not chip
measurements — chip_smoke.py is the run on a TPU) and report:

  * protocol metrics (hardware-independent): round trips / op, wire bytes /
    op, one-sided success fraction — these are what Storm's design actually
    changes, and they reproduce the paper's RELATIVE claims;
  * modeled IOPS: protocol bytes/hops priced with the paper's own hardware
    constants (CX4-IB-class: ~1.8us one-sided RT, ~2.7us RPC RT, 100Gbps
    links, per-message CPU costs for send/recv systems) — the absolute
    scale of Figs 4-6;
  * host wall time, printed for transparency but NOT the comparison metric
    (it is not a device time on any chip).
"""
from __future__ import annotations

import dataclasses
import sys
import time
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import chip
from repro.core import rpc as R
from repro.core import slots as sl
from repro.core.datastructs import hashtable as ht

# THE percentile helper: every benchmark reports latency distributions
# through this one summary ({p50, p90, p99, mean}) — never bare means, and
# never a private reimplementation.  It lives next to the flight recorder
# (core/telemetry.py) because the traced latency samples are produced there.
from repro.core.telemetry import summarize  # noqa: F401  (re-export)


# --- modeled fabric (CX4 Infiniband EDR) -------------------------------------
# Calibration (documented in EXPERIMENTS.md §Fig4/5): a one-sided read
# consumes a NIC slot at the requester AND the owner (2 slots of the ~40M/s
# read engine) plus fixed issue overhead -> T_READ ~= 0.085us/op/node, which
# puts Storm(perfect) at ~12 Mops/node — the paper's top line.  A write-based
# RPC adds the owner-side handler + completion (T_RPC ~= 0.18us) — the
# paper's RPC-only Storm at ~5.5 Mops/node.  Everything else (eRPC recv
# posting + app-level CC, FaRM 8x reads, LITE syscalls) layers on top of
# these two primitives with per-system terms from §6.2 / Table 5.
@dataclasses.dataclass(frozen=True)
class ModelFabric:
    t_read_us: float = 0.085             # per one-sided read (2 NIC slots)
    t_rpc_us: float = 0.18               # per write-based RPC (handler+CQ)
    link_gbps: float = 100.0
    rt_onesided_us: float = 1.8          # unloaded RT (Table 5)
    rt_rpc_us: float = 2.7
    recv_post_us: float = 0.04           # eRPC per-message RQ posting (x2/op)
    app_cc_us: float = 0.15              # eRPC app-level congestion control
    syscall_us: float = 1.55             # LITE kernel entry/exit + copy (latency)
    lite_serial_us: float = 1.8          # LITE throughput-path syscall+locks
    dma_seg_us_per_kb: float = 0.20      # large-read DMA segmentation (FaRM)


def modeled_throughput_per_node(*, reads_per_op: float, rpcs_per_op: float,
                                wire_bytes_per_op: float, lanes: int,
                                fabric: ModelFabric = ModelFabric(),
                                extra_cpu_us_per_op: float = 0.0,
                                nic=None):
    """Million ops/s/node for a pipelined (lanes deep) workload: the per-op
    serialization cost (NIC slots + wire bytes + CPU terms), floored by the
    latency/lanes term.

    nic: optional repro.core.nic.ConnTable — adds the modeled per-op
    connection-state penalty (NIC-cache misses of QP state, QP-sharing locks,
    DC reconnects) of that connection mode / emulated cluster scale."""
    wire_us = wire_bytes_per_op * 8 / (fabric.link_gbps * 1e3)
    slot_us = reads_per_op * fabric.t_read_us + rpcs_per_op * fabric.t_rpc_us
    rt_us = (reads_per_op * fabric.rt_onesided_us
             + rpcs_per_op * fabric.rt_rpc_us)
    if nic is not None:
        extra_cpu_us_per_op += nic.penalty_us_per_op
    per_op_us = max(slot_us + wire_us + extra_cpu_us_per_op,
                    rt_us / max(lanes, 1))
    return 1.0 / per_op_us  # Mops/s


def populate(cfg, layout, t, state, n_keys_per_node, seed=0):
    """Insert n keys per node; returns (state, key arrays (N, n))."""
    rng = np.random.RandomState(seed)
    N = cfg.n_nodes
    klo = jnp.asarray(rng.randint(0, 2**31, (N, n_keys_per_node)), jnp.uint32)
    khi = jnp.asarray(rng.randint(0, 2**31, (N, n_keys_per_node)), jnp.uint32)
    h = ht.make_rpc_handler(cfg, layout)
    B = 64
    for i in range(0, n_keys_per_node, B):
        kl, kh = klo[:, i:i + B], khi[:, i:i + B]
        node, _, _ = ht.lookup_start(cfg, layout, kl, kh)
        vals = sl._mix32(kl[..., None] + jnp.arange(sl.VALUE_WORDS, dtype=jnp.uint32))
        state, rep, _, _ = R.rpc_call(
            t, state, node, ht.make_record(R.OP_INSERT, kl, kh, value=vals), h)
    return state, (klo, khi)


def make_tx_workload(t, cfg, layout, state, *, lanes, n_keys, seed):
    """Populate the table and draw a deterministic one-read/one-write
    transaction batch per lane (shared by bench_gate and conn_scaling so the
    gated workload and the benchmarked one can never diverge).

    Returns (state, read_keys (N, lanes, 1, 2), write_keys, write_values)."""
    state, (klo, khi) = populate(cfg, layout, t, state, n_keys, seed=seed)
    rng = np.random.RandomState(seed + 1)
    s = rng.randint(0, cfg.n_nodes, (cfg.n_nodes, lanes, 1))
    i = rng.randint(0, n_keys, (cfg.n_nodes, lanes, 1))
    rk = jnp.asarray(np.stack([np.asarray(klo)[s, i],
                               np.asarray(khi)[s, i]], -1), jnp.uint32)
    wk = rk ^ jnp.uint32(0x9E3779B9)    # disjoint write set
    wv = sl._mix32(wk[..., 0] + jnp.uint32(seed + 11))[..., None] * \
        jnp.ones((sl.VALUE_WORDS,), jnp.uint32)
    return state, rk, wk, wv


def time_jit(fn, *args, iters=3):
    """Compile + time a jitted callable; returns (result, best_seconds).
    Compiles go through the persistent cache (repro.chip)."""
    chip.use_compile_cache(ROOT)
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return out, best


def csv_line(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.2f},{derived}")
