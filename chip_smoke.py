"""Smoke run of the Storm dataplane on a TPU: TATP's subscriber table at
scale factor 10, loaded through write-based RPC inserts and then driven by
tx_loop batches, with every result checked against a plain host reference.

    python chip_smoke.py              # one chip: 8 nodes under SimTransport
    python chip_smoke.py --chips 4    # four chips: MeshTransport, one node
                                      # per chip, against SimTransport(4)

The run needs a TPU and exits non-zero without one (there is no CPU
fallback).  It prints its timings and checks line by line; the last line of
standard output is one JSON object naming the device,
``{"ok": true, "device": {"platform", "kind", "count"}}``, and is printed
only when every phase passed.  The compile cache is placed by
``repro.chip.use_compile_cache``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import chip, tatp  # noqa: E402
from repro.core import slots as sl  # noqa: E402

CHECK_KEYS = 10_240         # keys sampled by the post-load lookup check
UNWRITTEN_KEYS = 1_024      # unwritten keys looked up after each batch
BATCHES = 5                 # timed tx_loop batches, after one warm-up


def log(msg):
    print(msg, flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def compile_step(name, fn, *args):
    """AOT-compile a jitted step for these arguments; prints the time."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    log(f"compile {name}: {time.perf_counter() - t0:.3f} s")
    return compiled


class Reference:
    """Plain host model of the table: the loaded rows plus a dict of the
    committed writes applied in commit order."""

    def __init__(self, klo, khi, vals):
        self.klo, self.khi, self.vals = klo, khi, vals
        self.per_node = klo.shape[1]
        self.written = {}            # flat row -> value

    def value(self, row):
        return self.written.get(int(row), self.vals.reshape(
            -1, sl.VALUE_WORDS)[int(row)])

    def keys(self, rows):
        return (self.klo.reshape(-1)[rows], self.khi.reshape(-1)[rows])


def lookup_check(cl, lookup, state, ref, rows, what):
    """Look ``rows`` up through the dataplane; every one must be found with
    the reference's value."""
    N = cl.n_nodes
    lanes = -(-CHECK_KEYS // N)
    check(len(rows) <= N * lanes, f"{what}: {len(rows)} rows over budget")
    flat = np.zeros(N * lanes, np.int64)
    flat[:len(rows)] = rows
    en = np.zeros(N * lanes, bool)
    en[:len(rows)] = True
    klo, khi = ref.keys(flat)
    found, value = lookup(state, *cl.put((klo.reshape(N, lanes),
                                          khi.reshape(N, lanes),
                                          en.reshape(N, lanes))))
    found = np.asarray(found).reshape(-1)[:len(rows)]
    value = np.asarray(value).reshape(-1, sl.VALUE_WORDS)[:len(rows)]
    want = np.stack([ref.value(r) for r in rows]) if len(rows) else value
    check(found.all(), f"{what}: {int((~found).sum())} of {len(rows)} keys "
          "not found")
    bad = (value != want).any(axis=1)
    check(not bad.any(), f"{what}: {int(bad.sum())} of {len(rows)} values "
          "differ from the reference")
    log(f"check {what}: {len(rows)} keys found with the reference values")


def draw_batch(rng, ref, n_nodes, batch):
    """One TATP batch: host arrays for tx_step plus row coordinates."""
    per = ref.per_node
    (rs, ri), (ws, wi), ren, wen = tatp.draw_mix(rng, n_nodes, per,
                                                 tatp.LANES)
    rrow, wrow = rs * per + ri, ws * per + wi
    rk = np.stack(ref.keys(rrow), -1)
    wk = np.stack(ref.keys(wrow), -1)
    wv = rng.randint(0, 2**32, wk.shape[:3] + (sl.VALUE_WORDS,),
                     dtype=np.uint64).astype(np.uint32)
    key = np.asarray(jax.random.PRNGKey(batch))
    return dict(args=(rk, wk, wv, ren, wen), key=key, rrow=rrow, wrow=wrow,
                ren=ren, wen=wen, wv=wv)


def run_batch(cl, tx, state, b):
    rk, wk, wv, ren, wen = cl.put(b["args"])
    t0 = time.perf_counter()
    state, lanes, counts = tx(state, rk, wk, wv, ren, wen,
                              cl.put_replicated(b["key"]))
    jax.block_until_ready((state, lanes, counts))
    dt = time.perf_counter() - t0
    lanes = jax.tree.map(np.asarray, lanes)
    counts = jax.tree.map(np.asarray, counts)
    return state, lanes, counts, dt


def check_batch(cl, lookup, state, ref, b, lanes, counts, rng, name):
    """Hold one batch's results to the host reference, then apply its
    committed writes to the reference in commit_round order."""
    committed, rnd = lanes["committed"], lanes["commit_round"]
    n_commit = int(committed.sum())
    check(n_commit > 0, f"{name}: no transaction committed")
    check(n_commit == int(counts["committed"].sum()),
          f"{name}: per-round commit counts disagree with the lanes")
    if "round_trips" in counts:
        attempted = int((counts["attempts"] > 0).sum())
        rt = float(counts["round_trips"])
        check(rt <= 4.0 * attempted,
              f"{name}: {rt} round trips over {attempted} protocol rounds")
    wen, ren = b["wen"][..., 0], b["ren"]
    writers = committed & wen
    # committed read-only lanes that read rows no committed lane wrote see
    # the value from before the batch
    written_now = set(b["wrow"][writers, 0].tolist())
    readers = committed & ~wen
    n_reads = 0
    for (n, l, r) in zip(*np.nonzero(readers[..., None] & ren)):
        row = int(b["rrow"][n, l, r])
        if row in written_now:
            continue
        check(lanes["read_found"][n, l, r],
              f"{name}: committed read of row {row} found nothing")
        check((lanes["read_values"][n, l, r] == ref.value(row)).all(),
              f"{name}: committed read of row {row} returned a value the "
              "reference does not hold")
        n_reads += 1
    order = np.argsort(rnd[writers], kind="stable")
    rows_w = b["wrow"][writers, 0][order]
    vals_w = b["wv"][writers][:, 0][order]
    for row, val in zip(rows_w, vals_w):
        ref.written[int(row)] = val
    log(f"check {name}: {n_reads} committed reads match the reference")
    touched = np.unique(b["wrow"][wen, 0])
    n_rows = cl.n_nodes * ref.per_node
    others = np.setdiff1d(rng.choice(n_rows, UNWRITTEN_KEYS, replace=False),
                          touched)
    lookup_check(cl, lookup, state, ref, np.concatenate([touched, others]),
                 f"{name} written and unwritten rows")


def load_table(cl, ref, seed_state, lanes):
    """Compile the load step and insert every subscriber."""
    cap = tatp.load_capacity(lanes, cl.n_nodes)
    step = cl.load_step(cap)
    N = cl.n_nodes
    z = np.zeros((N, lanes), np.uint32)
    ex = cl.put((z, z, np.zeros((N, lanes, sl.VALUE_WORDS), np.uint32),
                 np.zeros((N, lanes), bool)))
    step = compile_step(f"load_step (lanes {lanes}, capacity {cap})", step,
                        seed_state, *ex)
    t0 = time.perf_counter()
    n_calls = [0]

    def traced(*a):
        n_calls[0] += 1
        if n_calls[0] % 8 == 0:
            log(f"  load call {n_calls[0]}: {time.perf_counter() - t0:.3f} s")
        return step(*a)

    state, calls, reissued = cl.load(traced, seed_state, ref.klo, ref.khi,
                                     ref.vals, lanes)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    log(f"load: {ref.klo.size} subscribers inserted with ST_OK in {dt:.3f} s "
        f"({calls} calls, {reissued} lanes re-issued after back-pressure)")
    return state


def compile_tx(cl, state):
    _, *ex = cl.tx_arg_shapes()
    ex = [np.zeros(s.shape, s.dtype) for s in ex]
    return compile_step(f"tx_step ({cl.n_nodes} nodes x {tatp.LANES} lanes)",
                        cl.tx_step(), state, *cl.put(tuple(ex[:-1])),
                        cl.put_replicated(ex[-1]))


def compile_lookup(cl, state):
    N = cl.n_nodes
    lanes = -(-CHECK_KEYS // N)
    z = np.zeros((N, lanes), np.uint32)
    return compile_step("lookup_step", cl.lookup_step(), state,
                        *cl.put((z, z, np.zeros((N, lanes), bool))))


def one_chip(args):
    N = 8
    cl = tatp.Cluster(N)
    ref = Reference(*tatp.make_subscribers(args.seed, N, args.per_node))
    log(f"table: {N} nodes x {args.per_node} subscribers, {cl.cfg}")
    state = cl.init_state()
    lanes = min(tatp.LOAD_LANES, args.per_node)
    state = load_table(cl, ref, state, lanes)
    lookup = compile_lookup(cl, state)
    rng = np.random.RandomState(args.seed + 1)
    lookup_check(cl, lookup, state, ref,
                 rng.choice(ref.klo.size, CHECK_KEYS, replace=False),
                 "after load")
    tx = compile_tx(cl, state)
    times = []
    for i in range(args.batches + 1):
        name = "warm-up batch" if i == 0 else f"batch {i}"
        b = draw_batch(rng, ref, N, i)
        state, lanes_out, counts, dt = run_batch(cl, tx, state, b)
        if i:
            times.append(dt)
        log(f"{name}: {dt * 1e3:.3f} ms, committed "
            f"{int(lanes_out['committed'].sum())}/{N * tatp.LANES}, per round "
            f"{counts['committed'].tolist()}, aborts lock/validate/overflow "
            f"{int(counts['abort_lock'].sum())}/"
            f"{int(counts['abort_validate'].sum())}/"
            f"{int(counts['abort_overflow'].sum())}, round trips "
            f"{float(counts['round_trips'])}")
        check_batch(cl, lookup, state, ref, b, lanes_out, counts, rng, name)
    log(f"tx_step ms per batch: {[round(t * 1e3, 3) for t in times]}")


def four_chips(args):
    N = 4
    mesh = jax.make_mesh((N,), ("node",))
    mcl, scl = tatp.Cluster(N, mesh=mesh), tatp.Cluster(N)
    ref = Reference(*tatp.make_subscribers(args.seed, N, args.per_node))
    log(f"table: {N} nodes x {args.per_node} subscribers, mesh {mesh.devices}"
        f" against SimTransport({N}) on {jax.devices()[0]}")
    lanes = min(tatp.LOAD_LANES, args.per_node)
    ms = load_table(mcl, ref, mcl.init_state(), lanes)
    ss = load_table(scl, ref, scl.init_state(), lanes)
    same_arenas(ms, ss, "after load")
    lookup = compile_lookup(mcl, ms)
    mtx, stx = compile_tx(mcl, ms), compile_tx(scl, ss)
    rng = np.random.RandomState(args.seed + 1)
    for i in range(args.batches + 1):
        name = "warm-up batch" if i == 0 else f"batch {i}"
        b = draw_batch(rng, ref, N, i)
        ms, ml, mc, mdt = run_batch(mcl, mtx, ms, b)
        ss, sl_, sc, sdt = run_batch(scl, stx, ss, b)
        for k in ml:
            check(np.array_equal(ml[k], sl_[k]),
                  f"{name}: per-lane {k} differs between mesh and simulator")
        for k in mc:
            check(np.array_equal(mc[k], sc[k]),
                  f"{name}: psum'd {k} counts differ from the simulator's")
        log(f"{name}: mesh {mdt * 1e3:.3f} ms, simulator {sdt * 1e3:.3f} ms, "
            f"committed {int(ml['committed'].sum())}/{N * tatp.LANES}; "
            "per-lane committed/commit_round/read_found/read_values and "
            "per-round counts equal")
        check_batch(mcl, lookup, ms, ref, b, ml, sc, rng, name)
    same_arenas(ms, ss, "after the batches")


def same_arenas(ms, ss, what):
    a, b = np.asarray(ms["arena"]), np.asarray(ss["arena"])
    check(a.shape == b.shape and np.array_equal(a, b),
          f"{what}: mesh and simulator arenas differ")
    log(f"check arenas {what}: mesh == simulator ({a.nbytes} bytes)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the subscriber rows and the batches")
    args = ap.parse_args(argv)
    args.per_node, args.batches = tatp.PER_NODE, BATCHES
    log(f"jax {jax.__version__}")
    info = chip.device_info()
    log(f"devices: platform {info['platform']}, kind {info['kind']}, "
        f"count {info['count']}")
    chip.require_tpu(args.chips)
    log(f"compile cache: {chip.use_compile_cache(ROOT)}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args)
    else:
        one_chip(args)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")
    log(f"total: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["kind"], "count": info["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
