"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the result line.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up makes the table and the traffic from the seed on the host, compiles
(or loads from the persistent cache) the load step and the step the window
drives, loads the table through the program, and runs one warm-up batch.
The window is a closed loop of batches, one in flight at a time, for
``--seconds``: the host draws, puts, dispatches and fetches, and keeps every
batch's inputs and outputs.  After it, the peak device memory is read and
every batch (warm-up included) is replayed on the plain reference; the table
is read back through the program and compared.

With ``--trace 1`` the profiler records the window's first batches (at
least ``TRACE_MIN_BATCHES``, up to ``TRACE_SECONDS``), or, where the
warm-up batch took longer than ``TRACE_WHOLE_S``, the last
``TRACE_PART_S`` of the first batch alone, and the result carries the
cell's per-layer metrics; otherwise its end-to-end metrics.
The last line of standard output is the result; the compared numbers, each
with its limit, are the last lines of standard error and the result's last
key.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time
import types

from chipbench import peaks
from chipbench.harness import spec as S
from chipbench.harness import trace as TR
from chipbench.harness.traffic import Traffic

TRACE_SECONDS = 0.25     # the profiler records the window's first batches:
TRACE_MIN_BATCHES = 1    # at least this many, and up to TRACE_SECONDS;
TRACE_WHOLE_S = 4.0      # but where a batch takes longer than this, only
TRACE_PART_S = 1.5       # the first batch's last TRACE_PART_S: collecting
                         # the profile takes about 35 s for each second the
                         # device ran under it, until the profiler stops


def log(msg):
    print(msg, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run_batch(dep, table, b, span, before_fetch=None):
    """Draw-to-fetch of one batch; records its inputs, outputs and times.
    ``before_fetch(t0)`` runs once the batch is dispatched at ``t0``."""
    with span("put"):
        args = dep.put(table, b)
    t0 = time.perf_counter()
    with span("dispatch"):
        out = dep.dispatch(args)
    if before_fetch is not None:
        before_fetch(t0)
    with span("fetch"):
        lanes, counts = dep.fetch(out)
    b.update(lanes)
    b["counts"], b["t"] = counts, (t0, time.perf_counter())
    return b


def window(dep, table, traffic, seconds, span, tracer):
    """The closed loop: batches until ``seconds`` have passed.  Returns
    (batches, wall seconds to the last batch's results)."""
    batches = []
    start = time.perf_counter()
    while True:
        with span("draw"):
            b = traffic.batch()
        late = tracer is not None and tracer.late is not None and not batches
        batches.append(run_batch(dep, table, b, span,
                                 tracer.start_late if late else None))
        now = time.perf_counter()
        if tracer is not None and tracer.active and (
                len(batches) >= TRACE_MIN_BATCHES
                and now - start >= min(seconds, TRACE_SECONDS)):
            tracer.stop()
        if now - start >= seconds:
            return batches, now - start


class Tracer:
    """The profiler over the window's first batches, host spans included
    and the Python tracer off.  With ``late`` it starts that many seconds
    after the first batch's dispatch instead, so that the device runs under
    it for the batch's last part alone: the trace is then ``cut``."""

    def __init__(self, jax, log_dir, late=None):
        self.jax, self.dir, self.late = jax, log_dir, late
        self.active, self.cut = False, late is not None

    def start(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.active = True

    def start_late(self, t0):
        time.sleep(max(0.0, t0 + self.late - time.perf_counter()))
        self.start()

    def stop(self):
        self.jax.profiler.stop_trace()
        self.active = False


def main(argv=None, t0=None, *, require_chip=True, overrides=None,
         patch=None, controls=(), root=S.ROOT):
    """Returns the exit code.  ``overrides`` ({"config": {...}, "traffic":
    {...}}) and ``patch(dep, table)`` are for rehearsals on the CPU at small
    sizes and for runs with a planted fault; ``controls`` are transforms of
    the run's outputs judged beside the sound ones (``control.py``).  A
    measurement passes none of them."""
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    root = pathlib.Path(root)
    spec = S.load_spec(root)
    cell = S.cell(spec, args.workload)
    conf = S.config(spec, cell["config"], root)
    mix = S.traffic(cell["traffic"], root / "chipbench")
    conf.update((overrides or {}).get("config", {}))
    mix.update((overrides or {}).get("traffic", {}))

    import jax
    info = device_info(jax)
    if require_chip:
        if info["platform"] != "tpu" or info["count"] < cell["chips"]:
            print(f"no TPU found: the cell needs {cell['chips']} TPU chip(s), "
                  f"JAX reports {info['count']} x {info['platform']} "
                  f"({info['kind']})", file=sys.stderr)
            return 2
        peak_table = peaks.peaks(info["kind"])
        cache = root / ".jax_cache"
        jax.config.update("jax_compilation_cache_dir", str(cache))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        log(f"compile cache: {cache}")
    log(f"jax {jax.__version__}, devices {info}")
    system, ref = S.system(conf["system"], root / "chipbench")
    readers = [(m, S.reader(m["name"], root / "chipbench"))
               for m in S.metrics_for(spec, cell["name"], args.trace)]
    span = jax.profiler.TraceAnnotation

    def named(phase):
        return span(TR.SPAN_PREFIX + phase)

    # --- set-up -------------------------------------------------------------
    setup = {}
    t = time.perf_counter()
    table = ref.make_table(args.seed, conf)
    traffic = Traffic(mix, nodes=conf["nodes"], rows=table.rows,
                      value_words=conf["value_words"], seed=args.seed)
    setup["data_s"] = time.perf_counter() - t
    dep = system.Deployment(conf, mix)
    t = time.perf_counter()
    dep.compile()
    setup["compile_s"] = time.perf_counter() - t
    if patch is not None:
        patch(dep, table)
    t = time.perf_counter()
    load = dep.load(table)
    setup["load_s"] = time.perf_counter() - t
    t = time.perf_counter()
    warm = run_batch(dep, table, traffic.batch(), named)
    setup["warm_s"] = time.perf_counter() - t
    setup["total_s"] = time.perf_counter() - t0
    log(f"set-up: {json.dumps(setup)}; load {load}")

    # --- the window ---------------------------------------------------------
    tracer = None
    if args.trace:
        late = (setup["warm_s"] - TRACE_PART_S
                if setup["warm_s"] > TRACE_WHOLE_S else None)
        tracer = Tracer(jax, root / ".bench_trace" / cell["name"], late)
        if late is None:
            tracer.start()
    batches, window_s = window(dep, table, traffic, args.seconds, named,
                               tracer)
    if tracer is not None and tracer.active:
        tracer.stop()
    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    ms = [(b["t"][1] - b["t"][0]) * 1e3 for b in batches]
    log(f"window: {len(batches)} batches in {window_s:.6f} s, batch ms "
        f"min {min(ms):.4f} median {sorted(ms)[len(ms) // 2]:.4f} "
        f"max {max(ms):.4f}")
    if require_chip:
        log(f"peak device memory: {mem_peak} bytes, "
            f"{100 * mem_peak / peak_table['hbm_bytes']:.3f}% of the "
            f"{peak_table['hbm_bytes']} bytes of HBM ({peak_table['source']})")

    trace = None
    if tracer is not None:
        hlo = getattr(dep.step_fn, "as_text", lambda: "")()
        trace = TR.reduce_dir(tracer.dir, "jit_step", TR.op_paths(hlo),
                              cut=tracer.cut)
        log(f"trace: busy {trace['busy_s']:.6f} s of {trace['window_s']:.6f} s,"
            f" {trace['step_runs']} step runs, truncated {trace['truncated']}")

    # --- the comparison -----------------------------------------------------
    t = time.perf_counter()
    all_batches = [warm] + batches
    readback = lambda rows: dep.readback(table, rows)
    checks, detail = ref.judge(table, all_batches, readback, args.seed)
    correct = all(v <= lim for _, v, lim in checks)
    log(f"check: {detail}, {time.perf_counter() - t:.3f} s")
    judged = {}
    for ctl in controls:
        c_checks, c_detail = ref.judge(
            table, *ctl(table, all_batches, readback), args.seed)
        judged[ctl.__name__] = {n: {"value": v, "limit": lim}
                                for n, v, lim in c_checks}
        log(f"control {ctl.__name__}: {c_detail}")

    # what a metric reader may read: set-up times, the window's batches
    # (inputs, outputs, counts, host times), the trace's reduction (or
    # None), the comparison's counts, and the cell with its parts
    run = types.SimpleNamespace(setup=setup, load=load, batches=batches,
                                window_s=window_s, trace=trace, check=detail,
                                conf=conf, traffic=mix, cell=cell)
    metrics = {}
    for m, rd in readers:
        v = rd.read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    attempted = sum(b["committed"].size for b in batches)
    failed = sum(int((~b["committed"]).sum()) for b in batches)
    device = dict(info, memory_peak_bytes=mem_peak)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = trace["breakdown"]
    if judged:
        result["controls"] = judged
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    print(f"correct {bool(correct)}", file=sys.stderr)
    for n, v, lim in checks:
        print(f"{n} {v} limit {lim}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
