"""Reduction of a profiler trace (``.xplane.pb``) to the device numbers the
per-layer metrics and the result line read.

Device planes are named ``/device:<kind>:<i>``; on each, the line
``XLA Ops`` holds one event per operation run and ``XLA Modules`` one per
program run.  The harness's host spans are events named ``bench.<phase>``
(draw, put, dispatch, fetch) on the host plane, on the same clock.

* window: from the first host span's start to the last one's end;
* busy: the union of program runs (``XLA Modules``) and operations inside
  the window, averaged over the devices that ran anything: the device runs
  a program's loops itself, so a program run is busy from end to end (the
  operations count where a trace stopped inside a run holds no event of
  the run itself);
* step time: the summed durations of the runs of the step's program,
  per run;
* breakdown: the operations that took most device time, and the longest
  idle gaps, each named by the host span that covers most of it.

The profiler keeps a bounded number of device events (some 301 MB of
trace, about 6.3 million operations, on a v5e): a program run with more
operations than that is cut.  A trace that holds more than ``CUT_OPS``
operations and whose device events stop more than ``SLACK_NS`` before the
host's last span, or one the harness stopped inside a batch (``cut``), is
``truncated``: its window is clipped to the device's last event, and the
step time is not given.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

SPAN_PREFIX = "bench."
TOP = 10
SLACK_NS = 50e6       # the last fetch after the device's last event, at most
CUT_OPS = 5_000_000   # operations in a trace near the profiler's bound


def find_xplane(log_dir) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(starts, ends):
    """Disjoint sorted intervals covering the given ones."""
    if starts.size == 0:
        return starts, ends
    o = np.argsort(starts, kind="stable")
    s, e = starts[o], np.maximum.accumulate(ends[o])
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > e[:-1]
    idx = np.nonzero(new)[0]
    return s[idx], np.append(e[idx[1:] - 1], e[-1])


def _clip(s, e, lo, hi):
    s, e = np.maximum(s, lo), np.minimum(e, hi)
    keep = e > s
    return s[keep], e[keep]


def op_name(text: str) -> str:
    """``fusion.406`` from an operation's HLO text ``%fusion.406 = ...``."""
    return text.split(" = ", 1)[0].lstrip("%")


_HLO_LINE = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*?metadata=\{op_name="([^"]*)"',
                       re.MULTILINE)


def op_paths(hlo_text: str) -> dict:
    """Operation name -> the JAX source path it came from (``while/body/
    while``), from a compiled program's HLO text, so that the breakdown
    says which loop an operation number is."""
    return {m.group(1): m.group(2).replace("closed_call/", "")
            for m in _HLO_LINE.finditer(hlo_text)}


def read_events(path):
    """(host spans [(start, end, phase)], {device: {line: [(s, e, name)]}})
    from one xplane file, times in ns."""
    from jax.profiler import ProfileData
    spans, devices = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns,
                                      ev.name[len(SPAN_PREFIX):]))
        elif plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [(ev.start_ns, ev.end_ns,
                                         op_name(ev.name))
                                        for ev in line.events]
            if any(lines.values()):
                devices[plane.name] = lines
    return spans, devices


def reduce(spans, devices, step_module: str, paths=None, cut=False) -> dict:
    """busy_s, window_s, step_s (device seconds per step run, None where
    truncated), step_runs, truncated, breakdown {device_ops, idle_gaps}.
    ``paths`` (from ``op_paths``) adds each operation's source path to its
    name in the breakdown; ``cut`` says the profiler was stopped inside a
    batch."""
    if not spans:
        raise ValueError("the trace holds no bench.* host span")
    if not devices:
        raise ValueError("the trace holds no device operation")
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    dev_end = max(e for lines in devices.values()
                  for evs in lines.values() for _, e, _ in evs)
    n_ops = sum(len(lines.get("XLA Ops", [])) for lines in devices.values())
    truncated = cut or (n_ops > CUT_OPS and hi - dev_end > SLACK_NS)
    if truncated:
        hi = dev_end
    busy, op_time, gaps = [], {}, []
    step_s, step_runs = 0.0, 0
    for lines in devices.values():
        evs = lines.get("XLA Modules", []) + lines.get("XLA Ops", [])
        st = np.array([o[0] for o in evs], np.float64)
        en = np.array([o[1] for o in evs], np.float64)
        s, e = _clip(*_union(st, en), lo, hi)
        busy.append(float((e - s).sum()) * 1e-9)
        gs, ge = np.append(lo, e), np.append(s, hi)
        keep = ge > gs
        gaps += list(zip(gs[keep], ge[keep]))
        for (a, b, name) in lines.get("XLA Ops", []):
            if b > lo and a < hi:
                op_time[name] = op_time.get(name, 0.0) + (b - a) * 1e-9
        for (a, b, name) in lines.get("XLA Modules", []):
            if name.startswith(step_module) and b > lo and a < hi:
                step_s += (b - a) * 1e-9
                step_runs += 1
    gaps.sort(key=lambda g: g[0] - g[1])
    sp = np.array([(s, e) for s, e, _ in spans], np.float64)
    names = [n for _, _, n in spans]

    def covering(a, b):
        cover = np.minimum(sp[:, 1], b) - np.maximum(sp[:, 0], a)
        i = int(np.argmax(cover))
        return names[i] if cover[i] > 0 else "none"

    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    if paths:
        top_ops = [(f"{n} {paths[n]}" if n in paths else n, t)
                   for n, t in top_ops]
    return dict(
        busy_s=sum(busy) / len(busy),
        window_s=(hi - lo) * 1e-9,
        step_s=None if truncated else step_s / max(step_runs, 1),
        step_runs=step_runs,
        truncated=truncated,
        breakdown=dict(
            device_ops=[[n, t] for n, t in top_ops],
            idle_gaps=[[covering(a, b), (b - a) * 1e-9]
                       for a, b in gaps[:TOP]]))


def reduce_dir(log_dir, step_module: str, paths=None, cut=False) -> dict:
    return reduce(*read_events(find_xplane(log_dir)), step_module, paths,
                  cut)
