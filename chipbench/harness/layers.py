"""Device time per layer of the program, from a profiler trace whose
operations carry the program's ``storm.*`` named scopes.

The program names each layer of its jitted path with ``jax.named_scope``:
``storm.round.<phase>`` around every exchange round, and inside it the
parts ``storm.pack``, ``storm.exchange``, ``storm.handler.vector``,
``storm.handler.serial``, ``storm.gather`` and ``storm.unpack``; around the
client's work ``storm.occ.<step>`` and ``storm.txloop``.  The compiler
keeps each scope in the ``op_name`` of the operations it produced, and the
trace keeps the compiled program's HLO (with that metadata) in its
``/host:metadata`` plane.

* exclusive time: on each device's ``XLA Ops`` line, inside the window,
  each instant belongs to the operation that started most recently and is
  still running, so a ``while`` keeps its own loop control and not its
  body's time;
* ``parts``: each operation's exclusive seconds under the innermost
  ``storm.*`` scope of its path (``round`` where that is a round's own
  scope), ``unscoped`` where its path holds none;
* ``phases``: the same seconds under the innermost ``storm.round.<phase>``
  of the path, for the operations inside a round;
* ``total_s``: the summed exclusive time, which is the union of the
  operations' time.

Seconds are averaged over the devices that ran an operation, as ``busy_s``
is in ``trace.reduce``.  A trace of a program with no ``storm.*`` scope
gives no layers.
"""
from __future__ import annotations

import pathlib
import re
import time

import numpy as np

from chipbench.harness import trace as TR

SCOPE = re.compile(r"storm\.(\w+(?:\.\w+)*)")
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
STEP_PROGRAM = "jit_step"      # the window's program, as run.py names it


# --- the HLO in the trace ----------------------------------------------------
def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b, i=0, end=None):
    """(field number, value) of one protobuf message in ``b[i:end]``: an
    int for a varint, a (start, end) pair for a length-delimited field."""
    end = len(b) if end is None else end
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = (i, i + n), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _sub(b, f, span):
    """Fields numbered ``f`` of the message at ``span``."""
    return [v for g, v in _fields(b, *span) if g == f]


def _text(b, span):
    return bytes(b[span[0]:span[1]]).decode()


def hlo_protos(xplane_path) -> dict:
    """{program name (``jit_step(42)``): serialized HloProto} from a trace's
    metadata plane (XSpace.planes -> XPlane.event_metadata, XStat bytes)."""
    b = memoryview(pathlib.Path(xplane_path).read_bytes())
    out = {}
    for plane in _sub(b, 1, (0, len(b))):
        names = _sub(b, 2, plane)
        if not names or _text(b, names[0]) != METADATA_PLANE:
            continue
        stat_ids = set()
        for entry in _sub(b, 5, plane):             # map<int64, XStatMetadata>
            for meta in _sub(b, 2, entry):
                f = dict(_fields(b, *meta))
                if 2 in f and _text(b, f[2]) == HLO_PROTO_STAT:
                    stat_ids.add(f.get(1, 0))
        for entry in _sub(b, 4, plane):             # map<int64, XEventMetadata>
            for meta in _sub(b, 2, entry):
                name = _sub(b, 2, meta)
                for stat in _sub(b, 5, meta):
                    f = dict(_fields(b, *stat))
                    if f.get(1, 0) in stat_ids and 6 in f and name:
                        out[_text(b, name[0])] = bytes(b[f[6][0]:f[6][1]])
    return out


def _ids(b, v):
    """A repeated int64 field's values, packed or not."""
    if isinstance(v, int):
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(b, i)
        out.append(x)
    return out


def hlo_paths(proto: bytes) -> dict:
    """Operation name -> JAX source path (``op_name``, ``closed_call/``
    dropped as ``trace.op_paths`` drops it) from one serialized HloProto
    (hlo_module -> computations -> instructions -> name, metadata, called
    computations).  An instruction the compiler added without an
    ``op_name`` (a copy, a slice of a loop's carry) takes the path of the
    instruction that calls its computation: a loop's, a fusion's."""
    b = memoryview(proto)
    comps, entry = {}, None
    for module in _sub(b, 1, (0, len(b))):
        for f, v in _fields(b, *module):
            if f == 6:
                entry = v
            elif f == 3:
                cid, ins = 0, []
                for g, w in _fields(b, *v):
                    if g == 5:
                        cid = w
                    elif g == 2:
                        name, path, called = None, None, []
                        for h, x in _fields(b, *w):
                            if h == 1:
                                name = _text(b, x)
                            elif h == 7:
                                op = _sub(b, 2, x)
                                path = _text(b, op[0]) if op else None
                            elif h == 38:
                                called += _ids(b, x)
                        ins.append((name, path, called))
                comps[cid] = ins
    paths, todo, seen = {}, [(entry, None)], set()
    while todo:
        cid, outer = todo.pop()
        if cid in seen or cid not in comps:
            continue
        seen.add(cid)
        for name, path, called in comps[cid]:
            path = path.replace("closed_call/", "") if path else outer
            if path is not None:
                paths[name] = path
            todo += [(c, path) for c in called]
    return paths


# --- exclusive time ----------------------------------------------------------
def _paint_max(left, right, n):
    """out[j] = the largest i with left[i] <= j < right[i], -1 where none
    (each range split into two blocks of a power of two, pushed down)."""
    idx = np.arange(left.size)
    level = np.floor(np.log2(right - left)).astype(np.int64)
    cur = None
    for k in range(int(level.max(initial=0)), -1, -1):
        tab = np.full(n, -1, np.int64)
        sel = level == k
        np.maximum.at(tab, left[sel], idx[sel])
        np.maximum.at(tab, right[sel] - (1 << k), idx[sel])
        if cur is not None:
            h = 1 << k
            np.maximum(tab, cur, out=tab)
            np.maximum(tab[h:], cur[:n - h], out=tab[h:])
        cur = tab
    return cur


def exclusive(starts, ends, lo, hi):
    """Exclusive seconds of each event (ns times) inside [lo, hi]: every
    instant goes to the event that started most recently and is still
    running; of two that start together, to the one that ends first."""
    s0, e0 = np.asarray(starts, np.float64), np.asarray(ends, np.float64)
    s, e = np.clip(s0, lo, hi), np.clip(e0, lo, hi)
    out = np.zeros(s.size)
    live = np.nonzero(e > s)[0]
    if live.size == 0:
        return out
    order = live[np.lexsort((-e0[live], s0[live]))]
    bounds = np.unique(np.concatenate([s[order], e[order]]))
    owner = _paint_max(np.searchsorted(bounds, s[order]),
                       np.searchsorted(bounds, e[order]), bounds.size - 1)
    seg = np.diff(bounds)
    has = owner >= 0
    out[order] = np.bincount(owner[has], weights=seg[has],
                             minlength=order.size) * 1e-9
    return out


def classify(path):
    """(part, phase) of an operation's source path; phase is None outside
    every round."""
    names = SCOPE.findall(path or "")
    if not names:
        return "unscoped", None
    rounds = [n[len("round."):] for n in names if n.startswith("round.")]
    part = "round" if names[-1].startswith("round.") else names[-1]
    return part, rounds[-1] if rounds else None


def reduce_layers(devices, paths, lo, hi):
    """{parts, phases, total_s} of the ``XLA Ops`` in ``devices`` (as
    ``trace.read_events`` gives them) inside [lo, hi] ns; None where no
    operation's path holds a ``storm.*`` scope."""
    parts, phases, total, n_dev = {}, {}, 0.0, 0
    for lines in devices.values():
        ops = lines.get("XLA Ops", [])
        if not ops:
            continue
        n_dev += 1
        ids, index = np.empty(len(ops), np.int64), {}
        for j, (_, _, name) in enumerate(ops):
            ids[j] = index.setdefault(name, len(index))
        t = exclusive([o[0] for o in ops], [o[1] for o in ops], lo, hi)
        per_name = np.bincount(ids, weights=t, minlength=len(index))
        for name, k in index.items():
            part, phase = classify(paths.get(name))
            parts[part] = parts.get(part, 0.0) + per_name[k]
            if phase is not None:
                phases[phase] = phases.get(phase, 0.0) + per_name[k]
        total += float(t.sum())
    if set(parts) <= {"unscoped"}:
        return None
    avg = lambda d: {k: float(v) / n_dev for k, v in sorted(d.items())}
    return dict(parts=avg(parts), phases=avg(phases), total_s=total / n_dev)


# --- a run's trace -----------------------------------------------------------
def of_run(run, root):
    """The layers of a traced run's window (None where the run was not
    traced or its program names no layer).  The run's trace is in
    ``<root>/.bench_trace/<cell>``, as ``harness/run.py`` records it; the
    window is the one ``trace.reduce`` took.  Read once a run (kept on
    ``run``), and logged once."""
    if run.trace is None:
        return None
    if not hasattr(run, "layers"):
        t0 = time.perf_counter()
        xplane = TR.find_xplane(pathlib.Path(root) / ".bench_trace"
                                / run.cell["name"])
        spans, devices = TR.read_events(xplane)
        lo = min(s for s, _, _ in spans)
        hi = (max(e for lines in devices.values() for evs in lines.values()
                  for _, e, _ in evs)
              if run.trace["truncated"] else max(e for _, e, _ in spans))
        # the step's runs in the trace name its program; a trace cut inside
        # the run may hold none, and then every step program is read
        programs = {n for lines in devices.values()
                    for _, _, n in lines.get("XLA Modules", [])
                    if n.startswith(STEP_PROGRAM)}
        paths = {}
        for name, proto in hlo_protos(xplane).items():
            if name in programs or (not programs
                                    and name.startswith(STEP_PROGRAM)):
                paths.update(hlo_paths(proto))
        run.layers = reduce_layers(devices, paths, lo, hi)
        print(f"layers: {run.layers}, read in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
    return run.layers


def share(run, root, *prefixes):
    """Percent of the layers' ``total_s`` spent in the parts whose names
    start with one of ``prefixes``."""
    lay = of_run(run, root)
    if lay is None or lay["total_s"] <= 0:
        return None
    got = sum(v for k, v in lay["parts"].items() if k.startswith(prefixes))
    return 100.0 * got / lay["total_s"]
