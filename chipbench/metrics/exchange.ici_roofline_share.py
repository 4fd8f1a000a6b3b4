"""exchange.ici_roofline_share: percent of the inter-chip links' peak that
the exchanges' all_to_alls reach while they run, in the traced batches.

    100 x (ICI bytes a chip's all_to_alls send in the traced batches)
        / (their device time on that chip x the chip's ICI peak)

summed over the chips.  The bytes are the step's ``ici_bytes`` count for a
batch (the operand share each all_to_all sends to the other chips, padding
words included; summed over the chips, so a chip's share is 1/nodes of it,
one node a chip) times the step runs the trace holds on the chip.  The time
is the union, on the chip's ``XLA Ops`` line, of the operations named
``all_to_all`` (or ``all-to-all``) whose source path's innermost
``storm.*`` scope is ``storm.exchange``; an asynchronous start/done pair
counts from the start's beginning to the done's end.  The peak is
``peaks.py``'s ``ici_bits_per_s``.

It cannot pass 100%: every byte counted leaves the chip inside that union,
over links that carry at most the peak, and bytes that cross a second link
on the way to a chip two hops away are counted once.

Nothing where the run was not traced, the trace was cut inside a batch,
the step counts no ICI bytes, or the device has no published ICI peak (a
rehearsal on the CPU)."""
import pathlib

import jax
import numpy as np

from chipbench import peaks
from chipbench.harness import layers
from chipbench.harness import trace as TR

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAMES = ("all_to_all", "all-to-all")


def _intervals(ops, paths):
    """(start, end) ns of each all_to_all of the exchange on one device's
    ``XLA Ops`` line, a start/done pair joined."""
    out, starts = [], []
    for s, e, name in sorted(ops):
        if not name.startswith(NAMES) or layers.classify(
                paths.get(name))[0] != "exchange":
            continue
        if "start" in name:
            starts.append(s)
        elif "done" in name:
            out.append((starts.pop(0) if starts else s, e))
        else:
            out.append((s, e))
    return out


def device_kind():
    return jax.devices()[0].device_kind


def read(run):
    counts = [b["counts"].get("ici_bytes") for b in run.batches]
    kind = device_kind()
    if (run.trace is None or run.trace["truncated"] or not counts
            or None in counts or kind not in peaks.PEAKS):
        return None
    per_chip = float(counts[0]) / run.conf["nodes"]
    xplane = TR.find_xplane(ROOT / ".bench_trace" / run.cell["name"])
    spans, devices = TR.read_events(xplane)
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    paths = {}
    for name, proto in layers.hlo_protos(xplane).items():
        if name.startswith(layers.STEP_PROGRAM):
            paths.update(layers.hlo_paths(proto))
    sent = busy = 0.0
    for lines in devices.values():
        runs = sum(1 for a, b, n in lines.get("XLA Modules", [])
                   if n.startswith(layers.STEP_PROGRAM) and b > lo and a < hi)
        iv = _intervals(lines.get("XLA Ops", []), paths)
        if not iv:
            continue
        st, en = (np.array(x, np.float64) for x in zip(*iv))
        s, e = TR._clip(*TR._union(st, en), lo, hi)
        busy += float((e - s).sum()) * 1e-9
        sent += per_chip * runs
    if busy <= 0:
        return None
    return 100.0 * sent / (busy * peaks.peaks(kind)["ici_bits_per_s"] / 8)
