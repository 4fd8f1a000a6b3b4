"""handler.serial_share: percent of the traced device time spent in the
serial owner handlers (scope ``storm.handler.serial``: the node-by-node
folds of LOCK, COMMIT and backup writes through the table).

Exclusive device time per operation, summed by the innermost ``storm.*``
scope of its source path (``harness/layers.py``), over the layers' total.
Nothing where the run was not traced or its program names no layer."""
import pathlib

from chipbench.harness import layers

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(run):
    return layers.share(run, ROOT, "handler.serial")
