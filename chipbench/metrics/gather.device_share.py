"""gather.device_share: percent of the traced device time spent in the
one-sided gathers (scope ``storm.gather``: the owner side of one-sided
reads, and of one-sided writes' scatters).

Exclusive device time per operation, summed by the innermost ``storm.*``
scope of its source path (``harness/layers.py``), over the layers' total.
Nothing where the run was not traced or its program names no layer."""
import pathlib

from chipbench.harness import layers

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(run):
    return layers.share(run, ROOT, "gather")
