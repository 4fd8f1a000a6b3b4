"""client.device_share: percent of the traced device time spent in the
client's own work (scopes ``storm.occ.*``: building requests, parsing
replies, validating and deciding in each OCC step; ``storm.txloop``: the
retry engine's backoff, lane permutes, counts and loop).

Exclusive device time per operation, summed by the innermost ``storm.*``
scope of its source path (``harness/layers.py``), over the layers' total.
Nothing where the run was not traced or its program names no layer."""
import pathlib

from chipbench.harness import layers

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(run):
    return layers.share(run, ROOT, "occ.", "txloop")
