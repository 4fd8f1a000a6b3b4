"""exchange.device_share: percent of the traced device time spent moving a
round's messages (scopes ``storm.pack``, ``storm.exchange`` and
``storm.unpack``: routing lanes into send buffers, the all-to-alls, and
picking replies back into lane order).

Exclusive device time per operation, summed by the innermost ``storm.*``
scope of its source path (``harness/layers.py``), over the layers' total.
Nothing where the run was not traced or its program names no layer."""
import pathlib

from chipbench.harness import layers

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(run):
    return layers.share(run, ROOT, "pack", "exchange", "unpack")
