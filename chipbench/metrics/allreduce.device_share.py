"""allreduce.device_share: percent of the traced device time spent summing
the batch's counts across the chips (scope ``storm.allreduce``: the psum
over the mesh axis of the per-round transaction counts and the exchange
byte count).

Exclusive device time per operation, summed by the innermost ``storm.*``
scope of its source path (``harness/layers.py``), over the layers' total.
Nothing where the run was not traced or its program has no such scope."""
import pathlib

from chipbench.harness import layers

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(run):
    lay = layers.of_run(run, ROOT)
    if lay is None or "allreduce" not in lay["parts"]:
        return None
    return layers.share(run, ROOT, "allreduce")
