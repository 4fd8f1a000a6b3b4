"""handler.vector_share: percent of the traced device time spent in the
vector owner handlers (scope ``storm.handler.vector``: the read-only RPC
lookups of the read set's fallback).

Exclusive device time per operation, summed by the innermost ``storm.*``
scope of its source path (``harness/layers.py``), over the layers' total.
Nothing where the run was not traced or its program names no layer."""
import pathlib

from chipbench.harness import layers

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(run):
    return layers.share(run, ROOT, "handler.vector")
