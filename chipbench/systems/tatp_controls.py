"""Controls of the TATP cells: the program with one guarantee the
configuration states broken, which the comparison has to find.
``control_for`` picks the one a cell can show, with how it applies:

* ``primary_only`` (f > 0), a patch ``(dep, table)`` applied after
  compilation: the window's step is the program's own unreplicated path
  (``rep=None``) over a table loaded with every copy, so committed writes
  reach the primary alone;
* ``stale_reads`` (f = 0, a mix with writes), a transform of a run's
  outputs ``(table, batches, readback) -> (batches, readback)``: committed
  reads are served from the table as loaded, a client read cache that is
  never invalidated.

A transform is judged on the same run as the sound program (the harness's
``controls``); a patch needs a run of its own.
"""
from __future__ import annotations

import numpy as np


def primary_only(dep, table):
    fn, shapes = dep.step_program(f=0)
    dep.step_fn = fn.lower(*shapes).compile()


def stale_reads(table, batches, readback):
    out = [dict(b, read_values=np.where(b["read_found"][..., None],
                                        table.vals[b["rrow"]],
                                        b["read_values"]))
           for b in batches]
    return out, readback


def control_for(conf: dict, mix: dict):
    """(kind, control): kind "patch" or "outputs"."""
    if conf["replication_f"] > 0:
        return "patch", primary_only
    if mix["static_writes"] == 0:
        raise ValueError("no control for a read-only mix")
    return "outputs", stale_reads
