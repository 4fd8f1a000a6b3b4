"""Controls of the TATP mesh cells: ``systems/tatp_controls.py``'s
``stale_reads`` (committed reads served from the table as loaded), a
transform of a run's outputs that holds nothing of the transport."""
from __future__ import annotations

from chipbench.systems.tatp_controls import stale_reads


def control_for(conf: dict, mix: dict):
    """(kind, control): the outputs transform ``stale_reads``."""
    if mix["static_writes"] == 0:
        raise ValueError("no control for a read-only mix")
    return "outputs", stale_reads
