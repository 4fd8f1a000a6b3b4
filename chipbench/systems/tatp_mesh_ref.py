"""Plain reference of the TATP mesh deployment, and the comparison that
decides a run's ``correct``: those of ``systems/tatp_ref.py``, which hold
nothing of the transport.  The same operations on the same rows give the
same answers whether the nodes share a chip or sit one to a chip."""
from chipbench.systems.tatp_ref import judge, make_table  # noqa: F401
