"""tx_p95_ms: 95th percentile, over every transaction committed in the
window, of the time from its batch's dispatch to the batch's results on
the host (host clock).  In a closed batch a transaction's latency is its
batch's time."""
import numpy as np


def read(run):
    ms = np.array([(b["t"][1] - b["t"][0]) * 1e3 for b in run.batches])
    n = np.array([int(b["committed"].sum()) for b in run.batches])
    if n.sum() == 0:
        return None
    return float(np.percentile(np.repeat(ms, n), 95))
