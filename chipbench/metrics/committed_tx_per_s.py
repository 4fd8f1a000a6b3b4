"""committed_tx_per_s: transactions committed in the window over the
window's wall time, up to the last batch's results on the host (host
clock)."""


def read(run):
    return sum(int(b["committed"].sum()) for b in run.batches) / run.window_s
