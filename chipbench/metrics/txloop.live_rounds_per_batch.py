"""txloop.live_rounds_per_batch: the protocol rounds a batch ran with live
lanes (rounds whose per-round ``attempts`` count is above 0, the cluster's
on a mesh), per batch of the window, from tx_loop's per-round counts.  A
retry engine that skips rounds with no live lane runs exactly these."""


def read(run):
    if not run.batches:
        return None
    return (sum(int((b["counts"]["attempts"] > 0).sum())
                for b in run.batches) / len(run.batches))
