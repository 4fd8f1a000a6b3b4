"""exchange.round_trips_per_batch: the round_trips count tx_loop returns
(exchange rounds that carried traffic, summed over its protocol rounds),
per batch of the window."""


def read(run):
    if not run.batches:
        return None
    return (sum(float(b["counts"]["round_trips"]) for b in run.batches)
            / len(run.batches))
