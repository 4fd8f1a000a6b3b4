"""exchange.ici_bytes_per_batch: the bytes a batch's exchanges put on the
inter-chip links, summed over the chips: the ``ici_bytes`` count of the
step (``WireStats.ici_bytes``, the operand share each chip's all_to_all
sends to the other chips, padding words included, for every exchange the
batch runs), per batch of the window.  Nothing where the step does not
count them (one device, or a program without the counter)."""


def read(run):
    got = [float(b["counts"]["ici_bytes"]) for b in run.batches
           if "ici_bytes" in b["counts"]]
    if not got or len(got) < len(run.batches):
        return None
    return sum(got) / len(got)
