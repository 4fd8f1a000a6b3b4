"""txloop.attempts_per_commit: transaction attempts the retry engine
made (live lanes entering each protocol round) per transaction committed,
over the window, from tx_loop's per-round counts."""


def read(run):
    att = sum(int(b["counts"]["attempts"].sum()) for b in run.batches)
    com = sum(int(b["counts"]["committed"].sum()) for b in run.batches)
    return att / com if com else None
