"""Plain reference of the TATP subscriber deployments, and the comparison
that decides a run's ``correct``.  It imports nothing of the program and
takes nothing the program made: the table comes from the seed, and the
committed writes are replayed on a numpy copy of it.

Semantics held to (the guarantees the configuration states):

* a transaction of a batch that commits in round r reads, for every row it
  reads, the value after all commits of earlier batches and of rounds < r
  of its batch (every protocol round reads before any of its commits);
* after the window every row holds the value of its last committed write,
  or its loaded value, on each of its 1 + f copies;
* under uniform keys nearly every offered transaction commits within
  max_rounds: a lane reported uncommitted is one the reference never checks.
"""
from __future__ import annotations

import numpy as np

SAMPLE_ROWS = 2048     # unwritten rows read back after the window

# The compared numbers (readings in PERF.md):
# * wrong_answers counts committed reads, rows and copies whose value differs
#   from the reference or was not found: an exact comparison, limit 0 (0 on
#   every sound run, at least 1 under each control);
# * uncommitted_share, the share of offered transactions that did not commit
#   within max_rounds, so that a step which drops lanes as aborts cannot pass
#   on the answers it does give: 0 on every sound run, 0.5 with half of each
#   batch dropped; limit 0.01.
LIMIT = 0
UNCOMMITTED_LIMIT = 0.01


def distinct_uint32(rng, n: int, lo: int = 0, hi: int = 2**32 - 2):
    """``n`` distinct uint32 keys, uniform over [lo, hi), in random order
    (hi stays below the empty-slot marker 0xFFFFFFFF)."""
    out = np.empty(0, np.uint64)
    while out.size < n:
        out = np.unique(np.concatenate(
            [out, rng.integers(lo, hi, 2 * n, dtype=np.uint64)]))
    return rng.permutation(out)[:n].astype(np.uint32)


class Table:
    """The loaded subscriber rows: key words and value of every row."""

    def __init__(self, klo, khi, vals):
        self.klo, self.khi, self.vals = klo, khi, vals
        self.rows = klo.size


def make_table(seed: int, conf: dict) -> Table:
    rng = np.random.default_rng([seed, 0])
    n = conf["subscribers"]
    klo = distinct_uint32(rng, n)
    khi = rng.integers(0, 2**31, n, dtype=np.uint32)
    vals = rng.integers(0, 2**32, (n, conf["value_words"]), dtype=np.uint32)
    return Table(klo, khi, vals)


class Reference:
    """The table as the reference holds it while batches are replayed."""

    def __init__(self, table: Table):
        self.table = table
        self.cur = table.vals.copy()
        self.touched = np.zeros(table.rows, bool)

    def replay(self, batches) -> dict:
        """Hold every committed read to the replayed table, then apply the
        batch's committed writes round by round."""
        reads = read_bad = 0
        for b in batches:
            com = b["committed"].reshape(-1)
            rnd = b["commit_round"].reshape(-1)
            rrow = b["rrow"].reshape(com.size, -1)
            ren = b["ren"].reshape(com.size, -1)
            found = b["read_found"].reshape(ren.shape)
            got = b["read_values"].reshape(ren.shape + self.cur.shape[1:])
            wrow = b["wrow"].reshape(com.size, -1)
            wen = b["wen"].reshape(com.size, -1)
            wval = b["wval"].reshape(wen.shape + self.cur.shape[1:])
            for r in np.unique(rnd[com]):
                sel = com & (rnd == r)
                m = sel[:, None] & ren
                rows = rrow[m]
                bad = ~found[m] | (got[m] != self.cur[rows]).any(axis=-1)
                reads += rows.size
                read_bad += int(bad.sum())
                wm = sel[:, None] & wen
                rows_w = wrow[wm]
                self.cur[rows_w] = wval[wm]
                self.touched[rows_w] = True
        return dict(reads=reads, read_mismatch=read_bad)

    def rows_to_check(self, seed: int) -> np.ndarray:
        """Every written row and a sample of the others drawn from the
        seed."""
        rng = np.random.default_rng([seed, 2])
        written = np.nonzero(self.touched)[0]
        others = np.nonzero(~self.touched)[0]
        k = min(SAMPLE_ROWS, others.size)
        return np.concatenate([written, rng.choice(others, k, replace=False)])

    def compare_rows(self, rows, found, values) -> dict:
        """found (copies, n), values (copies, n, words): what the program
        holds for ``rows`` on each copy."""
        bad = ~found | (values != self.cur[rows][None]).any(axis=-1)
        return dict(rows=rows.size, copies=found.shape[0],
                    row_mismatch=int(bad[0].sum()),
                    copy_mismatch=int(bad[1:].sum()))


def judge(table: Table, batches, readback, seed: int):
    """The comparison that decides ``correct``.  ``batches`` are every
    batch the step ran (warm-up first), in order; ``readback(rows)`` reads
    ``rows`` back from the table the program holds: (found (copies, n),
    values (copies, n, words)).  Returns ([(name, value, limit)], the
    counts behind it)."""
    ref = Reference(table)
    detail = ref.replay(batches)
    rows = ref.rows_to_check(seed)
    detail.update(ref.compare_rows(rows, *readback(rows)))
    detail["offered"] = sum(b["committed"].size for b in batches)
    detail["uncommitted"] = sum(int((~b["committed"]).sum()) for b in batches)
    wrong = (detail["read_mismatch"] + detail["row_mismatch"]
             + detail["copy_mismatch"])
    return [("wrong_answers", wrong, LIMIT),
            ("uncommitted_share", detail["uncommitted"] / detail["offered"],
             UNCOMMITTED_LIMIT)], detail
