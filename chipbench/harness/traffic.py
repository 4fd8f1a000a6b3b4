"""The one traffic generator.  It reads a mix's parameter file
(``traffic/<mix>.json``) and draws closed batches of transactions from the
seed, as row numbers of the configured table plus the values to write.

Parameter file keys:

  lanes          transactions in flight per node; one closed batch is
                 nodes x lanes transactions
  static_reads   read slots of every transaction (tx_loop's static RD)
  static_writes  write slots of every transaction (static WR; 0 = the step
                 has no write set at all)
  types          the mix: [{"name", "share", "reads", "writes"}, ...]

Every batch holds each type in exactly its share of the lanes (largest
remainders), shuffled over the lanes, so every batch and every seed carry
the same amount of work; which rows, which values and which lanes change.
Rows are uniform over the whole population.  The rows of one transaction are distinct (read and write sets disjoint).
"""
from __future__ import annotations

import numpy as np


def type_counts(types, total: int) -> np.ndarray:
    """Lanes per type: shares of ``total`` by largest remainders."""
    share = np.array([t["share"] for t in types], np.float64)
    if not np.isclose(share.sum(), 1.0):
        raise ValueError(f"traffic shares sum to {share.sum()}, not 1")
    exact = share * total
    n = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - n), kind="stable")
    n[order[:total - n.sum()]] += 1
    return n


class Traffic:
    """Batches of one mix over a table of ``rows`` rows spread over
    ``nodes`` client nodes; ``value_words`` uint32 words per row value."""

    def __init__(self, params: dict, *, nodes: int, rows: int,
                 value_words: int, seed: int):
        self.p = params
        self.nodes, self.rows, self.value_words = nodes, rows, value_words
        self.lanes = params["lanes"]
        self.rd, self.wr = params["static_reads"], params["static_writes"]
        self.rng = np.random.default_rng([seed, 1])
        types = params["types"]
        for t in types:
            if t["reads"] > self.rd or t["writes"] > self.wr:
                raise ValueError(f"type {t['name']} exceeds the static "
                                 f"read/write slots {self.rd}/{self.wr}")
        counts = type_counts(types, nodes * self.lanes)
        self.n_reads = np.repeat([t["reads"] for t in types], counts)
        self.n_writes = np.repeat([t["writes"] for t in types], counts)

    def _rows(self, shape):
        return self.rng.integers(0, self.rows, shape)

    def batch(self) -> dict:
        """One closed batch: rrow (N, lanes, RD), ren, wrow (N, lanes, WR),
        wen, wval (N, lanes, WR, value_words) and ``key``, two uint32 words
        of the PRNG key tx_loop's backoff draws from."""
        N, L, rd, wr = self.nodes, self.lanes, self.rd, self.wr
        perm = self.rng.permutation(N * L)
        n_r, n_w = self.n_reads[perm], self.n_writes[perm]
        rows = self._rows((N * L, rd + wr))
        while True:                      # distinct rows within a transaction
            s = np.sort(rows, axis=1)
            dup = (s[:, 1:] == s[:, :-1]).any(axis=1)
            if not dup.any():
                break
            rows[dup] = self._rows((int(dup.sum()), rd + wr))
        slot = np.arange(max(rd, wr, 1))
        ren = slot[:rd] < n_r[:, None]
        wen = slot[:wr] < n_w[:, None]
        wval = self.rng.integers(0, 2**32, (N * L, wr, self.value_words),
                                 dtype=np.uint32)
        key = self.rng.integers(0, 2**32, 2, dtype=np.uint32)
        sh = lambda x: x.reshape((N, L) + x.shape[1:])
        return dict(rrow=sh(rows[:, :rd]), ren=sh(ren), wrow=sh(rows[:, rd:]),
                    wen=sh(wen), wval=sh(wval), key=key)
