"""The traffic generator: exact mix per batch, distinct rows per
transaction, the same batches from the same seed."""
import numpy as np

from chipbench.harness import spec as S
from chipbench.harness.traffic import Traffic, type_counts


def make(mix, seed=2**31 + 12345, rows=5000, **kw):
    params = dict(S.traffic(mix), **kw)
    return Traffic(params, nodes=8, rows=rows, value_words=27, seed=seed)


def test_type_counts_are_largest_remainders():
    types = S.traffic("tatp_mix")["types"]
    assert type_counts(types, 512).tolist() == [358, 51, 82, 21]
    assert type_counts(types, 512).sum() == 512


def test_every_batch_carries_the_mix_exactly():
    t = make("tatp_mix")
    want = sorted(zip(t.n_reads.tolist(), t.n_writes.tolist()))
    for _ in range(3):
        b = t.batch()
        got = sorted(zip(b["ren"].sum(-1).ravel().tolist(),
                         b["wen"].sum(-1).ravel().tolist()))
        assert got == want
        rows = np.concatenate([b["rrow"], b["wrow"]], -1).reshape(512, -1)
        assert (np.sort(rows, 1)[:, 1:] != np.sort(rows, 1)[:, :-1]).all()
        assert b["wval"].shape == (8, 64, t.wr, 27)


def test_same_seed_same_batches_other_seed_other_rows():
    a, b, c = make("tatp_mix"), make("tatp_mix"), make("tatp_mix", seed=7)
    for _ in range(2):
        x, y, z = a.batch(), b.batch(), c.batch()
        for k in x:
            assert np.array_equal(x[k], y[k])
        assert not np.array_equal(x["rrow"], z["rrow"])

