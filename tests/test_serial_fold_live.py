"""The serial fold steps through the live inbox cells only.

``roundsched.serial_apply`` folds a node's inbox through its state in flat
(source-major) scan order, skipping masked cells.  Against a full-cell
``lax.scan`` reference (kept here, not in ``src``) it must leave the arena
bit for bit as the reference does, give every live cell the reference's
reply, and give every dead cell ``[ST_BAD_OP, 0, ...]`` — for the hash
table's and the B-link tree's serial handlers, over sparse, dense and empty
masks.  The ``serial_steps`` counter of ``WireStats`` counts the live serial
cells a round folded."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core import placement as pl
from repro.core import replication as repl
from repro.core import roundsched as rs
from repro.core import wireproto as W
from repro.core.datastructs import btree as bt
from repro.core.datastructs import hashtable as ht
from repro.core.transport import SimTransport
from repro.core.txloop import tx_loop
from repro.testing.workloads import distinct_uint32, value_for

S, C = 8, 64                    # the chip cells' inbox: 8 sources x 64 cells
NCELL = S * C
# two cells adjacent in scan order, in different sources, the earlier one at
# the LARGER cell index: a compaction by cell index would swap them
P_CELL = (S - 1) * C - 1
Q_CELL = (S - 1) * C
TAIL = C + 8                    # the "tail" mask: only the last TAIL cells


def reference_fold(handler_fn, state, records, mask, reply_words):
    """Every cell of the inbox, live or masked, one handler step each."""
    flat_r = records.reshape(NCELL, -1)
    flat_m = mask.reshape(NCELL)
    state, rep = lax.scan(lambda st, rm: handler_fn(st, rm[0], rm[1]),
                          state, (flat_r, flat_m))
    return state, rep.reshape(S, C, reply_words)


def make_mask(kind, seed):
    rng = np.random.RandomState(seed)
    if kind == "all_dead":
        return np.zeros(NCELL, bool)
    if kind == "all_live":
        return np.ones(NCELL, bool)
    if kind == "sparse_1pct":
        return rng.rand(NCELL) < 0.01
    if kind == "half":
        return rng.rand(NCELL) < 0.5
    assert kind == "tail"
    return np.arange(NCELL) >= NCELL - TAIL


MASKS = ("all_dead", "all_live", "sparse_1pct", "half", "tail")


def check_fold(handler, state, records, mask):
    """serial_apply against the reference: arena, live and dead replies."""
    rw = handler.reply_words
    recs = jnp.asarray(records).reshape(S, C, -1)
    msk = jnp.asarray(mask).reshape(S, C)
    ref_st, ref_rep = jax.jit(
        lambda st, r, m: reference_fold(handler.fn, st, r, m, rw))(
            state, recs, msk)
    new_st, new_rep = jax.jit(
        lambda st, r, m: rs.serial_apply(handler.fn, st, r, m, rw))(
            state, recs, msk)
    np.testing.assert_array_equal(np.asarray(new_st["arena"]),
                                  np.asarray(ref_st["arena"]))
    live = np.asarray(mask).reshape(S, C)
    new_rep, ref_rep = np.asarray(new_rep), np.asarray(ref_rep)
    np.testing.assert_array_equal(new_rep[live], ref_rep[live])
    dead = np.zeros(rw, np.uint32)
    dead[0] = W.ST_BAD_OP
    assert np.all(new_rep[~live] == dead)
    return new_st, new_rep.reshape(NCELL, rw)


# ---------------------------------------------------------------------------
# Hash table
# ---------------------------------------------------------------------------
HT = ht.HashTableConfig(n_nodes=1, n_buckets=16, bucket_width=2,
                        n_overflow=128, max_chain=12)
HT_LAYOUT = ht.build_layout(HT)
SCENARIO_BUCKETS = (0, 1)       # background traffic never hashes here
TAG_A, TAG_B = 0xA0000001, 0xB0000002   # outside the background's tags 1..8


def _bucket(klo):
    _, b = ht.home_of(HT, jnp.asarray(klo, jnp.uint32),
                      jnp.zeros(np.shape(klo), jnp.uint32))
    return np.asarray(b)


@pytest.fixture(scope="module")
def ht_setup():
    """A loaded table: both scenario buckets full (so a fresh key there goes
    to the overflow area and links the chain tail), background keys in the
    other buckets, half of them loaded."""
    rng = np.random.RandomState(7)
    cand = distinct_uint32(rng, 4096, lo=1)
    b = _bucket(cand)
    in_scn = np.isin(b, SCENARIO_BUCKETS)
    scen = cand[in_scn][:2 * HT.bucket_width * len(SCENARIO_BUCKETS) + 1]
    back = cand[~in_scn][:48]
    # fill the scenario buckets: 2 keys each; the last key stays fresh
    loaded_scen, fresh = [], None
    per = {bb: 0 for bb in SCENARIO_BUCKETS}
    for k in scen:
        bb = int(_bucket(k))
        if per[bb] < HT.bucket_width:
            per[bb] += 1
            loaded_scen.append(k)
        elif fresh is None:
            fresh = k
    assert fresh is not None and len(loaded_scen) == 4
    preload = np.concatenate([np.asarray(loaded_scen), back[:24]])
    state = ht.init_node_state(HT, HT_LAYOUT)
    h = ht.make_rpc_handler(HT, HT_LAYOUT)
    klo = jnp.asarray(preload, jnp.uint32)
    recs = ht.make_record(W.OP_INSERT, klo, jnp.zeros_like(klo),
                          value=value_for(klo))
    state, rep = jax.jit(lambda st, r: lax.scan(
        lambda s, x: h.fn(s, x, jnp.asarray(True)), st, r))(state, recs)
    assert np.all(np.asarray(rep)[:, 0] == W.ST_OK)
    slot_of = {}
    arena = state["arena"]
    for k in loaded_scen:
        f = ht.find(HT, HT_LAYOUT, arena, jnp.uint32(k), jnp.uint32(0))
        assert bool(f["found"])
        slot_of[int(k)] = int(f["slot_idx"])
    return dict(state=state, handler=h, scen=[int(k) for k in loaded_scen],
                fresh=int(fresh), back=back, slot_of=slot_of)


def ht_background(s, seed):
    """A record in every cell: every opcode of the serial handler on the
    background keys (tags 1..8, slot addresses anywhere), plus NOPs."""
    rng = np.random.RandomState(seed)
    ops = np.array([W.OP_LOOKUP, W.OP_INSERT, W.OP_UPDATE, W.OP_DELETE,
                    W.OP_LOCK, W.OP_COMMIT_UNLOCK, W.OP_ABORT_UNLOCK,
                    W.OP_READ_VERSION, W.OP_BACKUP_WRITE, W.OP_NOP],
                   np.uint32)
    op = ops[rng.randint(0, len(ops), NCELL)]
    klo = s["back"][rng.randint(0, len(s["back"]), NCELL)].astype(np.uint32)
    tag = rng.randint(1, 9, NCELL).astype(np.uint32)
    slot = rng.randint(0, HT.n_slots, NCELL).astype(np.uint32)
    direct = (op == W.OP_COMMIT_UNLOCK) | (op == W.OP_ABORT_UNLOCK)
    aux = np.where(direct | (op == W.OP_READ_VERSION), slot,
                   np.where(op == W.OP_BACKUP_WRITE, 2 * tag, tag))
    key_lo = np.where(direct, tag, klo)
    return np.asarray(ht.make_record(op, key_lo, np.zeros(NCELL, np.uint32),
                                     aux=aux, value=value_for(klo)))


def _pl_install(epoch):
    pcfg = pl.PlacementConfig(n_nodes=1)
    rec = pl.install_records(pcfg, pl.initial_table(pcfg))[0]
    return np.asarray(rec.at[2].set(jnp.uint32(epoch)))


def ht_scenario(name, s):
    """{flat cell: record} placed at P_CELL / Q_CELL."""
    z = jnp.uint32(0)
    k, k2 = s["scen"][0], s["scen"][1]
    rec = lambda *a, **kw: np.asarray(ht.make_record(*a, **kw))
    if name == "two_locks_one_key":
        return {P_CELL: rec(W.OP_LOCK, k, z, aux=TAG_A),
                Q_CELL: rec(W.OP_LOCK, k, z, aux=TAG_B)}
    if name == "lock_then_commit":
        return {P_CELL: rec(W.OP_LOCK, k2, z, aux=TAG_A),
                Q_CELL: rec(W.OP_COMMIT_UNLOCK, TAG_A, z,
                            aux=s["slot_of"][k2],
                            value=value_for(jnp.uint32(99)))}
    if name == "insert_links_chain":
        return {P_CELL: rec(W.OP_INSERT, s["fresh"], z,
                            value=value_for(jnp.uint32(s["fresh"])))}
    assert name == "pl_install"
    return {P_CELL: _pl_install(5), Q_CELL: _pl_install(9)}


HT_SCENARIOS = ("two_locks_one_key", "lock_then_commit",
                "insert_links_chain", "pl_install")


def build_inbox(background, scenario, mask):
    recs = background.copy()
    mask = mask.copy()
    for cell, r in scenario.items():
        recs[cell] = r
        # the scenario's cells are live under every mask but the empty one
        mask[cell] = mask.any()
    return recs, mask


@pytest.mark.parametrize("scenario", HT_SCENARIOS)
@pytest.mark.parametrize("mask_kind", MASKS)
def test_hash_fold_matches_full_scan(ht_setup, mask_kind, scenario):
    s = ht_setup
    recs, mask = build_inbox(ht_background(s, 11),
                             ht_scenario(scenario, s),
                             make_mask(mask_kind, 12))
    st, rep = check_fold(s["handler"], s["state"], recs, mask)
    if not mask.any():
        np.testing.assert_array_equal(np.asarray(st["arena"]),
                                      np.asarray(s["state"]["arena"]))
        return
    arena = np.asarray(st["arena"])
    if scenario == "two_locks_one_key":
        # scan order is lock order: the earlier flat cell takes the lock
        assert rep[P_CELL, 0] == W.ST_OK
        assert rep[Q_CELL, 0] == W.ST_LOCK_FAIL
    elif scenario == "lock_then_commit":
        assert rep[P_CELL, 0] == W.ST_OK and rep[Q_CELL, 0] == W.ST_OK
    elif scenario == "insert_links_chain":
        assert rep[P_CELL, 0] == W.ST_OK
        assert rep[P_CELL, 1] >= HT.n_bucket_slots      # an overflow slot
        assert arena[HT_LAYOUT["alloc"].base] > np.asarray(
            s["state"]["arena"])[HT_LAYOUT["alloc"].base]
    else:
        # the later install's epoch is the one left standing
        assert arena[HT_LAYOUT["routing"].base + pl.EPOCH_WORD] == 9


def test_hash_fold_under_vmap(ht_setup):
    """A batched caller (vmap over nodes): each node's loop runs its own
    live count, and every node matches its own full scan."""
    s = ht_setup
    h, rw = s["handler"], s["handler"].reply_words
    inboxes = [build_inbox(ht_background(s, 20 + i),
                           ht_scenario(sc, s), make_mask(mk, 30 + i))
               for i, (sc, mk) in enumerate(
                   [("two_locks_one_key", "sparse_1pct"),
                    ("insert_links_chain", "half"),
                    ("pl_install", "all_dead")])]
    recs = jnp.stack([jnp.asarray(r).reshape(S, C, -1) for r, _ in inboxes])
    msk = jnp.stack([jnp.asarray(m).reshape(S, C) for _, m in inboxes])
    states = jax.tree.map(lambda x: jnp.stack([x] * len(inboxes)), s["state"])
    new_st, new_rep = jax.jit(jax.vmap(
        lambda st, r, m: rs.serial_apply(h.fn, st, r, m, rw)))(
            states, recs, msk)
    for i in range(len(inboxes)):
        ref_st, ref_rep = jax.jit(
            lambda st, r, m: reference_fold(h.fn, st, r, m, rw))(
                s["state"], recs[i], msk[i])
        np.testing.assert_array_equal(np.asarray(new_st["arena"][i]),
                                      np.asarray(ref_st["arena"]))
        live = np.asarray(msk[i])
        np.testing.assert_array_equal(np.asarray(new_rep[i])[live],
                                      np.asarray(ref_rep)[live])
        assert np.all(np.asarray(new_rep[i])[~live][:, 0] == W.ST_BAD_OP)
        assert np.all(np.asarray(new_rep[i])[~live][:, 1:] == 0)


# ---------------------------------------------------------------------------
# B-link tree
# ---------------------------------------------------------------------------
BT = bt.BTreeConfig(n_nodes=1, n_leaves=48, leaf_width=4)
BT_LAYOUT = bt.build_layout(BT)


@pytest.fixture(scope="module")
def bt_setup():
    """A tree of 40 keys (several leaves, some full) and its handler."""
    rng = np.random.RandomState(3)
    keys = np.sort(distinct_uint32(rng, 64, lo=1, hi=2**20))
    state = bt.init_node_state(BT, BT_LAYOUT, 0)
    h = bt.make_rpc_handler(BT, BT_LAYOUT)
    k = jnp.asarray(keys[:40], jnp.uint32)
    recs = bt.make_record(W.OP_BT_INSERT, k, jnp.zeros_like(k),
                          value=value_for(k))
    state, rep = jax.jit(lambda st, r: lax.scan(
        lambda s_, x: h.fn(s_, x, jnp.asarray(True)), st, r))(state, recs)
    assert np.all(np.asarray(rep)[:, 0] == W.ST_OK)
    # each key's leaf header slot, for direct COMMIT addressing
    look = bt.make_record(W.OP_BT_LOOKUP, k, jnp.zeros_like(k))
    _, lrep = jax.jit(lambda st, r: lax.scan(
        lambda s_, x: h.fn(s_, x, jnp.asarray(True)), st, r))(state, look)
    hdr = {int(a): int(b) for a, b in zip(keys[:40], np.asarray(lrep)[:, 1])}
    return dict(state=state, handler=h, keys=keys, hdr=hdr)


def bt_background(s, seed):
    rng = np.random.RandomState(seed)
    ops = np.array([W.OP_BT_LOOKUP, W.OP_BT_INSERT, W.OP_BT_DELETE,
                    W.OP_BT_LOCK, W.OP_BT_COMMIT, W.OP_BT_ABORT,
                    W.OP_BT_BACKUP, W.OP_NOP], np.uint32)
    op = ops[rng.randint(0, len(ops), NCELL)]
    key = s["keys"][rng.randint(8, len(s["keys"]), NCELL)].astype(np.uint32)
    tag = rng.randint(1, 9, NCELL).astype(np.uint32)
    hdr = (rng.randint(0, BT.n_leaves, NCELL) * BT.leaf_slots).astype(
        np.uint32)
    direct = (op == W.OP_BT_COMMIT) | (op == W.OP_BT_ABORT)
    key_hi = np.where(direct, tag, 0).astype(np.uint32)
    aux = np.where(direct, hdr, tag).astype(np.uint32)
    return np.asarray(bt.make_record(op, key, key_hi, aux=aux,
                                     value=value_for(key)))


def bt_scenario(name, s):
    k, k2 = int(s["keys"][0]), int(s["keys"][1])
    rec = lambda *a, **kw: np.asarray(bt.make_record(*a, **kw))
    if name == "two_locks_one_key":
        return {P_CELL: rec(W.OP_BT_LOCK, k, 0, aux=TAG_A),
                Q_CELL: rec(W.OP_BT_LOCK, k, 0, aux=TAG_B)}
    if name == "lock_then_commit":
        return {P_CELL: rec(W.OP_BT_LOCK, k2, 0, aux=TAG_A),
                Q_CELL: rec(W.OP_BT_COMMIT, k2, TAG_A, aux=s["hdr"][k2],
                            value=value_for(jnp.uint32(99)))}
    if name == "insert_splits_leaf":
        # fresh keys beside the loaded ones: the second fills or splits
        fresh = [int(x) for x in s["keys"][40:42]]
        return {P_CELL: rec(W.OP_BT_INSERT, fresh[0], 0,
                            value=value_for(jnp.uint32(fresh[0]))),
                Q_CELL: rec(W.OP_BT_INSERT, fresh[1], 0,
                            value=value_for(jnp.uint32(fresh[1])))}
    assert name == "pl_install"
    return {P_CELL: _pl_install(5), Q_CELL: _pl_install(9)}


BT_SCENARIOS = ("two_locks_one_key", "lock_then_commit",
                "insert_splits_leaf", "pl_install")


@pytest.mark.parametrize("scenario", BT_SCENARIOS)
@pytest.mark.parametrize("mask_kind", MASKS)
def test_btree_fold_matches_full_scan(bt_setup, mask_kind, scenario):
    s = bt_setup
    recs, mask = build_inbox(bt_background(s, 13),
                             bt_scenario(scenario, s),
                             make_mask(mask_kind, 14))
    _, rep = check_fold(s["handler"], s["state"], recs, mask)
    if mask.any() and scenario == "two_locks_one_key":
        assert rep[P_CELL, 0] == W.ST_OK
        assert rep[Q_CELL, 0] == W.ST_LOCK_FAIL


# ---------------------------------------------------------------------------
# The serial_steps counter
# ---------------------------------------------------------------------------
N = 4
TX_CFG = ht.HashTableConfig(n_nodes=N, n_buckets=64, bucket_width=2,
                            n_overflow=128, max_chain=8)


def _write_batch(seed, B, hot):
    """Write-only transactions, one write slot a lane; ``hot`` lanes of
    node 0 all write the same key, so their LOCKs collide and retry."""
    rng = np.random.RandomState(seed)
    klo = distinct_uint32(rng, N * B, lo=1).reshape(N, B, 1)
    klo[0, :hot, 0] = klo[0, 0, 0]
    klo = jnp.asarray(klo, jnp.uint32)
    wk = jnp.stack([klo, jnp.zeros_like(klo)], -1)
    rk = jnp.zeros((N, B, 1, 2), jnp.uint32)
    return dict(read_keys=rk, write_keys=wk,
                write_values=value_for(klo + jnp.uint32(5)),
                read_enabled=jnp.zeros((N, B, 1), bool))


@pytest.mark.parametrize("f", [0, 1])
def test_serial_steps_count_the_delivered_serial_requests(f):
    """Each round's LOCKs go to every lane still live; each committing lane
    then sends one COMMIT and f backup writes.  With one write a lane and
    no reads, those are all the serial requests, so the counter summed over
    the batch is sum(attempts) + (1 + f) * sum(committed)."""
    B = 8
    t = SimTransport(N)
    state = ht.init_cluster_state(TX_CFG)
    layout = ht.build_layout(TX_CFG)
    rep = repl.ReplicaConfig(N, f) if f else None
    run = jax.jit(lambda st, kw: tx_loop(t, st, TX_CFG, layout, rep=rep,
                                         max_rounds=4, **kw))
    _, _, res = run(state, _write_batch(1, B, hot=3))
    attempts = np.asarray(res.round_attempts)
    committed = np.asarray(res.round_committed)
    assert np.asarray(res.committed).all()
    assert attempts[1] > 0 and attempts[-1] == 0   # a retry, then parked
    want = attempts.sum() + (1 + f) * committed.sum()
    assert float(res.metrics.wire.serial_steps) == want


def test_parked_round_folds_no_serial_step():
    """A round whose rpc lanes are all disabled delivers no serial request:
    the counter reads 0 and the state is untouched."""
    t = SimTransport(N)
    state = ht.init_cluster_state(TX_CFG)
    layout = ht.build_layout(TX_CFG)
    h = ht.make_rpc_handler(TX_CFG, layout)
    klo = jnp.arange(1, N * 8 + 1, dtype=jnp.uint32).reshape(N, 8)
    recs = ht.make_record(W.OP_LOCK, klo, jnp.zeros_like(klo),
                          aux=jnp.ones_like(klo))
    dest, _ = ht.home_of(TX_CFG, klo, jnp.zeros_like(klo))

    def one(en):
        return rs.fused_round(t, state, [rs.rpc_class(dest, recs, h,
                                                      enabled=en)])

    st0, _, s0 = jax.jit(one)(jnp.zeros((N, 8), bool))
    assert float(s0.serial_steps) == 0.0
    np.testing.assert_array_equal(np.asarray(st0["arena"]),
                                  np.asarray(state["arena"]))
    _, _, s1 = jax.jit(one)(jnp.ones((N, 8), bool))
    assert float(s1.serial_steps) == N * 8 == float(s1.ops)
