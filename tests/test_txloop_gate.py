"""The retry engine's round gate: ``tx_loop`` runs a protocol round only
while some lane of the cluster is live, and ``rounds_run`` counts the rounds
it ran.

A skipped round must leave everything as an executed-but-empty round would:
the arena, the per-lane results, the counts and the wire totals.  So a batch
run at ``max_rounds`` = 4 is compared with the same batch run at exactly the
rounds it needed, which skips nothing.  The mesh case runs in a subprocess
that forces four host devices (as tests/test_mesh_transport.py does), with
only one node's lanes live after round 0: every chip must enter the retry
round together.
"""
import importlib.util
import os
import pathlib
import subprocess
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import placement as pl
from repro.core import rpc as R
from repro.core import telemetry as T
from repro.core.datastructs import hashtable as ht
from repro.core.replication import ReplicaConfig
from repro.core.transport import SimTransport
from repro.core.txloop import tx_loop
from repro.testing.workloads import distinct_uint32, value_for

N, B = 4, 8
ROOT = pathlib.Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")
LANE_FIELDS = ("committed", "commit_round", "read_found", "read_values")
ROUND_FIELDS = ("round_committed", "round_attempts", "round_retries",
                "round_abort_lock", "round_abort_validate",
                "round_abort_overflow", "round_abort_stale")


@pytest.fixture(scope="module")
def cfg():
    return ht.HashTableConfig(n_nodes=N, n_buckets=64, bucket_width=2,
                              n_overflow=64, max_chain=6)


@pytest.fixture(scope="module")
def layout(cfg):
    return ht.build_layout(cfg)


def insert_keys(t, state, cfg, layout, klo):
    h = ht.make_rpc_handler(cfg, layout)
    khi = jnp.zeros_like(klo)
    node, _, _ = ht.lookup_start(cfg, layout, klo, khi)
    state, rep, _, _ = R.rpc_call(
        t, state, node, ht.make_record(R.OP_INSERT, klo, khi,
                                       value=value_for(klo)), h)
    assert np.all(np.asarray(rep[..., 0]) == R.ST_OK)
    return state


def keys(klo):
    return jnp.stack([klo, jnp.zeros_like(klo)], -1)


def live_rounds_metric(results):
    """The benchmark's ``txloop.live_rounds_per_batch`` over a window of
    these tx_loop results (its reader sees the per-round counts)."""
    path = ROOT / "chipbench" / "metrics" / "txloop.live_rounds_per_batch.py"
    spec = importlib.util.spec_from_file_location("live_rounds", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    batches = [{"counts": {"attempts": np.asarray(r.round_attempts)}}
               for r in results]
    return mod.read(types.SimpleNamespace(batches=batches))


def assert_same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def assert_runs_agree(long, short, k):
    """``long`` (more rounds, some skipped) against ``short`` (exactly the
    ``k`` rounds that ran): equal arenas, lanes, totals and the first ``k``
    rounds' counts; the skipped rounds count nothing."""
    (s_l, c_l, r_l), (s_s, c_s, r_s) = long[:3], short[:3]
    assert_same((s_l, c_l), (s_s, c_s))
    for f in LANE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(r_l, f)),
                                      np.asarray(getattr(r_s, f)), err_msg=f)
    for f in ROUND_FIELDS:
        per = np.asarray(getattr(r_l, f))
        np.testing.assert_array_equal(per[:k], np.asarray(getattr(r_s, f)),
                                      err_msg=f)
        assert not per[k:].any(), (f, per)
    assert_same((r_l.metrics, r_l.round_trips), (r_s.metrics, r_s.round_trips))
    assert int(r_l.rounds_run) == int(r_s.rounds_run) == k


@pytest.mark.parametrize("rep_f", [0, 1], ids=["f0", "f1"])
def test_a_batch_that_commits_in_round_0_runs_one_round(cfg, layout, rep_f):
    """Distinct keys, no lane racing another: every lane commits in round 0,
    and max_rounds = 4 then does what max_rounds = 1 does, bit for bit."""
    t = SimTransport(N)
    rng = np.random.RandomState(5)
    klo = jnp.asarray(distinct_uint32(rng, N * B * 2).reshape(N, B, 2),
                      jnp.uint32)
    state = insert_keys(t, ht.init_cluster_state(cfg), cfg, layout,
                        klo[..., 0])
    kw = dict(read_keys=keys(klo[..., :1]), write_keys=keys(klo[..., 1:]),
              write_values=value_for(klo[..., 1:] + jnp.uint32(3)),
              rep=ReplicaConfig(N, rep_f) if rep_f else None)
    four = tx_loop(t, state, cfg, layout, max_rounds=4, **kw)
    one = tx_loop(t, state, cfg, layout, max_rounds=1, **kw)
    res = four[2]
    assert np.asarray(res.committed).all()
    assert (np.asarray(res.commit_round) == 0).all()
    np.testing.assert_array_equal(np.asarray(res.round_attempts),
                                  [N * B, 0, 0, 0])
    np.testing.assert_array_equal(np.asarray(res.round_committed),
                                  [N * B, 0, 0, 0])
    assert float(res.metrics.wire.ici_bytes) == 0
    assert_runs_agree(four, one, 1)
    assert live_rounds_metric([res]) == 1


def skewed_batch(cfg, layout, t):
    """Skewed writes, as in tests/test_txloop.py but bounded: each of twelve
    hot keys is written by two or three lanes, so one lane per key commits a
    round and the batch takes three rounds."""
    hot = (np.arange(16, dtype=np.uint32) + 1) * np.uint32(7919)
    pick = np.random.RandomState(1).permutation(np.arange(N * B) % 12)
    klo = jnp.asarray(hot[pick].reshape(N, B, 1), jnp.uint32)
    state = insert_keys(t, ht.init_cluster_state(cfg), cfg, layout,
                        jnp.asarray(hot).reshape(N, -1))
    return state, dict(read_keys=jnp.zeros((N, B, 0, 2), jnp.uint32),
                       write_keys=keys(klo),
                       write_values=value_for(klo + jnp.uint32(5)))


def stale_batch():
    """Writes routed by a placement table that predates a migration: every
    lane aborts stale in round 0 and commits after round 1's refresh."""
    pcfg = pl.PlacementConfig(N)
    cfg = ht.HashTableConfig(n_nodes=N, n_buckets=16, bucket_width=2,
                             n_overflow=64, max_chain=10)
    layout = ht.build_layout(cfg)
    fresh = pl.PlacementTable(
        jnp.uint32(1), pl.initial_table(pcfg).copies.at[0, 0].set(2),
        jnp.ones((N,), bool))
    state = pl.install_local(ht.init_cluster_state(cfg), layout, pcfg,
                             fresh)
    rng = np.random.RandomState(3)
    found = []
    while len(found) < N * 4:
        cand = rng.randint(0, 2**31, 256).astype(np.uint32)
        part = np.asarray(ht.part_of(cfg, jnp.asarray(cand),
                                     jnp.zeros_like(jnp.asarray(cand))))
        found = np.unique(np.concatenate([found, cand[part == 0]]))
    klo = jnp.asarray(found[:N * 4].reshape(N, 4, 1), jnp.uint32)
    return cfg, layout, state, dict(
        read_keys=jnp.zeros((N, 4, 0, 2), jnp.uint32), write_keys=keys(klo),
        write_values=value_for(klo), ptable=pl.initial_table(pcfg),
        pcfg=pcfg)


@pytest.mark.parametrize("variant", ["skewed", "skewed_telemetry", "stale"])
def test_rounds_run_counts_the_rounds_with_live_lanes(cfg, layout, variant):
    """A batch whose lanes need more than one round: ``rounds_run`` is the
    number of rounds that had live lanes, and the batch at max_rounds = 6
    ends as it does at max_rounds = rounds_run."""
    t = SimTransport(N)
    if variant == "stale":
        cfg, layout, state, kw = stale_batch()
    else:
        state, kw = skewed_batch(cfg, layout, t)
    if variant == "skewed_telemetry":
        kw["telemetry"] = T.TelemetryConfig()
    long = tx_loop(t, state, cfg, layout, max_rounds=6, **kw)
    res = long[2]
    k = int(res.rounds_run)
    assert k == int((np.asarray(res.round_attempts) > 0).sum())
    assert 2 <= k < 6, np.asarray(res.round_attempts)
    assert (np.asarray(res.round_abort_lock if variant != "stale"
                       else res.round_abort_stale)[0] > 0)
    assert np.asarray(res.committed).all()
    short = tx_loop(t, state, cfg, layout, max_rounds=k, **kw)
    assert_runs_agree(long, short, k)
    assert live_rounds_metric([res, short[2]]) == k
    if variant == "skewed_telemetry":
        # the skipped rounds record nothing: the same events, the same
        # modeled per-lane latency
        np.testing.assert_array_equal(T.events(long[3].trace),
                                      T.events(short[3].trace))
        np.testing.assert_array_equal(np.asarray(long[3].lane_latency_us),
                                      np.asarray(short[3].lane_latency_us))


# Two batches through the TATP deployment's tx_loop under MeshTransport(4)
# (one node per device) and SimTransport(4): one that commits in round 0,
# one in which node 0's lanes race for three rows of its own while the other
# nodes' lanes read rows nobody writes, so after round 0 only one chip has
# a live lane.  Lanes, psum'd counts, the gate's count and the arenas must
# agree, and the mesh's link bytes must be those of the rounds that ran.
MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from jax import lax
    from repro import tatp
    from repro.core import slots as sl
    from repro.core import txloop as txl
    from repro.core.datastructs import hashtable as ht

    N, PER, L = 4, 48, tatp.LANES
    cfg = ht.HashTableConfig(n_nodes=N, n_buckets=64, bucket_width=1,
                             n_overflow=64, max_chain=12)
    mesh = jax.make_mesh((N,), ("node",))
    klo, khi, vals = tatp.make_subscribers(0, N, PER)
    rng = np.random.RandomState(2)
    batches = []
    for b in range(2):
        # every lane reads one row of its own node's, rows 3.. only
        rs = np.broadcast_to(np.arange(N)[:, None, None], (N, L, tatp.RD))
        ri = rng.randint(3, PER, (N, L, tatp.RD))
        ren = np.zeros((N, L, tatp.RD), bool)
        ren[..., 0] = True
        ws = np.zeros((N, L, tatp.WR), np.int64)
        wi = np.broadcast_to((np.arange(L) % 3)[None, :, None], ws.shape)
        wen = np.zeros((N, L, tatp.WR), bool)
        if b:
            wen[0, :7] = True       # node 0's first lanes race for rows 0-2
        rk = np.stack([klo[rs, ri], khi[rs, ri]], -1)
        wk = np.stack([klo[ws, wi], khi[ws, wi]], -1)
        wv = rng.randint(0, 2**31, wk.shape[:3] + (sl.VALUE_WORDS,))
        batches.append(((rk, wk, wv.astype(np.uint32), ren, wen),
                        np.asarray(jax.random.PRNGKey(7 + b))))

    def build(cl):
        def step(state, rk, wk, wv, ren, wen, key):
            state, _, res = txl.tx_loop(
                cl.t, state, cl.cfg, cl.layout, read_keys=rk, write_keys=wk,
                write_values=wv, read_enabled=ren, write_enabled=wen,
                max_rounds=4, key=key)
            lanes = dict(committed=res.committed,
                         commit_round=res.commit_round,
                         read_found=res.read_found,
                         read_values=res.read_values)
            counts = dict(committed=res.round_committed,
                          attempts=res.round_attempts,
                          ici_bytes=res.metrics.wire.ici_bytes)
            if cl.mesh is not None:
                counts = jax.tree.map(lambda x: lax.psum(x, "node"), counts)
            counts.update(round_trips=res.round_trips,
                          rounds_run=res.rounds_run)
            return state, lanes, counts
        return cl.jit(step, (tatp.NODE,) * 6 + (tatp.REP,),
                      (tatp.NODE, tatp.NODE, tatp.REP), donate_argnums=0)

    runs = []
    for cl in (tatp.Cluster(N, mesh=mesh, cfg=cfg), tatp.Cluster(N, cfg=cfg)):
        state, _, _ = cl.load(cl.load_step(tatp.load_capacity(PER, N)),
                              cl.init_state(), klo, khi, vals, PER)
        tx, out = build(cl), []
        for args, key in batches:
            state, lanes, counts = tx(state, *cl.put(args),
                                      cl.put_replicated(key))
            out.append(jax.tree.map(np.asarray, (lanes, counts)))
        runs.append((out, np.asarray(state["arena"])))

    (mesh_out, mesh_arena), (sim_out, sim_arena) = runs
    ici = [c.pop("ici_bytes") for _, c in mesh_out]
    for (ml, mc), (sl_, sc) in zip(mesh_out, sim_out):
        for k in ml:
            np.testing.assert_array_equal(ml[k], sl_[k], err_msg=k)
        assert sc.pop("ici_bytes") == 0
        for k in mc:
            np.testing.assert_array_equal(mc[k], sc[k], err_msg=k)
    np.testing.assert_array_equal(mesh_arena, sim_arena)
    (l0, c0), (l1, c1) = mesh_out
    assert c0["rounds_run"] == 1 and (l0["commit_round"] == 0).all(), c0
    assert l1["committed"].all(), c1
    retried = l1["commit_round"] > 0
    assert retried[0].any() and not retried[1:].any(), l1["commit_round"]
    k = int(c1["rounds_run"])
    assert 2 <= k < 4 and k == int((c1["attempts"] > 0).sum()), c1
    # every round that runs puts the same collectives on the links
    assert ici[0] > 0 and ici[1] == k * ici[0], ici
    print("MESH_GATE_OK", k, list(c1["attempts"]))
""")


def test_mesh_chips_enter_a_retry_round_together():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                         capture_output=True, text=True, timeout=560, env=env)
    assert "MESH_GATE_OK" in res.stdout, (res.stdout + "\n"
                                          + res.stderr[-3000:])
