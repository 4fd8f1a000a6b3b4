"""MeshTransport (shard_map/all_to_all) must agree with SimTransport.

Each case runs in a SUBPROCESS that forces several host devices
(xla_force_host_platform_device_count), so the main test session keeps its
single-device view.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import rpc as R
    from repro.core import slots as sl
    from repro.core import onesided as osd
    from repro.core import hybrid as hy
    from repro.core.datastructs import hashtable as ht
    from repro.core.transport import SimTransport, MeshTransport

    N, B = 8, 16
    cfg = ht.HashTableConfig(n_nodes=N, n_buckets=32, bucket_width=2,
                             n_overflow=32)
    layout = ht.build_layout(cfg)
    rng = np.random.RandomState(0)
    klo = jnp.asarray(rng.randint(0, 2**31, (N, B)), jnp.uint32)
    khi = jnp.asarray(rng.randint(0, 2**31, (N, B)), jnp.uint32)
    vals = sl._mix32(klo[..., None] + jnp.arange(sl.VALUE_WORDS, dtype=jnp.uint32))
    node, _, _ = ht.lookup_start(cfg, layout, klo, khi)
    h = ht.make_rpc_handler(cfg, layout)

    # --- simulator reference -------------------------------------------
    ts = SimTransport(N)
    s_sim = ht.init_cluster_state(cfg)
    s_sim, rep_sim, _, _ = R.rpc_call(
        ts, s_sim, node, ht.make_record(R.OP_INSERT, klo, khi, value=vals), h)
    s_sim, _, f_sim, v_sim, *_ = hy.hybrid_lookup(
        ts, s_sim, klo, khi, cfg, layout)

    # --- mesh execution --------------------------------------------------
    mesh = jax.make_mesh((8,), ("node",))
    tm = MeshTransport(N, axis_name="node")
    sh = NamedSharding(mesh, P("node"))

    def put(tree):
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    def run(state, node, klo, khi, vals):
        recs = ht.make_record(R.OP_INSERT, klo, khi, value=vals)
        state, rep, _, _ = R.rpc_call(tm, state, node, recs, h)
        state, _, found, value, *_ = hy.hybrid_lookup(
            tm, state, klo, khi, cfg, layout)
        return rep, found, value

    fn = jax.jit(jax.shard_map(
        run, mesh=mesh,
        in_specs=(P("node"), P("node"), P("node"), P("node"), P("node")),
        out_specs=(P("node"), P("node"), P("node")), check_vma=False))
    s_mesh = put(ht.init_cluster_state(cfg))
    rep_m, f_m, v_m = fn(s_mesh, put(node), put(klo), put(khi), put(vals))

    np.testing.assert_array_equal(np.asarray(rep_m[..., 0]),
                                  np.asarray(rep_sim[..., 0]))
    np.testing.assert_array_equal(np.asarray(f_m), np.asarray(f_sim))
    np.testing.assert_array_equal(np.asarray(v_m), np.asarray(v_sim))
    print("MESH_OK")
""")


# tx_loop on a 4-node mesh (one node per device) against SimTransport(4):
# the TATP deployment's own load and transaction steps at a small table: one
# batch of the TATP mix, then one of hot-row writes whose retried lock races
# are settled by the per-node backoff permutations — which must be each
# node's own for the two transports to agree.
TX_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro import tatp
    from repro.core import slots as sl
    from repro.core.datastructs import hashtable as ht

    N, PER = 4, 48
    cfg = ht.HashTableConfig(n_nodes=N, n_buckets=64, bucket_width=1,
                             n_overflow=64, max_chain=12)
    mesh = jax.make_mesh((N,), ("node",))
    klo, khi, vals = tatp.make_subscribers(0, N, PER)
    rng = np.random.RandomState(1)
    batches = []
    for b in range(2):
        (rs, ri), (ws, wi), ren, wen = tatp.draw_mix(rng, N, PER, tatp.LANES)
        if b:
            # every lane writes one of two hot rows of its own node's: lock
            # races among one node's lanes, which that node's backoff order
            # settles in the retry rounds
            ws = np.broadcast_to(np.arange(N)[:, None, None], ws.shape)
            wi, wen = wi % 2, np.ones_like(wen)
        rk = np.stack([klo[rs, ri], khi[rs, ri]], -1)
        wk = np.stack([klo[ws, wi], khi[ws, wi]], -1)
        wv = rng.randint(0, 2**31, wk.shape[:3] + (sl.VALUE_WORDS,))
        batches.append(((rk, wk, wv.astype(np.uint32), ren, wen),
                        np.asarray(jax.random.PRNGKey(b))))

    runs = []
    for cl in (tatp.Cluster(N, mesh=mesh, cfg=cfg), tatp.Cluster(N, cfg=cfg)):
        state, _, _ = cl.load(cl.load_step(tatp.load_capacity(PER, N)),
                              cl.init_state(), klo, khi, vals, PER)
        tx = cl.tx_step()
        out = []
        for args, key in batches:
            state, lanes, counts = tx(state, *cl.put(args),
                                      cl.put_replicated(key))
            counts.pop("round_trips", None)     # per shard on the mesh
            out.append(jax.tree.map(np.asarray, (lanes, counts)))
        runs.append((out, np.asarray(state["arena"])))

    (mesh_out, mesh_arena), (sim_out, sim_arena) = runs
    retried = 0
    for (ml, mc), (sl_, sc) in zip(mesh_out, sim_out):
        for k in ml:
            np.testing.assert_array_equal(ml[k], sl_[k], err_msg=k)
        for k in mc:
            np.testing.assert_array_equal(mc[k], sc[k], err_msg=k)
        retried += int(sc["attempts"][1:].sum())
    np.testing.assert_array_equal(mesh_arena, sim_arena)
    assert retried > 0, "no lane was retried: the backoff went unused"
    print("MESH_TX_OK", retried)
""")


def _run(script, marker):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=560, env=env)
    assert marker in res.stdout, res.stdout + "\n" + res.stderr[-3000:]


def test_mesh_transport_matches_simulator():
    _run(SCRIPT, "MESH_OK")


def test_mesh_tx_loop_matches_simulator():
    """Per-lane committed / commit_round / read values, the psum'd per-round
    counts and the final arenas of a retried TATP batch are equal under
    MeshTransport(4) and SimTransport(4)."""
    _run(TX_SCRIPT, "MESH_TX_OK")
