"""The benchmark's four-node TATP mesh cell (``tatp_sf5_mesh4.mix``: one
Storm node per device, ``MeshTransport`` under ``jax.shard_map``) and its
read-only TATP cell (``tatp_sf10.read``), on the CPU at small sizes.

The runs that need four devices go to a subprocess that forces four host
devices (as tests/test_mesh_transport.py does), so that this session keeps
its single-device view: whole runs of the cells, sound and with a fault
planted, and the mesh deployment's batches beside the one-device
deployment's.  The new metric readers are checked here on a hand-made
trace whose answers are known.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]

# whole runs through the harness (chipbench/tests/conftest.py's tiny_run),
# each fault as chipbench/tests/test_cells.py plants it, and the exchange
# left out of the mesh step as test_cells leaves it out of SimTransport's
RUNS = textwrap.dedent("""
    import json
    import test_cells
    from conftest import tiny_run
    from chipbench.harness import spec as S
    from repro.core import transport

    def exchange_left_out(dep, table):
        exchange = transport.MeshTransport.exchange
        transport.MeshTransport.exchange = lambda self, x: x
        try:
            fn, shapes = dep.step_program()
            dep.step_fn = fn.lower(*shapes).compile()
        finally:
            transport.MeshTransport.exchange = exchange

    faults = dict(state_unchanged=test_cells.state_unchanged,
                  half_batch_dropped_as_aborts=(
                      test_cells.half_batch_dropped_as_aborts),
                  exchange_left_out=exchange_left_out)
    out = {}
    for name, workload, fault in [
            ("sound", "tatp_sf5_mesh4.mix", None),
            *((f, "tatp_sf5_mesh4.mix", f) for f in faults),
            ("read", "tatp_sf10.read", None)]:
        kw = {} if fault is None else dict(
            patch=faults[fault], seconds=1.5, config={"subscribers": 512})
        res, err = tiny_run(workload, **kw)
        want = sorted(m["name"] for m in S.metrics_for(
            S.load_spec(), workload, False))
        out[name] = dict(res=res, err=err[-3:], want=want)
    print("RUNS " + json.dumps(out))
""")

# the mesh deployment's batches beside the one-device deployment's, on the
# same table and batches: 256 transactions over 512 rows, so that some
# lanes retry
DRIVER = textwrap.dedent("""
    import contextlib, json, re, types
    import numpy as np
    from chipbench.harness import run as R, spec as S
    from chipbench.harness.traffic import Traffic
    from repro import tatp

    SEED = 2**31 + 15
    spec = S.load_spec()
    conf = S.config(spec, "tatp_sf5_mesh4")
    conf.update(subscribers=512, load_lanes=128,
                table=dict(n_buckets=256, bucket_width=1, n_overflow=256,
                           max_chain=12))
    mix = S.traffic("tatp_mix")
    _, ref = S.system("tatp_mesh")
    table = ref.make_table(SEED, conf)
    span = lambda phase: contextlib.nullcontext()
    runs = {}
    for name in ("tatp", "tatp_mesh"):
        system, _ = S.system(name)
        dep = system.Deployment(conf, mix)
        dep.compile()
        dep.load(table)
        traffic = Traffic(mix, nodes=conf["nodes"], rows=table.rows,
                          value_words=conf["value_words"], seed=SEED)
        bs = [R.run_batch(dep, table, traffic.batch(), span)
              for _ in range(3)]
        rows = np.arange(table.rows)
        runs[name] = (dep, bs, np.asarray(dep.state["arena"]),
                      *dep.readback(table, rows))

    (sim, sb, sa, sf, sv), (mesh, mb, ma, mf, mv) = runs["tatp"], \\
        runs["tatp_mesh"]
    for s, m in zip(sb, mb):
        for k in ("committed", "commit_round", "read_found", "read_values"):
            np.testing.assert_array_equal(m[k], s[k], err_msg=k)
        assert set(m["counts"]) == set(s["counts"]) | {"ici_bytes"}
        for k in s["counts"]:
            np.testing.assert_array_equal(m["counts"][k], s["counts"][k],
                                          err_msg=k)
    np.testing.assert_array_equal(ma, sa)
    np.testing.assert_array_equal(mf, sf)
    np.testing.assert_array_equal(mv, sv)

    # the bytes from the compiled step's all-to-alls: (N - 1) / N of each
    # operand leaves its chip, every round the loop runs (those with live
    # lanes), on every chip
    N, bytes_of = conf["nodes"], dict(pred=1, u8=1, s32=4, u32=4, f32=4)
    a2a = re.findall(r'^\\s*(?:ROOT )?%\\S+ = (.*?) all-to-all\\(.*?'
                     r'op_name="([^"]*)"', mesh.step_fn.as_text(), re.M)
    loop = other = 0
    for shape, path in a2a:
        n = sum(bytes_of[t] * int(np.prod([int(d) for d in dims.split(",")
                                           if d]))
                for t, dims in re.findall(r"(\\w+)\\[([\\d,]*)\\]", shape))
        if "while/body" in path:
            loop += n * (N - 1) // N
        else:
            other += n * (N - 1) // N
    live = [int((b["counts"]["attempts"] > 0).sum()) for b in mb]
    shapes = [N * (loop * k + other) for k in live]
    cl = tatp.Cluster(N, cfg=sim.cfg)
    _, _, c = cl.tx_step(max_rounds=conf["max_rounds"])(
        cl.init_state(), *sim.put(table, sb[0]))
    run = types.SimpleNamespace(batches=mb)
    read = lambda m: S.reader(m).read(run)
    print("DRIVER " + json.dumps(dict(
        a2a=len(a2a), shapes=shapes, sim_ici=float(c["ici_bytes"]),
        ici=[float(b["counts"]["ici_bytes"]) for b in mb],
        round_trips=[float(b["counts"]["round_trips"]) for b in mb],
        retried=sum(int(b["counts"]["attempts"][1:].sum()) for b in mb),
        metrics={m: read(m) for m in (
            "exchange.ici_bytes_per_batch", "exchange.round_trips_per_batch",
            "txloop.attempts_per_commit", "txloop.live_rounds_per_batch")})))
""")


def _subprocess(script, marker):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   str(p) for p in (ROOT / "src", ROOT,
                                    ROOT / "chipbench" / "tests")))
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=900, env=env, cwd=ROOT)
    lines = [l for l in res.stdout.splitlines() if l.startswith(marker + " ")]
    assert lines, res.stdout[-3000:] + "\n" + res.stderr[-3000:]
    return json.loads(lines[-1][len(marker) + 1:])


@pytest.fixture(scope="module")
def runs():
    return _subprocess(RUNS, "RUNS")


@pytest.fixture(scope="module")
def driver():
    return _subprocess(DRIVER, "DRIVER")


@pytest.mark.parametrize("name", ["sound", "read"])
def test_sound_run_of_a_new_cell_is_correct_with_the_contract_keys(runs,
                                                                   name):
    r = runs[name]
    res = r["res"]
    assert list(res) == KEYS
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert sorted(res["metrics"]) == r["want"]
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["checks"] == {"wrong_answers": {"value": 0, "limit": 0},
                             "uncommitted_share": {"value": 0.0,
                                                   "limit": 0.01}}
    assert r["err"][0] == "correct True"


@pytest.mark.parametrize("fault", ["state_unchanged",
                                   "half_batch_dropped_as_aborts",
                                   "exchange_left_out"])
def test_planted_fault_in_the_mesh_cell_is_not_correct(runs, fault):
    checks = runs[fault]["res"]["checks"]
    assert runs[fault]["res"]["correct"] is False, checks
    if fault == "half_batch_dropped_as_aborts":
        assert checks["wrong_answers"]["value"] == 0
        assert checks["uncommitted_share"]["value"] == 0.5


def test_mesh_deployment_matches_the_one_device_deployment(driver):
    """Per-lane results, the counts (round trips included), the arena and
    the read-back of the mesh deployment equal those of
    ``systems/tatp.py`` under SimTransport(4), batch by batch (asserted in
    the subprocess), on batches that retry."""
    assert driver["retried"] > 0
    assert all(rt >= 4 for rt in driver["round_trips"])


def test_ici_bytes_are_the_count_from_the_exchanged_shapes(driver):
    # the read, lock and validate rounds exchange requests, masks and
    # replies; the unreplicated commit round no replies
    assert driver["a2a"] == 11
    assert driver["ici"] == driver["shapes"]
    assert driver["sim_ici"] == 0


def test_program_counters_read_by_the_cell(driver):
    m = driver["metrics"]
    assert m["exchange.ici_bytes_per_batch"] == sum(driver["shapes"]) / 3
    assert m["exchange.round_trips_per_batch"] == pytest.approx(
        sum(driver["round_trips"]) / 3)
    assert m["txloop.attempts_per_commit"] > 1


# --- the new readers on a hand-made trace -------------------------------------
EXCHANGE = ("jit(step)/shard_map/storm.txloop/while/body/storm.occ.lock/"
            "storm.round.lock/storm.exchange")
PATHS = {"all_to_all.1": EXCHANGE + "/all_to_all",
         "all-to-all-start.2": EXCHANGE + "/all_to_all",
         "all-to-all-done.2": EXCHANGE + "/all_to_all",
         "copy.9": EXCHANGE + "/all_to_all",
         "fusion.3": EXCHANGE.replace("exchange", "gather") + "/gather",
         "all-reduce.4": "jit(step)/shard_map/storm.allreduce/psum"}
# two step runs on each of two devices, 10,000 ns apart; per run, ns from
# the run's start: (op, start, end)
OPS = {0: [("all_to_all.1", 1000, 2000), ("copy.9", 2000, 2500),
           ("all-to-all-start.2", 3000, 3200),
           ("all-to-all-done.2", 4000, 4500),
           ("fusion.3", 5000, 6000), ("all-reduce.4", 6000, 6500)],
       1: [("all_to_all.1", 1000, 3000),
           ("all-to-all-start.2", 3000, 3200),
           ("all-to-all-done.2", 4000, 4500),
           ("fusion.3", 5000, 6000), ("all-reduce.4", 6000, 6500)]}


def _varint(x):
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _len(field, payload):
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _hlo_proto():
    """HloProto of one computation that holds the operations of PATHS."""
    ins = b"".join(_len(2, _len(1, n.encode()) + _len(7, _len(2, p.encode())))
                   for n, p in PATHS.items())
    comp = _len(1, b"c1") + ins + _varint(5 << 3) + _varint(1)
    return _len(1, _len(1, b"jit_step") + _len(3, comp)
                + _varint(6 << 3) + _varint(1))


def _xspace():
    from jax.profiler import ProfileData
    names = sorted({n for ops in OPS.values() for n, _, _ in ops})
    meta = lambda: "".join(
        f'  event_metadata {{ key: {i + 2} value {{ id: {i + 2} name: '
        f'"{n}" }} }}\n' for i, n in enumerate(names))
    planes = ['planes { id: 1 name: "/host:CPU"\n'
              '  lines { id: 1 name: "python" timestamp_ns: 0\n'
              '    events { metadata_id: 1 offset_ps: 0 '
              'duration_ps: 21000000 } }\n'
              '  event_metadata { key: 1 value { id: 1 name: '
              '"bench.fetch" } }\n}\n']
    for d, ops in OPS.items():
        evs = "".join(
            f"    events {{ metadata_id: {names.index(n) + 2} offset_ps: "
            f"{(r + s) * 1000} duration_ps: {(e - s) * 1000} }}\n"
            for r in (0, 10000) for n, s, e in ops)
        planes.append(
            f'planes {{ id: {d + 2} name: "/device:TPU:{d}"\n'
            f'  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0\n'
            f'    events {{ metadata_id: 1 offset_ps: 500000 '
            f'duration_ps: 7000000 }}\n'
            f'    events {{ metadata_id: 1 offset_ps: 10500000 '
            f'duration_ps: 7000000 }} }}\n'
            f'  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0\n{evs}  }}\n'
            f'  event_metadata {{ key: 1 value {{ id: 1 name: '
            f'"jit_step(7)" }} }}\n{meta()}}}\n')
    esc = "".join(f"\\{c:03o}" for c in _hlo_proto())
    planes.append(
        f'planes {{ id: 9 name: "/host:metadata"\n'
        f'  event_metadata {{ key: 7 value {{ id: 7 name: "jit_step(7)" '
        f'stats {{ metadata_id: 1 bytes_value: "{esc}" }} }} }}\n'
        f'  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}'
        f'\n}}\n')
    return ProfileData.text_proto_to_serialized_xspace("".join(planes))


def _reader(name, monkeypatch, root):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from chipbench.harness import spec as S
    mod = S.reader(name)
    if hasattr(mod, "ROOT"):            # where a traced run's trace is
        monkeypatch.setattr(mod, "ROOT", root)
    return mod


@pytest.fixture
def traced(tmp_path):
    d = tmp_path / ".bench_trace" / "c" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace())
    per_chip = 60_000
    return types.SimpleNamespace(
        trace={"truncated": False}, cell={"name": "c"}, conf={"nodes": 2},
        batches=[{"counts": {"ici_bytes": 2.0 * per_chip}}] * 3)


def test_ici_roofline_share_on_a_hand_made_trace(traced, tmp_path,
                                                 monkeypatch):
    mod = _reader("exchange.ici_roofline_share", monkeypatch, tmp_path)
    monkeypatch.setattr(mod, "device_kind", lambda: "TPU v5 lite")
    # all-to-all time a run, the copy and the gather left out: device 0
    # 1,000 + 1,500 ns (the start/done pair from 3,000 to 4,500), device 1
    # the union 1,000..4,500; two runs each
    busy = 2 * (2500 + 3500) * 1e-9
    sent = 60_000 * 2 * 2
    want = 100 * sent / (busy * 1600e9 / 8)
    assert mod.read(traced) == pytest.approx(want)
    assert 0 < want <= 100


def test_allreduce_share_on_a_hand_made_trace(traced, tmp_path, monkeypatch):
    mod = _reader("allreduce.device_share", monkeypatch, tmp_path)
    # exclusive time a run: device 0 3,700 ns, device 1 4,200 ns, each
    # with 500 ns of all-reduce
    assert mod.read(traced) == pytest.approx(100 * 1000 / 7900)


def test_new_readers_read_nothing_without_their_inputs(traced, tmp_path,
                                                       monkeypatch):
    roof = _reader("exchange.ici_roofline_share", monkeypatch, tmp_path)
    ici = _reader("exchange.ici_bytes_per_batch", monkeypatch, tmp_path)
    ar = _reader("allreduce.device_share", monkeypatch, tmp_path)
    monkeypatch.setattr(roof, "device_kind", lambda: "cpu")
    assert roof.read(traced) is None            # no published ICI peak
    monkeypatch.setattr(roof, "device_kind", lambda: "TPU v5 lite")
    cut = types.SimpleNamespace(**dict(vars(traced),
                                       trace={"truncated": True}))
    assert roof.read(cut) is None
    no_count = types.SimpleNamespace(**dict(vars(traced),
                                            batches=[{"counts": {}}]))
    assert roof.read(no_count) is None and ici.read(no_count) is None
    untraced = types.SimpleNamespace(trace=None, cell={"name": "c"})
    assert ar.read(untraced) is None
