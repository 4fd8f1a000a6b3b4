"""The per-layer reduction (``harness/layers.py``) on hand-made traces
whose answers are known, and its reading of the HLO a trace carries."""
import pathlib
import types

import numpy as np
import pytest

from chipbench.harness import layers as L
from chipbench.harness import trace as TR

DATA = pathlib.Path(__file__).parent / "data"
SERIAL = ("jit(step)/while/body/storm.occ.lock/storm.round.lock/"
          "storm.handler.serial/while")
GATHER = "jit(step)/while/body/storm.occ.lock/storm.round.lock/storm.gather"


def xspace(extra=""):
    from jax.profiler import ProfileData
    text = "".join(l for l in (DATA / "layers.xplane.txt").open()
                   if not l.startswith("#"))
    return ProfileData.text_proto_to_serialized_xspace(text + extra)


def events(tmp_path, extra=""):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xspace(extra))
    return TR.read_events(TR.find_xplane(tmp_path))


def test_exclusive_time_per_part_and_phase(tmp_path):
    spans, devices = events(tmp_path)
    paths = {"while.1": "jit(step)/while", "fusion.2": SERIAL,
             "fusion.3": GATHER + "/gather"}
    lo, hi = 1000, 13000          # the host spans' window, ns
    r = L.reduce_layers(devices, paths, lo, hi)
    # while.1 runs 3000..11000 ns and keeps what its two operations leave;
    # fusion.4 (11000..12000) has no path
    assert r["parts"] == {"gather": pytest.approx(1e-6),
                          "handler.serial": pytest.approx(2e-6),
                          "unscoped": pytest.approx(5e-6 + 1e-6)}
    assert r["phases"] == {"lock": pytest.approx(3e-6)}
    # the union of the operations' time, 3000..12000 ns
    assert r["total_s"] == pytest.approx(9e-6)
    assert r["total_s"] == pytest.approx(
        TR.reduce(spans, devices, "jit_step")["busy_s"])
    # the window clips: from 5000 ns on
    r = L.reduce_layers(devices, paths, 5000, hi)
    assert r["parts"]["handler.serial"] == pytest.approx(1e-6)
    assert r["total_s"] == pytest.approx(7e-6)


def test_a_program_without_scopes_gives_no_layers(tmp_path):
    _, devices = events(tmp_path)
    assert L.reduce_layers(devices, {"while.1": "jit(step)/while"},
                           1000, 13000) is None


def test_exclusive_time_matches_an_instant_by_instant_count():
    rng = np.random.RandomState(7)
    for _ in range(20):
        n = rng.randint(1, 40)
        s = rng.randint(0, 200, n)
        e = s + rng.randint(0, 60, n)
        lo, hi = 20, 180
        got = L.exclusive(s, e, lo, hi) * 1e9
        want = np.zeros(n)
        # each ns goes to the latest start still running; of equal
        # starts, to the one that ends first
        order = sorted(range(n), key=lambda i: (s[i], -e[i]))
        for t in range(lo, hi):
            live = [i for i in order if s[i] <= t < e[i]]
            if live:
                want[live[-1]] += 1
        np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("path, want", [
    (None, ("unscoped", None)),
    ("jit(step)/while/body/add", ("unscoped", None)),
    ("jit(step)/storm.txloop/while/body/add", ("txloop", None)),
    (SERIAL + "/while/body/dynamic_slice", ("handler.serial", "lock")),
    ("jit(step)/storm.occ.commit/storm.round.commit/vmap(storm.gather)/x",
     ("gather", "commit")),
    ("jit(step)/storm.occ.lock/storm.round.lock/add", ("round", "lock")),
    ("jit(step)/storm.occ.validate/sub", ("occ.validate", None)),
])
def test_each_path_goes_to_its_innermost_scope(path, want):
    assert L.classify(path) == want


# --- the HLO a trace carries ---------------------------------------------------
def _varint(x):
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _len(field, payload):
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field, x):
    return _varint(field << 3) + _varint(x)


def _instr(name, op_name=None, called=()):
    b = _len(1, name.encode())
    if op_name:
        b += _len(7, _len(2, op_name.encode()))
    if called:
        b += _len(38, b"".join(_varint(c) for c in called))
    return b


def _comp(cid, instrs):
    return (_len(1, b"c%d" % cid) + b"".join(_len(2, i) for i in instrs)
            + _int(5, cid))


def hlo_proto(scoped=True):
    """HloProto of a program whose entry computation (1) holds while.1 and
    fusion.4, and whose loop body (2) holds fusion.2 and fusion.3; fusion.3
    has no op_name of its own."""
    s = "storm." if scoped else ""
    entry = _comp(1, [
        _instr("while.1", f"jit(step)/{s}txloop/closed_call/while",
               called=[2]),
        _instr("fusion.4", f"jit(step)/{s}occ.commit/{s}round.commit/"
                           f"vmap({s}gather)/gather")])
    body = _comp(2, [
        _instr("fusion.2", f"jit(step)/{s}txloop/while/body/{s}occ.lock/"
                           f"{s}round.lock/{s}handler.serial/while"),
        _instr("fusion.3")])
    return _len(1, _len(1, b"jit_step") + _len(3, entry) + _len(3, body)
                + _int(6, 1))


def metadata_plane(proto):
    esc = "".join(f"\\{c:03o}" for c in proto)
    return (f'planes {{ id: 3 name: "/host:metadata"\n'
            f'  event_metadata {{ key: 7 value {{ id: 7 name: "jit_step(7)" '
            f'stats {{ metadata_id: 1 bytes_value: "{esc}" }} }} }}\n'
            f'  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}'
            f'\n}}\n')


def traced_run(tmp_path, proto, cell="c"):
    d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xspace(metadata_plane(proto)))
    return types.SimpleNamespace(trace={"truncated": False},
                                 cell={"name": cell})


def test_paths_come_from_the_hlo_in_the_trace(tmp_path):
    traced_run(tmp_path, hlo_proto())
    xplane = TR.find_xplane(tmp_path / ".bench_trace" / "c")
    protos = L.hlo_protos(xplane)
    assert list(protos) == ["jit_step(7)"]
    paths = L.hlo_paths(protos["jit_step(7)"])
    # fusion.3 takes the path of the loop whose body holds it
    assert paths == {
        "while.1": "jit(step)/storm.txloop/while",
        "fusion.4": "jit(step)/storm.occ.commit/storm.round.commit/"
                    "vmap(storm.gather)/gather",
        "fusion.2": SERIAL.replace("while/body/", "storm.txloop/while/body/"),
        "fusion.3": "jit(step)/storm.txloop/while"}


def test_a_traced_run_reads_its_layers_once(tmp_path, capsys):
    run = traced_run(tmp_path, hlo_proto())
    assert L.share(run, tmp_path, "gather") == pytest.approx(100 / 9)
    assert L.share(run, tmp_path, "occ.", "txloop") == pytest.approx(600 / 9)
    assert L.share(run, tmp_path, "handler.vector") == 0.0
    lay = run.layers
    assert lay["parts"] == {"gather": pytest.approx(1e-6),
                            "handler.serial": pytest.approx(2e-6),
                            "txloop": pytest.approx(6e-6)}
    assert lay["phases"] == {"commit": pytest.approx(1e-6),
                             "lock": pytest.approx(2e-6)}
    assert lay["total_s"] == pytest.approx(9e-6)
    assert capsys.readouterr().out.count("layers: ") == 1


def test_runs_without_a_trace_or_scopes_read_nothing(tmp_path):
    untraced = types.SimpleNamespace(trace=None, cell={"name": "c"})
    assert L.share(untraced, tmp_path, "gather") is None
    run = traced_run(tmp_path, hlo_proto(scoped=False))
    assert L.share(run, tmp_path, "gather") is None


def test_paths_agree_with_the_compiled_text(tmp_path):
    """On a CPU profile of a small scoped program, every operation that
    names its source reads the same path from the trace's HLO as from the
    compiled program's text."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("storm.round.lock"):
            with jax.named_scope("storm.handler.serial"):
                y = jax.lax.fori_loop(0, 3, lambda i, c: c * 2 + 1, x)
        return y.sum()

    c = step.lower(jnp.ones(8)).compile()
    jax.profiler.start_trace(str(tmp_path))
    c(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    protos = L.hlo_protos(TR.find_xplane(tmp_path))
    name = next(n for n in protos if n.startswith("jit_step("))
    got = L.hlo_paths(protos[name])
    want = TR.op_paths(c.as_text())
    assert want and all(got[n] == p for n, p in want.items())
    assert any("storm.handler.serial" in p for p in got.values())
