"""Every layer of the compiled tx_loop step carries a stable named scope.

The dataplane names its layers with ``jax.named_scope`` so that a profiler
trace gives device time per layer by name (``chipbench/harness/layers.py``
reads them): ``storm.round.<phase>`` around each exchange round, the six
parts of a round inside it, ``storm.occ.<step>`` around the client's OCC
work and ``storm.txloop`` around the retry engine's, its round gate
included.  Compiled on the CPU for the TATP shape (8 ``SimTransport`` nodes,
2 reads and 1 write a lane), unreplicated and with two backups, at a small
table size; and the four-node mesh step, whose collectives sit under
``storm.exchange`` (the all-to-alls) and ``storm.allreduce`` (the
cross-chip psums of the counts and of the round gate's live lanes).
"""
import os
import pathlib
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.core import replication as repl
from repro.core import slots as sl
from repro.core import txloop as txl
from repro.core.datastructs import hashtable as ht
from repro.core.transport import SimTransport

N, LANES, RD, WR = 8, 8, 2, 1
PARTS = ("pack", "exchange", "handler.serial", "handler.vector", "gather",
         "unpack")
SCOPE = re.compile(r"storm\.(\w+(?:\.\w+)*)")
COMP = re.compile(r"^(ENTRY )?%(\S+) .*\{$")
OP = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*?metadata=\{op_name="([^"]*)"',
                re.MULTILINE)
INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$")
OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
CALLS = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.-]+)"
                   r"|branch_computations=\{([^}]*)\}")
# instructions that do no work of their own on the device
NO_WORK = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}


def opcode(rest):
    """The opcode of an instruction's text after ``%name = ``: the word
    after its shape, which is a tuple in brackets or one word."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.split(" ", 1)[1]
    return re.match(r"\s*([\w-]+)", rest).group(1)


def operations(text):
    """{instruction: source path} of the operations a profiler shows (those
    of the entry computation, loop bodies and conditions, not the insides
    of fusions or reducers).  An instruction the compiler added with no
    ``op_name`` takes the path of the instruction calling its computation,
    as ``chipbench/harness/layers.py`` reads them."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
            continue
        m = INSTR.match(line)
        if m and cur is not None:
            p = OP_NAME.search(line)
            called = [c for a, bs in CALLS.findall(line)
                      for c in ([a] if a else re.findall(r"%([\w.-]+)", bs))]
            cur.append((m.group(1), opcode(m.group(2)), p and p.group(1), called,
                        "to_apply=" in line or "calls=" in line))
    ops, todo, seen = {}, [(entry, None, True)], set()
    while todo:
        comp, outer, runs = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name, op, path, called, inner in comps[comp]:
            path = path or outer
            if runs and op not in NO_WORK:
                ops[name] = path or ""
            todo += [(c, path, runs and not inner) for c in called]
    return ops


def compiled_step(f, max_rounds=4):
    cfg = ht.HashTableConfig(n_nodes=N, n_buckets=256, bucket_width=1,
                             n_overflow=128, max_chain=4)
    layout = ht.build_layout(cfg)
    t = SimTransport(N)
    rep = repl.ReplicaConfig(N, f) if f else None

    def step(state, rk, wk, wv, ren, wen, key):
        state, _, res = txl.tx_loop(
            t, state, cfg, layout, read_keys=rk, write_keys=wk,
            write_values=wv, read_enabled=ren, write_enabled=wen,
            max_rounds=max_rounds, key=key, rep=rep)
        return state, res.committed, res.round_trips

    S, u32 = jax.ShapeDtypeStruct, jnp.uint32
    state = jax.eval_shape(lambda: ht.init_cluster_state(cfg))
    shapes = (state, S((N, LANES, RD, 2), u32), S((N, LANES, WR, 2), u32),
              S((N, LANES, WR, sl.VALUE_WORDS), u32), S((N, LANES, RD), jnp.bool_),
              S((N, LANES, WR), jnp.bool_), S((2,), u32))
    return jax.jit(step).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("f", [0, 2])
def test_every_layer_of_the_step_is_named(f):
    text = compiled_step(f)
    # every instruction that names its source, fused ones included
    paths = dict(OP.findall(text))
    scopes = {name: SCOPE.findall(path) for name, path in paths.items()}
    # an instruction inside a round also carries one of the round's parts
    in_round = {n: s for n, s in scopes.items()
                if any(x.startswith("round.") for x in s)}
    assert in_round
    bare = [paths[n] for n, s in in_round.items()
            if not any(x in PARTS for x in s)]
    assert not bare, bare[:5]
    seen = {x for s in scopes.values() for x in s}
    for part in ("handler.serial", "handler.vector", "gather", "exchange",
                 "pack"):
        assert part in seen, sorted(seen)
    assert {"round.read", "round.lock", "round.validate",
            "round.commit"} <= seen
    assert {"occ.read", "occ.lock", "occ.validate", "occ.commit",
            "txloop"} <= seen
    # under 5% of the step's operations name no layer
    ops = operations(text)
    unscoped = [n for n, p in ops.items() if not SCOPE.search(p)]
    assert len(unscoped) < 0.05 * len(ops), (len(ops), unscoped)


COND = re.compile(r'^\s*(?:ROOT )?%\S+ = .*? conditional\(.*?op_name="([^"]*)"',
                  re.MULTILINE)


@pytest.mark.parametrize("f", [0, 2])
def test_the_round_gate_is_named(f):
    """The conditional that skips a round with no live lane sits under
    ``storm.txloop``, and the gate adds no unscoped operation: the step at
    four rounds has no more than the ungated one-round step."""
    gated, one = compiled_step(f), compiled_step(f, max_rounds=1)
    gates = COND.findall(gated)
    assert gates and all("txloop" in SCOPE.findall(p) for p in gates), gates
    assert not COND.findall(one)
    unscoped = [len([n for n, p in operations(t).items()
                     if not SCOPE.search(p)]) for t in (gated, one)]
    assert unscoped[0] <= unscoped[1], unscoped


# The four-node mesh step (one MeshTransport node per device) compiled in a
# subprocess that forces four host devices, beside the same step under
# SimTransport(4); each writes its compiled program's text.
MESH_SCRIPT = textwrap.dedent("""
    import pathlib, sys
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import tatp
    from repro.core.datastructs import hashtable as ht

    cfg = ht.HashTableConfig(n_nodes=4, n_buckets=256, bucket_width=1,
                             n_overflow=128, max_chain=4)
    mesh = jax.make_mesh((4,), ("node",))
    for name, cl in (("mesh", tatp.Cluster(4, mesh=mesh, cfg=cfg)),
                     ("sim", tatp.Cluster(4, cfg=cfg))):
        *node, key = cl.tx_arg_shapes()
        if cl.mesh is not None:
            put = lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, spec))
            node = jax.tree.map(lambda s: put(s, P("node")), tuple(node))
            key = put(key, P())
        text = cl.tx_step().lower(*node, key).compile().as_text()
        (pathlib.Path(sys.argv[1]) / f"{name}.hlo").write_text(text)
""")
COLLECTIVE = re.compile(r'^\s*(?:ROOT )?%\S+ = .*? (all-to-all|all-reduce)\('
                        r'.*?op_name="([^"]*)"', re.MULTILINE)


def test_the_mesh_step_names_its_collectives(tmp_path):
    """The compiled mesh step places every all-to-all under
    ``storm.exchange`` and the cross-chip psum of the counts under
    ``storm.allreduce``, and adds no unscoped operation to the step."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(pathlib.Path(__file__).resolve().parents[1]
                              / "src"))
    res = subprocess.run([sys.executable, "-c", MESH_SCRIPT, str(tmp_path)],
                         capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    mesh, sim = ((tmp_path / f"{n}.hlo").read_text() for n in ("mesh", "sim"))
    found = {}
    for op, path in COLLECTIVE.findall(mesh):
        found.setdefault(op, set()).add(SCOPE.findall(path)[-1])
    assert found == {"all-to-all": {"exchange"}, "all-reduce": {"allreduce"}}
    assert not COLLECTIVE.findall(sim)
    unscoped = [len([n for n, p in operations(t).items()
                     if not SCOPE.search(p)]) for t in (mesh, sim)]
    assert unscoped[0] <= unscoped[1], unscoped
