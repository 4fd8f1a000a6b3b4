"""Whole runs of every cell on the CPU at small sizes: sound runs come out
correct with the contract's result line; runs with a fault planted under
the timed path, and each cell's control, come out not correct."""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import ROOT, tiny_run

from chipbench.harness import spec as S

SPEC = S.load_spec(ROOT)
CELLS = [c["name"] for c in SPEC["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_with_the_contract_keys(workload):
    res, err = tiny_run(workload)
    assert list(res) == KEYS
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in S.metrics_for(SPEC, workload, False)}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert err[-3] == "correct True"
    assert list(res["checks"]) == ["wrong_answers", "uncommitted_share"]
    assert err[-2:] == [f"{n} {c['value']} limit {c['limit']}"
                        for n, c in res["checks"].items()]


# --- faults planted under the timed path -----------------------------------
def state_unchanged(dep, table):
    step = dep.step_fn

    def fault(state, *args):
        _, lanes, counts = step(jax.tree.map(jnp.copy, state), *args)
        return state, lanes, counts
    dep.step_fn = fault


def half_batch_left_out(dep, table):
    put = dep.put

    def fault(table_, b):
        b = dict(b, ren=b["ren"].copy(), wen=b["wen"].copy())
        b["ren"][:, ::2] = False
        b["wen"][:, ::2] = False
        return put(table_, b)
    dep.put = fault


def half_batch_dropped_as_aborts(dep, table):
    """Every other lane neither read nor written, and reported uncommitted:
    the answers that are given are all right."""
    put, fetch = dep.put, dep.fetch

    def put_(table_, b):
        b = dict(b, ren=b["ren"].copy(), wen=b["wen"].copy())
        b["ren"][:, ::2] = False
        b["wen"][:, ::2] = False
        return put(table_, b)

    def fetch_(out):
        lanes, counts = fetch(out)
        lanes["committed"] = lanes["committed"].copy()
        lanes["committed"][:, ::2] = False
        return lanes, counts
    dep.put, dep.fetch = put_, fetch_


def answer_altered(dep, table):
    fetch = dep.fetch

    def fault(out):
        lanes, counts = fetch(out)
        lanes["read_values"] = lanes["read_values"].copy()
        i = np.argwhere(lanes["committed"][..., None] & lanes["read_found"])[0]
        lanes["read_values"][tuple(i)][0] ^= np.uint32(1)
        return lanes, counts
    dep.fetch = fault


def exchange_left_out(dep, table):
    """The window's step recompiled with every exchange a no-op: each node
    serves its own send buffer (the load before it is sound)."""
    from repro.core import transport
    exchange = transport.SimTransport.exchange
    transport.SimTransport.exchange = lambda self, x: x
    try:
        fn, shapes = dep.step_program()
        dep.step_fn = fn.lower(*shapes).compile()
    finally:
        transport.SimTransport.exchange = exchange


FAULTS = {"state_unchanged": state_unchanged,
          "half_batch_left_out": half_batch_left_out,
          "half_batch_dropped_as_aborts": half_batch_dropped_as_aborts,
          "answer_altered": answer_altered,
          "exchange_left_out": exchange_left_out}


def cases():
    for w in CELLS:
        for f in FAULTS:
            writes = S.traffic(S.cell(SPEC, w)["traffic"])["static_writes"]
            if f == "state_unchanged" and not writes:
                continue          # a read-only step never changes the state
            yield w, f


@pytest.mark.parametrize("workload,fault", list(cases()))
def test_planted_fault_is_not_correct(workload, fault):
    res, _ = tiny_run(workload, patch=FAULTS[fault], seconds=1.5,
                      config={"subscribers": 512})
    assert res["correct"] is False, res["checks"]
    if fault == "half_batch_dropped_as_aborts":
        assert res["checks"]["wrong_answers"]["value"] == 0
        assert res["checks"]["uncommitted_share"]["value"] == 0.5


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct_beside_a_sound_run(workload):
    """control.py's judgements at small size: the sound program correct
    where judged, its control not."""
    from chipbench import control
    from conftest import TINY
    ov = {"config": dict(TINY["config"], subscribers=512),
          "traffic": TINY["traffic"]}
    lines = control.main(["--workload", workload, "--seconds", "1.5",
                          "--seeds", "5", "6"], require_chip=False,
                         overrides=ov)
    assert all(l["correct"] == (l["control"] == "sound") for l in lines)
    assert sum(l["control"] != "sound" for l in lines) == 2
    assert all(l["checks"]["wrong_answers"]["value"] > 0
               for l in lines if l["control"] != "sound")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chipbench" / "run.py"),
                        "--workload", CELLS[0], "--seed", "1", "--seconds",
                        "1", "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert "{" not in p.stdout


def test_a_new_mix_is_a_data_file_and_an_entry(tmp_path):
    """A cell added by a traffic file and BENCHMARK.json entries alone,
    in a copy of the benchmark, runs with no other file edited."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    mix = S.traffic("tatp_mix")
    mix["types"] = [{"name": "update", "share": 1.0, "reads": 1,
                     "writes": 1}]
    (tmp_path / "chipbench" / "traffic" / "stub_write.json").write_text(
        json.dumps(mix))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "tatp_sf10.stub", "config": "tatp_sf10",
                              "traffic": "stub_write", "chips": 1,
                              "why": "stub"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    res, _ = tiny_run("tatp_sf10.stub", root=tmp_path)
    assert res["correct"] is True
    assert res["metrics"]["committed_tx_per_s"]["value"] > 0
