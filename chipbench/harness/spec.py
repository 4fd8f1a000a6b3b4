"""``BENCHMARK.json`` and the parts it names, found by name on disk.

A configuration is ``configs/<name>.json`` (its ``file`` entry), a traffic
mix ``traffic/<name>.json``, a deployment module ``systems/<system>.py`` with its
plain reference ``systems/<system>_ref.py``, and a metric reader
``metrics/<metric>.py``.  Adding a cell, a mix or a metric adds files and
entries; nothing here changes."""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_spec(root=ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def cell(spec: dict, workload: str) -> dict:
    for c in spec["workloads"]:
        if c["name"] == workload:
            return c
    raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                     f"{[c['name'] for c in spec['workloads']]}")


def config(spec: dict, name: str, root=ROOT) -> dict:
    entry = next(c for c in spec["configs"] if c["name"] == name)
    return json.loads((pathlib.Path(root) / entry["file"]).read_text())


def traffic(name: str, bench_dir=BENCH_DIR) -> dict:
    return json.loads((pathlib.Path(bench_dir) / "traffic" /
                       f"{name}.json").read_text())


def load_module(path: pathlib.Path, name: str):
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def system(name: str, bench_dir=BENCH_DIR):
    """(deployment module, reference module) of a deployment kind."""
    d = pathlib.Path(bench_dir) / "systems"
    return (load_module(d / f"{name}.py", f"chipbench_sys_{name}"),
            load_module(d / f"{name}_ref.py", f"chipbench_ref_{name}"))


def reader(metric: str, bench_dir=BENCH_DIR):
    return load_module(pathlib.Path(bench_dir) / "metrics" / f"{metric}.py",
                       "chipbench_metric_" + metric.replace(".", "_"))


def metrics_for(spec: dict, workload: str, traced: bool) -> list:
    """The metric entries a run of ``workload`` reports: its end-to-end
    metrics untraced, its per-layer metrics traced.  An entry without a
    ``workloads`` key covers every cell that reports what it moves."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]
