"""Rehearsals of the benchmark on the CPU at small sizes.

Run with ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests`` from the
root of the checkout.  ``tiny_run`` drives a whole run of a cell (set-up,
window, comparison, result line) with the chip check skipped and the
deployment shrunk; what it measures says nothing about the chip.
"""
import contextlib
import io
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"config": {"subscribers": 2048, "load_lanes": 128,
                   "table": {"n_buckets": 1024, "bucket_width": 1,
                             "n_overflow": 512, "max_chain": 12}},
        "traffic": {"lanes": 8}}


def tiny_run(workload, *, seed=3_000_000_017, seconds=1.0, trace=0,
             patch=None, root=ROOT, config=None):
    """One whole run of ``workload`` on the CPU at TINY size; returns
    (result line as a dict, standard error lines)."""
    from chipbench.harness import run
    ov = {"config": dict(TINY["config"], **(config or {})),
          "traffic": TINY["traffic"]}
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      require_chip=False, overrides=ov, patch=patch,
                      root=root)
    assert rc == 0, err.getvalue()
    return (json.loads(out.getvalue().strip().splitlines()[-1]),
            err.getvalue().strip().splitlines())
