"""Run one benchmark cell once on the chip it is started on:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cells, metrics and configurations are in BENCHMARK.json at the root of
the checkout; chipbench/harness/run.py says what a run does.  It exits
non-zero, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for.
"""
import time

T0 = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench.harness import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(sys.argv[1:], T0))
