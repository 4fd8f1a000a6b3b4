"""Run a cell's control (the program with one stated guarantee broken, see
``systems/<system>_controls.py``) beside the sound program on several seeds
in one process, and print each compared number of each:

    python3 chipbench/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]

Every run is a whole run of the cell at its own size and load.  A control
that transforms outputs is judged on a sound run, beside the sound
judgement; one that patches the program is a run of its own (the sound
readings of such a cell are its benchmark runs).  The benchmark's own runs
never run a control.  One JSON line is printed per judgement:
``{"seed", "control", "correct", "attempted", "checks"}``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench.harness import run, spec as S  # noqa: E402


def one_run(workload, seed, seconds, **kw):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"], **kw)
    if rc:
        raise SystemExit(rc)
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def main(argv=None, **kw):
    """``kw`` goes to ``run.main`` (a CPU rehearsal passes its overrides
    and ``require_chip=False``).  Returns the judgements."""
    ap = argparse.ArgumentParser(description="Run a cell's control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    spec = S.load_spec(ROOT)
    cell = S.cell(spec, a.workload)
    ov = kw.get("overrides", {})
    conf = dict(S.config(spec, cell["config"], ROOT), **ov.get("config", {}))
    mix = dict(S.traffic(cell["traffic"]), **ov.get("traffic", {}))
    controls = S.load_module(ROOT / "chipbench" / "systems" /
                             f"{conf['system']}_controls.py", "controls")
    kind, ctl = controls.control_for(conf, mix)
    out = []
    for seed in a.seeds:
        if kind == "outputs":
            res = one_run(a.workload, seed, a.seconds, controls=[ctl], **kw)
            checks = res["controls"][ctl.__name__]
            judged = [("sound", res["correct"], res["checks"]),
                      (ctl.__name__, all(c["value"] <= c["limit"]
                                         for c in checks.values()), checks)]
        else:
            res = one_run(a.workload, seed, a.seconds, patch=ctl, **kw)
            judged = [(ctl.__name__, res["correct"], res["checks"])]
        for name, correct, checks in judged:
            line = dict(seed=seed, control=name, correct=correct,
                        attempted=res["attempted"], checks=checks)
            print(json.dumps(line), flush=True)
            out.append(line)
    return out


if __name__ == "__main__":
    r = main()
    sys.exit(r if isinstance(r, int) else 0)
