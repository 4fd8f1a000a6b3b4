"""The trace reduction on a hand-made trace whose answers are known."""
import pathlib

import pytest

from chipbench.harness import trace as TR

DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture
def synthetic(tmp_path):
    from jax.profiler import ProfileData
    text = "".join(l for l in (DATA / "synthetic.xplane.txt").open()
                   if not l.startswith("#"))
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return tmp_path


def test_busy_window_step_and_breakdown(synthetic):
    r = TR.reduce_dir(synthetic, "jit_step")
    # window 1000..11000 ns; the step program runs 4000..10000 ns, its
    # operations 4000..6000 and 7000..10000 ns
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(6e-6)
    assert r["step_runs"] == 1 and r["step_s"] == pytest.approx(6e-6)
    assert r["breakdown"]["device_ops"] == [["while.2", pytest.approx(3e-6)],
                                            ["fusion.1", pytest.approx(2e-6)]]
    assert r["breakdown"]["idle_gaps"] == [["draw", pytest.approx(3e-6)],
                                           ["fetch", pytest.approx(1e-6)]]


def test_op_paths_name_the_loop_an_operation_sits_in():
    hlo = ('  %while.2 = (s32[]) while(%t), body=%b, metadata={op_name='
           '"jit(step)/while/body/closed_call/while" stack_frame_id=3}\n'
           '  ROOT %fusion.1 = u32[8] fusion(%p), metadata={op_name='
           '"jit(step)/gather"}\n  %copy.3 = u32[8] copy(%q)\n')
    assert TR.op_paths(hlo) == {"while.2": "jit(step)/while/body/while",
                                "fusion.1": "jit(step)/gather"}
    spans = [(0.0, 10.0, "fetch")]
    dev = {"/device:TPU:0": {"XLA Modules": [(1.0, 9.0, "jit_step(1)")],
                             "XLA Ops": [(1.0, 9.0, "while.2")]}}
    r = TR.reduce(spans, dev, "jit_step", TR.op_paths(hlo))
    assert r["breakdown"]["device_ops"][0][0] == \
        "while.2 jit(step)/while/body/while"


def test_op_names_are_cut_from_hlo_text():
    assert TR.op_name("%fusion.406 = u32[8]{0} fusion(u32[8]{0} %p)") == \
        "fusion.406"
    assert TR.op_name("while.2") == "while.2"


def test_union_merges_overlapping_and_nested_intervals():
    import numpy as np
    s, e = TR._union(np.array([0., 5, 1, 20]), np.array([10., 6, 2, 30]))
    assert s.tolist() == [0, 20] and e.tolist() == [10, 30]


def test_trace_without_spans_is_refused():
    with pytest.raises(ValueError):
        TR.reduce([], {"/device:TPU:0": {"XLA Ops": [(0, 1, "x")]}}, "jit")


def test_a_trace_the_profiler_cut_short_is_clipped_and_marked(monkeypatch):
    # the host waits 150 ms past the device's last recorded event, and the
    # trace holds as many operations as the profiler keeps
    monkeypatch.setattr(TR, "CUT_OPS", 0)
    spans = [(0.0, 50e6, "draw"), (50e6, 250e6, "fetch")]
    dev = {"/device:TPU:0": {"XLA Modules": [(60e6, 100e6, "jit_step(1)")],
                             "XLA Ops": [(60e6, 100e6, "while.1")]}}
    r = TR.reduce(spans, dev, "jit_step")
    assert r["truncated"] is True and r["step_s"] is None
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.04)
    whole = TR.reduce([(0.0, 50e6, "draw"), (50e6, 101e6, "fetch")], dev,
                      "jit_step")
    assert whole["truncated"] is False
    assert whole["step_s"] == pytest.approx(0.04)
    monkeypatch.setattr(TR, "CUT_OPS", 5_000_000)
    slow_host = TR.reduce(spans, dev, "jit_step")     # a late fetch alone
    assert slow_host["truncated"] is False
    assert slow_host["window_s"] == pytest.approx(0.25)


def test_a_trace_stopped_inside_a_batch_ends_at_the_device_last_event():
    # the profiler stopped 1 s into a batch: the fetch span never closed,
    # and no event of the program run itself was kept
    spans = [(0.0, 10e6, "draw"), (10e6, 20e6, "put"),
             (20e6, 35e6, "dispatch")]
    dev = {"/device:TPU:0": {"XLA Ops": [(40e6, 500e6, "while.1"),
                                         (500e6, 990e6, "while.2")]}}
    r = TR.reduce(spans, dev, "jit_step", cut=True)
    assert r["truncated"] is True and r["step_s"] is None
    assert r["window_s"] == pytest.approx(0.99)
    assert r["busy_s"] == pytest.approx(0.95)
    assert r["breakdown"]["idle_gaps"][0][0] == "dispatch"



class FakeProfiler:
    def __init__(self):
        self.calls = []

    def ProfileOptions(self):
        import types
        return types.SimpleNamespace()

    def start_trace(self, log_dir, profiler_options=None):
        self.calls.append(("start", __import__("time").perf_counter()))

    def stop_trace(self):
        self.calls.append(("stop", __import__("time").perf_counter()))


def test_a_late_tracer_starts_late_after_dispatch_and_is_cut(tmp_path):
    import time
    import types

    from chipbench.harness import run
    jax = types.SimpleNamespace(profiler=FakeProfiler())
    t = run.Tracer(jax, tmp_path / "trace", late=0.2)
    t0 = time.perf_counter()
    t.start_late(t0)
    assert t.active and t.cut
    t.stop()
    (start, ts), (stop, _) = jax.profiler.calls
    assert (start, stop) == ("start", "stop") and ts - t0 >= 0.2
    whole = run.Tracer(jax, tmp_path / "trace")
    assert whole.cut is False
