"""tx_step.device_ms: device milliseconds per run of the jitted tx_loop
step, from the runs of its program in the profiler trace; nothing where
the profiler cut the trace short (a run of more operations than it keeps)."""


def read(run):
    if run.trace is None or run.trace["step_s"] is None:
        return None
    return run.trace["step_s"] * 1e3
