"""device.idle_share: percent of the traced window in which no operation
ran on the device (1 - busy / window, from the profiler trace); nothing
where the profiler cut the trace short."""


def read(run):
    if run.trace is None or run.trace["truncated"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
