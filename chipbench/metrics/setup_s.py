"""setup_s: seconds from the process start to the start of the measured
window: data generation, compilation or cache load, the table load and the
warm-up batch (host clock)."""


def read(run):
    return run.setup["total_s"]
