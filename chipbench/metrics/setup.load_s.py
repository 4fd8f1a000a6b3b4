"""setup.load_s: seconds the table load took, up to the loaded state on
the device (host clock)."""


def read(run):
    return run.setup["load_s"]
