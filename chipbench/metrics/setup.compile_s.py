"""setup.compile_s: seconds to compile, or load from the persistent
cache, the load step and the window's step (host clock)."""


def read(run):
    return run.setup["compile_s"]
