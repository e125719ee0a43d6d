"""Process start to the first boundary: imports, data, initial
weights, compile or cache load, and generation 1 (which runs every
program once)."""


def read(run):
    if run.window.start is None:
        return None
    return run.window.start - run.t0
