"""Process start to the window's open: imports, weights, plan warm-up,
compilation or the compile cache, and the warm-up serve."""


def read(run):
    return run.setup_s
