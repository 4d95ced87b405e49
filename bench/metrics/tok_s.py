"""Output tokens appended in the window over the window's wall time."""


def read(run):
    n = sum(1 for s in run.served for t in s.times if run.t_open <= t < run.t_close)
    return n / (run.t_close - run.t_open)
