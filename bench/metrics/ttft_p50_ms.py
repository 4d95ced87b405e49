"""Median time from a request's send (its slot's admission) to its first
host-visible token, over requests whose first token fell in the window
(not those that stand for requests already in flight at its open)."""
import numpy as np


def ttfts_ms(run):
    return [(s.times[0] - s.admitted) * 1e3 for s in run.served
            if s.times and not s.in_flight
            and run.t_open <= s.times[0] < run.t_close]


def read(run):
    v = ttfts_ms(run)
    return float(np.percentile(v, 50)) if v else None
