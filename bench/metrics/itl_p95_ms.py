"""95th percentile of the gaps between consecutive output tokens of a
request, pooled over all requests, both tokens inside the window."""
import numpy as np


def read(run):
    gaps = [b - a for s in run.served for a, b in zip(s.times, s.times[1:])
            if run.t_open <= a and b < run.t_close]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
