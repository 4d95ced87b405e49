"""95th percentile of the time to first token (see ttft_p50_ms)."""
import numpy as np

from .ttft_p50_ms import ttfts_ms


def read(run):
    v = ttfts_ms(run)
    return float(np.percentile(v, 95)) if v else None
