"""Median of every gap between consecutive streamed tokens of a request,
over all requests, both tokens inside the window (host clock)."""

import numpy as np


def read(run):
    gaps = run.gaps()
    return float(np.percentile(gaps, 50)) * 1e3 if len(gaps) else None
