"""95th percentile of the same gaps as ``itl_p50_ms`` (host clock)."""

import numpy as np


def read(run):
    gaps = run.gaps()
    return float(np.percentile(gaps, 95)) * 1e3 if len(gaps) >= 20 else None
