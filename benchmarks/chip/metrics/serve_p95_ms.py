"""serve_p95_ms: 95th percentile (numpy's linear rule) of the latency of
every batch in the window, each timed from the call until its numpy array
is back."""

import numpy as np


def read(run):
    lat = [u["seconds"] for u in run.units if "rows" in u]
    if not lat:
        return None
    return float(np.percentile(lat, 95)) * 1e3
