"""Seeded HIGGS- and epsilon-shaped tabular data for the chip benchmark.

The benchmark's own copy of the program's ``synthetic_tabular`` generator
(standard-normal features, a linear-plus-interaction score, the label
``score > median``), so that no program change can move the data a cell
is measured on.  Two departures, both for set-up time at the cells'
sizes: features are drawn in float32 directly (``standard_normal`` with
``dtype``), and the guest's and the host's columns are drawn as two
contiguous matrices, so no 2,000-column slice is copied.  Every seed
gives the same sizes; the seed only changes the values.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """One independent stream per use of the seed (any non-negative int)."""
    return np.random.default_rng([int(seed), int(stream)])


def vertical(n_rows: int, n_guest: int, n_host: int, seed: int,
             stream: int = 0):
    """(X_guest, X_host, y): float32 features split guest / host, and a
    balanced binary float64 label."""
    rng = rng_for(seed, stream)
    Xg = rng.standard_normal((n_rows, n_guest), dtype=np.float32)
    Xh = rng.standard_normal((n_rows, n_host), dtype=np.float32)
    wg = rng.normal(0.0, 1.0, n_guest)
    wh = rng.normal(0.0, 1.0, n_host)
    first = Xg[:, 0].astype(np.float64)
    second = (Xg[:, 1] if n_guest > 1 else Xh[:, 0]).astype(np.float64)
    s = Xg @ wg.astype(np.float32) + Xh @ wh.astype(np.float32)
    s = s.astype(np.float64) + 0.5 * first * second \
        + 0.3 * rng.normal(0.0, 1.0, n_rows)
    y = (s > np.median(s)).astype(np.float64)
    return Xg, Xh, y


def features(n_rows: int, n_guest: int, n_host: int, seed: int,
             stream: int):
    """Unlabelled rows of the same shape (serving batches)."""
    rng = rng_for(seed, stream)
    return (rng.standard_normal((n_rows, n_guest), dtype=np.float32),
            rng.standard_normal((n_rows, n_host), dtype=np.float32))
