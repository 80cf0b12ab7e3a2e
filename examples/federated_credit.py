"""Give-credit-style run (paper §7.1): larger binary task, three parties
(guest + 2 hosts), GOSS + sparse optimization + cipher compressing on, and
a comparison against the local plaintext baseline (Table 3 role).

    PYTHONPATH=src python examples/federated_credit.py
"""

import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro.core import LocalGBDT, SBTParams, VerticalBoosting
from repro.data import synthetic_tabular
from repro.launch.cache import use_compile_cache

use_compile_cache()


def auc(p, y):
    pos, neg = p[y == 1], p[y == 0]
    return float((pos[:, None] > neg[None, :]).mean())


X, y = synthetic_tabular(n=15000, d=12, seed=1, sparsity=0.4)
Xg, Xh1, Xh2 = X[:, :4], X[:, 4:8], X[:, 8:]

base = SBTParams(n_trees=8, max_depth=4, n_bins=32, goss=True, sparse=True,
                 cipher="plain", seed=1)

t0 = time.time()
local = LocalGBDT(base).fit(X, y)
t_local = time.time() - t0

t0 = time.time()
fed = VerticalBoosting(base).fit(Xg, y, [Xh1, Xh2])
t_fed = time.time() - t0

a_local = auc(local.predict_proba(X), y)
a_fed = auc(fed.predict_proba(Xg, [Xh1, Xh2]), y)
print(f"local  : auc={a_local:.4f}  ({t_local:.1f}s)")
print(f"federated (2 hosts): auc={a_fed:.4f}  ({t_fed:.1f}s)")
print(f"lossless delta: {a_fed - a_local:+.5f}")
print(f"per-tree seconds: {np.mean(fed.stats.tree_seconds):.2f}")
print("comm:", {k: f"{v['bytes'] / 1e6:.2f}MB"
                for k, v in fed.channel.summary().items()})
