"""Quickstart: vertical federated GBDT in ~20 lines.

A guest (holds labels + 5 features) and one host (5 features) jointly train
a SecureBoost+ model; the host never sees labels or gradients (they arrive
homomorphically encrypted), the guest never sees host feature values.

    PYTHONPATH=src python examples/quickstart.py
"""

import sys

sys.path.insert(0, "src")

import numpy as np

from repro.core import SBTParams, VerticalBoosting
from repro.data import synthetic_tabular
from repro.launch.cache import use_compile_cache

use_compile_cache()

X, y = synthetic_tabular(n=4000, d=10, seed=0)
X_guest, X_host = X[:, :5], X[:, 5:]

params = SBTParams(
    n_trees=5, max_depth=4, n_bins=32,
    cipher="affine", key_bits=1024,     # the TPU-path cipher; try "paillier"
    goss=True,                          # gradient-based one-side sampling
)
model = VerticalBoosting(params).fit(X_guest, y, [X_host])

p = model.predict_proba(X_guest, [X_host])
acc = ((p > 0.5) == y).mean()
pos, neg = p[y == 1], p[y == 0]
auc = (pos[:, None] > neg[None, :]).mean()
print(f"train acc={acc:.3f}  auc={auc:.3f}")
print("HE ops:", {k: v for k, v in model.stats.as_dict().items()
                  if k.startswith("n_")})
print("comm bytes by message type:",
      {k: v["bytes"] for k, v in model.channel.summary().items()})
