"""SecureBoost-MO (paper §5.3): multi-output trees vs per-class trees.

One MO tree per boosting round replaces k per-class trees; g/h vectors are
packed across classes into ceil(k/eta_c) ciphertexts (Algorithm 7).

    PYTHONPATH=src python examples/multiclass_mo.py
"""

import sys
import time

sys.path.insert(0, "src")

from repro.core import SBTParams, VerticalBoosting
from repro.data import synthetic_tabular
from repro.launch.cache import use_compile_cache

use_compile_cache()

k = 7
X, y = synthetic_tabular(n=5000, d=20, seed=2, task="multi", n_classes=k)
Xg, Xh = X[:, :10], X[:, 10:]

for objective in ["multiclass", "mo"]:
    params = SBTParams(n_trees=4, max_depth=4, n_bins=32, objective=objective,
                       n_classes=k, cipher="affine", key_bits=1024,
                       precision=24, seed=2)
    t0 = time.time()
    m = VerticalBoosting(params).fit(Xg, y, [Xh])
    dt = time.time() - t0
    acc = (m.predict_proba(Xg, [Xh]).argmax(1) == y).mean()
    print(f"{objective:10s}: trees={len(m.trees):2d}  acc={acc:.3f}  "
          f"time={dt:.1f}s  decrypts={m.stats.n_decrypt}")
