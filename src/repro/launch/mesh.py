"""Mesh construction for the GBDT engine.

A FUNCTION (not module-level state) so importing never touches jax device
initialization.
"""

from __future__ import annotations

import jax


def make_gbdt_mesh(model: int | None = None):
    """(data, model) mesh over all local devices for the GBDT frontier
    engine (DESIGN.md §5/§7): instances shard over "data", the layer
    histogram's node axis over "model".  ``model`` caps the node-shard
    count (default 2 when the device count allows, so both collectives are
    exercised); instances take the remaining factor.  Returns None on a
    single device — the engine then uses the unsharded dispatch."""
    n = len(jax.devices())
    if n < 2:
        return None
    if model is None:
        model = 2 if n % 2 == 0 else 1
    model = max(1, min(model, n))
    while n % model:
        model -= 1
    # Auto axes: the engine places arrays with NamedShardings and lets the
    # compiler propagate them; it does not carry shardings in its types
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(auto, auto))

