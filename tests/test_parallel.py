"""GBDT sharding rule table unit tests.

Specs are pure functions of the mesh shape; a duck-typed fake mesh lets
these run without multi-device XLA."""

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import gbdt_specs


class FakeMesh:
    def __init__(self, shape, names):
        self.devices = np.empty(shape, dtype=object)
        self.axis_names = names


MESH = FakeMesh((16, 16), ("data", "model"))
POD_MESH = FakeMesh((2, 16, 16), ("pod", "data", "model"))


def test_gbdt_rule_table():
    """GBDT frontier specs (DESIGN.md §5): instances over data, at-rest
    features over model, layer-histogram node axis over model."""
    specs = gbdt_specs(MESH)
    assert specs["bins"] == P("data", "model")
    assert specs["gh_cts"] == P("data", None, None)
    assert specs["node_slot"] == P("data")
    assert specs["layer_hist"] == P("model", None, None, None, None)
    assert specs["layer_counts"] == P("model", None, None)
    # multi-pod: "data" expands to ("pod", "data")
    pod = gbdt_specs(POD_MESH)
    assert pod["bins"] == P(("pod", "data"), "model")
    assert pod["layer_hist"][0] == "model"


def test_gbdt_sharding_trims_and_replicates():
    from repro.parallel.sharding import gbdt_sharding

    # gbdt_sharding builds a NamedSharding, which needs a real mesh
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    flat2d = gbdt_sharding(mesh, "gh_cts", ndim=2)
    assert flat2d.spec == P("data", None)
    repl = gbdt_sharding(mesh, "bins", replicate=("model",))
    assert repl.spec == P("data", None)

