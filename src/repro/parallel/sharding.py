"""Sharding rule table of the GBDT engine: a PartitionSpec per array name.

``GBDT_RULES`` / :func:`gbdt_specs` are keyed by array name: the
SecureBoost+ frontier engine shards instances over "data" and the layer
histogram's node axis over "model" (DESIGN.md §5/§7).  "data" expands to
("pod", "data") on a multi-pod mesh.
"""

from __future__ import annotations

import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

# At-rest layouts for the frontier engine (core/frontier.py), DESIGN.md §5.
# Instances shard over "data"; the *feature* axis of at-rest binned
# matrices carries the party boundary on "model", while
# the per-layer histogram batch shards its *node* axis over "model" — the
# node axis is the one that doubles with depth, so it is what the
# intra-party frontier dispatch block-shards.

GBDT_RULES = {
    "bins": ("data", "model"),        # (instance, feature) binned matrix
    "zero_mask": ("data", "model"),   # (instance, feature) sparse mask
    "gh_cts": ("data", None, None),   # (instance, slot, limb) GH ciphertexts
    "node_slot": ("data",),           # (instance,) frontier slot assignment
    "layer_hist": ("model", None, None, None, None),
    #                                  (node, feature, bin, slot, limb)
    "layer_counts": ("model", None, None),   # (node, feature, bin) plaintext
    # round-forest mode (forest_size=k): the slot assignment gains a member
    # (tree) axis — one column per bagged member tree — and the histogram
    # batch gains a leading member axis while its member-local node axis
    # keeps the "model" block-sharding of the layer variant.
    "forest_slot": ("data", None),    # (instance, member) frontier slots
    "forest_hist": (None, "model", None, None, None, None),
    #                          (member, node, feature, bin, slot, limb)
    # crypto endpoints (DESIGN.md §8): both are embarrassingly parallel over
    # rows, so the encrypt input's instance axis and the per-layer decrypt
    # stack's candidate axis shard over "data" with no collective.
    "enc_plain": ("data", None, None),      # (instance, slot, plain-limb)
    "split_infos": ("data", None, None),    # (candidate, slot, limb)
    # serving engine (DESIGN.md §9): decision bits travel transposed and
    # bit-packed — (node-column, instance-byte) — so the *byte* axis is the
    # instance axis and shards over "data"; the routing cursor is
    # (instance, tree).  Routing is embarrassingly parallel over rows: no
    # collective on either array.
    "serve_bits": (None, "data"),           # (node-column, packed inst byte)
    "serve_route": ("data", None),          # (instance, tree)
}


def _data_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def data_pad(mesh, n: int) -> int:
    """Rows to append so an instance/candidate axis of extent ``n`` divides
    the mesh's data-axis extent (device_put of a sharded layout requires
    divisibility).  Pad rows are protocol-inert by construction: bins = -1,
    ciphertexts = 0, never assigned a frontier slot."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    d = int(np.prod([sizes.get(a, 1) for a in _data_axes(mesh)]))
    return -n % d


def gbdt_specs(mesh) -> dict:
    """PartitionSpec per GBDT frontier-engine array (name -> P).

    "data" expands to ("pod", "data") on a multi-pod mesh."""
    dax = _data_axes(mesh)
    d = dax if len(dax) > 1 else dax[0]
    return {k: P(*[d if a == "data" else a for a in v])
            for k, v in GBDT_RULES.items()}


def gbdt_sharding(mesh, name: str, ndim: int | None = None,
                  replicate: tuple = ()):
    """NamedSharding for one GBDT array.

    ``ndim`` trims/pads the rule to the actual rank (e.g. a 2-D flattened
    ciphertext batch).  ``replicate`` drops named axes — the intra-party
    frontier dispatch replicates features over "model" (every node shard
    needs every local feature) while the at-rest cross-party layout keeps
    them sharded."""
    rule = list(GBDT_RULES[name])
    if ndim is not None:
        rule = (rule + [None] * ndim)[:ndim]
    dax = _data_axes(mesh)
    d = dax if len(dax) > 1 else dax[0]
    parts = [None if (a in replicate or a is None)
             else (d if a == "data" else a) for a in rule]
    return NamedSharding(mesh, P(*parts))

