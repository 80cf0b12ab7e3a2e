#!/usr/bin/env python3
"""Train and serve SecureBoost+ on the TPU at HIGGS width, and check it.

    python chip_smoke.py              # one chip: the deployment run
    python chip_smoke.py --chips 4    # four chips: the mesh-sharded frontier
                                      # engine against one device, only

One process holds the chip and runs every party: the host party is
in-process, never a spawned interpreter.  The run:

* trains through ``VerticalBoosting.fit`` on 1,000,000 HIGGS-shaped rows
  (``repro.data.synthetic_tabular``, 28 features split 14 guest / 14 host)
  at the paper's cost-model settings: 1024-bit keys, depth 5, 32 bins,
  3 trees;
* checks the ``plain`` cipher bit-identical to ``LocalGBDT``, the
  ``affine`` cipher within 1e-6 of it, and packed serving
  (``predict_proba`` on one 65,536-row batch) equal to the legacy loop;
* checks every histogram, modmul and bucketize kernel compiled natively:
  ``tpu_custom_call`` in the compiled dispatch, not interpret mode and not
  the reference formulation.

Exits non-zero, printing no result line, when JAX finds no TPU or any
phase fails.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

HIGGS_ROWS = 11_000_000     # the published dataset
N_ROWS = 1_000_000          # cut to fit one run's time limit
N_FEATURES = 28             # HIGGS's published width
N_GUEST = 14                # guest 14 / host 14
SERVE_ROWS = 65_536
LEGACY_ROWS = 4_096
SEED = 0
# the paper's cost-model point (benchmarks/bench_cost_model.py), 3 trees
TRAIN = dict(n_trees=3, max_depth=5, n_bins=32, key_bits=1024, seed=SEED)
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


def log(msg: str) -> None:
    print(msg, flush=True)


class Phases:
    """Wall time of each phase, printed as it ends (not benchmark numbers:
    compilation is included)."""

    def __init__(self):
        self.seconds: dict = {}

    def run(self, name: str, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        dt = time.perf_counter() - t0
        self.seconds[name] = dt
        log(f"phase {name}: {dt:.2f} s")
        return out


def require_tpu():
    import jax
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX backend is "
                 f"{jax.default_backend()!r} ({jax.devices()})")
    return jax.devices()


def make_data():
    from repro.data import synthetic_tabular
    X, y = synthetic_tabular(N_ROWS, N_FEATURES, seed=SEED)
    Xs, _ = synthetic_tabular(SERVE_ROWS, N_FEATURES, seed=SEED + 1)
    log(f"data: HIGGS-shaped synthetic_tabular(seed={SEED}): rows cut "
        f"{HIGGS_ROWS:,} -> {N_ROWS:,}; features {N_FEATURES} (published "
        f"width) = {N_GUEST} guest + {N_FEATURES - N_GUEST} host; serving "
        f"batch {SERVE_ROWS:,} rows (seed {SEED + 1})")
    return X, y, Xs


def check_native_kernels():
    """Compile each kernel dispatch the training and serving paths call,
    at real widths, and require the Pallas kernel in the compiled program:
    interpret mode and the reference formulations lower to no
    ``tpu_custom_call``."""
    import jax
    import jax.numpy as jnp
    from repro.core.he import get_cipher
    from repro.kernels.binning import bucketize
    from repro.kernels.common import default_interpret
    from repro.kernels.histogram import (ciphertext_histogram,
                                         forest_ciphertext_histogram,
                                         layer_ciphertext_histogram)
    from repro.kernels.modmul import modmul_fixed

    if default_interpret():
        raise RuntimeError("Pallas kernels would run in interpret mode")
    cipher = get_cipher("affine", key_bits=TRAIN["key_bits"], seed=SEED)
    n, nf, nb, L = SERVE_ROWS, N_GUEST, TRAIN["n_bins"], cipher.hist_width
    bins = jnp.zeros((n, nf), jnp.int32)
    cts = jnp.zeros((n, L), jnp.int32)
    limbs_in = jnp.zeros((n, cipher.Ln), jnp.int32)
    checks = [
        ("layer histogram (layer_ciphertext_histogram)", 1,
         lambda b, s, c: layer_ciphertext_histogram(b, s, c, 16, nb),
         (bins, jnp.zeros((n,), jnp.int32), cts)),
        ("forest histogram k=4 (forest_ciphertext_histogram)", 1,
         lambda b, s, c: forest_ciphertext_histogram(b, s, c, 16, nb),
         (bins, jnp.zeros((n, 4), jnp.int32), cts)),
        ("node histogram (ciphertext_histogram)", 1,
         lambda b, c: ciphertext_histogram(b, c, nb), (bins, cts)),
        ("encrypt modmul (encrypt_batch -> modmul_fixed, 3 mul_fixed)", 3,
         lambda x: modmul_fixed(x, cipher.T_enc, cipher.bctx), (limbs_in,)),
        ("decrypt modmul (decrypt_batch -> modmul_fixed, 3 mul_fixed)", 3,
         lambda x: modmul_fixed(x, cipher.T_dec, cipher.bctx), (limbs_in,)),
        ("bucketize (apply_binning / bin_features)", 1,
         lambda v, t: bucketize(v, t),
         (jnp.zeros((n, nf), jnp.float32),
          jnp.zeros((nf, nb - 1), jnp.float32))),
    ]
    for name, want, fn, args in checks:
        text = jax.jit(fn).lower(*args).compile().as_text()
        got = text.count(CUSTOM_CALL)
        if got < want:
            raise RuntimeError(f"{name}: {got} tpu_custom_call in the "
                               f"compiled program, expected {want}")
        log(f"kernel {name}: {got} tpu_custom_call, compiled natively")


def kernel_cache_sizes() -> dict:
    """Compiled-variant counts of the Pallas wrappers: growth over a run
    shows the kernels (not the references) were called."""
    from repro.kernels.binning.binning import bucketize_pallas
    from repro.kernels.histogram.histogram import layer_hist_pallas
    from repro.kernels.modmul.modmul import mul_fixed_pallas
    return {"layer_hist_pallas": layer_hist_pallas._cache_size(),
            "mul_fixed_pallas": mul_fixed_pallas._cache_size(),
            "bucketize_pallas": bucketize_pallas._cache_size()}


def fit(params, X, y):
    from repro.core import VerticalBoosting
    return VerticalBoosting(params).fit(X[:, :N_GUEST], y,
                                        [X[:, N_GUEST:]])


def fit_local(params, X, y):
    from repro.core import LocalGBDT
    return LocalGBDT(params).fit(X, y)


def describe(tag: str, model) -> None:
    depths = [max(nd.depth for nd in t.nodes if nd.left == -1)
              for t in model.trees]
    st = model.stats
    log(f"{tag}: trees={len(model.trees)} leaf depths={depths} "
        f"n_hist_launches={st.n_hist_launches} "
        f"n_split_roundtrips={st.n_split_roundtrips} "
        f"n_encrypt={st.n_encrypt} n_decrypt={st.n_decrypt} "
        f"n_packages={st.n_packages} "
        f"encrypt_seconds={st.encrypt_seconds:.3f} "
        f"tree_seconds={[round(s, 3) for s in st.tree_seconds]}")
    if len(model.trees) != TRAIN["n_trees"] \
            or depths != [TRAIN["max_depth"]] * TRAIN["n_trees"]:
        raise RuntimeError(f"{tag}: expected {TRAIN['n_trees']} trees of "
                           f"depth {TRAIN['max_depth']}, got {depths}")


def serve(model, Xs):
    import numpy as np
    return np.asarray(model.predict_proba(Xs[:, :N_GUEST],
                                          [Xs[:, N_GUEST:]]))


def one_chip(phases: Phases) -> None:
    import numpy as np
    from repro.core import SBTParams

    X, y, Xs = phases.run("data", make_data)
    phases.run("kernel_check", check_native_kernels)
    before = kernel_cache_sizes()

    local = phases.run("train_local", fit_local, SBTParams(**TRAIN), X, y)
    plain = phases.run("train_plain", fit,
                       SBTParams(cipher="plain", **TRAIN), X, y)
    describe("plain", plain)
    affine = phases.run("train_affine", fit,
                        SBTParams(cipher="affine", **TRAIN), X, y)
    describe("affine", affine)

    after = kernel_cache_sizes()
    log(f"kernel variants compiled during the run: "
        f"{ {k: after[k] - before[k] for k in after} }")
    if any(after[k] <= before[k] for k in after):
        raise RuntimeError(f"a Pallas kernel never ran: {before} -> {after}")

    if not np.array_equal(plain.train_score_, local.train_score_):
        raise RuntimeError("plain-cipher training scores differ from "
                           "LocalGBDT")
    p_local = phases.run("serve_local",
                         lambda: np.asarray(local.predict_proba(Xs)))
    p_plain = phases.run("serve_plain", serve, plain, Xs)
    p_affine = phases.run("serve_affine", serve, affine, Xs)
    if not np.array_equal(p_plain, p_local):
        raise RuntimeError("plain-cipher predictions differ from LocalGBDT")
    log(f"check plain vs LocalGBDT: bit-identical on {N_ROWS:,} training "
        f"scores and {SERVE_ROWS:,} served predictions")
    err = float(np.abs(p_affine - p_local).max())
    if not err < 1e-6:
        raise RuntimeError(f"affine predictions off LocalGBDT by {err}")
    log(f"check affine vs LocalGBDT: max |dp| = {err!r} < 1e-6")

    Xl = Xs[:LEGACY_ROWS]
    p_legacy = phases.run(
        "serve_legacy", lambda: np.asarray(affine.predict_proba(
            Xl[:, :N_GUEST], [Xl[:, N_GUEST:]], packed=False)))
    if not np.array_equal(p_legacy, p_affine[:LEGACY_ROWS]):
        raise RuntimeError("packed serving differs from the legacy loop")
    log(f"check packed serving == legacy loop (packed=False) on "
        f"{LEGACY_ROWS:,} rows: equal; n_predict_batches="
        f"{affine.stats.n_predict_batches} n_predict_roundtrips="
        f"{affine.stats.n_predict_roundtrips}")


def four_chips(phases: Phases, devices) -> None:
    """The mesh-sharded frontier engine on a (2, 2) mesh against one
    device, in this one process: trees and predictions must be
    bit-identical, and the ciphertext batch must be sharded over all four
    chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import SBTParams
    from repro.core.he import get_cipher
    from repro.kernels.modmul import encrypt_batch
    from repro.launch.mesh import make_gbdt_mesh
    from repro.parallel.sharding import gbdt_sharding

    if len(devices) != 4:
        raise RuntimeError(f"--chips 4 needs four devices, found "
                           f"{len(devices)}")
    mesh = make_gbdt_mesh()
    if dict(mesh.shape) != {"data": 2, "model": 2}:
        raise RuntimeError(f"expected a (2, 2) mesh, got {mesh.shape}")
    log(f"mesh: {dict(mesh.shape)} over {[d.id for d in mesh.devices.flat]}")
    X, y, Xs = phases.run("data", make_data)
    cipher = get_cipher("affine", key_bits=TRAIN["key_bits"], seed=SEED)

    params = SBTParams(cipher="affine", **TRAIN)
    sharded = phases.run("train_mesh", fit,
                         dataclasses.replace(params, mesh=mesh), X, y)
    # first device work of the process: the peaks are the mesh fit's own
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    shard_bytes = N_ROWS // mesh.shape["data"] * cipher.hist_width * 4
    log(f"peak_bytes_in_use per device after the mesh fit: {peaks} "
        f"(one gh_cts shard: {shard_bytes:,} B)")
    if min(peaks) < shard_bytes:
        raise RuntimeError(f"a device peaked below one gh_cts shard "
                           f"({shard_bytes:,} B): {peaks}")
    describe("mesh", sharded)
    if sharded.stats.n_cts_placements:
        raise RuntimeError(f"gh_cts re-placed after encryption "
                           f"{sharded.stats.n_cts_placements} times")

    # the placement _encrypt_all gives gh_cts: born sharded at rest
    plain = jax.device_put(jnp.ones((N_ROWS, 1, cipher.Ln), jnp.int32),
                           gbdt_sharding(mesh, "enc_plain"))
    cts = encrypt_batch(cipher, plain, mesh=mesh,
                        out_width=cipher.hist_width)
    held = sorted({s.device.id for s in cts.addressable_shards})
    log(f"gh_cts {cts.shape} sharding {cts.sharding.spec}: shards on "
        f"devices {held}, {cts.addressable_shards[0].data.nbytes:,} B each")
    if held != sorted(d.id for d in devices):
        raise RuntimeError(f"gh_cts shards only on devices {held}")
    del plain, cts

    single = phases.run("train_one_device", fit, params, X, y)
    describe("one device", single)

    if [t.signature() for t in sharded.trees] != \
            [t.signature() for t in single.trees]:
        raise RuntimeError("mesh trees differ from one-device trees")
    if not np.array_equal(sharded.train_score_, single.train_score_):
        raise RuntimeError("mesh training scores differ from one device")
    p_mesh = phases.run("serve_mesh", serve, sharded, Xs)
    p_one = phases.run("serve_one_device", serve, single, Xs)
    if not np.array_equal(p_mesh, p_one):
        raise RuntimeError("mesh predictions differ from one device")
    log(f"check mesh (2, 2) vs one device: trees, {N_ROWS:,} training "
        f"scores and {SERVE_ROWS:,} served predictions bit-identical")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devices = require_tpu()
    from repro.launch.cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {__import__('jax').__version__}")
    phases = Phases()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(phases, devices)
    else:
        one_chip(phases)
    log(f"total: {time.perf_counter() - t0:.2f} s; phases "
        f"{ {k: round(v, 2) for k, v in phases.seconds.items()} }")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
