"""Pallas kernel sweeps (interpret mode) against pure-jnp oracles."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.he import get_cipher, limbs
from repro.kernels.binning import bucketize, bucketize_ref, fit_quantile_thresholds
from repro.kernels.histogram import ciphertext_histogram, hist_ref
from repro.kernels.modmul import decrypt_batch, encrypt_batch, modmul_fixed
from repro.kernels.modmul.ref import mul_fixed_ref
from repro.kernels.modmul.modmul import mul_fixed_pallas

HIST_SHAPES = [(64, 3, 8, 8), (300, 17, 33, 32), (257, 9, 130, 16),
               (1024, 8, 20, 32), (1, 1, 4, 4),
               # feature counts the (8, 128) block rule once refused on TPU
               (300, 14, 12, 32), (257, 33, 8, 16), (64, 1000, 4, 8)]


@pytest.mark.parametrize("n_i,n_f,L,n_b", HIST_SHAPES)
def test_histogram_kernel_vs_ref(n_i, n_f, L, n_b):
    rng = np.random.default_rng(n_i * 31 + n_f)
    bins = rng.integers(0, n_b, (n_i, n_f)).astype(np.int32)
    bins[rng.random((n_i, n_f)) < 0.15] = -1
    cts = rng.integers(0, 256, (n_i, L)).astype(np.int32)
    out = ciphertext_histogram(bins, cts, n_b, use_pallas=True)
    ref = hist_ref(jnp.asarray(bins), jnp.asarray(cts), n_b)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_histogram_kernel_masked_all():
    bins = np.full((50, 4), -1, np.int32)
    cts = np.random.default_rng(0).integers(0, 256, (50, 8)).astype(np.int32)
    out = np.asarray(ciphertext_histogram(bins, cts, 8))
    assert (out == 0).all()


@pytest.mark.parametrize("n_i,n_f,n_b", [(100, 4, 8), (1000, 33, 32),
                                         (513, 7, 16), (2, 1, 4),
                                         (700, 40, 32)])
@pytest.mark.parametrize("dist", ["normal", "uniform", "sparse"])
def test_binning_kernel_vs_ref(n_i, n_f, n_b, dist):
    rng = np.random.default_rng(n_i + n_b)
    if dist == "normal":
        v = rng.normal(0, 1, (n_i, n_f)).astype(np.float32)
    elif dist == "uniform":
        v = rng.uniform(-5, 5, (n_i, n_f)).astype(np.float32)
    else:
        v = rng.normal(0, 1, (n_i, n_f)).astype(np.float32)
        v[rng.random((n_i, n_f)) < 0.7] = 0.0
    thr = fit_quantile_thresholds(v, n_b)
    out = np.asarray(bucketize(v, thr, use_pallas=True))
    ref = np.asarray(bucketize_ref(jnp.asarray(v), jnp.asarray(thr)))
    np.testing.assert_array_equal(out, ref)
    assert out.min() >= 0 and out.max() <= n_b - 1


@pytest.mark.parametrize("bits", [64, 128, 256])
@pytest.mark.parametrize("batch", [1, 7, 100])
def test_modmul_kernel(bits, batch):
    rnd = random.Random(bits + batch)
    n_int = rnd.getrandbits(bits) | (1 << (bits - 1)) | 1
    bctx = limbs.barrett_precompute(n_int)
    Ln = bctx.Ln
    b_int = rnd.getrandbits(bits - 1)
    T = jnp.asarray(limbs.toeplitz(limbs.from_pyints([b_int], Ln)[0], Ln))
    vals = [rnd.getrandbits(bits - 1) % n_int for _ in range(batch)]
    x = jnp.asarray(limbs.from_pyints(vals, Ln))
    y = modmul_fixed(x, T, bctx)
    assert limbs.to_pyints(np.asarray(y)) == [(v * b_int) % n_int for v in vals]
    # raw mul kernel vs oracle
    y2 = mul_fixed_pallas(x, T)
    ref = mul_fixed_ref(x, T)
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(ref))


def test_kernelized_encrypt_decrypt_matches_jnp_path():
    aff = get_cipher("affine", key_bits=192, seed=9)
    rnd = random.Random(3)
    pts = [rnd.getrandbits(150) for _ in range(40)]
    pt = jnp.asarray(limbs.from_pyints(pts, aff.Ln))
    ct_kernel = encrypt_batch(aff, pt)
    ct_jnp = aff.encrypt_limbs(pt)
    np.testing.assert_array_equal(np.asarray(ct_kernel), np.asarray(ct_jnp))
    assert limbs.to_pyints(np.asarray(decrypt_batch(aff, ct_kernel))) == pts
