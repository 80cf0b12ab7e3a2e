"""IterativeAffine-style additively homomorphic cipher over limb vectors.

This is the JAX/TPU execution path for SecureBoost+'s ciphertext arithmetic
(the paper ships Paillier and IterativeAffine; only the affine family's
homomorphic-add-is-modadd structure maps onto the MXU -- see DESIGN.md §3).

    E(x)   = (a * x) mod n          (a random, gcd(a, n) = 1)
    E(x) + E(y) mod n = E(x + y)    additive homomorphism
    s * E(x) mod n    = E(s * x)    scalar homomorphism
    D(c)   = (a^{-1} * c) mod n

Encryption/decryption are modular multiplications by a *fixed* big integer,
lowered as Toeplitz matmuls + Barrett reduction (``kernels/modmul`` provides
the Pallas version; this module is the jnp fallback and the key holder).

Security note (honest): a known plaintext/ciphertext pair reveals ``a``; the
paper's IterativeAffine has the same symmetric-key character and was chosen
there for speed, with Paillier as the hardened option.  We mirror that menu:
``paillier.py`` is the semantically secure backend (python-int oracle), this
backend reproduces the affine column's cost structure at full fidelity.
"""

from __future__ import annotations

import math
import random as _random

import jax.numpy as jnp
import numpy as np

from ...analysis.registry import declassifies
from . import limbs


class AffineCipher:
    backend = "limb"
    name = "affine"

    def __init__(self, n_int: int, a_int: int, hist_headroom_limbs: int = 3):
        if math.gcd(a_int, n_int) != 1:
            raise ValueError("a must be invertible mod n")
        self.n_int = n_int
        self.a_int = a_int
        self.a_inv_int = pow(a_int, -1, n_int)
        self.Ln = limbs.num_limbs_for_bits(n_int.bit_length())
        self.plaintext_bits = n_int.bit_length() - 1
        self.hist_headroom_limbs = hist_headroom_limbs
        self.bctx = limbs.barrett_precompute(n_int, self.Ln)
        a_l = limbs.from_pyints([a_int], self.Ln)[0]
        ai_l = limbs.from_pyints([self.a_inv_int], self.Ln)[0]
        self.T_enc = jnp.asarray(limbs.toeplitz(a_l, self.Ln))
        self.T_dec = jnp.asarray(limbs.toeplitz(ai_l, self.Ln))

    @classmethod
    def keygen(cls, key_bits: int = 1024, seed: int | None = None,
               hist_headroom_limbs: int = 3) -> "AffineCipher":
        rng = _random.Random(seed)
        while True:
            n = rng.getrandbits(key_bits) | (1 << (key_bits - 1)) | 1
            a = rng.getrandbits(key_bits - 1) | 1
            if math.gcd(a, n) == 1:
                return cls(n, a, hist_headroom_limbs)

    # -- guest ---------------------------------------------------------
    @declassifies("affine-scheme encryption: ciphertext limbs only")
    def encrypt_limbs(self, x):
        """x: (..., Lp) plaintext limbs with value < n -> ciphertext (..., Ln)."""
        L = x.shape[-1]
        if L < self.Ln:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, self.Ln - L)])
        elif L > self.Ln:
            raise ValueError("plaintext wider than modulus")
        # mirror the Paillier backend's range check: values >= n would wrap
        # silently and decrypt to garbage
        if bool(jnp.any(limbs.geq(x, jnp.broadcast_to(self.bctx.n, x.shape)))):
            raise ValueError("plaintext out of range (>= modulus n)")
        return limbs.mod_mul_fixed(x, self.T_enc, self.bctx)

    @declassifies("affine-scheme encryption: ciphertext limbs only")
    def encrypt_ints(self, xs) -> jnp.ndarray:
        return self.encrypt_limbs(jnp.asarray(limbs.from_pyints(list(xs), self.Ln)))

    def decrypt_limbs(self, ct):
        return limbs.mod_mul_fixed(ct, self.T_dec, self.bctx)

    def decrypt_to_ints(self, ct) -> list:
        return limbs.to_pyints(np.asarray(self.decrypt_limbs(jnp.asarray(ct))))

    # -- homomorphic ops ------------------------------------------------
    def add(self, a, b):
        n = jnp.pad(self.bctx.n, (0, 1))
        return limbs.cond_sub(limbs.add(jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, 1)]),
                                        jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, 1)])),
                              n)[..., : self.Ln]

    def sub(self, a, b):
        """Homomorphic (a - b) mod n: a + (n - b)."""
        n = jnp.broadcast_to(self.bctx.n, b.shape)
        neg_b = jnp.where(limbs.is_zero(b)[..., None], b, limbs.sub(n, b))
        return self.add(a, neg_b)

    def mul_pow2(self, ct, k: int):
        """Homomorphic multiply by 2**k (cipher-compress shift)."""
        wide = limbs.shift_left_bits(ct, k, None)
        return self._reduce_wide(wide)

    def _reduce_wide(self, x):
        L = x.shape[-1]
        if L > 2 * self.Ln:
            raise ValueError("operand too wide; reduce more often")
        return limbs.barrett_reduce(x, self.bctx)

    # -- lazy histogram hooks -------------------------------------------
    @property
    def hist_width(self) -> int:
        return self.Ln + self.hist_headroom_limbs

    def reduce(self, acc):
        """Reduce a lazy accumulator (sum of < 2**(8*headroom) ciphertexts).
        Limbs may be mixed-sign (lazy subtraction) as long as values >= 0."""
        return limbs.by_row_blocks(
            lambda a: limbs.barrett_reduce(limbs.carry_fix(a), self.bctx), acc)

    def lazy_sub(self, parent, child_lazy, count_bound: int):
        """Histogram subtraction in the lazy limb domain: canonical parent
        (mod n) minus an un-carried child accumulator.  The child's lazy
        value can reach ``count_bound * n``, so ``count_bound * n`` is added
        to keep the represented value non-negative; the next :meth:`reduce`
        Barrett-reduces it away (sibling = parent - child mod n).  Requires
        ``(count_bound + 1) * n < RADIX**width``, i.e. count_bound below
        2**(8 * headroom) -- the same bound as direct lazy accumulation."""
        w = child_lazy.shape[-1]
        off = jnp.asarray(
            limbs.from_pyints([max(int(count_bound), 0) * self.n_int], w)[0])
        return limbs.pad_limbs(parent, w)[..., :w] + off - child_lazy

    def zero(self, shape) -> jnp.ndarray:
        return jnp.zeros(tuple(shape) + (self.Ln,), dtype=jnp.int32)
