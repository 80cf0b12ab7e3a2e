"""Vectorized big-unsigned-integer arithmetic over a trailing limb axis.

This is the numeric substrate for the TPU port of SecureBoost+'s ciphertext
arithmetic.  A big integer is a little-endian vector of radix-2**8 limbs
stored as int32 (canonical form: every limb in [0, 256)).  All operations are
batched over arbitrary leading axes and are jit/pallas friendly:

  * radix 2**8 keeps every intermediate product/sum far below 2**31, so
    schoolbook multiplication lowers to an exact int32 (or fp32) matmul on
    the MXU, and histogram accumulation can defer carries ("lazy carry").
  * multiplication by a *fixed* constant (encryption key, Barrett mu, the
    modulus, 2**b_gh for cipher compressing) is a matmul with the constant's
    Toeplitz limb matrix -- see :func:`toeplitz` / :func:`mul_fixed`.

Host-side helpers (``from_pyints`` / ``to_pyints``) convert to python ints for
tests and key generation.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

RADIX_BITS = 8
RADIX = 1 << RADIX_BITS
LIMB_MASK = RADIX - 1


# Rows of big integers one chain of limb programs takes at once.  A carry or
# borrow fix-up holds about 4.4x its operand in temporaries on a TPU v5e, so
# 2**19 rows of a Barrett product (2 Ln + 3 = 259 limbs at 1024 bits) hold
# 2.4 GB; an MO layer's 1.57M candidate rows at once would need 7 GB beside
# the layer's histograms.  Sized above every binary layer of the benchmark.
ROW_BLOCK = 1 << 19


def num_limbs_for_bits(bits: int) -> int:
    return -(-bits // RADIX_BITS)


def by_row_blocks(fn, x, block: int | None = None):
    """``fn(x)`` for a ``fn`` that treats each row of ``x`` (..., L) alone,
    run over blocks of ``block`` rows (default :data:`ROW_BLOCK`) when
    ``x`` holds more: the last block zero-padded to full size, so every
    block compiles to one shape, and the pad rows' results dropped."""
    block = ROW_BLOCK if block is None else block
    lead = x.shape[:-1]
    n = int(np.prod(lead))
    if n <= block:
        return fn(x)
    flat = jnp.reshape(x, (n, x.shape[-1]))
    pad = -n % block
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    out = jnp.concatenate([fn(flat[i: i + block])
                           for i in range(0, n + pad, block)])[:n]
    return out.reshape(lead + out.shape[-1:])


# ---------------------------------------------------------------------------
# host-side conversion helpers (numpy / python ints)
# ---------------------------------------------------------------------------

def from_pyints(xs, L: int) -> np.ndarray:
    """Pack an iterable of non-negative python ints into (len(xs), L) limbs."""
    out = np.zeros((len(xs), L), dtype=np.int32)
    for i, x in enumerate(xs):
        if x < 0:
            raise ValueError("limbs are unsigned; got negative value")
        j = 0
        while x and j < L:
            out[i, j] = x & LIMB_MASK
            x >>= RADIX_BITS
            j += 1
        if x:
            raise ValueError(f"value does not fit in {L} limbs")
    return out


def to_pyints(arr) -> list:
    """Inverse of :func:`from_pyints`; accepts any (..., L) canonical array."""
    a = np.asarray(arr, dtype=object)
    flat = a.reshape(-1, a.shape[-1])
    out = []
    for row in flat:
        x = 0
        for j in range(len(row) - 1, -1, -1):
            x = (x << RADIX_BITS) | int(row[j])
        out.append(x)
    return out


def to_pyint(arr) -> int:
    (x,) = to_pyints(np.asarray(arr).reshape(1, -1))
    return x


# ---------------------------------------------------------------------------
# carries / borrows
# ---------------------------------------------------------------------------

def _shift_up(x):
    """Move limb i to position i+1 (drop the overflowing top limb)."""
    pad = [(0, 0)] * (x.ndim - 1) + [(1, 0)]
    return jnp.pad(x, pad)[..., :-1]


@jax.jit
def carry_fix(x):
    """Propagate carries until canonical.  Limbs may be mixed-sign as long
    as the represented value is >= 0 (lazy histogram subtraction produces
    ``parent - child`` limb vectors before canonicalization): for int32
    two's complement, ``v == RADIX * (v >> RADIX_BITS) + (v & LIMB_MASK)``
    holds for negative limbs too (arithmetic shift + non-negative masked
    digit), so the same signed-digit normalization converges.

    Overflow past the last limb is dropped (arithmetic mod RADIX**L); size
    limb counts so this never happens in practice.  Jitted at module level
    so eager protocol code pays tracing once per shape, not per call.
    """
    def cond(v):
        return jnp.any((v > LIMB_MASK) | (v < 0))

    def body(v):
        return (v & LIMB_MASK) + _shift_up(v >> RADIX_BITS)

    return jax.lax.while_loop(cond, body, x)


@jax.jit
def borrow_fix(x):
    """Resolve negative limbs (borrow propagation).  Result must be >= 0."""
    def cond(v):
        return jnp.any(v < 0)

    def body(v):
        neg = (v < 0).astype(v.dtype)
        return v + neg * RADIX - _shift_up(neg)

    return jax.lax.while_loop(cond, body, x)


# ---------------------------------------------------------------------------
# basic arithmetic (canonical inputs unless noted)
# ---------------------------------------------------------------------------

def pad_limbs(x, width: int):
    """Zero-pad the trailing limb axis up to ``width`` (no-op if wider)."""
    L = x.shape[-1]
    if L >= width:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - L)])


def add(a, b):
    return carry_fix(a + b)


def sub(a, b):
    """a - b, assuming a >= b elementwise as big integers."""
    return borrow_fix(a - b)


def compare(a, b):
    """Elementwise big-int compare: returns -1 / 0 / +1 over leading axes."""
    d = jnp.sign(a - b)          # per-limb sign
    nz = d != 0
    # index of most significant nonzero limb
    L = a.shape[-1]
    rev = jnp.flip(nz, axis=-1)
    first = jnp.argmax(rev, axis=-1)          # 0 if none
    idx = L - 1 - first
    any_nz = jnp.any(nz, axis=-1)
    picked = jnp.take_along_axis(d, idx[..., None], axis=-1)[..., 0]
    return jnp.where(any_nz, picked, 0)


def geq(a, b):
    return compare(a, b) >= 0


def cond_sub(a, n):
    """a mod n given a < 2n (single conditional subtract)."""
    take = geq(a, n)[..., None]
    return jnp.where(take, sub(a, jnp.broadcast_to(n, a.shape)), a)


def is_zero(a):
    return jnp.all(a == 0, axis=-1)


# ---------------------------------------------------------------------------
# shifts and masks (static shift amounts)
# ---------------------------------------------------------------------------

def shift_left_bits(a, k: int, out_L: int | None = None):
    limb_shift, bit_shift = divmod(k, RADIX_BITS)
    L = a.shape[-1]
    out_L = out_L if out_L is not None else L + limb_shift + 1
    pad = [(0, 0)] * (a.ndim - 1) + [(limb_shift, max(0, out_L - L - limb_shift))]
    x = jnp.pad(a, pad)[..., :out_L]
    if bit_shift:
        x = carry_fix(x << bit_shift)
    return x


def shift_right_bits(a, k: int):
    limb_shift, bit_shift = divmod(k, RADIX_BITS)
    L = a.shape[-1]
    pad = [(0, 0)] * (a.ndim - 1) + [(0, limb_shift)]
    x = jnp.pad(a, pad)[..., limb_shift:]
    if bit_shift:
        nxt = jnp.pad(x, [(0, 0)] * (a.ndim - 1) + [(0, 1)])[..., 1:]
        x = (x >> bit_shift) | ((nxt << (RADIX_BITS - bit_shift)) & LIMB_MASK)
    return x


def shift_right_limbs(a, k: int):
    pad = [(0, 0)] * (a.ndim - 1) + [(0, k)]
    return jnp.pad(a, pad)[..., k:]


def mask_bits(a, nbits: int):
    """a mod 2**nbits (keeps the limb count)."""
    full, part = divmod(nbits, RADIX_BITS)
    L = a.shape[-1]
    idx = jnp.arange(L)
    keep = (idx < full).astype(a.dtype)
    out = a * keep
    if part and full < L:
        out = out.at[..., full].set(a[..., full] & ((1 << part) - 1))
    return out


# ---------------------------------------------------------------------------
# multiplication
# ---------------------------------------------------------------------------

def toeplitz(b_limbs: np.ndarray, La: int) -> np.ndarray:
    """(La, La+Lb) matrix T with T[i, i+j] = b[j]; then a @ T == a*b limbs."""
    b = np.asarray(b_limbs, dtype=np.int32).reshape(-1)
    Lb = b.shape[0]
    T = np.zeros((La, La + Lb), dtype=np.int32)
    for i in range(La):
        T[i, i:i + Lb] = b
    return T


def mul_fixed(a, T):
    """Multiply canonical a (..., La) by the fixed big int behind Toeplitz T."""
    y = jnp.einsum("...i,ij->...j", a, T.astype(jnp.int32))
    return carry_fix(y)


def mul(a, b):
    """Generic batched schoolbook multiply: (..., La) x (..., Lb) -> (..., La+Lb)."""
    La, Lb = a.shape[-1], b.shape[-1]
    batch = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    af = jnp.broadcast_to(a, batch + (La,)).reshape(-1, La)
    bf = jnp.broadcast_to(b, batch + (Lb,)).reshape(-1, Lb)

    def one(x, y):
        # convolve lowers via fp32; exact since coeffs < 2**24 for radix 2**8
        return jnp.convolve(x.astype(jnp.float32), y.astype(jnp.float32))

    out = jax.vmap(one)(af, bf).astype(jnp.int32)   # (N, La+Lb-1)
    out = jnp.pad(out, ((0, 0), (0, 1)))
    return carry_fix(out.reshape(batch + (La + Lb,)))


# ---------------------------------------------------------------------------
# Barrett reduction by a fixed modulus
# ---------------------------------------------------------------------------

class BarrettCtx(NamedTuple):
    """Precomputed tables for reduction mod a fixed n (Ln limbs).

    Valid for inputs x < RADIX**(2*Ln).  T_mu / T_n are Toeplitz matrices of
    mu = floor(RADIX**(2Ln) / n) and n, sized for the operand widths used in
    :func:`barrett_reduce`.
    """
    n: jnp.ndarray          # (Ln,) canonical limbs of the modulus
    T_mu: jnp.ndarray       # (Ln+2, 2Ln+3) toeplitz of mu (mu has <= Ln+1 limbs)
    T_n: jnp.ndarray        # (Ln+2, 2Ln+3) toeplitz of n
    Ln: int


def barrett_precompute(n_int: int, Ln: int | None = None) -> BarrettCtx:
    if Ln is None:
        Ln = num_limbs_for_bits(n_int.bit_length())
    mu = (RADIX ** (2 * Ln)) // n_int
    mu_l = from_pyints([mu], Ln + 1)[0]
    n_l = from_pyints([n_int], Ln)[0]
    T_mu = toeplitz(mu_l, Ln + 2)           # q1 has <= Ln+1 limbs; pad to Ln+2
    T_n = toeplitz(np.pad(n_l, (0, 1)), Ln + 2)
    return BarrettCtx(
        n=jnp.asarray(n_l), T_mu=jnp.asarray(T_mu), T_n=jnp.asarray(T_n), Ln=Ln
    )


def barrett_reduce(x, ctx: BarrettCtx):
    """x mod n for canonical x with x < RADIX**(2*Ln).  Returns (..., Ln)."""
    Ln = ctx.Ln
    L = x.shape[-1]
    if L < 2 * Ln:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, 2 * Ln - L)])
    elif L > 2 * Ln:
        raise ValueError(f"operand too wide for Barrett: {L} > {2 * Ln}")
    q1 = shift_right_limbs(x, Ln - 1)[..., : Ln + 2]      # floor(x / b^(Ln-1))
    q2 = mul_fixed(q1, ctx.T_mu)                           # q1 * mu
    q3 = shift_right_limbs(q2, Ln + 1)[..., : Ln + 2]      # floor(q2 / b^(Ln+1))
    # r = (x - q3*n) mod b^(Ln+1); classic Barrett guarantees 0 <= r < 3n.
    r1 = mask_bits(x[..., : Ln + 2], (Ln + 1) * RADIX_BITS)
    q3n = mask_bits(mul_fixed(q3, ctx.T_n)[..., : Ln + 2],
                    (Ln + 1) * RADIX_BITS)
    # compute t = r1 + b^(Ln+1) - q3n  (always >= 0), then drop the top limb
    # to realize the mod-b^(Ln+1) wrap.
    t = r1 - q3n
    t = t.at[..., Ln + 1].add(1)
    t = borrow_fix(t)
    r = t.at[..., Ln + 1].set(0)
    n_wide = jnp.pad(ctx.n, (0, 2))
    r = cond_sub(r, n_wide)
    r = cond_sub(r, n_wide)
    return r[..., :Ln]


def mod_mul_fixed(a, T_b, ctx: BarrettCtx):
    """(a * b) mod n for canonical a < n and fixed b < n (Toeplitz T_b)."""
    prod = mul_fixed(a, T_b)[..., : 2 * ctx.Ln]
    return barrett_reduce(prod, ctx)
