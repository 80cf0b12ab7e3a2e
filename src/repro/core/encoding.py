"""Fixed-point encoding and GH packing (paper §4.2, Algorithms 3 & 6).

A (g, h) pair is fixed-point encoded (eq 11), g offset to non-negative
(``g_off = |min(g)|``), and packed into one big integer ``gh = g_int << b_h
| h_int`` with bit budgets sized for the worst-case histogram sum over
``n_capacity`` instances (eqs 12-13).  Packing/unpacking is host-side numpy
(runs once per boosting round); the packed plaintext then flows through the
limb-based cipher backends.  The one device step is
:func:`fields_float64_bits`, which reads decrypted fields as float64 bits
where the plaintexts are, for the MO guest's candidates.

Note: Algorithm 6 in the paper writes ``g = gh >> b_g`` -- that is a typo
(the shift must be by ``b_h``, the width of the hessian field); we implement
the correct recovery and verify bit-exactness in tests.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .he import limbs

DEFAULT_PRECISION = 53


@dataclasses.dataclass(frozen=True)
class PackingPlan:
    r: int                # fixed-point fractional bits (eq 11)
    g_off: float          # offset added to every g so encodings are >= 0
    b_g: int              # bits reserved for the g field (eq 13)
    b_h: int              # bits reserved for the h field (eq 13)
    n_capacity: int       # max #instances any histogram sum may contain
    plaintext_bits: int   # iota: usable plaintext width of the cipher

    @property
    def b_gh(self) -> int:
        return self.b_g + self.b_h

    @property
    def limb_width(self) -> int:
        return limbs.num_limbs_for_bits(self.b_gh)

    @property
    def compress_capacity(self) -> int:
        """eta_s = floor(iota / b_gh): split-infos packable per ciphertext."""
        return max(1, self.plaintext_bits // self.b_gh)


def plan_packing(g: np.ndarray, h: np.ndarray, n_capacity: int,
                 plaintext_bits: int, r: int = DEFAULT_PRECISION) -> PackingPlan:
    """Derive bit budgets (eqs 12-13), shrinking r if iota is too small."""
    g = np.asarray(g, dtype=np.float64)
    h = np.asarray(h, dtype=np.float64)
    g_off = float(max(0.0, -float(g.min()))) if g.size else 0.0
    g_max = float(g.max() + g_off) if g.size else 1.0
    h_max = float(max(h.max(), 2.0 ** -r)) if h.size else 1.0
    while True:
        # exact integer bounds on any histogram sum (python ints: no overflow)
        per_g = int(math.floor(g_max * (1 << r))) + 1
        per_h = int(math.floor(h_max * (1 << r))) + 1
        b_g = max(1, (n_capacity * per_g).bit_length())
        b_h = max(1, (n_capacity * per_h).bit_length())
        if b_g + b_h <= plaintext_bits or r <= 4:
            break
        r -= 1
    if b_g + b_h > plaintext_bits:
        raise ValueError(
            f"cannot pack: b_gh={b_g + b_h} > iota={plaintext_bits}")
    return PackingPlan(r=r, g_off=g_off, b_g=b_g, b_h=b_h,
                       n_capacity=n_capacity, plaintext_bits=plaintext_bits)


# ---------------------------------------------------------------------------
# encode (Algorithm 3)
# ---------------------------------------------------------------------------

def encode_int64(x: np.ndarray, r: int) -> np.ndarray:
    """eq 11: round(x * 2**r) as int64 (exact for |x| <= ~2**10 at r=53)."""
    return np.round(np.asarray(x, dtype=np.float64) * float(1 << r)).astype(np.int64)


def _int64_to_limbs(x: np.ndarray, L: int) -> np.ndarray:
    """Non-negative int64 array -> (..., L) radix-2**8 limbs."""
    if np.any(x < 0):
        raise ValueError("negative value in limb conversion")
    shifts = (np.arange(L, dtype=np.int64) * limbs.RADIX_BITS)[None, :]
    return ((x[..., None] >> shifts) & limbs.LIMB_MASK).astype(np.int32)


def pack_gh(g: np.ndarray, h: np.ndarray, plan: PackingPlan) -> np.ndarray:
    """Pack per-instance (g, h) -> (n, Lp) plaintext limbs (Algorithm 3)."""
    g_int = encode_int64(np.asarray(g, np.float64) + plan.g_off, plan.r)
    h_int = encode_int64(h, plan.r)
    Lp = plan.limb_width
    g_l = _int64_to_limbs(g_int, Lp)
    h_l = _int64_to_limbs(h_int, Lp)
    # gh = (g_int << b_h) | h_int, in limb domain (b_h may exceed 63 bits)
    limb_shift, bit_shift = divmod(plan.b_h, limbs.RADIX_BITS)
    g_shifted = np.zeros_like(g_l)
    if bit_shift:
        lo = (g_l.astype(np.int64) << bit_shift) & limbs.LIMB_MASK
        hi = g_l.astype(np.int64) >> (limbs.RADIX_BITS - bit_shift)
        g_shifted_wide = lo
        g_shifted_wide[..., 1:] += hi[..., :-1]
    else:
        g_shifted_wide = g_l.astype(np.int64)
    if limb_shift:
        g_shifted[..., limb_shift:] = g_shifted_wide[..., : Lp - limb_shift]
    else:
        g_shifted = g_shifted_wide
    out = g_shifted.astype(np.int64) + h_l
    while np.any(out > limbs.LIMB_MASK):
        carry = out >> limbs.RADIX_BITS
        out &= limbs.LIMB_MASK
        out[..., 1:] += carry[..., :-1]
    assert np.all(out >= 0) and np.all(out <= limbs.LIMB_MASK)
    return out.astype(np.int32)


# ---------------------------------------------------------------------------
# decode (Algorithm 6, typo-corrected)
# ---------------------------------------------------------------------------

def unpack_gh_int(x: int, plan: PackingPlan, sample_count: int) -> tuple:
    """Recover (sum g, sum h) floats from one decrypted big int."""
    h_int = x & ((1 << plan.b_h) - 1)
    g_int = x >> plan.b_h          # paper alg 6 says b_g: typo, must be b_h
    scale = float(1 << plan.r)
    g = g_int / scale - plan.g_off * sample_count
    h = h_int / scale
    return g, h


# Word decode: the same sums from plaintext limbs, without python ints.
# A field of up to 2 * 53 bits reads as two pieces that float64 holds
# exactly; ``lo + hi * 2**53`` is then one IEEE rounding of the exact value,
# which is what ``float(int)`` gives (nearest, ties to even).
FIELD_BITS_MAX = 106
_PIECE_BITS = 53


def limb_top_bit(arr: np.ndarray) -> int:
    """Bit length of the largest value in a (P, L) canonical limb array."""
    a = np.asarray(arr)
    cols = np.flatnonzero(a.any(axis=0))
    if not cols.size:
        return 0
    j = int(cols[-1])
    return limbs.RADIX_BITS * j + int(a[:, j].max()).bit_length()


def limb_words(arr: np.ndarray, bits: int) -> np.ndarray:
    """(P, L) canonical radix-2**8 limbs -> (W, P) uint64 words, word j of
    every value in one contiguous row: the low ``bits`` bits of each
    value, and a spare zero word so a field read never runs off the end."""
    a = np.asarray(arr)
    per_word = 64 // limbs.RADIX_BITS
    W = bits // 64 + 2
    take = min(a.shape[-1], W * per_word)
    buf = np.zeros((a.shape[0], W * per_word), np.uint8)
    buf[:, :take] = a[:, :take]
    return np.ascontiguousarray(buf.view("<u8").T)


def _word_bits(words: np.ndarray, off: np.ndarray, width: int) -> np.ndarray:
    """Bits [off, off + width) of every value, width <= 64: (P, len(off))."""
    k, s = np.divmod(off, 64)
    s = s.astype(np.uint64)[:, None]
    lo = words[k] >> s
    # (w << 1) << (63 - s): the next word's share, with no shift by 64
    hi = (words[k + 1] << np.uint64(1)) << (np.uint64(63) - s)
    return ((lo | hi) & np.uint64((1 << width) - 1)).T


def field_float64(words: np.ndarray, off, width: int) -> np.ndarray:
    """``float(int)`` of the ``width``-bit field at bit ``off`` (an int
    array of offsets) of every value in ``words``: (P, len(off)) float64."""
    if width > FIELD_BITS_MAX:
        raise ValueError(f"field of {width} bits > {FIELD_BITS_MAX}")
    off = np.asarray(off, np.int64)
    lo = _word_bits(words, off, min(width, _PIECE_BITS)).astype(np.float64)
    if width <= _PIECE_BITS:
        return lo
    hi = _word_bits(words, off + _PIECE_BITS,
                    width - _PIECE_BITS).astype(np.float64)
    return lo + hi * 2.0 ** _PIECE_BITS


def gh_fields(words: np.ndarray, off, plan: PackingPlan,
              g_bits: int | None = None) -> tuple:
    """``float`` of the g and h fields of the packed slot at bit ``off``:
    h the low ``b_h`` bits, g the ``g_bits`` (default ``b_g``) above."""
    off = np.asarray(off, np.int64)
    h = field_float64(words, off, plan.b_h)
    g = field_float64(words, off + plan.b_h,
                      plan.b_g if g_bits is None else g_bits)
    return g, h


def _u32_at(lo, hi, s):
    """32 bits at bit ``s`` (0-31) of the 64-bit ``hi:lo``; the next
    word's share as ``(hi << 1) << (31 - s)``, with no shift by 32."""
    return (lo >> s) | ((hi << 1) << (31 - s))


def _u32_low_mask(n):
    """The low ``n`` bits set, ``n`` clipped to 0-32."""
    n = jnp.clip(n, 0, 32)
    return jnp.where(n >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << jnp.minimum(n, 31).astype(jnp.uint32))
                     - 1)


@jax.jit
def fields_float64_bits(x, off, width):
    """:func:`field_float64` on the device, as IEEE-754 binary64 bits.

    ``x`` (B, L) canonical radix-2**8 limbs, one integer a row; ``off`` and
    ``width`` (F,) int32, each field's bit offset and width (at most
    :data:`FIELD_BITS_MAX`), traced, so fields that move or widen reuse the
    executable.  Returns (B, 2F) uint32, the low then the high word of
    ``float(field)`` for each field: nearest, ties to even, in 32-bit
    integer ops (:func:`float64_of_bits` reads them on the host).
    """
    u32 = jnp.uint32
    B, L = x.shape
    W = -(-L // 4) + 4             # 32-bit words, and spare zero words
    t = jnp.pad(x.T.astype(u32), ((0, 4 * W - L), (0, 0))).reshape(W, 4, B)
    words = t[:, 0] | (t[:, 1] << 8) | (t[:, 2] << 16) | (t[:, 3] << 24)
    off = off.astype(jnp.int32)
    width = width.astype(jnp.int32)[:, None]
    w = words[(off >> 5)[:, None] + jnp.arange(5)]          # (F, 5, B)
    s = (off & 31).astype(u32)[:, None]
    # the field as four words, least significant first
    v = [_u32_at(w[:, j], w[:, j + 1], s) & _u32_low_mask(width - 32 * j)
         for j in range(4)]
    n = jnp.zeros(v[0].shape, jnp.int32)                     # bit length
    for j, vj in enumerate(v):
        n = jnp.where(vj != 0, 32 * (j + 1) - lax.clz(vj).astype(jnp.int32),
                      n)
    # the 53-bit significand is bits [n - 53, n) of the field: bits
    # [o, o + 53) of ``field << 64``, o = n + 11 >= 12, so every read
    # below is in range and a field of 53 bits or fewer comes out exact
    z = jnp.zeros_like(v[0])
    ext = [z, z] + v

    def word(i):
        out = z
        for k in range(2, 6):
            out = jnp.where(i == k, ext[k], out)
        return out

    o = n + 11
    q, sh = o >> 5, (o & 31).astype(u32)
    mid = word(q + 1)
    m_lo = _u32_at(word(q), mid, sh)
    m_hi = _u32_at(mid, word(q + 2), sh) & u32(0x1FFFFF)
    r = o - 1                                                # rounding bit
    half = ((word(r >> 5) >> (r & 31).astype(u32)) & 1) == 1
    sticky = z != 0
    for k in range(2, 6):
        sticky |= (ext[k] & _u32_low_mask(r - 32 * k)) != 0
    inc = (half & (sticky | ((m_lo & 1) == 1))).astype(u32)
    m_lo = m_lo + inc
    m_hi = m_hi + (inc & (m_lo == 0))
    # a carry to 2**53 moves the exponent and leaves the fraction 0
    e = (n + 1022).astype(u32) + (m_hi >> 21)
    hi = jnp.where(n == 0, u32(0), (e << 20) | (m_hi & u32(0xFFFFF)))
    lo = jnp.where(n == 0, u32(0), m_lo)
    return jnp.stack([lo, hi], axis=1).reshape(2 * lo.shape[0], B).T


@jax.jit
def _word_pairs_by_field(bits):
    """(B, 2F) word pairs -> (F, 2B): a field's doubles in one long row.
    A device may lay a narrow (B, 2F) array out column-major, which no
    float64 view can read; the long rows it keeps row-major."""
    B, F2 = bits.shape
    return bits.reshape(B, F2 // 2, 2).transpose(1, 0, 2).reshape(F2 // 2,
                                                                   2 * B)


def float64_of_bits(bits) -> np.ndarray:
    """:func:`fields_float64_bits`' (B, 2F) words as (F, B) float64 on the
    host: one copy of 8 bytes a field, read in place by a view."""
    rows = np.ascontiguousarray(np.asarray(_word_pairs_by_field(bits)))
    return rows.view(np.float64)


def unpack_gh_floats(g_f: np.ndarray, h_f: np.ndarray, plan: PackingPlan,
                     counts) -> tuple:
    """:func:`unpack_gh_int`'s arithmetic on fields read by
    :func:`gh_fields`, elementwise: the same float operations, so the same
    bits."""
    scale = float(1 << plan.r)
    return (g_f / scale - plan.g_off * np.asarray(counts, np.float64),
            h_f / scale)
