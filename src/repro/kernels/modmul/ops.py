"""Jit'd wrappers: kernelized modular multiply / encrypt / decrypt batches.

Composes the mul_fixed Pallas kernel with jnp glue to realize a full Barrett
modular multiplication by a fixed constant: all three O(L^2) products (x*b,
q1*mu, q3*n) run on the MXU; shifts/masks/conditional subtracts are O(L).

Mesh path (DESIGN.md §8): modular multiplication by a fixed constant is
embarrassingly parallel over rows, so when a (data, model) mesh is passed
the batch shards over "data" via ``shard_map`` — each shard runs the same
three Pallas kernels on its row block with NO collective, and the result is
bit-identical to the single-device path (per-row arithmetic is untouched by
the partitioning).  ``encrypt_batch`` can additionally width-pad the output
*inside* the shard (``out_width``) so ciphertexts are born at the
histogram accumulator width with their at-rest sharding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...analysis.registry import declassifies
from ...core.he import limbs
from ..common import round_up
from .modmul import mul_fixed_pallas


def modmul_fixed(x: jnp.ndarray, T_b: jnp.ndarray, bctx: limbs.BarrettCtx,
                 interpret: bool | None = None) -> jnp.ndarray:
    # NOTE: not @jit at this level -- BarrettCtx.Ln drives static slicing;
    # the three mul_fixed_pallas calls below are individually jitted.
    """(x * b) mod n for a batch x (N, Ln) of canonical limbs, b fixed."""
    Ln = bctx.Ln
    prod = mul_fixed_pallas(x, T_b, interpret=interpret)[..., : 2 * Ln]
    # Barrett with kernelized q1*mu and q3*n
    q1 = limbs.shift_right_limbs(prod, Ln - 1)[..., : Ln + 2]
    q2 = mul_fixed_pallas(q1, bctx.T_mu, interpret=interpret)
    q3 = limbs.shift_right_limbs(q2, Ln + 1)[..., : Ln + 2]
    r1 = limbs.mask_bits(prod[..., : Ln + 2], (Ln + 1) * limbs.RADIX_BITS)
    q3n = mul_fixed_pallas(q3, bctx.T_n, interpret=interpret)[..., : Ln + 2]
    q3n = limbs.mask_bits(q3n, (Ln + 1) * limbs.RADIX_BITS)
    t = r1 - q3n
    t = t.at[..., Ln + 1].add(1)
    t = limbs.borrow_fix(t)
    r = t.at[..., Ln + 1].set(0)
    n_wide = jnp.pad(bctx.n, (0, 2))
    r = limbs.cond_sub(r, n_wide)
    r = limbs.cond_sub(r, n_wide)
    return r[..., :Ln]


@functools.partial(jax.jit, static_argnames=("mesh", "Ln", "interpret",
                                             "out_width"))
def _sharded_modmul(x, T_b, n_l, T_mu, T_n, *, mesh, Ln: int,
                    interpret: bool | None, out_width: int | None):
    # module-level jit so repeated calls hit the compilation cache (keyed on
    # shapes + statics) instead of re-staging the shard_map per call
    def local(xs, T, nl, Tmu, Tn):
        b = limbs.BarrettCtx(n=nl, T_mu=Tmu, T_n=Tn, Ln=Ln)
        flat = xs.reshape(-1, xs.shape[-1])
        r = modmul_fixed(flat, T, b, interpret=interpret)
        if out_width is not None and r.shape[-1] < out_width:
            r = jnp.pad(r, ((0, 0), (0, out_width - r.shape[-1])))
        return r.reshape(xs.shape[:-1] + (r.shape[-1],))

    spec_x = P(*(("data",) + (None,) * (x.ndim - 1)))
    # check_vma=False: the per-shard Pallas calls declare plain output
    # shapes, which the varying-axes check cannot type
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_x, P(None, None), P(None), P(None, None),
                  P(None, None)),
        out_specs=spec_x, check_vma=False,
    )(x, T_b, n_l, T_mu, T_n)


def modmul_fixed_sharded(x: jnp.ndarray, T_b: jnp.ndarray,
                         bctx: limbs.BarrettCtx, mesh,
                         interpret: bool | None = None,
                         out_width: int | None = None) -> jnp.ndarray:
    """Row-sharded :func:`modmul_fixed` over the mesh's "data" axis.

    x: (N, ..., Lx) canonical limbs; leading axis shards over "data" (rows
    padded to divisibility with zeros — E/D of 0 is 0 for the fixed-constant
    multiply — and kept, see below), remaining axes replicate.  Each shard
    runs the per-shard Pallas kernels with no collective, so the result is
    bit-identical to the single-device path row-for-row.

    Returns the FULL padded batch (``data_pad`` rows included) so callers
    that keep state device-resident (the frontier engine pads its instance
    axis by the same rule) never reshard; slice ``[:N]`` host-side when the
    pad rows are unwanted.  ``out_width`` zero-pads the trailing limb axis
    inside the shard (ciphertexts born at histogram width).

    Like the §7 layer dispatch, this assumes the 2-axis (data, model) GBDT
    mesh of ``launch.mesh.make_gbdt_mesh`` — a multi-pod ("pod", "data",
    "model") mesh is out of contract for the frontier engine.
    """
    n = x.shape[0]
    sizes = dict(mesh.shape)
    dd = sizes.get("data", 1)
    pn = round_up(max(n, 1), dd)
    if pn != n:
        x = jnp.pad(x, [(0, pn - n)] + [(0, 0)] * (x.ndim - 1))
    return _sharded_modmul(x, T_b, bctx.n, bctx.T_mu, bctx.T_n, mesh=mesh,
                           Ln=bctx.Ln, interpret=interpret,
                           out_width=out_width)


def _mesh_active(mesh) -> bool:
    return mesh is not None and mesh.devices.size > 1


@declassifies("kernelized affine encryption: ciphertext limbs only")
def encrypt_batch(cipher, plaintext_limbs, interpret: bool | None = None,
                  mesh=None, out_width: int | None = None):
    """Kernelized affine encryption of a (N, ..., Lp) plaintext batch.

    With ``mesh``, rows shard over "data" (no collective) and the returned
    batch keeps its pad rows and born sharding — pre-pad the input with
    ``parallel.sharding.data_pad`` rows to control the padded extent.
    ``out_width`` pads ciphertext limbs to the histogram accumulator width
    shard-locally (no eager pad on the shard_map output)."""
    x = jnp.asarray(plaintext_limbs, jnp.int32)
    if x.shape[-1] < cipher.Ln:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, cipher.Ln - x.shape[-1])])
    elif x.shape[-1] > cipher.Ln:
        raise ValueError("plaintext wider than modulus")
    # same range guard as AffineCipher.encrypt_limbs: values >= n would wrap
    # silently through the Barrett pipeline and decrypt to garbage
    if bool(jnp.any(limbs.geq(x, jnp.broadcast_to(cipher.bctx.n, x.shape)))):
        raise ValueError("plaintext out of range (>= modulus n)")
    if _mesh_active(mesh):
        return modmul_fixed_sharded(x, cipher.T_enc, cipher.bctx, mesh,
                                    interpret=interpret, out_width=out_width)
    out = modmul_fixed(x.reshape(-1, x.shape[-1]), cipher.T_enc, cipher.bctx,
                       interpret=interpret)
    if out_width is not None and out.shape[-1] < out_width:
        out = jnp.pad(out, ((0, 0), (0, out_width - out.shape[-1])))
    return out.reshape(x.shape[:-1] + (out.shape[-1],))


def decrypt_batch(cipher, ct, interpret: bool | None = None, mesh=None):
    """Kernelized affine decryption -> plaintext limbs (N, Ln).

    With ``mesh``, the candidate rows shard over "data"; internal pad rows
    (decrypt(0) = 0) are sliced back off so the single-device contract — one
    output row per input row — is unchanged."""
    x = jnp.asarray(ct, jnp.int32)
    if _mesh_active(mesh):
        out = modmul_fixed_sharded(x, cipher.T_dec, cipher.bctx, mesh,
                                   interpret=interpret)
        return out[: x.shape[0]]
    return modmul_fixed(x, cipher.T_dec, cipher.bctx, interpret=interpret)
