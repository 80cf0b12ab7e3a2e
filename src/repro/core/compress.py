"""Cipher compressing (paper §4.4, Algorithm 4).

Hosts pack up to ``eta_s = floor(iota / b_gh)`` split-info ciphertexts into
one by repeated homomorphic shift-and-add: ``e <- e * 2**b_gh + c``.  The
guest then performs a single decryption per package and unpacks ``eta_s``
histogram statistics from the plaintext, dividing decryption count and
transfer bytes by ``eta_s`` (eqs 15-16).

Works with any cipher suite (limb backends vectorize over whole batches;
the Paillier oracle loops).  Slot order: the FIRST ciphertext in a group is
most significant (Algorithm 4 shifts the accumulator before each add).
"""

from __future__ import annotations

import numpy as np


def compress_batch(cipher, cts, eta_s: int, b_slot: int, mesh=None):
    """Compress a batch of N ciphertexts into ceil(N / eta_s) packages.

    cts: for limb backends a (N, Ln) array; for pyobj an object array (N,).
    Returns (packages, group_sizes) where group_sizes[i] is how many source
    ciphertexts package i holds (the last group may be short).

    ``mesh``: optional (data, model) jax Mesh — large batches shard the
    shift-and-add over the "data" axis (see :func:`_sharded_compress`);
    small ones keep the single-device path.
    """
    if eta_s < 1:
        raise ValueError("eta_s must be >= 1")
    if cipher.backend == "limb":
        import jax.numpy as jnp
        cts = jnp.asarray(cts)
    n = cts.shape[0]
    n_groups = -(-n // eta_s)
    sizes = np.full(n_groups, eta_s, dtype=np.int64)
    if n % eta_s:
        sizes[-1] = n % eta_s

    if cipher.backend == "limb":
        import jax.numpy as jnp
        pad = n_groups * eta_s - n
        # pad with encrypted zeros at the END of the last group; they occupy
        # the LEAST significant slots, so real stats keep their positions iff
        # we also tell the guest the true group size (we do).  To keep slot
        # arithmetic simple we instead pad and report the padded size layout:
        # the guest unpacks eta_s slots and discards the trailing pad.
        if pad:
            # E(0) = 0 for both limb schemes; match the incoming width
            # (canonical histograms may carry headroom limbs).
            zero_ct = jnp.zeros((pad, cts.shape[-1]), cts.dtype)
            cts = jnp.concatenate([cts, zero_ct], axis=0)
        groups = cts.reshape(n_groups, eta_s, -1)
        acc = _sharded_compress(cipher, groups, eta_s, b_slot, mesh)
        if acc is None:
            acc = groups[:, 0, :]
            for s in range(1, eta_s):
                acc = cipher.mul_pow2(acc, b_slot)
                acc = cipher.add(acc, groups[:, s, :])
        return acc, sizes
    else:  # pyobj (Paillier oracle)
        cts = np.asarray(cts, dtype=object)
        packages = np.empty(n_groups, dtype=object)
        for gi in range(n_groups):
            grp = cts[gi * eta_s: gi * eta_s + int(sizes[gi])]
            acc = grp[0]
            for c in grp[1:]:
                acc = cipher.mul_pow2(np.asarray([acc], dtype=object), b_slot)[0]
                acc = cipher.add(np.asarray([acc], dtype=object),
                                 np.asarray([c], dtype=object))[0]
            packages[gi] = acc
        return packages, sizes


def _sharded_compress(cipher, groups, eta_s: int, b_slot: int, mesh):
    """Mesh-sharded shift-and-add over the package axis.

    Every homomorphic op in Algorithm 4 (``mul_pow2`` then ``add``, slot by
    slot) is row-wise over packages, so sharding the group axis over "data"
    runs the whole compress with NO collective and stays bit-identical to
    the single-device loop.  Gated exactly like the sharded decrypt/cumsum
    paths: shard only when every data shard gets at least one full kernel
    row block (``n_groups >= BLOCK_N * data_shards``); returns None below
    the gate and the caller falls back."""
    if mesh is None:
        return None
    dd = dict(mesh.shape).get("data", 1)
    G = int(groups.shape[0])
    from ..kernels.modmul.modmul import BLOCK_N
    if dd <= 1 or G < BLOCK_N * dd:
        return None
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import data_pad, gbdt_sharding
    # pow2 bucketing caps distinct shard_map compilations at O(log max_G)
    bucket = 1 << max(G - 1, 0).bit_length()
    bucket += data_pad(mesh, bucket)
    x = groups
    if bucket > G:
        # pad groups are all-zero ciphertexts: E(0) shift-and-adds to E(0)
        x = jnp.pad(x, [(0, bucket - G), (0, 0), (0, 0)])
    x = jax.device_put(x, gbdt_sharding(mesh, "split_infos", ndim=3))

    def shard(xs):
        acc = xs[:, 0, :]
        for s in range(1, eta_s):
            acc = cipher.mul_pow2(acc, b_slot)
            acc = cipher.add(acc, xs[:, s, :])
        return acc

    out = jax.shard_map(shard, mesh=mesh, in_specs=P("data", None, None),
                        out_specs=P("data", None))(x)
    # land on the mesh's first device next to the decrypt consumer's
    # single-device operands
    return jax.device_put(out[:G], mesh.devices.flat[0])


def decompress_ints(plain_ints, sizes, eta_s: int, b_slot: int,
                    padded: bool) -> list:
    """Unpack decrypted package ints back into per-split-info ints.

    ``padded`` says whether short groups were zero-padded to eta_s slots
    (limb backends) or built with their true size (pyobj backend).
    """
    out = []
    mask = (1 << b_slot) - 1
    for x, size in zip(plain_ints, np.asarray(sizes, dtype=np.int64)):
        x = int(x)
        slots_here = eta_s if padded else int(size)
        vals = []
        for _ in range(slots_here):
            vals.append(x & mask)
            x >>= b_slot
        vals.reverse()                  # first ciphertext was most significant
        out.extend(vals[: int(size)])
    return out
