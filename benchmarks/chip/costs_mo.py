"""Work and bytes one SecureBoost-MO round requires, from the
configuration's shapes alone: ``costs.train_round`` extended to ``l``
classes packed into shared ciphertexts (arXiv:2110.10927, section 5.3).

``eta_c = floor(iota / b_gh)`` classes share a ciphertext and every row
takes ``n_k = ceil(l / eta_c)`` of them (eqs. (21)-(22)), with ``iota =
key_bits - 1`` and ``b_gh`` the binary cost model's packing width
(``costs.packing_width``).  Against one binary tree:

* ``n_i n_k`` encryptions;
* the homomorphic additions times ``n_k`` (each histogram cell holds
  ``n_k`` ciphertexts);
* ``n_b n_fh n_n n_k`` decryptions: MO candidates are not compressed;
* the guest's plaintext accumulations times ``l`` (a g and an h per
  class).

Operations and bytes are priced as ``costs`` prices them; at one class the
counts other than the decryptions are ``costs.train_round``'s.
"""

from __future__ import annotations

import math

import costs


def train_round(n_rows: int, guest_features: int, host_features: int,
                max_depth: int, n_bins: int, key_bits: int,
                n_classes: int, precision: int = 53) -> dict:
    """Operations and bytes one multi-output tree over ``n_classes``
    needs."""
    n_i, h, n_b, l = n_rows, max_depth, n_bins, n_classes
    n_fg, n_fh = guest_features, host_features
    n_n = 2 ** h
    k = key_bits // 8
    eta_c = max(1, (key_bits - 1) // costs.packing_width(n_i, precision))
    n_k = math.ceil(l / eta_c)
    hom_add = (0.5 * n_i * h * n_fh + n_n * n_fh * n_b) * n_k
    enc = n_i * n_k
    dec = n_b * n_fh * n_n * n_k
    guest_add = 0.5 * n_i * h * n_fg * l
    modmul_ops = 6 * k * k
    ops = hom_add * k + (enc + dec) * modmul_ops + 3 * guest_add
    nbytes = (2 * enc * k                      # plaintext in, ciphertext out
              + 0.5 * n_i * h * k * n_k        # ciphertexts per layer
              + 0.5 * n_i * h * (n_fh + n_fg)  # bin ids per layer
              + 0.5 * n_i * h * 16 * l         # guest g, h per layer
              + 2 * dec * k)                   # candidates in and out
    return {"ops": float(ops), "bytes": float(nbytes),
            "hom_add": hom_add, "encrypt": enc, "decrypt": dec,
            "guest_add": guest_add, "eta_c": eta_c, "n_k": n_k}
