"""Work and bytes a cell requires, from the configuration's shapes alone,
and the least time the chip could take for them.

The counts never read the program's limb layout or its kernel choice, so
the same work is counted whatever implements it.

Training follows the SecureBoost+ cost model (arXiv:2110.10927, eqs.
(14)-(16); the repo's ``bench_cost_model.analytic`` codes the same):

* ``0.5 n_i h n_fh + n_n n_fh n_b`` homomorphic additions (histograms with
  subtraction, then the cumulative sums),
* ``n_i`` encryptions,
* ``n_b n_fh n_n / eta`` decryptions (compressed packages),
* ``0.5 n_i h n_fg`` guest plaintext (g, h, count) accumulations,

with ``n_n = 2**h`` nodes and ``eta = floor(iota / b_gh)`` split-infos per
ciphertext, where ``iota = key_bits - 1`` and ``b_gh`` is the paper's
packing width (eqs. 12-13) for g in [-1, 1], h in [0, 1] at ``r`` bits.

Each operation is priced in 8-bit limb operations of the key width,
``k = key_bits / 8`` limbs:

* homomorphic addition: ``k`` limb additions;
* encryption and decryption (one Barrett modular multiplication of the
  affine cipher): three ``k x k`` limb products of ``k**2`` multiply-adds
  each, ``6 k**2`` operations (Menezes, van Oorschot and Vanstone,
  Handbook of Applied Cryptography, section 14.3.3, Algorithm 14.42);
* a guest accumulation: 3 operations.

Bytes are the least HBM traffic: every ciphertext is written once and read
once per histogram layer it enters (half the rows per layer, by
subtraction), each bin id is one byte read per layer, and the plaintext
batch is read once.  Operations are held against the int8 peak, bytes
against HBM bandwidth, and the least time is the larger of the two.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """Peak rates of one chip; an unknown ``device_kind`` is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def packing_width(n_rows: int, r: int = 53) -> int:
    """b_gh of eqs. (12)-(13): bits one packed (g, h) sum over ``n_rows``
    rows needs, for g offset from [-1, 1] to [0, 2] and h in [0, 1]."""
    per_g = math.floor(2.0 * (1 << r)) + 1
    per_h = math.floor(1.0 * (1 << r)) + 1
    return (n_rows * per_g).bit_length() + (n_rows * per_h).bit_length()


def train_round(n_rows: int, guest_features: int, host_features: int,
                max_depth: int, n_bins: int, key_bits: int,
                precision: int = 53) -> dict:
    """Operations and bytes one boosting round (one binary tree) needs."""
    n_i, h, n_b = n_rows, max_depth, n_bins
    n_fg, n_fh = guest_features, host_features
    n_n = 2 ** h
    k = key_bits // 8
    eta = max(1, (key_bits - 1) // packing_width(n_i, precision))
    hom_add = 0.5 * n_i * h * n_fh + n_n * n_fh * n_b
    enc = n_i
    dec = n_b * n_fh * n_n / eta
    guest_add = 0.5 * n_i * h * n_fg
    modmul_ops = 6 * k * k
    ops = hom_add * k + (enc + dec) * modmul_ops + 3 * guest_add
    nbytes = (2 * n_i * k                      # plaintext in, ciphertext out
              + 0.5 * n_i * h * k              # ciphertexts per layer
              + 0.5 * n_i * h * (n_fh + n_fg)  # bin ids per layer
              + 0.5 * n_i * h * 16             # guest g, h per layer
              + 2 * dec * k)                   # packages in and out
    return {"ops": float(ops), "bytes": float(nbytes),
            "hom_add": hom_add, "encrypt": enc, "decrypt": dec,
            "guest_add": guest_add, "eta": eta}


def serve_batch(batch_rows: int, guest_features: int, host_features: int,
                n_trees: int, max_depth: int, n_bins: int) -> dict:
    """Operations and bytes one scored batch needs: bucketize every value
    against its ``n_bins - 1`` thresholds, one comparison per tree level
    per row, and one float add per tree per row."""
    n = batch_rows
    n_f = guest_features + host_features
    ops = n * n_f * (n_bins - 1) + n * n_trees * max_depth + n * n_trees
    nbytes = n * n_f * 4 + n * n_f + n * 8     # values in, bins, scores out
    return {"ops": float(ops), "bytes": float(nbytes)}


def least_seconds(work: dict, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    the int8 peak and bytes over HBM bandwidth."""
    return max(work["ops"] / peak["int8_ops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
