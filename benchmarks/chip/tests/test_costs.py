"""Work and byte counts at a tiny shape against hand-worked numbers, and
the peak table."""

import math

import pytest

import costs


def test_packing_width_by_hand():
    # r = 4, 8 rows: per_g = floor(2 * 16) + 1 = 33, 8 * 33 = 264 -> 9 bits;
    # per_h = 17, 8 * 17 = 136 -> 8 bits
    assert costs.packing_width(8, r=4) == 17


@pytest.mark.parametrize("precision,eta,dec", [(53, 1, 32.0),
                                               (4, 3, 32 / 3)])
def test_train_round_by_hand(precision, eta, dec):
    # 8 rows, 1 guest + 2 host features, depth 2, 4 bins, 64-bit keys
    w = costs.train_round(8, 1, 2, 2, 4, 64, precision)
    k = 8                                      # 64 / 8 limbs
    assert w["eta"] == eta
    assert w["hom_add"] == 0.5 * 8 * 2 * 2 + 4 * 2 * 4 == 48
    assert w["encrypt"] == 8
    assert w["decrypt"] == pytest.approx(dec)  # 4 bins * 2 feats * 4 nodes
    assert w["guest_add"] == 0.5 * 8 * 2 * 1 == 8
    ops = 48 * k + (8 + dec) * 6 * k * k + 3 * 8
    assert w["ops"] == pytest.approx(ops)
    nbytes = (2 * 8 * k + 0.5 * 8 * 2 * k + 0.5 * 8 * 2 * 3
              + 0.5 * 8 * 2 * 16 + 2 * dec * k)
    assert w["bytes"] == pytest.approx(nbytes)


def test_serve_batch_by_hand():
    # 16 rows, 2 + 3 features, 4 trees of depth 3, 8 bins
    w = costs.serve_batch(16, 2, 3, 4, 3, 8)
    assert w["ops"] == 16 * 5 * 7 + 16 * 4 * 3 + 16 * 4
    assert w["bytes"] == 16 * 5 * 4 + 16 * 5 + 16 * 8


def test_least_seconds_takes_the_binding_roof():
    peak = costs.peaks("TPU v5 lite")
    compute = {"ops": 393e12, "bytes": 1.0}
    memory = {"ops": 1.0, "bytes": 819e9 * 2}
    assert costs.least_seconds(compute, peak) == pytest.approx(1.0)
    assert costs.least_seconds(memory, peak) == pytest.approx(2.0)


def test_peaks_refuse_an_unknown_device_kind():
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks("TPU v99 imaginary")


def test_peaks_of_v5e_as_published():
    p = costs.peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["int8_ops_per_s"],
            p["hbm_bytes_per_s"], p["hbm_bytes"]) == (197e12, 393e12,
                                                      819e9, 16e9)
    assert all(math.isfinite(v) for v in p.values())
