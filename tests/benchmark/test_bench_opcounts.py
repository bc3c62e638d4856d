"""opcounts.py against counts made by hand at a tiny shape (no JAX)."""
from benchmark import opcounts as oc


def test_field_and_ring_multiplications_by_hand():
    assert oc.FIELD_MUL == 22 * 22 + 22 * 2 + 2 == 530
    assert oc.BARRETT_REDUCE == 23 * 24 + 23 * 23 == 1081
    assert oc.SCALAR_MULMOD == 484 + 1081


def test_exponentiations_by_hand():
    assert oc.pow_const_mults(0b1011) == 4 + 3
    # p - 2 = 2^255 - 21: 255 bits, all ones but bits 2 and 4
    assert oc.INV == 255 + 253
    # (p - 5) / 8 = 2^252 - 3: 252 bits, all ones but bit 1
    assert oc.DECOMPRESS == 13 + 252 + 251


def test_one_lane_by_hand():
    fm = 530
    assert oc.nonce_commitments(1) == 1081 + (256 * 9 + 508 + 2) * fm
    assert oc.aggregate_nonce(3, 1) == (3 * 516 + 2 * 9 + 510) * fm
    assert oc.partial_signature(1) == 1081 + 1565
    assert oc.combine_signatures(3, 1) == 0
    assert oc.verify_signatures(1) == 1081 + (
        2 * 516 + 256 * 9 + 512 * 9 + 9 + 4) * fm


def test_a_wave_is_every_party_over_every_lane():
    wave = oc.per_wave(wave=2, q=3)
    assert set(wave) == set(oc.KERNELS)
    assert wave["nonce_commitments"] == 3 * oc.nonce_commitments(2)
    assert wave["aggregate_nonce"] == 3 * oc.aggregate_nonce(3, 2)
    assert wave["verify_signatures"] == 3 * 2 * oc.verify_signatures(1)
