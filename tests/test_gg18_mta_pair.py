"""One ordered MtA pair of the batched GG18 engine, kernel by kernel on the
CPU (no round program is compiled: each ``MtaBatch`` step runs its own
small kernels), at the 1024-bit fixtures with the shrunk proof domains of
the other GG18 suites. Tier-1's check of the Paillier side the round
programs are made of: the two parties' additive shares multiply out
(α + β = a·b mod q), an honest proof passes the batched verdict, and a
tampered range proof (Alice's) and a tampered response (Bob's) each fail
the combined check, fall back to the strict one, and cost exactly their
lane. Runs in a process of its own (``run_isolated``: tests/conftest.py
says why the GG18 graphs are kept out of the long pytest process).
"""
import os
import random

import numpy as np
import pytest
from conftest import run_isolated

_INNER = os.environ.get("MPCIUM_GG18_MTA_PAIR_INNER")


def test_one_mta_pair_isolated():
    if _INNER:
        pytest.skip("wrapper entry; inner run executes the real test")
    run_isolated(__file__, "test_shares_multiply_out_and_a_bad_lane_is_found",
                 "MPCIUM_GG18_MTA_PAIR_INNER", timeout=1200)


@pytest.mark.skipif(not _INNER, reason="runs via the subprocess wrapper")
def test_shares_multiply_out_and_a_bad_lane_is_found():
    from mpcium_tpu.cluster import load_test_preparams
    import jax.numpy as jnp

    from mpcium_tpu.core import bignum as bn
    from mpcium_tpu.core import secp256k1_jax as sp
    from mpcium_tpu.engine import gg18_batch as gb

    assert gb.BATCH_VERIFY == "rand"  # the served path's verdict
    B = 2
    rng = random.Random(29)
    dom = gb.Domains(alpha=600, beta_prime=320, gamma_bob=600)
    pre = load_test_preparams(bits=1024)  # committed, fixed keys
    alice = gb.PartyCtx("node0", pre["node0"])
    bob = gb.PartyCtx("node1", pre["node1"])
    mta = gb.MtaBatch(alice, bob, dom)

    a = [rng.randrange(1, gb.Q) for _ in range(B)]
    b = [rng.randrange(1, gb.Q) for _ in range(B)]
    kp = gb._scalar_to_plain(
        alice.pmx, jnp.asarray(bn.batch_to_limbs(a, bn.P256)))
    u_bits = gb.rand_bit_tensor(B, gb.RAND_BITS)
    c_a, _r = alice.pmx.encrypt(kp, u_bits)
    Ra = mta.alice_randoms(B)
    T = mta.alice_init(kp, Ra)
    e = mta.e_limbs(mta.alice_challenge(c_a, T))
    P = mta.alice_finish(e, kp, Ra, u_bits)
    assert np.asarray(mta.bob_check_alice(c_a, T, P, e)).all()

    def off_by_one(tree, field, lane):
        x = np.array(tree[field])
        x[lane, 0] ^= 1
        return dict(tree, **{field: jnp.asarray(x)})

    # Alice's range proof with s1 altered in lane 0
    ok = np.asarray(mta.bob_check_alice(c_a, T, off_by_one(P, "s1", 0), e))
    assert list(ok) == [False, True]

    b_e = jnp.asarray(bn.batch_to_limbs(b, mta.p_e))
    Rb = mta.bob_randoms(B)
    Tb = mta.bob_respond(c_a, b_e, Rb)
    e_b = mta.e_limbs(mta.bob_challenge(c_a, Tb))
    Pb = mta.bob_finish(e_b, b_e, Rb)
    assert np.asarray(mta.alice_check_bob(c_a, Tb, Pb, e_b)).all()
    # the two additive shares: Alice's Dec(c_b) mod q, Bob's -β' mod q
    alpha = bn.batch_from_limbs(
        np.asarray(mta.alice_decrypt_share(Tb["c_b"])), bn.P256)
    beta = bn.batch_from_limbs(np.asarray(sp.scalar_ring().negmod(
        gb._mod_q_from_limbs(Rb["beta_prime"], mta.p_bp))), bn.P256)
    assert [(x + y) % gb.Q for x, y in zip(alpha, beta)] == [
        x * y % gb.Q for x, y in zip(a, b)]

    # Bob's response with the ciphertext altered in lane 1, then with a
    # proof value altered in lane 0
    ok = np.asarray(mta.alice_check_bob(
        c_a, off_by_one(Tb, "c_b", 1), Pb, e_b))
    assert list(ok) == [True, False]
    ok = np.asarray(mta.alice_check_bob(
        c_a, Tb, off_by_one(Pb, "t1", 0), e_b))
    assert list(ok) == [False, True]
