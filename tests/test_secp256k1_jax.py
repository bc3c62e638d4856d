"""Batched secp256k1 JAX kernels vs hostmath ground truth."""
import secrets

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpcium_tpu.core import bignum as bn
from mpcium_tpu.core import hostmath as hm
from mpcium_tpu.core import secp256k1_jax as sj


def rand_scalars(n):
    return [secrets.randbelow(hm.SECP_N - 1) + 1 for _ in range(n)]


def host_points(ks):
    return [hm.secp_mul(k, hm.SECP_G) for k in ks]


def test_add_matches_host():
    k1, k2 = rand_scalars(4), rand_scalars(4)
    out = sj.to_host(
        jax.jit(sj.add)(sj.from_host(host_points(k1)), sj.from_host(host_points(k2)))
    )
    for a, b, got in zip(k1, k2, out):
        assert got == hm.secp_mul((a + b) % hm.SECP_N, hm.SECP_G)


@pytest.mark.slow
def test_complete_edge_cases():
    """The completeness claims: P+(-P)=O, P+O=P, O+O=O, P+P=2P."""
    k = rand_scalars(1)[0]
    P = hm.secp_mul(k, hm.SECP_G)
    negP = hm.SecpPoint(P.x, hm.SECP_P - P.y)
    pj = sj.from_host([P, P, P])
    qj = sj.from_host([negP, P, P])
    # batch: P+(-P), P+P (doubling through add), P+P again
    out = sj.to_host(sj.add(pj, qj))
    assert out[0].is_infinity
    assert out[1] == hm.secp_mul(2 * k % hm.SECP_N, hm.SECP_G)
    # identity handling
    ident = sj.identity((3,))
    out2 = sj.to_host(sj.add(pj, ident))
    for got in out2:
        assert got == P
    out3 = sj.to_host(sj.add(ident, ident))
    for got in out3:
        assert got.is_infinity


def test_base_mul_matches_host():
    ks = rand_scalars(4) + [1, hm.SECP_N - 1]
    bits = jnp.asarray(sj.scalars_to_bits(ks))
    out = sj.to_host(jax.jit(sj.base_mul)(bits))
    for k, got in zip(ks, out):
        assert got == hm.secp_mul(k, hm.SECP_G), k


def test_scalar_mul_variable_base():
    base_k = rand_scalars(1)[0]
    base = sj.from_host(host_points([base_k] * 3))
    ks = rand_scalars(3)
    bits = jnp.asarray(sj.scalars_to_bits(ks))
    out = sj.to_host(jax.jit(sj.scalar_mul)(bits, base))
    for k, got in zip(ks, out):
        assert got == hm.secp_mul(k * base_k % hm.SECP_N, hm.SECP_G)


def test_compress_and_x():
    ks = rand_scalars(3)
    bits = jnp.asarray(sj.scalars_to_bits(ks))
    pts = jax.jit(sj.base_mul)(bits)
    comp = np.asarray(jax.jit(sj.compress)(pts))
    xs = np.asarray(jax.jit(sj.x_coordinate)(pts))
    for k, row, xl in zip(ks, comp, xs):
        host = hm.secp_mul(k, hm.SECP_G)
        assert bytes(row.tolist()) == hm.secp_compress(host)
        assert bn.from_limbs(xl, bn.P256) == host.x


def test_equal_batch():
    ks = rand_scalars(2)
    p = sj.from_host(host_points(ks + [ks[0]]))
    q = sj.from_host(host_points([ks[0], ks[1], ks[1]]))
    # make third pair identity-vs-point
    eq = np.asarray(sj.equal(p, q))
    assert list(eq) == [True, True, False]
    ident = sj.identity((3,))
    eq2 = np.asarray(sj.equal(ident, ident))
    assert all(eq2)
    eq3 = np.asarray(sj.equal(p, ident))
    assert not any(eq3)


def test_decompress_roundtrip_and_rejection():
    ks = rand_scalars(4)
    bits = jnp.asarray(sj.scalars_to_bits(ks))
    pts = jax.jit(sj.base_mul)(bits)
    comp = jax.jit(sj.compress)(pts)
    got, ok = jax.jit(sj.decompress)(comp)
    assert np.asarray(ok).all()
    assert np.asarray(jax.jit(sj.equal)(got, pts)).all()
    # corrupt one row: bad tag; another: x with no square root
    bad = np.asarray(comp).copy()
    bad[0, 0] = 0x05
    bad[1, 1:] = 0xFF  # x >= p
    _, ok = jax.jit(sj.decompress)(jnp.asarray(bad))
    assert list(np.asarray(ok)) == [False, False, True, True]


# --- PR 44: the four-call addition, the one-hot table reads ---------------

P = hm.SECP_P
_TOP = (1 << 264) - 1  # every limb 4,095


def _formula(p1, p2):
    """RCB15 Algorithm 7 (a = 0, b3 = 21) on python ints: what `add` must
    give for ANY pair of triples, on the curve or not."""
    (X1, Y1, Z1), (X2, Y2, Z2) = p1, p2
    t0, t1, t2 = X1 * X2, Y1 * Y2, Z1 * Z2
    t3 = (X1 + Y1) * (X2 + Y2) - t0 - t1
    t4 = (Y1 + Z1) * (Y2 + Z2) - t1 - t2
    y3 = 21 * ((X1 + Z1) * (X2 + Z2) - t0 - t2)
    k0, z3, t1 = 3 * t0, t1 + 21 * t2, t1 - 21 * t2
    return ((t3 * t1 - t4 * y3) % P, (t1 * z3 + y3 * k0) % P,
            (z3 * t4 + k0 * t3) % P)


def _raw(triples) -> sj.SecpPointJ:
    """Triples of ints below 2^264 as they are, not reduced mod p."""
    return sj.SecpPointJ(*(
        jnp.asarray(bn.batch_to_limbs([t[i] for t in triples], bn.P256))
        for i in range(3)))


def _ints3(pt: sj.SecpPointJ):
    F = sj.secp256k1_field()
    return list(zip(F.to_ints(pt.X), F.to_ints(pt.Y), F.to_ints(pt.Z)))


def _lift(pt: hm.SecpPoint, z: int):
    """An on-curve point as a projective triple whose coordinates are the
    LARGEST representatives below 2^264 (x·z + k·p with k as large as
    fits): the top of the range a field operation may return."""
    def top(v):
        v %= P
        return v + (_TOP - v) // P * P

    if pt.is_infinity:
        return (top(0), top(z), top(0))
    return (top(pt.x * z), top(pt.y * z), top(z))


_ADD4 = jax.jit(sj.add)


@pytest.mark.parametrize("case", ["all_limbs_4095", "mixed_with_zero", "random_unreduced"])
def test_add_is_the_formula_at_the_representations_extremes(case):
    """Off the curve too: the int32 column bound and the offset multiple
    of p hold for every operand below 2^264, exercised and not assumed."""
    import random
    rng = random.Random(44)
    rnd = lambda: tuple(rng.randrange(1 << 264) for _ in range(3))  # noqa: E731
    a, b = {
        "all_limbs_4095": ([(_TOP,) * 3] * 4, [(_TOP,) * 3] * 4),
        "mixed_with_zero": ([(_TOP, 0, _TOP), (0, _TOP, 0), (0, 0, 0), (_TOP, _TOP, 0)],
                            [(0, _TOP, _TOP), (_TOP,) * 3, (_TOP,) * 3, (0, 0, _TOP)]),
        "random_unreduced": ([rnd() for _ in range(4)], [rnd() for _ in range(4)]),
    }[case]
    got = _ADD4(_raw(a), _raw(b))
    assert _ints3(got) == [_formula(x, y) for x, y in zip(a, b)]
    assert int(jnp.max(jnp.stack(got))) < 1 << 12 and int(jnp.min(jnp.stack(got))) >= 0


@pytest.mark.parametrize("edge", ["ident+P", "P+ident", "P+P", "P+(-P)", "ident+ident", "P+Q"])
def test_add_edges_with_the_largest_representatives(edge):
    k1, k2 = rand_scalars(2)
    Pt, Qt = hm.secp_mul(k1, hm.SECP_G), hm.secp_mul(k2, hm.SECP_G)
    neg = hm.SecpPoint(Pt.x, P - Pt.y)
    a, b = {
        "ident+P": (hm.SECP_INF, Pt), "P+ident": (Pt, hm.SECP_INF),
        "P+P": (Pt, Pt), "P+(-P)": (Pt, neg),
        "ident+ident": (hm.SECP_INF, hm.SECP_INF), "P+Q": (Pt, Qt),
    }[edge]
    zs = rand_scalars(4)
    got = sj.to_host(_ADD4(_raw([_lift(a, z) for z in zs]),
                           _raw([_lift(b, z) for z in zs[::-1]])))
    assert got == [hm.secp_add(a, b)] * 4


@pytest.mark.parametrize("how", ["adding_Q", "doubling"])
def test_add_fed_its_own_output_for_40_rounds(how):
    """The result of an addition is an operand of the next: 40 rounds from
    the largest representatives stay exact and normalized."""
    ks = rand_scalars(4)
    pts = host_points(ks)
    q = hm.secp_mul(7, hm.SECP_G)
    acc = _raw([_lift(p, z) for p, z in zip(pts, rand_scalars(4))])
    Q = _raw([_lift(q, z) for z in rand_scalars(4)])
    for _ in range(40):
        acc = _ADD4(acc, Q if how == "adding_Q" else acc)
        assert int(jnp.max(jnp.stack(acc))) < 1 << 12
    mult = (lambda k: k + 40 * 7) if how == "adding_Q" else (lambda k: k << 40)
    assert sj.to_host(acc) == [hm.secp_mul(mult(k) % hm.SECP_N, hm.SECP_G) for k in ks]


# scalars at the ends of the range, and digits that read every table entry
_EVERY_DIGIT = int("0123456789abcdef" * 4, 16)
_EDGE_SCALARS = [0, 1, hm.SECP_N - 1, (1 << 256) - 1, _EVERY_DIGIT,
                 int("fedcba9876543210" * 4, 16)]


def test_base_mul_edge_scalars_and_every_table_entry():
    bits = jnp.asarray(sj.scalars_to_bits(_EDGE_SCALARS))
    out = sj.to_host(jax.jit(sj.base_mul)(bits))
    assert out == [hm.secp_mul(k % hm.SECP_N, hm.SECP_G) for k in _EDGE_SCALARS]
    assert out[0].is_infinity


def test_scalar_mul_edge_scalars_and_every_table_entry():
    base_k = rand_scalars(1)[0]
    base = sj.from_host(host_points([base_k] * len(_EDGE_SCALARS)))
    bits = jnp.asarray(sj.scalars_to_bits(_EDGE_SCALARS))
    out = sj.to_host(jax.jit(sj.scalar_mul)(bits, base))
    assert out == [hm.secp_mul(k * base_k % hm.SECP_N, hm.SECP_G)
                   for k in _EDGE_SCALARS]
    assert out[0].is_infinity


def test_pick_reads_each_entry_without_an_address():
    """The one-hot read returns entry d for every digit, for a lane's
    table and for a constant table with lane axes of extent 1; its
    program holds no gather."""
    lanes = 16
    d = jnp.arange(lanes, dtype=jnp.int32)
    table = jnp.arange(3 * 16 * lanes * 22, dtype=jnp.int32).reshape(3, 16, lanes, 22)
    got = sj._pick(table, d)
    for c in range(3):
        assert (np.asarray(got[c]) == np.asarray(table)[c, np.arange(lanes), np.arange(lanes)]).all()
    const = table[:, :, :1]
    got = sj._pick(const, d)
    assert (np.asarray(got.Y) == np.asarray(const)[1, :, 0]).all()
    bits = jnp.zeros((2, 256), jnp.int32)
    pt = sj.identity((2,))
    for fn, args in ((sj.base_mul, (bits,)), (sj.scalar_mul, (bits, pt))):
        text = str(jax.make_jaxpr(fn)(*args))
        assert "gather" not in text, fn.__name__
