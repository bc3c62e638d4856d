"""Field-arithmetic property tests vs python-int ground truth."""
import secrets

import jax.numpy as jnp
import numpy as np
import pytest

from mpcium_tpu.core import bignum as bn
from mpcium_tpu.core import fields as fl
from mpcium_tpu.core import hostmath as hm

PROF = bn.P256
FIELDS = {
    "ed25519": (fl.ed25519_field, hm.ED_P),
    "secp256k1": (fl.secp256k1_field, hm.SECP_P),
}


def rand_elems(n, p):
    return [secrets.randbelow(p) for _ in range(n)]


@pytest.mark.parametrize("name", list(FIELDS))
def test_field_mul_add_sub(name):
    mk, p = FIELDS[name]
    F = mk()
    n = 8
    xs, ys = rand_elems(n, p), rand_elems(n, p)
    lx = jnp.asarray(F.from_ints(xs))
    ly = jnp.asarray(F.from_ints(ys))
    assert F.to_ints(F.mul(lx, ly)) == [x * y % p for x, y in zip(xs, ys)]
    assert F.to_ints(F.add(lx, ly)) == [(x + y) % p for x, y in zip(xs, ys)]
    assert F.to_ints(F.sub(lx, ly)) == [(x - y) % p for x, y in zip(xs, ys)]
    assert F.to_ints(F.neg(lx)) == [(-x) % p for x in xs]


@pytest.mark.parametrize("name", list(FIELDS))
def test_field_redundant_chains(name):
    """Long chains of non-canonical intermediates stay correct."""
    mk, p = FIELDS[name]
    F = mk()
    xs = rand_elems(4, p)
    acc = jnp.asarray(F.from_ints(xs))
    ref = list(xs)
    for i in range(12):
        acc = F.mul(acc, acc) if i % 3 else F.add(acc, acc)
        ref = [x * x % p if i % 3 else 2 * x % p for x in ref]
    assert F.to_ints(acc) == ref


@pytest.mark.parametrize("name", list(FIELDS))
def test_field_edge_values(name):
    mk, p = FIELDS[name]
    F = mk()
    xs = [0, 1, p - 1, p - 2, 2]
    lx = jnp.asarray(F.from_ints(xs))
    assert F.to_ints(F.mul(lx, lx)) == [x * x % p for x in xs]
    assert list(np.asarray(F.is_zero(lx))) == [x == 0 for x in xs]


@pytest.mark.parametrize("name", list(FIELDS))
def test_field_inverse(name):
    mk, p = FIELDS[name]
    F = mk()
    xs = [x + 1 for x in rand_elems(4, p - 1)]
    lx = jnp.asarray(F.from_ints(xs))
    assert F.to_ints(F.inv(lx)) == [pow(x, -1, p) for x in xs]


def test_ed25519_sqrt():
    S = fl.Ed25519Sqrt()
    p = hm.ED_P
    xs = rand_elems(4, p)
    squares = [x * x % p for x in xs]
    lx = jnp.asarray(S.F.from_ints(squares))
    roots, ok = S.sqrt(lx)
    assert all(np.asarray(ok))
    got = S.F.to_ints(roots)
    for g, sq in zip(got, squares):
        assert g * g % p == sq
    # a non-residue must report ok=False
    nr = 2  # 2 is a non-residue mod 2^255-19
    assert pow(nr, (p - 1) // 2, p) == p - 1
    _, ok2 = S.sqrt(jnp.asarray(S.F.from_ints([nr])))
    assert not np.asarray(ok2)[0]


def test_secp256k1_sqrt():
    S = fl.Secp256k1Sqrt()
    p = hm.SECP_P
    xs = rand_elems(4, p)
    squares = [x * x % p for x in xs]
    roots, ok = S.sqrt(jnp.asarray(S.F.from_ints(squares)))
    assert all(np.asarray(ok))
    for g, sq in zip(S.F.to_ints(roots), squares):
        assert g * g % p == sq
    # find a non-residue
    nr = 3
    while pow(nr, (p - 1) // 2, p) != p - 1:
        nr += 1
    _, ok2 = S.sqrt(jnp.asarray(S.F.from_ints([nr])))
    assert not np.asarray(ok2)[0]


# --- PR 44: secp256k1's inverse and square root by addition chains ---------


def _secp_chain_inputs():
    p = hm.SECP_P
    nr = 3
    while pow(nr, (p - 1) // 2, p) != p - 1:
        nr += 1
    return {
        "zero": [0], "one": [1], "p_minus_1": [p - 1],
        "random": rand_elems(5, p),
        "non_residues": [nr, nr * 4 % p, p - 4],  # p ≡ 3 (mod 4): −1 is none
    }


@pytest.mark.parametrize("which", ["zero", "one", "p_minus_1", "random", "non_residues"])
def test_secp256k1_chains_are_the_powers(which):
    """x^(p − 2) and x^((p + 1)/4) by the chains, against `pow`; the
    representatives at the top of the range (x + k·p just under 2^264)
    give the same answers."""
    p = hm.SECP_P
    F = fl.secp256k1_field()
    xs = _secp_chain_inputs()[which]
    lx = jnp.asarray(F.from_ints(xs))
    top = jnp.asarray(bn.batch_to_limbs(
        [x + ((1 << 264) - 1 - x) // p * p for x in xs], bn.P256))
    for v in (lx, top):
        assert F.to_ints(F.inv(v)) == [pow(x, p - 2, p) for x in xs]
        assert F.to_ints(F.sqrt_candidate(v)) == [pow(x, (p + 1) // 4, p) for x in xs]
    roots, ok = fl.Secp256k1Sqrt().sqrt(lx)
    want = [x == 0 or pow(x, (p - 1) // 2, p) == 1 for x in xs]
    assert list(np.asarray(ok)) == want
    if which == "non_residues":
        assert not any(want)
    for r, x, w in zip(F.to_ints(roots), xs, want):
        assert (r * r % p == x) == w


def test_secp256k1_chain_programs_spell_the_exponents():
    """The straight-line programs on python ints: 255 squarings and 15
    products for p − 2, 253 and 13 for (p + 1)/4."""
    p = hm.SECP_P
    for prog, e, sq, mu in ((fl.Secp256k1Field._INV, p - 2, 255, 15),
                            (fl.Secp256k1Field._SQRT, (p + 1) // 4, 253, 13)):
        regs = [0xC0FFEE] * 7
        for i, j, k in prog.tolist():
            regs[k] = regs[i] * regs[j] % p
        assert regs[fl._T] == pow(0xC0FFEE, e, p)
        squarings = sum(1 for i, j, _ in prog.tolist() if i == j)
        assert (squarings, len(prog) - squarings) == (sq, mu)
