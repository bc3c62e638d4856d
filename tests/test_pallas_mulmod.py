"""Bit-exactness of the fused Pallas mulmod kernel (ops/pallas_mulmod.py)
against python-int ground truth, in interpreter mode on CPU (the Mosaic
lowering itself is gated on the real chip by .scratch/chipcheck.py).

Covers the widths the GG18 engine dispatches (2048-bit Paillier moduli,
4096-bit Paillier-squared / NTilde domains), a small curve-order width,
edge values (0, 1, m-1), squaring, broadcasting, and the powmod scan
path with the module-level MPCIUM_MULMOD=pallas dispatch.

The live-limb product (PR 34) is held, at every width the fixtures have
(occ 147, 293, 585, 586: both N² occupancies), to python ints AND to the
band path limb for limb, for random values, the worst column sums (every
limb 127), values whose top limbs are zero, and 0 and 1; the same for a
value times itself, which is what a ladder's squarings dispatch.
"""
import secrets

import numpy as np
import pytest

import jax.numpy as jnp

from mpcium_tpu.core import bignum as bn
from mpcium_tpu.ops import modmul as mm
from mpcium_tpu.ops import pallas_mulmod as pmm


def _rand_mod(bits: int) -> int:
    return secrets.randbits(bits) | (1 << (bits - 1)) | 1


def _limbs(vals, ctx):
    return jnp.asarray(np.stack([bn.to_limbs(v, ctx.prof) for v in vals]))


def _ints(arr, ctx):
    return [bn.from_limbs(np.asarray(r), ctx.prof) for r in np.asarray(arr)]


@pytest.mark.parametrize("bits", [2048, 4096])
def test_mulmod_matches_host_ints(bits):
    m = _rand_mod(bits)
    ctx = mm.MXUBarrett(m)
    B = 8
    av = [secrets.randbits(bits) % m for _ in range(B)]
    bv = [secrets.randbits(bits) % m for _ in range(B)]
    # edges: zero, one, m-1 (max conditional-subtraction pressure)
    av[0], bv[0] = 0, secrets.randbits(bits) % m
    av[1], bv[1] = 1, m - 1
    av[2], bv[2] = m - 1, m - 1
    out = pmm.mulmod(
        _limbs(av, ctx), _limbs(bv, ctx), ctx._T_mu, ctx._T_m, ctx._comp,
        ctx.occ, ctx.prof.n_limbs, interpret=True,
    )
    got = _ints(out, ctx)
    for i in range(B):
        assert got[i] == av[i] * bv[i] % m, f"lane {i}"


def test_mulmod_small_width_and_broadcast():
    """256-bit modulus (occ close to n — exercises the conv frame guard)
    plus (n,)-constant broadcasting against a batch."""
    m = _rand_mod(256)
    ctx = mm.MXUBarrett(m)
    B = 5  # deliberately not a tile multiple: exercises batch padding
    av = [secrets.randbits(256) % m for _ in range(B)]
    c = secrets.randbits(256) % m
    a = _limbs(av, ctx)
    b1 = jnp.asarray(bn.to_limbs(c, ctx.prof))  # (n,) broadcasts
    out = pmm.mulmod(
        a, b1, ctx._T_mu, ctx._T_m, ctx._comp, ctx.occ, ctx.prof.n_limbs,
        interpret=True,
    )
    got = _ints(out, ctx)
    for i in range(B):
        assert got[i] == av[i] * c % m


def test_squaring_exact():
    m = _rand_mod(2048)
    ctx = mm.MXUBarrett(m)
    av = [secrets.randbits(2048) % m for _ in range(4)]
    a = _limbs(av, ctx)
    out = pmm.mulmod(
        a, a, ctx._T_mu, ctx._T_m, ctx._comp, ctx.occ, ctx.prof.n_limbs,
        interpret=True,
    )
    got = _ints(out, ctx)
    for i, v in enumerate(av):
        assert got[i] == v * v % m


def test_powmod_scan_under_pallas_dispatch(monkeypatch):
    """The module-level MPCIUM_MULMOD=pallas switch routes every
    mul+reduce inside the powmod scans through the fused kernel; the
    full square-and-multiply chain must stay exact end to end."""
    monkeypatch.setattr(mm, "MULMOD_IMPL", "pallas")
    m = _rand_mod(1024)
    ctx = mm.MXUBarrett(m)
    B = 3
    xv = [secrets.randbits(1024) % m for _ in range(B)]
    ev = [secrets.randbits(64) for _ in range(B)]
    x = _limbs(xv, ctx)
    ebits = jnp.asarray(
        np.stack([
            [(e >> i) & 1 for i in range(64)] for e in ev
        ]).astype(np.int32)
    )
    out = ctx.powmod(x, ebits)
    got = _ints(out, ctx)
    for i in range(B):
        assert got[i] == pow(xv[i], ev[i], m), f"lane {i}"


# ---------------------------------------------------------------------------
# the live-limb product, every fixture width
# ---------------------------------------------------------------------------

WIDTHS = [1024, 2048, 4095, 4096]  # occ 147, 293, 585, 586
KINDS = ["random", "worst", "short", "zero_one"]


def _case(bits: int, kind: str):
    """(context, a values, b values) of one case, 8 lanes. ``worst``: the
    modulus is R^occ - 159, so m - 1 has every limb but the lowest two at
    127 and every column of (m-1)(m-1) holds the largest sum a product of
    two residues can have; its lanes mix m - 1 with all-127 runs."""
    occ = -(-bits // 7)
    if kind == "worst":
        m = (1 << (7 * occ)) - 159
    else:
        m = _rand_mod(bits)
    ctx = mm.MXUBarrett(m)
    assert ctx.occ == occ
    B = 8
    if kind == "random":
        av = [secrets.randbits(bits) % m for _ in range(B)]
        bv = [secrets.randbits(bits) % m for _ in range(B)]
    elif kind == "worst":
        ones = [(1 << (7 * k)) - 1 for k in (occ - 1, occ // 2, 9, 8, 1)]
        av = [m - 1, m - 1, m - 2] + ones
        bv = [m - 1, m - 2, m - 1] + ones[::-1]
    elif kind == "short":
        # top limbs zero: lengths around the group (8) and block (128)
        # boundaries of the product's sweep
        lens = [1, 7, 8, 9, 127, 128, 129, 7 * (occ // 2)]
        av = [secrets.randbits(k) | 1 << (k - 1) for k in lens]
        bv = [secrets.randbits(k) | 1 << (k - 1) for k in lens[::-1]]
    else:
        av = [0, 0, 1, 1, 0, m - 1, 1, 2]
        bv = [0, 1, 1, 0, m - 1, 1, secrets.randbits(bits) % m, 0]
    return ctx, av, bv


def _pallas(ctx, a, b):
    return np.asarray(pmm.mulmod(
        a, b, ctx._T_mu, ctx._T_m, ctx._comp, ctx.occ, ctx.prof.n_limbs,
        interpret=True))


def _band(ctx, a, b):
    return np.asarray(mm._reduce_impl(
        mm.mul_pair(a, b), ctx._T_mu, ctx._T_m, ctx._comp, ctx.occ,
        ctx.prof.n_limbs))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("bits", WIDTHS)
def test_live_limb_product_matches_ints_and_band(bits, kind):
    ctx, av, bv = _case(bits, kind)
    a, b = _limbs(av, ctx), _limbs(bv, ctx)
    out = _pallas(ctx, a, b)
    assert _ints(out, ctx) == [x * y % ctx.modulus for x, y in zip(av, bv)]
    assert (out == _band(ctx, a, b)).all()  # limb for limb


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("bits", WIDTHS)
def test_squaring_matches_ints_and_band(bits, kind):
    """A value times itself (``worst``: (m - 1)², the largest column sums
    there are): what the ladders' squarings and `sqrmod` dispatch."""
    ctx, av, bv = _case(bits, kind)
    for vals in (av, bv):
        a = _limbs(vals, ctx)
        out = _pallas(ctx, a, a)
        assert _ints(out, ctx) == [x * x % ctx.modulus for x in vals]
        assert (out == _band(ctx, a, a)).all()


@pytest.mark.parametrize("tile", [5, 8, 16, 64])
def test_product_every_tile_and_leading_shape(tile):
    """The product at each tile height (5 pads to 8), and a (2, tile/2, n)
    operand where the tile is even."""
    ctx, _, _ = _case(1024, "random")
    m = ctx.modulus
    vals = [secrets.randbits(1024) % m for _ in range(tile)]
    a = _limbs(vals, ctx)
    if tile % 2 == 0:
        a = a.reshape(2, tile // 2, -1)
    out = _pallas(ctx, a, a)
    assert out.shape == a.shape
    assert _ints(out.reshape(tile, -1), ctx) == [v * v % m for v in vals]


@pytest.fixture
def pallas_dispatch(monkeypatch):
    """Every `_mm` through the fused kernel (interpreted here); a ladder
    traced under the other dispatch is not reused, either way."""
    monkeypatch.setattr(mm, "MULMOD_IMPL", "pallas")
    kernels = (mm._k_powmod, mm._k_powmod_digits)
    for k in kernels:
        k.clear_cache()
    yield
    for k in kernels:
        k.clear_cache()


@pytest.mark.parametrize("bits", [1024, 2048])
@pytest.mark.parametrize("ladder", ["powmod", "powmod_digits"])
def test_ladders_end_to_end_under_pallas_dispatch(pallas_dispatch, ladder,
                                                  bits):
    """`_k_powmod` (per-lane exponent bits) and `_k_powmod_digits` (one
    exponent for the batch): the table, four squarings and one product a
    window, exact end to end."""
    m = _rand_mod(bits)
    ctx = mm.MXUBarrett(m)
    xv = [secrets.randbits(bits) % m for _ in range(3)] + [m - 1]
    x = _limbs(xv, ctx)
    if ladder == "powmod":
        ev = [secrets.randbits(24) for _ in xv]
        ebits = jnp.asarray(np.stack(
            [[(e >> i) & 1 for i in range(24)] for e in ev]
        ).astype(np.int32))
        out = ctx.powmod(x, ebits)
    else:
        e = secrets.randbits(24) | 1 << 23
        ev = [e] * len(xv)
        out = ctx.powmod_const_exp(x, e)
    assert _ints(out, ctx) == [pow(v, e, m) for v, e in zip(xv, ev)]
