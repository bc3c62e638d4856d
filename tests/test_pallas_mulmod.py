"""Bit-exactness of the fused Pallas mulmod kernel (ops/pallas_mulmod.py)
against python-int ground truth, in interpreter mode on CPU (the Mosaic
lowering itself is gated on the real chip by .scratch/chipcheck.py).

Covers the widths the GG18 engine dispatches (2048-bit Paillier moduli,
4096-bit Paillier-squared / NTilde domains), a small curve-order width,
edge values (0, 1, m-1), squaring, broadcasting, and the powmod scan
path with the module-level MPCIUM_MULMOD=pallas dispatch.
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
