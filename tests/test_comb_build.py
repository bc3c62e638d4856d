"""A fixed-base comb table is built on the device (ops.modmul
``_k_comb_rows``); the reference is the host loop the package used to
run, kept here: one big-int product a row, then numpy's unpacking."""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpcium_tpu.ops import modmul as mm


def host_comb_table(modulus: int, base: int, nw: int, prof) -> np.ndarray:
    """tbl[i, w] = base^(w·2^(COMB_W·i)) mod m as (nw, 2^COMB_W, n_limbs)
    int32 limbs: ``MXUBarrett._comb_table`` as it was before the device
    built it."""
    rows = 1 << mm.COMB_W
    vals = []
    b_i = base % modulus
    for _ in range(nw):
        acc = 1
        for _w in range(rows):
            vals.append(acc)
            acc = acc * b_i % modulus
        b_i = pow(b_i, rows, modulus)
    return mm.ints_to_limbs(vals, prof).reshape(nw, rows, prof.n_limbs)


_CTX = {}


def _ctx(bits: int) -> mm.MXUBarrett:
    """One odd modulus of exactly ``bits`` bits a width, from a fixed seed
    (a case names its inputs: a failure can be run again)."""
    if bits not in _CTX:
        rng = random.Random(bits)
        _CTX[bits] = mm.MXUBarrett(rng.getrandbits(bits) | 1 << (bits - 1) | 1)
    return _CTX[bits]


BASES = {
    "one": lambda m: 1,
    "two": lambda m: 2,
    "m-1": lambda m: m - 1,
    "random": lambda m: random.Random(m).randrange(3, m - 1),
}


# 37 windows: no multiple of a lane count, so the last rows of the chunk
# are padding the table must not show
@pytest.mark.parametrize("base", sorted(BASES))
@pytest.mark.parametrize("nw", [1, 32, 37])
@pytest.mark.parametrize("bits", [1024, 2047, 2048, 4095, 4096])
def test_device_built_table_is_the_host_table(bits, nw, base):
    ctx = _ctx(bits)
    b = BASES[base](ctx.modulus)
    got = ctx._comb_table(b, nw)
    ref = host_comb_table(ctx.modulus, b, nw, ctx.prof)
    assert got.shape == ref.shape and got.dtype == ref.dtype
    np.testing.assert_array_equal(np.asarray(got), ref)


def test_a_table_wider_than_the_lanes_is_built_in_chunks():
    """More windows than the widest build program takes: two chunks, one
    table, and the base beyond the modulus reduced first."""
    ctx = _ctx(1024)
    nw = mm._COMB_LANES[-1] + 3
    b = ctx.modulus + 5
    np.testing.assert_array_equal(
        np.asarray(ctx._comb_table(b, nw)),
        host_comb_table(ctx.modulus, b, nw, ctx.prof),
    )


def test_named_combs_grow_and_are_kept():
    """``name_comb`` rebuilds only to grow; a context's tree then holds the
    wider table under the same name."""
    ctx = mm.MXUBarrett(_ctx(1024).modulus)
    ctx.name_comb("g", 7, 16)
    first = ctx._named["g"]
    ctx.name_comb("g", 7, 9)
    assert ctx._named["g"] is first and first.shape[0] == 2
    ctx.name_comb("g", 7, 40)
    assert ctx._named["g"].shape == (5, 1 << mm.COMB_W, ctx.prof.n_limbs)
    np.testing.assert_array_equal(
        np.asarray(ctx._named["g"]),
        host_comb_table(ctx.modulus, 7, 5, ctx.prof),
    )
    names = jax.tree_util.tree_structure(ctx).node_data()[1][2]
    assert names == ("g",)


def _avals(tree):
    return [(x.shape, x.dtype) for x in jax.tree.leaves(tree)]


def test_a_party_context_built_twice_is_one_argument_shape():
    """The round programs take a party's context as an argument: two
    builds from the same material, and one whose combs are the host's
    tables (what every build gave before), have one tree structure and
    the same avals, so a program traced for one serves the others."""
    from mpcium_tpu.cluster import load_test_preparams
    from mpcium_tpu.engine import gg18_batch as gb

    pre = load_test_preparams(bits=1024)
    pid = sorted(pre)[0]
    bits = gb.MtaBatch.ring_comb_bits(gb.Domains(), pre[pid].NTilde.bit_length())

    def build():
        ctx = gb.PartyCtx(pid, pre[pid])
        ctx.name_ring_combs(*bits)
        return ctx

    first, second = build(), build()
    before = build()
    for ctx, bases in (
        (before.ctx_nt, {"h1": before.h1, "h2": before.h2}),
        (before.pmx.ctx_N, {"y": before.pmx.y}),
        (before.pmx.ctx_N2, {"h": before.pmx.h}),
    ):
        for name, base in bases.items():
            nw = ctx._named[name].shape[0]
            ref = host_comb_table(ctx.modulus, base, nw, ctx.prof)
            np.testing.assert_array_equal(np.asarray(ctx._named[name]), ref)
            ctx._named[name] = jnp.asarray(ref)
    structure = jax.tree.structure(first)
    assert jax.tree.structure(second) == structure
    assert jax.tree.structure(before) == structure
    assert _avals(first) == _avals(second) == _avals(before)

    traced = []

    @jax.jit
    def program(ctx, ebits):
        traced.append(1)
        return ctx.ctx_nt.powmod_named_base("h1", ebits)

    ebits = jnp.ones((2, 16), jnp.int32)
    outs = [program(ctx, ebits) for ctx in (first, second, before)]
    assert len(traced) == 1
    want = pow(first.h1, (1 << 16) - 1, first.NTilde)
    for out in outs:
        got = mm.bn.batch_from_limbs(np.asarray(out), first.ctx_nt.prof)
        assert got == [want, want]
