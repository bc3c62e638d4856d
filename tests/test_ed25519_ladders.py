"""The windowed Ed25519 ladders (core/ed25519_jax.py): every result
against hostmath, against RFC 8032, and byte for byte against the
bit-serial ladders they replaced, which live on here as the plain
reference; and their counts of steps, read from the jaxpr."""
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mpcium_tpu.core import bignum as bn
from mpcium_tpu.core import ed25519_jax as ed
from mpcium_tpu.core import hostmath as hm
from mpcium_tpu.core.bignum import P256 as PROF
from mpcium_tpu.core.fields import ed25519_field
from mpcium_tpu.engine import eddsa_batch as eb

SEED = 36


# ---------------------------------------------------------------------------
# the plain reference: the bit-serial ladders as they stood before PR 36
# ---------------------------------------------------------------------------


def ref_add(a, b):
    """add-2008-hwcd-3, one field operation after another."""
    F = ed25519_field()
    d2 = jnp.broadcast_to(
        jnp.asarray(bn.to_limbs(2 * hm.ED_D % hm.ED_P, PROF)), a.T.shape)
    A = F.mul(F.sub(a.Y, a.X), F.sub(b.Y, b.X))
    B = F.mul(F.add(a.Y, a.X), F.add(b.Y, b.X))
    C = F.mul(F.mul(a.T, b.T), d2)
    D = F.mul_small(F.mul(a.Z, b.Z), 2)
    E, Fv, G, H = F.sub(B, A), F.sub(D, C), F.add(D, C), F.add(B, A)
    return ed.EdPointJ(F.mul(E, Fv), F.mul(G, H), F.mul(Fv, G), F.mul(E, H))


def ref_scalar_mul(bits, p):
    """256 steps of select-add and double-by-adding."""

    def step(carry, bit):
        acc, addend = carry
        acc = ed.select(bit > 0, ref_add(acc, addend), acc)
        return (acc, ref_add(addend, addend)), None

    init = (ed.identity(bits.shape[:-1]), p)
    (acc, _), _ = lax.scan(step, init, jnp.moveaxis(bits, -1, 0))
    return acc


@functools.lru_cache(maxsize=None)
def _ref_base_table():
    pts, cur = [], hm.ED_B
    for _ in range(ed.SCALAR_BITS):
        pts.append(cur)
        cur = hm.ed_add(cur, cur)
    return _limbs(pts, z=1)


def _limbs(points, z):
    """Host points → numpy limb arrays of (x·z : y·z : z : x·y·z)."""
    F = ed25519_field()
    xy = [p.affine() for p in points]
    return tuple(
        np.asarray(F.from_ints(v)) for v in (
            [x * z for x, _ in xy], [y * z for _, y in xy],
            [z] * len(xy), [x * y * z for x, y in xy]))


def ref_base_mul(bits):
    """One conditional addition a bit over the table of B·2^i."""

    def step(acc, sl):
        bit, X, Y, Z, T = sl
        tbl = ed.EdPointJ(*(jnp.broadcast_to(c, acc.X.shape) for c in (X, Y, Z, T)))
        return ed.select(bit > 0, ref_add(acc, tbl), acc), None

    acc, _ = lax.scan(
        step, ed.identity(bits.shape[:-1]),
        (jnp.moveaxis(bits, -1, 0),) + tuple(jnp.asarray(c) for c in _ref_base_table()))
    return acc


# ---------------------------------------------------------------------------
# points and scalars
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def small_order_points():
    """A point of order 8 and its multiples of order 4 and 2."""
    y = 2
    while True:
        enc = y.to_bytes(32, "little")
        y += 1
        try:
            t8 = hm.ed_mul(hm.ED_L, hm.ed_decompress(enc))
        except ValueError:
            continue
        t4 = hm.ed_add(t8, t8)
        if not hm.ed_add(t4, t4).equals(hm.ED_IDENT):
            return {"order8": t8, "order4": t4, "order2": hm.ed_add(t4, t4)}


def host_point(name):
    rng = np.random.default_rng(SEED)
    k = int.from_bytes(rng.bytes(32), "little") % hm.ED_L
    named = {
        "identity": hm.ED_IDENT,
        "base": hm.ED_B,
        "random": hm.ed_mul(k, hm.ED_B),
        "random_plus_order8": hm.ed_add(
            hm.ed_mul(k, hm.ED_B), small_order_points()["order8"]),
    }
    return named[name] if name in named else small_order_points()[name]


POINTS = ("random", "base", "identity", "order2", "order4", "order8",
          "random_plus_order8")


def device_points(names):
    """Host points → a batch with Z ≠ 1, so the formulas see a projective
    operand."""
    return ed.EdPointJ(*(
        jnp.asarray(c) for c in _limbs([host_point(n) for n in names], z=0x1234567)))


def on_curve_and_equal(got: hm.EdPoint, want: hm.EdPoint) -> bool:
    """The same point, and T carried right (T·Z = X·Y)."""
    return (got.equals(want)
            and (got.T * got.Z - got.X * got.Y) % hm.ED_P == 0)


SCALARS = {
    "zero": 0,
    "one": 1,
    "l_minus_1": hm.ED_L - 1,
    "above_2_252": 2**252 + 0x5DEECE66D1234567,
    "zero_windows": 0xF00F000000F0 << 100,
    "all_fifteen": 2**256 - 1,
    "random": int.from_bytes(np.random.default_rng(SEED + 1).bytes(32), "little"),
    "alternating": int("a5" * 32, 16),
}
WIDTHS = (8, 16, 253, 256)


# ---------------------------------------------------------------------------
# double, add
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _doubled():
    p = device_points(POINTS)
    return ed.to_host(jax.jit(ed.double)(p)), ed.to_host(jax.jit(ed.add)(p, p))


@pytest.mark.parametrize("name", POINTS)
def test_double_matches_host(name):
    want = hm.ed_add(host_point(name), host_point(name))
    dbl, add_self = (r[POINTS.index(name)] for r in _doubled())
    assert on_curve_and_equal(dbl, want)
    assert on_curve_and_equal(add_self, want)


@pytest.mark.parametrize("other", POINTS)
def test_add_is_complete(other):
    """random + every kind of point, both ways round, identity included."""
    a = device_points(["random_plus_order8"] * len(POINTS))
    b = device_points(POINTS)
    i = POINTS.index(other)
    want = hm.ed_add(host_point("random_plus_order8"), host_point(other))
    assert on_curve_and_equal(ed.to_host(_jit_add()(a, b))[i], want)
    assert on_curve_and_equal(ed.to_host(_jit_add()(b, a))[i], want)


@functools.lru_cache(maxsize=None)
def _jit_add():
    return jax.jit(ed.add)


# ---------------------------------------------------------------------------
# the ladders against hostmath
# ---------------------------------------------------------------------------

LADDER_BASES = ("random", "identity", "order8", "random_plus_order8")


@functools.lru_cache(maxsize=None)
def _ladder_results(width):
    """Every scalar (cut to ``width`` bits) × every base, as one batch a
    program: (base_mul's points, scalar_mul's points), host side."""
    ks = [k % (1 << width) for k in SCALARS.values()]
    bits = jnp.asarray(ed.scalars_to_bits(ks, width))
    fixed = ed.to_host(jax.jit(ed.base_mul)(bits))
    lanes_k = [k for k in ks for _ in LADDER_BASES]
    lanes_p = [b for _ in ks for b in LADDER_BASES]
    var = ed.to_host(jax.jit(ed.scalar_mul)(
        jnp.asarray(ed.scalars_to_bits(lanes_k, width)), device_points(lanes_p)))
    return fixed, var


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("scalar", SCALARS)
def test_base_mul_matches_host(scalar, width):
    k = SCALARS[scalar] % (1 << width)
    got = _ladder_results(width)[0][list(SCALARS).index(scalar)]
    assert on_curve_and_equal(got, hm.ed_mul(k, hm.ED_B))


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("scalar", SCALARS)
def test_scalar_mul_matches_host(scalar, width):
    k = SCALARS[scalar] % (1 << width)
    row = list(SCALARS).index(scalar) * len(LADDER_BASES)
    for j, base in enumerate(LADDER_BASES):
        got = _ladder_results(width)[1][row + j]
        assert on_curve_and_equal(got, hm.ed_mul(k, host_point(base))), base


# ---------------------------------------------------------------------------
# byte for byte against the bit-serial ladders
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _seeded_batch():
    rng = np.random.default_rng(SEED + 2)
    B = 6
    r64 = rng.integers(0, 256, (B, 64), dtype=np.uint8)
    c64 = rng.integers(0, 256, (B, 64), dtype=np.uint8)
    bits = jnp.asarray(rng.integers(0, 2, (B, 256), dtype=np.int32))
    return jnp.asarray(r64), jnp.asarray(c64), bits


def _ref_nonce_commitments(r64):
    r = eb._reduce_wide(r64)
    return r, ed.compress(ref_base_mul(bn.limbs_to_bits(r, PROF, 256)))


def _ref_verify(sig, A_comp, c64):
    R_pt, okR = ed.decompress(sig[..., :32])
    A_pt, okA = ed.decompress(A_comp)
    s = bn.bytes_to_limbs_le(sig[..., 32:], PROF, PROF.n_limbs)
    l_l = jnp.broadcast_to(jnp.asarray(bn.to_limbs(hm.ED_L, PROF)), s.shape)
    c = eb._reduce_wide(c64)
    lhs = ref_base_mul(bn.limbs_to_bits(s, PROF, 256))
    rhs = ref_add(R_pt, ref_scalar_mul(bn.limbs_to_bits(c, PROF, 256), A_pt))
    return ed.equal(lhs, rhs) & okR & okA & (bn.compare(s, l_l) < 0)


def _case_base_mul():
    _, _, bits = _seeded_batch()
    return (ed.compress(jax.jit(ed.base_mul)(bits)),
            ed.compress(jax.jit(ref_base_mul)(bits)))


def _case_scalar_mul():
    _, _, bits = _seeded_batch()
    p = device_points(["random", "random_plus_order8", "order8",
                       "identity", "base", "random"])
    return (ed.compress(jax.jit(ed.scalar_mul)(bits, p)),
            ed.compress(jax.jit(ref_scalar_mul)(bits, p)))


def _case_nonce_commitments():
    r64, _, _ = _seeded_batch()
    r_new, R_new = eb.nonce_commitments(r64)
    r_ref, R_ref = jax.jit(_ref_nonce_commitments)(r64)
    return (jnp.concatenate([bn.limbs_to_bytes_le(r_new, PROF, 32), R_new], -1),
            jnp.concatenate([bn.limbs_to_bytes_le(r_ref, PROF, 32), R_ref], -1))


def _case_verify_signatures():
    sig, A, c64, _ = _verify_batch()
    return eb.verify_signatures(sig, A, c64), jax.jit(_ref_verify)(sig, A, c64)


@pytest.mark.parametrize("program", [
    "base_mul", "scalar_mul", "nonce_commitments", "verify_signatures"])
def test_output_equals_the_bit_serial_ladders(program):
    new, ref = globals()["_case_" + program]()
    assert np.asarray(new).tobytes() == np.asarray(ref).tobytes()


# ---------------------------------------------------------------------------
# verify_signatures against RFC 8032
# ---------------------------------------------------------------------------

# RFC 8032 §7.1, TEST 1-3 and TEST SHA(abc): (public key, message, signature)
RFC8032 = [
    ("d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a", "",
     "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
     "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"),
    ("3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c", "72",
     "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da"
     "085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00"),
    ("fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025", "af82",
     "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3ac"
     "18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a"),
    ("ec172b93ad5e563bf4932c70e1245034c35467ef2efd4d64ebf819683467e2bf",
     "ddaf35a193617abacc417349ae20413112e6fa4e89a97ea20a9eeee64b55d39a"
     "2192992a274fc1a836ba3c23a3feebbd454d4423643ce80e2a9ac94fa54ca49f",
     "dc2a4459e7369633a52b1bf277839a00201009a3efbf3ecb69bea2186c26b589"
     "09351fc9ac90b3ecfdfbc7c66431e0303dca179c138ac17ad9bef1177331a704"),
]


def _not_a_point() -> bytes:
    """A y < p with no x on the curve."""
    y = 2
    while True:
        try:
            hm.ed_decompress(y.to_bytes(32, "little"))
        except ValueError:
            return y.to_bytes(32, "little")
        y += 1


def _flip(b: bytes, bit: int) -> bytes:
    out = bytearray(b)
    out[bit // 8] ^= 1 << (bit % 8)
    return bytes(out)


@functools.lru_cache(maxsize=None)
def _verify_cases():
    """name → (signature, public key, challenge hash, expected verdict)."""
    cases = {}
    for i, (pk, msg, sig) in enumerate(RFC8032):
        pk, msg, sig = bytes.fromhex(pk), bytes.fromhex(msg), bytes.fromhex(sig)
        c = hashlib.sha512(sig[:32] + pk + msg).digest()
        cases[f"rfc8032_vector_{i + 1}"] = (sig, pk, c, True)
    sig, pk, c, _ = cases["rfc8032_vector_2"]
    s = int.from_bytes(sig[32:], "little")
    cases.update({
        "flipped_bit_of_R": (_flip(sig, 13), pk, c, False),
        "flipped_bit_of_s": (_flip(sig, 256 + 77), pk, c, False),
        "flipped_bit_of_A": (sig, _flip(pk, 5), c, False),
        "flipped_bit_of_c": (sig, pk, _flip(c, 300), False),
        "s_plus_l": (sig[:32] + (s + hm.ED_L).to_bytes(32, "little"), pk, c, False),
        "R_not_a_point": (_not_a_point() + sig[32:], pk, c, False),
        "A_not_a_point": (sig, _not_a_point(), c, False),
        "R_y_not_below_p": ((hm.ED_P + 1).to_bytes(32, "little") + sig[32:], pk, c, False),
    })
    return cases


@functools.lru_cache(maxsize=None)
def _verify_batch():
    rows = list(_verify_cases().values())
    as_u8 = lambda k: jnp.asarray(  # noqa: E731
        np.stack([np.frombuffer(r[k], np.uint8) for r in rows]))
    sig, A, c64 = as_u8(0), as_u8(1), as_u8(2)
    return sig, A, c64, np.asarray(eb.verify_signatures(sig, A, c64))


@pytest.mark.parametrize("case", list(_verify_cases()))
def test_verify_signatures_verdict(case):
    i = list(_verify_cases()).index(case)
    assert bool(_verify_batch()[3][i]) is _verify_cases()[case][3]


# ---------------------------------------------------------------------------
# how often the mechanism engages: the ladders' counts, from the jaxpr
# ---------------------------------------------------------------------------


def _loops(jaxpr):
    """(trip count, body) of every loop equation of a jaxpr, calls inlined."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn.params["length"], eqn.params["jaxpr"].jaxpr
        elif "jaxpr" in eqn.params:
            inner = eqn.params["jaxpr"]
            yield from _loops(getattr(inner, "jaxpr", inner))


def ladder_steps(jaxpr):
    """Trip counts of the top-level loops that hold loops themselves: the
    ladders (a field operation's carries are loops with flat bodies)."""
    return [n for n, body in _loops(jaxpr) if any(_loops(body))]


def field_products(jaxpr, times=1):
    """Field multiplications a program RUNS, one after another: products of
    two 22-limb values (43 columns), a stack of them counted once, each
    weighted by the trip counts of the loops around it."""
    total = 0
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "dot_general"
                and eqn.outvars[0].aval.shape[-1] == 2 * PROF.n_limbs - 1):
            total += times
        elif eqn.primitive.name == "scan":
            total += field_products(
                eqn.params["jaxpr"].jaxpr, times * eqn.params["length"])
        elif "jaxpr" in eqn.params:
            inner = eqn.params["jaxpr"]
            total += field_products(getattr(inner, "jaxpr", inner), times)
    return total


def _traced(width, lanes=2):
    bits = jnp.zeros((lanes, width), jnp.int32)
    return (jax.make_jaxpr(ed.base_mul)(bits).jaxpr,
            jax.make_jaxpr(ed.scalar_mul)(bits, ed.identity((lanes,))).jaxpr)


# per point operation: an addition of a cached entry is 2 stacked products,
# a doubling 2, add(a, b) 3 (one more to cache b)
@pytest.mark.parametrize("width,base_steps,var_steps,base_products,var_products", [
    (256, [64], [14, 64], 64 * 2, 1 + 14 * 2 + 1 + 64 * (4 * 2 + 2)),
    (253, [64], [14, 64], 64 * 2, 1 + 14 * 2 + 1 + 64 * (4 * 2 + 2)),
    (32, [8], [14, 8], 8 * 2, 1 + 14 * 2 + 1 + 8 * (4 * 2 + 2)),
    (16, [4], [16], 4 * 2, 16 * (3 + 2)),
    (8, [2], [8], 2 * 2, 8 * (3 + 2)),
])
def test_ladders_run_their_counted_steps(
        width, base_steps, var_steps, base_products, var_products):
    fixed, var = _traced(width)
    assert ladder_steps(fixed) == base_steps
    assert ladder_steps(var) == var_steps
    assert field_products(fixed) == base_products
    assert field_products(var) == var_products


def test_short_scalars_cost_no_more_than_the_bit_serial_ladder():
    """DKG's 8-bit x-coordinates: 8 additions and 8 doublings as before
    PR 36, each now fewer products in a row than the nine it was."""
    bits = jnp.zeros((2, 8), jnp.int32)
    before = jax.make_jaxpr(ref_scalar_mul)(bits, ed.identity((2,))).jaxpr
    assert ladder_steps(before) == [8]
    assert field_products(before) == 8 * 2 * 9
    assert field_products(_traced(8)[1]) <= 8 * 2 * 3


def test_a_window_step_compiles_one_doubling_and_one_addition():
    """The 64-step loop's body holds the doubling once (run four times by
    an inner loop) and the addition once: 4 stacked products as written."""
    _, var = _traced(256)
    (body,) = [b for n, b in _loops(var) if n == 64 and any(_loops(b))]
    written = sum(
        1 for j in _walk(body)
        for e in j.eqns
        if e.primitive.name == "dot_general"
        and e.outvars[0].aval.shape[-1] == 2 * PROF.n_limbs - 1)
    assert written == 4


def _walk(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        inner = eqn.params.get("jaxpr")
        if inner is not None:
            yield from _walk(getattr(inner, "jaxpr", inner))
