"""Fused Pallas TPU kernel for batched mulmod (the GG18 hot op).

The XLA band-GEMM path (`ops.modmul._reduce_impl` over `mul_pair`)
materializes the Toeplitz band (~78 MB bf16 at B=1024/4096-bit) and the
block products (~93 MB f32) in HBM between fusions — PERFORMANCE.md
"kernel gaps" #1 puts the resulting traffic floor at ~0.25-0.35 ms out
of the 1.82 ms that mulmod measured at B=1024. This kernel keeps the
ENTIRE mulmod — pairwise product, carry normalization, both Barrett
constant legs, and the trailing conditional subtractions — inside one
`pallas_call`, so per batch-tile the only HBM traffic is x, y in and the
result out (~0.9 MB per 128 rows at 4096-bit; at B=1024 ~7 MB against
the band path's ~170 MB).

Design notes (why it looks nothing like a GPU bignum kernel):

* **The pairwise product cannot ride the MXU.** A batched x·y product
  needs a per-element operand matrix (the Toeplitz band of y_b differs
  for every b), and the systolic array only amortizes SHARED operands.
  Instead the product runs on the VPU as a shift-and-FMA convolution in
  f32 — exact, because 7-bit limbs give partial products ≤ 127² and any
  convolution column sums ≤ `occ` of them: occ·127² < 2²⁴ for moduli up
  to ~7280 bits (the same exactness budget `ops.modmul.mul_const` uses).
  Eight phase accumulators S_r (r = 0..7) turn 1-lane shifts into one
  8-lane shift per 8 FMA sweeps:
      conv = Σ_r shift_r(S_r),   S_r = Σ_q shift_{8q}(x) · y[8q+r]
* **The product sweeps only the lanes that are live.** Limb group q
  (limbs 8q..8q+7 of y) multiplies x shifted up 8q lanes: n live lanes
  of a frame of 2n. With q = 16a + c the shift is 128a + 8c: the sixteen
  shifts by 8c are made once a tile (`xs_ref[c]`, n + 120 lanes rounded
  up to whole 128-lane blocks), and the 128a is where the sweep lands in
  the accumulators (`acc_ref`, VMEM scratch), a 128-aligned offset
  Mosaic takes dynamically. So a sweep is `roundup(n + 120, 128)` lanes
  wide whatever the frame, and x is never shifted inside the loop. What
  an FMA costs is its accumulator's trip through VMEM (nine (tb, frame)
  f32 arrays never fit the 64 vregs: a load and a store a vreg), so a
  pass takes `_GROUPS_PER_PASS` groups of one block, sums their
  products and adds the sum to the window once. What is left is mostly
  the lane-broadcast of each limb of y (about two FMA-vregs' worth, one
  a limb whatever the order: measured, PERF.md §6 PR 34). Every width
  follows from `occ` and `n`, which are static.
* **The Barrett legs DO ride the MXU.** µ and m are shared across the
  batch, so `q1 @ T_µ` and `q3 @ T_m` are plain 2D bf16 matmuls with f32
  accumulation (bit-exact below 2²⁴), issued from inside the kernel on
  VMEM-resident constant tiles that persist across grid steps.
* **Carries are lane-axis passes.** Three shift-and-add roll passes bound
  limbs ≤ 135, then a Hillis–Steele doubling pass over the
  generate/propagate semiring replaces `lax.associative_scan` (which
  Mosaic does not lower). All shifts are static `jnp.concatenate` slices
  — no `pltpu.roll` — so the kernel also runs under `interpret=True` for
  CPU-exactness tests.

Same reduction algebra as `ops.modmul._reduce_impl` (HAC Alg. 14.42);
bit-for-bit equality against `core.bignum` host ints and against the
band path is property-tested in tests/test_pallas_mulmod.py. It is the
chip's default (`ops.modmul._impl`: the fused kernel on a TPU, the band
path elsewhere, where this would run interpreted; MPCIUM_MULMOD
overrides). A squaring is the product of a value with itself: a
triangular product of its own (each cross term once, doubled) was
written and measured on the chip in PR 34 and taken out again, because
what is left of the product is the lane-broadcast of y's limbs, which it
does not halve (PERF.md §6). Reference correspondence: this executes the
tss-lib Paillier/MtA arithmetic the reference delegates to
(SURVEY.md §2.3); the leading axis is the concurrent-session batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LIMB_BITS = 7
MASK = (1 << LIMB_BITS) - 1

# the product's geometry: a limb group is one FMA sweep a phase, and
# sixteen groups' shifts (8 lanes each) make one 128-lane block
_GROUP = 8
_GROUPS_PER_BLOCK = 128 // _GROUP
# groups a pass: their products are summed before the sum is added to the
# accumulators' window, so a window goes through VMEM once a pass
_GROUPS_PER_PASS = 4


def _roundup(v: int, m: int) -> int:
    return -(-v // m) * m


def _shift_up(x: jnp.ndarray, k: int, fill: int = 0):
    """shift limbs toward HIGHER lane index by k (value · R^k), static k.
    (`lax` primitives, here and in the loops below, not their `jnp`
    wrappers: a kernel body is thousands of such calls, traced anew for
    every shape of every program, and a wrapper costs several times its
    primitive to trace. The operations are the same.)"""
    if k == 0:
        return x
    axis = x.ndim - 1
    pad = lax.full(x.shape[:-1] + (k,), fill, x.dtype)
    return lax.concatenate(
        [pad, lax.slice_in_dim(x, 0, x.shape[-1] - k, axis=axis)], axis)


def _carry_int(v: jnp.ndarray) -> jnp.ndarray:
    """Exact carry normalization along the lane axis — the in-kernel
    port of `ops.modmul.carry` (3 roll passes then carry-lookahead; input
    contract limb < 127·2²¹). The lookahead runs as a Hillis–Steele
    doubling over the (generate, propagate) semiring: identity shifts in
    g=0 / p=1."""
    low = functools.partial(lax.bitwise_and, jnp.int32(MASK))
    high = lambda u: lax.shift_right_arithmetic(u, jnp.int32(LIMB_BITS))
    for _ in range(3):
        v = lax.add(low(v), _shift_up(high(v), 1))
    g = high(v)  # 0/1 after the roll passes
    r = low(v)
    p = lax.convert_element_type(lax.eq(r, jnp.int32(MASK)), jnp.int32)
    d = 1
    n = v.shape[-1]
    while d < n:
        gs = _shift_up(g, d, fill=0)
        ps = _shift_up(p, d, fill=1)
        g = lax.bitwise_or(g, lax.bitwise_and(p, gs))
        p = lax.bitwise_and(p, ps)
        d *= 2
    return low(lax.add(r, _shift_up(g, 1)))


def _product_widths(occ: int, n: int, frame: int):
    """(limb groups, padded to whole passes; width of a shifted copy of x;
    width of a phase accumulator): widths in lanes, whole blocks."""
    nq = _roundup(-(-occ // _GROUP), _GROUPS_PER_PASS)
    wx = _roundup(n + _GROUP * (_GROUPS_PER_BLOCK - 1), 128)
    blocks = -(-nq // _GROUPS_PER_BLOCK)
    return nq, wx, max(frame, 128 * (blocks - 1) + wx)


def _product(x_ref, y2_ref, xs_ref, acc_ref, *, occ: int, n: int, n_pad: int,
             frame: int):
    """Stage 1: the convolution of x with y as f32 column sums over
    ``frame`` lanes. See the module docstring for the layout."""
    f32 = jnp.float32
    gpb, per = _GROUPS_PER_BLOCK, _GROUPS_PER_PASS
    nq, wx, _ = _product_widths(occ, n, frame)
    x = x_ref[:].astype(f32)
    x = (jnp.pad(x, ((0, 0), (0, wx - n_pad))) if wx > n_pad
         else x[:, :wx])
    for c in range(gpb):
        xs_ref[c] = _shift_up(x, _GROUP * c)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)
    tb = x.shape[0]

    def one_pass(i, carry):
        """Groups q0 .. q0 + per - 1 into the accumulators' window."""
        q0 = i * per
        c0 = q0 % gpb
        win = pl.ds(pl.multiple_of(q0 // gpb * 128, 128), wx)
        ys = [y2_ref[q0 + k].astype(f32) for k in range(per)]  # (tb, 8)
        for r in range(_GROUP):
            # limb 8q + r of y along the lanes, for each group of the pass
            yb = [lax.broadcast_in_dim(
                lax.slice_in_dim(y, r, r + 1, axis=1), (tb, wx), (0, 1))
                for y in ys]
            t = lax.mul(xs_ref[c0], yb[0])
            for k in range(1, per):
                t = lax.add(t, lax.mul(xs_ref[c0 + k], yb[k]))
            acc_ref[r, :, win] = lax.add(acc_ref[r, :, win], t)
        return carry

    lax.fori_loop(0, nq // per, one_pass, 0)

    conv = acc_ref[0, :, :frame]
    for r in range(1, _GROUP):
        conv = lax.add(conv, _shift_up(acc_ref[r, :, :frame], r))
    return conv


def _mulmod_kernel(
    x_ref, y2_ref, tmu_ref, tm_ref, comp_ref, out_ref, xs_ref, acc_ref, *,
    occ: int, n: int, n_pad: int, frame: int, l1: int
):
    tb = x_ref.shape[0]
    f32 = jnp.float32

    # ---- stage 1: pairwise product as a VPU shift-FMA convolution -----
    conv = _product(x_ref, y2_ref, xs_ref, acc_ref, occ=occ, n=n,
                    n_pad=n_pad, frame=frame)
    # f32 column sums ≤ occ·127² < 2²⁴ ⇒ exact; normalize in int32
    prod = _carry_int(conv.astype(jnp.int32))  # (tb, frame)

    # ---- stage 2: Barrett reduction (MXU constant legs) ----------------
    # q1 = prod >> (occ-1) limbs over the 2n-limb product window
    q1 = prod[:, occ - 1:occ - 1 + l1]  # (tb, l1)
    q2 = _carry_int(
        jnp.dot(
            q1.astype(jnp.bfloat16), tmu_ref[:],
            preferred_element_type=f32,
        ).astype(jnp.int32)
    )  # (tb, c1)
    q3 = q2[:, occ + 1:]  # (tb, l3)
    # only limbs [0, occ+1) of q3·m are consumed; carries propagate
    # upward, so the Toeplitz is pre-sliced to occ+2 columns
    q3m = _carry_int(
        jnp.dot(
            q3.astype(jnp.bfloat16), tm_ref[:],
            preferred_element_type=f32,
        ).astype(jnp.int32)
    )  # (tb, occ+2)

    # r = x - q3·m over occ+1 limbs via the elementwise radix complement
    # (keeps limbs non-negative for the carry; the spurious R^(occ+1)
    # lands exactly in limb occ+1 and is dropped by the slice)
    one0 = jnp.pad(
        jnp.ones((tb, 1), jnp.int32), ((0, 0), (0, occ + 1))
    )
    t = jnp.pad(
        prod[:, :occ + 1] + (MASK - q3m[:, :occ + 1]),
        ((0, 0), (0, 1)),
    ) + one0
    r1 = _carry_int(t)[:, :occ + 1]

    comp = comp_ref[:]  # (1, occ+2)

    def cond_sub(rr):
        u = _carry_int(jnp.pad(rr, ((0, 0), (0, 1))) + comp)
        ge = (u[:, occ + 1] >= 1)[:, None]
        return jnp.where(ge, u[:, :occ + 1], rr)

    r1 = cond_sub(cond_sub(r1))
    out_ref[:] = jnp.pad(r1[:, :occ], ((0, 0), (0, n_pad - occ)))


@functools.partial(
    jax.jit,
    static_argnames=("occ", "n", "tb", "interpret"),
)
def _mulmod_call(
    x, y, tmu_p, tm_p, comp_p, occ: int, n: int, tb: int, interpret: bool
):
    """Single fused mulmod dispatch. x, y: (B, n) normalized int32 limbs,
    B a multiple of tb. Constants pre-padded by `_consts_for`."""
    b = x.shape[0]
    n_pad = _roundup(n, 128)
    # conv frame: highest nonzero conv lane < 2·occ + 14 (phase shifts);
    # Barrett's q1 window needs lanes < 2n
    frame = _roundup(max(2 * n, 2 * occ + 16), 128)
    l1 = 2 * n - occ + 1
    xp = jnp.pad(x, ((0, 0), (0, n_pad - n)))
    # pre-arrange y as (nq, B, 8): y2[q, b, r] = y[b, 8q+r]. Mosaic only
    # allows dynamic lane-dim offsets it can prove 128-aligned, so the
    # group loop indexes the LEADING dim (dynamic ok) and the 8 per-phase
    # scalars are static lane slices broadcast along the window.
    nq, wx, acc_w = _product_widths(occ, n, frame)  # nq: zero groups too
    ypad = max(0, _GROUP * nq - n)
    y2 = jnp.pad(y, ((0, 0), (0, ypad)))[:, :_GROUP * nq]
    y2 = y2.reshape(b, nq, _GROUP).transpose(1, 0, 2)
    kernel = functools.partial(
        _mulmod_kernel, occ=occ, n=n, n_pad=n_pad, frame=frame, l1=l1,
    )
    # x shifted up 8c lanes, c = 0..15, and the eight phase sums
    scratch = [pltpu.VMEM((_GROUPS_PER_BLOCK, tb, wx), jnp.float32),
               pltpu.VMEM((_GROUP, tb, acc_w), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, n_pad), jnp.int32),
        grid=(b // tb,),
        in_specs=[
            pl.BlockSpec((tb, n_pad), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((nq, tb, _GROUP), lambda i: (0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(tmu_p.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(tm_p.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(comp_p.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tb, n_pad), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
        interpret=interpret,
    )(xp, y2, tmu_p, tm_p, comp_p)
    return out[:, :n]


def _consts_for(T_mu, T_m, comp, occ: int, n: int):
    """Kernel-shaped views of the MXUBarrett operands: T_mu sliced to the
    q1 row count, T_m to the q3 rows × (occ+2) consumed columns, comp as
    a broadcastable row."""
    l1 = 2 * n - occ + 1
    tmu_p = T_mu[:l1]  # (l1, c1)
    c1 = tmu_p.shape[1]
    l3 = c1 - occ - 1
    tm_p = T_m[:l3, :occ + 2]
    comp_p = comp.reshape(1, occ + 2).astype(jnp.int32)
    return tmu_p, tm_p, comp_p


def _pick_tile(b: int) -> int:
    for tb in (64, 32, 16, 8):
        if b % tb == 0:
            return tb
    return 0  # pad to 8 below


def mulmod(a, b, T_mu, T_m, comp, occ: int, n: int, interpret: bool):
    """Fused a·b mod m. a, b: (..., n) normalized int32 limbs. Drop-in
    for `ops.modmul._k_mulmod` given the same context operands."""
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    lead = shape[:-1]
    a2 = jnp.broadcast_to(a, shape).reshape(-1, n)
    b2 = jnp.broadcast_to(b, shape).reshape(-1, n)
    B = a2.shape[0]
    tb = _pick_tile(B)
    if tb == 0:
        bp = _roundup(B, 8)
        a2 = jnp.pad(a2, ((0, bp - B), (0, 0)))
        b2 = jnp.pad(b2, ((0, bp - B), (0, 0)))
        tb = 8
    tmu_p, tm_p, comp_p = _consts_for(T_mu, T_m, comp, occ, n)
    out = _mulmod_call(a2, b2, tmu_p, tm_p, comp_p, occ, n, tb, interpret)
    return out[:B].reshape(lead + (n,))
