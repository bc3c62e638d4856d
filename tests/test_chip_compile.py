"""The main path's kernels compile for the chip — no chip attached.

The TPU compiler is installed in the sandbox and compiles for a DESCRIBED
v5e (`jax.experimental.topologies`), so a kernel the chip's compiler would
refuse fails here, at no chip time. Nothing runs: these cases say nothing
about results or speed, only that the served Ed25519 path's five party
kernels (at the width chip_smoke.py's cohorts present them), the fused
Pallas mulmod, the secp256k1 ladder and the device hash cores still lower
and compile for the real target.

Rules this file keeps (only one process may load the TPU library): the
topology is described inside a module-scoped fixture that skips where it
cannot be described — never at import, never in a skipif/parametrize
argument, not autouse, not in conftest.py — and every compile happens in
the test's own process. All cases live in this ONE file so xdist's
loadfile distribution hands them to one worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest

# the smoke's wave is 1024 signs split into K=2 cohorts: the engine sees
# 512; every ready participant signs, so a healthy 2-of-3 cluster has q=3
ED_B, ED_Q = 512, 3
WIDE_B = 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next run warns and
    recompiles) — keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ed_nonce(s):
    from mpcium_tpu.engine import eddsa_batch as eb

    return eb.nonce_commitments, (_sds((ED_B, 64), jnp.uint8, s),), {}


def _ed_aggregate(s):
    from mpcium_tpu.engine import eddsa_batch as eb

    return eb.aggregate_nonce, (_sds((ED_Q, ED_B, 32), jnp.uint8, s),), {}


def _ed_partial(s):
    from mpcium_tpu.engine import eddsa_batch as eb

    return eb.partial_signature, (
        _sds((ED_B, 22), jnp.int32, s),
        _sds((ED_B, 64), jnp.uint8, s),
        _sds((ED_B, 22), jnp.int32, s),
    ), {}


def _ed_combine(s):
    from mpcium_tpu.engine import eddsa_batch as eb

    return eb.combine_signatures, (
        _sds((ED_Q, ED_B, 22), jnp.int32, s),
        _sds((ED_B, 32), jnp.uint8, s),
    ), {}


def _ed_verify(s):
    from mpcium_tpu.engine import eddsa_batch as eb

    return eb.verify_signatures, (
        _sds((ED_B, 64), jnp.uint8, s),
        _sds((ED_B, 32), jnp.uint8, s),
        _sds((ED_B, 64), jnp.uint8, s),
    ), {}


def _pallas_mulmod(bits):
    def build(s):
        from mpcium_tpu.ops import modmul as mm
        from mpcium_tpu.ops import pallas_mulmod as pmm

        # a fixed odd full-width modulus: only shapes reach the compiler
        ctx = mm.MXUBarrett((1 << bits) - 159)
        n = ctx.prof.n_limbs
        consts = pmm._consts_for(ctx._T_mu, ctx._T_m, ctx._comp, ctx.occ, n)
        return pmm._mulmod_call, (
            _sds((WIDE_B, n), jnp.int32, s),
            _sds((WIDE_B, n), jnp.int32, s),
            *(_sds(c.shape, c.dtype, s) for c in consts),
        ), dict(occ=ctx.occ, n=n, tb=pmm._pick_tile(WIDE_B), interpret=False)

    return build


def _secp_point(s):
    from mpcium_tpu.core import secp256k1_jax as sp
    from mpcium_tpu.core.bignum import P256

    c = _sds((WIDE_B, P256.n_limbs), jnp.int32, s)
    return sp.SecpPointJ(c, c, c)


def _secp_scalar_mul(s):
    from mpcium_tpu.core import secp256k1_jax as sp

    bits = _sds((WIDE_B, sp.SCALAR_BITS), jnp.int32, s)
    return jax.jit(sp.scalar_mul), (bits, _secp_point(s)), {}


def _secp_base_mul(s):
    from mpcium_tpu.core import secp256k1_jax as sp

    bits = _sds((WIDE_B, sp.SCALAR_BITS), jnp.int32, s)
    return jax.jit(sp.base_mul), (bits,), {}


def _sha256(s):
    from mpcium_tpu.ops import hash_suite as hs

    # the commitment rows: 23-byte prefix + 32-byte blind + 32-byte point
    return hs.sha256_fixed, (_sds((WIDE_B, 87), jnp.uint8, s),), dict(
        msg_len=87)


def _sha512(s):
    from mpcium_tpu.ops import hash_suite as hs

    # the RFC 8032 challenge rows: R ‖ A ‖ 32-byte digest
    return hs.sha512_fixed, (_sds((ED_B, 96), jnp.uint8, s),), dict(
        msg_len=96)


def _sha512_masked(blocks):
    def build(s):
        from mpcium_tpu.ops import hash_suite as hs

        # the challenge rows of the served party: R ‖ A ‖ the raw message
        # zero-filled to a rung's width, lengths as data (1 block: the
        # 32-byte digests; 16: Solana messages up to the packet's limit)
        cap = blocks * 128 - 17
        assert hs.sha512_rung_cap(cap) == cap
        return hs.sha512_masked, (
            _sds((ED_B, cap), jnp.uint8, s), _sds((ED_B,), jnp.int32, s)), {}

    return build


CASES = {
    "ed25519.nonce_commitments": (_ed_nonce, None),
    "ed25519.aggregate_nonce": (_ed_aggregate, None),
    "ed25519.partial_signature": (_ed_partial, None),
    "ed25519.combine_signatures": (_ed_combine, None),
    "ed25519.verify_signatures": (_ed_verify, None),
    "pallas_mulmod.2048": (_pallas_mulmod(2048), "tpu_custom_call"),
    "pallas_mulmod.4096": (_pallas_mulmod(4096), "tpu_custom_call"),
    "secp256k1.scalar_mul": (_secp_scalar_mul, None),
    "secp256k1.base_mul": (_secp_base_mul, None),
    "hash.sha256": (_sha256, None),
    "hash.sha512": (_sha512, None),
    "hash.sha512_masked.1": (_sha512_masked(1), None),
    "hash.sha512_masked.16": (_sha512_masked(16), None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, one_chip, no_persistent_cache):
    build, must_contain = CASES[case]
    fn, args, static = build(one_chip)
    compiled = fn.lower(*args, **static).compile()
    assert compiled is not None
    if must_contain:
        assert must_contain in compiled.as_text()
