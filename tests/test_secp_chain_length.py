"""The length of a curve program's chain of field operations, counted.

On the chip a field operation ends in `lax.scan`s over the limbs that run
one step after another, whatever the lane count: a curve program's time
is its count of those steps (PERF.md §6, PR 36 and PR 44). The count is
static, so a CPU trace reads it: `chain_length` walks a function's jaxpr
and returns the scans it runs in a row and their sequential steps, an
outer scan's body counted once a trip. The secp256k1 figures are
ceilings (PR 44 took the addition from 1,085 steps and an inversion from
103,936); the Ed25519 figures are exact, because PR 44 promised those
programs would not change.
"""
import jax
import jax.numpy as jnp
import pytest

from mpcium_tpu.core import ed25519_jax as ed
from mpcium_tpu.core import secp256k1_jax as sj
from mpcium_tpu.core.fields import secp256k1_field


def _count(jaxpr) -> tuple:
    scans = steps = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            n, s = _count(eqn.params["jaxpr"].jaxpr)
            length = eqn.params["length"]
            # a scan with no scan inside is one call of `length` steps
            scans += length * n if n else 1
            steps += length * s if n else length
            continue
        assert eqn.primitive.name != "while", "a loop whose trips a trace cannot count"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n, s = _count(sub)
            scans, steps = scans + n, steps + s
    return scans, steps


def chain_length(fn, *args) -> tuple:
    """(scan calls, sequential scan steps) `fn(*args)` runs in a row."""
    return _count(jax.make_jaxpr(fn)(*args).jaxpr)


_LANES = 2
_EL = jnp.zeros((_LANES, 22), jnp.int32)
_BITS = jnp.zeros((_LANES, 256), jnp.int32)
_SECP = sj.SecpPointJ(_EL, _EL, _EL)
_ED = ed.EdPointJ(_EL, _EL, _EL, _EL)

SECP_CEILINGS = {
    "add": (lambda: chain_length(sj.add, _SECP, _SECP), 700),
    "inv": (lambda: chain_length(secp256k1_field().inv, _EL), 56_000),
    "compress": (lambda: chain_length(sj.compress, _SECP), 57_000),
    "base_mul": (lambda: chain_length(sj.base_mul, _BITS), 45_000),
    "scalar_mul": (lambda: chain_length(sj.scalar_mul, _BITS, _SECP), 235_000),
}

ED_EXACT = {
    "add": (lambda: chain_length(ed.add, _ED, _ED), (42, 864)),
    "double": (lambda: chain_length(ed.double, _ED), (23, 481)),
    "compress": (lambda: chain_length(ed.compress, _ED), (4620, 99046)),
}


@pytest.mark.parametrize("name", sorted(SECP_CEILINGS))
def test_secp256k1_chain_is_short(name):
    count, ceiling = SECP_CEILINGS[name]
    _, steps = count()
    assert steps <= ceiling, (name, steps)


@pytest.mark.parametrize("name", sorted(ED_EXACT))
def test_ed25519_chain_unchanged(name):
    count, want = ED_EXACT[name]
    assert count() == want, name
