"""What decides ``correct``: the control, and each fault the check is
there to catch, on a made-up run (no cluster, no JAX).

The control. The scheme is integer arithmetic, so there is no lower
precision to compute the reference in; the guarantee the configuration
states is that every returned signature verifies under its wallet's key.
The control breaks that guarantee on the benchmark's side (one bit of a
returned signature; a signature under another wallet's key) and the
comparison has to come out as not correct.
"""
import random
from types import SimpleNamespace

import pytest
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
)

from benchmark import harness, reference
from benchmark.served import Request, Wave

TRAFFIC = {"max_failed": 0}


def _sound(n_waves=3, size=4):
    """A served/run pair as a sound run leaves them: OpenSSL signs."""
    rng = random.Random(7)
    keys = [Ed25519PrivateKey.from_private_bytes(rng.randbytes(32))
            for _ in range(size)]
    pubkeys = [k.public_key().public_bytes_raw() for k in keys]
    waves = []
    for wi in range(n_waves):
        reqs = []
        for i in range(size):
            d = rng.randbytes(32)
            reqs.append(Request(
                tx_id=f"t{wi}-{i}", wave=wi, wallet=i, digest=d,
                submit_ns=wi * 100, done_ns=wi * 100 + 50, success=True,
                signature=keys[i].sign(d)))
        waves.append(Wave(index=wi, measured=wi > 0, size=size,
                          t0_ns=wi * 100, submitted_ns=wi * 100 + 1,
                          done_ns=wi * 100 + 50, requests=reqs,
                          batches_fired=1))
    counters = {"scheduler.shed_total": 0.0, "scheduler.fallback_total": 0.0,
                "scheduler.batches_fired_total": float(n_waves)}
    served = SimpleNamespace(
        pubkeys=pubkeys, strays=0, wave_size=size, shape=f"B{size}|q3",
        config={"serving": {"batch_max_batch": size},
                "layout": {"session_axis_devices": 1}},
        counter_total=lambda name: counters[name],
        party_shapes=lambda: [f"B{size}|q3"],
    )
    fill = {"count": n_waves, "min": 1.0, "max": 1.0}
    run = SimpleNamespace(
        waves=waves, measured_waves=[w for w in waves if w.measured],
        measured=[r for w in waves if w.measured for r in w.requests],
        metrics_end={"node0": {"histograms": {
            "scheduler.batch_fill_ratio": fill}}},
    )
    return served, run, counters, fill


def test_a_sound_run_is_correct(capsys):
    served, run, _c, _f = _sound()
    assert harness.check(served, run, TRAFFIC)["correct"] is True
    assert '"invalid_signatures": {"value": 0, "limit": "== 0"' in \
        capsys.readouterr().out  # each number printed beside its limit


def _flip_a_bit(served, run, counters, fill):
    r = run.measured_waves[-1].requests[1]
    sig = bytearray(r.signature)
    sig[40] ^= 0x04
    r.signature = bytes(sig)


def _other_wallets_key(served, run, counters, fill):
    served.pubkeys[0], served.pubkeys[1] = served.pubkeys[1], served.pubkeys[0]


def _truncated_signature(served, run, counters, fill):
    run.measured_waves[0].requests[0].signature = b""


def _no_terminal_outcome(served, run, counters, fill):
    r = run.measured_waves[0].requests[2]
    r.done_ns, r.success = None, False


def _refused_request(served, run, counters, fill):
    r = run.measured_waves[0].requests[2]
    r.success, r.error = False, "verification failed"


def _compiled_in_the_window(served, run, counters, fill):
    run.measured_waves[1].compile_requests = 1


def _second_shape(served, run, counters, fill):
    served.party_shapes = lambda: ["B2|q3", served.shape]


def _partial_manifest(served, run, counters, fill):
    fill["min"] = 0.5
    counters["scheduler.batches_fired_total"] += 1


def _shed(served, run, counters, fill):
    counters["scheduler.shed_total"] = 1.0


def _fell_back(served, run, counters, fill):
    counters["scheduler.fallback_total"] = 2.0


def _stray_result(served, run, counters, fill):
    served.strays = 1


def _unmeasured_wave_failed(served, run, counters, fill):
    run.waves[0].requests[0].success = False


@pytest.mark.parametrize("fault", [
    _flip_a_bit, _other_wallets_key, _truncated_signature,
    _no_terminal_outcome, _refused_request, _compiled_in_the_window,
    _second_shape, _partial_manifest, _shed, _fell_back, _stray_result,
    _unmeasured_wave_failed,
], ids=lambda f: f.__name__.lstrip("_"))
def test_each_fault_comes_out_as_not_correct(fault):
    served, run, counters, fill = _sound()
    fault(served, run, counters, fill)
    assert harness.check(served, run, TRAFFIC)["correct"] is False


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 3_000_000_019])
def test_the_control_fails_the_reference_on_every_seed(seed):
    """One altered bit anywhere in R or s, or the right signature under
    the wrong wallet's key, never verifies."""
    rng = random.Random(seed)
    k = Ed25519PrivateKey.from_private_bytes(rng.randbytes(32))
    other = Ed25519PrivateKey.from_private_bytes(rng.randbytes(32))
    pub = k.public_key().public_bytes_raw()
    msg = rng.randbytes(32)
    sig = k.sign(msg)
    assert reference.verifies(pub, msg, sig)
    for bit in rng.sample(range(512), 24):
        bad = bytearray(sig)
        bad[bit // 8] ^= 1 << (bit % 8)
        assert not reference.verifies(pub, msg, bytes(bad)), bit
    assert not reference.verifies(
        other.public_key().public_bytes_raw(), msg, sig)
    assert not reference.verifies(pub, rng.randbytes(32), sig)


def test_wallets_from_the_seed_are_openssl_keys_shared_t_of_n():
    from benchmark import wallets

    xs = {"node0": 1, "node1": 2, "node2": 3, "node3": 4, "node4": 5}
    pubs, shares = wallets.make_wallets(3, xs, 2, random.Random(99))
    pubs2, shares2 = wallets.make_wallets(3, xs, 2, random.Random(99))
    assert pubs == pubs2 and shares == shares2  # the seed fixes the data
    L = wallets.ED_L

    def lagrange_at_zero(points):
        total = 0
        for xi, yi in points:
            num = den = 1
            for xj, _ in points:
                if xj != xi:
                    num = num * (-xj) % L
                    den = den * (xi - xj) % L
            total = (total + yi * num * pow(den, -1, L)) % L
        return total

    rng = random.Random(99)
    for w in range(3):
        seed32 = rng.randbytes(32)
        for _ in range(2):
            rng.randrange(1, L)  # the polynomial's two coefficients
        secret = wallets._secret_scalar(seed32)
        any3 = [(xs[p], shares[p][w]) for p in ("node4", "node1", "node2")]
        assert lagrange_at_zero(any3) == secret
        two = [(xs[p], shares[p][w]) for p in ("node0", "node3")]
        assert lagrange_at_zero(two) != secret  # t shares do not suffice
        sk = Ed25519PrivateKey.from_private_bytes(seed32)
        assert sk.public_key().public_bytes_raw() == pubs[w]
