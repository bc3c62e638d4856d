"""CPU rehearsal of chip_smoke.py: the same script, a tiny wave.

The steer is test-only (monkeypatch), not an option of the script: the
device check is replaced (the CPU is not a chip), the wave and wallet
count are shrunk, and the script's cache placement is pinned to the
directory conftest.py already uses so later tests keep their cache.
"""
import importlib.util
import json
import os
from pathlib import Path

import jax
import pytest
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PublicKey,
)

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture()
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "WAVE", 8)
    monkeypatch.setattr(mod, "N_WALLETS", 16)
    monkeypatch.setenv(
        "JAX_COMPILATION_CACHE_DIR",
        jax.config.jax_compilation_cache_dir or str(ROOT / ".jax_cache_tests"),
    )
    from mpcium_tpu.perf import compile_watch

    compile_watch.reset()  # shapes other tests of this worker ledgered
    yield mod
    compile_watch.reset()


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def _cpu_as_accelerator(count):
    def fake(min_count=1):
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": count}

    return fake


def test_rehearsal_one_shape_full_batches_closed_books(
    smoke, monkeypatch, capsys
):
    monkeypatch.setattr(smoke, "accelerator", _cpu_as_accelerator(1))
    verified = []
    real_verify = smoke.Served.verify_signatures

    def checking_verify(self):
        # independently of the script: every signature under OpenSSL
        for wid, digest, sig in self.signed:
            Ed25519PublicKey.from_public_bytes(
                self.pubkeys[wid]).verify(sig, digest)
            verified.append(sig)
        return real_verify(self)

    monkeypatch.setattr(smoke.Served, "verify_signatures", checking_verify)
    assert smoke.main([]) == 0
    lines = _lines(capsys)
    last = lines[-1]
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    by_phase = {}
    for ln in lines:
        by_phase.setdefault(ln.get("phase"), []).append(ln)
    waves = by_phase["wave"]
    assert [w["measured"] for w in waves] == [False, True, True, True]
    assert all(w["batches_fired"] == 1 and w["succeeded"] == 8
               and w["compiles"]["requests"] == 0 for w in waves)
    books = by_phase["books"][0]
    assert books["party_eddsa_shapes"] == ["B8|q3"]
    assert books["batch_fill_ratio_min"] == books["batch_fill_ratio_max"] == 1.0
    assert books["submitted"] == books["succeeded"] == 32
    assert books["shed"] == 0 and books["fallbacks"] == 0
    assert by_phase["verify"][0]["signatures"] == 24 == len(verified)
    assert len(set(verified)) == 24


def test_no_accelerator_fails_and_never_says_ok(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    lines = _lines(capsys)
    assert lines and lines[-1].get("ok") is not True
    assert not any(ln.get("ok") is True for ln in lines)


def test_four_chips_rehearsal_on_virtual_devices(
    smoke, monkeypatch, capsys, eight_devices
):
    monkeypatch.setattr(smoke, "accelerator", _cpu_as_accelerator(4))
    # the CPU backend reports no memory statistics
    monkeypatch.setattr(
        smoke, "device_memory",
        lambda dev: {"peak_bytes_in_use": 1, "bytes_in_use": 1},
    )
    assert smoke.main(["--four-chips"]) == 0
    lines = _lines(capsys)
    assert lines[-1] == {"ok": True, "device": lines[-1]["device"]}
    assert lines[-1]["device"]["count"] == 4
    placement = [ln for ln in lines if ln.get("phase") == "placement"][0]
    assert placement["device_set_sizes"] == [4]
    assert placement["unsharded_placements"] == 0
    assert [ln["run"] for ln in lines if ln.get("phase") == "verify"] == [
        "session-axis-4", "one-device"]
    assert any(ln.get("phase") == "compare" for ln in lines)
