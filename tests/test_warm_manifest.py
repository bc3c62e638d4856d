"""Warm-manifest drift gate (mpcium_tpu/warm/manifest.py): the pre-warm
work-list must be a pure, gap-free function of the committed
COMPILE_SURFACE.json — knobs × engine/buckets.BUCKETS over
serving-reachable templates only — keyed by the host/toolchain
fingerprint and ordered hot-shapes-first. Pure stdlib: no jax import.
"""
import json
import sys

import pytest

from mpcium_tpu.engine.buckets import BUCKETS
from mpcium_tpu.perf import envfp
from mpcium_tpu.warm import manifest as wm

pytestmark = pytest.mark.perf


@pytest.fixture(scope="module")
def surface():
    return wm.load_default_surface()


@pytest.fixture()
def knobs():
    return wm.default_knobs()


def test_no_jax_needed(surface, knobs):
    """Enumeration must never warm a backend — the daemon builds the
    work-list before deciding whether to compile anything at all."""
    import re

    wm.build_manifest(surface, knobs)
    for mod in ("mpcium_tpu.warm.manifest", "mpcium_tpu.warm"):
        src = open(sys.modules[mod].__file__).read()
        assert not re.search(r"^\s*(import jax|from jax)", src, re.M), mod


def test_enumeration_is_knobs_times_buckets(surface, knobs):
    """The drift gate: every serving template × every knob combination ×
    every pow-2 bucket, nothing more, nothing silently less."""
    man = wm.build_manifest(surface, knobs)
    by_engine = {}
    for e in man["entries"]:
        by_engine[e["engine"]] = by_engine.get(e["engine"], 0) + 1
    nb = len(BUCKETS)
    assert by_engine == {
        "eddsa.sign": nb,            # B × {q}
        "dkg.run": nb * 2,           # B × {q} × {ed25519, secp256k1}
        "gg18.sign": nb * 2,         # B × {q} × {paillier, ot}
        "party.dkg": nb * 2,
        "party.ecdsa": nb,
        "party.reshare": nb * 2,     # B × {q} × key_type × {t_new}
        "reshare.run": nb * 2,       # B × key_type × {t_new}
    }
    assert man["counts"]["entries"] == 12 * nb
    assert man["gaps"] == []


def test_serving_only(surface, knobs):
    """party.eddsa is serving:false on the committed surface (the node
    signs through the batched engine, not the per-party path) — it must
    not burn warm budget."""
    man = wm.build_manifest(surface, knobs)
    assert not any(e["engine"] == "party.eddsa" for e in man["entries"])


def test_every_entry_is_statically_predicted(surface, knobs):
    """Round-trip: every enumerated shape must match its own surface
    template, i.e. a warmed shape can never ledger predicted:false."""
    from mpcium_tpu.analysis.shape.surface import shape_predicted

    for e in wm.manifest_entries(wm.build_manifest(surface, knobs)):
        assert shape_predicted(surface, e.engine, e.shape), e


def test_scheme_and_bucket_filters(surface, knobs):
    man = wm.build_manifest(surface, knobs, schemes=("eddsa",), max_b=8)
    assert {e["engine"] for e in man["entries"]} == {"eddsa.sign"}
    assert sorted(e["B"] for e in man["entries"]) == [1, 2, 4, 8]
    man = wm.build_manifest(surface, knobs, buckets=(2,),
                            schemes=("ecdsa",))
    assert {(e["engine"], e["B"]) for e in man["entries"]} == {
        ("gg18.sign", 2), ("party.ecdsa", 2),
    }


def test_traffic_prioritizes_hot_shapes(surface, knobs):
    traffic = {("eddsa.sign", "B4096|q2"): 10.0,
               ("eddsa.sign", "B64|q2"): 1.0}
    man = wm.build_manifest(surface, knobs, schemes=("eddsa",),
                            traffic=traffic)
    shapes = [e["shape"] for e in man["entries"]]
    assert shapes[0] == "B4096|q2"  # the signature compiled most often
    assert shapes[1] == "B64|q2"    # then the next hottest
    # cold shapes keep the deterministic small-B-first order
    assert shapes[2] == "B1|q2"


def test_traffic_weights_from_ledger(tmp_path, surface, knobs):
    ledger = tmp_path / "COMPILE_LEDGER.json"
    ledger.write_text(json.dumps({"entries": [
        {"engine": "eddsa.sign", "shape": "B2|q2"},
        {"engine": "eddsa.sign", "shape": "B2|q2"},
        {"engine": "eddsa.sign", "shape": "B16|q2"},
        {"engine": 7, "shape": None},  # malformed entries carry no weight
    ]}))
    t = wm.load_traffic(str(ledger))
    assert t == {("eddsa.sign", "B2|q2"): 2.0, ("eddsa.sign", "B16|q2"): 1.0}
    # the work-list's order comes from that ledger alone
    man = wm.build_manifest(surface, knobs, schemes=("eddsa",), traffic=t)
    assert [e["shape"] for e in man["entries"]][:3] == [
        "B2|q2", "B16|q2", "B1|q2"]
    # a missing or malformed file is silently empty — a fresh node has
    # no traffic yet
    assert wm.load_traffic(str(tmp_path / "nope")) == {}
    ledger.write_text("not json")
    assert wm.load_traffic(str(ledger)) == {}


def test_coverage_check_clean_on_committed_surface(surface, knobs):
    assert wm.coverage_check(surface, knobs) == []


def test_coverage_check_flags_empty_knob(surface):
    bad = wm.WarmKnobs(q=(), key_type=("ed25519",),
                       mta_impl=("paillier",), t_new=(1,))
    problems = wm.coverage_check(surface, bad)
    assert problems and any("q" in p for p in problems)


def test_manifest_key_stability_and_invalidation():
    """Same host+toolchain → same key (a restart reuses the cache); a
    jax version bump → loud invalidation with the reason named."""
    a, b = wm.manifest_key(), wm.manifest_key()
    assert a == b
    ok, _reason = wm.key_matches(a, b)
    assert ok
    bumped = dict(a, jax="999.0.0")
    ok, reason = wm.key_matches(bumped, a)
    assert not ok
    assert "jax" in reason and "999.0.0" in reason
    # a missing stored key (pre-warm cache from an older layout) never
    # validates — stale artifacts are skipped, not trusted
    ok, reason = wm.key_matches(None, a)
    assert not ok


def test_envfp_host_fingerprint_stable():
    """ISSUE 13 satellite: same host → same fingerprint, every time —
    the property the cache-dir naming and manifest key both lean on."""
    fp1 = envfp.host_fingerprint()
    fp2 = envfp.host_fingerprint()
    assert fp1 == fp2
    assert len(fp1) == 12 and all(c in "0123456789abcdef" for c in fp1)
    key = wm.manifest_key()
    assert key["host"] == fp1
    assert key["jax"] == envfp.jax_version()


def test_knobs_from_config_follow_threshold():
    from mpcium_tpu.config import AppConfig

    cfg = AppConfig(mpc_threshold=2)
    knobs = wm.knobs_from_config(cfg)
    assert knobs.q == (3,)
    assert knobs.t_new == (2,)


def test_default_knobs_always_include_ot_backend(monkeypatch):
    """ISSUE 16: the OT backend's check kernels must be enumerated (and
    so pre-warmed) no matter which MtA backend the node serves today —
    deduped when the node already serves ot."""
    monkeypatch.delenv("MPCIUM_MTA", raising=False)
    assert wm.default_knobs().mta_impl == ("paillier", "ot")
    monkeypatch.setenv("MPCIUM_MTA", "ot")
    assert wm.default_knobs().mta_impl == ("ot",)


def test_report_basename_is_stable():
    # scripts/prewarm.py, the daemon, and the docs all point here
    assert wm.REPORT_BASENAME == "WARM_MANIFEST.json"
