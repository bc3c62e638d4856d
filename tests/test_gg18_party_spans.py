"""BatchedECDSASigningParty's device-phase spans, with the engine's round
programs stubbed out (no GG18 compile): every handler opens and closes one
``phase:gg18_*`` span, a child of the ``round:`` span of the message that
completed its round, with the attributes ``batch``, ``n``, ``q`` and
``cohort`` (and ``pairs`` on the two MtA phases), with every node of the
committee signing and with node0 out (two signers of three: PR 45); the
table of phase names is exactly what the handlers emit; the node
registry gets the phase histogram and the count of MtA responses; and the
wire blocks have the widths the receiving side parses."""
import numpy as np
import pytest

from mpcium_tpu.core import bignum as bn
from mpcium_tpu.engine import gg18_batch as gb
from mpcium_tpu.protocol.ecdsa import batch_signing as bs
from mpcium_tpu.utils import tracing
from mpcium_tpu.utils.metrics import MetricsRegistry

IDS = ["node0", "node1", "node2"]
B = 4
SID = "bsign:b-1"


class _Pmx:
    prof_n = bn.LimbProfile(bits=7, n_limbs=4)
    prof_n2 = bn.LimbProfile(bits=7, n_limbs=8)


class _Ctx:
    """A party's context as far as the party's host code reads it."""

    pmx = _Pmx()

    def __init__(self, pid, *_a, **_k):
        self.pid = pid
        self.ctx_nt = type("nt", (), {"prof": bn.LimbProfile(7, 5)})()

    @classmethod
    def public(cls, pid, *_a, **_k):
        return cls(pid)

    def name_ring_combs(self, h1_bits, h2_bits):
        self.comb_bits = (h1_bits, h2_bits)


class _Mta:
    p_s1 = bn.LimbProfile(7, 3)
    p_s2 = bn.LimbProfile(7, 6)
    p_t1 = bn.LimbProfile(7, 7)

    def __init__(self, alice, bob, dom):
        self.alice, self.bob = alice, bob

    ring_comb_bits = staticmethod(gb.MtaBatch.ring_comb_bits)

    def alice_raw(self, B, rng):
        return {}

    bob_raw = alice_raw


def _z(*shape):
    return np.zeros(shape, np.uint8)


def _w(prof):
    return _z(B, gb.wire_bytes(prof))


@pytest.fixture()
def stubbed(monkeypatch):
    """The round programs as functions of their shapes alone."""
    calls = []

    def prog(name, fn):
        def stub(*a, **k):
            calls.append(name)
            return fn(*a, **k)
        monkeypatch.setattr(gb, name, stub)

    ok = np.ones((B,), bool)
    pt = object()
    prog("gg18_setup", lambda pub, C, digests, x_bits, lam_bits: (
        pt, (pt,) * len(x_bits), (_z(B, 33),) * len(x_bits), ok, 0))
    prog("gg18_r1_commit", lambda own, *a: {
        "k": 0, "gamma": 0, "Gamma": pt, "Gamma_comp": _z(B, 33),
        "commit": _z(B, 32), "kp": 0, "c_k": 0, "ck": _w(own.pmx.prof_n2)})
    prog("gg18_r1_prove", lambda mta, *a: {
        "z": _w(mta.bob.ctx_nt.prof), "u": _w(mta.alice.pmx.prof_n2),
        "w": _w(mta.bob.ctx_nt.prof), "s": _w(mta.alice.pmx.prof_n),
        "s1": _w(mta.p_s1), "s2": _w(mta.p_s2)})
    prog("gg18_r2_verify", lambda mta, ok_, *a: (0, ok_, None, (0, 0, 0)))

    def respond(mta, *a):
        A = mta.alice
        nt, n2 = A.ctx_nt.prof, A.pmx.prof_n2

        def two(prof):  # 2·B rows: the γ leg, then the w leg
            return _z(2 * B, gb.wire_bytes(prof))

        out = {"cb": two(n2), "z": two(nt), "zp": two(nt), "t": two(nt),
               "v": two(n2), "w": two(nt), "s": two(A.pmx.prof_n),
               "s1": two(mta.p_s1), "s2": two(mta.p_s2), "t1": two(mta.p_t1),
               "t2": two(mta.p_s2)}
        return out, _z(B, 33), (0, 0)

    prog("gg18_r2_respond", respond)

    def verify3(mta, ok_, c_k, rs, U, rho_bits, *a):
        assert all(v.shape[0] == 2 * B for v in rs.values())
        assert U.shape == (B, 33) and rho_bits.shape == (2 * B, 128)
        return ok_, None, (0, 0), (0, 0, 0, 0)

    prog("gg18_r3_verify", verify3)
    prog("gg18_r3_delta", lambda *a: (0, 0, _z(B, 32)))
    prog("gg18_r4_pok", lambda *a: (_z(B, 33), _z(B, 32)))
    prog("gg18_r5a_verify", lambda ok_, *a: (ok_, 0, pt))
    prog("gg18_r5a_commit", lambda ok_, *a: {
        "ok": ok_, "R": pt, "r": 0, "rec": 0, "li": 0, "rho": 0, "ka": 0,
        "kb": 0, "s": 0, "V": pt, "A": pt, "vc": _z(B, 33),
        "ac": _z(B, 33), "commit": _z(B, 32)})
    prog("gg18_r5b", lambda *a: (_z(B, 33), _z(B, 32), _z(B, 32)))
    prog("gg18_r5c_verify", lambda ok_, *a: (ok_, pt, pt))
    prog("gg18_r5c_commit", lambda *a: {
        "U": pt, "T": pt, "uc": _z(B, 33), "tc": _z(B, 33),
        "commit": _z(B, 32)})
    prog("gg18_r5e", lambda ok_, *a: (ok_, _z(B, 32)))
    prog("gg18_final", lambda ok_, *a: (
        _z(B, 32), _z(B, 32), np.zeros((B,), np.int32), ok_))
    monkeypatch.setattr(gb, "PartyCtx", _Ctx)
    monkeypatch.setattr(gb, "MtaBatch", _Mta)
    monkeypatch.setattr(gb, "agg_holds", lambda *a: True)
    return calls


@pytest.fixture()
def spans():
    got = []
    tracing.enable(sink=got.append)
    yield got
    tracing.disable()


def _parties(registries, caches=None, signers=IDS):
    """The parties of ``signers``, each over its share of a key dealt to
    the whole committee ``IDS``."""
    from mpcium_tpu.cluster import load_test_preparams

    shares = gb.dealer_keygen_secp_batch(
        B, IDS, threshold=1, preparams=load_test_preparams(bits=1024))
    digests = [bytes([i]) * 32 for i in range(B)]
    return {
        pid: bs.BatchedECDSASigningParty(
            SID, pid, signers, shares[IDS.index(pid)], digests,
            metrics=registries[pid],
            contexts=caches[pid] if caches else None)
        for pid in signers
    }


def _run_as_a_session_does(parties):
    """Deliver every message inside a ``round:`` span on the receiver's
    track, as node/session.py does."""
    queue = []
    for pid, p in parties.items():
        with tracing.span("round:start", node=pid, tid=SID,
                          trace_id=tracing.trace_id_for(SID)):
            queue.extend(p.start())
    while queue:
        msg = queue.pop(0)
        for pid, p in parties.items():
            if pid == msg.from_id or (msg.to and msg.to != pid):
                continue
            with tracing.span(f"round:{msg.round}", node=pid, tid=SID,
                              trace_id=tracing.trace_id_for(SID),
                              sender=msg.from_id):
                queue.extend(p.receive(msg))


@pytest.mark.parametrize("signers", [IDS, ["node1", "node2"]],
                         ids=["every_node", "node0_out"])
def test_each_handler_has_one_phase_span_under_its_round(
        stubbed, spans, signers):
    q = len(signers)
    registries = {pid: MetricsRegistry() for pid in signers}
    parties = _parties(registries, signers=signers)
    _run_as_a_session_does(parties)
    assert all(p.done and p.result["ok"].all() for p in parties.values())

    by_id = {s["span_id"]: s for s in spans}
    phases = [s for s in spans if s["name"].startswith("phase:")]
    # the table is the set the handlers emit, once a handler and node
    assert {s["name"] for s in phases} == set(bs.PHASE_SPANS)
    assert len(bs.PHASE_SPANS) == len(bs.PHASES) == 10
    for pid in signers:
        mine = [s["name"] for s in phases if s["node"] == pid]
        assert sorted(mine) == sorted(bs.PHASE_SPANS), pid
    assert {s["node"] for s in phases} == set(signers)
    # each under the round span that caused it: the start handler under
    # ``round:start``, a later one under the round whose last message
    # completed the stage before it
    parent_round = {
        "start": "round:start", "_respond": "round:gg18/b/1/",
        "_delta": "round:" + bs.R2, "_decommit_gamma": "round:" + bs.R3,
        "_phase5a": "round:" + bs.R4, "_phase5b": "round:" + bs.R5,
        "_phase5c": "round:" + bs.R6, "_phase5d": "round:" + bs.R7,
        "_partial": "round:" + bs.R8, "_finalize": "round:" + bs.R9,
    }
    for s in phases:
        parent = by_id[s["parent_id"]]
        handler = next(h for h, n in bs.PHASES.items()
                       if s["name"] == f"phase:gg18_{n}")
        assert parent["name"].startswith(parent_round[handler]), s["name"]
        assert parent["node"] == s["node"] and parent["tid"] == s["tid"]
        assert parent["t0_ns"] <= s["t0_ns"] <= s["t1_ns"] <= parent["t1_ns"]
        attrs = dict(s["attrs"])
        # the handler's own CPU seconds (PR 40): never more than the span
        # lasted, give or take one 10 ms step of the thread clock
        assert 0 <= attrs.pop("cpu_s") <= (
            s["t1_ns"] - s["t0_ns"]) / 1e9 + 0.010
        # the party's signers on every phase (PR 45); on the two MtA
        # phases the ordered pairs this signer answers, or verifies
        want = {"batch": "b-1", "n": B, "q": q, "cohort": 0}
        if handler in ("_respond", "_delta"):
            want["pairs"] = q - 1
        assert attrs == want, s["name"]
        assert s["trace_id"] == tracing.trace_id_for(SID)

    # the registry of each node: ten phases observed, and its responses:
    # two secrets a peer and lane
    for pid, reg in registries.items():
        snap = reg.snapshot()
        assert snap["histograms"]["party.ecdsa.phase_s"]["count"] == 10
        assert snap["counters"]["party.ecdsa.mta_responses_total"] == (
            2 * (q - 1) * B)
    # over the signers, a signature: 12 with every node, 4 with node0 out
    assert sum(reg.snapshot()["counters"]["party.ecdsa.mta_responses_total"]
               for reg in registries.values()) // B == 2 * q * (q - 1)

    # every round program ran, the per-peer ones once a peer (and secret)
    per_node = {n: stubbed.count(n) // q
                for names in gb.ROUND_PROGRAMS.values() for n in names}
    assert per_node == {
        "gg18_setup": 1, "gg18_r1_commit": 1, "gg18_r1_prove": q - 1,
        "gg18_r2_verify": q - 1, "gg18_r2_respond": q - 1,
        "gg18_r3_verify": q - 1,
        "gg18_r3_delta": 1, "gg18_r4_pok": 1, "gg18_r5a_verify": 1,
        "gg18_r5a_commit": 1, "gg18_r5b": 1, "gg18_r5c_verify": 1,
        "gg18_r5c_commit": 1, "gg18_r5e": 1, "gg18_final": 1}


def test_untraced_a_party_opens_no_span_and_keeps_its_counters(stubbed):
    assert not tracing.enabled()
    reg = {pid: MetricsRegistry() for pid in IDS}
    parties = _parties(reg)
    _run_as_a_session_does(parties)
    assert all(p.done for p in parties.values())
    assert reg["node0"].snapshot()["histograms"][
        "party.ecdsa.phase_s"]["count"] == 10
    # and without a registry it keeps none
    bare = _parties({pid: None for pid in IDS})
    _run_as_a_session_does(bare)
    assert all(p.done for p in bare.values())


def test_a_failed_combined_check_falls_back_to_the_strict_one(
        stubbed, monkeypatch):
    """The host's verdict on a batch-verified proof gates a strict
    per-lane check whose mask lands in the batch's ok."""
    monkeypatch.setattr(gb, "agg_holds", lambda *a: False)
    bad = np.array([True, False, True, True])
    monkeypatch.setattr(_Mta, "bob_check_alice_strict",
                        lambda self, *a: bad, raising=False)
    monkeypatch.setattr(_Mta, "alice_check_bob_strict",
                        lambda self, *a: np.ones((2 * B,), bool),
                        raising=False)
    parties = _parties({pid: None for pid in IDS})
    _run_as_a_session_does(parties)
    for p in parties.values():
        assert list(p.result["ok"]) == list(bad)


def test_a_nodes_cache_keeps_its_committees_contexts_across_batches(stubbed):
    """A party handed its node's cache builds a context once a committee
    and epoch; a party handed none builds its own and keeps none."""
    built = []
    real = _Ctx.__init__

    def counting(self, pid, *a, **k):
        built.append(pid)
        real(self, pid, *a, **k)

    caches = {pid: bs.ContextCache() for pid in IDS}
    _Ctx.__init__ = counting
    try:
        _parties({pid: None for pid in IDS}, caches)
        first = len(built)
        _parties({pid: None for pid in IDS}, caches)
        again = len(built)
        _parties({pid: None for pid in IDS})
    finally:
        _Ctx.__init__ = real
    # a node's cache: its private context and a public one a peer
    assert first == len(IDS) * len(IDS)
    assert again == first          # the second batch built none
    assert len(built) == 2 * first  # without a cache, all of them again
    assert all(len(c) == len(IDS) for c in caches.values())
    caches["node0"].clear()
    assert len(caches["node0"]) == 0
    # with node0 out a live node asks for the two live parties' only
    live = ["node1", "node2"]
    fresh = {pid: bs.ContextCache() for pid in live}
    built.clear()
    _Ctx.__init__ = counting
    try:
        _parties({pid: None for pid in live}, fresh, signers=live)
    finally:
        _Ctx.__init__ = real
    assert sorted(built) == ["node1", "node1", "node2", "node2"]
    assert all(len(c) == 2 for c in fresh.values())


def test_a_cached_context_ages_out_and_the_cap_holds():
    now = [0.0]
    cache = bs.ContextCache(clock=lambda: now[0])
    made = []

    def build(tag):
        def b():
            made.append(tag)
            return object()
        return b

    a = cache.get(("private", "n0", "d", 0), build("a"))
    assert cache.get(("private", "n0", "d", 0), build("a2")) is a
    # another epoch of the same committee is another entry
    assert cache.get(("private", "n0", "d", 1), build("b")) is not a
    now[0] = bs.ContextCache.MAX_AGE_S + 1
    assert cache.get(("private", "n0", "d", 0), build("a3")) is not a
    assert made == ["a", "b", "a3"]
    for i in range(bs.ContextCache.CAP + 4):
        cache.get(("public", f"p{i}", "d", 0), build(i))
    assert len(cache) == bs.ContextCache.CAP


def test_the_cache_counts_hits_misses_and_builds():
    """With the node's registry: a miss and one ``context_build_s``
    observation a context built (an expired one is built, and counted,
    again), a hit a context found; with no registry nothing is counted."""
    from mpcium_tpu.utils.metrics import MetricsRegistry

    now = [0.0]
    reg = MetricsRegistry()
    cache = bs.ContextCache(clock=lambda: now[0], metrics=reg)

    def read():
        snap = reg.snapshot()
        return (snap["counters"]["party.ecdsa.context_misses_total"],
                snap["counters"]["party.ecdsa.context_hits_total"],
                snap["histograms"]["party.ecdsa.context_build_s"]["count"])

    key = ("private", "n0", "d", 0)
    a = cache.get(key, object)
    assert read() == (1, 0, 1)
    assert cache.get(key, object) is a and cache.get(key, object) is a
    assert read() == (1, 2, 1)
    cache.get(("public", "n1", "d", 0), object)
    assert read() == (2, 2, 2)
    now[0] = bs.ContextCache.MAX_AGE_S + 1
    assert cache.get(key, object) is not a
    assert read() == (3, 2, 3)
    assert cache.get(key, object) is not a
    assert read() == (3, 3, 3)
    quiet = bs.ContextCache(clock=lambda: now[0])
    assert quiet.get(key, object) is quiet.get(key, object)
    assert read() == (3, 3, 3)
