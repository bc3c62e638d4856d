"""What PR 45 adds to the benchmark: the configuration
``secp-2of3-paillier-degraded`` (``secp-2of3-paillier`` with node0 out and a
served quorum of 2), the traffic file ``gg18-node-down-waves`` of the new
kind ``node_down_warm_waves`` (PR 38's ``node_down_waves``, loaded and not
copied: the very waves ``gg18-waves`` sends, after the nodes the
configuration names have left, with a warm batch of the LIVE nodes first),
the cell ``secp-2of3-paillier-degraded.gg18-node-down-waves`` and eight
per-layer readers that list it alone. Every entry is found BY NAME: nothing
here pins a position in the manifest's lists.

No JAX program runs in tier-1 here. The cell's CPU rehearsal is the slow
tier's, by the scheme file's ``REHEARSAL["slow"]`` (the GG18 programs
compile for tens of minutes on XLA:CPU): the traced one at the end of this
file, the whole batched party at q = 2 on the 1,024-bit fixtures over node1
and node2, through the subprocess wrapper of the other distributed-GG18
suites; a test below holds that tier-1 collects none of it."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest
import test_bench_rehearsal
from conftest import _tier1_cells, run_isolated
from test_bench_cold_sweep import _Recording, _generator, _mix
from test_bench_gg18_readers import (  # noqa: F401 — gg18_trace is a fixture
    RUNS, gg18_trace, make_run, span)
from test_bench_node_down import _RecordingCluster
from test_bench_gg18_rehearsal import _GG18Tracer
from test_bench_rehearsal import (  # noqa: F401 — steer is a fixture
    _manifest, _run, steer)

from benchmark import harness, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CONFIG = "secp-2of3-paillier-degraded"
TWIN = "secp-2of3-paillier"
CELL = CONFIG + ".gg18-node-down-waves"
TWIN_CELL = TWIN + ".gg18-waves"
NEW = ["quorum.gg18_phase_ms_per_wave", "quorum.gg18_mta_responses_per_sign",
       "quorum.gg18_mta_device_share_pct", "quorum.gg18_achieved_gops",
       "quorum.gg18_mxu_roofline_pct", "quorum.gg18_wire_ms_per_wave",
       "quorum.gg18_select_ms_per_wave", "quorum.gg18_loss_detect_ms"]
# a reader that is its sibling's arithmetic, loaded: the sibling's name
SIBLING = {"quorum.gg18_mta_device_share_pct": "gg18.mta_device_share_pct",
           "quorum.gg18_achieved_gops": "gg18.achieved_gops",
           "quorum.gg18_mxu_roofline_pct": "gg18.mxu_roofline_pct",
           "quorum.gg18_wire_ms_per_wave": "gg18.wire_ms_per_wave",
           "quorum.gg18_select_ms_per_wave": "quorum.select_ms_per_wave",
           "quorum.gg18_loss_detect_ms": "registry.loss_detect_ms"}
KIND = "node_down_warm_waves"
SEEDS = [0, 45, 3_000_000_019]  # the last: more than 32 signed bits hold
_INNER = os.environ.get("MPCIUM_BENCH_GG18_NODE_DOWN_INNER")


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(ROOT, CELL)


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as fh:
        return json.load(fh)


# -- the configuration ----------------------------------------------------------

def test_the_configuration_is_secp_2of3_paillier_with_node0_out(cell):
    base, config = _config(TWIN), cell.config
    assert set(config) == set(base)
    assert config["scheme"] == dict(base["scheme"], served_quorum=2)
    layout = dict(config["layout"])
    assert layout.pop("down_nodes") == ["node0"]  # rank 0: the deputy leads
    assert layout == base["layout"]
    for group in ("population", "serving", "reduced"):
        assert config[group] == base[group], group
    assert config["reduced"] == []
    assert config["serving"]["batch_max_batch"] == 16
    assert set(config["guarantees"]) == set(base["guarantees"])
    assert {k for k in base["guarantees"]
            if config["guarantees"][k] != base["guarantees"][k]} == {"t_of_n"}
    t_of_n = config["guarantees"]["t_of_n"]
    for words in ("node0 has resigned", "two ordered MtA pairs", "not read"):
        assert words in t_of_n
    assumed = dict(config["assumed"])
    assert "Resign()" in assumed.pop("down_nodes")
    assert "keeps its batch width" in assumed.pop("batch_max_batch_at_q2")
    assert assumed == base["assumed"]
    assert config["deployment"] != base["deployment"]
    source = config["source"]
    assert len(source) <= 200 and source != base["source"]
    for words in ("Threshold & Nodes", "config.yaml.template", "registry.go",
                  "ecdsa_signing_session.go", "examples/sign"):
        assert words in source
    # the Ed25519 deployment one node down states the same departure
    assert config["assumed"]["down_nodes"] == _config(
        "ed25519-2of3-degraded")["assumed"]["down_nodes"]


def test_the_mix_is_gg18_waves_after_the_nodes_have_left():
    down, plain = _mix("gg18-node-down-waves"), _mix("gg18-waves")
    assert down["kind"] == KIND
    own = {"down_nodes": "layout.down_nodes", "settle_timeout_s": 10.0}
    assert {k: down[k] for k in own} == own
    assert own == {k: _mix("node-down-waves")[k] for k in own}
    # gg18-waves but for its kind and its words: every number is cell 4's,
    # a wave's 60 s among them
    words = {"kind", "who"}
    assert set(down) == set(plain) | set(own)
    assert {k: v for k, v in plain.items() if k not in words} == {
        k: v for k, v in down.items() if k not in words | set(own)}
    assert down["wave_timeout_s"] == plain["wave_timeout_s"] == 60.0
    assert (down["network_internal_code"], down["unmeasured_waves"],
            down["max_failed"]) == ("eth", 1, 0)


class _Party:
    """What the runner and the kind ask of a warm batch's party."""

    done = True

    def __init__(self, ok=True):
        self.result = {"ok": np.array([ok])}

    def start(self):
        return []


class _WarmRecording(_RecordingCluster):
    """``_RecordingCluster`` with what the live nodes' warm batch reads:
    the wallets' names, each node's share store, the scheme file's party
    builder. ``events`` orders the warm batch among the stops and waves."""

    def __init__(self, *args, quorum=2, ok=True, **kw):
        super().__init__(*args, **kw)
        self.quorum, self.cohorts = quorum, 2
        self.config = {"layout": {"down_nodes": ["node0"]},
                       "scheme": {"key_type": "secp256k1"}}
        self.wallet_ids = [f"w-{i}" for i in range(self.n_wallets)]
        self.events, self.loaded, self.built = [], [], []
        for nid, node in self.cluster.nodes.items():
            node.load_share = (
                lambda key_type, wid, nid=nid:
                self.loaded.append((nid, key_type, wid)) or (nid, wid))
        stop = self.cluster.stop_node
        self.cluster.stop_node = (
            lambda nid: (self.events.append(("stop", nid)), stop(nid)))

        def warm_party(session, pid, ids, shares, digests, cohorts, config):
            self.events.append(("warm", pid))
            self.built.append((session, pid, list(ids), shares, digests,
                               cohorts, config))
            return _Party(ok)

        self.scheme = SimpleNamespace(warm_party=warm_party)

    def run_wave(self, index, *args):
        self.events.append(("wave", index))
        return super().run_wave(index, *args)


def test_the_live_nodes_are_warmed_after_the_stop_and_before_any_wave():
    served = _WarmRecording(4096, 16, 3)
    hooks = []
    _generator(KIND).drive(
        served, _mix("gg18-node-down-waves"), 45, seconds=3600.0,
        before_wave=lambda index, measured: hooks.append((index, measured)))
    assert served.events == [
        ("stop", "node0"), ("warm", "node1"), ("warm", "node2"),
        ("wave", 0), ("wave", 1), ("wave", 2), ("wave", 3)]  # warmed ONCE
    assert hooks == [(0, False), (1, True), (2, True), (3, True)]
    # each live node's own shares of the first wave-size wallets, read back
    # from its own store; the stopped node's store is not opened
    names = served.wallet_ids[:16]
    assert served.loaded == [(nid, "secp256k1", w)
                             for nid in ("node1", "node2") for w in names]
    for (session, pid, ids, shares, digests, cohorts, config) in served.built:
        assert (session, ids, cohorts) == (
            "bench-warm-live", ["node1", "node2"], 2)
        assert shares == [(pid, w) for w in names]
        assert digests == [bytes([i]) * 32 for i in range(16)]
        assert config is served.config


def test_a_warm_batch_that_fails_or_a_quorum_that_is_not_the_live_nodes():
    mix = _mix("gg18-node-down-waves")
    served = _WarmRecording(4096, 16, 3, ok=False)
    with pytest.raises(RuntimeError, match="failed verification at node1"):
        _generator(KIND).drive(served, mix, 1, seconds=3600.0)
    assert served.sent == []  # and not a wave went out
    served = _WarmRecording(4096, 16, 3, quorum=3)
    with pytest.raises(RuntimeError, match="quorum 3, and 2 of its nodes"):
        _generator(KIND).drive(served, mix, 1, seconds=3600.0)
    assert served.sent == [] and served.built == []
    # the warm batch is set-up: with no unmeasured wave it would fall in
    # the window, and the kind refuses the mix before a node is stopped
    served = _WarmRecording(4096, 16, 3)
    with pytest.raises(ValueError, match="unmeasured"):
        _generator(KIND).drive(served, dict(mix, unmeasured_waves=0), 1,
                               seconds=3600.0)
    assert served.events == []


def test_the_kind_holds_a_stopped_nodes_books_still_as_its_parent_does():
    def moves(served, index):
        if index == 2:
            served.books["node0"]["counters"]["scheduler.submitted_total"] = 1.0
    served = _WarmRecording(4096, 16, 3, after_wave=moves)
    with pytest.raises(RuntimeError, match="stopped node took part"):
        _generator(KIND).drive(served, _mix("gg18-node-down-waves"), 1,
                               seconds=3600.0)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_seed_sends_cell_4s_very_waves_with_node0_stopped_first(seed):
    served = _WarmRecording(4096, 16, 30)
    driven = _generator(KIND).drive(
        served, _mix("gg18-node-down-waves"), seed, seconds=3600.0)
    assert served.stopped_before_first_wave == ["node0"]
    assert len(driven["waves"]) == 31
    plain = _Recording(4096, 16, 30)
    _generator("closed_waves").drive(plain, _mix("gg18-waves"), seed,
                                     seconds=3600.0)
    # (index, measured, wallets, digests, lane, the time a wave may take)
    assert served.sent == plain.sent


# -- the manifest, by name ---------------------------------------------------------

def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def test_the_manifest_names_the_configuration_the_cell_and_the_eight(cell):
    manifest = _manifest()
    config = _named(manifest["configs"], CONFIG)
    assert config == {
        "name": CONFIG, "source": cell.config["source"],
        "file": f"benchmark/configs/{CONFIG}.json", "reduced": [],
        "why": config["why"]}
    assert _named(manifest["workloads"], CELL) == {
        "name": CELL, "config": CONFIG, "traffic": "gg18-node-down-waves",
        "chips": 1, "why": _named(manifest["workloads"], CELL)["why"]}
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == CONFIG] == [CELL]  # no second cell
    new = [_named(manifest["per_layer"], name) for name in NEW]
    assert [(m["unit"], m["better"], m["source"], m["layer"], m["moves"])
            for m in new] == [
        ("ms", "lower", "program_span", "session and party",
         "sign_throughput"),
        ("count", "lower", "program_counter", "session and party",
         "sign_throughput"),
        ("%", "lower", "device_trace", "engine kernels", "sign_throughput"),
        ("Gop/s", "higher", "device_trace", "engine kernels",
         "sign_throughput"),
        ("%", "higher", "device_trace", "engine kernels", "sign_throughput"),
        ("ms", "lower", "program_span", "session and party",
         "sign_throughput"),
        ("ms", "lower", "program_span", "batch scheduler",
         "sign_latency_p50_ms"),
        ("ms", "lower", "program_counter", "cluster host objects",
         "setup_s")]
    assert all(m["workloads"] == [CELL] for m in new)
    # each twin is its sibling's entry but for the name and the cell
    for name, sibling in SIBLING.items():
        assert {**_named(manifest["per_layer"], sibling), "name": name,
                "workloads": [CELL]} == _named(manifest["per_layer"], name)
    # nothing that was there lists the new cell, and its twin's five and
    # the Ed25519 degraded cell's three are not the new cell's
    listing = {m["name"] for m in manifest["per_layer"]
               if CELL in m.get("workloads", ())}
    assert listing == set(NEW)
    names = [m["name"] for m in cell.metrics("per_layer")]
    everywhere = [m["name"] for m in manifest["per_layer"]
                  if "workloads" not in m]
    assert sorted(names) == sorted(everywhere + NEW)
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "sign_throughput", "sign_latency_p50_ms", "sign_latency_p95_ms",
        "setup_s"]
    assert cell.traffic["kind"] == KIND
    assert cell.scheme.ENGINE == "party.ecdsa"


def test_tier_1_collects_none_of_the_cells_cpu_rehearsal(cell):
    """A cell's rehearsal is the slow tier's where its scheme file says so
    (``test_bench_rehearsal._rehearsed_cells`` marks it by that flag), and
    the control script's tier-1 rehearsal stays on the last cell whose
    rehearsal is tier-1 (``tests/conftest.py`` ``_tier1_cells``)."""
    assert cell.scheme.REHEARSAL["slow"] is True

    def every_cell():
        return [w["name"] for w in _manifest()["workloads"]]

    assert {CELL, TWIN_CELL} <= set(every_cell())
    tier1 = _tier1_cells(test_bench_rehearsal, every_cell)()
    assert CELL not in tier1 and TWIN_CELL not in tier1
    assert tier1[-1] == "ed25519-2of3-solana.message-waves"


# -- the readers --------------------------------------------------------------------

def _reader(cell, name):
    return cell.reader("per_layer", name)


def _degraded(cell, spans, trace=None, quorum=2, wave=16):
    run = make_run(cell, spans, trace, wave=wave, quorum=quorum)
    run.config = {"scheme": {"n_nodes": 3}}
    return run


def test_phase_ms_counts_the_gg18_phases_below_the_committees_size(cell):
    P = cell.scheme.PHASE_SPANS
    spans = [span(P[0], "node1", 10, 40, q=2),
             span(P[1], "node1", 60, 80, q=2, pairs=1),
             span(P[2], "node2", 200, 120, q=2, pairs=1),
             # a healthy batch of the same run: every node signed
             span(P[9], "node0", 5000, 999, q=3),
             # before the window, and the other scheme's phase
             span(P[0], "node1", -300, 500, q=2),
             span("phase:bsign_nonce_commit", "node1", 20, 500, q=2)]
    # 240 ms over two nodes and two measured waves
    assert _reader(cell, NEW[0])(_degraded(cell, spans)) == pytest.approx(60.0)
    # the parent's phases carry no q: nothing to read, nothing raised
    bare = [span(P[0], "node1", 10, 40), span(P[2], "node2", 200, 120)]
    assert _reader(cell, NEW[0])(_degraded(cell, bare)) is None
    assert _reader(cell, NEW[0])(_degraded(cell, [])) is None


def _books(responses):
    return {nid: {"counters": dict(
                {"scheduler.submitted_total": 1.0},
                **({"party.ecdsa.mta_responses_total": float(n)}
                   if n is not None else {})),
                  "gauges": {}, "histograms": {}}
            for nid, n in responses.items()}


def test_responses_per_sign_is_the_counters_rise_over_the_requests(cell):
    run = _degraded(cell, [])
    # the warm batch and the unmeasured wave stand before the window; two
    # waves of 16 inside it: each live node answers 2 legs x 1 peer x 16
    run.metrics_start = _books({"node0": 32, "node1": 64, "node2": 32})
    run.metrics_end = _books({"node0": 32, "node1": 128, "node2": 96})
    run.measured = [object()] * 32
    assert _reader(cell, NEW[1])(run) == pytest.approx(4.0)
    # a program without the counter, and a window with no request
    run.metrics_start = run.metrics_end = _books(
        {"node0": None, "node1": None})
    assert _reader(cell, NEW[1])(run) is None
    run.metrics_end = _books({"node1": 64})
    run.measured = []
    assert _reader(cell, NEW[1])(run) is None


def test_the_trace_readers_count_the_work_of_two_signers(
        cell, gg18_trace, monkeypatch):  # noqa: F811
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [
        SimpleNamespace(device_kind="TPU v5 lite")])
    run = _degraded(cell, [], gg18_trace)
    assert _reader(cell, NEW[2])(run) == pytest.approx(
        (RUNS[0] + RUNS[1]) / sum(RUNS) * 100)
    ops = sum(cell.scheme.ops_per_wave(16, 2).values())
    gg18_s = (RUNS[0] + RUNS[1] + RUNS[2]) / 1e9
    assert _reader(cell, NEW[3])(run) == pytest.approx(ops / gg18_s / 1e9)
    mxu = sum(cell.scheme.mxu_ops_per_wave(16, 2).values())
    peak = peaks.for_device("TPU v5 lite")["bf16_flops_per_s"]
    assert _reader(cell, NEW[4])(run) == pytest.approx(
        2 * mxu / ((RUNS[0] + RUNS[1]) / 1e9) / peak * 100)
    # each is its sibling's arithmetic at the run's quorum, and nothing
    # with no trace
    for name in NEW[2:5]:
        sibling = cell.reader("per_layer", SIBLING[name])
        assert _reader(cell, name)(run) == sibling(run)
        assert _reader(cell, name)(_degraded(cell, [])) is None
    whole = _degraded(cell, [], gg18_trace, quorum=3)
    assert _reader(cell, NEW[3])(whole) == pytest.approx(
        sum(cell.scheme.ops_per_wave(16, 3).values()) / gg18_s / 1e9)


def test_the_host_twins_read_the_wire_the_selection_and_the_loss(cell):
    P = cell.scheme.PHASE_SPANS
    respond = span("round:gg18/b/2/respond", "node1", 10, 100)
    delta = span("round:gg18/b/3/delta", "node2", 200, 50)
    spans = [respond, delta,
             dict(span(P[1], "node1", 20, 60, q=2, pairs=1),
                  parent_id=respond["span_id"]),
             span("host:envelope_in", "node2", 150, 10),
             span("host:quorum_select", "node1", 5, 0.5, q=2),
             span("host:quorum_select", "node2", 6, 0.25, q=2),
             span("host:quorum_select", "node1", -100, 50, q=2)]  # unmeasured
    run = _degraded(cell, spans)
    # (100 - 60) + 50 + 10 ms over two nodes and two measured waves
    assert _reader(cell, NEW[5])(run) == pytest.approx(25.0)
    # 0.75 ms over two nodes and two measured waves
    assert _reader(cell, NEW[6])(run) == pytest.approx(0.1875)
    # node1 and node2 each dropped node0, 40 and 60 ms after its last beat
    lost = {"registry.loss_detect_s": {"sum": 0.04, "count": 1}}
    run.metrics_start = {
        "node0": {"histograms": {}},
        "node1": {"histograms": lost},
        "node2": {"histograms": {
            "registry.loss_detect_s": {"sum": 0.06, "count": 1}}}}
    assert _reader(cell, NEW[7])(run) == pytest.approx(50.0)
    # each is its sibling's arithmetic, loaded; and nothing to read gives
    # nothing (a program without the spans or the histogram)
    for name in NEW[5:]:
        sibling = cell.reader("per_layer", SIBLING[name])
        assert _reader(cell, name)(run) == sibling(run)
        assert _reader(cell, name)(_degraded(cell, [])) is None


# -- the counted work ----------------------------------------------------------------

def test_two_signers_mta_programs_count_a_third_of_three_signers(cell):
    """q (q − 1) ordered pairs: two where six stand. The programs a pair
    runs count a third; a node's own curve programs two thirds; the curve
    programs that check the peers' blocks a third (q (q − 1) again)."""
    two = cell.scheme.ops_per_wave(16, 2)
    three = cell.scheme.ops_per_wave(16, 3)
    mxu2 = cell.scheme.mxu_ops_per_wave(16, 2)
    mxu3 = cell.scheme.mxu_ops_per_wave(16, 3)
    assert set(two) == set(three) == set(cell.scheme.KERNELS)
    per_pair = ("gg18_r1_prove", "gg18_r2_verify", "gg18_r2_respond",
                "gg18_r3_verify")
    for name in per_pair:
        assert two[name] * 3 == pytest.approx(three[name]), name
        assert mxu2[name] * 3 == pytest.approx(mxu3[name]), name
    assert set(cell.scheme.MTA_KERNELS) - set(per_pair) == {"gg18_r3_delta"}
    for name in ("gg18_r1_commit", "gg18_r3_delta", "gg18_r4_pok",
                 "gg18_r5a_commit", "gg18_r5b", "gg18_r5c_commit",
                 "gg18_final"):
        assert two[name] * 3 == pytest.approx(three[name] * 2), name
    for name in ("gg18_r5a_verify", "gg18_r5c_verify"):
        assert two[name] * 3 == pytest.approx(three[name]), name
    # a signature: 70.7 G multiply-adds at q = 3 (PERF.md section 3)
    assert sum(three.values()) / 16 == pytest.approx(70.7e9, rel=0.01)
    assert sum(two.values()) < sum(three.values()) * 0.4


# -- the cell, rehearsed (slow tier) -------------------------------------------------

@pytest.mark.slow
def test_a_traced_rehearsal_at_q2_isolated():
    if _INNER:
        pytest.skip("wrapper entry; inner run executes the real test")
    run_isolated(__file__, "test_a_traced_rehearsal_is_correct_at_q2",
                 "MPCIUM_BENCH_GG18_NODE_DOWN_INNER")


@pytest.mark.slow
@pytest.mark.skipif(not _INNER, reason="runs via the subprocess wrapper")
def test_a_traced_rehearsal_is_correct_at_q2(
        steer, capsys, monkeypatch):  # noqa: F811
    """The whole cell on the CPU at the scheme file's tiny size (a wave of
    2, 1,024-bit fixtures, shrunk proof domains): node0 stopped, node1 and
    node2 sign at ``B2|q2`` under node1's manifest, every ``r ‖ s`` verifies
    under its OpenSSL-made key and is low, and the line holds the cell's
    names."""
    monkeypatch.setattr(harness, "Tracer", _GG18Tracer)
    v5e = peaks.for_device("TPU v5 lite")  # the CPU has no published peak
    monkeypatch.setattr(peaks, "for_device", lambda kind: v5e)
    # long enough for the second counted wave once the first has run
    rc, lines = _run(steer, capsys, CELL, trace=1, seconds=120.0)
    last = lines[-1]
    rows = [ln for ln in lines if ln.get("phase") == "check"][0]["compared"]
    assert rc == 0 and last["correct"] is True, rows
    setup = [ln for ln in lines if ln.get("phase") == "setup"][0]
    assert (setup["nodes"], setup["threshold"], setup["quorum"]) == (3, 1, 2)
    waves = [ln for ln in lines if ln.get("phase") == "wave"]
    assert sum(1 for w in waves if w["measured"]) >= 2
    assert all(w["compile_requests"] == 0 and w["succeeded"] == 2
               and w["batches_fired"] == 1 for w in waves)
    assert rows["party_shapes"]["value"] == ["B2|q2"]
    assert rows["high_s_signatures"]["value"] == 0
    assert rows["fallbacks"]["value"] == 0
    want = {m["name"] for m in harness.Cell(str(steer), CELL)
            .metrics("per_layer")}
    assert set(last["metrics"]) == want and set(NEW) <= want
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert m["quorum.gg18_mta_responses_per_sign"] == 4.0
    assert m["quorum.gg18_phase_ms_per_wave"] > 0
    assert m["quorum.gg18_mta_device_share_pct"] == pytest.approx(50.0)
    assert m["quorum.gg18_achieved_gops"] > 0
    assert m["quorum.gg18_mxu_roofline_pct"] > 0
    assert m["quorum.gg18_wire_ms_per_wave"] > 0
    assert m["quorum.gg18_select_ms_per_wave"] > 0
    assert m["quorum.gg18_loss_detect_ms"] > 0
