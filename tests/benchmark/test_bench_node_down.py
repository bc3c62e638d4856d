"""What PR 38 adds to the benchmark: the configuration
``ed25519-2of3-degraded`` (``ed25519-2of3`` with node0 out and a served
quorum of 2), the traffic kind ``node_down_waves`` (it stops the nodes the
configuration names, waits for the live registries to agree, sends the very
waves ``closed_waves`` sends, and holds the run to "the node was out"), the
cell ``ed25519-2of3-degraded.node-down-waves`` and three per-layer readers.

The rehearsals run the whole cell on the CPU at the scheme's tiny size
(waves of 8, 16 wallets): degraded it is ``correct`` at ``B8|q2``; with
nobody stopped the check's ``party_shapes`` row catches the cluster that
never degraded. The rest needs no JAX."""
import json
import os
from types import SimpleNamespace

import pytest
from test_bench_cold_sweep import _Recording, _generator, _mix
from test_bench_rehearsal import (  # noqa: F401 — steer is a fixture
    _RecordedTracer, _manifest, _run, printed, steer)
from test_bench_stage_readers import hist, make_run, snapshot, span

from benchmark import harness
from mpcium_tpu.cluster import LocalCluster

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "ed25519-2of3-degraded.node-down-waves"
NEW = ["quorum.select_ms_per_wave", "quorum.phase_ms_per_wave",
       "registry.loss_detect_ms"]
SEEDS = [0, 38, 3_000_000_019]  # the last: more than 32 signed bits hold


# -- the cell, rehearsed --------------------------------------------------------

def _check_rows(lines):
    return [ln for ln in lines if ln.get("phase") == "check"][0]["compared"]


def test_a_traced_rehearsal_is_correct_at_q2_and_prints_the_new_metrics(
        steer, capsys, monkeypatch):  # noqa: F811
    monkeypatch.setattr(harness, "Tracer", _RecordedTracer)
    rc, lines = _run(steer, capsys, CELL, trace=1)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True
    rows = _check_rows(lines)
    assert rows["party_shapes"]["value"] == ["B8|q2"]
    assert rows["party_shapes"]["limit"] == '== ["B8|q2"]'
    assert rows["fallbacks"]["value"] == 0
    setup = [ln for ln in lines if ln.get("phase") == "setup"][0]
    assert (setup["nodes"], setup["threshold"], setup["quorum"]) == (3, 1, 2)
    m = {k: v["value"] for k, v in last["metrics"].items()}
    must, may = printed(_manifest(steer), "per_layer", CELL)
    assert must == set(NEW) and must <= set(m) <= may
    assert 0 < m["quorum.select_ms_per_wave"] < m["batch.prepare_ms_per_wave"]
    assert m["quorum.phase_ms_per_wave"] > 0
    # a resignation shows at the next 50 ms poll, not after the 3 s a
    # crashed peer's heartbeat takes to go stale
    assert 0 <= m["registry.loss_detect_ms"] < 2000
    assert 1 <= m["bridge.inflight_peak"] <= 8  # a wave of 8, two bridges


def test_with_nobody_stopped_the_check_catches_the_healthy_cluster(
        steer, capsys):  # noqa: F811
    """The control: the same configuration with ``down_nodes: []`` warms
    ``B8|q2`` and then serves at q = 3, and is NOT correct."""
    path = steer / "benchmark" / "configs" / "ed25519-2of3-degraded.json"
    config = json.loads(path.read_text())
    assert config["layout"]["down_nodes"] == ["node0"]
    config["layout"]["down_nodes"] = []
    path.write_text(json.dumps(config))
    rc, lines = _run(steer, capsys, CELL)
    assert rc != 0 and lines[-1]["correct"] is False
    rows = _check_rows(lines)
    assert rows["party_shapes"] == {
        "value": ["B8|q2", "B8|q3"], "limit": '== ["B8|q2"]', "ok": False}
    # every signature still verified: the shape row alone says "not this"
    assert rows["invalid_signatures"]["ok"] and rows["failed_requests"]["ok"]
    assert lines[-1]["failed"] == 0


# -- the generator ---------------------------------------------------------------

class _Registry:
    def __init__(self, cluster, nid):
        self.cluster, self.nid = cluster, nid

    def ready_peers(self):
        return sorted(self.cluster.views[self.nid])


class _Cluster:
    """Stands where ``LocalCluster`` would: ``stop_node`` takes the node
    out of every registry's view at once."""

    def __init__(self, n=3):
        self.node_ids = [f"node{i}" for i in range(n)]
        self.views = {nid: set(self.node_ids) for nid in self.node_ids}
        self.nodes = {nid: SimpleNamespace(registry=_Registry(self, nid))
                      for nid in self.node_ids}
        self.stopped = []

    def stop_node(self, nid):
        self.stopped.append(nid)
        for view in self.views.values():
            view.discard(nid)


class _RecordingCluster(_Recording):
    """``test_bench_cold_sweep._Recording`` with a cluster and its books;
    ``after_wave(served, index)`` lets a test move them."""

    def __init__(self, *args, after_wave=None, **kw):
        super().__init__(*args, **kw)
        self.cluster = _Cluster()
        self.config = {"layout": {"down_nodes": ["node0"]}}
        self.after_wave = after_wave
        self.books = {nid: {"counters": {}, "histograms": {}}
                      for nid in self.cluster.node_ids}
        self.stopped_before_first_wave = None

    def run_wave(self, index, *args):
        if self.stopped_before_first_wave is None:
            self.stopped_before_first_wave = list(self.cluster.stopped)
        wave = super().run_wave(index, *args)
        if self.after_wave is not None:
            self.after_wave(self, index)
        return wave

    def metrics_snapshot(self):
        return json.loads(json.dumps(self.books))

    def counter_total(self, name):
        return sum(s["counters"].get(name, 0.0) for s in self.books.values())


def _drive(seed, last=5, **kw):
    served = _RecordingCluster(4096, 64, last, **kw)
    driven = _generator("node_down_waves").drive(
        served, _mix("node-down-waves"), seed, seconds=3600.0)
    return served, driven


@pytest.mark.parametrize("seed", SEEDS)
def test_node_down_waves_stops_the_node_then_sends_closed_waves_waves(seed):
    served, driven = _drive(seed, last=70)
    assert served.stopped_before_first_wave == ["node0"]
    assert len(driven["waves"]) == 71
    plain = _Recording(4096, 64, 70)
    _generator("closed_waves").drive(plain, _mix("bulk-waves"), seed,
                                     seconds=3600.0)
    assert served.sent == plain.sent  # cell 1's very waves
    assert served.sent != _drive(seed + 1, last=70)[0].sent


def test_the_mix_is_bulk_waves_and_two_parameters_of_its_own():
    down, bulk = _mix("node-down-waves"), _mix("bulk-waves")
    assert down["kind"] == "node_down_waves"
    own = {"down_nodes": "layout.down_nodes", "settle_timeout_s": 10.0}
    assert {k: down[k] for k in own} == own
    assert set(down) == set(bulk) | set(own)
    words = {"kind", "who"}
    assert {k: v for k, v in bulk.items() if k not in words} == {
        k: v for k, v in down.items() if k not in words | set(own)}


@pytest.mark.parametrize("counter", ["scheduler.submitted_total",
                                     "scheduler.batches_fired_total"])
def test_a_stopped_node_whose_scheduler_moved_prints_no_result(counter):
    def moves(served, index):
        if index == 3:
            served.books["node0"]["counters"][counter] = 1.0
    with pytest.raises(RuntimeError, match="stopped node took part"):
        _drive(SEEDS[0], after_wave=moves)


def test_a_stopped_node_whose_store_was_read_prints_no_result():
    def reads(served, index):
        served.books["node0"]["histograms"]["store.get_s"] = hist(0.1, 64)
    with pytest.raises(RuntimeError, match="share reads"):
        _drive(SEEDS[0], after_wave=reads)
    # a live node's reads are the run's own
    def live_reads(served, index):
        served.books["node1"]["histograms"]["store.get_s"] = hist(0.1, 64)
    _drive(SEEDS[0], after_wave=live_reads)


def test_a_manifest_that_waited_for_a_deputy_prints_no_result():
    def takeover(served, index):
        served.books["node1"]["counters"][
            "scheduler.deputy_takeover_total"] = 1.0
    with pytest.raises(RuntimeError, match="waited out a timeout"):
        _drive(SEEDS[0], after_wave=takeover)


def test_registries_that_never_agree_raise_inside_the_settle_limit():
    served = _RecordingCluster(4096, 64, 2)
    served.cluster.stop_node = lambda nid: None  # nobody notices
    mix = dict(_mix("node-down-waves"), settle_timeout_s=0.05)
    with pytest.raises(RuntimeError, match="do not list exactly"):
        _generator("node_down_waves").drive(served, mix, 1, seconds=1.0)
    assert served.sent == []  # and not a wave went out


def test_a_program_that_cannot_stop_a_node_is_refused_when_loaded(
        monkeypatch):
    """On the parent commit the cell fails at ``harness.Cell(...)``, before
    JAX is asked for a device and before a wallet is made."""
    monkeypatch.delattr(LocalCluster, "stop_node")
    with pytest.raises(RuntimeError, match="LocalCluster.stop_node"):
        _generator("node_down_waves")
    with pytest.raises(RuntimeError, match="nodes can leave"):
        harness.Cell(ROOT, CELL)
    harness.Cell(ROOT, "ed25519-2of3.bulk-waves")  # the others still load
    monkeypatch.undo()
    assert harness.Cell(ROOT, CELL).traffic["kind"] == "node_down_waves"


# -- the readers -----------------------------------------------------------------

def _reader(name):
    return harness.Cell(ROOT, CELL).reader("per_layer", name)


def _degraded_run(spans, start=None, end=None):
    run = make_run(spans, start or {}, end or {})
    run.config = {"scheme": {"n_nodes": 3}}
    return run


def test_select_ms_is_the_quorum_spans_a_node_and_wave():
    spans = [span("host:quorum_select", "node1", -100, 50, q=2),  # unmeasured
             span("host:quorum_select", "node1", 10, 0.5, q=2),
             span("host:quorum_select", "node2", 11, 0.25, q=2),
             span("host:quorum_select", "node1", 5000, 0.75, q=2),
             span("host:quorum_select", "node2", 5001, 0.5, q=2),
             span("host:batch_prepare", "node1", 10, 700)]
    # 2.0 ms over two nodes and two measured waves
    assert _reader("quorum.select_ms_per_wave")(
        _degraded_run(spans)) == pytest.approx(0.5)


def test_phase_ms_counts_the_phases_below_the_committees_size_alone():
    spans = [span("phase:bsign_nonce_commit", "node1", 10, 40, q=2),
             span("phase:bsign_aggregate_partial", "node1", 60, 80, q=2),
             span("phase:bsign_combine_verify", "node2", 200, 120, q=2),
             # a healthy batch of the same run: every node signed
             span("phase:bsign_combine_verify", "node0", 5000, 999, q=3),
             # before the window, and another scheme's phase
             span("phase:bsign_nonce_commit", "node1", -300, 500, q=2),
             span("phase:gg18_r1", "node1", 20, 500, q=2)]
    # 240 ms over two nodes and two measured waves
    assert _reader("quorum.phase_ms_per_wave")(
        _degraded_run(spans)) == pytest.approx(60.0)


def test_loss_detect_ms_is_the_histogram_at_the_windows_start():
    start = {"node0": snapshot(),  # the node that left observed nothing
             "node1": snapshot({"registry.loss_detect_s": hist(0.060, 1)}),
             "node2": snapshot({"registry.loss_detect_s": hist(0.100, 1)})}
    end = {"node1": snapshot({"registry.loss_detect_s": hist(9.0, 2)})}
    assert _reader("registry.loss_detect_ms")(
        _degraded_run([], start, end)) == pytest.approx(80.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_span_or_histogram_gives_none(name):
    """The parent's phases carry no ``q``, it has no ``host:quorum_select``
    and its registry observes nothing: each reader returns None, does not
    raise, and the line leaves the metric out."""
    bare = {"a": snapshot({"batch.share_load_s": hist(1.0, 10)})}
    spans = [span("phase:bsign_nonce_commit", "a", 10, 40),
             span("host:batch_prepare", "a", 10, 700)]
    assert _reader(name)(_degraded_run(spans, bare, bare)) is None
    assert _reader(name)(_degraded_run([])) is None


def test_a_healthy_run_has_no_degraded_phase_to_read():
    spans = [span("phase:bsign_nonce_commit", "a", 10, 40, q=3)]
    assert _reader("quorum.phase_ms_per_wave")(_degraded_run(spans)) is None


# -- the configuration and the manifest -----------------------------------------

def test_the_configuration_is_ed25519_2of3_with_node0_out():
    with open(os.path.join(BENCH, "configs", "ed25519-2of3.json")) as fh:
        base = json.load(fh)
    config = harness.Cell(ROOT, CELL).config
    assert set(config) == set(base)
    assert config["scheme"] == dict(base["scheme"], served_quorum=2)
    layout = dict(config["layout"])
    assert layout.pop("down_nodes") == ["node0"]  # rank 0: the deputy leads
    assert layout == base["layout"]
    for group in ("population", "serving", "reduced"):
        assert config[group] == base[group], group
    assert config["reduced"] == []
    differ = {k for k in base["guarantees"]
              if config["guarantees"][k] != base["guarantees"][k]}
    assert differ == {"t_of_n"} and set(config["guarantees"]) == set(
        base["guarantees"])
    assert "node0 has resigned" in config["guarantees"]["t_of_n"]
    assert "not read" in config["guarantees"]["t_of_n"]
    assumed = dict(config["assumed"])
    assert "Resign()" in assumed.pop("down_nodes")
    assert assumed == base["assumed"]
    assert "registry.go" in config["source"] and len(config["source"]) <= 200


def test_the_manifest_lists_the_new_cell_alone_for_the_new_metrics():
    manifest = _manifest()
    assert manifest["configs"][-1]["name"] == "ed25519-2of3-degraded"
    assert manifest["configs"][-1]["reduced"] == []
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": "ed25519-2of3-degraded",
                    "traffic": "node-down-waves", "chips": 1,
                    "why": cell["why"]}
    new = [m for m in manifest["per_layer"] if m["name"] in NEW]
    assert [(m["name"], m["layer"], m["moves"], m["source"]) for m in new] == [
        ("quorum.select_ms_per_wave", "batch scheduler",
         "sign_latency_p50_ms", "program_span"),
        ("quorum.phase_ms_per_wave", "session and party", "sign_throughput",
         "program_span"),
        ("registry.loss_detect_ms", "cluster host objects", "setup_s",
         "program_counter")]
    assert all(m["workloads"] == [CELL] and m["unit"] == "ms"
               and m["better"] == "lower" for m in new)
    # the cell reports the sixteen entries that hold everywhere and its three
    names = [m["name"] for m in harness.Cell(ROOT, CELL).metrics("per_layer")]
    assert len(names) == 16 + 3 and names[-3:] == NEW
