"""The interpreter account (ISSUE 40): thread CPU by role, the hand-over
lag canary, the log line's cost. CPU only; every test has a time limit of
its own (a deadline in the test, no waiting without one)."""
import ast
import os
import threading
import time

import pytest

from mpcium_tpu.cluster import LocalCluster
from mpcium_tpu.utils import interp, log
from mpcium_tpu.utils.metrics import MetricsRegistry

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "mpcium_tpu")


# -- the roles table ------------------------------------------------------------

def _leading_text(node):
    """The constant text a thread-name expression starts with: a literal,
    or an f-string's first piece; None for anything else."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if (isinstance(node, ast.JoinedStr) and node.values
            and isinstance(node.values[0], ast.Constant)):
        return node.values[0].value
    return None


def _thread_sites():
    """(file:line, the name's leading text or None) of every
    ``threading.Thread(``, ``threading.Timer(`` and ``thread_name_prefix=``
    in the package. A Timer's name is the ``.name = `` assignment that
    follows it (its constructor takes none)."""
    sites = []
    for folder, _dirs, files in os.walk(PACKAGE):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(folder, fname)
            with open(path) as fh:
                tree = ast.parse(fh.read())
            timer_names = [
                _leading_text(n.value) for n in ast.walk(tree)
                if isinstance(n, ast.Assign) and len(n.targets) == 1
                and isinstance(n.targets[0], ast.Attribute)
                and n.targets[0].attr == "name"]
            n_timers = 0
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                where = f"{os.path.relpath(path, PACKAGE)}:{node.lineno}"
                for kw in node.keywords:
                    if kw.arg == "thread_name_prefix":
                        sites.append((where, _leading_text(kw.value)))
                func = node.func
                if not (isinstance(func, ast.Attribute)
                        and isinstance(func.value, ast.Name)
                        and func.value.id == "threading"):
                    continue
                if func.attr == "Thread":
                    name = [kw.value for kw in node.keywords
                            if kw.arg == "name"]
                    sites.append(
                        (where, _leading_text(name[0]) if name else None))
                elif func.attr == "Timer":
                    sites.append((where, timer_names[n_timers]
                                  if n_timers < len(timer_names) else None))
                    n_timers += 1
    return sites


def test_every_thread_the_package_starts_has_a_role_other_than_other():
    sites = _thread_sites()
    assert len(sites) >= 25  # the walk found the package's sites
    unnamed = [where for where, text in sites if not text]
    assert not unnamed, f"threads started without a literal name: {unnamed}"
    strays = [(where, text) for where, text in sites
              if interp.role_of(text) == interp.OTHER]
    assert not strays, f"add a role to interp.ROLES for: {strays}"


@pytest.mark.parametrize("name,role", [
    ("MainThread", "main"), ("loopback-q_17", "loopback-q"),
    ("loopback_3", "loopback"), ("tcpbus-q_0", "tcpbus-q"),
    ("tcpbus-read", "tcpbus"), ("bsign-ab12", "bsign"),
    ("bdkg-ab12", "bsign"), ("brs-ab12", "bsign"),
    ("send-bsign:ab12", "send"), ("registry-node0", "registry"),
    ("session-gc-node1", "session-gc"), ("batch-wheel-node2", "batch-wheel"),
    ("health-node0", "health"), ("pipe-host_0", "pipe-host"),
    ("ot-host_0", "ot-host"), ("interp-canary", "interp"),
    ("keygen-wait-w1", "keygen-wait"), ("timer-hello-bsign:x", "timer"),
    ("Thread-7 (worker)", "other"),
])
def test_a_role_is_the_longest_prefix_of_the_table(name, role):
    assert interp.role_of(name) == role


# -- thread CPU by role ---------------------------------------------------------

def _spin(seconds):
    """Burn ``seconds`` of this thread's OWN CPU clock."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def test_a_spinning_batch_thread_shows_in_its_role_and_keeps_it_retired():
    before = interp.snapshot()["cpu_s"].get("bsign", 0.0)
    spun, leave = threading.Event(), threading.Event()

    def batch():
        _spin(0.2)
        spun.set()
        leave.wait(30)
        interp.retire()

    t = threading.Thread(target=batch, name="bsign-x", daemon=True)
    t.start()
    assert spun.wait(30)
    live = interp.snapshot()
    assert 0.15 <= live["cpu_s"]["bsign"] - before <= 0.35
    assert live["threads"]["bsign"] >= 1
    leave.set()
    t.join(30)
    assert not t.is_alive()
    gone = interp.snapshot()
    # kept after it has retired and exited, and counted once
    assert 0.15 <= gone["cpu_s"]["bsign"] - before <= 0.35
    assert gone["cpu_s"]["bsign"] >= live["cpu_s"]["bsign"]
    assert gone["threads"].get("bsign", 0) == live["threads"]["bsign"] - 1


def test_a_sleeping_thread_shows_next_to_nothing():
    leave = threading.Event()
    t = threading.Thread(target=lambda: leave.wait(30), name="send-sleeper",
                         daemon=True)
    before = interp.snapshot()["cpu_s"].get("send", 0.0)
    t.start()
    time.sleep(0.2)
    assert interp.snapshot()["cpu_s"].get("send", 0.0) - before < 0.02
    leave.set()
    t.join(30)


def test_two_snapshots_are_monotone_through_threads_that_come_and_go():
    """Threads that exit WITHOUT retire() (a closed pool's workers, a
    timer) leave what the last snapshot saw of them: no role's total
    ever falls."""
    def worker():
        _spin(0.02)

    last = interp.snapshot()["cpu_s"]
    for _round in range(5):
        threads = [threading.Thread(target=worker, name=f"loopback_{i}",
                                    daemon=True) for i in range(4)]
        for t in threads:
            t.start()
        mid = interp.snapshot()["cpu_s"]
        for t in threads:
            t.join(30)
        now = interp.snapshot()["cpu_s"]
        for snap in (mid, now):
            for role, value in last.items():
                assert snap.get(role, 0.0) >= value, role
            last = snap


def test_gauges_name_cpu_and_live_threads_by_role():
    g = interp.gauges()
    assert g["interp.cpu_s.main"] > 0
    assert g["interp.threads.main"] == 1.0
    assert all(k.startswith(("interp.cpu_s.", "interp.threads.")) for k in g)


def test_two_gauge_readings_in_a_row_pay_one_walk(monkeypatch):
    walks = []
    real = interp.snapshot
    monkeypatch.setattr(interp, "snapshot",
                        lambda: walks.append(1) or real())
    monkeypatch.setattr(interp, "_last_gauges", (float("-inf"), {}))
    first = interp.gauges()
    assert interp.gauges() == first and len(walks) == 1
    time.sleep(interp.MIN_WALK_INTERVAL_S + 0.05)
    assert interp.gauges()["interp.cpu_s.main"] >= first["interp.cpu_s.main"]
    assert len(walks) == 2


# -- the hand-over lag ------------------------------------------------------------

def _mean_lag_s(seconds):
    lags = []
    canary = interp.Canary(lags.append)
    try:
        time.sleep(seconds)
    finally:
        canary.close()
    assert canary.thread.name == "interp-canary"
    assert not canary.thread.is_alive()
    assert len(lags) >= 10
    return sum(lags) / len(lags)


def test_the_canary_reads_the_switch_interval_beside_a_spinner_and_slack_idle():
    idle = _mean_lag_s(0.5)
    stop = threading.Event()

    def spinner():
        while not stop.is_set():
            pass

    t = threading.Thread(target=spinner, name="soak-spinner", daemon=True)
    t.start()
    try:
        busy = _mean_lag_s(0.8)
    finally:
        stop.set()
        t.join(30)
    assert idle < 0.002, idle
    assert busy > 0.002, busy


# -- the log line's cost ----------------------------------------------------------

def test_log_totals_count_lines_and_their_seconds(capfd):
    log.init()
    before = log.totals()
    for i in range(20):
        log.info("interp account test line", i=i)
    log.debug("below the level: not a line")
    after = log.totals()
    assert after["log.lines_total"] - before["log.lines_total"] == 20
    assert after["log.emit_s_total"] > before["log.emit_s_total"]
    assert capfd.readouterr().err.count("interp account test line") == 20


def test_no_line_is_lost_when_more_threads_log_than_there_are_cores(capfd):
    """The totals are kept under the handler's own lock alone: a lost
    update would show as a line short."""
    import sys

    log.init()
    n_threads, n_lines = 4 * (os.cpu_count() or 4), 100
    before = log.totals()["log.lines_total"]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(
            target=lambda: [log.info("stress line") for _ in range(n_lines)],
            name=f"soak-logger-{i}", daemon=True) for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert log.totals()["log.lines_total"] - before == n_threads * n_lines
    capfd.readouterr()


def test_threads_that_retire_under_a_walk_are_counted_once():
    """Batch threads retiring while snapshots walk: the role's total is
    what they spun, each once (retire() and the walk share one lock)."""
    import sys

    n, spin_s = 24, 0.01
    before = interp.snapshot()["cpu_s"].get("bsign", 0.0)

    def batch():
        _spin(spin_s)
        interp.retire()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=batch, name=f"brs-{i}",
                                    daemon=True) for i in range(n)]
        for t in threads:
            t.start()
            interp.snapshot()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    grew = interp.snapshot()["cpu_s"]["bsign"] - before
    assert n * spin_s * 0.9 <= grew <= n * spin_s * 2.0 + 0.05, grew


def test_a_registry_folds_totals_up_and_never_down():
    m = MetricsRegistry()
    m.fold(counters={"log.lines_total": 5.0}, gauges={"interp.cpu_s.main": 1.5})
    m.fold(counters={"log.lines_total": 3.0}, gauges={"interp.cpu_s.main": 2.0})
    snap = m.snapshot()
    assert snap["counters"]["log.lines_total"] == 5.0
    assert snap["gauges"]["interp.cpu_s.main"] == 2.0
    m.fold(counters={"log.lines_total": 9.0})
    assert m.snapshot()["counters"]["log.lines_total"] == 9.0


# -- the cluster carries them -----------------------------------------------------

def test_a_cluster_folds_the_account_into_its_first_node_and_ends_its_canary(
        tmp_path):
    cluster = LocalCluster(n_nodes=3, threshold=1, root_dir=str(tmp_path))
    try:
        assert cluster._canary.thread.is_alive()
        deadline = time.monotonic() + 10
        first = cluster.node_ids[0]
        while time.monotonic() < deadline:
            snap = cluster.metrics_snapshot()
            lag = snap[first]["histograms"].get("interp.handover_lag_s")
            if lag and lag["count"] >= 5:
                break
            time.sleep(0.05)
        assert lag and lag["count"] >= 5 and lag["min"] >= 0
        gauges = snap[first]["gauges"]
        assert gauges["interp.cpu_s.main"] > 0
        assert gauges["interp.threads.registry"] >= 3
        assert gauges["interp.threads.interp"] >= 1
        assert snap[first]["counters"]["log.lines_total"] >= 1
        for nid in cluster.node_ids[1:]:
            own = snap[nid]
            assert not [k for kind in ("gauges", "counters", "histograms")
                        for k in own[kind]
                        if k.startswith(("interp.", "log."))], nid
        health = cluster.health()
        assert "interp.cpu_s.main" in health[first]["metrics"]["gauges"]
        again = cluster.metrics_snapshot()[first]["gauges"]
        assert again["interp.cpu_s.main"] >= gauges["interp.cpu_s.main"]
    finally:
        cluster.close()
    assert not cluster._canary.thread.is_alive()
