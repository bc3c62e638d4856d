"""CPU rehearsal of a whole run of each cell at a tiny size (waves of 8,
16 wallets), steered by the test and not by an option of run.py: the
device check is replaced (the CPU is not a chip), a temporary copy of the
manifest and its data files is shrunk, and the cache placement is pinned
to the directory conftest.py already uses.

Also here: the run with the timed path broken underneath (a signature
altered where the client receives it) comes out ``correct: false``; a run
that finds no TPU prints no result; and a configuration, a traffic mix, a
cell and a layer metric are added as files and entries only.
"""
import json
import os
import shutil

import jax
import pytest

from benchmark import harness, run, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 3_000_000_019  # more than 32 signed bits hold


@pytest.fixture()
def steer(monkeypatch, tmp_path):
    """-> (root of a shrunk temporary copy, run(cell, trace) -> (rc, lines))."""
    from mpcium_tpu.engine import sharded
    from mpcium_tpu.perf import compile_watch

    bench = tmp_path / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for f in (bench / "configs").iterdir():
        c = json.loads(f.read_text())
        c["serving"]["batch_max_batch"] = 8
        c["population"]["wallets"] = 16
        f.write_text(json.dumps(c))
    monkeypatch.setenv(
        "JAX_COMPILATION_CACHE_DIR",
        jax.config.jax_compilation_cache_dir
        or os.path.join(ROOT, ".jax_cache_tests"))
    # a traced run appends its flag to this before JAX loads the TPU
    # library; nothing loads it here, and the test puts the variable back
    monkeypatch.setenv("LIBTPU_INIT_ARGS",
                       os.environ.get("LIBTPU_INIT_ARGS", ""))
    floor = jax.config.jax_persistent_cache_min_compile_time_secs

    def as_accelerator(chips):
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": chips}

    monkeypatch.setattr(harness, "accelerator", as_accelerator)
    # the CPU backend reports no memory statistics
    monkeypatch.setattr(harness, "memory_peak_bytes",
                        lambda chips: [1] * chips)
    compile_watch.reset()  # shapes other tests of this worker ledgered
    yield tmp_path
    compile_watch.reset()
    sharded.arm_session_axis(1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


def _run(root, capsys, cell, trace=0, seconds=2.0):
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   str(seconds), "--trace", str(trace)], root=str(root))
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return rc, lines


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return [w["name"] for w in json.load(fh)["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_rehearsal_of_each_cell(steer, capsys, cell, eight_devices):
    rc, lines = _run(steer, capsys, cell)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["failed"] == 0 and last["attempted"] % 8 == 0
    assert last["attempted"] >= 8
    assert set(last["metrics"]) == {
        "sign_throughput", "sign_latency_p50_ms", "sign_latency_p95_ms",
        "setup_s"}
    for m in last["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    waves = [ln for ln in lines if ln.get("phase") == "wave"]
    assert [w["measured"] for w in waves][:2] == [False, True]
    assert all(w["batches_fired"] == 1 and w["compile_requests"] == 0
               and w["succeeded"] == 8 for w in waves)
    compared = [ln for ln in lines if ln.get("phase") == "check"][0]
    assert all(c["ok"] for c in compared["compared"].values())


class _RecordedTracer:
    """Stands where the profiler would: no chip, so no device plane can be
    recorded here. Hands the run a made-up device plane laid over the
    traced wave (busy for a tenth of it, in two programs) and the clock
    annotation that ties it to the host's clock."""

    OFFSET = 1_000_000_000_000  # monotonic_ns - profiler_ns

    def __init__(self):
        self.wave = None

    def before_wave(self, index, measured):
        pass

    def on_wave(self, wave):
        if wave.measured and self.wave is None:
            self.wave = wave

    def finish(self, run_data):
        w = self.wave
        lo, hi = w.t0_ns - self.OFFSET, w.done_ns - self.OFFSET
        tenth = (hi - lo) // 10
        mid = lo + 5 * tenth
        run_data.trace = {"planes": [
            {"name": "/host:CPU", "lines": [{"name": "python", "events": [
                [f"{trace_reduce.CLOCK_PREFIX}{w.t0_ns}", lo, 10]]}]},
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": [
                    ["jit_nonce_commitments(1)", mid, tenth // 2],
                    ["jit_verify_signatures(2)", mid + tenth // 2,
                     tenth // 2]]},
                {"name": "XLA Ops", "events": [
                    ["%while.1", mid, tenth // 2],
                    ["%fusion.7", mid, tenth // 2],
                    ["%fusion.9", mid + tenth // 2, tenth // 2]]}]},
        ]}
        run_data.traced_waves = 1
        run_data.traced_lo_ns, run_data.traced_hi_ns = lo, hi
        run_data.host_spans = [
            {"name": "bench:submit", "t0_ns": w.t0_ns,
             "t1_ns": w.submitted_ns},
            {"name": "bench:await_results", "t0_ns": w.submitted_ns,
             "t1_ns": w.done_ns}]


def test_files_and_entries_alone_add_a_cell_and_a_traced_run_reads_it(
        steer, capsys, monkeypatch):
    """A configuration, a traffic mix, a layer metric and a cell, added to
    the temporary copy as new files and new entries — no file that was
    there is edited — and run traced (the trace itself is stood in for)."""
    bench = steer / "benchmark"
    cfg = json.loads((bench / "configs" / "ed25519-3of5.json").read_text())
    cfg["source"] = "a later PR's deployment"
    (bench / "configs" / "later-config.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "bulk-waves.json").read_text())
    mix["unmeasured_waves"] = 0
    (bench / "traffic" / "later-mix.json").write_text(json.dumps(mix))
    (bench / "layer_metrics" / "later.waves_counted.py").write_text(
        "def read(run):\n    return float(len(run.measured_waves))\n")
    (bench / "layer_metrics" / "later.nothing_to_read.py").write_text(
        "def read(run):\n    return None\n")
    manifest = json.loads((steer / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "later-config", "source": cfg["source"],
        "file": "benchmark/configs/later-config.json",
        "reduced": cfg["reduced"], "why": "a dummy"})
    manifest["workloads"].append({
        "name": "later-config.later-mix", "config": "later-config",
        "traffic": "later-mix", "chips": 1, "why": "a dummy"})
    for name in ("later.waves_counted", "later.nothing_to_read"):
        manifest["per_layer"].append({
            "name": name, "unit": "waves", "better": "higher",
            "source": "program_counter", "layer": "client SDK",
            "moves": "sign_throughput",
            "workloads": ["later-config.later-mix"]})
    (steer / "BENCHMARK.json").write_text(json.dumps(manifest))
    monkeypatch.setattr(harness, "Tracer", _RecordedTracer)

    rc, lines = _run(steer, capsys, "later-config.later-mix", trace=1)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    m = last["metrics"]
    assert "later.nothing_to_read" not in m  # nothing read: left out
    assert m["later.waves_counted"]["value"] >= 1
    assert set(m) - {"later.waves_counted"} == {
        "client.submit_ms_per_sign", "scheduler.batch_fill_ratio",
        "scheduler.dispatch_age_ms", "party.phase_ms_per_wave",
        "kernels.device_ms_per_wave", "kernels.achieved_gops",
        "device.idle_share_pct"} | (
            {"cluster.wave_growth_pct"}
            if m["later.waves_counted"]["value"] >= 2 else set())
    assert m["scheduler.batch_fill_ratio"]["value"] == 1.0
    assert m["device.idle_share_pct"]["value"] == pytest.approx(90.0, abs=0.1)
    assert m["party.phase_ms_per_wave"]["value"] > 0
    dev = last["device"]
    assert dev["busy_s"] > 0
    assert dev["busy_s"] == pytest.approx(dev["window_s"] / 10, rel=1e-3)
    ops = dict(last["breakdown"]["device_ops"])
    assert set(ops) == {"%fusion.7", "%fusion.9"}  # the while shell is out
    gaps = dict(last["breakdown"]["idle_gaps"])
    assert set(gaps) <= {"bench:submit", "bench:await_results"} | {
        s for s in gaps if s.startswith(("phase:", "round:", "sched",
                                         "session", "host:", "batch"))}
    assert sum(gaps.values()) == pytest.approx(dev["window_s"] * 0.9, rel=0.01)
    # the first wave was not warmed up by an unmeasured one: no such wave
    waves = [ln for ln in lines if ln.get("phase") == "wave"]
    assert waves[0]["measured"] is True


def test_a_signature_altered_where_it_is_produced_is_not_correct(
        steer, capsys, monkeypatch):
    from benchmark import served

    real = served.Served._on_result

    def altering(self, ev):
        if ev.tx_id == "bench-1-3" and ev.signature:
            flipped = bytearray(bytes.fromhex(ev.signature))
            flipped[7] ^= 0x20
            ev.signature = bytes(flipped).hex()
        real(self, ev)

    monkeypatch.setattr(served.Served, "_on_result", altering)
    rc, lines = _run(steer, capsys, _cells()[0])
    last = lines[-1]
    assert rc != 0 and last["correct"] is False
    assert last["failed"] == 1  # the altered one counts as failed
    compared = [ln for ln in lines if ln.get("phase") == "check"][0]
    bad = {k for k, c in compared["compared"].items() if not c["ok"]}
    assert bad == {"invalid_signatures"}


def test_the_control_script_holds_at_the_tiny_size(steer, capsys):
    """benchmark/control.py, the script that shows on the chip that the
    comparison fails when it should, rehearsed on one seed."""
    from benchmark import control

    rc = control.main(["--workload", _cells()[-1], "--seeds", str(SEED),
                       "--seconds", "1"], root=str(steer))
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rc == 0 and last["control_holds"] is True
    (row,) = last["runs"]
    assert row["sound_correct"] is True and row["compared"] >= 8
    assert row["flip_bit_correct"] is False
    assert row["other_key_correct"] is False


def test_no_accelerator_prints_no_result(capsys):
    assert jax.devices()[0].platform == "cpu"
    rc = run.main(["--workload", _cells()[0], "--seed", "1", "--seconds",
                   "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert '"correct"' not in out.out and "no accelerator" in out.err
