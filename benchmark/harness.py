"""One run of one cell: set-up from the seed, the warm compile, the traffic
generator's window, the checks that decide ``correct``, the metrics.

Nothing here names a cell, a configuration, a traffic mix or a metric:
``BENCHMARK.json`` names them, and each is found as a file by its name —
``configs/<config>.json`` (the path the manifest gives), ``traffic/<traffic>
.json`` with its generator ``traffic/<kind>.py``, and one reader per metric,
``end_to_end/<metric>.py`` or ``layer_metrics/<metric>.py``. See README.md.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

from . import opcounts, reference, stats, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoAccelerator(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


# -- the manifest and the files it names --------------------------------------

class Cell:
    """A ``workloads`` entry of BENCHMARK.json with its files resolved."""

    def __init__(self, root: str, name: str):
        with open(os.path.join(root, "BENCHMARK.json")) as fh:
            self.manifest = json.load(fh)
        self.bench_dir = os.path.join(root, self.manifest["paths"][0])
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(has: {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.manifest["configs"]}
        with open(os.path.join(root, configs[self.entry["config"]]["file"])) as fh:
            self.config = json.load(fh)
        traffic_path = os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json")
        with open(traffic_path) as fh:
            self.traffic = json.load(fh)
        self.generator = _load_module(os.path.join(
            self.bench_dir, "traffic", self.traffic["kind"] + ".py"))

    def metrics(self, group: str) -> List[dict]:
        """The manifest's metrics of ``group`` that this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, group: str, metric: str) -> Callable:
        """The metric's own reader: ``end_to_end/<name>.py`` or
        ``layer_metrics/<name>.py``, ``read(run) -> float | None``."""
        folder = "layer_metrics" if group == "per_layer" else group
        return _load_module(os.path.join(
            self.bench_dir, folder, metric + ".py")).read


def _load_module(path: str):
    name = "bench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the device ---------------------------------------------------------------

def accelerator(chips: int) -> dict:
    """The device as JAX reports it. Anything but a TPU with at least the
    cell's chips is a failure: a run never carries on on the CPU."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise NoAccelerator(f"no accelerator: JAX found {dev}")
    if dev["count"] < chips:
        raise NoAccelerator(f"the cell needs {chips} chips: JAX found {dev}")
    return dev


def memory_peak_bytes(chips: int) -> List[int]:
    import jax

    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()[:chips]]


# -- what the layer readers are handed ----------------------------------------

class RunData:
    """The run's requests, counters, spans and reduced trace, as the
    readers under ``end_to_end/`` and ``layer_metrics/`` see them."""

    def __init__(self, served, driven: dict, metrics_start: dict,
                 spans: List[dict]):
        self.config = served.config
        self.wave_size = served.wave_size
        self.quorum = served.quorum
        self.waves = driven["waves"]
        self.measured_waves = [w for w in self.waves if w.measured]
        self.measured = [r for w in self.measured_waves for r in w.requests]
        self.window_start_ns = driven["window_start_ns"]
        self.window_ns = driven["window_end_ns"] - driven["window_start_ns"]
        self.setup_s = 0.0
        self.good: List[bool] = []  # set by settle(), after the check
        self.metrics_start = metrics_start
        self.metrics_end = served.metrics_snapshot()
        self.spans = spans
        self.trace: Optional[dict] = None
        self.traced_waves = 0
        self.traced_lo_ns = self.traced_hi_ns = 0  # profiler clock
        self.host_spans: List[dict] = []

    def settle(self, served, invalid: int) -> None:
        """Which measured requests count as good: the successful ones
        whose signature the reference accepts. With none invalid (every
        sound run) the reference is not run a second time."""
        if invalid == 0:
            self.good = [r.success for r in self.measured]
        else:
            self.good = [r.success and reference.verifies(
                served.pubkeys[r.wallet], r.digest, r.signature)
                for r in self.measured]

    def latencies_ms(self) -> List[float]:
        return stats.latencies_ms(
            [r.submit_ns for r in self.measured],
            [r.done_ns for r in self.measured], self.good, self.window_ns)

    def busy_and_idle(self) -> dict:
        return trace_reduce.busy_and_idle(
            self.trace, self.traced_lo_ns, self.traced_hi_ns)

    def program_seconds(self) -> Dict[str, float]:
        return trace_reduce.program_seconds(
            self.trace, self.traced_lo_ns, self.traced_hi_ns)

    def kernel_program_seconds(self) -> Dict[str, float]:
        """The five signing kernels' programs (``jit_<kernel>``) among
        those that ran in the traced span."""
        names = {"jit_" + k for k in opcounts.KERNELS}
        return {k: v for k, v in self.program_seconds().items()
                if k in names}


# -- tracing ------------------------------------------------------------------

class Tracer:
    """Profiler trace of the first measured wave, with the clock
    annotations that tie the profiler's clock to ``monotonic_ns``."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="mpcium-bench-trace-")
        self.wave = None
        self.active = False

    def _clock_mark(self) -> None:
        import jax

        for _ in range(3):
            with jax.profiler.TraceAnnotation(
                    f"{trace_reduce.CLOCK_PREFIX}{time.monotonic_ns()}"):
                pass

    def before_wave(self, _index: int, measured: bool) -> None:
        import jax

        if measured and self.wave is None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # no python frames: the node
            options.host_tracer_level = 2    # threads would bury the trace
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.active = True
            self._clock_mark()

    def on_wave(self, wave) -> None:
        import jax

        if self.active:
            self._clock_mark()
            jax.profiler.stop_trace()
            self.active = False
            self.wave = wave

    def finish(self, run: RunData) -> None:
        """Reduce the trace into ``run``; the trace's files are removed."""
        try:
            loaded = trace_reduce.load_xplane(
                trace_reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        if not trace_reduce.device_planes(loaded):
            # no chip, no device metric: the readers of the trace then
            # find nothing, and the result line lacks busy_s
            emit(phase="trace", error="no device plane in the trace")
            return
        w = self.wave
        offset = trace_reduce.clock_offset_ns(loaded)
        run.trace = loaded
        run.traced_waves = 1
        run.traced_lo_ns = w.t0_ns - offset
        run.traced_hi_ns = w.done_ns - offset
        run.host_spans = [
            {"name": "bench:submit", "t0_ns": w.t0_ns,
             "t1_ns": w.submitted_ns},
            {"name": "bench:await_results", "t0_ns": w.submitted_ns,
             "t1_ns": w.done_ns},
        ] + [{"name": s["name"], "t0_ns": s["t0_ns"], "t1_ns": s["t1_ns"]}
             for s in run.spans if s.get("t1_ns") is not None]


# -- the checks ---------------------------------------------------------------

def check(served, run: RunData, traffic: dict) -> dict:
    """Every number compared, beside its limit. ``correct`` is true only
    if each is inside its limit."""
    measured = run.measured
    every = [r for w in run.waves for r in w.requests]
    returned = [r for r in measured if r.success]
    invalid = reference.count_invalid(
        (served.pubkeys[r.wallet], r.digest, r.signature) for r in returned)
    fills = [s["histograms"].get("scheduler.batch_fill_ratio", {})
             for s in run.metrics_end.values()]
    fills = [f for f in fills if f.get("count")]
    full_manifests = len(every) // served.config["serving"]["batch_max_batch"]
    devices = served.config["layout"]["session_axis_devices"]
    compared = {
        "invalid_signatures": (invalid, "==", 0),
        "failed_requests": (sum(1 for r in measured if not r.success
                                and r.done_ns is not None),
                            "<=", int(traffic["max_failed"])),
        "pending_requests": (sum(1 for r in every if r.done_ns is None),
                             "==", 0),
        "stray_results": (served.strays, "==", 0),
        "unmeasured_wave_failures": (
            sum(1 for w in run.waves if not w.measured
                for r in w.requests if not r.success), "==", 0),
        "shed": (served.counter_total("scheduler.shed_total"), "==", 0),
        "fallbacks": (served.counter_total("scheduler.fallback_total"),
                      "==", 0),
        "compile_requests_in_window": (
            sum(w.compile_requests for w in run.measured_waves), "==", 0),
        "manifests_fired": (
            int(served.counter_total("scheduler.batches_fired_total")),
            "==", full_manifests),
        "batch_fill_ratio_min": (
            min((f["min"] for f in fills), default=None), "==", 1.0),
        "batch_fill_ratio_max": (
            max((f["max"] for f in fills), default=None), "==", 1.0),
        "party_eddsa_shapes": (served.party_shapes(), "==", [served.shape]),
    }
    if devices > 1:
        from mpcium_tpu.engine import eddsa_batch as eb

        peaks = memory_peak_bytes(devices)
        compared["unsharded_placements"] = (
            eb.unsharded_placements(), "==", 0)
        compared["devices_with_memory_in_use"] = (
            sum(1 for p in peaks if p > 0), "==", devices)
    ok = {}
    for name, (value, op, limit) in compared.items():
        ok[name] = (value == limit) if op == "==" else (
            value is not None and value <= limit)
    emit(phase="check", compared={
        k: {"value": v, "limit": f"{op} {json.dumps(lim)}", "ok": ok[k]}
        for k, (v, op, lim) in compared.items()},
        errors=sorted({r.error for r in every if r.error})[:3])
    return {"correct": all(ok.values()), "invalid": invalid}


# -- one run ------------------------------------------------------------------

def prepare(cell: Cell):
    """Once per process: the device, the compile cache, the session axis.
    -> (device as JAX reports it, compile counter)."""
    from mpcium_tpu.utils import jax_cache

    from .served import CompileCounter

    dev = accelerator(cell.chips)
    emit(phase="device", **dev)
    # every program lands in the cache, the small eager ones too: a later
    # run of this cell in this checkout then compiles nothing
    emit(phase="compile_cache", dir=jax_cache.configure(min_compile_s=0.0))
    devices = cell.config["layout"]["session_axis_devices"]
    if devices > 1:
        from mpcium_tpu.engine import sharded

        if sharded.arm_session_axis(devices) is None:
            raise RuntimeError(f"session axis did not arm over {devices}")
    return dev, CompileCounter()


def measure(cell: Cell, seed: int, seconds: float, trace: bool, counter,
            t_start_ns: int):
    """Set-up from the seed, the warm compile, the generator's window.
    -> (served, run); the caller closes ``served``."""
    from .served import Served

    t0 = time.monotonic()
    served = Served(cell.config, seed, counter)
    try:
        emit(phase="setup", seconds=time.monotonic() - t0,
             nodes=served.n_nodes, threshold=served.threshold,
             quorum=served.quorum, wallets=served.n_wallets,
             wave=served.wave_size, cohorts=served.cohorts)
        t0 = time.monotonic()
        served.warm()
        emit(phase="warm_compile", seconds=time.monotonic() - t0,
             **counter.snapshot())
        served.drain_spans()  # the warm batch's spans are not the window's
        tracer = Tracer() if trace else None
        spans: List[dict] = []
        metrics_start: dict = {}

        def before_wave(index: int, measured: bool) -> None:
            if measured and not metrics_start:
                metrics_start.update(served.metrics_snapshot())
            if tracer is not None:
                tracer.before_wave(index, measured)

        def on_wave(wave) -> None:
            if tracer is not None:
                tracer.on_wave(wave)
                spans.extend(served.drain_spans())  # before the ring wraps
            emit(phase="wave", wave=wave.index, measured=wave.measured,
                 size=wave.size, seconds=wave.seconds,
                 submit_seconds=wave.submit_seconds,
                 succeeded=sum(1 for r in wave.requests if r.success),
                 batches_fired=wave.batches_fired,
                 compile_requests=wave.compile_requests)

        driven = cell.generator.drive(
            served, cell.traffic, seed, seconds,
            on_wave=on_wave, before_wave=before_wave)
        run = RunData(served, driven, metrics_start, spans)
        run.setup_s = (driven["window_start_ns"] - t_start_ns) / 1e9
        if tracer is not None:
            tracer.finish(run)
        emit(phase="window", seconds=run.window_ns / 1e9,
             measured_waves=len(run.measured_waves),
             compiles=counter.snapshot())
        return served, run
    except BaseException:
        served.close()
        raise


def result_line(cell: Cell, served, run: RunData, dev: dict,
                trace: bool) -> dict:
    """The comparison with the reference (after the window, untimed), then
    the contract's result object."""
    checked = check(served, run, cell.traffic)
    run.settle(served, checked["invalid"])
    device = dict(dev)
    device["memory_peak_bytes"] = max(memory_peak_bytes(cell.chips))
    result = {"correct": checked["correct"],
              "attempted": len(run.measured),
              "failed": len(run.measured) - sum(1 for g in run.good if g)}
    group = "per_layer" if trace else "end_to_end"
    result["metrics"] = {}
    for m in cell.metrics(group):
        value = cell.reader(group, m["name"])(run)
        if value is not None:  # nothing to read: left out of the line
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    if trace and run.trace is not None:
        bi = run.busy_and_idle()
        device["busy_s"] = bi["busy_s"]
        device["window_s"] = bi["window_s"]
        result["breakdown"] = {
            "device_ops": trace_reduce.top_device_ops(
                run.trace, run.traced_lo_ns, run.traced_hi_ns),
            "idle_gaps": trace_reduce.idle_gaps(
                run.trace, run.traced_lo_ns, run.traced_hi_ns,
                run.host_spans),
        }
        emit(phase="trace", programs=run.program_seconds(),
             busy_s_per_device=bi["busy_s_per_device"])
    result["device"] = device
    return result


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start_ns: int) -> dict:
    """-> the result object of the contract (the last line of output)."""
    dev, counter = prepare(cell)
    served, run = measure(cell, seed, seconds, trace, counter, t_start_ns)
    try:
        return result_line(cell, served, run, dev, trace)
    finally:
        served.close()
