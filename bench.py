"""Throughput benchmark: batched threshold signatures per second on one chip.

Prints the flagship JSON line {"metric", "value", "unit", "vs_baseline", ...}
the MOMENT the flagship number is known; if secondary metrics complete, a
second (merged) line with the same metric name follows, so the last parseable
line of stdout is always the flagship metric.

Flagship metric (BASELINE.md north star): batched 2-of-3 **secp256k1 GG18**
signing at full key size (2048-bit Paillier, default ZK exponent domains)
through the complete 9-round protocol — MtA with range proofs, phase-5
commit–reveal, final in-protocol ECDSA verification — with all hashing and
bignum work on device (engine.gg18_batch on ops.modmul MXU kernels).

Robustness (the round-4 lesson — a run ended rc=124 with nothing
printed):
  * The process that measures is the one that asks for the device: with
    no TPU the bench prints why and exits non-zero. It never carries on
    on the CPU.
  * A hard WATCHDOG (MPCIUM_BENCH_WATCHDOG_S, default 2700 s) dumps the
    best-known record — the last real on-chip measurement if this run hasn't
    produced a number yet — and exits 0 before any outer timeout can kill
    the process silently.
  * The XLA compile cache goes where mpcium_tpu/utils/jax_cache.py says:
    JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache.

Env knobs: MPCIUM_BENCH_B (batch, default 1024 tpu / 2 cpu),
MPCIUM_BENCH_RUNS (timed runs, default 1), MPCIUM_BENCH_NO_SECONDARY=1 /
MPCIUM_BENCH_SECONDARY=1 (secondary metrics off/on override),
MPCIUM_BENCH_NO_OT=1 (skip the OT-MtA variant's extra compile+sign pass
on TPU), MPCIUM_BENCH_WATCHDOG_S (watchdog deadline, 0 disables).
The OT variant also honors MPCIUM_OT_CHUNKS (pipeline chunking,
0/unset = auto) and MPCIUM_NATIVE_THREADS (host hash/transpose/PRG
thread count); its host-vs-device overlap lands in the bench JSON as
gg18_ot_mta_host_s / gg18_ot_mta_device_s / gg18_ot_mta_overlap_ratio.
The host-only extension-stage microbench is scripts/bench_ot_host.py.

Batch sweep: MPCIUM_BENCH_B_SWEEP="1024,4096,8192" appends a final
merged line; unset on TPU it defaults to the DEFAULT_B_SWEEP ladder
("1024,4096,8192,16384" — ISSUE 17 adds the 16384 bucket), and
MPCIUM_BENCH_B_SWEEP=none disables. "b_sweep" maps each batch size to either the measured
sigs/sec or a STRUCTURED DNF — {"dnf": true, "reason": "..."} — never a
bare prose string (the BENCH_TPU_OT B=8192 entry predates this and is
flagged by the ledger as unstructured). Each size runs in a fresh
subprocess with its own deadline (MPCIUM_BENCH_SWEEP_TIMEOUT_S, default
the watchdog deadline), so one superlinear size cannot starve the rest.
"""
from __future__ import annotations

import json
import os
import secrets
import subprocess
import sys
import threading
import time

BASELINE_SIGS_PER_SEC = 10_000.0
_HERE = os.path.dirname(os.path.abspath(__file__))

# Shared with the watchdog thread. "record" is the most complete result so
# far; "printed" flips once the flagship line has been flushed to stdout.
_STATE: dict = {"record": None, "printed": False, "stage": "init"}


def _ensure_backend() -> str:
    """This process asks for the device itself; anything but a TPU ends
    the run, non-zero, with nothing printed on stdout."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.stderr.write(
            f"bench.py measures on the chip; JAX found platform "
            f"{platform!r} — refusing to carry on\n"
        )
        sys.exit(1)
    return platform


def _load_last_tpu_record() -> dict | None:
    """Most recent REAL on-chip measurement (written by
    .scratch/tpu_probe.sh after every successful on-chip bench), for
    degraded/watchdog output. Age comes from the embedded measured_at
    stamp; file mtime is only a fallback (it resets on every checkout)."""
    path = os.path.join(_HERE, "BENCH_TPU_LATEST.json")
    try:
        with open(path) as f:
            rec = json.load(f)
    except FileNotFoundError:
        return None
    except Exception as e:  # noqa: BLE001 — corrupt record: surface it
        return {"corrupt": True, "error": repr(e)}
    try:
        if "measured_at" in rec:
            import calendar

            # measured_at is written with time.gmtime (UTC): decode with
            # timegm, not mktime (which would assume local time and skew
            # the staleness figure by the host's UTC offset)
            then = calendar.timegm(time.strptime(
                rec["measured_at"][:19], "%Y-%m-%dT%H:%M:%S"
            ))
        else:
            then = os.path.getmtime(path)
        rec["age_hours"] = round((time.time() - then) / 3600, 1)
        # explicit seconds-resolution staleness for the claims engine:
        # a claim satisfied only by this embedded record is `stale`
        rec["stale_s"] = round(time.time() - then, 1)
        if "measured_at" not in rec:
            rec["age_hours_is_mtime_guess"] = True
    except Exception:  # noqa: BLE001
        pass
    return rec


def _emit(record: dict) -> None:
    sys.stdout.write(json.dumps(record) + "\n")
    sys.stdout.flush()


def _arm_watchdog(platform: str) -> None:
    deadline = float(os.environ.get("MPCIUM_BENCH_WATCHDOG_S", "2700"))
    if deadline <= 0:
        return

    def _fire() -> None:
        time.sleep(deadline)
        # whatever we emit below is fresher than the process child's
        # arm-time snapshot: stand it down so its staler line cannot
        # shadow ours as the last parseable stdout line
        _mark_flagship_printed()
        if _STATE["record"] is not None:
            # This run produced a number — re-emit it even if "printed" is
            # already set: the main thread may sit BETWEEN setting the flag
            # and the actual write, and a duplicate flagship line is
            # harmless where rc=0-with-empty-stdout is not.
            _emit(_STATE["record"])
            os._exit(0)
        if _STATE["printed"]:
            os._exit(0)
        from mpcium_tpu.perf.envfp import env_fingerprint

        rec = {
            "metric": "secp256k1_2of3_gg18_sigs_per_sec",
            "value": 0.0,
            "unit": "signatures/sec",
            "vs_baseline": 0.0,
            "platform": platform,
            "watchdog_timeout": True,
            "watchdog_s": deadline,
            "elapsed_s": round(deadline, 1),
            "env": env_fingerprint(),
            "stage_reached": _STATE["stage"],
        }
        # loaded at FIRE time, not arm time, so age_hours is current.
        # The live "value" stays 0.0 — a watchdog line is NOT a
        # measurement, and a driver parsing only metric/value must not
        # take a stale number as this run's result; the cached record
        # rides along under last_tpu_measurement only.
        fallback = _load_last_tpu_record()
        if fallback and fallback.get("corrupt"):
            rec["last_tpu_measurement_error"] = fallback.get("error")
        elif fallback:
            rec["last_tpu_measurement"] = fallback
        _emit(rec)
        os._exit(0)

    threading.Thread(target=_fire, daemon=True, name="bench-watchdog").start()
    _arm_process_watchdog(platform, deadline)


_SENTINEL = os.path.join(
    "/tmp" if os.access("/tmp", os.W_OK) else _HERE,
    f".bench_flagship_printed.{os.getpid()}",
)

_CHILD_SRC = r"""
import json, os, sys, time
deadline = float(sys.argv[1]); sentinel = sys.argv[2]
ppid = int(sys.argv[3])


def parent_alive():
    try:
        os.kill(ppid, 0)
        return True
    except OSError:
        return False


def stood_down():
    if os.path.exists(sentinel):
        try:
            os.unlink(sentinel)
        except OSError:
            pass
        return True
    return False


t0 = time.time()
while time.time() - t0 < deadline:
    time.sleep(5)
    if stood_down():
        sys.exit(0)  # parent printed the flagship line
    if not parent_alive():
        # parent EXITED without a flagship line (crash, not a native
        # freeze): a fabricated success line would mask the failure,
        # and holding the inherited stdout open would block a driver
        # reading to EOF -- leave silently.
        sys.exit(0)
if stood_down() or not parent_alive():
    sys.exit(0)
# deadline reached with the parent still alive and silent: it is frozen
# in native code holding the GIL -- emit the best-known record for it.
rec = json.loads(os.environ["MPCIUM_BENCH_FALLBACK"])
rec["watchdog_timeout"] = True
rec["watchdog"] = "process"
rec["elapsed_s"] = round(time.time() - t0, 1)
sys.stdout.write(json.dumps(rec) + "\n")
sys.stdout.flush()
"""


def _arm_process_watchdog(platform: str, deadline: float) -> None:
    """Backstop for the THREAD watchdog: a forked child that shares our
    stdout but not our GIL. The round-5 lesson — a wedged remote-compile
    call can sit in native code HOLDING the GIL for the entire driver
    budget, so no Python thread (watchdog or signal handler) ever runs
    again; round 4's rc=124-with-empty-stdout recurred at B=8192
    despite the thread watchdog. The child needs nothing from this
    process after the fork: it sleeps, checks the sentinel file the
    parent writes after the flagship line, and otherwise emits the
    best-known record itself."""
    from mpcium_tpu.perf.envfp import env_fingerprint

    rec = {
        "metric": "secp256k1_2of3_gg18_sigs_per_sec",
        "value": 0.0,
        "unit": "signatures/sec",
        "vs_baseline": 0.0,
        "platform": platform,
        # env stamped at ARM time (the child imports nothing from this
        # repo); the child stamps elapsed_s itself at fire time
        "env": env_fingerprint(),
        "stage_reached": "unknown (parent frozen in native code)",
    }
    # value stays 0.0 (same contract as the thread watchdog): the cached
    # on-chip record is surfaced only under last_tpu_measurement, never
    # as the live value of THIS run
    fallback = _load_last_tpu_record()
    if fallback and fallback.get("corrupt"):
        rec["last_tpu_measurement_error"] = fallback.get("error")
    elif fallback:
        rec["last_tpu_measurement"] = fallback
    env = dict(os.environ)
    env["MPCIUM_BENCH_FALLBACK"] = json.dumps(rec)
    # the child imports nothing of this repo: give it a bare path
    env["PYTHONPATH"] = ""
    env.pop("JAX_PLATFORMS", None)
    try:
        os.unlink(_SENTINEL)  # a recycled-PID leftover would disarm us
    except OSError:
        pass
    try:
        subprocess.Popen(
            [sys.executable, "-c", _CHILD_SRC,
             str(deadline), _SENTINEL, str(os.getpid())],
            env=env,
            stdout=None,  # inherit: the driver reads OUR stdout
            stderr=subprocess.DEVNULL,
        )
    except OSError:
        pass  # thread watchdog remains the only backstop


def _mark_flagship_printed() -> None:
    try:
        with open(_SENTINEL, "w") as f:
            f.write("1")
    except OSError:
        pass


def main() -> None:
    platform = _ensure_backend()
    _arm_watchdog(platform)
    default_b = "1024" if platform == "tpu" else "2"
    # CPU fallback shrinks the batch: full-size GG18 at even B=8 is ~8 min
    # of single-core arithmetic after a ~30 min compile — B=2 is the
    # honest degraded result (explicit MPCIUM_BENCH_B overrides), and the
    # per-host cache is kept warm at B=2 so a fallback run stays ~2 min
    B = int(os.environ.get("MPCIUM_BENCH_B", default_b))
    runs = int(os.environ.get("MPCIUM_BENCH_RUNS", "1"))

    from mpcium_tpu.utils import jax_cache

    jax_cache.configure()

    import numpy as np

    from mpcium_tpu.cluster import load_test_preparams
    from mpcium_tpu.engine import gg18_batch as gb

    party_ids = ["node0", "node1", "node2"]
    _STATE["stage"] = "setup"
    t0 = time.perf_counter()
    shares = gb.dealer_keygen_secp_batch(B, party_ids, threshold=1)
    preparams = load_test_preparams()
    signer = gb.GG18BatchCoSigners(
        party_ids[:2], shares[:2], preparams, rng=secrets
    )
    setup_s = time.perf_counter() - t0
    digests = np.frombuffer(
        secrets.token_bytes(B * 32), dtype=np.uint8
    ).reshape(B, 32)

    # warmup: compile every kernel at this batch size
    _STATE["stage"] = "compile"
    t0 = time.perf_counter()
    out = signer.sign(digests)
    compile_s = time.perf_counter() - t0
    assert out["ok"].all(), "warmup GG18 signatures invalid"

    # one phase-profiled run (sync at phase boundaries) — skipped on the
    # degraded CPU path, where a duplicate full run costs minutes and
    # measures nothing the timed run doesn't. Phase shares come from the
    # tracing spans the engine emits (utils/tracing.PhaseTimer), folded
    # back into the legacy table shape by phase_share().
    phases: dict = {}
    profiled_s = 0.0
    idle_fraction = 0.0
    if platform == "tpu":
        from mpcium_tpu.utils import tracing

        _STATE["stage"] = "profiled_run"
        spans: list = []
        tracing.enable(sink=spans.append)
        try:
            t0 = time.perf_counter()
            out = signer.sign(digests)
            profiled_s = time.perf_counter() - t0
        finally:
            tracing.disable()
        assert out["ok"].all()
        phases = tracing.phase_share(spans)
        # span-derived pipeline health: fraction of the profiled window
        # with NO device phase in flight (ISSUE 17 zero-idle target);
        # kept out of phase_s so the 2-decimal rounding there cannot
        # flatten a small idle share to 0.00
        idle_fraction = tracing.device_idle_fraction(spans)

    # timed runs (no internal sync)
    _STATE["stage"] = "timed_run"
    t0 = time.perf_counter()
    for _ in range(runs):
        out = signer.sign(digests)
        assert out["ok"].all()
    elapsed = time.perf_counter() - t0

    from mpcium_tpu.engine.pipeline import resolve_cohorts

    sigs_per_sec = runs * B / elapsed
    record = {
        "metric": "secp256k1_2of3_gg18_sigs_per_sec",
        "value": round(sigs_per_sec, 3),
        "unit": "signatures/sec",
        "vs_baseline": round(sigs_per_sec / BASELINE_SIGS_PER_SEC, 4),
        "platform": platform,
        "batch": B,
        "pipeline_cohorts": resolve_cohorts(B),
        "runs": runs,
        "mta": os.environ.get("MPCIUM_MTA", "paillier"),
        "setup_s": round(setup_s, 1),
        "compile_s": round(compile_s, 1),
        "profiled_run_s": round(profiled_s, 1),
        "device_idle_fraction": round(idle_fraction, 4),
        "phase_s": {k: round(v, 2) for k, v in phases.items()},
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime()),
    }
    # env fingerprint + compile ledger: which machine/toolchain/knob set
    # produced this number and what the
    # warmup actually compiled vs deserialized from the persistent cache
    from mpcium_tpu.perf import compile_watch
    from mpcium_tpu.perf.envfp import env_fingerprint

    record["env"] = env_fingerprint()
    record["compile"] = compile_watch.health_summary()
    if platform == "cpu":
        last = _load_last_tpu_record()
        if last is not None and last.get("corrupt"):
            record["last_tpu_measurement_error"] = last.get("error")
        elif last is not None:
            record["last_tpu_measurement"] = last
    # Print the flagship line NOW — everything after this is bonus that
    # must not cost the round its number (round-4 failure mode). "printed"
    # flips BEFORE the emit: if the watchdog fires inside the window it
    # must not append a stale record AFTER the fresh flagship line (a
    # duplicate flagship line is harmless; shadowing it is not).
    _STATE["record"] = dict(record)
    _STATE["printed"] = True
    _emit(record)
    _mark_flagship_printed()

    # secondary metrics (BASELINE configs 2/4/5): on by default on TPU,
    # off by default on the degraded CPU path. A secondary failure or
    # straggle must not cost the flagship line (already printed above);
    # on completion a merged line re-states the flagship metric so the
    # LAST parseable stdout line still carries it.
    want_secondary = (
        os.environ.get("MPCIUM_BENCH_SECONDARY") == "1"
        or (platform == "tpu"
            and not os.environ.get("MPCIUM_BENCH_NO_SECONDARY"))
    )
    if want_secondary:
        _STATE["stage"] = "secondary"
        try:
            extra = _secondary_metrics(B)
        except Exception as e:  # noqa: BLE001
            extra = {"secondary_error": repr(e)}
        if extra:
            record.update(extra)
            _STATE["record"] = dict(record)
            _emit(record)

    # OT-MtA variant (MPCIUM_MTA=ot; SECURITY.md "OT-MtA"): measured as
    # a LABELED extra when the main run used the default Paillier MtA —
    # the honest flagship keeps tss-lib security parity, but the
    # variant's number belongs in the driver artifact too.
    if (platform == "tpu"
            and os.environ.get("MPCIUM_MTA", "paillier") == "paillier"
            and not os.environ.get("MPCIUM_BENCH_NO_OT")):
        _STATE["stage"] = "ot_variant"
        try:
            # MPCIUM_MTA is read per-instance in GG18BatchCoSigners
            # (gg18_batch.py), so flipping the env and constructing a
            # second signer is sufficient — no re-import involved
            os.environ["MPCIUM_MTA"] = "ot"
            signer_ot = gb.GG18BatchCoSigners(
                party_ids[:2], shares[:2], preparams, rng=secrets
            )
            out = signer_ot.sign(digests)  # warmup/compile
            assert out["ok"].all()
            t0 = time.perf_counter()
            out = signer_ot.sign(digests)
            assert out["ok"].all()
            checked_s = time.perf_counter() - t0
            record["gg18_ot_mta_sigs_per_sec"] = round(B / checked_s, 3)
            record["gg18_ot_mta_batch"] = B
            # one phase-profiled pass for the host/device A/B split of
            # the OT phase: r2_mta_ot_host (worker-thread IKNP time:
            # PRG + transpose + pad hashing), r2_mta_ot_device
            # (main-thread block time on device arrays) and the
            # pipeline's overlap ratio (fraction of host time hidden
            # behind device compute) — the chunked double-buffer's win,
            # measured rather than asserted.
            from mpcium_tpu.utils import tracing

            spans_ot: list = []
            tracing.enable(sink=spans_ot.append)
            try:
                out = signer_ot.sign(digests)
            finally:
                tracing.disable()
            assert out["ok"].all()
            phases_ot = tracing.phase_share(spans_ot)
            record["gg18_ot_mta_phase_s"] = {
                k: round(v, 3) for k, v in phases_ot.items()
            }
            record["gg18_ot_mta_device_idle_fraction"] = round(
                tracing.device_idle_fraction(spans_ot), 4
            )
            record["gg18_ot_mta_host_s"] = round(
                phases_ot.get("r2_mta_ot_host", 0.0), 3
            )
            record["gg18_ot_mta_device_s"] = round(
                phases_ot.get("r2_mta_ot_device", 0.0), 3
            )
            record["gg18_ot_mta_overlap_ratio"] = round(
                phases_ot.get("r2_mta_ot_overlap_ratio", 0.0), 3
            )
            record["gg18_ot_mta_chunks"] = int(
                phases_ot.get("r2_mta_ot_chunks", 1)
            )
            # checks-on vs checks-off A/B (ISSUE 16): the timed run
            # above paid the active-security check kernels (on by
            # default); one more timed run under MPCIUM_OT_CHECKS=0
            # isolates their cost. gg18_ot_checks_s is the per-batch
            # overhead the KOS + Gilboa + consistency checks add — the
            # number PERFORMANCE.md quotes for the passive escape
            # hatch. Env is read per sign() call, so flip + restore.
            prev_checks = os.environ.get("MPCIUM_OT_CHECKS")
            os.environ["MPCIUM_OT_CHECKS"] = "0"
            try:
                out = signer_ot.sign(digests)  # compile the passive path
                assert out["ok"].all()
                t0 = time.perf_counter()
                out = signer_ot.sign(digests)
                assert out["ok"].all()
                passive_s = time.perf_counter() - t0
            finally:
                if prev_checks is None:
                    os.environ.pop("MPCIUM_OT_CHECKS", None)
                else:
                    os.environ["MPCIUM_OT_CHECKS"] = prev_checks
            record["gg18_ot_checks_on_s"] = round(checked_s, 3)
            record["gg18_ot_checks_off_s"] = round(passive_s, 3)
            record["gg18_ot_checks_s"] = round(checked_s - passive_s, 3)
        except Exception as e:  # noqa: BLE001
            record["gg18_ot_mta_error"] = repr(e)
        finally:
            os.environ["MPCIUM_MTA"] = "paillier"
        _STATE["record"] = dict(record)
        _emit(record)

    _run_b_sweep(record)


def _parse_last_metric_line(stdout: bytes) -> dict | None:
    for line in reversed(stdout.decode(errors="replace").splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and "metric" in doc:
            return doc
    return None


def _b_sweep_entry(bsz: int, timeout_s: float) -> object:
    """One sweep point: re-exec this bench in a subprocess at batch bsz.
    Returns the measured sigs/sec (float) or a structured DNF dict —
    {"dnf": True, "reason": ...}: never a missing key or a prose string."""
    env = dict(os.environ)
    env.pop("MPCIUM_BENCH_B_SWEEP", None)  # no recursive sweeps
    env["MPCIUM_BENCH_B"] = str(bsz)
    # sweep points measure the flagship metric only
    env["MPCIUM_BENCH_NO_SECONDARY"] = "1"
    env["MPCIUM_BENCH_NO_OT"] = "1"

    # every DNF shape below is stamped with how long the point ran and
    # where (env fingerprint): a DNF in the ledger must be attributable
    # to a host/platform and a timing, not just a reason string
    from mpcium_tpu.perf.envfp import env_fingerprint

    t0 = time.time()

    def _dnf(reason: str) -> dict:
        return {
            "dnf": True,
            "reason": reason,
            "elapsed_s": round(time.time() - t0, 1),
            "env": env_fingerprint(),
        }

    try:
        r = subprocess.run(
            [sys.executable, os.path.join(_HERE, "bench.py")],
            env=env, timeout=timeout_s, capture_output=True,
        )
    except subprocess.TimeoutExpired:
        return _dnf(
            f"no metric line within {timeout_s:.0f}s — "
            "killed by sweep driver"
        )
    doc = _parse_last_metric_line(r.stdout)
    if doc is None:
        return _dnf(f"rc={r.returncode} with no parseable metric line")
    if doc.get("watchdog_timeout"):
        return _dnf(
            f"watchdog fired at {doc.get('watchdog_s', '?')}s "
            f"(stage: {doc.get('stage_reached', 'unknown')})"
        )
    value = doc.get("value")
    if isinstance(value, (int, float)) and value > 0:
        return round(float(value), 3)
    return _dnf(f"rc={r.returncode} with non-positive value {value!r}")


# Default sweep on TPU when MPCIUM_BENCH_B_SWEEP is unset: the ladder
# measured round over round, now topped by the 16384 bucket
# (ISSUE 17). A size that wedges or times out lands as a structured DNF
# via _b_sweep_entry — never a missing key or a bare prose string.
DEFAULT_B_SWEEP = "1024,4096,8192,16384"


def _run_b_sweep(record: dict) -> None:
    """MPCIUM_BENCH_B_SWEEP: comma-separated batch sizes, each timed in
    its own subprocess; results land under record["b_sweep"] keyed by
    batch size, as numbers or structured DNFs. Unset on TPU → the
    DEFAULT_B_SWEEP ladder; "0"/"none" disables. The degraded CPU path
    never sweeps by default (each point re-pays a multi-minute compile)."""
    spec = os.environ.get("MPCIUM_BENCH_B_SWEEP", "").strip()
    if not spec and record.get("platform") == "tpu":
        spec = DEFAULT_B_SWEEP
    if not spec or spec.lower() in ("0", "none"):
        return
    _STATE["stage"] = "b_sweep"
    timeout_s = float(os.environ.get(
        "MPCIUM_BENCH_SWEEP_TIMEOUT_S",
        os.environ.get("MPCIUM_BENCH_WATCHDOG_S", "2700"),
    ))
    from mpcium_tpu.engine.buckets import bucket_b

    sweep: dict = {}
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        # snap to the pow-2 bucket grid (engine/buckets.py): an off-grid
        # sweep point would time a compile signature no production path
        # requests — the scheduler only ever emits floor_bucket chunks
        bsz = bucket_b(int(tok))
        if str(bsz) in sweep:
            continue
        sweep[str(bsz)] = _b_sweep_entry(bsz, timeout_s)
        # partial progress beats an empty field if a later size wedges
        record["b_sweep"] = dict(sweep)
        _STATE["record"] = dict(record)
    _emit(record)


def _secondary_metrics(B: int) -> dict:
    """BASELINE configs 2/4/5: ed25519 signing, batched DKG, batched
    resharing throughputs. Ed25519 runs at max(B, 4096) — BASELINE config
    2 is a 4096-wallet batch and the round-1 comparison point is B=4096."""
    import secrets as sec

    from mpcium_tpu.engine import eddsa_batch as eb
    from mpcium_tpu.engine.dkg_batch import BatchedDKG, BatchedReshare

    out = {}
    ids = ["node0", "node1", "node2"]

    Be = max(B, 4096) if B >= 256 else B
    shares = eb.dealer_keygen_batch(Be, ids, 1, rng=sec)
    signer = eb.BatchedCoSigners(ids[:2], shares[:2], rng=sec)
    messages = [sec.token_bytes(32) for _ in range(Be)]
    sigs, ok = signer.sign(messages)  # warmup/compile
    assert ok.all()
    t0 = time.perf_counter()
    sigs, ok = signer.sign(messages)
    out["ed25519_2of3_sigs_per_sec"] = round(
        Be / (time.perf_counter() - t0), 1
    )
    out["ed25519_batch"] = Be

    dkg = BatchedDKG(ids, threshold=1, key_type="secp256k1", rng=sec)
    # warmup at the SAME batch shape: XLA kernels are shape-specialized,
    # so a smaller warmup left the timed run paying full recompiles
    # (r4 on-chip: 4.3 wallets/s reported where compute alone is far
    # higher)
    dkg.run(B)
    t0 = time.perf_counter()
    dshares = dkg.run(B)
    out["secp256k1_dkg_wallets_per_sec"] = round(
        B / (time.perf_counter() - t0), 1
    )
    out["dkg_batch"] = B

    Br = max(B // 4, 1)
    rs = BatchedReshare(
        ids[:2], [dshares[0][:Br], dshares[1][:Br]],
        ["node0", "node1", "node2", "node3", "node4"], new_threshold=2,
        rng=sec,
    )
    rs.run()  # warmup/compile at the timed shape
    t0 = time.perf_counter()
    rs.run()
    out["reshare_2of3_to_3of5_wallets_per_sec"] = round(
        Br / (time.perf_counter() - t0), 1
    )
    out["reshare_batch"] = Br
    return out


if __name__ == "__main__":
    main()
