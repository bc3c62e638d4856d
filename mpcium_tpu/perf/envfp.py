"""Environment fingerprints: which machine/toolchain produced a number.

A CPU-degraded bench record once sat in a round's slot with the chip
number only under ``last_tpu_measurement``, because records carried no
durable statement of WHERE they were measured. Every ``bench.py`` and
soak record stamps ``env_fingerprint()``, and the warm manifest keys
its cached executables by ``host_fingerprint()``.

Deliberately import-light: no jax import at module scope, and device
facts are read only from an already-initialized jax (``sys.modules``),
never by importing it — stamping a record must not cost a backend
bring-up.
"""
from __future__ import annotations

import hashlib
import os
import subprocess
import sys
from typing import Dict, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(os.path.dirname(_HERE))

# the env knobs that change what a perf number means; anything else
# (paths, passwords) is noise the fingerprint must not leak
_KNOB_PREFIXES = (
    "MPCIUM_MTA", "MPCIUM_OT_CHUNKS", "MPCIUM_NATIVE_THREADS",
    "MPCIUM_BENCH_B", "MPCIUM_BENCH_RUNS", "JAX_PLATFORMS",
)


def host_fingerprint() -> str:
    """Short stable id for THIS host's CPU feature set (the same scheme
    bench.py keys its per-host XLA:CPU cache dirs by: AOT artifacts are
    machine-feature-stamped and containers live-migrate)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    return hashlib.sha256(
                        " ".join(sorted(line.split()[2:])).encode()
                    ).hexdigest()[:12]
    except OSError:
        pass
    import platform as _p

    return hashlib.sha256(_p.processor().encode() or b"?").hexdigest()[:12]


def git_sha() -> Optional[str]:
    try:
        r = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=_REPO, capture_output=True, timeout=10,
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    if r.returncode != 0:
        return None
    return r.stdout.decode().strip() or None


def jax_version() -> Optional[str]:
    jax = sys.modules.get("jax")
    if jax is not None:
        return getattr(jax, "__version__", None)
    try:
        from importlib.metadata import version

        return version("jax")
    except Exception:  # noqa: BLE001 — fingerprinting must never raise
        return None


def device_facts() -> Dict[str, object]:
    """platform/kind/count of the ALREADY-initialized jax backend, or
    ``{"platform": "uninitialized"}``. Never imports or initializes jax:
    a fingerprint read must not pay (or hang on) a backend bring-up."""
    jax = sys.modules.get("jax")
    if jax is None:
        return {"platform": "uninitialized"}
    try:
        devs = jax.devices()
    except Exception:  # noqa: BLE001 — a wedged backend is a fact too
        return {"platform": "unavailable"}
    return {
        "platform": devs[0].platform if devs else "none",
        "device_kind": getattr(devs[0], "device_kind", "?") if devs else "?",
        "device_count": len(devs),
    }


def knob_snapshot() -> Dict[str, str]:
    return {
        k: v for k, v in sorted(os.environ.items())
        if k.startswith(_KNOB_PREFIXES)
    }


def env_fingerprint() -> Dict[str, object]:
    """The full stamp bench/soak records carry. Values are public build/
    machine facts only (SECURITY.md: no secret-taxonomy values)."""
    fp: Dict[str, object] = {
        "git_sha": git_sha(),
        "jax": jax_version(),
        "python": ".".join(map(str, sys.version_info[:3])),
        "host": host_fingerprint(),
        "knobs": knob_snapshot(),
    }
    fp.update(device_facts())
    return fp
