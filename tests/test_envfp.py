"""``perf/envfp.py``: what a bench or soak record says about where it ran.

SECURITY.md states two properties no other test holds: the knob snapshot
is a prefix whitelist (never a dump of the environment, which carries
store passwords), and stamping a record never brings a JAX backend up."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from mpcium_tpu.perf import envfp

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("name,kept", [
    ("MPCIUM_MTA", True),
    ("MPCIUM_OT_CHUNKS", True),
    ("MPCIUM_BENCH_B", True),
    ("JAX_PLATFORMS", True),
    ("MPCIUM_BADGER_PASSWORD", False),
    ("MPCIUM_BROKER_TOKEN", False),
    ("HOME", False),
])
def test_knob_snapshot_is_a_prefix_whitelist(monkeypatch, name, kept):
    monkeypatch.setenv(name, "v-" + name)
    fp = envfp.env_fingerprint()
    assert (fp["knobs"].get(name) == "v-" + name) is kept
    assert ("v-" + name in json.dumps(fp)) is kept


def test_fingerprint_is_json_ready_and_names_this_host():
    fp = envfp.env_fingerprint()
    assert json.loads(json.dumps(fp)) == fp
    assert {"git_sha", "jax", "python", "host", "knobs", "platform"} <= set(fp)
    assert fp["host"] == envfp.host_fingerprint()
    assert fp["python"] == ".".join(map(str, sys.version_info[:3]))
    # this process has JAX up (conftest), so the facts are the backend's
    assert fp["platform"] == "cpu" and fp["device_count"] >= 1


def test_stamping_never_imports_jax():
    code = (
        "import json, sys\n"
        "from mpcium_tpu.perf import compile_watch, envfp\n"
        "fp = envfp.env_fingerprint()\n"
        "print(json.dumps([fp['platform'], 'jax' in sys.modules]))\n"
    )
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, timeout=120,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) == [
        "uninitialized", False]
