"""The one compile-cache rule (mpcium_tpu/utils/jax_cache.py): with
JAX_COMPILATION_CACHE_DIR set the program sets no cache directory in
code; unset, every entry point agrees on <checkout>/.jax_cache."""
import re
from pathlib import Path

import jax
import pytest

from mpcium_tpu.utils import jax_cache
from mpcium_tpu.warm import prewarm as pw

ROOT = Path(__file__).resolve().parent.parent
_UPDATE = re.compile(r"config\.update\(\s*[\"']jax_compilation_cache_dir")


@pytest.fixture()
def restore_cache_config():
    was_dir = jax.config.jax_compilation_cache_dir
    was_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield was_dir
    jax.config.update("jax_compilation_cache_dir", was_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was_min)


def _program_sources():
    for sub in ("mpcium_tpu", "scripts"):
        yield from (ROOT / sub).rglob("*.py")
    yield from (ROOT / n for n in ("bench.py", "chip_smoke.py",
                                   "__graft_entry__.py"))


def test_env_var_wins_over_every_entry_point(
    monkeypatch, tmp_path, restore_cache_config
):
    monkeypatch.setenv(jax_cache.ENV_VAR, str(tmp_path / "operator"))
    before = restore_cache_config
    assert jax_cache.configure() == before  # bench.py, chip_smoke.py
    pw.configure_cache(str(tmp_path / "explicit"))  # the pre-warmer
    assert jax_cache.configure(str(tmp_path / "explicit"), 0.0) == before
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "explicit").exists()


def test_unset_agrees_on_checkout_cache(
    monkeypatch, tmp_path, restore_cache_config
):
    monkeypatch.delenv(jax_cache.ENV_VAR, raising=False)
    want = str(ROOT / ".jax_cache")
    assert jax_cache.default_dir() == want
    assert jax_cache.configure() == want
    assert jax.config.jax_compilation_cache_dir == want
    # an operator's explicit directory is honoured only here
    pw.configure_cache(str(tmp_path / "explicit"))
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "explicit")


def test_only_the_helper_sets_the_cache_dir():
    helper = ROOT / "mpcium_tpu" / "utils" / "jax_cache.py"
    offenders = [
        str(p.relative_to(ROOT)) for p in _program_sources()
        if p != helper and _UPDATE.search(p.read_text())
    ]
    assert offenders == []
    for name, call in (("bench.py", "jax_cache.configure("),
                       ("chip_smoke.py", "jax_cache.configure("),
                       ("mpcium_tpu/warm/prewarm.py", "jax_cache.configure("),
                       ("scripts/prewarm.py", "pw.configure_cache(")):
        src = (ROOT / name).read_text()
        assert call in src, name
        assert "host_fingerprint" not in src, name
    assert "ALLOW_MULTIPLE_LIBTPU_LOAD" not in "".join(
        p.read_text() for p in _program_sources())


def test_locations_do_not_carry_the_checkouts_path(restore_cache_config):
    """A Pallas kernel travels in its program as a serialized module with
    its locations, which the persistent cache's key covers: with the
    checkout's path cut from file names the same program has the same
    text in every checkout (PR 29: ten GG18 programs recompiled in each)."""
    was = jax.config.jax_hlo_source_file_canonicalization_regex
    try:
        jax_cache.configure()
        text = jax.jit(lambda x: x * 2 + 1).lower(1.0).as_text(
            debug_info=True)
    finally:
        jax.config.update("jax_hlo_source_file_canonicalization_regex", was)
    assert "tests/test_jax_cache.py" in text
    assert str(ROOT) not in text
