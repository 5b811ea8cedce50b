"""Process-level setup of the entry points: compile cache and device report."""
from pathlib import Path

import jax
import pytest

from repro.launch import runtime
from repro.launch.serve_secure import party_devices

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def cache_config():
    """Restore JAX's compile-cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_repo_dir(cache_config, monkeypatch):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    got = runtime.enable_compile_cache()
    assert got == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_compile_cache_env_wins_and_nothing_is_set(cache_config,
                                                   monkeypatch, tmp_path):
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_device_info_names_the_default_backend():
    devs = jax.devices()
    assert runtime.device_info() == {"platform": devs[0].platform,
                                     "kind": devs[0].device_kind,
                                     "count": len(devs)}


def test_party_mesh_needs_three_devices(monkeypatch):
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    with pytest.raises(SystemExit, match="needs 3 devices; found 1"):
        party_devices()
