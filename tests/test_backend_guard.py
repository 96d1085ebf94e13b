"""The entry guard fails without a chip; one function places the cache."""

import importlib.util
import json
import os
import types

import jax
import pytest

from ibamr_tpu.serve import aot_cache
from ibamr_tpu.utils import backend_guard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the process's compile-cache config after the test."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


def test_auto_backend_honors_requested_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    j = backend_guard.auto_backend()
    assert j is jax
    assert jax.config.jax_platforms == "cpu"
    assert jax.devices()[0].platform == "cpu"


def test_auto_backend_raises_without_tpu(monkeypatch):
    # no explicit cpu request, and the backend that answers is not a
    # TPU: the run must stop, not continue on whatever was found
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    found = types.SimpleNamespace(platform="gpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [found])
    placed = []
    monkeypatch.setattr(aot_cache, "enable_persistent_cache",
                        lambda *a, **kw: placed.append(1))
    with pytest.raises(RuntimeError, match="no TPU.*'gpu'"):
        backend_guard.auto_backend()
    assert not placed


def test_cache_dir_comes_from_env_when_set(monkeypatch, tmp_path,
                                           cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    d = aot_cache.enable_persistent_cache(directory=str(tmp_path / "arg"),
                                          min_compile_secs=0.5)
    # JAX reads the variable itself: no directory is set in code, and
    # neither the argument's nor the checkout's directory is created
    assert d == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "arg").exists()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.5


def test_cache_dir_defaults_to_checkout(monkeypatch, tmp_path,
                                        cache_config):
    assert aot_cache.REPO_ROOT == REPO
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(aot_cache, "REPO_ROOT", str(tmp_path))
    d = aot_cache.enable_persistent_cache()
    assert d == str(tmp_path / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == d
    assert os.path.isdir(d)


def test_chip_smoke_rehearsal_fails_off_chip(monkeypatch, capsys):
    """16^3 walk of every phase on the CPU: exits non-zero, and its
    last line never claims ok."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr("sys.argv", ["chip_smoke.py", "--rehearse"])
    # the one-chip smoke wants exactly one device; tests see eight
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:1])
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code not in (0, None)
    captured = capsys.readouterr()
    lines = captured.out.strip().splitlines()
    phases = [json.loads(ln)["phase"] for ln in lines
              if ln.startswith('{"phase"')]
    assert phases[0] == "start" and phases[-1] == "compile_cache"
    assert {"run", "metrics", "restart", "engine", "scatter_vs_engine",
            "memory"} <= set(phases)
    assert '"ok"' not in lines[-1]
    assert "rehearsal walked every phase" in captured.err
