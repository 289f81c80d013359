"""The entry points' XLA set-up: compile cache placement and exact
rounding."""
import os

import jax
import pytest
from jax._src import xla_bridge

from repro.launch import xla_setup


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


@pytest.fixture
def backend_not_started(monkeypatch):
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: False)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("XLA_FLAGS", xla_setup.EXACT_ROUNDING)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert xla_setup.configure().cache_dir == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("XLA_FLAGS", xla_setup.EXACT_ROUNDING)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = xla_setup.configure().cache_dir
    assert path == str(xla_setup.CHECKOUT / ".jax_cache")
    assert (xla_setup.CHECKOUT / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path
    assert xla_setup.configure().cache_dir == path   # same on every call


def test_exact_rounding_joins_xla_flags_once(monkeypatch, restore_cache_dir,
                                             backend_not_started):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/dev/null")
    for _ in range(2):
        assert xla_setup.configure().exact_rounding
    assert os.environ["XLA_FLAGS"] == \
        f"--xla_dump_to=/dev/null {xla_setup.EXACT_ROUNDING}"


def test_users_own_rounding_flag_is_left(monkeypatch, restore_cache_dir,
                                         backend_not_started):
    monkeypatch.setenv("XLA_FLAGS", "--xla_allow_excess_precision=true")
    assert not xla_setup.configure().exact_rounding
    assert os.environ["XLA_FLAGS"] == "--xla_allow_excess_precision=true"


def test_too_late_once_the_backend_runs(monkeypatch, restore_cache_dir):
    jax.devices()
    monkeypatch.setenv("XLA_FLAGS", "")
    with pytest.raises(RuntimeError, match="before JAX starts"):
        xla_setup.configure()
