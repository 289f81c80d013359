"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
serving-and-compare path holds on the CPU at a small width (Mosaic
kernels in interpret mode)."""
import dataclasses
import importlib.util
import pathlib

import jax
import pytest

from repro import configs
from repro.kernels import registry
from repro.models import lm

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "N_SLOTS", 4)
    monkeypatch.setattr(mod, "MAX_CACHE_LEN", 64)
    monkeypatch.setattr(mod, "SEGMENT_LEN", 4)
    monkeypatch.setattr(mod, "NEW_TOKENS", 6)
    monkeypatch.setattr(mod, "PROMPT_LENS", (4, 9, 16, 30))
    return mod


def test_exits_nonzero_without_tpu(smoke, capsys, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax.devices()[0].platform != "tpu"
    # the backend is up, so main() cannot add its XLA flag any more
    monkeypatch.setenv("XLA_FLAGS", "")
    assert smoke.main([]) != 0
    assert "before JAX starts its backend" in capsys.readouterr().err
    monkeypatch.setenv("XLA_FLAGS", "--xla_allow_excess_precision=false")
    assert smoke.main([]) != 0
    assert smoke.main(["--chips", "4"]) != 0
    captured = capsys.readouterr()
    assert "needs 1 TPU chip(s)" in captured.err
    assert '"ok"' not in captured.out


@pytest.mark.parametrize("fmt", ["w8a8", "w4a8"])
def test_one_chip_path_matches_ref_lowering(smoke, fmt, capsys):
    # wide enough that the production quantization floors quantize the
    # layer weights, as they do at full width
    cfg = dataclasses.replace(
        configs.get_config(smoke.ARCH), n_layers=1, d_model=256,
        n_heads=4, n_kv=4, d_ff=512, vocab=512)
    params = lm.init_params(jax.random.PRNGKey(0), cfg, max_seq=64)
    with registry.force("tpu-pallas"):
        n_tok = smoke.one_chip(cfg, params, fmt, seed=0)
    assert n_tok == 4 * 6
    out = capsys.readouterr().out
    assert f"[{fmt}] tokens identical to ref lowering: True" in out


def test_divergence_is_located(smoke):
    import numpy as np
    a = {0: np.array([1, 2, 3]), 1: np.array([4, 5, 6])}
    assert smoke.first_divergence(a, dict(a)) is None
    assert smoke.first_divergence(a, {0: a[0], 1: np.array([4, 9, 6])}) \
        == (1, 1)
    assert smoke.first_divergence(a, {0: a[0][:2], 1: a[1]}) == (0, 2)
