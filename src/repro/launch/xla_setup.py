"""How an entry point sets up XLA: exact rounding and the compile cache.

Entry points (`chip_smoke.py`, the serve and train CLIs, the benchmark
mains) call `configure()` once, first thing, before anything starts JAX's
backend.  It is never called while a module is imported; the tests run
at XLA's default flags.

* Exact rounding.  `XLA_FLAGS` gains `--xla_allow_excess_precision=false`.
  By default XLA may keep a bf16 value at f32 inside a fusion, so two
  programs that fuse differently -- the `tpu-pallas` and `ref` lowerings,
  a mesh and one device -- round differently, and on a TPU v5e greedy
  tokens from random weights part within a few dozen steps.  With every
  convert rounded, each program follows the source's precision, which is
  what the bit-identical promises of DESIGN.md sec. 6-7 rest on.  XLA
  reads the flag when its backend starts, so `configure()` raises if the
  backend is already up; libtpu refuses the flag in `LIBTPU_INIT_ARGS`.
  An `XLA_FLAGS` that already names the flag is left as the user set it.
* Compile cache.  If `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
  itself and nothing else is set here.  Otherwise the cache lives at
  `<checkout>/.jax_cache`, a fixed path: the directory is part of every
  entry's key, so a name made from a temp dir, a pid or the time would
  never hit.  Git ignores it.
"""
from __future__ import annotations

import os
import pathlib
from typing import NamedTuple

import jax
from jax._src import xla_bridge

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
EXACT_ROUNDING = "--xla_allow_excess_precision=false"


class Setup(NamedTuple):
    cache_dir: str
    exact_rounding: bool


def configure() -> Setup:
    """Set exact rounding and place the compile cache; returns what is in
    effect."""
    exact = _exact_rounding()         # raises before anything is set
    return Setup(_cache_dir(), exact)


def _exact_rounding() -> bool:
    flags = os.environ.get("XLA_FLAGS", "")
    if EXACT_ROUNDING.split("=")[0] not in flags:
        if xla_bridge.backends_are_initialized():
            raise RuntimeError(
                "xla_setup.configure() must run before JAX starts its "
                "backend: XLA_FLAGS is read only then")
        flags = os.environ["XLA_FLAGS"] = f"{flags} {EXACT_ROUNDING}".strip()
    return EXACT_ROUNDING in flags.split()


def _cache_dir() -> str:
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
