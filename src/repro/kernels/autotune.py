"""Block-size autotuner for the packed Pallas kernels.

The paper's flow bakes its packing decisions in at synthesis time; the TPU
serving analogue of that "pay once" philosophy is an AutoDSE-style search
over the kernel tile sizes with a *persistent on-disk cache*: the first time
a (kernel, shape..., backend) signature is seen with tuning enabled, every
candidate block is timed and the winner is written to a JSON cache; every
later process start reads the cache and pays nothing.

    from repro.kernels import autotune
    autotune.enable(True)                  # or REPRO_AUTOTUNE=1
    block = autotune.resolve("quant_matmul", m, k, n)   # Mosaic (default
    block = autotune.resolve("simd_add", rows, cols,    # lowering id is
                             lowering="gpu-pallas",     # "tpu-pallas")
                             interpret=False)

Kernels call `resolve()` when invoked with `block=None`; with tuning
disabled and no cache entry it falls through to the kernel's static default,
so the tuner is strictly opt-in.

Covered kinds: the GEMMs ("quant_matmul", "packed_w4_matmul"; 3-D
(bm, bn, bk) blocks keyed on M/K/N) and the SWAR units ("simd_add",
"mul4", "muladd2"; 2-D (bm, bn) blocks keyed on their padded 2-D layout,
plus the chain length for muladd2).

Cache keys (v2) include the **lowering id** ("tpu-pallas" / "gpu-pallas" --
the registry families that own tunable Pallas kernels) and the **execution
mode** ("native" / "interp") on top of kind/shape/backend.  v1 keyed on
`jax.default_backend()` alone, so interpret-mode CPU tuning results could
shadow real TPU timings for the same shapes; v2 entries can never collide
across lowerings or modes, and stale v1 entries are simply never read.

Cache location: $REPRO_AUTOTUNE_CACHE, else ~/.cache/repro/autotune.json.
On-disk format and failure handling live in kernels/diskcache.py: a
schema-versioned, checksummed envelope written atomically under a file
lock -- a corrupt/truncated/foreign-version cache file warns and
recomputes, it can never crash an engine.
"""
from __future__ import annotations

import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import diskcache

CACHE_VERSION = 2   # bumped: v2 keys fold in (lowering id, interpret mode)

DEFAULT_BLOCK = (256, 256, 512)

# Candidate (bm, bn, bk) tiles: all keep x/w/acc blocks within a small slice
# of the ~16 MiB VMEM budget (see quant_matmul.py header for the arithmetic).
CANDIDATE_BLOCKS = (
    (128, 128, 256),
    (128, 256, 512),
    (256, 128, 512),
    (256, 256, 256),
    (256, 256, 512),
    (256, 512, 512),
    (512, 256, 512),
)

# (bm, bn) tiles for the elementwise SWAR kernels.  pad_to_2d flattens to
# (rows, 128) -- one vreg-width column -- so only bm varies; bn is pinned
# at 128 (a larger bn would be clamped to cols inside the kernels anyway).
DEFAULT_BLOCK_2D = (256, 128)
CANDIDATE_BLOCKS_2D = (
    (32, 128),
    (64, 128),
    (128, 128),
    (256, 128),
    (512, 128),
    (1024, 128),
)

# kind -> (default block, candidate list); the SWAR kinds use 2-D blocks
KIND_SPECS = {
    "quant_matmul": (DEFAULT_BLOCK, CANDIDATE_BLOCKS),
    "packed_w4_matmul": (DEFAULT_BLOCK, CANDIDATE_BLOCKS),
    "simd_add": (DEFAULT_BLOCK_2D, CANDIDATE_BLOCKS_2D),
    "mul4": (DEFAULT_BLOCK_2D, CANDIDATE_BLOCKS_2D),
    "mul4_split": (DEFAULT_BLOCK_2D, CANDIDATE_BLOCKS_2D),
    "muladd2": (DEFAULT_BLOCK_2D, CANDIDATE_BLOCKS_2D),
}


def default_block(kind: str) -> tuple:
    return KIND_SPECS[kind][0]

_enabled = os.environ.get("REPRO_AUTOTUNE", "") not in ("", "0", "false")
_cache: dict | None = None


def enable(on: bool = True) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def cache_path() -> pathlib.Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return pathlib.Path(env)
    return pathlib.Path.home() / ".cache" / "repro" / "autotune.json"


def _load() -> dict:
    global _cache
    if _cache is None:
        _cache = diskcache.load(cache_path(), CACHE_VERSION)
    return _cache


def _save() -> None:
    global _cache
    path = cache_path()
    # lock the read-merge-write cycle: another process may have tuned
    # other shapes since we loaded; our in-process entries win only on
    # key collision.  diskcache handles atomicity and read-only FS
    # (tuning still works in-process when store() fails)
    with diskcache.locked(path):
        on_disk = diskcache.load(path, CACHE_VERSION)
        _cache = {**on_disk, **(_cache or {})}
        diskcache.store(path, CACHE_VERSION, _cache)


def _interpret_default(lowering: str) -> bool:
    """A Pallas lowering tunes in the same mode it runs in (the shared
    common.interpret_default_for rule, so cache-key mode and kernel
    defaults can never disagree)."""
    from repro.kernels import common
    return common.interpret_default_for(lowering)


def _key(kind: str, *dims: int, lowering: str = "tpu-pallas",
         interpret: bool | None = None) -> str:
    if interpret is None:
        interpret = _interpret_default(lowering)
    mode = "interp" if interpret else "native"
    return (f"v{CACHE_VERSION}:{kind}:{'x'.join(map(str, dims))}:"
            f"{jax.default_backend()}:{lowering}:{mode}")


def lookup(kind: str, *dims: int, lowering: str = "tpu-pallas",
           interpret: bool | None = None) -> tuple | None:
    ent = _load().get(_key(kind, *dims, lowering=lowering,
                           interpret=interpret))
    if ent is None:
        return None
    return tuple(ent["block"])


def resolve(kind: str, *dims: int, lowering: str = "tpu-pallas",
            interpret: bool | None = None) -> tuple:
    """Best known block for this (shape, lowering, mode): cache hit >
    (tune now if enabled) > the kind's static default."""
    hit = lookup(kind, *dims, lowering=lowering, interpret=interpret)
    if hit is not None:
        return hit
    if _enabled:
        return tune(kind, *dims, lowering=lowering, interpret=interpret)
    return default_block(kind)


def _time_call(fn, *args, iters: int = 3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _tune_runner(kind: str, dims: tuple, lowering: str, interpret: bool):
    """Synthetic-operand closure for one (kind, lowering): run(blk) ->
    kernel output, invoked in the same mode the cache key records."""
    # lazy imports: the kernels import this module for resolve()
    from repro.kernels import (gpu_pallas, mul4, muladd2, packed_matmul,
                               quant_matmul, simd_add)

    gpu = lowering == "gpu-pallas"
    rng = np.random.default_rng(0)
    if kind in ("quant_matmul", "packed_w4_matmul"):
        m, k, n = dims
        x = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
        if kind == "packed_w4_matmul":
            w = jnp.asarray(rng.integers(-128, 128, (k, n // 2)), jnp.int8)
            fn = gpu_pallas.packed_w4_matmul_acc if gpu else \
                packed_matmul.packed_w4_matmul_acc
            return lambda blk: fn(x, w, block=blk, interpret=interpret)
        w = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
        fn = gpu_pallas.quant_matmul_acc if gpu else \
            quant_matmul.quant_matmul_acc
        return lambda blk: fn(x, w, block=blk, interpret=interpret)
    if kind == "simd_add":
        rows, cols = dims
        x = jnp.asarray(rng.integers(0, 1 << 32, (rows, cols),
                                     dtype=np.uint32))
        y = jnp.asarray(rng.integers(0, 1 << 32, (rows, cols),
                                     dtype=np.uint32))
        fn = gpu_pallas.simd_add_packed if gpu else simd_add.simd_add_packed
        return lambda blk: fn(x, y, block=blk, interpret=interpret)
    if kind in ("mul4", "mul4_split"):
        rows, cols = dims
        a = jnp.asarray(rng.integers(-8, 8, (4, rows, cols)), jnp.int8)
        b = jnp.asarray(rng.integers(-8, 8, (rows, cols)), jnp.int8)
        if kind == "mul4_split":
            if gpu:
                # no gpu-pallas mul4_split kernel exists; timing the Mosaic
                # one here would persist a mislabeled gpu-pallas cache entry
                raise ValueError("mul4_split has no gpu-pallas kernel")
            return lambda blk: mul4.mul4_split(a, b, block=blk,
                                               interpret=interpret)
        fn = gpu_pallas.mul4 if gpu else mul4.mul4_full32
        return lambda blk: fn(a, b, block=blk, interpret=interpret)
    if kind == "muladd2":
        nc, rows, cols = dims
        a = jnp.asarray(rng.integers(-8, 8, (nc, rows, cols)), jnp.int8)
        b = jnp.asarray(rng.integers(-8, 8, (nc, rows, cols)), jnp.int8)
        c = jnp.asarray(rng.integers(-128, 128, (nc, rows, cols)), jnp.int8)
        fn = gpu_pallas.muladd2 if gpu else muladd2.muladd2
        return lambda blk: fn(a, b, c, block=blk, interpret=interpret)
    raise ValueError(f"unknown autotune kind: {kind}")


def tune(kind: str, *dims: int, candidates=None, iters: int = 3,
         lowering: str = "tpu-pallas", interpret: bool | None = None) -> tuple:
    """Time every candidate block on synthetic operands, persist and
    return the winner.  Runs real kernel invocations, so only call at
    set-up time (resolve() does, once per shape signature)."""
    if lowering not in ("tpu-pallas", "gpu-pallas"):
        # only the Pallas families have tunable blocks; timing anything
        # else here would persist a mislabeled entry to the shared cache
        raise ValueError(f"no tunable kernels for lowering {lowering!r} "
                         "(tunable: tpu-pallas, gpu-pallas)")
    if candidates is None:
        candidates = KIND_SPECS[kind][1]
    if interpret is None:
        interpret = _interpret_default(lowering)
    run = _tune_runner(kind, dims, lowering, interpret)

    best_blk, best_us = default_block(kind), float("inf")
    results = {}
    first_err = None
    for blk in candidates:
        try:
            us = _time_call(jax.jit(run, static_argnums=0), blk, iters=iters)
        except Exception as e:  # noqa: BLE001
            first_err = first_err or e
            continue  # candidate illegal on this backend/shape
        results[str(blk)] = round(us, 1)
        if us < best_us:
            best_blk, best_us = blk, us
    if not results:
        # every candidate failed.  Natively that is a kernel the device
        # refuses at every block, which the default would hit too: raise.
        # In interpret mode fall back without recording (a cache hit would
        # suppress retries forever)
        if not interpret:
            raise RuntimeError(
                f"autotune: every {kind} block failed on {lowering} for "
                f"{dims}; first error: {first_err}") from first_err
        return default_block(kind)
    cache = _load()
    cache[_key(kind, *dims, lowering=lowering, interpret=interpret)] = {
        "block": list(best_blk), "us": round(best_us, 1),
        "candidates": results,
    }
    _save()
    return best_blk
