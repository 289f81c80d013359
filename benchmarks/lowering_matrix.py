"""Per-op, per-lowering throughput of the kernel registry, plus the cost
of resolution itself.

For every packed op the registry serves, time a jitted dispatch under each
forced lowering and report logical narrow-op throughput -- the Ops/Unit
economics of the paper measured across technology bindings instead of
across DSP shapes.  Also times `registry.resolve()` cold (first call after
`invalidate()`, pays the env parse) and warm (cached), verifying the
satellite claim that resolution is pay-once, not per-trace.

By default only lowerings that run NATIVELY on this host are timed (ref +
cpu-vector on CPU, plus tpu-/gpu-pallas on their own backends);
``--interpret`` adds the foreign Pallas families in interpret mode (their
timings measure the interpreter, not the kernel -- useful only as a
liveness check).

Emits one machine-readable line:  BENCH {json}

    PYTHONPATH=src python -m benchmarks.lowering_matrix [--smoke]
        [--interpret] [--iters N]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.kernels import ref, registry, timings
from repro.launch import xla_setup


def _native_lowerings() -> list:
    native = registry.native_lowering()
    return ["ref"] + ([native] if native else [])


def _cases(smoke: bool):
    """op -> ((args, kwargs), logical narrow-op count per call)."""
    rng = np.random.default_rng(0)
    shape = (64, 128) if smoke else (512, 1024)
    m, k, n = (16, 128, 64) if smoke else (256, 1024, 1024)
    size = int(np.prod(shape))

    i8 = lambda lo, hi, s: jnp.asarray(rng.integers(lo, hi, s), jnp.int8)
    xs = [i8(-128, 128, shape) for _ in range(4)]
    ys = [i8(-128, 128, shape) for _ in range(4)]
    ma = [i8(-8, 8, shape) for _ in range(4)]
    mb = [i8(-8, 8, shape) for _ in range(4)]
    mc = [i8(-128, 128, shape) for _ in range(4)]
    a4 = [i8(-8, 8, shape) for _ in range(4)]
    b4 = i8(-8, 8, shape)
    x_q = i8(-128, 128, (m, k))
    w_q = i8(-128, 128, (k, n))
    w_p = ref.pack_w4(i8(-8, 8, (k, n)))
    x_s = jnp.asarray(rng.random((m, 1)), jnp.float32)
    w_s = jnp.asarray(rng.random((1, n)), jnp.float32)

    return {
        "simd_add": (((xs, ys), {"lane_bits": 8}), 4 * size),
        # chain n=4: 2n muls + 2(n-1) adds per element (paper Eq. 1)
        "muladd2": (((ma, mb, mc), {}), (2 * 4 + 2 * 3) * size),
        "mul4": (((a4, b4), {}), 4 * size),
        "quant_matmul": (((x_q, w_q, x_s, w_s), {}), 2 * m * k * n),
        "packed_w4_matmul": (((x_q, w_p, x_s, w_s), {}), 2 * m * k * n),
    }


def _time_dispatch(op, args, kwargs, lid, iters: int) -> float:
    """us per jitted dispatch under the forced lowering."""
    with registry.force(**{op: lid}):
        fn = jax.jit(lambda *a: registry.dispatch(op, *a, **kwargs))
        out = fn(*args)                      # trace+compile inside force
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _resolution_overhead(iters: int = 200) -> dict:
    registry.invalidate()
    t0 = time.perf_counter()
    registry.resolve("simd_add", lane_bits=8)
    cold_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    for _ in range(iters):
        registry.resolve("simd_add", lane_bits=8)
    warm_us = (time.perf_counter() - t0) / iters * 1e6
    return {"cold_us": round(cold_us, 2), "warm_us": round(warm_us, 3)}


def run(smoke: bool = False, interpret: bool = False,
        iters: int = 20, record: bool = False) -> dict:
    lids = _native_lowerings()
    if interpret:
        lids += [l for l in ("tpu-pallas", "gpu-pallas") if l not in lids]
    backend = jax.default_backend()
    rows = []
    for op, ((args, kwargs), n_ops) in _cases(smoke).items():
        for lid in lids:
            us = _time_dispatch(op, args, kwargs, lid, iters)
            rows.append({
                "op": op, "lowering": lid, "us_per_call": round(us, 1),
                "gops_s": round(n_ops / us * 1e-3, 2),
            })
            if record and not smoke:
                # persist serving-scale timings only: smoke shapes are
                # the noise PR 4 refused to flip priorities on
                timings.record(backend, op, lid, us, shape="full",
                               iters=iters)
    if record and not smoke:
        registry.invalidate()   # stored winners now steer CPU defaults
    return {
        "config": {"backend": backend, "smoke": smoke,
                   "iters": iters, "lowerings_timed": lids,
                   "recorded": bool(record and not smoke)},
        "active_lowerings": registry.active_lowerings(),
        "resolution": _resolution_overhead(),
        "rows": rows,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, few iters (CI)")
    ap.add_argument("--interpret", action="store_true",
                    help="also time foreign Pallas families in interpret "
                         "mode (liveness check, not a perf number)")
    ap.add_argument("--record", action="store_true",
                    help="persist per-(op, lowering) timings to the "
                         "kernels/timings.py cache so registry auto-"
                         "defaults use measurements (full shapes only; "
                         "--smoke runs never record)")
    ap.add_argument("--iters", type=int, default=None)
    args = ap.parse_args()
    xla_setup.configure()
    iters = args.iters or (5 if args.smoke else 20)
    result = run(smoke=args.smoke, interpret=args.interpret, iters=iters,
                 record=args.record)
    print(json.dumps(result, indent=2))
    common.write_bench_json(result, "lowering_matrix")
    print("BENCH " + json.dumps(result))


if __name__ == "__main__":
    main()
