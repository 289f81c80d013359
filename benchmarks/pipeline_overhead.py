"""Pass-pipeline + serving-overhead benchmark: the "zero-cost drop-in" claim.

Measures, on a reduced decoder config:

* pass-pipeline wall time: first `optimize()` call (trace + SILVIA rewrite
  + compile) vs steady-state calls that hit the trace cache,
* the trace/sub-jaxpr/analysis cache hit counters,
* decode throughput: per-step dispatch loop vs the fused lax.scan loop.

Emits one machine-readable line:  BENCH {json}

    PYTHONPATH=src python -m benchmarks.pipeline_overhead [--smoke]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from benchmarks import common
from repro import configs
from repro import core as silvia
from repro.launch import serve, xla_setup
from repro.models import lm
from repro.quant.qtensor import quantize_tree_for_serving


def _ms(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e3, out


def measure_pipeline_overhead(cfg, params, cache_len: int, batch: int,
                              steady_iters: int = 10) -> dict:
    """Per-call overhead of the optimize()-wrapped decode step: call 1 pays
    trace + rewrite + compile; calls 2..N must hit the trace cache."""
    def decode_fn(p, tok, kv, pos):
        return lm.decode_step(p, tok, kv, pos, cfg)

    opt = silvia.optimize(decode_fn, silvia.DEFAULT_PASSES)
    tok = jnp.zeros((batch, 1), jnp.int32)
    cache = lm.init_cache(cfg, batch, cache_len)
    pos = jnp.full((batch,), 1, jnp.int32)

    first_ms, (_, cache) = _ms(opt, params, tok, cache, pos)
    steady = []
    for _ in range(steady_iters):
        dt, (_, cache) = _ms(opt, params, tok, cache, pos)
        steady.append(dt)
    steady_ms = sorted(steady)[len(steady) // 2]          # median
    info = opt.cache_info()
    calls = info["trace_hits"] + info["trace_misses"]
    return {
        "first_call_ms": round(first_ms, 2),
        "steady_call_ms": round(steady_ms, 2),
        "overhead_ratio": round(first_ms / max(steady_ms, 1e-6), 1),
        "rewrite_ms": round(info["rewrite_ms"], 2),
        "trace_cache_hit_rate": round(info["trace_hits"] / calls, 3),
        **{k: info[k] for k in ("trace_hits", "trace_misses",
                                "subjaxpr_hits", "subjaxpr_misses",
                                "analysis_builds", "analysis_hits")},
    }


def measure_decode_tps(cfg, params, prompts, gen: int, cache_len: int,
                       silvia_passes: str = "off") -> dict:
    """tok/s of the per-step dispatch loop vs the fused lax.scan loop
    (warm: one throwaway run each so compile time is excluded)."""
    b = prompts.shape[0]
    out = {}
    for fused in (False, True):
        run = lambda: serve.generate(params, prompts, cfg, gen=gen,
                                     cache_len=cache_len,
                                     silvia_passes=silvia_passes,
                                     fused=fused)
        jax.block_until_ready(run())                      # warm-up/compile
        dt, _ = _ms(run)
        out["fused_tok_s" if fused else "stepwise_tok_s"] = round(
            b * gen / (dt / 1e3), 1)
    out["fused_speedup"] = round(out["fused_tok_s"]
                                 / max(out["stepwise_tok_s"], 1e-6), 2)
    return out


def run(smoke: bool = False) -> dict:
    cfg = configs.get_reduced_config("smollm-135m")
    batch, prompt_len = (2, 8) if smoke else (4, 32)
    gen = 8 if smoke else 32
    cache_len = prompt_len + gen
    rng = jax.random.PRNGKey(0)
    params = quantize_tree_for_serving(
        lm.init_params(rng, cfg, max_seq=cache_len + 8), "w8a8")
    prompts = jax.random.randint(rng, (batch, prompt_len), 0, cfg.vocab,
                                 dtype=jnp.int32)
    result = {
        "config": {"arch": "smollm-135m(reduced)", "batch": batch,
                   "prompt_len": prompt_len, "gen": gen, "quant": "w8a8",
                   "backend": jax.default_backend()},
        "pipeline": measure_pipeline_overhead(cfg, params, cache_len, batch),
        "decode": measure_decode_tps(cfg, params, prompts, gen, cache_len),
    }
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / few iters (CI)")
    args = ap.parse_args()
    xla_setup.configure()
    result = run(smoke=args.smoke)
    print(json.dumps(result, indent=2))
    common.write_bench_json(result, "pipeline_overhead")
    print("BENCH " + json.dumps(result))


if __name__ == "__main__":
    main()
