"""Continuous-batching engine vs static batched generate() under ragged
synthetic traffic (Poisson arrivals, mixed prompt/gen lengths).

The engine packs an ever-changing request mix into bucketed compiled decode
segments (launch/engine.py); the static path forms fixed batches in arrival
order, waits for each batch to fill, pads prompts/gens to the batch max,
and pays one compiled graph per distinct batch shape.  The gap between the
two is the serving analogue of the DSP under-utilization the paper's passes
reclaim.

`--family {dense,ssm,hybrid,encdec}` picks the model family served through
the SAME engine (the slot-state registry, models/slot_state.py); ssm/hybrid
rows demonstrate the family-agnostic slot layer (ssm: constant-size pages,
batch-bucket-only graph growth); encdec rows carry per-request encoder
features through the same segment loop.

`--mesh DxM` (e.g. `--mesh 8x1`, `--mesh 2x4`; a bare `8` means `8x1`)
serves the ENGINE row on a ("data", "model") mesh via the sharded
shard_map bundles (DESIGN.md sec. 7) -- on CI this runs under
XLA_FLAGS=--xla_force_host_platform_device_count=8.  The static row stays
single-device, so the speedup column also reflects the device-packing win;
outputs remain bit-identical either way (tests/test_sharded_serve.py).

Emits one machine-readable line:  BENCH {json}  with the family, aggregate
tok/s, p50/p99 per-request latency, mean slot occupancy, compiled-graph
counts (the engine's is bounded by its bucket sets), the **active lowering
census** {op: lowering id} from kernels/registry.py, the packed-op
dispatch census (nonzero: the quantized path really bound packed matmuls),
and the mesh layout when sharded.  With $BENCH_DIR set the payload is also
written to $BENCH_DIR/serve_throughput_<family>[_<mesh>].json for the CI
artifact + scripts/bench_compare.py regression gate.

`--chaos [SPEC]` serves the engine row under an injected-fault schedule
(launch/resilience.py ChaosSchedule; default spec exercises a few
deterministic seeded faults) plus a TTL mix on the traffic, and reports
the robustness counters: shed/expired/recovered requests, replayed
tokens, and `recovery_overhead` (replayed / delivered tokens -- the cost
of bit-exact recovery-as-replay).  The BENCH file gains a `_chaos`
suffix so the regression gate tracks chaos throughput separately.

`--device-loss [SPEC]` (requires `--mesh`) additionally KILLS devices
mid-run (distributed/elastic.py DeviceLossInjector; the default arm
loses half the mesh at the second decode segment) and reports the
elastic-serving metrics: degradation count, re-shard latency
(`reshard_s`), the final degraded mesh shape, and `post_shrink_tok_s`
(throughput after the last degrade -- what the shrunken mesh sustains).
Streams stay bit-identical throughout (tests/test_elastic.py).  The
BENCH file gains an `_elastic` suffix so the gate tracks degraded-mesh
throughput against its own baseline.

`--prefix-reuse` swaps the Poisson traffic for zipfian shared-prefix
traffic (scheduler.shared_prefix_traffic: a few hot system-prompt-style
prefixes dominate, fresh random tails) and serves it TWICE -- once with
the cross-request prefix cache on (launch/prefix_cache.py; this is the
gated `engine` row) and once cold (`engine_cold`).  The `prefix` block
reports the cache hit rate, prefill tokens skipped, warm-vs-cold p50
TTFT, and `bit_exact` (the warm token streams must equal the cold ones
byte for byte -- the pool's correctness bar).  The BENCH file gains a
`_prefix` suffix so the gate tracks warm throughput against its own
baseline.  Composes with `--chaos` and `--mesh`.  `--admit-budget N`
additionally caps uncached prefill tokens per admission round (the
fairness dial; deferral counts land in the engine row).

    PYTHONPATH=src python -m benchmarks.serve_throughput [--smoke]
        [--family {dense,ssm,hybrid,encdec}] [--silvia {off,add,muladd,all}]
        [--mesh DxM] [--chaos [SPEC]] [--device-loss [SPEC]]
        [--prefix-reuse] [--admit-budget N] [--n-requests N] [--rate R]
"""
from __future__ import annotations

import argparse
import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro import configs
from repro.distributed import context as dctx
from repro.distributed import elastic
from repro.kernels import registry
from repro.launch import resilience, scheduler, serve, xla_setup
from repro.launch.engine import ServeEngine
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.quant.qtensor import quantize_tree_for_serving


def _percentiles(latencies) -> dict:
    lat = np.asarray(sorted(latencies))
    return {"p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2)}


def _summary(requests, elapsed: float) -> dict:
    useful = sum(r.max_new_tokens for r in requests)
    return {
        "requests": len(requests),
        "useful_tokens": useful,
        "elapsed_s": round(elapsed, 3),
        "agg_tok_s": round(useful / max(elapsed, 1e-9), 1),
        **_percentiles([r.latency() for r in requests]),
    }


def parse_mesh(spec: str):
    """"8x1" / "2x4" -> (data, model); a bare "8" means data-only."""
    parts = spec.lower().split("x")
    if len(parts) == 1:
        parts = [parts[0], "1"]
    if len(parts) != 2 or not all(p.isdigit() for p in parts):
        raise ValueError(f"--mesh wants DxM (e.g. 2x4), got {spec!r}")
    return int(parts[0]), int(parts[1])


def run_engine(params, cfg, requests, *, n_slots, max_cache_len,
               segment_len, silvia_passes, prefill_chunk=None,
               enc_len=None, mesh=None, warmup=True, chaos=None,
               prefix_cache=None, admit_token_budget=None,
               return_tokens=False):
    kw = {"enc_len": enc_len} if enc_len is not None else {}
    scope = contextlib.nullcontext()
    if mesh is not None:
        mesh_obj = make_mesh(tuple(mesh), ("data", "model"))
        scope = dctx.mesh_scope(mesh_obj, ("data",), "model")
    with scope:
        eng = ServeEngine(params, cfg, n_slots=n_slots,
                          max_cache_len=max_cache_len,
                          segment_len=segment_len,
                          silvia_passes=silvia_passes,
                          prefill_chunk=prefill_chunk,
                          prefix_cache=prefix_cache,
                          admit_token_budget=admit_token_budget,
                          chaos=chaos if chaos is not None else "env", **kw)
    if warmup:
        # startup pre-compilation over the advertised traffic profile --
        # the static path below gets the matching per-shape warm pass
        eng.warmup(prompt_lens=sorted({r.prompt_len for r in requests}))
    clock = scheduler.FastForwardClock()
    t0 = clock.now()
    eng.run(requests, clock)
    end = clock.now()
    elapsed = end - t0
    info = eng.cache_info()
    out = _summary(eng.finished, elapsed)
    occ = info["occupancy"]
    out["mean_occupancy"] = round(
        occ["active_slot_segments"] / occ["slot_segments"], 3) \
        if occ["slot_segments"] else 0.0
    ttfts = [r.first_token_time - r.arrival_time for r in eng.finished
             if r.first_token_time is not None]
    out["ttft_p50_ms"] = round(float(np.percentile(ttfts, 50)) * 1e3, 2) \
        if ttfts else None
    out["graphs"] = info["graphs"]
    out["graph_bound"] = info["graph_bound"]
    out["graph_keys"] = [" ".join(map(str, k)) for k in info["graph_keys"]]
    out["has_length_axis"] = info["has_length_axis"]
    out["compactions"] = info["compactions"]
    out["lowerings"] = info["lowerings"]
    if "prefix_cache" in info:
        out["prefix_cache"] = info["prefix_cache"]
    if admit_token_budget is not None:
        out["admission"] = info["admission"]
    if "mesh" in info:
        out["mesh"] = info["mesh"]
    if "silvia" in info:
        out["silvia_trace"] = {k: info["silvia"][k]
                               for k in ("trace_hits", "trace_misses")}
    if chaos is not None:
        rb = info["robustness"]
        delivered = sum(len(r.tokens) for r in eng.finished)
        outcomes: dict = {}
        for r in eng.finished:
            outcomes[r.outcome] = outcomes.get(r.outcome, 0) + 1
        out["robustness"] = rb
        out["outcomes"] = outcomes
        out["delivered_tokens"] = delivered
        out["shed_rate"] = round(
            rb["shed"] / max(len(eng.finished), 1), 3)
        # cost of bit-exact recovery-as-replay: tokens regenerated with
        # teacher forcing per token actually delivered
        out["recovery_overhead"] = round(
            rb["replayed_tokens"] / max(delivered, 1), 3)
    degrade_at = out.get("mesh", {}).get("degrade_at", [])
    if degrade_at:
        # throughput the SHRUNKEN mesh sustained: tokens delivered after
        # the last degrade over the remaining serving time
        t_d = max(degrade_at)
        post = sum(len(r.tokens) for r in eng.finished
                   if r.finish_time is not None and r.finish_time >= t_d)
        out["post_shrink_tok_s"] = round(post / max(end - t_d, 1e-9), 1)
        out["degraded"] = info["mesh"]["degraded"]
        out["reshard_s"] = round(info["mesh"]["reshard_s"], 4)
        out["final_mesh"] = "x".join(
            str(v) for v in info["mesh"]["shape"].values())
    if return_tokens:
        return out, {r.rid: list(r.tokens) for r in eng.finished}
    return out


def run_static(params, cfg, requests, *, n_slots, silvia_passes,
               enc_len=None, warmup=True) -> dict:
    """PR-1 static path: batches of n_slots in arrival order; each batch
    waits until its last request arrives, pads every prompt/gen to the
    batch max, and decodes gen_max steps for every row."""
    encdec = cfg.family == "encdec"
    reqs = sorted(requests, key=lambda r: (r.arrival_time, r.rid))
    batches = [reqs[i:i + n_slots] for i in range(0, len(reqs), n_slots)]
    shapes = set()
    for batch in batches:
        pl = max(r.prompt_len for r in batch)
        gen = max(r.max_new_tokens for r in batch)
        shapes.add((len(batch), pl, gen, pl + gen))

    def inputs_for(batch, pl):
        prompts = np.zeros((len(batch), pl), np.int32)
        for i, r in enumerate(batch):
            prompts[i, :r.prompt_len] = r.prompt
        if not encdec:
            return jnp.asarray(prompts)
        feats = np.stack([np.asarray(r.features, np.float32)
                          for r in batch])
        return (jnp.asarray(feats).astype(jnp.dtype(cfg.dtype)),
                jnp.asarray(prompts))

    if warmup:
        for (b, pl, gen, cl) in sorted(shapes):
            prompts = jnp.zeros((b, pl), jnp.int32)
            if encdec:
                feats = jnp.zeros((b, enc_len, cfg.d_model),
                                  jnp.dtype(cfg.dtype))
                prompts = (feats, prompts)
            jax.block_until_ready(serve.generate(
                params, prompts, cfg, gen=gen, cache_len=cl,
                silvia_passes=silvia_passes))
    clock = scheduler.FastForwardClock()
    t0 = clock.now()
    for batch in batches:
        clock.wait_until(max(r.arrival_time for r in batch))
        pl = max(r.prompt_len for r in batch)
        gen = max(r.max_new_tokens for r in batch)
        toks = serve.generate(params, inputs_for(batch, pl), cfg, gen=gen,
                              cache_len=pl + gen,
                              silvia_passes=silvia_passes)
        toks = np.asarray(toks)
        done = clock.now()
        for i, r in enumerate(batch):
            r.tokens = [int(t) for t in toks[i, :r.max_new_tokens]]
            r.finish_time = done
    elapsed = clock.now() - t0
    out = _summary(reqs, elapsed)
    out["graphs"] = len(shapes)
    return out


FAMILY_ARCHS = {"dense": "smollm-135m", "ssm": "mamba2-2.7b",
                "hybrid": "jamba-v0.1-52b", "encdec": "whisper-small"}


# one pinned mid-run fault so even the tiny smoke trace exercises the
# recovery path, plus a seeded random schedule on top
DEFAULT_CHAOS = "segment:1,rate=0.04,seed=11,max=4"
# TTL mix for chaos rows: mostly deadline-free, a slice of generous TTLs
# so the deadline machinery runs without starving the throughput metric
CHAOS_TTLS = (None, None, None, 5.0)


def run(smoke: bool = False, silvia_passes: str = "off",
        n_requests: int | None = None, rate: float | None = None,
        family: str = "dense", mesh=None, chaos: str | None = None,
        device_loss: str | None = None, prefix_reuse: bool = False,
        admit_budget: int | None = None, trace_seed: int = 0) -> dict:
    arch = FAMILY_ARCHS[family]
    cfg = configs.get_reduced_config(arch)
    rate_arg = rate
    if smoke:
        n_req = n_requests or 8
        rate = rate or 50.0
        n_slots, seg, max_len = 2, 4, 64
        prompt_lens, gen_lens = (4, 8, 12), (2, 4, 8)
    else:
        n_req = n_requests or 32
        rate = rate or 20.0
        n_slots, seg, max_len = 4, 8, 128
        prompt_lens, gen_lens = (8, 16, 32, 48), (2, 8, 16, 32)
    if mesh is not None:
        # the slot axis must split over the data shards
        n_slots = max(n_slots, mesh[0])
    if device_loss is not None:
        if mesh is None:
            raise ValueError("--device-loss needs --mesh (there is no mesh "
                             "to shrink on a single device)")
        if device_loss == "auto":
            # lose half the mesh at the second decode segment
            device_loss = f"lose@segment:1={max(1, mesh[0] * mesh[1] // 2)}"
        chaos = device_loss if chaos is None else f"{chaos};{device_loss}"
    enc_len = None
    if family == "encdec":
        enc_len = 16 if smoke else 32
    # --prefix-reuse: zipfian shared-prefix traffic + chunked prefill for
    # chunkable families, so chain (per-chunk) sharing engages; others
    # share at exact-repeat (terminal) granularity
    pchunk = None
    if prefix_reuse:
        # denser trace + longer shared prefix than the plain rows: the
        # cache's win is queueing relief from skipped prefill chunks, so
        # the trace needs enough simultaneous arrivals (and enough shared
        # chunks per arrival) for the delta to clear run-to-run noise
        if smoke:
            n_prefixes, zipf_a, prefix_len, tail_lens = 3, 1.4, 32, (2, 6, 10)
            pchunk = 8 if family == "dense" else None
            n_req = n_requests or 16
            rate = rate_arg or 200.0
        else:
            n_prefixes, zipf_a, prefix_len, tail_lens = 4, 1.4, 64, (4, 8, 16)
            pchunk = 16 if family == "dense" else None
            n_req = n_requests or 48
            rate = rate_arg or 100.0
    rng = jax.random.PRNGKey(0)
    registry.reset_dispatch_counts()
    # force=True: reduced-config weights all sit under the production
    # quantization floors -- without it these "quantized" rows serve
    # bf16 graphs with zero packed-matmul dispatches (ROADMAP no-op)
    params = quantize_tree_for_serving(
        lm.init_params(rng, cfg, max_seq=max_len + 8), "w8a8", force=True)

    def traffic():
        if prefix_reuse:
            reqs = scheduler.shared_prefix_traffic(
                seed=trace_seed, n_requests=n_req, rate=rate,
                n_prefixes=n_prefixes, prefix_len=prefix_len,
                tail_lens=tail_lens, gen_lens=gen_lens, vocab=cfg.vocab,
                zipf_a=zipf_a,
                ttls=CHAOS_TTLS if chaos is not None else None)
        else:
            reqs = scheduler.synthetic_traffic(
                seed=trace_seed, n_requests=n_req, rate=rate,
                prompt_lens=prompt_lens, gen_lens=gen_lens, vocab=cfg.vocab,
                ttls=CHAOS_TTLS if chaos is not None else None)
        if family == "encdec":
            frng = np.random.default_rng(1)
            if prefix_reuse:
                # a small feature pool (assigned by rid) so exact repeats
                # can terminal-hit -- the features digest is part of the
                # pool key, fresh-noise features would force all-miss
                pool = [frng.standard_normal(
                    (enc_len, cfg.d_model)).astype(np.float32)
                    for _ in range(2)]
                for r in reqs:
                    r.features = pool[r.rid % 2]
            else:
                for r in reqs:
                    r.features = frng.standard_normal(
                        (enc_len, cfg.d_model)).astype(np.float32)
        return reqs

    def chaos_obj():
        # a fresh stateful schedule per engine run (fired-site bookkeeping
        # must not leak from the warm run into the cold one)
        if chaos is None:
            return None
        if "lose" in chaos:
            return elastic.DeviceLossInjector.parse(chaos)
        return resilience.ChaosSchedule.parse(chaos)

    result = {
        "config": {"arch": f"{arch}(reduced)", "family": family,
                   "n_requests": n_req,
                   "rate_req_s": rate, "n_slots": n_slots,
                   "segment_len": seg, "max_cache_len": max_len,
                   "prompt_lens": list(prompt_lens),
                   "gen_lens": list(gen_lens), "quant": "w8a8(forced)",
                   "silvia": silvia_passes, "enc_len": enc_len,
                   "mesh": None if mesh is None else f"{mesh[0]}x{mesh[1]}",
                   "chaos": chaos, "device_loss": device_loss,
                   "prefix_reuse": prefix_reuse,
                   "prefill_chunk": pchunk,
                   "admit_budget": admit_budget,
                   "devices": jax.device_count(),
                   "backend": jax.default_backend(),
                   "lowerings": registry.active_lowerings()},
    }
    if prefix_reuse:
        result["config"]["prefix_traffic"] = {
            "n_prefixes": n_prefixes, "prefix_len": prefix_len,
            "tail_lens": list(tail_lens), "zipf_a": zipf_a}
    engine_kw = dict(n_slots=n_slots, max_cache_len=max_len,
                     segment_len=seg, silvia_passes=silvia_passes,
                     enc_len=enc_len, mesh=mesh, prefill_chunk=pchunk,
                     admit_token_budget=admit_budget)
    if prefix_reuse:
        # the gated `engine` row is the WARM (pool-backed) run; the cold
        # run rides along for the TTFT delta and the bit-exactness bar
        warm, warm_toks = run_engine(params, cfg, traffic(),
                                     prefix_cache=256, chaos=chaos_obj(),
                                     return_tokens=True, **engine_kw)
        cold, cold_toks = run_engine(params, cfg, traffic(),
                                     chaos=chaos_obj(),
                                     return_tokens=True, **engine_kw)
        result["engine"] = warm
        result["engine_cold"] = cold
        result["prefix"] = {
            "hit_rate": warm["prefix_cache"]["hit_rate"],
            "prefill_tokens_skipped": warm["prefix_cache"]["tokens_skipped"],
            "pages_resident": warm["prefix_cache"]["pages_resident"],
            "pages_evicted": warm["prefix_cache"]["pages_evicted"],
            "ttft_warm_ms": warm["ttft_p50_ms"],
            "ttft_cold_ms": cold["ttft_p50_ms"],
            "bit_exact": (set(warm_toks) == set(cold_toks)
                          and all(warm_toks[k] == cold_toks[k]
                                  for k in warm_toks)),
        }
    else:
        result["engine"] = run_engine(params, cfg, traffic(),
                                      chaos=chaos_obj(), **engine_kw)
    result["static"] = run_static(params, cfg, traffic(), n_slots=n_slots,
                                  silvia_passes=silvia_passes,
                                  enc_len=enc_len)
    result["speedup_tok_s"] = round(
        result["engine"]["agg_tok_s"]
        / max(result["static"]["agg_tok_s"], 1e-9), 2)
    result["graphs_bounded"] = (result["engine"]["graphs"]
                                <= result["engine"]["graph_bound"])
    # packed-op dispatch census: nonzero quant_matmul proves the forced
    # quantization actually bound packed GEMMs into the compiled graphs
    result["packed_dispatches"] = registry.dispatch_counts()
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model/traffic (CI)")
    ap.add_argument("--family", default="dense",
                    choices=sorted(FAMILY_ARCHS),
                    help="model family served through the engine's "
                         "slot-state registry")
    ap.add_argument("--silvia", default="off",
                    choices=list(serve.SILVIA_PASS_SETS))
    ap.add_argument("--mesh", default=None,
                    help="serve the engine row sharded over a DxM "
                         "(data, model) mesh, e.g. 8x1 or 2x4 (needs that "
                         "many visible devices)")
    ap.add_argument("--chaos", nargs="?", const=DEFAULT_CHAOS, default=None,
                    metavar="SPEC",
                    help="serve the engine row under an injected-fault "
                         "schedule (resilience.ChaosSchedule syntax, e.g. "
                         "'segment:2;prefill:1' or 'rate=0.05,seed=3'); "
                         f"bare --chaos uses '{DEFAULT_CHAOS}'")
    ap.add_argument("--device-loss", nargs="?", const="auto", default=None,
                    metavar="SPEC",
                    help="kill mesh devices mid-run and serve on the "
                         "re-planned degraded mesh (DeviceLossInjector "
                         "syntax, e.g. 'lose@segment:1=4'); bare "
                         "--device-loss loses half the mesh at segment 1; "
                         "requires --mesh")
    ap.add_argument("--prefix-reuse", action="store_true",
                    help="zipfian shared-prefix traffic served warm (with "
                         "the cross-request prefix cache) AND cold; "
                         "reports hit rate, prefill tokens skipped, "
                         "warm/cold TTFT and bit-exactness")
    ap.add_argument("--admit-budget", type=int, default=None,
                    metavar="N",
                    help="cap uncached prefill tokens per admission round "
                         "(token-budget admission fairness; deferrals are "
                         "reported in the engine row)")
    ap.add_argument("--n-requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--trace-seed", type=int, default=0,
                    help="seed for the synthetic/shared-prefix traffic "
                         "trace (one knob for BOTH builders; baselines "
                         "use the default 0)")
    args = ap.parse_args()
    xla_setup.configure()
    mesh = parse_mesh(args.mesh) if args.mesh else None
    if mesh is not None and mesh[0] * mesh[1] > jax.device_count():
        raise SystemExit(
            f"--mesh {args.mesh} needs {mesh[0] * mesh[1]} devices, have "
            f"{jax.device_count()} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N to simulate)")
    if args.device_loss is not None and mesh is None:
        raise SystemExit("--device-loss needs --mesh (no mesh to shrink)")
    result = run(smoke=args.smoke, silvia_passes=args.silvia,
                 n_requests=args.n_requests, rate=args.rate,
                 family=args.family, mesh=mesh, chaos=args.chaos,
                 device_loss=args.device_loss,
                 prefix_reuse=args.prefix_reuse,
                 admit_budget=args.admit_budget,
                 trace_seed=args.trace_seed)
    print(json.dumps(result, indent=2))
    name = f"serve_throughput_{args.family}"
    if args.mesh:
        name += f"_{args.mesh}"
    if args.device_loss is not None:
        name += "_elastic"
    elif args.chaos is not None:
        name += "_chaos"
    if args.prefix_reuse:
        name += "_prefix"
    common.write_bench_json(result, name)
    print("BENCH " + json.dumps(result))


if __name__ == "__main__":
    main()
