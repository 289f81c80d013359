"""Self-speculative decoding benchmark: acceptance rate and
tokens-per-target-dispatch speedup vs the non-speculative engine on the
SAME trace, with a byte-identity check over every stream.

    PYTHONPATH=src python benchmarks/spec_decode.py --smoke

Two draft variants are reported:

* ``same`` -- the target's own weights as draft.  Acceptance is a pure
  function of (seed, rid, token prefix), so tokens_per_dispatch is
  DETERMINISTIC: this is the row the CI regression gate
  (scripts/bench_compare.py, baseline spec_decode_dense_smoke.json)
  arms on.
* ``weak`` -- same config, fresh weights: frequently-wrong drafts that
  exercise the partial-acceptance rollback path and put a realistic
  floor under the acceptance numbers.

The streams of BOTH variants must equal the non-spec engine's bytes
(``bit_exact``); if they do not, the benchmark exits non-zero -- a perf
number for a wrong stream is not a number."""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from benchmarks import common
from repro import configs
from repro.launch import scheduler, xla_setup
from repro.launch.engine import ServeEngine, SpecDecodeConfig
from repro.models import lm

FAMILY_ARCHS = {"dense": "smollm-135m", "ssm": "mamba2-2.7b",
                "hybrid": "jamba-v0.1-52b"}


def _traffic(cfg, n_req, rate, prompt_lens, gen_lens, trace_seed):
    mix = (None,
           scheduler.SamplingParams(temperature=0.8, top_k=8, seed=5),
           scheduler.GREEDY,
           scheduler.SamplingParams(temperature=1.0, top_p=0.9, seed=2))
    return scheduler.synthetic_traffic(
        seed=trace_seed, n_requests=n_req, rate=rate,
        prompt_lens=prompt_lens, gen_lens=gen_lens, vocab=cfg.vocab,
        sampling_mix=mix)


def _run(params, cfg, requests, *, n_slots, max_len, seg, spec_decode):
    eng = ServeEngine(params, cfg, n_slots=n_slots, max_cache_len=max_len,
                      segment_len=seg, spec_decode=spec_decode)
    t0 = time.perf_counter()
    out = eng.run(requests, scheduler.FastForwardClock())
    elapsed = time.perf_counter() - t0
    return out, elapsed, eng.cache_info()


def run(smoke: bool = False, family: str = "dense", k: int = 3,
        n_requests: int | None = None, trace_seed: int = 0) -> dict:
    cfg = configs.get_reduced_config(FAMILY_ARCHS[family])
    if smoke:
        n_req = n_requests or 8
        n_slots, seg, max_len = 4, 4, 64
        prompt_lens, gen_lens = (5, 9, 12), (6, 8, 10)
    else:
        n_req = n_requests or 24
        n_slots, seg, max_len = 8, 8, 128
        prompt_lens, gen_lens = (8, 16, 24), (8, 16, 24)
    params = lm.init_params(jax.random.PRNGKey(0), cfg,
                            max_seq=max_len + 8)
    weak = lm.init_params(jax.random.PRNGKey(9), cfg, max_seq=max_len + 8)
    kw = dict(n_slots=n_slots, max_len=max_len, seg=seg)

    def trace():
        return _traffic(cfg, n_req, 1e9, prompt_lens, gen_lens,
                        trace_seed)

    ref, ref_s, ref_info = _run(params, cfg, trace(), spec_decode=None,
                                **kw)
    ref_dispatches = ref_info["dispatch_sites"]["segment"]
    result = {
        "config": {"family": family, "k": k, "n_requests": n_req,
                   "smoke": smoke, "trace_seed": trace_seed},
        "nonspec": {"elapsed_s": round(ref_s, 3),
                    "segment_dispatches": ref_dispatches},
    }
    ok = True
    for label, dparams in (("same", params), ("weak", weak)):
        sd = SpecDecodeConfig(draft_params=dparams, draft_cfg=cfg, k=k)
        out, sec, info = _run(params, cfg, trace(), spec_decode=sd, **kw)
        bit_exact = set(out) == set(ref) and all(
            np.array_equal(out[r], ref[r]) for r in ref)
        ok = ok and bit_exact
        row = dict(info["spec_decode"])
        row.pop("draft", None)
        row.update({
            "elapsed_s": round(sec, 3),
            "bit_exact": bit_exact,
            # dispatch-count speedup: target dispatches the non-spec
            # engine needed per target dispatch the spec engine needed
            "dispatch_speedup": round(
                ref_dispatches / max(info["spec_decode"]
                                     ["target_dispatches"], 1), 2),
        })
        result[label] = row
    # the gated metric lives at the payload top level under the name
    # bench_compare._metric reads: the DETERMINISTIC same-draft row
    result["spec_decode"] = {
        "tokens_per_dispatch": result["same"]["tokens_per_dispatch"],
        "acceptance_rate": result["same"]["acceptance_rate"],
    }
    result["bit_exact"] = ok
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model/traffic (CI)")
    ap.add_argument("--family", default="dense",
                    choices=sorted(FAMILY_ARCHS))
    ap.add_argument("--k", type=int, default=3,
                    help="draft tokens per speculative round")
    ap.add_argument("--n-requests", type=int, default=None)
    ap.add_argument("--trace-seed", type=int, default=0,
                    help="seed for the traffic trace (baselines use the "
                         "default 0)")
    args = ap.parse_args()
    xla_setup.configure()
    result = run(smoke=args.smoke, family=args.family, k=args.k,
                 n_requests=args.n_requests, trace_seed=args.trace_seed)
    print(json.dumps(result, indent=2))
    name = f"spec_decode_{args.family}"
    if args.smoke:
        name += "_smoke"
    common.write_bench_json(result, name)
    print("BENCH " + json.dumps(result))
    if not result["bit_exact"]:
        print("spec_decode: streams diverged from the non-spec engine",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
