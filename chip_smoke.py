"""Serve qwen1.5-0.5b at full width through ServeEngine on a TPU and check it.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # a (data 2, model 2) mesh over 4 chips

One chip: for w8a8 and then w4a8, the weights (random, from --seed) are
quantized with the production size floors and 8 greedy requests (prompts
of 16 to 600 tokens, 32 new tokens each) are served through a
`ServeEngine` on the `tpu-pallas` lowering, twice (a cold run that
compiles, then a warm one).  The same requests are then served on a
second engine built and run under `registry.force("ref")`.  Every token
must match: both lowerings accumulate in exact int32 (checked on its own
against a host product for every projection shape), and
`launch/xla_setup.configure()` turns XLA's excess precision off, as for
every entry point, so both programs round bf16 values exactly where the
source does.  An `XLA_FLAGS` that names the flag itself wins (with
`--xla_allow_excess_precision=true` the comparisons show what default
flags give).

Four chips: only the sharded path and what it is compared with.  The w8a8
requests are served by an engine built under `mesh_scope` on the 2x2 mesh
and by a single-chip engine in the same process; the tokens must match
(DESIGN.md sec. 7).

The script fails (exit code 1, no result line) unless JAX finds a TPU,
every outcome is OK with no recovery, and every check holds.  Its last
line of output is one JSON object naming the device.  The rates it
prints are a smoke rate, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"
N_SLOTS = 8
MAX_CACHE_LEN = 1024
SEGMENT_LEN = 16
NEW_TOKENS = 32
# four prompt buckets (16, 128, 512, 1024) keep the prefill compiles few
PROMPT_LENS = (16, 100, 128, 300, 420, 512, 560, 600)


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(label: str, value) -> None:
    print(f"{label}: {value}", flush=True)


def make_requests(vocab: int, seed: int, rid0: int = 0):
    import numpy as np

    from repro.launch import scheduler
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, n, dtype=np.int32)
               for n in PROMPT_LENS]
    return [scheduler.Request(rid=rid0 + i, prompt=p,
                              max_new_tokens=NEW_TOKENS)
            for i, p in enumerate(prompts)]


def serve(params, cfg, requests):
    """Build a ServeEngine (under whatever mesh scope or forced lowering
    the caller holds), serve `requests` and check every outcome.  Returns
    (engine, {rid: tokens}, seconds)."""
    from repro.launch.engine import ServeEngine

    eng = ServeEngine(params, cfg, n_slots=N_SLOTS,
                      max_cache_len=MAX_CACHE_LEN, segment_len=SEGMENT_LEN,
                      silvia_passes="all", chaos=None)
    t0 = time.perf_counter()
    out = eng.run(requests)
    secs = time.perf_counter() - t0
    check_engine(eng, [r.rid for r in requests])
    return eng, out, secs


def check_engine(eng, rids) -> None:
    from repro.launch import resilience
    info = eng.cache_info()
    rb = info["robustness"]
    results = eng.results()
    bad = {rid: (results[rid].outcome, results[rid].error)
           for rid in rids
           if rid not in results or results[rid].outcome != resilience.OK}
    check(not bad and rb["recoveries"] == 0 and rb["errors"] == 0,
          f"outcomes {bad}; recoveries {rb['recoveries']}, errors "
          f"{rb['errors']}; first error: {info['first_error']}")


def first_divergence(a: dict, b: dict):
    """(rid, step) of the first differing token, or None."""
    import numpy as np
    for rid in sorted(a):
        x, y = np.asarray(a[rid]), np.asarray(b.get(rid, []))
        n = min(len(x), len(y))
        diff = np.nonzero(x[:n] != y[:n])[0]
        if len(diff) or len(x) != len(y):
            return rid, int(diff[0]) if len(diff) else n
    return None


def kernels_exact(qparams, fmt: str, seed: int) -> list:
    """Run the Mosaic GEMM on the first-layer weights of each quantized
    projection shape, at decode and prefill M, and check its int32
    accumulator against a float64 product on the host (exact: every sum
    is far below 2**53).  Returns the (M, K, N) shapes checked."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import packed_matmul, quant_matmul, ref
    from repro.quant.qtensor import QTensor

    rng = np.random.default_rng(seed)
    done = []
    for leaf in jax.tree_util.tree_leaves(
            qparams, is_leaf=lambda x: isinstance(x, QTensor)):
        if not isinstance(leaf, QTensor):
            continue
        w = leaf.q[0] if leaf.q.ndim == 3 else leaf.q
        if fmt == "w4a8":
            run, w_host = packed_matmul.packed_w4_matmul_acc, ref.unpack_w4(w)
        else:
            run, w_host = quant_matmul.quant_matmul_acc, w
        w_host = np.asarray(w_host, np.float64)
        k, n = w_host.shape
        for m in (8, 512):
            if (m, k, n) in done:
                continue
            x = rng.integers(-128, 128, (m, k), dtype=np.int8)
            acc = np.asarray(run(jnp.asarray(x), w))
            check(np.array_equal(acc, x.astype(np.float64) @ w_host),
                  f"{fmt} Mosaic GEMM {(m, k, n)} differs from the host "
                  f"product")
            done.append((m, k, n))
    return done


def one_chip(cfg, params, fmt: str, seed: int) -> int:
    """Serve one weight format on tpu-pallas (cold + warm) and on ref;
    returns the tokens generated per run."""
    import jax

    from repro.kernels import registry
    from repro.quant.qtensor import quantize_tree_for_serving

    t0 = time.perf_counter()
    qparams = quantize_tree_for_serving(params, fmt)
    jax.block_until_ready(qparams)
    say(f"[{fmt}] quantize s", time.perf_counter() - t0)

    census = registry.active_lowerings()
    say(f"[{fmt}] lowering census", census)
    check(set(census.values()) == {"tpu-pallas"},
          f"census is not tpu-pallas for every op: {census}")
    say(f"[{fmt}] Mosaic GEMMs exact vs host, (M, K, N)",
        kernels_exact(qparams, fmt, seed))

    registry.reset_dispatch_counts()
    reqs = make_requests(cfg.vocab, seed)
    eng, cold, cold_s = serve(qparams, cfg, reqs)
    counts = registry.dispatch_counts()
    say(f"[{fmt}] dispatch counts", counts)
    op = "quant_matmul" if fmt == "w8a8" else "packed_w4_matmul"
    check(counts[op] > 0, f"no {op} dispatch: {counts}")
    check(eng.cache_info()["lowerings"] == census,
          f"engine census {eng.cache_info()['lowerings']}")
    say(f"[{fmt}] compile + first run s", cold_s)

    n = len(reqs)
    warm_reqs = make_requests(cfg.vocab, seed, rid0=n)
    t0 = time.perf_counter()
    warm = eng.run(warm_reqs)        # every finished request so far
    warm_s = time.perf_counter() - t0
    check_engine(eng, [r.rid for r in warm_reqs])
    n_tok = sum(len(t) for t in cold.values())
    check(n_tok == n * NEW_TOKENS, f"generated {n_tok} tokens")
    div = first_divergence(cold, {rid - n: t for rid, t in warm.items()
                                  if rid >= n})
    check(div is None, f"warm and cold runs diverge at (rid, step) {div}")
    say(f"[{fmt}] warm run s", warm_s)
    say(f"[{fmt}] smoke rate tok/s (not a benchmark)", n_tok / warm_s)
    del eng

    with registry.force("ref"):
        ref_eng, ref_out, ref_s = serve(qparams, cfg,
                                        make_requests(cfg.vocab, seed))
        ref_census = ref_eng.cache_info()["lowerings"]
    check(set(ref_census.values()) == {"ref"}, f"ref census {ref_census}")
    say(f"[{fmt}] ref lowering compile + run s", ref_s)
    div = first_divergence(cold, ref_out)
    say(f"[{fmt}] tokens identical to ref lowering", div is None)
    check(div is None, f"tpu-pallas and ref diverge at (rid, step) {div}")
    return n_tok


def four_chips(cfg, params, seed: int) -> None:
    """The w8a8 requests on a (data 2, model 2) mesh vs one chip."""
    from repro.distributed import context as dctx
    from repro.launch.mesh import make_mesh
    from repro.quant.qtensor import quantize_tree_for_serving

    qparams = quantize_tree_for_serving(params, "w8a8")
    mesh = make_mesh((2, 2), ("data", "model"))
    with dctx.mesh_scope(mesh, ("data",), "model"):
        eng, sharded, secs = serve(qparams, cfg,
                                   make_requests(cfg.vocab, seed))
    block = eng.cache_info()["mesh"]
    say("[w8a8 2x2] mesh", json.dumps(block))
    say("[w8a8 2x2] compile + run s", secs)
    check(block["tp_attn"] and block["n_devices"] == 4,
          "attention is not tensor-parallel over 4 devices")
    del eng
    _, single, secs = serve(qparams, cfg, make_requests(cfg.vocab, seed))
    say("[w8a8 1 chip] compile + run s", secs)
    div = first_divergence(single, sharded)
    say("[w8a8] sharded tokens identical to one chip", div is None)
    check(div is None, f"sharded and single-chip tokens diverge at "
          f"(rid, step) {div}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        import jax

        from repro import configs
        from repro.kernels import common
        from repro.launch import xla_setup
        from repro.models import lm
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}",
              file=sys.stderr)
        return 2
    try:
        setup = xla_setup.configure()     # before the backend starts
    except RuntimeError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 1
    say("cache dir", setup.cache_dir)
    say("exact rounding (XLA excess precision off)", setup.exact_rounding)
    dev = devs[0]
    say("device", f"{dev.platform} {dev.device_kind} x{len(devs)}")
    try:
        check(not common.interpret_default(),
              "the Mosaic kernels would run in interpret mode")
        cfg = configs.get_config(ARCH)
        t0 = time.perf_counter()
        # one compiled program: op-by-op init at full width took 51 s
        params = jax.jit(lm.init_params, static_argnums=(1, 2))(
            jax.random.PRNGKey(args.seed), cfg, MAX_CACHE_LEN)
        jax.block_until_ready(params)
        say(f"{ARCH} init s", time.perf_counter() - t0)
        if args.chips == 4:
            four_chips(cfg, params, args.seed)
        else:
            for fmt in ("w8a8", "w4a8"):
                one_chip(cfg, params, fmt, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    say("peak_bytes_in_use", stats.get("peak_bytes_in_use", "not reported"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
