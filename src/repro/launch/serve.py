"""Serving driver: quantized weights + batched prefill/decode.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
        --quant w4a8 --batch 4 --prompt-len 64 --gen 32 \
        [--silvia all] [--autotune] [--no-fused-decode]

The serving path is where the paper's technique lives end to end:

* weights are quantized offline (w8a8 / w4a8 packed -- two int4 per int8
  word, the DSP-packing insight applied to HBM);
* with ``--silvia {off,add,muladd,all}``, the decode step function is
  rewritten by the SILVIA passes (core/pipeline.py) before jit, packing any
  narrow-int ops the quantized graph exposes -- the
  `SILVIA::csynth_design` drop-in, one flag.  The pass pipeline's trace
  cache makes this compile-once/run-many: repeated `generate()` calls with
  the same shapes never re-run the passes;
* decode runs as a **fused `jax.lax.scan` loop**: the whole decode phase is
  ONE dispatch with the KV cache donated to the loop, instead of one
  python-level dispatch per generated token (``--no-fused-decode`` restores
  the per-step loop for A/B measurement -- benchmarks/pipeline_overhead.py
  reports both);
* with ``--autotune``, the Pallas kernels (matmuls AND the SWAR units)
  search their block sizes on first use and persist the winners on disk
  (kernels/autotune.py; cache at $REPRO_AUTOTUNE_CACHE or
  ~/.cache/repro/autotune.json, keyed per lowering id + mode);
* every packed op binds to its backend implementation through the
  **lowering registry** (kernels/registry.py): `tpu-pallas` / `gpu-pallas`
  / `cpu-vector` / `ref`, auto-selected per backend.
  ``REPRO_LOWERING=<op>=<id>,...`` (or ``*=<id>``) forces specific
  lowerings -- e.g. ``REPRO_LOWERING='*=ref'`` serves everything on the
  pure-jnp oracle, bit-identically (on a TPU with the exact rounding
  that ``launch/xla_setup.configure()`` sets; DESIGN.md sec. 7); the
  census of active lowerings is printed per run and reported by the
  engine's ``cache_info()``.

For ragged multi-request traffic, use the continuous-batching engine
instead of calling `generate()` per batch (see launch/engine.py and
examples/serve_engine.py).  The engine serves every family registered in
models/slot_state.py -- dense/vlm/moe KV pages, pure-SSM and hybrid
state, and (with `enc_len` + per-request `features`) encdec -- through
the same bucketed segment loop::

    from repro.launch.engine import ServeEngine
    from repro.launch.scheduler import Request

    eng = ServeEngine(params, cfg, n_slots=8, max_cache_len=256,
                      segment_len=16, silvia_passes="all")
    eng.submit(Request(rid=0, prompt=prompt_tokens, max_new_tokens=64))
    done = eng.run()          # {rid: np.ndarray of generated tokens}

The engine shares this module's decode-bundle cache: one compiled segment
graph per (batch bucket, cache-length bucket) serves an ever-changing
request mix, token-identically to `generate()`.  Constructed under a
`repro.distributed.context.mesh_scope`, the engine additionally shard_maps
those segment graphs over the mesh (slot axes over the data axes, probed
head/state axes over "model") while staying bit-identical, with the same
exact rounding -- see launch/engine.py and DESIGN.md sec. 7.
"""
from __future__ import annotations

import argparse
import collections
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro import core as silvia
from repro.kernels import ops as kops
from repro.kernels import registry
from repro.launch import xla_setup
from repro.launch import sampling as sampling_lib
from repro.models import lm
from repro.quant.qtensor import quantize_tree_for_serving

SILVIA_PASS_SETS = {
    "off": [],
    "muladd": [silvia.PassConfig(op="muladd")],
    "add": [silvia.PassConfig(op="add", op_size=8),
            silvia.PassConfig(op="add", op_size=16)],
    "all": list(silvia.DEFAULT_PASSES),
}


class LRUCache:
    """Bounded LRU keyed cache with cache_info()/cache_clear() counters
    mirroring core/pipeline.py's trace-cache bookkeeping.

    Decode bundles hold compiled executables (and, with SILVIA passes on,
    their own trace caches), so an unbounded dict leaks a full compiled
    graph per distinct (cfg, pass set) forever; serving fleets cycle
    through many configs.  Default bound via $REPRO_DECODE_CACHE_SIZE."""

    def __init__(self, maxsize: int = 16):
        self.maxsize = max(1, int(maxsize))
        self._store: collections.OrderedDict = collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_or_build(self, key, builder):
        ent = self._store.get(key)
        if ent is not None:
            self.hits += 1
            self._store.move_to_end(key)
            return ent
        self.misses += 1
        ent = builder()
        self._store[key] = ent
        while len(self._store) > self.maxsize:
            self._store.popitem(last=False)
            self.evictions += 1
        return ent

    def info(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "size": len(self._store),
                "maxsize": self.maxsize}

    def clear(self) -> None:
        self._store.clear()
        self.hits = self.misses = self.evictions = 0


# (cfg, silvia_passes, lowering fingerprint[, variant]) -> decode bundle.
# ModelConfig is a frozen dataclass, so this composes with the SILVIA trace
# cache to give compile-once/run-many across generate() calls; the serve
# engine stores its segment bundles here too under a "engine" variant key.
# The registry fingerprint keys out forced-lowering changes: a bundle
# compiled under one lowering census is never served under another.
_DECODE_CACHE = LRUCache(
    maxsize=int(os.environ.get("REPRO_DECODE_CACHE_SIZE", "16")))


def decode_cache_info() -> dict:
    """Counters for the decode-bundle LRU (hits/misses/evictions/size)."""
    return _DECODE_CACHE.info()


def decode_cache_clear() -> None:
    _DECODE_CACHE.clear()


def _pin_lowerings(fn, census: dict):
    """Run every call of a bundle callable under the lowering census its
    cache key records.  jit tracing (where the registry is consulted) is
    lazy -- a bundle may first trace, or re-trace for a new shape, long
    after it was built, when the ambient resolution could have changed;
    pinning makes key and trace consistent for the bundle's lifetime."""
    @functools.wraps(fn)
    def pinned(*args, **kwargs):
        with registry.force(**census):
            return fn(*args, **kwargs)
    return pinned


def _decode_bundle(cfg, silvia_passes: str):
    census = registry.active_lowerings()

    def build():
        def decode_fn(p, tok, kv, pos):
            return lm.decode_step(p, tok, kv, pos, cfg)

        passes = SILVIA_PASS_SETS[silvia_passes]
        if passes:
            decode_fn = silvia.optimize(decode_fn, passes)

        @functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(2,))
        def fused_loop(params, tok0, cache, pos0, n_steps):
            def step(carry, i):
                tok, kv = carry
                logits, kv = decode_fn(params, tok, kv, pos0 + i)
                nxt = jnp.argmax(logits[:, -1, :], axis=-1)
                nxt = nxt.astype(jnp.int32)[:, None]
                return (nxt, kv), nxt

            (_, kv), seq = jax.lax.scan(step, (tok0, cache),
                                        jnp.arange(n_steps))
            return seq, kv

        # per-request sampling variant: its own jitted graph, so the
        # greedy fused_loop above stays byte-for-byte the pre-sampling
        # program (greedy rows INSIDE a sampled batch still take the
        # argmax select in sampling.sample)
        @functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(2,))
        def sampled_loop(params, tok0, cache, pos0, samp, n_steps):
            key, temp, top_k, top_p, plen = samp

            def step(carry, i):
                tok, kv = carry
                logits, kv = decode_fn(params, tok, kv, pos0 + i)
                nxt = sampling_lib.sample(logits[:, -1, :], key, temp,
                                          top_k, top_p, pos0 + i - plen + 1)
                return (nxt[:, None], kv), nxt[:, None]

            (_, kv), seq = jax.lax.scan(step, (tok0, cache),
                                        jnp.arange(n_steps))
            return seq, kv

        decode_jit = jax.jit(decode_fn, donate_argnums=(2,))
        return (_pin_lowerings(decode_fn, census),
                _pin_lowerings(decode_jit, census),
                _pin_lowerings(fused_loop, census),
                _pin_lowerings(sampled_loop, census))

    return _DECODE_CACHE.get_or_build(
        (cfg, silvia_passes, tuple(sorted(census.items()))), build)


def get_decode_step(cfg, silvia_passes: str = "off"):
    """The (possibly SILVIA-rewritten) single-token decode step for cfg.

    Cached per (cfg, pass set); the SILVIA wrapper's own trace cache then
    guarantees the passes run once per input-shape signature (inspect via
    `get_decode_step(...).cache_info()` when passes are on)."""
    return _decode_bundle(cfg, silvia_passes)[0]


def generate(params, prompts, cfg, *, gen: int, cache_len: int,
             silvia_passes="off", fused: bool = True,
             sampling=None, rids=None):
    """Generation: prefill + gen decode steps (greedy by default).

    prompts: [B,S] int tokens; encdec families take a tuple
    (features [B,S_enc,d_model], dec_tokens [B,S]) instead.
    fused=True runs the whole decode phase as one `jax.lax.scan` dispatch
    (state cache donated); fused=False is the per-step reference loop.
    `sampling` takes one scheduler.SamplingParams (or None = greedy) per
    row, with `rids` giving each row's request id for key derivation
    (default: the row index) -- the static reference the engine's sampled
    streams are tested against.  All-greedy batches take the original
    argmax graphs untouched."""
    b, s = (prompts[1] if cfg.family == "encdec" else prompts).shape
    logits, cache = lm.prefill(params, prompts, cfg, cache_len=cache_len)
    _, decode_jit, fused_loop, sampled_loop = _decode_bundle(
        cfg, silvia_passes)

    samp = sampling_lib.static_operand(sampling, s, rids) \
        if sampling is not None else None
    pos = jnp.full((b,), s, jnp.int32)
    if samp is None:
        tok = jnp.argmax(logits[:, -1, :],
                         axis=-1).astype(jnp.int32)[:, None]
        if fused:
            seq, _ = fused_loop(params, tok, cache, pos, gen - 1)
            # seq: [gen-1, B, 1] of generated tokens, in step order
            return jnp.concatenate([tok, jnp.moveaxis(seq[:, :, 0], 0, 1)],
                                   axis=1)
        out = [tok]
        for i in range(gen - 1):
            logits, cache = decode_jit(params, tok, cache, pos + i)
            tok = jnp.argmax(logits[:, -1, :],
                             axis=-1).astype(jnp.int32)[:, None]
            out.append(tok)
        return jnp.concatenate(out, axis=1)
    key, temp, top_k, top_p, _ = samp
    tok = sampling_lib.sample(logits[:, -1, :], key, temp, top_k, top_p,
                              jnp.zeros((b,), jnp.int32))[:, None]
    if fused:
        seq, _ = sampled_loop(params, tok, cache, pos, samp, gen - 1)
        return jnp.concatenate([tok, jnp.moveaxis(seq[:, :, 0], 0, 1)],
                               axis=1)
    out = [tok]
    for i in range(gen - 1):
        logits, cache = decode_jit(params, tok, cache, pos + i)
        tok = sampling_lib.sample(logits[:, -1, :], key, temp, top_k,
                                  top_p,
                                  jnp.full((b,), i + 1, jnp.int32))[:, None]
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default="w8a8",
                    choices=["bf16", "w8a8", "w4a8"])
    ap.add_argument("--quant-force", action="store_true",
                    help="drop the quantization size floors (reduced "
                         "configs sit entirely under them; without this, "
                         "--reduced --quant w8a8 serves bf16 graphs with "
                         "zero packed-matmul dispatches)")
    ap.add_argument("--silvia", default="off",
                    choices=list(SILVIA_PASS_SETS))
    ap.add_argument("--autotune", action="store_true",
                    help="tune + persist Pallas kernel block sizes -- "
                         "matmuls and SWAR units (kernels/autotune.py)")
    ap.add_argument("--no-fused-decode", action="store_true",
                    help="per-step decode dispatch instead of the fused "
                         "lax.scan loop (for A/B comparison)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    xla_setup.configure()

    cfg = configs.get_reduced_config(args.arch) if args.reduced \
        else configs.get_config(args.arch)
    assert cfg.family != "encdec", "use --arch with a decoder-only model"
    if args.autotune:
        kops.set_autotune(True)
    rng = jax.random.PRNGKey(args.seed)
    cache_len = args.prompt_len + args.gen
    params = lm.init_params(rng, cfg, max_seq=cache_len + 8)
    if args.quant != "bf16":
        params = quantize_tree_for_serving(params, args.quant,
                                           force=args.quant_force)
        print(f"quantized weights to {args.quant}"
              + (" (forced floors)" if args.quant_force else ""))
    prompts = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                 cfg.vocab, dtype=jnp.int32)
    print("active lowerings:", registry.census_str())
    t0 = time.time()
    toks = generate(params, prompts, cfg, gen=args.gen, cache_len=cache_len,
                    silvia_passes=args.silvia,
                    fused=not args.no_fused_decode)
    dt = time.time() - t0
    n_tok = args.batch * args.gen
    print(f"generated {toks.shape} in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s batch-aggregate)")
    print("sample tokens:", np.asarray(toks[0, :16]))


if __name__ == "__main__":
    main()
