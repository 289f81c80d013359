"""Continuous-batching serve engine over the cached fused decode loop.

SILVIA packs independent narrow ops into one wide DSP; this engine packs
independent requests into one compiled decode dispatch.  Decode runs in
fixed-length **scan segments** (one dispatch for `segment_len` tokens across
all slots); between segments the scheduler admits queued requests into free
slots and evicts finished ones, so ONE compiled graph serves an
ever-changing request mix:

* **family-agnostic slot state** -- the engine never touches a concrete
  cache layout.  Each model family (dense/vlm/moe KV pages, pure-SSM
  recurrent state, jamba-style hybrid mixes, whisper encdec self+cross
  caches) registers its state builder in `models/slot_state.py`; the
  engine slices, scatters and compacts through the probed
  `SlotStateSpec`, exactly as SILVIA's one packing transformation covers
  add2/mul4/muladd2 behind one DSP-slot interface.
* **bucketed shape cache** -- segment batch size and (for families with a
  sliceable cache-length axis) attended cache length are rounded up to
  power-of-two buckets (launch/scheduler.py), so the SILVIA trace cache
  and `jax.jit` compile a handful of graphs, bounded by the bucket-set
  product (`cache_info()["graphs"]`); `warmup()` pre-compiles the grid.
  Constant-size-state families (SSM) skip length bucketing entirely:
  their graph census grows with batch buckets only.
* **slot-based paged state** -- state buffers carry a slot axis; each slot
  is a page with its own position and active flag, threaded through
  `lm.decode_step` so inactive slots neither mutate their page nor
  contribute sampled tokens.  KV pages are reused WITHOUT scrubbing (the
  causal mask zeroes stale positions exactly); constant-size pages (SSM
  state) are reset-on-admit, because admission overwrites them whole.
* **chunked prefill** -- with `prefill_chunk=C`, prompts are fed through the
  same decode path C tokens at a time (same bucket shapes, same compiled
  family).  KV-cache families only: sequential-state families would change
  the floating-point reduction order (see slot_state.FamilyState).
* **cross-request prefix caching** -- with `prefix_cache=N`, prompt chunks
  are hashed into a content-addressed pool of immutable host-resident
  prefix pages (launch/prefix_cache.py) shared copy-on-write across
  requests: admission copies the longest cached prefix into the slot's
  private pages and prefills only the uncached tail, skipping whole chunk
  dispatches (the TTFT win).  Eviction is LRU-by-refcount -- a page is
  pinned while a live slot was admitted from it.  KV rows are a pure
  function of the token prefix and masking hides everything beyond them,
  so warm streams stay BIT-IDENTICAL to cold ones
  (tests/test_prefix_cache.py) -- including under chaos replay and
  elastic degrade (host pages are mesh-free and re-enter device state
  through the CURRENT plan's PartitionSpecs; DESIGN.md sec. 10).
* **stop tokens** -- a request carrying `stop_tokens` is harvested the
  segment it emits one (the stop token ends the output), instead of
  always running to max_new_tokens.
* **slot compaction** -- when evictions leave holes that inflate the live
  batch bucket, surviving slots are remapped downward on admission
  (`permute_slots`), shrinking the next segment's compiled shape.
* **mesh-aware serving** -- constructed under a `distributed.context.
  mesh_scope`, the engine shard_maps its segment/prefill/chunk fns over
  the mesh (DESIGN.md sec. 7): slot axes shard over the dp axes (request
  packing over devices), probed head/state axes shard over the model
  axis when the config's head counts divide it (slot_state.tp_plan),
  and weights enter under the distributed/sharding.py suffix rules and
  are all_gathered whole at dispatch entry (explicit ZeRO-3 gather).
  Every collective is an exact concat -- no partitioned float
  contraction -- so sharded outputs stay BIT-IDENTICAL to the
  single-device engine (tests/test_sharded_serve.py).  The bucket grid
  is unchanged (the dp size only becomes the batch-bucket floor), so
  the compiled-graph census bound carries over per shard.
* decode bundles live in launch/serve.py's LRU decode cache, keyed
  (cfg, pass set, "engine"); greedy outputs are token-identical to the
  static `serve.generate()` path, including with SILVIA passes on
  (tests/test_engine.py, tests/test_slot_state.py assert bitwise equality
  for dense, ssm, hybrid, and encdec families).
* **resilience** -- admission control (bounded queue + load shedding,
  per-request deadlines/TTL), chaos-testable fault recovery, a
  non-finite-logit quarantine and drain/snapshot hooks, all defined in
  launch/resilience.py and wired through `submit()`/`step()`.  Every
  device dispatch funnels through `_guarded` (the fault-injection site),
  every dispatch failure unwinds to `_recover`, and recovered requests
  REPLAY their recorded tokens through the same compiled decode path, so
  surviving streams are bit-identical to a fault-free run -- SILVIA's
  behavior-preservation obligation carried into failure handling
  (DESIGN.md sec. 8; tests/test_resilience.py).
* **elastic degraded-mesh serving** -- a mesh-aware engine survives losing
  devices (distributed/elastic.py; DESIGN.md sec. 9): a `DeviceLoss`
  fault marks devices dead in the health registry, `_degrade` re-plans
  onto the largest valid healthy sub-mesh (dp floor + tp divisibility),
  rebuilds the compiled bundle (the mesh fingerprint keys the LRU),
  re-shards the weights, and the ordinary recovery path then replays
  in-flight requests on the shrunken mesh -- surviving streams stay
  bit-identical to the fault-free run (tests/test_elastic.py).

Exactness invariants (why masking is exact, not approximate): an attention
row only attends cache positions `<= pos`, every such position was written
by the CURRENT request, and masked score entries become exact float zeros
after softmax.  SSM/conv state is constant-size and masked wholesale
(`jnp.where` on the full page), and serving-mode MoE routes per token
(mlp.moe per_token), so neither stale pages, batch padding, length padding,
nor batch COMPOSITION can perturb an active row by even one ULP.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import core as silvia
from repro.distributed import context as dctx
from repro.distributed import elastic as delastic
from repro.distributed import fault as dfault
from repro.distributed import sharding as dshard
from repro.distributed.fault import SimulatedFailure
from repro.kernels import registry
from repro.launch import methods as smethods
from repro.launch import prefix_cache as pfx
from repro.launch import resilience as res
from repro.launch import sampling
from repro.launch import scheduler
from repro.launch import serve
from repro.launch import telemetry
from repro.models import attention, lm
from repro.models import slot_state


@dataclasses.dataclass(frozen=True)
class _EngineBundle:
    """Compiled callables shared by every engine with the same (cfg, pass
    set); stored in serve.py's LRU decode cache."""
    decode_fn: object      # (params, tok [B,C], cache, pos, active) -> ...
    segment: object        # jitted segment loop (static n_steps)
    chunk_step: object     # jitted single chunk-decode dispatch
    prefill: object        # jitted bucketed full prefill (static cache_len)
    embed: object          # jitted pooled-embedding dispatch (no cache out)


@dataclasses.dataclass(frozen=True)
class _MeshPlan:
    """How a mesh-aware engine lays the serve state over the device mesh
    (built at engine construction from the ambient `mesh_scope`):

    * slot axes of every state leaf, tokens, positions and active masks
      shard over the dp axes -- request packing over devices, the direct
      analogue of SILVIA packing independent narrow ops onto one wide DSP;
    * head/state axes (the probed `tp_axes`) shard over `model_axis` when
      the config's head counts divide it (slot_state.tp_plan);
    * weights enter the shard_map body under the `param_pspecs` suffix
      rules and are all_gathered back whole at segment entry (explicit
      ZeRO-3 gather -- pure data movement, bitwise-exact), then
      attention/SSM re-slice their local head columns.

    Every collective is a gather (exact concat); no float contraction is
    ever partitioned, which is what keeps sharded decode BIT-IDENTICAL to
    the single-device engine.
    """
    mesh: object
    dp_axes: tuple
    model_axis: str
    tp: slot_state.TPPlan
    slot_axes: tuple           # per-leaf, tree_flatten order
    tp_axes: tuple
    state_treedef: object

    @property
    def dp_size(self) -> int:
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def dp(self):
        return dshard.dp_spec_entry(self.dp_axes)

    def state_specs(self):
        return dshard.slot_state_pspecs(
            self.state_treedef, self.slot_axes, self.tp_axes, self.dp_axes,
            self.model_axis if self.tp.active else None)

    @property
    def key(self) -> tuple:
        """Hashable mesh-topology fingerprint for the decode-bundle LRU:
        two engines may share a compiled bundle only when mesh shape,
        axis roles, device assignment and the tp plan all agree."""
        m = self.mesh
        return (tuple((n, m.shape[n]) for n in m.axis_names),
                tuple(int(d.id) for d in m.devices.flat),
                self.dp_axes, self.model_axis,
                self.tp.size, self.tp.attn, self.tp.ssm,
                self.slot_axes, self.tp_axes)


def _mesh_plan(cfg, spec: slot_state.SlotStateSpec, init_kwargs: dict,
               params) -> Optional[_MeshPlan]:
    ctx = dctx.current()
    if ctx is None:
        return None
    mesh, dp_axes, model_axis = ctx
    m = mesh.shape[model_axis] if model_axis in mesh.axis_names else 1
    plan = slot_state.tp_plan(cfg, m)
    if plan.attn:
        attention.check_w4_tp(params, cfg, m)
    tp_axes = slot_state.tp_axes_for(cfg, m, **init_kwargs) if plan.active \
        else (None,) * len(spec.batch_axes)
    return _MeshPlan(mesh=mesh, dp_axes=tuple(dp_axes),
                     model_axis=model_axis, tp=plan,
                     slot_axes=spec.batch_axes, tp_axes=tp_axes,
                     state_treedef=spec.treedef)


def _build_bundle(cfg, silvia_passes: str, census: dict,
                  plan: Optional[_MeshPlan] = None) -> _EngineBundle:
    # census is REQUIRED and must be the one the caller keys the bundle
    # LRU with -- computing it here instead would let key and pinned
    # trace diverge
    passes = serve.SILVIA_PASS_SETS[silvia_passes]

    def decode_fn(p, tok, state, pos, active):
        return lm.decode_step(p, tok, state, pos, cfg, active=active)

    if passes:
        decode_fn = silvia.optimize(decode_fn, passes)

    def decode_scan(params, tok, cache, pos, active, samp, n_steps):
        key, temp, top_k, top_p, plen = samp

        def step(carry, _):
            tok, st, pos, bad = carry
            logits, st = decode_fn(params, tok, st, pos, active)
            # per-request sampling (launch/sampling.py): greedy rows take
            # the literal argmax path the pre-sampling engine ran; sampled
            # rows draw under the counter-based key folded with the
            # generated-token index pos - plen + 1, so a slot's stream is
            # a pure function of (seed, rid, logits) -- batch composition,
            # compaction and replay cannot move its bits
            nxt = sampling.sample(logits[:, -1, :], key, temp, top_k,
                                  top_p, pos - plen + 1)
            nxt = nxt[:, None]
            nxt = jnp.where(active[:, None], nxt, 0)
            # output-validation guard: flag slots whose sampled-from logits
            # row went non-finite, so the host can quarantine THAT request
            # (per-slot state is independent, so a poisoned row never
            # perturbs a healthy row's tokens -- the flag is observability,
            # not a numerical change)
            bad = bad | (active & ~jnp.all(
                jnp.isfinite(logits[:, -1, :]), axis=-1))
            # unclamped advance, exactly matching the static loop's pos0+i:
            # every write this segment lands below t_b (the engine sizes
            # t_b >= max(pos)+n_steps), and a slot that finished
            # mid-segment only overruns into its own discarded row (XLA
            # clamps the slice start) before eviction at harvest
            pos = jnp.where(active, pos + 1, pos)
            return (nxt, st, pos, bad), nxt

        carry0 = (tok, cache, pos, jnp.zeros(active.shape, bool))
        (tok, cache, pos, bad), seq = jax.lax.scan(step, carry0,
                                                   None, length=n_steps)
        return seq[:, :, 0], tok, cache, pos, bad

    def prefill_fn(params, prompts, last_positions, cache_len, enc_pad):
        # prompts: [B,S] tokens, or (features, [B,S], enc_lens) for encdec
        # (ragged encoder lengths; enc_pad is the static cross-page width
        # every enc bucket pads up to -- zero-extension is exact, see
        # models/attention.py).  `last` -- each row's final logits row --
        # rides along so score admissions get their first logprob from
        # the SAME dispatch that sampled tok0.
        if isinstance(prompts, tuple) and len(prompts) == 3:
            audio, dec, enc_lens = prompts
            logits, cache = lm.prefill(params, (audio, dec), cfg,
                                       cache_len=cache_len,
                                       last_positions=last_positions,
                                       enc_lengths=enc_lens,
                                       enc_pad=enc_pad)
        else:
            logits, cache = lm.prefill(params, prompts, cfg,
                                       cache_len=cache_len,
                                       last_positions=last_positions)
        last = logits[:, -1, :]
        tok0 = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
        bad0 = ~jnp.all(jnp.isfinite(last), axis=-1)
        return tok0, last, cache, bad0

    def embed_fn(params, prompts, last_positions):
        # pooled final-hidden-state embedding (lm.embed_pool): one
        # prefill-shaped dispatch, caches never materialize (DCE'd)
        if isinstance(prompts, tuple) and len(prompts) == 3:
            audio, dec, enc_lens = prompts
            emb = lm.embed_pool(params, (audio, dec), cfg,
                                last_positions=last_positions,
                                enc_lengths=enc_lens)
        else:
            emb = lm.embed_pool(params, prompts, cfg,
                                last_positions=last_positions)
        bad = ~jnp.all(jnp.isfinite(emb), axis=-1)
        return emb, bad

    if plan is None:
        @functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(2,))
        def segment(params, tok, cache, pos, active, samp, n_steps):
            return decode_scan(params, tok, cache, pos, active, samp,
                               n_steps)

        chunk_step = jax.jit(decode_fn, donate_argnums=(2,))
        prefill = functools.partial(jax.jit,
                                    static_argnums=(3, 4))(prefill_fn)
        embed = jax.jit(embed_fn)
    else:
        segment, chunk_step, prefill, embed = _shard_bundle_fns(
            plan, decode_scan, decode_fn, prefill_fn, embed_fn)

    pin = lambda fn: serve._pin_lowerings(fn, census)
    return _EngineBundle(pin(decode_fn), pin(segment), pin(chunk_step),
                         pin(prefill), pin(embed))


def _shard_bundle_fns(plan: _MeshPlan, decode_scan, decode_fn, prefill_fn,
                      embed_fn):
    """shard_map'd segment / chunk-step / prefill over plan.mesh.

    Inside each body the single-device functions run UNMODIFIED on this
    shard's slot slice; the tp scope makes attention/SSM mixers keep only
    their local head block (distributed/context.py).  Weights arrive
    sharded per the param_pspecs suffix rules and are gathered whole
    first -- the explicit FSDP gather, after which every contraction sees
    bitwise the single-device operands."""
    mesh, dp = plan.mesh, plan.dp
    sspecs = plan.state_specs()

    def tp_ctx():
        if plan.tp.active:
            return dctx.tp_scope(plan.model_axis, plan.tp.size,
                                 attn=plan.tp.attn, ssm=plan.tp.ssm)
        return contextlib.nullcontext()

    def pspecs_for(params):
        # at trace time, from the traced arg tree: the bundle stays lazy
        # over params structure (plain vs QTensor leaves), like jit
        return dshard.param_pspecs(params, mesh, None)

    @functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(2,))
    def segment(params, tok, cache, pos, active, samp, n_steps):
        pspecs = pspecs_for(params)

        def body(params, tok, cache, pos, active, samp):
            with tp_ctx():
                params = dshard.gather_sharded(params, pspecs)
                return decode_scan(params, tok, cache, pos, active, samp,
                                   n_steps)

        # the sampling page shards like every other per-slot array: slot
        # axis over dp.  The sampler is per-row (no cross-row reduction),
        # so sharded sampled tokens stay bit-identical to single-device
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(pspecs, P(dp), sspecs, P(dp), P(dp),
                                     (P(dp),) * 5),
                           out_specs=(P(None, dp), P(dp), sspecs, P(dp),
                                      P(dp)),
                           check_vma=False)
        return fn(params, tok, cache, pos, active, samp)

    @functools.partial(jax.jit, donate_argnums=(2,))
    def chunk_step(params, tok, cache, pos, active):
        pspecs = pspecs_for(params)

        def body(params, tok, cache, pos, active):
            with tp_ctx():
                params = dshard.gather_sharded(params, pspecs)
                return decode_fn(params, tok, cache, pos, active)

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(pspecs, P(dp), sspecs, P(dp), P(dp)),
                           out_specs=(P(dp), sspecs),
                           check_vma=False)
        return fn(params, tok, cache, pos, active)

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def prefill(params, prompts, last_positions, cache_len, enc_pad):
        pspecs = pspecs_for(params)
        prspecs = jax.tree_util.tree_map(lambda _: P(dp), prompts)

        def body(params, prompts, last_positions):
            with tp_ctx():
                params = dshard.gather_sharded(params, pspecs)
                return prefill_fn(params, prompts, last_positions,
                                  cache_len, enc_pad)

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(pspecs, prspecs, P(dp)),
                           out_specs=(P(dp), P(dp), sspecs, P(dp)),
                           check_vma=False)
        return fn(params, prompts, last_positions)

    @jax.jit
    def embed(params, prompts, last_positions):
        pspecs = pspecs_for(params)
        prspecs = jax.tree_util.tree_map(lambda _: P(dp), prompts)

        def body(params, prompts, last_positions):
            with tp_ctx():
                params = dshard.gather_sharded(params, pspecs)
                return embed_fn(params, prompts, last_positions)

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(pspecs, prspecs, P(dp)),
                           out_specs=(P(dp), P(dp)),
                           check_vma=False)
        return fn(params, prompts, last_positions)

    return segment, chunk_step, prefill, embed


def _engine_bundle(cfg, silvia_passes: str, census: dict,
                   plan: Optional[_MeshPlan] = None) -> _EngineBundle:
    # the census keys out forced-lowering changes AND pins every (lazy)
    # trace of the bundle callables to the resolution the key records;
    # the mesh-plan key keys out topology changes -- a bundle compiled
    # for one mesh (or tp plan) is never served under another
    return serve._DECODE_CACHE.get_or_build(
        (cfg, silvia_passes, tuple(sorted(census.items())), "engine",
         None if plan is None else plan.key),
        lambda: _build_bundle(cfg, silvia_passes, census, plan))


@dataclasses.dataclass(frozen=True)
class SpecDecodeConfig:
    """Self-speculative decoding knobs (ServeEngine `spec_decode=`).

    A small-config draft model of the SAME family free-runs `k` tokens
    per slot, then the target verifies all k in one batched
    `chunk_step`-shaped dispatch -- SILVIA's pack-then-check rewrite at
    the serve-loop level (DESIGN.md sec. 12).  Emitted tokens are always
    the TARGET's tokens under a teacher-forced prefix, so streams are
    byte-identical to the non-speculative engine regardless of how often
    the draft is right; acceptance only changes how many target
    dispatches that takes."""
    draft_params: object
    draft_cfg: object
    k: int = 3

    def __post_init__(self):
        if self.k < 1:
            raise ValueError("spec_decode.k must be >= 1")


@dataclasses.dataclass(frozen=True)
class _SpecFns:
    """Compiled speculative-decode callables (LRU-cached like the engine
    bundle, under a "spec" variant key)."""
    draft: object      # free-running sampled scan w/ const-leaf snapshots
    verify: object     # teacher-forced verify + in-graph accept/rollback
    rollback: object   # const-leaf snapshot-restore select


def _build_spec_fns(cfg, silvia_passes: str, census: dict,
                    spec: slot_state.SlotStateSpec,
                    plan: Optional[_MeshPlan] = None) -> _SpecFns:
    passes = serve.SILVIA_PASS_SETS[silvia_passes]

    def decode_fn(p, tok, state, pos, active):
        return lm.decode_step(p, tok, state, pos, cfg, active=active)

    if passes:
        decode_fn = silvia.optimize(decode_fn, passes)

    def one_step(params, tok, st, pos, active, samp):
        key, temp, top_k, top_p, plen = samp
        logits, st = decode_fn(params, tok, st, pos, active)
        last = logits[:, -1, :]
        g = sampling.sample(last, key, temp, top_k, top_p, pos - plen + 1)
        bad = active & ~jnp.all(jnp.isfinite(last), axis=-1)
        return g, st, bad

    def draft_scan(params, tok, cache, pos, active, samp, n_steps):
        # free-running sampled decode (the DRAFT side of a round): the
        # per-step snapshots of the constant-size leaves let the round
        # roll the draft back to exactly the accepted prefix afterwards
        # (rollback below); length-paged leaves need no snapshot --
        # overrun rows are stale-but-masked (engine docstring)
        def step(carry, _):
            tok, st, pos = carry
            g, st, _ = one_step(params, tok, st, pos, active, samp)
            nxt = jnp.where(active[:, None], g[:, None], 0)
            pos = jnp.where(active, pos + 1, pos)
            return (nxt, st, pos), (g, tuple(spec.const_leaves(st)))

        (_, cache, _), (seq, snaps) = jax.lax.scan(
            step, (tok, cache, pos), None, length=n_steps)
        return seq, cache, snaps

    def verify_scan(params, cache, pos, active, samp, xs):
        # teacher-forced verify of k drafted tokens in ONE batched
        # dispatch: xs is [k+1, B, 1] (the pending token, then the k
        # drafts).  The target's own token at each position rides out in
        # g_seq -- emitted streams are the target's stream by
        # construction -- and the accept count m plus the state rollback
        # happen in-graph, so accept/rollback is one masked slot_state
        # update per round
        def step(carry, tok):
            st, p = carry
            g, st, bad = one_step(params, tok, st, p, active, samp)
            return (st, jnp.where(active, p + 1, p)), \
                (g, bad, tuple(spec.const_leaves(st)))

        (cache, _), (g_seq, bad_seq, snaps) = jax.lax.scan(
            step, (cache, pos), xs)
        k = xs.shape[0] - 1
        drafts = xs[1:, :, 0]
        # m = longest accepted prefix: cumprod of the running equality
        eq = (drafts == g_seq[:k]).astype(jnp.int32)
        m = jnp.sum(jnp.cumprod(eq, axis=0), axis=0)
        cache = spec.rollback_select(cache, snaps, m)
        pos_out = jnp.where(active, pos + m + 1, pos)
        # only steps the round actually consumed (j <= m) can poison it
        used = jnp.arange(k + 1, dtype=jnp.int32)[:, None] <= m[None, :]
        bad = jnp.any(bad_seq & used, axis=0)
        return g_seq, m, cache, pos_out, bad

    def rollback_fn(cache, snaps, idx):
        return spec.rollback_select(cache, snaps, idx)

    if plan is None:
        draft = functools.partial(jax.jit, static_argnums=(6,),
                                  donate_argnums=(2,))(draft_scan)
        verify = functools.partial(jax.jit,
                                   donate_argnums=(1,))(verify_scan)
        rollback = functools.partial(jax.jit,
                                     donate_argnums=(0,))(rollback_fn)
    else:
        draft, verify, rollback = _shard_spec_fns(
            plan, spec, draft_scan, verify_scan, rollback_fn)

    pin = lambda fn: serve._pin_lowerings(fn, census)
    return _SpecFns(pin(draft), pin(verify), pin(rollback))


def _shard_spec_fns(plan: _MeshPlan, spec: slot_state.SlotStateSpec,
                    draft_scan, verify_scan, rollback_fn):
    """shard_map'd speculative-decode fns over plan.mesh -- the same
    layout rules as _shard_bundle_fns (slot axes over dp, samp page over
    dp, weights gathered whole), so sharded spec rounds emit bitwise the
    single-device tokens.  Snapshot stacks carry a LEADING step axis, so
    their specs are the state specs shifted right by one."""
    mesh, dp = plan.mesh, plan.dp
    sspecs = plan.state_specs()
    flat_specs = jax.tree_util.tree_leaves(
        sspecs, is_leaf=lambda x: isinstance(x, P))
    snap_specs = tuple(P(None, *tuple(s))
                       for s, la in zip(flat_specs, spec.length_axes)
                       if la is None)

    def tp_ctx():
        if plan.tp.active:
            return dctx.tp_scope(plan.model_axis, plan.tp.size,
                                 attn=plan.tp.attn, ssm=plan.tp.ssm)
        return contextlib.nullcontext()

    def pspecs_for(params):
        return dshard.param_pspecs(params, mesh, None)

    @functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(2,))
    def draft(params, tok, cache, pos, active, samp, n_steps):
        pspecs = pspecs_for(params)

        def body(params, tok, cache, pos, active, samp):
            with tp_ctx():
                params = dshard.gather_sharded(params, pspecs)
                return draft_scan(params, tok, cache, pos, active, samp,
                                  n_steps)

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(pspecs, P(dp), sspecs, P(dp), P(dp),
                                     (P(dp),) * 5),
                           out_specs=(P(None, dp), sspecs, snap_specs),
                           check_vma=False)
        return fn(params, tok, cache, pos, active, samp)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def verify(params, cache, pos, active, samp, xs):
        pspecs = pspecs_for(params)

        def body(params, cache, pos, active, samp, xs):
            with tp_ctx():
                params = dshard.gather_sharded(params, pspecs)
                return verify_scan(params, cache, pos, active, samp, xs)

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(pspecs, sspecs, P(dp), P(dp),
                                     (P(dp),) * 5, P(None, dp)),
                           out_specs=(P(None, dp), P(dp), sspecs, P(dp),
                                      P(dp)),
                           check_vma=False)
        return fn(params, cache, pos, active, samp, xs)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def rollback(cache, snaps, idx):
        fn = jax.shard_map(rollback_fn, mesh=mesh,
                           in_specs=(sspecs, snap_specs, P(dp)),
                           out_specs=sspecs,
                           check_vma=False)
        return fn(cache, snaps, idx)

    return draft, verify, rollback


def _spec_fns(cfg, silvia_passes: str, census: dict,
              spec: slot_state.SlotStateSpec,
              plan: Optional[_MeshPlan] = None) -> _SpecFns:
    return serve._DECODE_CACHE.get_or_build(
        (cfg, silvia_passes, tuple(sorted(census.items())), "spec",
         None if plan is None else plan.key),
        lambda: _build_spec_fns(cfg, silvia_passes, census, spec, plan))


@dataclasses.dataclass
class _PendingSegment:
    """A dispatched-but-not-harvested decode segment (step_begin /
    step_finish).  The fields are DEVICE arrays still being computed --
    JAX's async dispatch returns futures -- which is what lets the host
    run admission planning and stream publishing while the device works
    (the double-buffered serve loop, launch/frontend.py)."""
    bb: int
    seq: object     # [n_steps, bb] token block
    tok: object     # [bb, 1] final tokens
    pos: object     # [bb] final positions
    bad: object     # [bb] non-finite quarantine flags
    span: telemetry.Open    # engine.segment, ended by _finish_segment


class ServeEngine:
    """Continuous-batching greedy-decode engine (see module docstring).

    Parameters
    ----------
    n_slots:        state pages / maximum in-flight requests.
    max_cache_len:  page length; every request needs
                    prompt_len + max_new_tokens <= max_cache_len.  For
                    families without a cache-length axis (SSM) it only
                    bounds request sizes and prompt buckets.
    segment_len:    decode steps per dispatch.  Longer segments amortize
                    dispatch overhead; shorter ones admit/evict sooner --
                    the classic continuous-batching latency/throughput dial.
    silvia_passes:  serve.SILVIA_PASS_SETS key ("off" | "add" | "muladd"
                    | "all").
    prefill_chunk:  if set (power of two), prompts are prefilled through
                    the chunked decode path this many tokens per dispatch;
                    None uses one bucketed full-prefill dispatch.  Only
                    for families whose slot state is prefill-chunkable.
    enc_len:        encdec families only: the fixed encoder length; every
                    request must carry `features` of [enc_len, d_model].
    min_len_bucket / min_batch_bucket: smallest cache-length / batch
                    buckets (both clamped up to the physical maxima).
    resilience:     launch/resilience.py ResilienceConfig -- admission
                    control (queue bound, shed policy, default TTL) and
                    the per-request recovery budget.  None = defaults
                    (unbounded queue, no TTL).
    chaos:          fault-injection schedule for the dispatch path.  The
                    default "env" arms resilience.chaos_from_env()
                    ($REPRO_CHAOS -- how the tier1-chaos CI job injects
                    faults under the whole suite); pass an explicit
                    resilience.ChaosSchedule to pin a schedule, or None
                    to disable injection regardless of the environment.
    prefix_cache:   if set, the page capacity of the cross-request prefix
                    cache (launch/prefix_cache.py): admission reuses
                    pooled prefix pages instead of re-prefilling cached
                    prompt prefixes, bit-identically.  None (the
                    default) disables the pool entirely -- admission is
                    byte-for-byte the pre-pool engine.
    admit_token_budget: admission-fairness cap: each admission round
                    prefills at most this many UNCACHED prompt tokens
                    (the head-of-queue request always proceeds, so big
                    prompts cannot starve); the overflow is deferred back
                    to the queue with arrival order preserved, counted in
                    cache_info()["admission"]["deferrals"].
    """

    def __init__(self, params, cfg, *, n_slots: int = 8,
                 max_cache_len: int = 256, segment_len: int = 16,
                 silvia_passes: str = "off",
                 prefill_chunk: Optional[int] = None,
                 enc_len: Optional[int] = None,
                 min_len_bucket: int = 32, min_batch_bucket: int = 1,
                 resilience: Optional[res.ResilienceConfig] = None,
                 chaos: object = "env",
                 prefix_cache: Optional[int] = None,
                 admit_token_budget: Optional[int] = None,
                 spec_decode: Optional[SpecDecodeConfig] = None):
        if cfg.family == "encdec" and enc_len is None:
            raise ValueError("encdec serving needs enc_len (the fixed "
                             "encoder length of every request's features)")
        if cfg.family != "encdec" and enc_len is not None:
            raise ValueError(f"enc_len is encdec-only, got family "
                             f"{cfg.family!r}")
        init_kwargs = {"s_enc": enc_len} if enc_len is not None else {}
        # raises with registry guidance for unregistered families
        self._spec = slot_state.spec_for(cfg, **init_kwargs)
        # how lm.decode_step writes the stacked state back each step:
        # leaves with a length axis take their new rows in place, the
        # constant-size ones are written a whole layer at a time
        n_row = sum(a is not None for a in self._spec.length_axes)
        self._state_writes = {"row": n_row,
                              "layer": len(self._spec.length_axes) - n_row}
        if segment_len < 1:
            raise ValueError("segment_len must be >= 1")
        if prefill_chunk is not None and not self._spec.prefill_chunkable:
            raise ValueError(
                f"family {cfg.family!r} slot state is not prefill-chunkable "
                "(models/slot_state.py): use full prefill (prefill_chunk="
                "None)")
        if prefill_chunk is not None and prefill_chunk & (prefill_chunk - 1):
            raise ValueError("prefill_chunk must be a power of two")
        if prefill_chunk is not None and max_cache_len % prefill_chunk:
            # a prompt bucket clamped to the cap must still split into
            # whole chunks, or the prompt tail would be silently dropped
            raise ValueError("max_cache_len must be a multiple of "
                             "prefill_chunk")
        if spec_decode is not None:
            if cfg.family == "encdec":
                raise ValueError("spec_decode does not support encdec "
                                 "serving (draft prefill has no ragged "
                                 "feature path)")
            if spec_decode.draft_cfg.family != cfg.family:
                raise ValueError(
                    f"spec_decode draft must be the SAME family as the "
                    f"target (self-speculation): draft is "
                    f"{spec_decode.draft_cfg.family!r}, target is "
                    f"{cfg.family!r}")
            if spec_decode.draft_cfg.vocab != cfg.vocab:
                raise ValueError("spec_decode draft/target vocab mismatch")
            if prefix_cache is not None or prefill_chunk is not None:
                raise ValueError("spec_decode composes with full-prefill "
                                 "engines only (prefill_chunk=None, "
                                 "prefix_cache=None)")
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_cache_len = max_cache_len
        self.segment_len = segment_len
        self.silvia_passes = silvia_passes
        self.prefill_chunk = prefill_chunk
        self.enc_len = enc_len
        self.min_len_bucket = min(min_len_bucket, max_cache_len)
        # the caller's floor, pre-dp: re-applied when a degraded mesh
        # shrinks the dp floor (_degrade re-buckets from this)
        self._user_min_batch = min(min_batch_bucket, n_slots)
        self.min_batch_bucket = self._user_min_batch
        # mesh-aware serving: an ambient mesh_scope at construction makes
        # the engine shard its decode/prefill bundles over the mesh
        # (module docstring; _MeshPlan).  The slot axis needs to split
        # evenly over the dp shards, so the dp size becomes the batch
        # bucket floor (admission included)
        self._init_kwargs = init_kwargs
        self._plan = _mesh_plan(cfg, self._spec, init_kwargs, params)
        self._adm_floor = 1
        self._health: Optional[delastic.DeviceHealthRegistry] = None
        self._reshard_s = 0.0
        self._degrade_at: List[float] = []   # serving-clock degrade times
        if self._plan is not None:
            dp = self._plan.dp_size
            scheduler.validate_slot_sharding(n_slots, dp)
            self.min_batch_bucket = min(max(self.min_batch_bucket, dp),
                                        n_slots)
            self._adm_floor = min(dp, n_slots)
            self._health = delastic.DeviceHealthRegistry(
                self._plan.mesh.devices)
        # smallest prompt bucket: chunked prefill needs chunk-aligned
        # buckets; full prefill just avoids degenerate tiny graphs
        self.min_prompt_bucket = min(prefill_chunk or 8, max_cache_len)
        self.batch_buckets = scheduler.bucket_set(self.min_batch_bucket,
                                                  n_slots)
        self.len_buckets = scheduler.bucket_set(self.min_len_bucket,
                                                max_cache_len) \
            if self._spec.has_length_axis else ()
        # encdec: encoder-length buckets for RAGGED features.  The encoder
        # runs at the request's bucket width; the cross-KV page is padded
        # to the full enc_len (slot pages have ONE constant shape) and the
        # padding is masked to exact softmax zeros -- so a short request
        # is bit-identical to itself zero-padded to enc_len
        # (models/attention.py zero-extension invariant).
        self.enc_buckets = scheduler.bucket_set(min(8, enc_len), enc_len) \
            if enc_len is not None else ()

        # pin the lowering census at construction: the bundle (and every
        # graph compiled from it) is traced under THIS resolution, even if
        # the process later mutates REPRO_LOWERING / uses registry.force
        self._lowerings = registry.active_lowerings()
        self._bundle = _engine_bundle(cfg, silvia_passes, self._lowerings,
                                      self._plan)
        self._queue = scheduler.RequestQueue()
        self._cache = self._spec.init_state(n_slots, max_cache_len)
        if self._plan is not None:
            # place weights HBM-sharded per the suffix rules and the slot
            # state per the plan up front; the bundle's out_specs keep
            # both layouts steady across segments
            mesh = self._plan.mesh
            self.params = jax.device_put(
                params, dshard.to_shardings(
                    dshard.param_pspecs(params, mesh, cfg), mesh))
            self._cache = jax.device_put(
                self._cache, dshard.to_shardings(self._plan.state_specs(),
                                                 mesh))
        # per-slot sampling page (launch/sampling.py): host-resident like
        # _tok/_pos, shipped [:bb] as a segment operand each dispatch;
        # registered as a slot_state family so its layout is probed, not
        # hand-declared, and it survives admit/evict/compaction/replay by
        # the same bookkeeping as every other per-slot array
        self._samp = sampling.host_page(n_slots)
        # -- self-speculative decoding (SpecDecodeConfig) --
        self._sd = spec_decode
        self._spec_stats = {"rounds": 0, "drafted": 0, "accepted": 0,
                            "emitted": 0, "target_dispatches": 0}
        if spec_decode is not None:
            dcfg = spec_decode.draft_cfg
            self._draft_spec = slot_state.spec_for(dcfg)
            self._draft_plan = _mesh_plan(dcfg, self._draft_spec, {},
                                          spec_decode.draft_params)
            self._draft_bundle = _engine_bundle(dcfg, silvia_passes,
                                                self._lowerings,
                                                self._draft_plan)
            self._sfns = _spec_fns(cfg, silvia_passes, self._lowerings,
                                   self._spec, self._plan)
            self._dfns = _spec_fns(dcfg, silvia_passes, self._lowerings,
                                   self._draft_spec, self._draft_plan)
            # families whose draft state has constant-size leaves need the
            # explicit snapshot-restore dispatch after each round; pure
            # length-paged drafts roll back for free (stale rows masked)
            self._draft_const = any(
                la is None for la in self._draft_spec.length_axes)
            self._draft_params = spec_decode.draft_params
            self._draft_cache = self._draft_spec.init_state(n_slots,
                                                            max_cache_len)
            if self._draft_plan is not None:
                dmesh = self._draft_plan.mesh
                self._draft_params = jax.device_put(
                    self._draft_params, dshard.to_shardings(
                        dshard.param_pspecs(spec_decode.draft_params,
                                            dmesh, dcfg), dmesh))
                self._draft_cache = jax.device_put(
                    self._draft_cache,
                    dshard.to_shardings(self._draft_plan.state_specs(),
                                        dmesh))
        self._tok = np.zeros((n_slots, 1), np.int32)
        self._pos = np.zeros((n_slots,), np.int32)
        self._active = np.zeros((n_slots,), bool)
        self._slot_req: List[Optional[scheduler.Request]] = [None] * n_slots
        self._remaining = np.zeros((n_slots,), np.int64)
        self.finished: List[scheduler.Request] = []
        self.total_generated = 0
        self.compactions = 0
        # decode occupancy: slots active over slot pages, summed over the
        # segments (and speculative rounds) dispatched
        self._occupancy = {"active_slot_segments": 0, "slot_segments": 0}
        # the sampler branch each segment and speculative round takes:
        # "sampled" when some row of its batch has a temperature (the
        # host mirror of the predicate in sampling.sample)
        self._sampler_path = {"argmax": 0, "sampled": 0}
        self._graphs: set = set()
        # -- resilience state (launch/resilience.py) --
        self._res = resilience if resilience is not None \
            else res.ResilienceConfig()
        self._chaos = res.chaos_from_env() if chaos == "env" else chaos
        self._site_counts = {"segment": 0, "prefill": 0, "chunk": 0,
                             "embed": 0, "draft": 0, "verify": 0}
        self._replay: List[List[int]] = [[] for _ in range(n_slots)]
        # score: remaining teacher-forced completion tokens per slot --
        # drained through the SAME single-token chunk path as recovery
        # replay (_drain_replay), logprobs harvested host-side
        self._score: List[List[int]] = [[] for _ in range(n_slots)]
        self._admitting: List[scheduler.Request] = []
        self._rids: set = set()
        self._results: Dict[int, res.RequestResult] = {}
        # per-method admission bucket accounting (launch/methods.py)
        self._method_admits: Dict[str, int] = {m: 0
                                               for m in smethods.METHODS}
        self._robust: Dict[str, int] = {k: 0 for k in (
            "shed", "expired_queued", "expired_inflight", "failed",
            "quarantined", "faults_injected", "errors", "recoveries",
            "replayed_tokens", "replay_divergence", "duplicate_rejects",
            "snapshots", "restores", "drains", "degraded",
            "cancelled_queued", "cancelled_inflight")}
        # the first real (not injected) dispatch error, kept for
        # diagnosis: recovery turns it into replays and FAILED outcomes
        self._first_error: Optional[str] = None
        # -- cross-request prefix cache (launch/prefix_cache.py) --
        self._prefix: Optional[pfx.PrefixCache] = None
        if prefix_cache is not None:
            # chain (per-chunk) sharing needs EVERY leaf length-paged:
            # resuming mid-prompt would otherwise skip the sequential
            # updates a constant-size leaf accumulated over the skipped
            # chunks.  Families with any constant-size state still share
            # at exact-full-prompt (terminal) granularity.
            chain_ok = prefill_chunk is not None and all(
                la is not None for la in self._spec.length_axes)
            self._prefix = pfx.PrefixCache(
                prefix_cache, chunk=prefill_chunk, chain_ok=chain_ok,
                salt=f"{cfg.family}:{prefill_chunk}")
            if self._plan is not None:
                self._prefix.note_remesh(self._plan.key)
        # keys pinned in the pool per slot, released at eviction
        self._slot_pins: List[tuple] = [()] * n_slots
        # -- admission fairness (token budget) --
        self._admit_budget = admit_token_budget
        self._deferrals = 0

    # -- request lifecycle --------------------------------------------------

    def submit(self, req: scheduler.Request,
               submitted: Optional[float] = None) -> str:
        """Validate and enqueue; returns resilience.QUEUED, or
        resilience.SHED when the bounded queue rejects the newcomer under
        the reject-new policy (under drop-oldest the VICTIM is shed and
        the newcomer queued).  Malformed requests and duplicate rids still
        raise -- those are caller bugs, not load conditions (duplicates
        would corrupt per-rid results and recovery bookkeeping).
        `submitted` is the `telemetry.now()` stamp of the client's own
        submit, where a front end took it first: the request's
        `request.queued` span starts there (else now)."""
        if req.rid in self._rids:
            self._robust["duplicate_rejects"] += 1
            raise ValueError(
                f"duplicate request id {req.rid}: this engine already "
                f"tracks that rid (rids key structured results and "
                f"recovery requeues)")
        if req.served_len > self.max_cache_len:
            raise ValueError(
                f"request {req.rid}: prompt+gen {req.served_len} exceeds "
                f"max_cache_len {self.max_cache_len}")
        if self.cfg.family == "encdec":
            shape = None if req.features is None \
                else np.asarray(req.features).shape
            if shape is None or len(shape) != 2 \
                    or shape[1] != self.cfg.d_model \
                    or not 1 <= shape[0] <= self.enc_len:
                raise ValueError(
                    f"request {req.rid}: encdec serving needs features of "
                    f"shape [1..enc_len={self.enc_len}, "
                    f"{self.cfg.d_model}], got {shape}")
        elif req.features is not None:
            raise ValueError(f"request {req.rid}: features are encdec-only "
                             f"(family {self.cfg.family!r})")
        if req.deadline is None and self._res.default_ttl_s is not None:
            req.deadline = req.arrival_time + self._res.default_ttl_s
        cap = self._res.max_queue
        if cap is not None and len(self._queue) >= cap:
            if self._res.shed_policy == "reject-new":
                self._robust["shed"] += 1
                self._rids.add(req.rid)
                self._finish(req, req.arrival_time, res.SHED,
                             f"queue full ({cap} queued), policy "
                             f"reject-new")
                return res.SHED
            victim = self._queue.pop_oldest()       # drop-oldest
            if victim is not None:
                self._robust["shed"] += 1
                self._finish(victim, req.arrival_time, res.SHED,
                             f"queue full ({cap} queued), policy "
                             f"drop-oldest")
        self._rids.add(req.rid)
        self._queue.submit(req)
        telemetry.request_begin("request.queued", req, submitted)
        return res.QUEUED

    def _finish(self, req: scheduler.Request, now: float,
                outcome: str = res.OK,
                error: Optional[str] = None) -> None:
        req.finish_time = now
        req.outcome = outcome
        req.error = error
        telemetry.request_done(req)
        if outcome == res.FAILED:
            self._robust["failed"] += 1
        self.finished.append(req)
        self._results[req.rid] = res.RequestResult(
            rid=req.rid, outcome=outcome, tokens=list(req.tokens),
            error=error, retries=req.retries,
            logprobs=list(req.logprobs) if req.logprobs else None,
            embedding=None if req.embedding is None
            else np.asarray(req.embedding, np.float32))

    def _evict(self, slot: int) -> None:
        """Free a page: no scrubbing needed (see module docstring)."""
        self._active[slot] = False
        self._slot_req[slot] = None
        self._remaining[slot] = 0
        self._pos[slot] = 0
        self._tok[slot] = 0
        self._replay[slot] = []
        self._score[slot] = []
        sampling.clear_row(self._samp, slot)
        if self._prefix is not None and self._slot_pins[slot]:
            self._prefix.release(self._slot_pins[slot])
        self._slot_pins[slot] = ()

    @staticmethod
    def _stopped(req: scheduler.Request, tok: int) -> bool:
        return bool(req.stop_tokens) and tok in req.stop_tokens

    # -- admission / prefill ------------------------------------------------

    def _compact(self) -> bool:
        """Remap surviving slots downward when eviction holes inflate the
        live batch bucket (the permutation is exact: slot identity is pure
        bookkeeping, every per-slot array moves together)."""
        live = np.nonzero(self._active)[0]
        if live.size == 0 or int(live[-1]) == live.size - 1:
            return False          # already a dense prefix
        cur = scheduler.bucket_pow2(int(live[-1]) + 1,
                                    minimum=self.min_batch_bucket,
                                    maximum=self.n_slots)
        tgt = scheduler.bucket_pow2(int(live.size),
                                    minimum=self.min_batch_bucket,
                                    maximum=self.n_slots)
        if cur <= tgt:
            return False          # hole doesn't change the bucket
        holes = np.asarray([i for i in range(self.n_slots)
                            if not self._active[i]], np.int64)
        perm = np.concatenate([live, holes])
        with telemetry.span("engine.compact"):
            self._cache = self._spec.permute_slots(self._cache, perm)
            self._samp = sampling.permute(self._samp, perm)
            if self._sd is not None:
                self._draft_cache = self._draft_spec.permute_slots(
                    self._draft_cache, perm)
        self._tok = self._tok[perm]
        self._pos = self._pos[perm]
        self._active = self._active[perm]
        self._remaining = self._remaining[perm]
        self._slot_req = [self._slot_req[i] for i in perm]
        self._replay = [self._replay[i] for i in perm]
        self._score = [self._score[i] for i in perm]
        self._slot_pins = [self._slot_pins[i] for i in perm]
        self.compactions += 1
        return True

    def _admit(self, now: float, clock: scheduler.Clock,
               resume_only: bool = False) -> int:
        with telemetry.span("engine.admit") as sp:
            n = self._admit_ready(now, clock, resume_only)
            sp.count(requests=n)
            if not n:
                sp.drop()
        return n

    @staticmethod
    def _note_popped(reqs: List[scheduler.Request]) -> None:
        """The requests left the queue: their queue wait ends and their
        prefill wait begins, at one stamp."""
        t = telemetry.now()
        for r in reqs:
            telemetry.request_end("request.queued", r, t)
            telemetry.request_begin("request.prefill", r, t)

    def _admit_ready(self, now: float, clock: scheduler.Clock,
                     resume_only: bool) -> int:
        self._compact()
        # resume_only (drain): only requests a fault recovery requeued --
        # carrying emitted tokens (generate) or a retry count (score/embed
        # leave no token trail) -- are taken; fresh requests keep their
        # queue position
        pred = (lambda r: bool(r.tokens) or r.retries > 0) \
            if resume_only else None
        # embed admission runs FIRST and separately: an embed request is
        # one prefill-shaped dispatch with no decode slot, so embeds admit
        # even when every slot is busy and never count against the slot
        # path's free-list or token budget (its own admission bucket
        # accounting, cache_info()["methods"])
        embeds = self._queue.pop_ready(
            now, limit=self.n_slots,
            predicate=lambda r: r.method == "embed"
            and (pred is None or pred(r)))
        self._note_popped(embeds)
        n_embed = self._admit_embed(embeds, clock) if embeds else 0
        free = [i for i in range(self.n_slots) if not self._active[i]]
        ready = self._queue.pop_ready(
            now, limit=len(free),
            predicate=lambda r: r.method != "embed"
            and (pred is None or pred(r)))
        if ready and self._admit_budget is not None:
            ready = self._defer_over_budget(ready)
        if not ready:
            return n_embed
        self._note_popped(ready)
        # popped but not yet registered in a slot: a fault mid-admission
        # leaves the leftovers here for _recover to requeue
        self._admitting = list(ready)
        # group by (prompt bucket, enc bucket) so one compiled prefill
        # graph per (batch bucket, prompt bucket[, enc bucket]) covers
        # the mix
        groups: Dict[tuple, List[scheduler.Request]] = {}
        for r in ready:
            sb = scheduler.bucket_pow2(r.prompt_len,
                                       minimum=self.min_prompt_bucket,
                                       maximum=self.max_cache_len)
            groups.setdefault((sb, self._enc_bucket(r)), []).append(r)
        for (sb, eb), group in sorted(
                groups.items(), key=lambda kv: (kv[0][0], kv[0][1] or 0)):
            self._admit_group(group, sb, eb, free, clock)
        self._admitting = []
        return len(ready) + n_embed

    def _enc_bucket(self, r: scheduler.Request) -> Optional[int]:
        if self.cfg.family != "encdec":
            return None
        return scheduler.bucket_pow2(int(np.asarray(r.features).shape[0]),
                                     minimum=self.enc_buckets[0],
                                     maximum=self.enc_len)

    def _admit_embed(self, group: List[scheduler.Request],
                     clock: scheduler.Clock) -> int:
        """Serve embed requests: per (prompt bucket, enc bucket) group,
        one pooled-embedding dispatch (lm.embed_pool through the bundle)
        whose result finishes each request immediately -- no slot state is
        touched, so embeds coexist with a full decode batch."""
        self._admitting = list(group)
        groups: Dict[tuple, List[scheduler.Request]] = {}
        for r in group:
            sb = scheduler.bucket_pow2(r.prompt_len,
                                       minimum=self.min_prompt_bucket,
                                       maximum=self.max_cache_len)
            groups.setdefault((sb, self._enc_bucket(r)), []).append(r)
        for (sb, eb), g in sorted(
                groups.items(), key=lambda kv: (kv[0][0], kv[0][1] or 0)):
            bb = scheduler.bucket_pow2(len(g), minimum=self._adm_floor,
                                       maximum=self.n_slots)
            inputs, lens = self._prefill_inputs(g, bb, sb, eb)
            self._graphs.add(("embed", bb, sb)
                             + (() if eb is None else (eb,)))
            emb, bad = self._guarded("embed", self._bundle.embed,
                                     self.params, inputs,
                                     jnp.asarray(lens - 1))
            emb = np.asarray(emb)
            bad = np.asarray(bad)
            now = clock.now()
            for i, r in enumerate(g):
                self._admitting = [x for x in self._admitting if x is not r]
                self._method_admits["embed"] += 1
                if bad[i]:
                    self._robust["quarantined"] += 1
                    self._finish(r, now, res.FAILED,
                                 "non-finite pooled embedding")
                    continue
                r.embedding = np.asarray(emb[i], np.float32)
                r.first_token_time = now
                self._finish(r, now)
        self._admitting = []
        return len(group)

    def _defer_over_budget(
            self, ready: List[scheduler.Request]) -> List[scheduler.Request]:
        """Admission fairness: take ready requests in queue order until
        their summed UNCACHED prompt tokens exceed admit_token_budget,
        then defer the rest back to the queue (ordered re-insertion
        preserves arrival order, so deferral never reorders).  The head
        request always proceeds -- an over-budget prompt stalls behind
        the budget forever otherwise.  With the prefix cache on, a
        request's cost is only its uncached tail (peek, so the budget
        probe never perturbs hit/miss counters or LRU order)."""
        take, spent = [], 0
        for r in ready:
            cost = r.prompt_len
            if self._prefix is not None:
                cost -= min(self._prefix.peek_cached_tokens(r), cost)
            if take and spent + cost > self._admit_budget:
                break
            take.append(r)
            spent += cost
        for r in ready[len(take):]:
            self._deferrals += 1
            self._queue.submit(r)
        return take

    def _prefill_bucket(self, sb: int) -> int:
        """static cache_len for a prefill dispatch.  Families without a
        length axis get cache_len == sb (the arg is unused by their
        blocks, and tying it to the prompt bucket keeps the compiled
        prefill census at one graph per (batch bucket, prompt bucket))."""
        if not self._spec.has_length_axis:
            return sb
        return scheduler.bucket_pow2(sb, minimum=self.min_len_bucket,
                                     maximum=self.max_cache_len)

    def _prefill_inputs(self, group: List[scheduler.Request], bb: int,
                        sb: int, eb: Optional[int] = None):
        prompts = np.zeros((bb, sb), np.int32)
        lens = np.ones((bb,), np.int32)
        for i, r in enumerate(group):
            prompts[i, :r.prompt_len] = r.prompt
            lens[i] = r.prompt_len
        if self.cfg.family != "encdec":
            return jnp.asarray(prompts), lens
        # ragged features, right-padded to the group's enc bucket; the
        # real frame counts ride along and mask the padding to exact
        # zeros inside the encoder and the cross-attention
        eb = eb or self.enc_len
        feats = np.zeros((bb, eb, self.cfg.d_model), np.float32)
        enc_lens = np.ones((bb,), np.int32)
        for i, r in enumerate(group):
            f = np.asarray(r.features, np.float32)
            feats[i, :f.shape[0]] = f
            enc_lens[i] = f.shape[0]
        audio = jnp.asarray(feats).astype(jnp.dtype(self.cfg.dtype))
        return (audio, jnp.asarray(prompts), jnp.asarray(enc_lens)), lens

    def _admit_group(self, group: List[scheduler.Request], sb: int,
                     eb: Optional[int], free: List[int],
                     clock: scheduler.Clock) -> None:
        g = len(group)
        t_pre = self._prefill_bucket(sb)
        if self._prefix is None:
            bb = scheduler.bucket_pow2(g, minimum=self._adm_floor,
                                       maximum=self.n_slots)
            with telemetry.span("engine.prefill", rows=bb * sb,
                                tokens=sum(r.prompt_len for r in group)):
                inputs, lens = self._prefill_inputs(group, bb, sb, eb)
                if self.prefill_chunk is None:
                    self._graphs.add(("prefill", bb, sb, t_pre)
                                     + (() if eb is None else (eb,)))
                    tok0, last, rows, bad0 = self._guarded(
                        "prefill", self._bundle.prefill, self.params,
                        inputs, jnp.asarray(lens - 1), t_pre, self.enc_len)
                else:
                    tok0, last, rows, bad0 = self._chunked_prefill(
                        np.asarray(inputs), lens, t_pre)
                tok0 = np.asarray(tok0)
                bad0 = np.asarray(bad0)
            slots = np.asarray([free.pop(0) for _ in range(g)], np.int32)
            # scatter the admitted pages into their slots; leaves without
            # a length axis (SSM/conv state, cross-KV) are reset wholesale
            with telemetry.span("engine.scatter", group=g):
                self._cache = self._spec.admit(self._cache, rows, slots, g,
                                               t_pre=t_pre)
            if self._sd is not None:
                # draft prefill: same prompts, same bucket, same slots --
                # draft and target stay position-synchronized (they share
                # self._pos) from admission through every round/replay
                self._graphs.add(("dprefill", bb, sb, t_pre))
                _, _, d_rows, _ = self._guarded(
                    "draft", self._draft_bundle.prefill,
                    self._draft_params, inputs, jnp.asarray(lens - 1),
                    t_pre, None)
                self._draft_cache = self._draft_spec.admit(
                    self._draft_cache, d_rows, slots, g, t_pre=t_pre)
            pins: List[tuple] = [()] * g
        elif self.prefill_chunk is not None:
            tok0, bad0, slots, pins, last = self._prefix_admit_chunked(
                group, sb, t_pre, free)
        else:
            tok0, bad0, slots, pins, last = self._prefix_admit_full(
                group, sb, eb, t_pre, free)
        # registration time is read AFTER the admitting dispatch, so a
        # request's TTFT (first_token_time - arrival) includes its own
        # prefill cost -- the time a prefix hit actually saves
        self._register_admitted(group, tok0, bad0, slots, pins, free,
                                clock.now(), last=last)

    def _register_admitted(self, group: List[scheduler.Request],
                           tok0: np.ndarray, bad0: np.ndarray,
                           slots: np.ndarray, pins: List[tuple],
                           free: List[int], now: float,
                           last=None) -> None:
        """Per-request bookkeeping once a group's pages are in their
        slots -- the shared tail of the cold and prefix-cache admission
        paths: quarantine, recovery-replay scheduling, fresh-stream
        start.  `last` gives each row's final prefill logits (an array or
        an {index: row} dict); score admissions read their first logprob
        from it (score rows never take the terminal-hit shortcut, so the
        row is always present for them)."""
        # writable copy: np.asarray over a device array is read-only, and
        # sampled admissions override their row's tok0 below
        tok0 = np.array(tok0)
        t = telemetry.now()
        for i, r in enumerate(group):
            telemetry.request_end("request.prefill", r, t)
            slot = int(slots[i])
            self._admitting = [x for x in self._admitting if x is not r]
            self._method_admits[r.method] += 1
            if bad0[i]:
                # quarantine at prefill: structured FAILED outcome, and
                # the slot's freshly-scattered pages are scrubbed -- the
                # mask zeroes stale FINITE values exactly, but 0*NaN=NaN
                # would leak into a later tenant's softmax.  The slot
                # never owned its pins (release directly)
                if self._prefix is not None and pins[i]:
                    self._prefix.release(pins[i])
                self._robust["quarantined"] += 1
                self._finish(r, now, res.FAILED,
                             "non-finite logits at prefill")
                self._scrub(slot)
                free.append(slot)
                free.sort()
                continue
            # pins transfer to the slot BEFORE any eviction path below,
            # so _evict is the single release point for owned pins
            self._slot_pins[slot] = tuple(pins[i])
            if r.method == "generate" and not sampling.is_greedy(r):
                # sampled first token, recomputed host-side from this
                # row's final prefill logits at generated-token index 0
                # (bitwise the in-scan sample: the sampler is per-row).
                # Non-greedy rows never take the terminal-hit shortcut,
                # so the row is always present; the pool keeps the GREEDY
                # argmax token, so cached entries stay policy-free
                tok0[i, 0] = sampling.expected_token(r, last[i], 0)
            # the slot's sampling-page row: policy + counter key +
            # prompt_len, consumed by every segment/spec dispatch
            sampling.write_row(self._samp, slot, r)
            if r.method == "score":
                # teacher-forced scoring: the prefill's last logits row is
                # the distribution completion[0] is scored under; the rest
                # of the completion drains through the replay chunk path.
                # A recovery re-admission recomputes bitwise-identical
                # rows, so resetting logprobs repeats the lost floats.
                comp = list(r.score_tokens)
                row = np.asarray(last[i], np.float32)
                r.logprobs = [smethods.logprob_from_logits(row, comp[0])]
                if r.first_token_time is None:
                    r.first_token_time = now
                if len(comp) == 1:
                    self._finish(r, now)
                    self._evict(slot)
                    free.append(slot)
                    free.sort()
                    continue
                self._slot_req[slot] = r
                self._active[slot] = True
                self._pos[slot] = r.prompt_len
                self._tok[slot] = comp[0]
                self._remaining[slot] = 0
                self._score[slot] = [int(t) for t in comp[1:]]
                continue
            if r.tokens:
                # recovery-as-replay: this request was requeued by
                # _recover with its already-emitted tokens.  The prefill
                # above bitwise repeated its original admission (original
                # prompt -> same prompt bucket -> same compiled graph);
                # verify the regenerated first token and schedule the
                # remaining recorded tokens for teacher-forced replay
                # through the decode path (_drain_replay)
                if int(tok0[i, 0]) != r.tokens[0]:
                    self._robust["replay_divergence"] += 1
                self._slot_req[slot] = r
                self._active[slot] = True
                self._pos[slot] = r.prompt_len
                self._tok[slot] = r.tokens[0]
                self._remaining[slot] = r.max_new_tokens - len(r.tokens)
                self._replay[slot] = [int(t) for t in r.tokens[1:]]
                continue
            r.tokens = [int(tok0[i, 0])]
            r.first_token_time = now
            self.total_generated += 1
            if r.max_new_tokens == 1 or self._stopped(r, r.tokens[0]):
                self._finish(r, now)
                self._evict(slot)
                free.append(slot)
                free.sort()
                continue
            self._slot_req[slot] = r
            self._active[slot] = True
            self._pos[slot] = r.prompt_len
            self._tok[slot] = tok0[i]
            self._remaining[slot] = r.max_new_tokens - 1

    def _concat_pages(self, entries: List[pfx.Entry]) -> list:
        """Concatenate consecutive chain entries' pages along each leaf's
        length axis (host-side; chain entries exist only for all-length-
        paged families, so no leaf is None)."""
        out = []
        for j, la in enumerate(self._spec.length_axes):
            ps = [e.pages[j] for e in entries]
            out.append(ps[0] if len(ps) == 1 else np.concatenate(ps,
                                                                 axis=la))
        return out

    def _chunk_pages(self, span: list, j: int, c: int) -> list:
        """Host-side chunk j of an extracted multi-chunk span."""
        out = []
        for la, p in zip(self._spec.length_axes, span):
            if p is None:
                out.append(None)
                continue
            idx = [slice(None)] * p.ndim
            idx[la] = slice(j * c, (j + 1) * c)
            out.append(np.ascontiguousarray(p[tuple(idx)]))
        return out

    def _reshard_state(self) -> None:
        """Host-sourced page writes re-enter device state under the
        CURRENT plan's PartitionSpecs (the _scrub pattern) -- this is
        where pooled pages get 're-sharded' after an elastic degrade."""
        if self._plan is not None:
            self._cache = jax.device_put(
                self._cache, dshard.to_shardings(self._plan.state_specs(),
                                                 self._plan.mesh))

    def _prefix_admit_full(self, group: List[scheduler.Request], sb: int,
                           eb: Optional[int], t_pre: int,
                           free: List[int]):
        """Prefix-cache admission for full-prefill engines (every family,
        including sequential-state ones): an exact-repeat (terminal) hit
        copies its pooled pages -- KV rows plus constant-size state
        snapshots -- straight into the slot, ZERO prefill dispatches; the
        misses prefill as one smaller bucketed sub-group (batch
        composition cannot perturb a row, module docstring, so the
        shrunken bucket is bit-safe) and donate their pages back to the
        pool."""
        g = len(group)
        slots = np.asarray([free.pop(0) for _ in range(g)], np.int32)
        tok0 = np.zeros((g, 1), np.int32)
        bad0 = np.zeros((g,), bool)
        pins: List[tuple] = [()] * g
        last: Dict[int, np.ndarray] = {}
        miss_idx: List[int] = []
        wrote = False
        for i, r in enumerate(group):
            # score requests need the final LOGITS row, which pooled pages
            # don't carry -- they always take the prefill path (and still
            # donate their pages for later generate hits); skipping lookup
            # keeps their traffic out of the hit/miss stats and LRU order.
            # Sampled (non-greedy) requests also need the row: a pooled
            # entry's tok0 is the GREEDY token, theirs must be re-sampled
            hit = self._prefix.lookup(r) \
                if r.method != "score" and sampling.is_greedy(r) else None
            if hit is None or hit.terminal is None:
                miss_idx.append(i)
                continue
            ent = hit.terminal
            self._cache = self._spec.write_row_pages(
                self._cache, int(slots[i]), 0, ent.pages)
            wrote = True
            tok0[i, 0] = ent.tok0
            pins[i] = self._prefix.pin([ent.key])
            self._prefix.note_skip(r.prompt_len)
        if miss_idx:
            sub = [group[i] for i in miss_idx]
            bb = scheduler.bucket_pow2(len(sub), minimum=self._adm_floor,
                                       maximum=self.n_slots)
            with telemetry.span("engine.prefill", rows=bb * sb,
                                tokens=sum(r.prompt_len for r in sub)):
                inputs, lens = self._prefill_inputs(sub, bb, sb, eb)
                self._graphs.add(("prefill", bb, sb, t_pre)
                                 + (() if eb is None else (eb,)))
                stok0, slast, rows, sbad0 = self._guarded(
                    "prefill", self._bundle.prefill, self.params, inputs,
                    jnp.asarray(lens - 1), t_pre, self.enc_len)
                stok0 = np.asarray(stok0)
                sbad0 = np.asarray(sbad0)
            need_last = any(group[i].method == "score"
                            or not sampling.is_greedy(group[i])
                            for i in miss_idx)
            slast_np = np.asarray(slast) if need_last else None
            sub_slots = slots[np.asarray(miss_idx, np.int64)]
            with telemetry.span("engine.scatter", group=len(sub)):
                self._cache = self._spec.admit(self._cache, rows,
                                               sub_slots, len(sub),
                                               t_pre=t_pre)
            for j, i in enumerate(miss_idx):
                tok0[i, 0] = stok0[j, 0]
                bad0[i] = sbad0[j]
                if slast_np is not None:
                    last[i] = slast_np[j]
                if not sbad0[j]:
                    r = group[i]
                    self._prefix.insert_terminal(
                        r, self._spec.extract_row_pages(
                            rows, j, 0, r.prompt_len),
                        int(stok0[j, 0]))
        if wrote:
            self._reshard_state()
        return tok0, bad0, slots, pins, last

    def _prefix_admit_chunked(self, group: List[scheduler.Request],
                              sb: int, t_pre: int, free: List[int]):
        """Prefix-cache admission for chunked-prefill engines: each row
        resumes at its first uncached chunk -- pooled chunk pages are
        copied in below the resume point (copy-on-write: everything at or
        past it is computed into the row's private pages) -- and a chunk
        dispatch is skipped outright once every row is past it.  The rows
        that do run go through the SAME compiled ("chunk", bb, c, t_pre)
        graph as a cold admission; copied pages are bitwise what this
        row's own chunks would have written (KV purity), and masking
        hides batch composition, so the harvested logits -- and every
        downstream token -- are bit-identical to the cold path."""
        g = len(group)
        bb = scheduler.bucket_pow2(g, minimum=self._adm_floor,
                                   maximum=self.n_slots)
        c = min(self.prefill_chunk, sb)
        n_chunks = sb // c
        prompts = np.zeros((bb, sb), np.int32)
        lens = np.ones((bb,), np.int32)
        for i, r in enumerate(group):
            prompts[i, :r.prompt_len] = r.prompt
            lens[i] = r.prompt_len
        last_chunk = (lens - 1) // c
        cache = self._spec.init_state(bb, t_pre)
        resume = np.full((bb,), n_chunks, np.int64)  # padding: never runs
        term: List[Optional[pfx.Entry]] = [None] * g
        n_chain = [0] * g
        pin_keys: List[List[bytes]] = [[] for _ in range(g)]
        for i, r in enumerate(group):
            if r.method == "score":
                # score needs the final logits row: run every chunk cold
                # (resume stays 0; pages still donate back to the pool)
                # without touching the pool's stats or LRU order
                resume[i] = 0
                continue
            hit = self._prefix.lookup(r)
            if hit.terminal is not None and sampling.is_greedy(r):
                # terminal shortcut is greedy-only: the pooled tok0 is
                # the argmax token.  A sampled request still rides any
                # chain hits below and re-runs its final chunk, which
                # recovers the logits row its tok0 is sampled from
                cache = self._spec.write_row_pages(cache, i, 0,
                                                   hit.terminal.pages)
                term[i] = hit.terminal
                pin_keys[i].append(hit.terminal.key)
                self._prefix.note_skip(r.prompt_len)
                continue
            if hit.chain:
                # one write per leaf for the whole cached span (chunk
                # pages concatenated host-side), not one per chunk
                cache = self._spec.write_row_pages(
                    cache, i, 0, self._concat_pages(hit.chain))
                pin_keys[i].extend(ent.key for ent in hit.chain)
            n_chain[i] = len(hit.chain)
            # resume at the first uncached chunk; a chain covering the
            # final chunk still re-runs it (rewriting identical bits)
            # to recover the first-token logits
            resume[i] = min(len(hit.chain), int(last_chunk[i]))
            self._prefix.note_skip(int(resume[i]) * c)
        last: Dict[int, object] = {}
        with telemetry.span("engine.prefill", tokens=sum(
                int(lens[i]) - int(resume[i]) * c
                for i in range(g) if term[i] is None)) as sp:
            n_run = 0
            for k in range(n_chunks):
                act = (resume <= k) & (k <= last_chunk)
                act[g:] = False
                if not act.any():
                    continue    # every row is past this chunk: no dispatch
                n_run += 1
                self._graphs.add(("chunk", bb, c, t_pre))
                toks = jnp.asarray(prompts[:, k * c:(k + 1) * c])
                pos = jnp.full((bb,), k * c, jnp.int32)
                logits, cache = self._guarded(
                    "chunk", self._bundle.chunk_step, self.params, toks,
                    cache, pos, jnp.asarray(act))
                hit_rows = np.nonzero((last_chunk == k) & act)[0]
                if hit_rows.size:
                    # harvest on the host: a device gather would compile
                    # one program per hit-row arity, and argmax over the
                    # exact same bits is order-free either way
                    lg = np.asarray(logits)
                    for b in hit_rows:
                        last[int(b)] = lg[int(b), int((lens[b] - 1) % c)]
            tok0 = np.zeros((g, 1), np.int32)
            bad0 = np.zeros((g,), bool)
            for i in range(g):
                if term[i] is not None:
                    tok0[i, 0] = term[i].tok0
                    continue
                row = np.asarray(last[i])
                # host argmax over identical logits bits == the device
                # argmax (comparison-based, no float accumulation; same
                # argument as _replay_step)
                tok0[i, 0] = int(np.argmax(row))
                bad0[i] = not bool(np.all(np.isfinite(row)))
            sp.count(rows=n_run * bb * c)
            if not n_run:
                sp.drop()
        # donate computed pages back to the pool (never from a faulted
        # dispatch -- an exception above unwinds before this point)
        for i in range(g):
            if term[i] is not None or bad0[i]:
                continue
            r = group[i]
            # ONE extraction (and one blocking device transfer) per miss
            # row: the terminal pages cover [0, prompt_len), and chain
            # chunk pages are host-side slices of them (chain_ok engines
            # have every leaf length-paged, so the slices line up)
            full = self._spec.extract_row_pages(cache, i, 0, r.prompt_len)
            n_full = r.prompt_len // c
            if self._prefix.chain_ok and n_full > n_chain[i]:
                keys = self._prefix.chain_keys(r.prompt, req=r)
                for k in range(n_chain[i], n_full):
                    self._prefix.insert_chain(
                        keys[k], self._chunk_pages(full, k, c))
            self._prefix.insert_terminal(r, full, int(tok0[i, 0]))
        pins = [self._prefix.pin(pk) for pk in pin_keys]
        slots = np.asarray([free.pop(0) for _ in range(g)], np.int32)
        with telemetry.span("engine.scatter", group=g):
            self._cache = self._spec.admit(self._cache, cache, slots, g,
                                           t_pre=t_pre)
        self._reshard_state()
        return tok0, bad0, slots, pins, last

    def _chunked_prefill(self, prompts: np.ndarray, lens: np.ndarray,
                         t_pre: int):
        """Prefill through the decode path, `prefill_chunk` tokens per
        dispatch -- the same compiled family (and bucket shapes) as decode
        segments, so prefill work interleaves instead of needing its own
        wide graphs."""
        bb, sb = prompts.shape
        c = min(self.prefill_chunk, sb)
        assert sb % c == 0, (sb, c)
        cache = self._spec.init_state(bb, t_pre)
        active = jnp.ones((bb,), bool)
        # only each row's last-real-position logits are needed; harvest
        # them per chunk so one [bb, c, V] block is ever live
        last = [None] * bb
        self._graphs.add(("chunk", bb, c, t_pre))
        for k in range(sb // c):
            toks = jnp.asarray(prompts[:, k * c:(k + 1) * c])
            pos = jnp.full((bb,), k * c, jnp.int32)
            logits, cache = self._guarded(
                "chunk", self._bundle.chunk_step, self.params, toks,
                cache, pos, active)
            hit = np.nonzero((lens - 1) // c == k)[0]
            if hit.size:
                sel = logits[jnp.asarray(hit),
                             jnp.asarray((lens[hit] - 1) % c)]
                for j, b in enumerate(hit):
                    last[b] = sel[j]
        stack = jnp.stack(last)
        tok0 = jnp.argmax(stack, axis=-1)
        bad0 = ~jnp.all(jnp.isfinite(stack), axis=-1)
        return tok0.astype(jnp.int32)[:, None], stack, cache, bad0

    # -- decode segments ----------------------------------------------------

    def _segment_shape(self):
        """(bb, t_b) for the next segment; t_b is None for constant-size
        state (no length bucketing -- batch-bucket-only graph growth)."""
        hi = int(np.max(np.nonzero(self._active)[0])) + 1
        bb = scheduler.bucket_pow2(hi, minimum=self.min_batch_bucket,
                                   maximum=self.n_slots)
        if not self._spec.has_length_axis:
            return bb, None
        need = int(np.max(self._pos[:bb][self._active[:bb]])) \
            + self.segment_len
        t_b = scheduler.bucket_pow2(min(need, self.max_cache_len),
                                    minimum=self.min_len_bucket,
                                    maximum=self.max_cache_len)
        return bb, t_b

    def _begin_segment(self) -> "_PendingSegment":
        """DISPATCH one fused decode segment over the bucketed active
        prefix and return immediately -- the outputs stay device arrays
        (JAX async dispatch), so the host is free to do other work while
        the device crunches.  `_finish_segment` is the blocking sync."""
        bb, t_b = self._segment_shape()
        n_steps = self.segment_len
        self._graphs.add(("segment", bb, t_b, n_steps))
        active = int(np.sum(self._active))
        sampled = int(np.sum(self._samp[1][:bb] > 0))
        sp = telemetry.span("engine.segment", active=active, bb=bb,
                            t_b=t_b or 0, sampled=sampled)
        try:
            fast = bb == self.n_slots and (t_b is None
                                           or t_b == self.max_cache_len)
            cache_in = self._cache if fast else \
                self._spec.slice_live(self._cache, bb, t_b)
            seq, tok, cache_out, pos, bad = self._guarded(
                "segment", self._bundle.segment,
                self.params, jnp.asarray(self._tok[:bb]), cache_in,
                jnp.asarray(self._pos[:bb]), jnp.asarray(self._active[:bb]),
                sampling.operand(self._samp, bb), n_steps)
            if fast:
                self._cache = cache_out
            else:
                self._cache = self._spec.merge_live(self._cache, cache_out,
                                                    bb, t_b)
        except BaseException:
            sp.close()
            raise
        self._note_occupancy(active)
        self._note_sampler(sampled)
        return _PendingSegment(bb=bb, seq=seq, tok=tok, pos=pos, bad=bad,
                               span=sp)

    def _note_occupancy(self, active: int) -> None:
        self._occupancy["active_slot_segments"] += active
        self._occupancy["slot_segments"] += self.n_slots

    def _note_sampler(self, sampled: int) -> None:
        self._sampler_path["sampled" if sampled else "argmax"] += 1

    def _finish_segment(self, p: "_PendingSegment",
                        clock: scheduler.Clock) -> None:
        """Block on a dispatched segment's outputs and harvest.  An
        eviction between begin and finish (cancel/expire) is safe: the
        tok/pos writeback lands stale values on the freed slot, but an
        inactive slot's tok/pos are dead state -- admission overwrites
        them before the slot decodes again, and _harvest skips slots
        whose request is gone."""
        with p.span:
            with telemetry.span("engine.sync"):
                self._tok[:p.bb] = np.asarray(p.tok)
                self._pos[:p.bb] = np.asarray(p.pos)
                seq, bad = np.asarray(p.seq), np.asarray(p.bad)
            self._harvest(seq, bad, clock.now())

    def _harvest(self, seq: np.ndarray, bad: np.ndarray,
                 now: float) -> None:
        with telemetry.span("engine.harvest") as sp:
            before = self.total_generated
            self._harvest_slots(seq, bad, now)
            sp.count(tokens=self.total_generated - before)

    def _harvest_slots(self, seq: np.ndarray, bad: np.ndarray,
                       now: float) -> None:
        n_steps, bb = seq.shape
        for slot in range(bb):
            req = self._slot_req[slot]
            if req is None:
                continue
            if bad[slot]:
                # quarantine: this slot's logits went non-finite during
                # the segment.  Masking isolation means no OTHER slot saw
                # it, but this segment's tokens for the slot are not
                # trustworthy (the flag is per-segment, not per-step), so
                # the request fails with the tokens it had, and its pages
                # are scrubbed before reuse (_scrub)
                self._robust["quarantined"] += 1
                self._finish(req, now, res.FAILED,
                             "non-finite logits during decode")
                self._evict(slot)
                self._scrub(slot)
                continue
            take = int(min(self._remaining[slot], n_steps))
            toks = seq[:take, slot]
            done = False
            if req.stop_tokens:
                hits = np.nonzero(np.isin(toks, req.stop_tokens))[0]
                if hits.size:
                    toks = toks[:int(hits[0]) + 1]   # stop token included
                    done = True
            req.tokens.extend(int(t) for t in toks)
            self.total_generated += len(toks)
            self._remaining[slot] -= len(toks)
            if done or self._remaining[slot] == 0:
                self._finish(req, now)
                self._evict(slot)

    # -- self-speculative decoding (SpecDecodeConfig) ------------------------

    def _spec_round(self, clock: scheduler.Clock) -> None:
        """One speculative round: the draft free-runs k+1 sampled steps
        (k drafts, plus the consumption step a full acceptance needs),
        the target verifies all k drafts in ONE batched dispatch, and
        both states roll back to the accepted prefix in-graph -- SILVIA's
        speculatively-pack / verify-legality / roll-back-on-conflict
        rewrite at the serve-loop level (DESIGN.md sec. 12).

        Emitted tokens are always the TARGET's g_seq tokens under a
        teacher-forced prefix, so streams are byte-identical to the
        non-speculative engine no matter how often the draft is right;
        acceptance only changes how many tokens one target dispatch
        yields (tokens-per-dispatch, benchmarks/spec_decode.py).  Both
        models sample under the SAME per-slot counter keys, so acceptance
        is a pure function of (seed, rid, token prefix) -- recovery
        replay is therefore acceptance-history-exact by construction."""
        with telemetry.span("engine.spec_round"):
            self._spec_round_inner(clock)

    def _spec_round_inner(self, clock: scheduler.Clock) -> None:
        k = self._sd.k
        hi = int(np.max(np.nonzero(self._active)[0])) + 1
        bb = scheduler.bucket_pow2(hi, minimum=self.min_batch_bucket,
                                   maximum=self.n_slots)
        t_b = None
        if self._spec.has_length_axis:
            # the verify scan writes rows pos..pos+k (overruns clamp into
            # the slot's own discarded row, as in decode_scan)
            need = int(np.max(self._pos[:bb][self._active[:bb]])) + k + 1
            t_b = scheduler.bucket_pow2(min(need, self.max_cache_len),
                                        minimum=self.min_len_bucket,
                                        maximum=self.max_cache_len)
        self._graphs.add(("draft", bb, t_b, k + 1))
        self._graphs.add(("verify", bb, t_b, k + 1))
        samp = sampling.operand(self._samp, bb)
        tok = jnp.asarray(self._tok[:bb])
        pos = jnp.asarray(self._pos[:bb])
        active = jnp.asarray(self._active[:bb])
        fast = bb == self.n_slots and (t_b is None
                                       or t_b == self.max_cache_len)
        d_in = self._draft_cache if fast else \
            self._draft_spec.slice_live(self._draft_cache, bb, t_b)
        d_seq, d_cache, d_snaps = self._guarded(
            "draft", self._dfns.draft, self._draft_params, tok, d_in,
            pos, active, samp, k + 1)
        # the verify dispatch consumes the pending token then the k
        # drafts, teacher-forced
        xs = jnp.concatenate([tok[None], d_seq[:k, :, None]], axis=0)
        c_in = self._cache if fast else \
            self._spec.slice_live(self._cache, bb, t_b)
        g_seq, m, c_out, pos_out, bad = self._guarded(
            "verify", self._sfns.verify, self.params, c_in, pos, active,
            samp, xs)
        if self._draft_const:
            # constant-size draft leaves restore from the per-step
            # snapshots; pure length-paged drafts roll back for free
            self._graphs.add(("rollback", bb, t_b, k + 1))
            d_cache = self._dfns.rollback(d_cache, d_snaps, m)
        if fast:
            self._cache = c_out
            self._draft_cache = d_cache
        else:
            self._cache = self._spec.merge_live(self._cache, c_out,
                                                bb, t_b)
            self._draft_cache = self._draft_spec.merge_live(
                self._draft_cache, d_cache, bb, t_b)
        self._note_occupancy(int(np.sum(self._active)))
        self._note_sampler(int(np.sum(self._samp[1][:bb] > 0)))
        self._pos[:bb] = np.asarray(pos_out)
        self._spec_harvest(np.asarray(g_seq), np.asarray(m),
                           np.asarray(bad), clock.now())

    def _spec_harvest(self, g_seq: np.ndarray, m: np.ndarray,
                      bad: np.ndarray, now: float) -> None:
        """Host bookkeeping after a round: per live slot, emit the m+1
        target tokens the round settled (the accepted drafts' positions
        plus the first disagreeing/extending target token) -- the same
        stop-token/remaining logic as _harvest, so streams truncate
        identically."""
        k1, bb = g_seq.shape
        self._spec_stats["rounds"] += 1
        self._spec_stats["target_dispatches"] += 1
        for slot in range(bb):
            req = self._slot_req[slot]
            if req is None or not self._active[slot]:
                continue
            if bad[slot]:
                self._robust["quarantined"] += 1
                self._finish(req, now, res.FAILED,
                             "non-finite logits during decode")
                self._evict(slot)
                self._scrub(slot)
                continue
            self._spec_stats["drafted"] += k1 - 1
            self._spec_stats["accepted"] += int(m[slot])
            e = int(m[slot]) + 1
            take = int(min(self._remaining[slot], e))
            toks = g_seq[:take, slot]
            done = False
            if req.stop_tokens:
                hits = np.nonzero(np.isin(toks, req.stop_tokens))[0]
                if hits.size:
                    toks = toks[:int(hits[0]) + 1]
                    done = True
            req.tokens.extend(int(t) for t in toks)
            self.total_generated += len(toks)
            self._spec_stats["emitted"] += len(toks)
            self._remaining[slot] -= len(toks)
            if done or self._remaining[slot] == 0:
                self._finish(req, now)
                self._evict(slot)
                continue
            # the new pending token: the target's token right after the
            # last accepted draft (pos was advanced to p+m+1 in-graph)
            self._tok[slot] = g_seq[e - 1, slot]

    # -- resilience: chaos sites, expiry, replay, recovery ------------------

    def _guarded(self, kind: str, fn, *args):
        """Every device dispatch funnels through here: count the per-kind
        site, give the chaos schedule its shot at it, then dispatch.  The
        check fires BEFORE the call, so an injected fault never leaves a
        donated buffer half-consumed; failures unwind to step()/drain(),
        which recover."""
        idx = self._site_counts[kind]
        self._site_counts[kind] = idx + 1
        if self._chaos is not None:
            self._chaos.check_site(f"{kind}:{idx}")
        return fn(*args)

    def _expire(self, now: float) -> int:
        """EXPIRED outcomes for requests past their deadline: queued ones
        never dispatch; in-flight ones are cancelled by slot eviction,
        keeping the tokens already emitted."""
        n = 0
        for req in self._queue.pop_expired(now):
            self._robust["expired_queued"] += 1
            self._finish(req, now, res.EXPIRED,
                         "deadline exceeded in queue")
            n += 1
        for slot in range(self.n_slots):
            req = self._slot_req[slot]
            if req is not None and req.expired(now):
                self._robust["expired_inflight"] += 1
                self._finish(req, now, res.EXPIRED,
                             "deadline exceeded in flight")
                self._evict(slot)
                n += 1
        return n

    def _scrub(self, slot: int) -> None:
        """Overwrite a quarantined slot's pages with freshly initialized
        state.  Normal eviction never scrubs (stale FINITE values are
        masked to exact zeros -- module docstring), but non-finite pages
        would survive the mask: a masked softmax weight is an exact 0,
        and 0 * NaN = NaN."""
        zeros = self._spec.init_state(1, self.max_cache_len)
        self._cache = self._spec.admit(self._cache, zeros,
                                       np.asarray([slot], np.int32), 1)
        if self._plan is not None:
            self._cache = jax.device_put(
                self._cache, dshard.to_shardings(self._plan.state_specs(),
                                                 self._plan.mesh))
        if self._sd is not None:
            # the draft saw the same poisoned row: scrub its page too
            dz = self._draft_spec.init_state(1, self.max_cache_len)
            self._draft_cache = self._draft_spec.admit(
                self._draft_cache, dz, np.asarray([slot], np.int32), 1)
            if self._draft_plan is not None:
                self._draft_cache = jax.device_put(
                    self._draft_cache,
                    dshard.to_shardings(self._draft_plan.state_specs(),
                                        self._draft_plan.mesh))

    def _drain_replay(self, clock: scheduler.Clock) -> None:
        """Teacher-forced replay of recovered requests' recorded tokens,
        one single-token chunk dispatch at a time, through the SAME
        compiled decode family as live traffic.  Replaying -- rather than
        re-prefilling prompt+emitted in one go -- is what keeps recovery
        bit-exact for EVERY family: prefill and stepwise decode are
        different floating-point reduction orders for sequential state
        (slot_state.FamilyState.prefill_chunkable), but a replayed step
        repeats the fault-free step's ops bitwise.  Each replayed token is
        verified against the recorded stream (`replay_divergence` --
        determinism doubling as the recovery proof obligation, DESIGN.md
        sec. 8).

        Score requests drain through the SAME dispatches: teacher-forcing
        a fixed completion is exactly replay with the expected token
        supplied by the caller instead of the recorded stream, plus a
        host logprob harvested from each step's logits row
        (methods.logprob_from_logits)."""
        while any(self._replay) or any(self._score):
            self._replay_step(clock.now())

    def _replay_step(self, now: float) -> None:
        hi = int(np.max(np.nonzero(self._active)[0])) + 1
        bb = scheduler.bucket_pow2(hi, minimum=self.min_batch_bucket,
                                   maximum=self.n_slots)
        t_b = None
        if self._spec.has_length_axis:
            need = int(np.max(self._pos[:bb][self._active[:bb]])) + 1
            t_b = scheduler.bucket_pow2(min(need, self.max_cache_len),
                                        minimum=self.min_len_bucket,
                                        maximum=self.max_cache_len)
        self._graphs.add(("chunk", bb, 1, t_b))
        # only slots mid-replay (or mid-score) are active in this
        # dispatch: co-resident caught-up requests neither advance nor
        # perturb (masking + batch composition invariants, module
        # docstring)
        replaying = np.asarray([bool(self._replay[s])
                                or bool(self._score[s])
                                for s in range(bb)])
        fast = bb == self.n_slots and (t_b is None
                                       or t_b == self.max_cache_len)
        cache_in = self._cache if fast else \
            self._spec.slice_live(self._cache, bb, t_b)
        logits, cache_out = self._guarded(
            "chunk", self._bundle.chunk_step,
            self.params, jnp.asarray(self._tok[:bb]), cache_in,
            jnp.asarray(self._pos[:bb]), jnp.asarray(replaying))
        if fast:
            self._cache = cache_out
        else:
            self._cache = self._spec.merge_live(self._cache, cache_out,
                                                bb, t_b)
        if self._sd is not None:
            # the draft teacher-forces the same token at the same
            # position, so draft state stays replay-synchronized and the
            # post-recovery rounds draft from exactly the state a
            # fault-free run would have -- acceptance-history-exact
            self._graphs.add(("dchunk", bb, 1, t_b))
            d_in = self._draft_cache if fast else \
                self._draft_spec.slice_live(self._draft_cache, bb, t_b)
            _, d_out = self._guarded(
                "draft", self._draft_bundle.chunk_step,
                self._draft_params, jnp.asarray(self._tok[:bb]), d_in,
                jnp.asarray(self._pos[:bb]), jnp.asarray(replaying))
            if fast:
                self._draft_cache = d_out
            else:
                self._draft_cache = self._draft_spec.merge_live(
                    self._draft_cache, d_out, bb, t_b)
        last = logits[:, -1, :]
        nxt = np.asarray(jnp.argmax(last, axis=-1))
        bad = np.asarray(~jnp.all(jnp.isfinite(last), axis=-1))
        # full rows transfer when a score slot needs its logprob, or a
        # sampled slot needs replay verification (sampling.sample_host)
        need_rows = any(self._score[s] for s in range(bb)) or any(
            self._replay[s] and self._slot_req[s] is not None
            and not sampling.is_greedy(self._slot_req[s])
            for s in range(bb))
        last_np = np.asarray(last) if need_rows else None
        for slot in range(bb):
            if not replaying[slot]:
                continue
            if bad[slot]:
                self._robust["quarantined"] += 1
                self._finish(self._slot_req[slot], now, res.FAILED,
                             "non-finite logits during replay")
                self._evict(slot)
                self._scrub(slot)
                continue
            if self._score[slot]:
                req = self._slot_req[slot]
                tok = self._score[slot].pop(0)
                req.logprobs.append(
                    smethods.logprob_from_logits(last_np[slot], tok))
                self._tok[slot] = tok      # teacher forcing
                self._pos[slot] += 1
                if not self._score[slot]:
                    self._finish(req, now)
                    self._evict(slot)
                continue
            expect = self._replay[slot].pop(0)
            self._robust["replayed_tokens"] += 1
            req = self._slot_req[slot]
            # greedy: host argmax over identical logits bits == the
            # in-scan argmax (comparison-based, no float accumulation).
            # Sampled: recompute the token through the SAME jitted
            # sampler on this row (sampling.expected_token) -- the
            # counter key needs only (seed, rid, t), no sampler state
            if sampling.is_greedy(req):
                actual = int(nxt[slot])
            else:
                actual = sampling.expected_token(
                    req, last_np[slot],
                    int(self._pos[slot]) - req.prompt_len + 1)
            if actual != expect:
                self._robust["replay_divergence"] += 1
            self._tok[slot] = expect       # teacher forcing
            self._pos[slot] += 1

    def _degrade(self, exc: "delastic.DeviceLoss") -> None:
        """Elastic re-shard after device loss (distributed/elastic.py).

        SILVIA rebinds ops to fewer DSPs with identical results; this
        rebinds slots to fewer devices with identical tokens (DESIGN.md
        sec. 9).  The health registry drops the lost devices, the planner
        picks the largest valid healthy sub-mesh (dp floor + tp
        divisibility respected), and the engine rebuilds itself on it:
        new `_MeshPlan` (its `key` makes the decode-bundle LRU compile a
        FRESH bundle -- a bundle built for the dead mesh is never
        dispatched again), re-bucketed admission floors (the dp floor may
        shrink), params re-sharded onto the survivors
        (fault.elastic_remesh = param_pspecs on the new mesh), and a
        cleared graph census (every old graph targeted dead devices).
        `_recover` then rebuilds slot state under the NEW plan and
        replays in-flight requests bit-exactly -- no operator in the
        loop."""
        t0 = time.perf_counter()
        lost = self._health.kill(exc.n_lost)
        old = self._plan
        new_mesh = delastic.plan_degraded_mesh(
            old.mesh, self._health.healthy(), dp_axes=old.dp_axes,
            model_axis=old.model_axis, n_slots=self.n_slots, cfg=self.cfg)
        with dctx.mesh_scope(new_mesh, old.dp_axes, old.model_axis):
            self._plan = _mesh_plan(self.cfg, self._spec, self._init_kwargs,
                                    self.params)
        dp = self._plan.dp_size
        scheduler.validate_slot_sharding(self.n_slots, dp)
        self.min_batch_bucket = min(max(self._user_min_batch, dp),
                                    self.n_slots)
        self._adm_floor = min(dp, self.n_slots)
        self.batch_buckets = scheduler.bucket_set(self.min_batch_bucket,
                                                  self.n_slots)
        self._bundle = _engine_bundle(self.cfg, self.silvia_passes,
                                      self._lowerings, self._plan)
        self.params = dfault.elastic_remesh(self.params, new_mesh, self.cfg)
        if self._sd is not None:
            dcfg = self._sd.draft_cfg
            with dctx.mesh_scope(new_mesh, old.dp_axes, old.model_axis):
                self._draft_plan = _mesh_plan(dcfg, self._draft_spec, {},
                                              self._draft_params)
            self._draft_bundle = _engine_bundle(dcfg, self.silvia_passes,
                                                self._lowerings,
                                                self._draft_plan)
            self._sfns = _spec_fns(self.cfg, self.silvia_passes,
                                   self._lowerings, self._spec, self._plan)
            self._dfns = _spec_fns(dcfg, self.silvia_passes,
                                   self._lowerings, self._draft_spec,
                                   self._draft_plan)
            self._draft_params = dfault.elastic_remesh(
                self._draft_params, new_mesh, dcfg)
        self._graphs = set()
        self._robust["degraded"] += 1
        if self._prefix is not None:
            # pooled pages are host-resident and mesh-free: nothing to
            # invalidate, they re-shard through the NEW plan's
            # PartitionSpecs on the next write-back (_reshard_state);
            # the pool records the new fingerprint for observability
            self._prefix.note_remesh(self._plan.key)
        self._reshard_s += time.perf_counter() - t0
        del lost  # recorded in self._health.dead_ids (cache_info)

    def _recover(self, exc: Exception, now: float) -> None:
        """Requeue every in-flight (and mid-admission) request with its
        already-emitted tokens, then rebuild the slot state from scratch.
        The rebuilt state is NEVER derived from the old buffers: a failed
        dispatch may already have consumed its donated cache argument.
        Requeued requests re-enter through normal admission and REPLAY
        their recorded tokens before generating new ones, so surviving
        streams stay bit-identical to a fault-free run.  Device-loss
        faults additionally re-plan the mesh FIRST (`_degrade`), so the
        rebuilt state and the replay both land on the degraded mesh."""
        key = "faults_injected" if isinstance(exc, SimulatedFailure) \
            else "errors"
        self._robust[key] += 1
        if key == "errors" and self._first_error is None:
            self._first_error = f"{type(exc).__name__}: {exc}"
        self._robust["recoveries"] += 1
        if isinstance(exc, delastic.DeviceLoss) and self._plan is not None:
            self._degrade_at.append(now)
            self._degrade(exc)
        victims = [r for r in self._slot_req if r is not None]
        seen = {id(r) for r in victims}
        victims += [r for r in self._admitting
                    if id(r) not in seen and r.outcome is None]
        self._admitting = []
        for r in victims:
            r.retries += 1
            if r.retries > self._res.max_recoveries:
                self._finish(r, now, res.FAILED,
                             f"recovery budget "
                             f"({self._res.max_recoveries}) exhausted; "
                             f"last error: {exc}")
            else:
                self._queue.submit(r)
        self._cache = self._spec.init_state(self.n_slots,
                                            self.max_cache_len)
        if self._plan is not None:
            self._cache = jax.device_put(
                self._cache, dshard.to_shardings(self._plan.state_specs(),
                                                 self._plan.mesh))
        if self._sd is not None:
            self._draft_cache = self._draft_spec.init_state(
                self.n_slots, self.max_cache_len)
            if self._draft_plan is not None:
                self._draft_cache = jax.device_put(
                    self._draft_cache,
                    dshard.to_shardings(self._draft_plan.state_specs(),
                                        self._draft_plan.mesh))
        self._samp = sampling.host_page(self.n_slots)
        self._tok[:] = 0
        self._pos[:] = 0
        self._active[:] = False
        self._remaining[:] = 0
        self._slot_req = [None] * self.n_slots
        self._replay = [[] for _ in range(self.n_slots)]
        self._score = [[] for _ in range(self.n_slots)]
        if self._prefix is not None:
            for pk in self._slot_pins:
                if pk:
                    self._prefix.release(pk)
        self._slot_pins = [()] * self.n_slots

    # -- driver -------------------------------------------------------------

    def step(self, clock: Optional[scheduler.Clock] = None) -> bool:
        """Admit what has arrived, then run one decode segment.  Returns
        False when there was nothing to do (caller should wait for the next
        arrival).  Dispatch failures -- injected or real -- never escape:
        `_recover` requeues the in-flight work and subsequent steps replay
        it bit-exactly.  Equivalent to step_begin + an immediate
        step_finish (same dispatch order, same bits)."""
        clock = clock or scheduler.Clock()
        try:
            pending, progressed = self._step_begin_inner(clock)
            if pending is None:
                return progressed
            self._finish_segment(pending, clock)
            return True
        except Exception as e:  # noqa: BLE001 -- the serve loop survives
            self._recover(e, clock.now())
            return True

    def step_begin(self, clock: Optional[scheduler.Clock] = None):
        """First half of step(): expire/admit/replay, then DISPATCH one
        decode segment WITHOUT syncing on it.  Returns (pending,
        progressed); pending is None when no segment ran.  While the
        segment is in flight, the host may submit(), cancel(), publish
        already-harvested tokens and run admission_plan() -- the
        double-buffered serve pipeline (launch/frontend.py) -- then MUST
        call step_finish(pending).  Failures surfacing at dispatch
        recover here (returning (None, True)); failures surfacing at the
        blocking sync recover in step_finish."""
        clock = clock or scheduler.Clock()
        try:
            return self._step_begin_inner(clock)
        except Exception as e:  # noqa: BLE001
            self._recover(e, clock.now())
            return None, True

    def step_finish(self, pending,
                    clock: Optional[scheduler.Clock] = None) -> bool:
        """Second half of step(): block on the dispatched segment and
        harvest its tokens."""
        clock = clock or scheduler.Clock()
        try:
            self._finish_segment(pending, clock)
        except Exception as e:  # noqa: BLE001
            self._recover(e, clock.now())
        return True

    def _step_begin_inner(self, clock: scheduler.Clock,
                          resume_only: bool = False):
        now = clock.now()
        expired = self._expire(now)
        admitted = self._admit(now, clock, resume_only=resume_only)
        self._drain_replay(clock)
        if not self._active.any():
            return None, bool(admitted or expired)
        if self._sd is not None:
            # speculative rounds are synchronous (draft -> verify ->
            # rollback -> harvest); there is no pending segment to
            # double-buffer, the round IS the step
            self._spec_round(clock)
            return None, True
        return self._begin_segment(), True

    def _step_inner(self, clock: scheduler.Clock,
                    resume_only: bool = False) -> bool:
        pending, progressed = self._step_begin_inner(clock, resume_only)
        if pending is None:
            return progressed
        self._finish_segment(pending, clock)
        return True

    def cancel(self, rid: int, now: float = 0.0,
               reason: Optional[str] = None) -> bool:
        """Cancel a request by rid (stream disconnects, client aborts).
        Queued: removed before it ever dispatches.  In flight: the slot
        is evicted mid-stream and the request finishes CANCELLED with the
        tokens (or logprobs) harvested so far -- per-slot state isolation
        means the surviving batch mates are not perturbed by even one ULP
        (module docstring).  Returns False when the rid is not live
        (unknown, or already finished)."""
        req = self._queue.remove(rid)
        if req is not None:
            self._robust["cancelled_queued"] += 1
            self._finish(req, now, res.CANCELLED,
                         reason or "cancelled while queued")
            return True
        for slot in range(self.n_slots):
            req = self._slot_req[slot]
            if req is not None and req.rid == rid:
                self._robust["cancelled_inflight"] += 1
                self._finish(req, now, res.CANCELLED,
                             reason or "cancelled in flight")
                self._evict(slot)
                return True
        return False

    def drain(self, clock: Optional[scheduler.Clock] = None) -> None:
        """Finish all in-flight work WITHOUT admitting fresh requests
        (recovering requests -- requeued by a fault mid-drain with
        emitted tokens or a retry count -- are still re-admitted so their
        streams complete).  Fresh queued requests stay queued; pair with
        snapshot()/restore() for rolling restarts."""
        clock = clock or scheduler.Clock()
        self._robust["drains"] += 1
        while True:
            try:
                self._step_inner(clock, resume_only=True)
            except Exception as e:  # noqa: BLE001
                self._recover(e, clock.now())
                continue
            if not self._active.any() and not any(
                    r.tokens or r.retries > 0
                    for r in self._queue.pending()):
                return

    def snapshot(self, ckpt_dir: str, step: int = 0) -> str:
        """Persist queue + per-slot request state atomically through
        checkpoint/ckpt.py (launch/resilience.py encoding).  In-flight
        requests are stored WITH their emitted tokens and resume on
        restore() through the bit-exact recovery/replay path, so device
        state never needs serializing.  The snapshot is stamped with the
        CURRENT mesh topology (observability only): because request state
        is mesh-free, a snapshot taken on mesh A restores onto mesh B --
        including a single device -- with bit-identical tokens
        (tests/test_elastic.py)."""
        reqs = [r for r in self._slot_req if r is not None] \
            + list(self._queue.pending())
        self._robust["snapshots"] += 1
        extra = None
        if self._plan is not None:
            p = self._plan
            extra = {"mesh": {
                "shape": {n: p.mesh.shape[n] for n in p.mesh.axis_names},
                "dp_axes": list(p.dp_axes), "model_axis": p.model_axis,
                "dead_devices": list(self._health.dead_ids),
            }}
        return res.snapshot_requests(ckpt_dir, step, reqs, extra=extra)

    def restore(self, ckpt_dir: str, step: Optional[int] = None) -> int:
        """Load a snapshot into this (fresh or drained) engine's queue;
        returns the number of requests restored."""
        reqs = res.restore_requests(ckpt_dir, step=step)
        for r in reqs:
            if r.rid in self._rids:
                raise ValueError(
                    f"restore: rid {r.rid} is already tracked by this "
                    f"engine (restore targets a fresh or drained engine)")
            self._rids.add(r.rid)
            self._queue.submit(r)
        self._robust["restores"] += 1
        return len(reqs)

    def results(self) -> Dict[int, res.RequestResult]:
        """Structured terminal outcome per finished request, keyed by rid
        (resilience.RequestResult: outcome OK/SHED/EXPIRED/FAILED/
        CANCELLED, tokens, logprobs, embedding, error, retries)."""
        return dict(self._results)

    def result(self, rid: int) -> Optional[res.RequestResult]:
        """The structured result of one request, or None while it is
        still queued/in flight."""
        return self._results.get(rid)

    def next_arrival(self, now: float) -> Optional[float]:
        """Earliest future arrival_time in the queue (None when nothing
        is in transit) -- the front-end's idle-wait target."""
        return self._queue.next_arrival(now)

    def admission_plan(self) -> int:
        """Host-side admission planning that is safe to run while a
        dispatched segment is in flight (between step_begin and
        step_finish): precompute and memoize the prefix-cache content
        digests of queued requests, so the NEXT admission starts with its
        sha256 work already done.  Pure host bookkeeping -- no device
        dispatch, no admission decision, no LRU mutation -- so running it
        mid-segment cannot perturb a single bit of the served streams.
        Returns the number of requests whose digests were warmed."""
        if self._prefix is None:
            return 0
        return sum(1 for r in self._queue.pending()
                   if self._prefix.warm_digest(r))

    def run(self, requests: Sequence[scheduler.Request] = (),
            clock: Optional[scheduler.Clock] = None) -> Dict[int, np.ndarray]:
        """Serve until the queue drains; returns {rid: generated tokens}."""
        for r in requests:
            self.submit(r)
        clock = clock or scheduler.Clock()
        while True:
            if not self.step(clock):
                nxt = self._queue.next_arrival(clock.now())
                if nxt is not None:
                    clock.wait_until(nxt)
                    continue
                if not len(self._queue) and not self._active.any():
                    break
        return {r.rid: np.asarray(r.tokens, np.int32) for r in self.finished}

    # -- observability ------------------------------------------------------

    @property
    def prompt_buckets(self) -> tuple:
        return scheduler.bucket_set(self.min_prompt_bucket,
                                    self.max_cache_len)

    @property
    def admission_batch_buckets(self) -> tuple:
        return scheduler.bucket_set(self._adm_floor, self.n_slots)

    def graph_bound(self) -> int:
        """Upper bound on distinct compiled graphs: the segment bucket grid
        (batch buckets only for constant-size state) plus one prefill (or
        chunk) graph per (admission batch bucket, prompt bucket[, enc
        bucket]) -- what `warmup()` walks -- plus the same-size embed grid
        and the single-token chunk grid that BOTH recovery replay and the
        score method walk (score traffic can arrive on any engine, so the
        chunk grid is always in the bound)."""
        enc = max(1, len(self.enc_buckets))
        seg = len(self.batch_buckets) * max(1, len(self.len_buckets))
        pre = len(self.admission_batch_buckets) \
            * len(self.prompt_buckets) * enc
        bound = seg + pre + seg + pre
        if self._sd is not None:
            # draft/verify/rollback round grids (segments themselves
            # never dispatch on a spec engine, but their term stays in
            # the base bound), plus the draft prefill and draft replay
            # chunk grids
            bound += 3 * seg + pre + seg
        return bound

    def _warmup_prefill_inputs(self, bb: int, sb: int,
                               eb: Optional[int] = None):
        prompts = jnp.zeros((bb, sb), jnp.int32)
        if self.cfg.family != "encdec":
            return prompts
        eb = eb or self.enc_len
        audio = jnp.zeros((bb, eb, self.cfg.d_model),
                          jnp.dtype(self.cfg.dtype))
        return (audio, prompts, jnp.full((bb,), eb, jnp.int32))

    def warmup(self, prompt_lens: Optional[Sequence[int]] = None,
               methods: Sequence[str] = ("generate",)) -> int:
        """Pre-compile the (batch bucket x length bucket) segment grid on
        throwaway state, plus -- when the expected prompt-length mix is
        known -- the prefill graphs it maps to; returns the number of
        graphs compiled.  `methods` names the servable methods the
        traffic will use: "score" additionally warms the single-token
        chunk grid its teacher-forcing drains through, "embed" the pooled
        embedding graphs (launch/methods.py) -- without these a
        multi-method front-end pays their compiles mid-traffic."""
        n = 0
        state0 = self._spec.init_state(self.n_slots, self.max_cache_len)
        if self._plan is not None:
            state0 = jax.device_put(
                state0, dshard.to_shardings(self._plan.state_specs(),
                                            self._plan.mesh))
        if self._sd is None:
            for bb in self.batch_buckets:
                for t_b in (self.len_buckets or (None,)):
                    key = ("segment", bb, t_b, self.segment_len)
                    if key in self._graphs:
                        continue
                    # feed the segment the same state the serve loop
                    # will: the live slot state (plan-sharded on a mesh)
                    # for the "fast" full combo, a slice_live view
                    # otherwise -- compiling on a fresh unsharded
                    # init_state would leave the sharded variant to
                    # lazy-compile mid-traffic
                    fast = (bb == self.n_slots
                            and t_b in (None, self.max_cache_len))
                    cache = state0 if fast else \
                        self._spec.slice_live(state0, bb, t_b)
                    out = self._bundle.segment(
                        self.params, jnp.zeros((bb, 1), jnp.int32), cache,
                        jnp.zeros((bb,), jnp.int32),
                        jnp.zeros((bb,), bool),
                        sampling.null_operand(bb), self.segment_len)
                    jax.block_until_ready(out[0])
                    self._graphs.add(key)
                    n += 1
                    # also pre-compile the eager merge wrapper a
                    # non-"fast" segment step runs on the FULL slot
                    # state, with the segment's own output sub-state as
                    # the merge source -- exactly the operands the serve
                    # loop hands it
                    if not fast:
                        state0 = self._spec.merge_live(state0, out[2],
                                                       bb, t_b)
        else:
            # a spec-decode engine never dispatches plain segments: warm
            # the draft/verify(/rollback) round grid instead, on the same
            # state shapes _spec_round slices
            k = self._sd.k
            dstate0 = self._draft_spec.init_state(self.n_slots,
                                                  self.max_cache_len)
            if self._draft_plan is not None:
                dstate0 = jax.device_put(
                    dstate0,
                    dshard.to_shardings(self._draft_plan.state_specs(),
                                        self._draft_plan.mesh))
            for bb in self.batch_buckets:
                for t_b in (self.len_buckets or (None,)):
                    key = ("verify", bb, t_b, k + 1)
                    if key in self._graphs:
                        continue
                    fast = (bb == self.n_slots
                            and t_b in (None, self.max_cache_len))
                    d_in = dstate0 if fast else \
                        self._draft_spec.slice_live(dstate0, bb, t_b)
                    c_in = state0 if fast else \
                        self._spec.slice_live(state0, bb, t_b)
                    samp = sampling.null_operand(bb)
                    zt = jnp.zeros((bb, 1), jnp.int32)
                    zp = jnp.zeros((bb,), jnp.int32)
                    za = jnp.zeros((bb,), bool)
                    d_seq, d_cache, d_snaps = self._dfns.draft(
                        self._draft_params, zt, d_in, zp, za, samp, k + 1)
                    xs = jnp.concatenate([zt[None], d_seq[:k, :, None]],
                                         axis=0)
                    out = self._sfns.verify(self.params, c_in, zp, za,
                                            samp, xs)
                    if self._draft_const:
                        d_cache = self._dfns.rollback(d_cache, d_snaps,
                                                      out[1])
                        self._graphs.add(("rollback", bb, t_b, k + 1))
                    jax.block_until_ready(out[0])
                    self._graphs.add(("draft", bb, t_b, k + 1))
                    self._graphs.add(key)
                    n += 2
                    if not fast:
                        state0 = self._spec.merge_live(state0, out[2],
                                                       bb, t_b)
                        dstate0 = self._draft_spec.merge_live(
                            dstate0, d_cache, bb, t_b)
        if self._chaos is not None or "score" in methods:
            # a chaos-armed engine WILL recover, and recovery replays
            # through single-token chunk dispatches: pre-compile that grid
            # too, so the census stays warm-bounded under injected faults
            # (tier1-chaos runs the warmup-census tests unchanged).
            # Scoring teacher-forces completions through the SAME grid.
            for bb in self.batch_buckets:
                for t_b in (self.len_buckets or (None,)):
                    key = ("chunk", bb, 1, t_b)
                    if key in self._graphs:
                        continue
                    cache = self._spec.init_state(
                        bb, t_b or self.max_cache_len)
                    out = self._bundle.chunk_step(
                        self.params, jnp.zeros((bb, 1), jnp.int32), cache,
                        jnp.zeros((bb,), jnp.int32),
                        jnp.zeros((bb,), bool))
                    jax.block_until_ready(out[0])
                    self._graphs.add(key)
                    n += 1
                    if self._sd is not None:
                        # replay advances the draft through the same
                        # single-token grid
                        dcache = self._draft_spec.init_state(
                            bb, t_b or self.max_cache_len)
                        dout = self._draft_bundle.chunk_step(
                            self._draft_params,
                            jnp.zeros((bb, 1), jnp.int32), dcache,
                            jnp.zeros((bb,), jnp.int32),
                            jnp.zeros((bb,), bool))
                        jax.block_until_ready(dout[0])
                        self._graphs.add(("dchunk", bb, 1, t_b))
                        n += 1
        if prompt_lens is None:
            return n
        sbs = sorted({scheduler.bucket_pow2(pl,
                                            minimum=self.min_prompt_bucket,
                                            maximum=self.max_cache_len)
                      for pl in prompt_lens})
        # encdec admission groups ragged features by enc bucket, and the
        # compile cache keys on the audio operand shape: warm every
        # bucket or ragged traffic pays the smaller ones mid-stream
        ebs = self.enc_buckets or (None,)
        for bb in self.admission_batch_buckets:
            for sb in sbs:
                for eb in ebs:
                    t_pre = self._prefill_bucket(sb)
                    lens = jnp.ones((bb,), jnp.int32)
                    if self.prefill_chunk is None:
                        key = ("prefill", bb, sb, t_pre) \
                            + (() if eb is None else (eb,))
                        if key in self._graphs:
                            continue
                        out = self._bundle.prefill(
                            self.params,
                            self._warmup_prefill_inputs(bb, sb, eb),
                            lens - 1, t_pre, self.enc_len)
                    else:
                        key = ("chunk", bb, min(self.prefill_chunk, sb),
                               t_pre)
                        if key in self._graphs:
                            continue
                        out = self._chunked_prefill(
                            np.zeros((bb, sb), np.int32),
                            np.asarray(lens), t_pre)
                    jax.block_until_ready(out[0])
                    self._graphs.add(key)
                    n += 1
                    if self._sd is not None:
                        dkey = ("dprefill", bb, sb, t_pre)
                        if dkey not in self._graphs:
                            dout = self._draft_bundle.prefill(
                                self._draft_params,
                                self._warmup_prefill_inputs(bb, sb, eb),
                                lens - 1, t_pre, None)
                            jax.block_until_ready(dout[0])
                            self._graphs.add(dkey)
                            n += 1
        if "embed" in methods:
            for bb in self.admission_batch_buckets:
                for sb in sbs:
                    for eb in ebs:
                        key = ("embed", bb, sb) \
                            + (() if eb is None else (eb,))
                        if key in self._graphs:
                            continue
                        lens = jnp.ones((bb,), jnp.int32)
                        out = self._bundle.embed(
                            self.params,
                            self._warmup_prefill_inputs(bb, sb, eb),
                            lens - 1)
                        jax.block_until_ready(out[0])
                        self._graphs.add(key)
                        n += 1
        if self._prefix is not None:
            # pre-compile the pool's page ops.  The dynamic_slice /
            # dynamic_update_slice programs are keyed by the FULL operand
            # shape, not just the page size, so the warm set must cover
            # every state shape admission actually touches: the
            # (bb, t_pre) local prefill states (chunked path + full-path
            # extraction from prefill rows) and the engine's own
            # (n_slots, max_cache_len) slot state (full-path terminal
            # writes).  Sizes are the advertised prompt lengths plus every
            # whole-chunk span up to the longest.
            sizes = {int(pl) for pl in prompt_lens}
            if self.prefill_chunk is not None:
                cc = self.prefill_chunk
                sizes |= {k * cc for k in range(1, max(sizes) // cc + 1)}
            big = self._spec.init_state(self.n_slots, self.max_cache_len)
            if self._plan is not None:
                # the live slot state is sharded: warm the sharded
                # variant of the programs, not the host one
                big = jax.device_put(
                    big, dshard.to_shardings(self._plan.state_specs(),
                                             self._plan.mesh))
            for s in sorted(sizes):
                pages = self._spec.extract_row_pages(big, 0, 0, s)
                big = self._spec.write_row_pages(big, 0, 0, pages)
            for bb in self.admission_batch_buckets:
                for t in sorted({self._prefill_bucket(sb) for sb in sbs}):
                    local = self._spec.init_state(bb, t)
                    for s in sorted(x for x in sizes if x <= t):
                        pages = self._spec.extract_row_pages(
                            local, 0, 0, s)
                        local = self._spec.write_row_pages(
                            local, 0, 0, pages)
                    if (self._plan is not None
                            and self.prefill_chunk is not None):
                        # what admission actually scatters is the CHUNK
                        # DISPATCH's output state, whose leaves carry the
                        # shard_map out-shardings -- run one chunk on the
                        # written state (an already-warmed graph key) so
                        # the admit below compiles on those shardings
                        cands = [min(self.prefill_chunk, sb) for sb in sbs
                                 if self._prefill_bucket(sb) == t]
                        if cands:
                            c = max(cands)
                            _, local = self._bundle.chunk_step(
                                self.params,
                                jnp.zeros((bb, c), jnp.int32), local,
                                jnp.zeros((bb,), jnp.int32),
                                jnp.zeros((bb,), bool))
                    # admission also scatters the local rows into the
                    # slot state with one eager program per admitted
                    # GROUP SIZE (the slots index array is [g]):
                    # pre-compile every arity so neither the warm nor
                    # the cold serving path pays it mid-run
                    for g in range(1, min(bb, self.n_slots) + 1):
                        big = self._spec.admit(
                            big, local,
                            np.arange(g, dtype=np.int32), g, t_pre=t)
            jax.block_until_ready(jax.tree_util.tree_leaves(big))
        return n

    def cache_info(self) -> dict:
        """Compiled-graph census: engine shape keys (bounded by the bucket
        sets), the active kernel lowering per packed op (the registry
        resolution every compiled graph in this census was traced under),
        the serve-module decode-bundle LRU, and -- with SILVIA passes on --
        the pass pipeline's own trace-cache counters."""
        info = {
            "family": self.cfg.family,
            "has_length_axis": self._spec.has_length_axis,
            "decode_state_writes": dict(self._state_writes),
            "graphs": len(self._graphs),
            "graph_bound": self.graph_bound(),
            "graph_keys": sorted(self._graphs,
                                 key=lambda k: tuple(str(x) for x in k)),
            "batch_buckets": list(self.batch_buckets),
            "len_buckets": list(self.len_buckets),
            "enc_buckets": list(self.enc_buckets),
            "compactions": self.compactions,
            "occupancy": dict(self._occupancy),
            "sampler_path": dict(self._sampler_path),
            "methods": {"admits": dict(self._method_admits)},
            "lowerings": dict(self._lowerings),
            "decode_bundle_lru": serve.decode_cache_info(),
            "robustness": dict(self._robust),
            "first_error": self._first_error,
            "dispatch_sites": dict(self._site_counts),
            "admission": {
                "token_budget": self._admit_budget,
                "deferrals": self._deferrals,
            },
            "resilience": {
                "max_queue": self._res.max_queue,
                "shed_policy": self._res.shed_policy,
                "default_ttl_s": self._res.default_ttl_s,
                "max_recoveries": self._res.max_recoveries,
                "chaos": None if self._chaos is None else {
                    "sites": list(self._chaos.fail_at_sites),
                    "rate": self._chaos.rate,
                    "seed": self._chaos.seed,
                    "max_failures": self._chaos.max_failures,
                    "fired": sorted(self._chaos.failed),
                },
            },
        }
        if self._prefix is not None:
            info["prefix_cache"] = self._prefix.info()
        if self._sd is not None:
            s = dict(self._spec_stats)
            s["k"] = self._sd.k
            s["draft"] = getattr(self._sd.draft_cfg, "name",
                                 str(self._sd.draft_cfg))
            s["acceptance_rate"] = (s["accepted"] / s["drafted"]) \
                if s["drafted"] else 0.0
            s["tokens_per_dispatch"] = (
                s["emitted"] / s["target_dispatches"]) \
                if s["target_dispatches"] else 0.0
            info["spec_decode"] = s
        chaos = info["resilience"]["chaos"]
        if chaos is not None and isinstance(self._chaos,
                                            delastic.DeviceLossInjector):
            chaos["lose_at_sites"] = [list(x)
                                      for x in self._chaos.lose_at_sites]
            chaos["lose_rate"] = self._chaos.lose_rate
            chaos["lost_sites"] = dict(self._chaos.lost_sites)
        if self._plan is not None:
            p = self._plan
            info["mesh"] = {
                "shape": {n: p.mesh.shape[n] for n in p.mesh.axis_names},
                "dp_axes": list(p.dp_axes),
                "model_axis": p.model_axis,
                "dp_size": p.dp_size,
                "tp_size": p.tp.size,
                "tp_attn": p.tp.attn,
                "tp_ssm": p.tp.ssm,
                "n_devices": int(p.mesh.devices.size),
                "dead_devices": list(self._health.dead_ids),
                "degraded": self._robust["degraded"],
                "reshard_s": self._reshard_s,
                "degrade_at": list(self._degrade_at),
            }
        if hasattr(self._bundle.decode_fn, "cache_info"):
            info["silvia"] = self._bundle.decode_fn.cache_info()
        return info

    @property
    def n_active(self) -> int:
        return int(np.sum(self._active))

    @property
    def n_queued(self) -> int:
        return len(self._queue)
