"""Per-request sampling policies for the serve engine and static path.

SILVIA's packing transformation must leave every covered op's result
bit-identical; the sampling layer carries that obligation into stochastic
decoding by making every sampled token a PURE FUNCTION of
(seed, rid, token index, logits row):

* the per-token RNG key is counter-based -- ``fold_in(fold_in(
  PRNGKey(seed), rid), t)`` with ``t`` the generated-token index -- so no
  sampler state ever needs checkpointing: chaos recovery replay and
  prefix-cache warm admissions recompute the exact key from values they
  already carry;
* temperature / top-k / top-p truncation and the Gumbel-max draw are all
  per-row ops with no cross-row reduction, so a row samples the same
  token bits regardless of batch composition, mesh sharding, or whether
  it is evaluated in-scan ([B,V]) or host-side on a [1,V] slice
  (`sample_host`, the replay-verification path);
* greedy rows (temperature <= 0, the default) take the literal
  ``jnp.argmax`` path through a ``jnp.where`` select, keeping greedy
  streams bit-identical to the pre-sampling engine;
* a batch in which no row samples skips the truncation and the draw
  altogether (a ``lax.cond`` on the temperatures), so an all-greedy
  decode step runs no full-vocabulary sort, softmax or Gumbel draw.

The per-slot sampling state -- base key, temperature, top-k, top-p,
prompt length -- is registered through `models/slot_state.py` as its own
constant-size slot page family (``"sampling"``), so its probed
`SlotStateSpec` gives the engine the same admit/permute/slice operations
the model caches use and the page survives admit/evict/compaction/replay
by construction (tests/test_sampling.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.scheduler import GREEDY, SamplingParams
from repro.models import slot_state

# operand order of the page leaves (one flat tuple everywhere: host page,
# device operand, shard_map specs)
PAGE_LEAVES = ("key", "temp", "top_k", "top_p", "plen")


@dataclasses.dataclass(frozen=True)
class _SamplingPageCfg:
    """Minimal config handle so `slot_state.spec_for` can probe the
    sampling page like any model family's cache."""
    family: str = "sampling"


def _init_page(cfg, n_slots: int, max_cache_len: int):
    del cfg, max_cache_len            # constant-size: no length axis
    return (jnp.zeros((n_slots, 2), jnp.uint32),    # fold_in(seed, rid)
            jnp.zeros((n_slots,), jnp.float32),     # temperature
            jnp.zeros((n_slots,), jnp.int32),       # top_k (0 = off)
            jnp.ones((n_slots,), jnp.float32),      # top_p (1 = off)
            jnp.zeros((n_slots,), jnp.int32))       # prompt_len


slot_state.register("sampling", _init_page)


def page_spec() -> slot_state.SlotStateSpec:
    """The probed SlotStateSpec of the sampling page (all leaves slot-axis
    0, no length axis -- a constant-size page)."""
    return slot_state.spec_for(_SamplingPageCfg())


# ---------------------------------------------------------------------------
# host-side page bookkeeping
# ---------------------------------------------------------------------------

def params_of(req) -> SamplingParams:
    return req.sampling if req.sampling is not None else GREEDY


def is_greedy(req) -> bool:
    """Whether this request's stream is the argmax stream (score/embed
    never sample)."""
    return req.method != "generate" or params_of(req).greedy


@functools.lru_cache(maxsize=8192)
def base_key(seed: int, rid: int) -> tuple:
    """fold_in(PRNGKey(seed), rid) as a hashable uint32 pair."""
    k = jax.random.fold_in(jax.random.PRNGKey(seed), rid)
    return tuple(int(x) for x in np.asarray(k, np.uint32))


def host_page(n_slots: int) -> list:
    """Fresh host-resident sampling page (numpy leaves, PAGE_LEAVES
    order), built by the registered slot-state init so layout cannot
    drift from the probed spec."""
    return [np.array(leaf)   # np.array copies: jax arrays are read-only
            for leaf in page_spec().init_state(n_slots, 1)]


def write_row(page: list, slot: int, req) -> None:
    """Admit one request's policy into its slot row."""
    p = params_of(req)
    page[0][slot] = np.asarray(base_key(p.seed, req.rid), np.uint32)
    page[1][slot] = np.float32(p.temperature)
    page[2][slot] = np.int32(p.top_k)
    page[3][slot] = np.float32(p.top_p)
    page[4][slot] = np.int32(req.prompt_len)


def clear_row(page: list, slot: int) -> None:
    """Evict: reset the row to the greedy defaults."""
    page[0][slot] = 0
    page[1][slot] = 0.0
    page[2][slot] = 0
    page[3][slot] = 1.0
    page[4][slot] = 0


def permute(page: list, perm) -> list:
    """Slot compaction (the host mirror of SlotStateSpec.permute_slots)."""
    return [leaf[np.asarray(perm)] for leaf in page]


def operand(page: list, bb: int) -> tuple:
    """The [:bb] device operand tuple a bucketed dispatch consumes."""
    return tuple(jnp.asarray(leaf[:bb]) for leaf in page)


def null_operand(bb: int) -> tuple:
    """All-greedy operand (warmup: graphs key on shapes, not values)."""
    return operand(host_page(bb), bb)


# ---------------------------------------------------------------------------
# the sampler
# ---------------------------------------------------------------------------

def sample(last, key, temp, top_k, top_p, t):
    """Next tokens [B] int32 from logits rows `last` [B,V].

    Greedy rows (temp <= 0) are the literal argmax of the raw logits --
    the same op as the pre-sampling engine, selected by `jnp.where`, so
    greedy bits cannot move.  Sampled rows divide by temperature in
    float32, mask everything outside the top-k/top-p truncation to -inf,
    and take the Gumbel-max argmax under the per-row key folded with the
    token index `t` [B].  Every op is per-row: a row's token is invariant
    to batch composition (the engine's packing invariant).

    The truncation and draw run only when some row samples: the branch
    is chosen on the device from `temp`, so one compiled program serves
    greedy, sampled and mixed batches.  Under a mesh `temp` is
    row-sharded and each shard chooses for its own rows; neither branch
    holds a collective."""
    return jax.lax.cond(jnp.any(temp > 0.0), _sampled, _greedy,
                        last, key, temp, top_k, top_p, t)


def _greedy(last, key, temp, top_k, top_p, t):
    del key, temp, top_k, top_p, t
    return jnp.argmax(last, axis=-1).astype(jnp.int32)


def _sampled(last, key, temp, top_k, top_p, t):
    v = last.shape[-1]
    arg = jnp.argmax(last, axis=-1).astype(jnp.int32)

    x = last.astype(jnp.float32) / jnp.maximum(temp, 1e-6)[:, None]
    srt = jnp.sort(x, axis=-1)[:, ::-1]
    # top-k threshold: the kth largest value (0 or oversize k = disabled)
    kk = jnp.where(top_k > 0, jnp.clip(top_k, 1, v), v)
    thr_k = jnp.take_along_axis(srt, (kk - 1)[:, None], axis=-1)
    # top-p nucleus: keep the smallest sorted prefix with mass >= top_p;
    # the EXCLUSIVE cumsum keeps at least the first entry
    probs = jax.nn.softmax(srt, axis=-1)
    excl = jnp.cumsum(probs, axis=-1) - probs
    kept = excl < top_p[:, None]
    thr_p = jnp.min(jnp.where(kept, srt, jnp.inf), axis=-1, keepdims=True)
    keep = (x >= thr_k) & (x >= thr_p)

    kt = jax.vmap(jax.random.fold_in)(key, t)
    gum = jax.vmap(
        lambda k: jax.random.gumbel(k, (v,), jnp.float32))(kt)
    smp = jnp.argmax(jnp.where(keep, x + gum, -jnp.inf),
                     axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0.0, smp, arg)


@jax.jit
def _sample_jit(last, key, temp, top_k, top_p, t):
    return sample(last, key, temp, top_k, top_p, t)


def sample_host(row, params: SamplingParams, rid: int, t: int) -> int:
    """Recompute ONE row's sampled token host-side -- the replay
    verification / sampled-tok0 path.  Runs the same jitted sampler on a
    [1,V] slice: `sample` has no cross-row reduction, so the result is
    bitwise the in-scan batch row's token."""
    row = jnp.asarray(np.asarray(row, np.float32))[None]
    out = _sample_jit(
        row, jnp.asarray(np.asarray(base_key(params.seed, rid),
                                    np.uint32))[None],
        jnp.full((1,), params.temperature, jnp.float32),
        jnp.full((1,), params.top_k, jnp.int32),
        jnp.full((1,), params.top_p, jnp.float32),
        jnp.full((1,), t, jnp.int32))
    return int(out[0])


def expected_token(req, row, t: int) -> int:
    """The token request `req` emits at generated-token index `t` from
    logits row `row` -- host argmax for greedy rows (comparison-based, no
    float accumulation, so it equals the in-scan argmax), `sample_host`
    otherwise.  This is the single verification oracle replay and
    admission share."""
    row = np.asarray(row, np.float32)
    if is_greedy(req):
        return int(np.argmax(row))
    return sample_host(row, params_of(req), req.rid, t)


def static_operand(reqs_or_params, prompt_len: int, rids=None) -> Optional[tuple]:
    """Batch sampling operand for the STATIC `serve.generate` path: one
    SamplingParams (or None) per row, rid defaulting to the row index.
    Returns None when every row is greedy -- the caller then keeps the
    untouched greedy fused loop."""
    ps = [p if isinstance(p, SamplingParams) else GREEDY
          for p in (reqs_or_params or [])]
    if all(p.greedy for p in ps):
        return None
    rids = list(rids) if rids is not None else list(range(len(ps)))
    key = np.asarray([base_key(p.seed, r) for p, r in zip(ps, rids)],
                     np.uint32)
    return (jnp.asarray(key),
            jnp.asarray([p.temperature for p in ps], jnp.float32),
            jnp.asarray([p.top_k for p in ps], jnp.int32),
            jnp.asarray([p.top_p for p in ps], jnp.float32),
            jnp.full((len(ps),), prompt_len, jnp.int32))
