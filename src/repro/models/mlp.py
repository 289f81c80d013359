"""Feed-forward layers: SwiGLU/GELU dense MLPs and top-k MoE.

MoE uses sort-based (megablocks-style) dispatch: token->expert assignments
are sorted by expert id, gathered into fixed-capacity expert batches
(capacity factor -> token dropping, standard practice), processed by an
expert-batched einsum whose expert dimension is sharded over the `model`
mesh axis (expert parallelism -- GSPMD inserts the all-to-all style
resharding between token-sharded and expert-sharded layouts), and
scatter-combined weighted by router probabilities.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common
from repro.quant.qtensor import QTensor, qmatmul
from repro.models.config import ModelConfig, MoEConfig


# ---------------------------------------------------------------------------
# dense MLPs
# ---------------------------------------------------------------------------

def init_mlp(rng, cfg: ModelConfig, d_ff=None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    r = common.split_rngs(rng, 3)
    if cfg.activation == "swiglu":
        return {"wi": common.dense_init(r[0], d, f, dt),
                "wg": common.dense_init(r[1], d, f, dt),
                "wo": common.dense_init(r[2], f, d, dt)}
    return {"wi": common.dense_init(r[0], d, f, dt),
            "bi": jnp.zeros((f,), dt),
            "wo": common.dense_init(r[2], f, d, dt),
            "bo": jnp.zeros((d,), dt)}


def mlp(p, x, cfg: ModelConfig):
    if cfg.activation == "swiglu":
        return qmatmul(jax.nn.silu(qmatmul(x, p["wg"])) * qmatmul(x, p["wi"]),
                       p["wo"])
    return qmatmul(jax.nn.gelu(qmatmul(x, p["wi"]) + p["bi"]), p["wo"]) + p["bo"]


# ---------------------------------------------------------------------------
# mixture of experts
# ---------------------------------------------------------------------------

def init_moe(rng, cfg: ModelConfig):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts
    dt = jnp.dtype(cfg.dtype)
    r = common.split_rngs(rng, 4)
    scale = 1.0 / jnp.sqrt(d)

    def stack(rng_, d_in, d_out, sc):
        return (jax.random.normal(rng_, (e, d_in, d_out), jnp.float32) * sc
                ).astype(dt)

    return {
        "router": common.dense_init(r[0], d, e, jnp.float32),
        "wi": stack(r[1], d, f, scale),
        "wg": stack(r[2], d, f, scale),
        "wo": stack(r[3], f, d, 1.0 / jnp.sqrt(f)),
    }


def _emm(xe, w):
    """Expert-batched matmul ([E,C,*] x [E,*,*]), QTensor-aware."""
    if isinstance(w, QTensor):
        return qmatmul(xe, w)
    return jnp.einsum("ecd,edf->ecf", xe, w)


def _dispatch_combine(xt, top_e, top_p, p, cfg, cap):
    """Sort-based dispatch over ONE token group.

    xt: [T, d]; top_e/top_p: [T, k].  Returns [T, d]."""
    m: MoEConfig = cfg.moe
    t, d = xt.shape
    e, k = m.n_experts, m.top_k
    flat_e = top_e.reshape(-1)                               # [T*k]
    flat_p = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)                              # stable
    se, sp, stok = flat_e[order], flat_p[order], flat_tok[order]
    same = jnp.cumsum(jnp.ones_like(se), axis=0) - 1
    grp_start = jnp.searchsorted(se, jnp.arange(e))          # [E]
    slot = same - grp_start[se]                              # rank in group
    keep = slot < cap
    dest = se * cap + jnp.where(keep, slot, 0)               # [T*k]
    buf = jnp.zeros((e * cap, d), xt.dtype)
    src = xt[stok] * keep[:, None].astype(xt.dtype)
    buf = buf.at[dest].add(src)                              # unique dests
    ein = buf.reshape(e, cap, d)
    # expert ffn (E sharded over `model` -> expert parallelism)
    h = jax.nn.silu(_emm(ein, p["wg"])) * _emm(ein, p["wi"])
    eout = _emm(h, p["wo"]).reshape(e * cap, d)
    contrib = eout[dest] * (sp * keep).astype(xt.dtype)[:, None]
    return jnp.zeros((t, d), xt.dtype).at[stok].add(contrib)


def moe(p, x, cfg: ModelConfig, per_token: bool = False):
    """x: [B, S, d] -> ([B, S, d], aux_loss scalar).

    per_token=True (serving: prefill/decode) routes every token dropless
    via a dense one-hot combine: all experts run on all tokens and each
    token keeps its top-k, so a token's output depends only on that token.
    Capacity-factor dropping is a training throughput device; batch-coupled
    dropping would make generations depend on which other requests share
    the batch, which breaks the serve engine's slot-packing exactness
    (engine output must be bit-identical to a solo run of the same
    request).  The E/k x compute overhead is the price of exactness at
    smoke scale; a production path would gather the k expert slices per
    token instead."""
    m: MoEConfig = cfg.moe
    if per_token:
        b, s, d = x.shape
        t = b * s
        xt = x.reshape(t, d)
        logits = (xt.astype(jnp.float32) @ p["router"])      # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, m.top_k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], m.n_experts,
                                     dtype=jnp.float32), axis=0)
        aux = m.n_experts * jnp.sum(me * ce)
        # gate[t, e] = routing weight iff e is one of t's top-k (distinct)
        gate = jnp.zeros((t, m.n_experts), xt.dtype)
        gate = gate.at[jnp.arange(t)[:, None], top_e].set(
            top_p.astype(xt.dtype))
        xe = jnp.broadcast_to(xt[None], (m.n_experts, t, d))
        h = jax.nn.silu(_emm(xe, p["wg"])) * _emm(xe, p["wi"])
        eout = _emm(h, p["wo"])                              # [E, T, d]
        yt = jnp.einsum("etd,te->td", eout, gate)
        return yt.reshape(b, s, d), aux
    if m.dispatch == "shard_map" and not isinstance(p["wi"], QTensor):
        from repro.distributed import context
        ctx = context.current()
        if ctx is not None:
            return moe_shard_map(p, x, cfg, *ctx)
    b, s, d = x.shape
    t = b * s
    e, k = m.n_experts, m.top_k
    xt = x.reshape(t, d)
    logits = (xt.astype(jnp.float32) @ p["router"])          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                   # [T, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)

    if m.dispatch == "grouped" and t >= m.dispatch_groups > 1 \
            and t % m.dispatch_groups == 0:
        # GShard-style: dispatch within fixed token groups so the argsort
        # and capacity bookkeeping stay LOCAL to a data shard; only the
        # expert exchange itself crosses devices (all-to-all)
        g = m.dispatch_groups
        tg = t // g
        cap = int(m.capacity_factor * k * tg / e) + 1
        yt = jax.vmap(
            lambda xg, eg, pg: _dispatch_combine(xg, eg, pg, p, cfg, cap)
        )(xt.reshape(g, tg, d), top_e.reshape(g, tg, k),
          top_p.reshape(g, tg, k))
        return yt.reshape(b, s, d), aux

    cap = int(m.capacity_factor * k * t / e) + 1
    yt = _dispatch_combine(xt, top_e, top_p, p, cfg, cap)
    return yt.reshape(b, s, d), aux


def moe_shard_map(p, x, cfg: ModelConfig, mesh, dp_axes, model_axis):
    """Explicitly-collective MoE (Megatron/GShard style) under shard_map.

    Why: under pure GSPMD the data-dependent scatter-adds of the dispatch
    partition as replicate+all-reduce of the FULL [E*cap, d] buffers --
    measured at ~13 TB/chip-step on arctic-480b train (EXPERIMENTS §Perf A).
    Inside shard_map every scatter is shard-local; the only collectives are

      * all_gather of the (FSDP-sharded) expert weights over the dp axes,
      * one psum over the model axis to combine expert outputs.

    Layout: tokens sharded over dp (replicated over model); experts
    block-assigned to model shards.  Capacity is per-dp-shard (same token
    dropping semantics as grouped dispatch with G = |dp|)."""
    from jax.sharding import PartitionSpec as P

    m: MoEConfig = cfg.moe
    b, s, d = x.shape
    e = m.n_experts
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    e_loc = e // mesh.shape[model_axis]
    assert e_loc * mesh.shape[model_axis] == e, (e, model_axis)

    def local_fn(wi, wg, wo, router, xl):
        # wi/wg: [E_loc, d/|dp|, F]; wo: [E_loc, F, d/|dp|] (FSDP-sharded)
        for ax in dp_axes:
            wi = jax.lax.all_gather(wi, ax, axis=1, tiled=True)
            wg = jax.lax.all_gather(wg, ax, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, ax, axis=2, tiled=True)
        bl, sl, _ = xl.shape
        tl = bl * sl
        xt = xl.reshape(tl, d)
        logits = xt.astype(jnp.float32) @ router            # [T_loc, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, m.top_k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32),
                      axis=0)
        aux = e * jnp.sum(me * ce)
        for ax in dp_axes:
            aux = jax.lax.pmean(aux, ax)

        cap = int(m.capacity_factor * m.top_k * tl / e) + 1
        # local sort-based dispatch (identical math to the global path)
        flat_e = top_e.reshape(-1)
        flat_p = top_p.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(tl), m.top_k)
        order = jnp.argsort(flat_e)
        se, sp, stok = flat_e[order], flat_p[order], flat_tok[order]
        same = jnp.cumsum(jnp.ones_like(se)) - 1
        grp_start = jnp.searchsorted(se, jnp.arange(e))
        slot = same - grp_start[se]
        keep = slot < cap
        dest = se * cap + jnp.where(keep, slot, 0)
        buf = jnp.zeros((e * cap, d), xl.dtype)
        buf = buf.at[dest].add(xt[stok] * keep[:, None].astype(xl.dtype))
        ein = buf.reshape(e, cap, d)
        # this model-shard computes only ITS experts
        j = jax.lax.axis_index(model_axis)
        ein_loc = jax.lax.dynamic_slice_in_dim(ein, j * e_loc, e_loc, 0)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ein_loc, wg)) * \
            jnp.einsum("ecd,edf->ecf", ein_loc, wi)
        eout_loc = jnp.einsum("ecf,efd->ecd", h, wo)         # [E_loc,cap,d]
        # pad back to the global expert axis, combine, then psum partials
        eout = jnp.zeros((e, cap, d), xl.dtype)
        eout = jax.lax.dynamic_update_slice_in_dim(
            eout, eout_loc.astype(xl.dtype), j * e_loc, 0)
        flat_out = eout.reshape(e * cap, d)[dest]
        contrib = flat_out * (sp * keep).astype(xl.dtype)[:, None]
        yt = jnp.zeros((tl, d), xl.dtype).at[stok].add(contrib)
        yt = jax.lax.psum(yt, model_axis)
        return yt.reshape(bl, sl, d), aux

    wi_spec = P(model_axis, dp, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(wi_spec, wi_spec, P(model_axis, None, dp), P(None, None),
                  P(dp, None, None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False)
    return fn(p["wi"], p["wg"], p["wo"], p["router"], x)
