"""Grouped-query attention with RoPE/M-RoPE, KV cache, and cross-attention.

Modes:
  full(x)                      -- causal self-attention over the sequence
                                  (training / prefill; optionally emits cache)
  decode(x_t, cache, pos)      -- one new token against a static-size cache
  cross(x, memory)             -- encoder-decoder cross attention (whisper)

The KV cache is a dict {k: [B, S_max, KV, D], v: ..., } with positions filled
up to `pos`; decode writes each row's new entries in place into the stacked
cache through the page handles lm.decode_step passes (lm.StackedPage).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import context as dctx
from repro.models import common
from repro.quant.qtensor import QTensor, qmatmul
from repro.kernels.ref import W4_GROUP
from repro.quant.quantize import slice_int4_cols
from repro.models.config import ModelConfig


def _attn_tp():
    """Active serve-time tensor-parallel context for attention (set inside
    the engine's shard_map body; see distributed/context.py).  When
    active, projections compute only this shard's heads and the head
    outputs are all_gathered before the merged wo matmul -- collectives
    are exact concats, never partial-sum reductions, so sharded decode
    stays bit-identical to the single-device path."""
    tp = dctx.tp_current()
    return tp if tp is not None and tp.attn else None


def _tp_slice_cols(w, j, width: int):
    """Columns [j*width, (j+1)*width) of a dense or QTensor weight
    [..., K, N] (w4a8 packs two logical columns per stored word)."""
    if isinstance(w, QTensor):
        if w.fmt == "w4a8":
            q = slice_int4_cols(w.q, j * width, width)
        else:
            q = jax.lax.dynamic_slice_in_dim(w.q, j * width, width,
                                             axis=w.q.ndim - 1)
        scale = jax.lax.dynamic_slice_in_dim(w.scale, j * width, width,
                                             axis=w.scale.ndim - 1)
        return QTensor(q, scale, w.fmt)
    return jax.lax.dynamic_slice_in_dim(w, j * width, width, axis=w.ndim - 1)


def check_w4_tp(params, cfg: ModelConfig, size: int) -> None:
    """Refuse head tensor parallelism over `size` model shards where it
    would cut a w4a8 q/k/v weight inside a packing group
    (kernels/ref.pack_w4): each shard's columns must be whole groups, so
    that its slice is one word range.  qwen1.5-0.5b fits 2 and 4 shards;
    yi-6b's k/v (4 heads x 128) fit 2, not 4."""
    widths = {"wq": cfg.q_dim // size, "wk": cfg.kv_dim // size,
              "wv": cfg.kv_dim // size}
    for path, w in jax.tree_util.tree_leaves_with_path(
            params, is_leaf=lambda x: isinstance(x, QTensor)):
        name = getattr(path[-1], "key", None)
        if (isinstance(w, QTensor) and w.fmt == "w4a8" and name in widths
                and widths[name] % W4_GROUP):
            raise ValueError(
                f"w4a8 {jax.tree_util.keystr(path)} over {size} model "
                f"shards gives {widths[name]}-column slices, not whole "
                f"{W4_GROUP}-column packing groups: use fewer model "
                f"shards or w8a8")


def _tp_gather_heads(out):
    """all_gather the per-shard head outputs along the feature axis before
    the merged output projection (tiled: shard-major concat == the
    original head order, since shards own contiguous head blocks)."""
    tp = _attn_tp()
    if tp is None:
        return out
    return jax.lax.all_gather(out, tp.axis, axis=out.ndim - 1, tiled=True)


def init_attn(rng, cfg: ModelConfig, cross: bool = False):
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    r = common.split_rngs(rng, 4)
    p = {
        "wq": common.dense_init(r[0], d, cfg.q_dim, dt),
        "wk": common.dense_init(r[1], d, cfg.kv_dim, dt),
        "wv": common.dense_init(r[2], d, cfg.kv_dim, dt),
        "wo": common.dense_init(r[3], cfg.q_dim, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dt)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dt)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dt)
    return p


def _project_q(p, x, cfg: ModelConfig):
    tp = _attn_tp()
    wq, bq = p["wq"], p.get("bq")
    h = cfg.n_heads
    if tp is not None:
        h = cfg.n_heads // tp.size
        j = jax.lax.axis_index(tp.axis)
        wq = _tp_slice_cols(wq, j, h * cfg.head_dim)
        if bq is not None:
            bq = jax.lax.dynamic_slice_in_dim(bq, j * h * cfg.head_dim,
                                              h * cfg.head_dim, axis=0)
    q = qmatmul(x, wq)
    if bq is not None:
        q = q + bq
    b, s, _ = q.shape
    return q.reshape(b, s, h, cfg.head_dim)


def _project_kv(p, x, cfg: ModelConfig):
    tp = _attn_tp()
    wk, wv = p["wk"], p["wv"]
    bk, bv = p.get("bk"), p.get("bv")
    kv = cfg.n_kv
    if tp is not None:
        kv = cfg.n_kv // tp.size
        j = jax.lax.axis_index(tp.axis)
        wk = _tp_slice_cols(wk, j, kv * cfg.head_dim)
        wv = _tp_slice_cols(wv, j, kv * cfg.head_dim)
        if bk is not None:
            sl = lambda b_: jax.lax.dynamic_slice_in_dim(
                b_, j * kv * cfg.head_dim, kv * cfg.head_dim, axis=0)
            bk, bv = sl(bk), sl(bv)
    k = qmatmul(x, wk)
    v = qmatmul(x, wv)
    if bk is not None:
        k, v = k + bk, v + bv
    b, s, _ = k.shape
    return (k.reshape(b, s, kv, cfg.head_dim),
            v.reshape(b, s, kv, cfg.head_dim))


def _gqa_scores(q, k, cfg: ModelConfig):
    """q: [B,S,H,D], k: [B,T,KV,D] -> scores [B,KV,G,S,T] (G = H//KV)."""
    b, s, h, d = q.shape
    kv = k.shape[2]     # shape-driven, not cfg.n_kv: under serve TP the
    g = h // kv         # projections carry only this shard's head block
    q = q.reshape(b, s, kv, g, d)
    return jnp.einsum("bskgd,btkd->bkgst", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(w, v, cfg: ModelConfig):
    """w: [B,KV,G,S,T], v: [B,T,KV,D] -> [B,S,H*D]."""
    b, kv, g, s, t = w.shape
    o = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return o.reshape(b, s, kv * g * o.shape[-1])


def _kv_quantize(t):
    """Per-position symmetric int8 quantization of a [B,S,KV,D] tensor:
    returns (int8 values, [B,S,KV] f32 scales)."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = amax / 127.0 + 1e-8
    q = jnp.round(t.astype(jnp.float32) / scale[..., None]
                  ).astype(jnp.int8)
    return q, scale


def _kv_dequant(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _attn_chunked(q, k, v, srcpos, cfg: ModelConfig, q_chunk: int):
    """Causal attention with the query dim scanned in chunks: only a
    [B, KV, G, q_chunk, T] score block is ever live (flash-attention memory
    behaviour expressed at the XLA level)."""
    b, s, h, d = q.shape
    nc = s // q_chunk
    scale = 1.0 / np.sqrt(cfg.head_dim)
    q_c = jnp.moveaxis(q.reshape(b, nc, q_chunk, h, d), 1, 0)
    p_c = jnp.moveaxis(srcpos.reshape(b, nc, q_chunk), 1, 0)

    def body(_, inp):
        qi, pi = inp
        scores = _gqa_scores(qi, k, cfg) * scale      # [B,KV,G,qc,T]
        mask = pi[:, None, None, :, None] >= srcpos[:, None, None, None, :]
        scores = jnp.where(mask, scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return None, _gqa_out(w, v, cfg)              # [B,qc,H*D]

    _, outs = jax.lax.scan(body, None, (q_c, p_c))
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h * d)


def attn_full(p, x, cfg: ModelConfig, positions=None, causal: bool = True,
              return_cache: bool = False, cache_len: Optional[int] = None,
              kv_lengths=None):
    """Self attention over the full sequence (train / prefill).

    kv_lengths: optional [B] int32 per-row count of REAL source positions
    (non-causal / encoder use): keys at positions >= kv_lengths[b] are
    masked out of row b's softmax.  Masked weights are exact float zeros,
    so a right-padded batch attends bit-identically to an unpadded one --
    the invariant that lets the serve engine bucket ragged encoder
    lengths (variable-length whisper features) without perturbing any
    real position by a single ULP."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q = _project_q(p, x, cfg)
    k, v = _project_kv(p, x, cfg)
    if not cfg.learned_pos:   # whisper-style models use absolute embeddings
        q = common.apply_rope(q, positions, cfg.rope_theta, cfg.m_rope_sections)
        k = common.apply_rope(k, positions, cfg.rope_theta, cfg.m_rope_sections)
    scale = 1.0 / np.sqrt(cfg.head_dim)
    srcpos = positions if positions.ndim == 2 else positions[0]
    if (cfg.attn_q_chunk and causal and s > cfg.attn_q_chunk
            and s % cfg.attn_q_chunk == 0):
        out = qmatmul(_tp_gather_heads(
            _attn_chunked(q, k, v, srcpos, cfg, cfg.attn_q_chunk)), p["wo"])
        if not return_cache:
            return out
        s_max = cache_len or s
        pad = s_max - s
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if cfg.serve_kv_dtype == "int8":
            kq, ks = _kv_quantize(kp)
            vq, vs = _kv_quantize(vp)
            return out, {"k": kq, "v": vq, "k_s": ks, "v_s": vs}
        return out, {"k": kp, "v": vp}
    scores = _gqa_scores(q, k, cfg) * scale
    if causal:
        mask = srcpos[:, None, None, :, None] >= srcpos[:, None, None, None, :]
        scores = jnp.where(mask, scores, -1e30)
    if kv_lengths is not None:
        valid = jnp.arange(s)[None, :] < kv_lengths[:, None]        # [B,T]
        scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = qmatmul(_tp_gather_heads(_gqa_out(w, v, cfg)), p["wo"])
    if not return_cache:
        return out
    s_max = cache_len or s
    pad = s_max - s
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if cfg.serve_kv_dtype == "int8":
        kq, ks = _kv_quantize(kp)
        vq, vs = _kv_quantize(vp)
        return out, {"k": kq, "v": vq, "k_s": ks, "v_s": vs}
    return out, {"k": kp, "v": vp}


def attn_decode(p, x_t, cache, pos, cfg: ModelConfig, active=None):
    """Decode C new tokens against the cache: x_t [B, C, d] (C=1 is the
    classic single-token step; C>1 is a chunked-prefill step); pos [B] int32
    position of the FIRST new token per row; active: optional [B] bool slot
    mask -- inactive rows leave their cache untouched.

    `cache` maps each page name (k, v; k_s, v_s under int8 KV) to a page
    handle (lm.StackedPage): `read()` gives the layer's page,
    `rows_at(pos, c)` each row's c entries from pos, and `put(rows, pos)`
    a handle with them written.  Only the new entries are written: an
    inactive row writes back the entries it already holds there.

    Returns (out [B,C,d], new_cache).  Token c of row b is written at cache
    position pos[b]+c and attends causally to positions <= pos[b]+c."""
    b, c = x_t.shape[:2]
    qpos = pos[:, None] + jnp.arange(c, dtype=pos.dtype)    # [B,C]
    if cfg.m_rope_sections is not None:
        posq = jnp.broadcast_to(qpos[None], (3, b, c))
    else:
        posq = qpos
    q = _project_q(p, x_t, cfg)
    k_t, v_t = _project_kv(p, x_t, cfg)
    if not cfg.learned_pos:
        q = common.apply_rope(q, posq, cfg.rope_theta, cfg.m_rope_sections)
        k_t = common.apply_rope(k_t, posq, cfg.rope_theta, cfg.m_rope_sections)
    quantized = cfg.serve_kv_dtype == "int8"
    if quantized:
        kq, ks = _kv_quantize(k_t)
        vq, vs = _kv_quantize(v_t)
        rows = {"k": kq, "v": vq, "k_s": ks, "v_s": vs}
    else:
        rows = {"k": k_t, "v": v_t}
    if active is not None:
        rows = {n: jnp.where(active.reshape((b,) + (1,) * (r.ndim - 1)), r,
                             cache[n].rows_at(pos, c))
                for n, r in rows.items()}
    # the C new rows go in at pos..pos+C-1, then the layer is read back
    new_cache = {n: cache[n].put(r, pos) for n, r in rows.items()}
    if quantized:
        k = _kv_dequant(new_cache["k"].read(), new_cache["k_s"].read(),
                        x_t.dtype)
        v = _kv_dequant(new_cache["v"].read(), new_cache["v_s"].read(),
                        x_t.dtype)
    else:
        k, v = new_cache["k"].read(), new_cache["v"].read()
    scale = 1.0 / np.sqrt(cfg.head_dim)
    scores = _gqa_scores(q, k, cfg) * scale      # [B,KV,G,C,T]
    t = k.shape[1]
    valid = jnp.arange(t)[None, None, :] <= qpos[:, :, None]   # [B,C,T]
    scores = jnp.where(valid[:, None, None, :, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(x_t.dtype)
    out = qmatmul(_tp_gather_heads(_gqa_out(w, v, cfg)), p["wo"])
    return out, new_cache


def attn_cross(p, x, memory, cfg: ModelConfig, mem_kv=None, enc_lengths=None):
    """Cross attention (decoder -> encoder memory).  If mem_kv is given
    (precomputed at prefill), memory projection is skipped.

    enc_lengths: optional [B] int32 count of real encoder positions per
    row; memory positions >= enc_lengths[b] contribute exactly-zero
    softmax weight, so a cross-KV page right-padded to a bucket width is
    bit-identical to the unpadded computation (ragged encdec serving).
    A `len` leaf stored in mem_kv by prefill serves as the default, so
    the decode path picks the mask up from the slot cache for free.
    Rows with length 0 (inactive slots) get a uniform finite softmax --
    never NaN -- and their output is discarded by the slot mask."""
    q = _project_q(p, x, cfg)
    if mem_kv is None:
        k, v = _project_kv(p, memory, cfg)
    else:
        k, v = mem_kv["k"], mem_kv["v"]
        if enc_lengths is None:
            enc_lengths = mem_kv.get("len")
    scale = 1.0 / np.sqrt(cfg.head_dim)
    scores = _gqa_scores(q, k, cfg) * scale
    if enc_lengths is not None:
        valid = jnp.arange(k.shape[1])[None, :] < enc_lengths[:, None]
        scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    return qmatmul(_tp_gather_heads(_gqa_out(w, v, cfg)), p["wo"])


def init_cache(cfg: ModelConfig, batch: int, s_max: int, dtype=None):
    shape = (batch, s_max, cfg.n_kv, cfg.head_dim)
    if cfg.serve_kv_dtype == "int8":
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(shape[:-1], jnp.float32),
                "v_s": jnp.zeros(shape[:-1], jnp.float32)}
    dt = dtype or jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
