"""Top-level language models: init / forward / prefill / decode per family.

All families share the skeleton:

    embed (or frontend-stub embeddings) -> scan(blocks) -> norm -> lm_head

with layer params stacked on a leading axis and the stack run under
jax.lax.scan (optionally remat'd), so jaxpr/HLO size is depth-independent.

Caches are pytrees stacked over the scan axis; decode carries them through
the same scan and writes each layer's new entries in place.  Whisper
(encdec) runs two scans and carries cross-attention KV in the cache.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import blocks, common, slot_state, ssm
from repro.models.config import ModelConfig
from repro.quant.qtensor import qmatmul

BLOCK_FNS = {
    "dense": (blocks.init_dense_block, blocks.dense_block),
    "vlm": (blocks.init_dense_block, blocks.dense_block),
    "moe": (blocks.init_moe_block, blocks.moe_block),
    "ssm": (blocks.init_ssm_block, blocks.ssm_block),
    "hybrid": (blocks.init_hybrid_block, blocks.hybrid_block),
}


def n_scan_units(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        assert cfg.n_layers % cfg.hybrid.period == 0
        return cfg.n_layers // cfg.hybrid.period
    return cfg.n_layers


def _stacked_init(rng, n, init_fn):
    return jax.vmap(init_fn)(jax.random.split(rng, n))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(rng, cfg: ModelConfig, max_seq: int = 4096):
    r = common.split_rngs(rng, 6)
    dt = jnp.dtype(cfg.dtype)
    p: dict[str, Any] = {}
    p["embed"] = common.embed_init(r[0], cfg.vocab, cfg.d_model, dt)
    if not cfg.tie_embeddings:
        p["lm_head"] = common.dense_init(r[1], cfg.d_model, cfg.vocab, dt)
    p["final_norm"] = common.norm_init(cfg.d_model, cfg.norm)
    if cfg.learned_pos:
        p["pos_embed"] = common.embed_init(r[2], max_seq, cfg.d_model, dt)

    if cfg.family == "encdec":
        p["enc"] = _stacked_init(r[3], cfg.n_layers,
                                 lambda k: blocks.init_enc_block(k, cfg))
        p["enc_norm"] = common.norm_init(cfg.d_model, cfg.norm)
        p["enc_pos"] = common.embed_init(r[5], max_seq, cfg.d_model, dt)
        nd = cfg.n_decoder_layers or cfg.n_layers
        p["dec"] = _stacked_init(r[4], nd,
                                 lambda k: blocks.init_dec_block(k, cfg))
    else:
        init_fn, _ = BLOCK_FNS[cfg.family]
        p["blocks"] = _stacked_init(r[3], n_scan_units(cfg),
                                    lambda k: init_fn(k, cfg))
    return p


def _lm_head(p, x, cfg: ModelConfig):
    w = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    return qmatmul(x, w).astype(jnp.float32)


def _embed(p, tokens_or_embeds, cfg: ModelConfig):
    if tokens_or_embeds.dtype in (jnp.int32, jnp.int64):
        return jnp.take(p["embed"], tokens_or_embeds, axis=0)
    # frontend stub: precomputed frame/patch embeddings
    return tokens_or_embeds.astype(jnp.dtype(cfg.dtype))


# ---------------------------------------------------------------------------
# decoder-only forward (train) / prefill / decode
# ---------------------------------------------------------------------------

def forward(params, inputs, cfg: ModelConfig, *, remat: bool = True,
            positions=None):
    """inputs: [B,S] int tokens or [B,S,d] stub embeddings -> logits, aux."""
    if cfg.family == "encdec":
        return encdec_forward(params, inputs, cfg, remat=remat)
    x = _embed(params, inputs, cfg)
    if cfg.learned_pos:
        x = x + params["pos_embed"][None, :x.shape[1], :]
    if cfg.m_rope_sections is not None and positions is None:
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s)[None, None, :], (3, b, s))
    _, block_fn = BLOCK_FNS[cfg.family]

    def body(carry, layer_params):
        h, aux = carry
        h2, _, aux_i = block_fn(layer_params, h, cfg, mode="train",
                                positions=positions)
        return (h2, aux + aux_i), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params["blocks"])
    x = common.norm_apply(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return _lm_head(params, x, cfg), aux


def init_cache(cfg: ModelConfig, batch: int, s_max: int,
               s_enc: Optional[int] = None):
    """Stacked per-scan-unit cache pytree."""
    n = n_scan_units(cfg)

    def one(_):
        if cfg.family in ("dense", "vlm", "moe"):
            return attn_mod.init_cache(cfg, batch, s_max)
        if cfg.family == "ssm":
            return ssm.init_ssm_state(cfg, batch)
        if cfg.family == "hybrid":
            return {
                "mamba": jax.tree_util.tree_map(
                    lambda t: jnp.broadcast_to(
                        t, (cfg.hybrid.period - 1,) + t.shape),
                    ssm.init_ssm_state(cfg, batch)),
                "attn": attn_mod.init_cache(cfg, batch, s_max),
            }
        if cfg.family == "encdec":
            return {
                "self": attn_mod.init_cache(cfg, batch, s_max),
                "cross": {
                    "k": jnp.zeros((batch, s_enc or s_max, cfg.n_kv,
                                    cfg.head_dim), jnp.dtype(cfg.dtype)),
                    "v": jnp.zeros((batch, s_enc or s_max, cfg.n_kv,
                                    cfg.head_dim), jnp.dtype(cfg.dtype)),
                    # real encoder frames per row; attn_cross masks the
                    # padded tail so ragged enc lengths share one page shape
                    "len": jnp.zeros((batch,), jnp.int32),
                },
            }
        raise ValueError(cfg.family)

    if cfg.family == "encdec":
        n = cfg.n_decoder_layers or cfg.n_layers
    unit = one(None)
    return jax.tree_util.tree_map(
        lambda t: jnp.broadcast_to(t[None], (n,) + t.shape).copy(), unit)


def prefill(params, inputs, cfg: ModelConfig, cache_len: int,
            positions=None, last_positions=None, enc_lengths=None,
            enc_pad=None):
    """Run the prompt, return (last-position logits, cache).

    last_positions: optional [B] int32 -- per-row index of the last REAL
    prompt token (for right-padded ragged batches; the serve engine pads
    prompts up to a shape bucket).  Default: the final column.
    enc_lengths / enc_pad (encdec only): per-row real encoder frame
    counts and the static cross-KV page width to pad to."""
    if cfg.family == "encdec":
        return encdec_prefill(params, inputs, cfg, cache_len,
                              last_positions=last_positions,
                              enc_lengths=enc_lengths, enc_pad=enc_pad)
    x = _embed(params, inputs, cfg)
    if cfg.learned_pos:
        x = x + params["pos_embed"][None, :x.shape[1], :]
    if cfg.m_rope_sections is not None and positions is None:
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s)[None, None, :], (3, b, s))
    _, block_fn = BLOCK_FNS[cfg.family]
    # per-row real lengths: attention masks right-padding causally, but
    # SSM state is sequential -- padded steps must become identity
    # updates.  Always materialized so every prefill (static generate()
    # and the engine's padded prompt buckets alike) runs ssd_forward on
    # the same FIXED chunk grid -- the bit-exactness precondition
    if last_positions is None:
        lengths = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    else:
        lengths = last_positions + 1

    def body(h, layer_params):
        h2, cache, _ = block_fn(layer_params, h, cfg, mode="prefill",
                                positions=positions, cache_len=cache_len,
                                lengths=lengths)
        return h2, cache

    x, caches = jax.lax.scan(body, x, params["blocks"])
    x = common.norm_apply(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    if last_positions is None:
        x_last = x[:, -1:, :]
    else:
        x_last = x[jnp.arange(x.shape[0]), last_positions][:, None, :]
    return _lm_head(params, x_last, cfg), caches


def cache_spec(cfg: ModelConfig, cache) -> slot_state.SlotStateSpec:
    """The probed slot-state spec of a stacked cache built like
    init_cache's: per leaf, its slot axis and its length axis (None for
    constant-size pages).  encdec's cross pages are as wide as the encoder
    memory, not the cache, so the probe is told that width."""
    kw = {}
    if cfg.family == "encdec":
        kw["s_enc"] = cache["cross"]["k"].shape[2]
    return slot_state.spec_for(cfg, **kw)


@dataclasses.dataclass(frozen=True)
class StackedPage:
    """One leaf of the stacked cache that has a length axis (an attention
    page or its int8 scales), as one layer of the decode scan sees it.

    `read()` is the layer's page; `rows_at(pos, c)` and `put(rows, pos)`
    read and write each slot's c entries from its own position, in place
    in the stack: a gather and a scatter of c-entry windows in CLIP mode,
    so a start past T-c is clamped exactly as dynamic_update_slice clamps
    it (the engine's stale-but-masked overrun rows rely on that;
    launch/engine.py)."""

    stack: Any
    layer: Any                  # traced layer index
    batch_axis: int
    length_axis: int

    def read(self):
        return jax.lax.dynamic_index_in_dim(self.stack, self.layer, 0,
                                            keepdims=False)

    def _windows(self, pos):
        """Start indices [B, 3] (layer, slot, position) of each slot's
        window, and the window's axes as gather/scatter numbers."""
        ba, la, nd = self.batch_axis, self.length_axis, self.stack.ndim
        n_b = self.stack.shape[ba]
        idx = jnp.stack([jnp.full((n_b,), self.layer, jnp.int32),
                         jnp.arange(n_b, dtype=jnp.int32),
                         pos.astype(jnp.int32)], axis=1)
        return idx, tuple(range(1, nd - 1)), (0, ba), (0, ba, la)

    def rows_at(self, pos, c: int):
        idx, window, inserted, to_operand = self._windows(pos)
        sizes = list(self.stack.shape)
        sizes[0] = sizes[self.batch_axis] = 1
        sizes[self.length_axis] = c
        rows = jax.lax.gather(
            self.stack, idx,
            jax.lax.GatherDimensionNumbers(
                offset_dims=window, collapsed_slice_dims=inserted,
                start_index_map=to_operand),
            tuple(sizes), indices_are_sorted=True,
            mode=jax.lax.GatherScatterMode.CLIP)
        return jnp.moveaxis(rows, 0, self.batch_axis - 1)

    def put(self, rows, pos) -> "StackedPage":
        idx, window, inserted, to_operand = self._windows(pos)
        stack = jax.lax.scatter(
            self.stack, idx, jnp.moveaxis(rows, self.batch_axis - 1, 0),
            jax.lax.ScatterDimensionNumbers(
                update_window_dims=window, inserted_window_dims=inserted,
                scatter_dims_to_operand_dims=to_operand),
            indices_are_sorted=True, unique_indices=True,
            mode=jax.lax.GatherScatterMode.CLIP)
        return dataclasses.replace(self, stack=stack)


def decode_step(params, token_t, cache, pos, cfg: ModelConfig, active=None):
    """token_t: [B,C] int (or [B,C,d] stub embed); pos: [B] int32 position
    of the first new token per row; active: optional [B] bool slot mask --
    inactive rows compute but neither mutate their cache nor (at the caller)
    contribute sampled tokens.  C=1 is the serving decode step; C>1 is a
    chunked-prefill step over the same cache layout.

    Returns (logits [B,C,V], new_cache).  Every family has a masked state
    update (attention: masked KV rows; SSM: masked {ssm, conv} state;
    encdec: masked self-KV, read-only cross-KV), so inactive slots are
    bit-identical across the step for any registered family
    (models/slot_state.py; property-tested in tests/test_slot_state.py).

    The stacked cache is the layer scan's carry, not its xs/ys: each layer
    reads its slice at its index and writes back only what it changed.
    Leaves with a length axis (attention pages, int8 scales) take their C
    new entries per slot in place; constant-size leaves (SSM state, conv
    windows, cross-KV) are written whole at the layer's index.  So a step
    moves the new rows, not a fresh copy of the whole cache."""
    if cfg.family == "encdec":
        x = jnp.take(params["embed"], token_t, axis=0)
        x = x + jnp.take(params["pos_embed"], pos, axis=0)[:, None, :]
        stacked, block_fn = params["dec"], blocks.dec_block
    else:
        x = _embed(params, token_t, cfg)
        if cfg.learned_pos:
            qpos = pos[:, None] + jnp.arange(x.shape[1], dtype=pos.dtype)
            x = x + jnp.take(params["pos_embed"], qpos, axis=0)
        stacked, block_fn = params["blocks"], BLOCK_FNS[cfg.family][1]
    spec = cache_spec(cfg, cache)
    treedef = jax.tree_util.tree_structure(cache)
    if treedef != spec.treedef:
        raise ValueError(f"cache tree {treedef} is not the {cfg.family!r} "
                         f"cache layout {spec.treedef}")
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]

    def body(carry, xs):
        h, stack = carry
        layer_params, layer = xs
        leaves = jax.tree_util.tree_leaves(stack)
        view = [jax.lax.dynamic_index_in_dim(t, layer, 0, keepdims=False)
                if la is None else StackedPage(t, layer, ba, la)
                for t, ba, la in zip(leaves, spec.batch_axes,
                                     spec.length_axes)]
        h, upd, _ = block_fn(layer_params, h, cfg, mode="decode",
                             cache=jax.tree_util.tree_unflatten(treedef,
                                                                view),
                             pos=pos, active=active)
        out = [jax.lax.dynamic_update_index_in_dim(t, u, layer, 0)
               if la is None else u.stack
               for t, u, la in zip(leaves, treedef.flatten_up_to(upd),
                                   spec.length_axes)]
        return (h, jax.tree_util.tree_unflatten(treedef, out)), None

    (x, cache), _ = jax.lax.scan(body, (x, cache),
                                 (stacked, jnp.arange(n, dtype=jnp.int32)))
    x = common.norm_apply(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return _lm_head(params, x, cfg), cache


# ---------------------------------------------------------------------------
# encoder-decoder (whisper)
# ---------------------------------------------------------------------------

def encode(params, embeds, cfg: ModelConfig, lengths=None):
    """lengths: optional [B] int32 real-frame counts; padded frames are
    masked out of every encoder self-attention, so real positions of a
    right-padded batch are bit-identical to an unpadded encode."""
    x = embeds.astype(jnp.dtype(cfg.dtype))
    x = x + params["enc_pos"][None, :x.shape[1], :]

    def body(h, layer_params):
        return blocks.enc_block(layer_params, h, cfg, lengths=lengths), None

    x, _ = jax.lax.scan(body, x, params["enc"])
    return common.norm_apply(x, params["enc_norm"], cfg.norm, cfg.norm_eps)


def encdec_forward(params, inputs, cfg: ModelConfig, *, remat: bool = True):
    """inputs: (audio_embeds [B,S_enc,d], dec_tokens [B,S_dec])."""
    audio, dec_tokens = inputs
    memory = encode(params, audio, cfg)
    x = jnp.take(params["embed"], dec_tokens, axis=0)
    x = x + params["pos_embed"][None, :x.shape[1], :]

    def body(carry, layer_params):
        h, = carry
        h2, _, _ = blocks.dec_block(layer_params, h, cfg, memory=memory,
                                    mode="train")
        return (h2,), None

    if remat:
        body = jax.checkpoint(body)
    (x,), _ = jax.lax.scan(body, (x,), params["dec"])
    x = common.norm_apply(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return _lm_head(params, x, cfg), jnp.float32(0.0)


def encdec_prefill(params, inputs, cfg: ModelConfig, cache_len: int,
                   last_positions=None, enc_lengths=None, enc_pad=None):
    audio, dec_tokens = inputs
    memory = encode(params, audio, cfg, lengths=enc_lengths)
    x = jnp.take(params["embed"], dec_tokens, axis=0)
    x = x + params["pos_embed"][None, :x.shape[1], :]

    def body(h, layer_params):
        h2, cache, _ = blocks.dec_block(layer_params, h, cfg, memory=memory,
                                        mode="prefill", cache_len=cache_len,
                                        enc_lengths=enc_lengths,
                                        enc_pad=enc_pad)
        return h2, cache

    x, caches = jax.lax.scan(body, x, params["dec"])
    x = common.norm_apply(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    if last_positions is None:
        x_last = x[:, -1:, :]
    else:
        x_last = x[jnp.arange(x.shape[0]), last_positions][:, None, :]
    return _lm_head(params, x_last, cfg), caches


# ---------------------------------------------------------------------------
# embedding method (serve `embed`)
# ---------------------------------------------------------------------------

def embed_pool(params, inputs, cfg: ModelConfig, last_positions=None,
               enc_lengths=None):
    """Final-hidden-state embedding of a prompt: run the stack exactly as
    prefill does (per-token MoE routing, SSM identity updates on padded
    rows, masked encoder frames) and masked-mean-pool the post-final-norm
    hidden states over the real positions, in float32.

    Riding the prefill code path is what makes embeddings batch-
    composition invariant: a request's vector is bit-identical whatever
    its batch mates or padding, the same invariant the engine's token
    bit-exactness tests rest on.  Returns [B, d_model] float32; no KV is
    materialized (the caches the blocks emit are dropped, so XLA DCEs
    the page writes)."""
    if cfg.family == "encdec":
        audio, dec_tokens = inputs
        memory = encode(params, audio, cfg, lengths=enc_lengths)
        x = jnp.take(params["embed"], dec_tokens, axis=0)
        x = x + params["pos_embed"][None, :x.shape[1], :]

        def body(h, layer_params):
            h2, _, _ = blocks.dec_block(layer_params, h, cfg, memory=memory,
                                        mode="prefill",
                                        cache_len=x.shape[1],
                                        enc_lengths=enc_lengths)
            return h2, None

        x, _ = jax.lax.scan(body, x, params["dec"])
    else:
        x = _embed(params, inputs, cfg)
        if cfg.learned_pos:
            x = x + params["pos_embed"][None, :x.shape[1], :]
        positions = None
        if cfg.m_rope_sections is not None:
            b, s = x.shape[:2]
            positions = jnp.broadcast_to(jnp.arange(s)[None, None, :],
                                         (3, b, s))
        if last_positions is None:
            lengths = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
        else:
            lengths = last_positions + 1
        _, block_fn = BLOCK_FNS[cfg.family]

        def body(h, layer_params):
            h2, _, _ = block_fn(layer_params, h, cfg, mode="prefill",
                                positions=positions, cache_len=x.shape[1],
                                lengths=lengths)
            return h2, None

        x, _ = jax.lax.scan(body, x, params["blocks"])
    x = common.norm_apply(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    b, s = x.shape[:2]
    if last_positions is None:
        lengths = jnp.full((b,), s, jnp.int32)
    else:
        lengths = last_positions + 1
    mask = (jnp.arange(s)[None, :] < lengths[:, None]).astype(jnp.float32)
    xf = x.astype(jnp.float32) * mask[:, :, None]
    return xf.sum(axis=1) / lengths[:, None].astype(jnp.float32)


# ---------------------------------------------------------------------------
# slot-state registry (models/slot_state.py)
# ---------------------------------------------------------------------------
# The serve engine builds, slices, scatters and compacts per-slot decode
# state through these registrations; axis layout is probed from init_cache,
# so a family only ever declares its builder.  Chunked prefill is limited
# to pure-KV families: SSM/hybrid state updates are sequential and encdec
# prefill must run the encoder, so pushing their prompts through the decode
# path C tokens at a time would change the floating-point reduction order
# (or skip the encoder) and lose bit-exactness against the static path.
for _fam in ("dense", "vlm", "moe"):
    slot_state.register(_fam, init_cache)
for _fam in ("ssm", "hybrid", "encdec"):
    slot_state.register(_fam, init_cache, prefill_chunkable=False)
