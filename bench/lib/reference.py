"""Plain reference for a dense decoder with quantized projections.

It follows the published architecture (pre-norm RMSNorm, rotary
embeddings on halves, grouped-query causal attention, SwiGLU, optional
q/k/v bias, tied or separate head) and the serving format's semantics:
each quantized projection uses symmetric per-output-channel weight
scales (max |w| over the input axis / qmax), symmetric per-token
activation scales (max |x| over the row / qmax), round to nearest,
an exact integer product, then both scales.  Everything else is float32
at `highest` matmul precision.  It imports nothing of the program: the
bf16 weights come from `weights.py`, made again from the seed one layer
at a time, and every scale is computed here.

`served_gaps` runs it over whole sequences (prompt + served tokens) and
returns, for every served token, how far that token's logit lies below
the reference's best at its position.  With `control_act_bits`, a
second pass at that lower activation precision runs beside it and the
gaps are those of the tokens the control puts first.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import weights

EPS = 1e-8


def _qmax(bits: int) -> int:
    return 2 ** (bits - 1) - 1


def quantize_cols(w, bits: int):
    """Per-output-channel symmetric quantization of w [K, N]."""
    qm = _qmax(bits)
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / qm + EPS
    return jnp.clip(jnp.round(w / s), -qm - 1, qm).astype(jnp.int8), s


def qlinear(x, wq, ws, act_bits: int):
    """x [..., K] float32; (wq, ws) from `quantize_cols`."""
    qm = _qmax(act_bits)
    xs = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qm + EPS
    xq = jnp.clip(jnp.round(x / xs), -qm - 1, qm).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x [B, S, H, D], positions 0..S-1, rotation on the two halves."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ang = np.arange(x.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(m: dict, wbits: int, abits: int, quantized: tuple,
           root, layer, x):
    """One decoder layer over x [B, S, d] float32, weights made here."""
    w = weights.layer_weights(root, layer, m)
    hd = m["hidden_size"] // m["num_attention_heads"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    eps = m["rms_norm_eps"]

    def lin(h, name):
        if name in quantized:
            return qlinear(h, *quantize_cols(w[name], wbits), abits)
        return h @ w[name]

    b, s, _ = x.shape
    h = rms_norm(x, w["ln1"], eps)
    q, k, v = lin(h, "wq"), lin(h, "wk"), lin(h, "wv")
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = rope(q.reshape(b, s, nh, hd), m["rope_theta"])
    k = rope(k.reshape(b, s, nkv, hd), m["rope_theta"])
    v = v.reshape(b, s, nkv, hd)
    q = q.reshape(b, s, nkv, nh // nkv, hd)
    sc = jnp.einsum("bskgd,btkd->bkgst", q, k) / np.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    o = jnp.einsum("bkgst,btkd->bskgd", jax.nn.softmax(sc, -1), v)
    x = x + lin(o.reshape(b, s, nh * hd), "wo")
    h = rms_norm(x, w["ln2"], eps)
    return x + lin(jax.nn.silu(lin(h, "wg")) * lin(h, "wi"), "wo_mlp")


def _head(m: dict, wbits: int, abits: int, quantized: tuple, root, x, rows):
    """Logits [R, V] of the final hidden rows x[rows]."""
    g = weights.global_weights(root, m)
    h = rms_norm(x[rows[:, 0], rows[:, 1]], g["final_norm"],
                 m["rms_norm_eps"])
    if "lm_head" in g:
        if "lm_head" in quantized:
            return qlinear(h, *quantize_cols(g["lm_head"], wbits), abits)
        return h @ g["lm_head"]
    return h @ g["embed"].T


@functools.lru_cache(maxsize=None)
def _programs(mkey, wbits, abits, quantized):
    m = dict(mkey)
    layer = jax.jit(functools.partial(_layer, m, wbits, abits, quantized))
    head = jax.jit(functools.partial(_head, m, wbits, abits, quantized))
    embed = jax.jit(lambda root, toks: weights.global_weights(root, m)
                    ["embed"][toks])
    return embed, layer, head


def served_gaps(m: dict, fmt: dict, seed: int, seqs, control_act_bits=None,
                block: int = 256):
    """seqs: list of (prompt, served) int arrays.  Returns one float32
    array of gaps per sequence, one entry per served token: the
    reference's best logit minus the logit of the served token (or, with
    `control_act_bits`, of the control's first choice) at that position.

    Runs one layer at a time over all sequences, padded at the end to a
    multiple of `block` (padding lies after every real position, so the
    causal mask keeps it out)."""
    root = weights.root_key(seed)
    mkey = tuple(sorted(m.items()))
    quantized = tuple(fmt["quantized"])
    wb, ab = fmt["weight_bits"], fmt["act_bits"]
    passes = [_programs(mkey, wb, ab, quantized)]
    if control_act_bits is not None:
        passes.append(_programs(mkey, wb, control_act_bits, quantized))
    full = [np.concatenate([p, t]).astype(np.int32) for p, t in seqs]
    s = -(-max(len(f) for f in full) // block) * block
    toks = np.zeros((len(full), s), np.int32)
    for i, f in enumerate(full):
        toks[i, :len(f)] = f
    # the rows whose logits choose served tokens: positions P-1 .. P+T-2
    rows = np.concatenate([
        np.stack([np.full(len(t), i), len(p) - 1 + np.arange(len(t))], 1)
        for i, (p, t) in enumerate(seqs)]).astype(np.int32)
    served = np.concatenate([t for _, t in seqs]).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        xs = [embed(root, jnp.asarray(toks)) for embed, _, _ in passes]
        for layer in range(m["num_hidden_layers"]):
            xs = [prog[1](root, layer, x) for prog, x in zip(passes, xs)]
        logits = [prog[2](root, x, jnp.asarray(rows))
                  for prog, x in zip(passes, xs)]
        ref = logits[0]
        pick = jnp.asarray(served) if control_act_bits is None \
            else jnp.argmax(logits[1], -1)
        gaps = np.asarray(jnp.max(ref, -1) - jnp.take_along_axis(
            ref, pick[:, None], -1)[:, 0])
    return np.split(gaps, np.cumsum([len(t) for _, t in seqs])[:-1])
