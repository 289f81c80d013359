"""The decode step writes each step's new K/V rows in place.

`lm.decode_step` carries the stacked cache through its layer scan and
writes only what a layer changed: C new entries per slot on leaves with a
length axis, whole layers on constant-size leaves.  Three guards:

* bit-exactness against the formulation it replaced, kept here as the
  oracle: the cache as the scan's xs/ys and a `where` over every layer
  page (`_oracle_decode_step`) -- tokens, logits and the whole returned
  cache must be equal, with inactive rows and a position that overruns
  the cache mixed in;
* the `decode_state_writes` counter of `ServeEngine.cache_info()`;
* the compiled structure of an engine segment: no copy or fresh buffer of
  the whole stacked cache and no layer-sized `select` inside the step
  loop, and no sort outside the sampler's conditional branch, here on
  the CPU at reduced widths (tests/test_tpu_compile.py checks the same
  at qwen1.5-0.5b widths on a described v5e, where the segment's temp
  must also stay small).
"""
import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import engine
from repro.launch.engine import ServeEngine
from repro.models import attention, blocks, common, lm
from repro.quant.qtensor import qmatmul, quantize_tree_for_serving

ENC_LEN = 8
T = 16

FAMILY_ARCH = {
    "dense": "smollm-135m",
    "moe": "granite-moe-1b-a400m",
    "ssm": "mamba2-2.7b",
    "hybrid": "jamba-v0.1-52b",
    "encdec": "whisper-small",
}


def _cfg(family, kv="bfloat16"):
    return dataclasses.replace(configs.get_reduced_config(FAMILY_ARCH[family]),
                               serve_kv_dtype=kv)


# ---------------------------------------------------------------------------
# the oracle: the decode step before the cache became the scan's carry
# ---------------------------------------------------------------------------

def _oracle_cache_insert(cache_t, scale_t, new, pos, quantized):
    if quantized:
        q, s = attention._kv_quantize(new)
        t = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u, (i, 0, 0)))(cache_t, q, pos)
        sc = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u, (i, 0)))(scale_t, s, pos)
        return t, sc
    t = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
        c, u, (i, 0, 0)))(cache_t, new, pos)
    return t, None


def _oracle_mask_inactive(new, old, active):
    m = active.reshape((active.shape[0],) + (1,) * (new.ndim - 1))
    return jnp.where(m, new, old)


def _oracle_attn_decode(p, x_t, cache, pos, cfg, active=None):
    b, c = x_t.shape[:2]
    qpos = pos[:, None] + jnp.arange(c, dtype=pos.dtype)
    posq = (jnp.broadcast_to(qpos[None], (3, b, c))
            if cfg.m_rope_sections is not None else qpos)
    q = attention._project_q(p, x_t, cfg)
    k_t, v_t = attention._project_kv(p, x_t, cfg)
    if not cfg.learned_pos:
        q = common.apply_rope(q, posq, cfg.rope_theta, cfg.m_rope_sections)
        k_t = common.apply_rope(k_t, posq, cfg.rope_theta,
                                cfg.m_rope_sections)
    quantized = cfg.serve_kv_dtype == "int8"
    kc, ksc = _oracle_cache_insert(cache["k"], cache.get("k_s"), k_t, pos,
                                   quantized)
    vc, vsc = _oracle_cache_insert(cache["v"], cache.get("v_s"), v_t, pos,
                                   quantized)
    if active is not None:
        kc = _oracle_mask_inactive(kc, cache["k"], active)
        vc = _oracle_mask_inactive(vc, cache["v"], active)
        if quantized:
            ksc = _oracle_mask_inactive(ksc, cache["k_s"], active)
            vsc = _oracle_mask_inactive(vsc, cache["v_s"], active)
    if quantized:
        k = attention._kv_dequant(kc, ksc, x_t.dtype)
        v = attention._kv_dequant(vc, vsc, x_t.dtype)
        new_cache = {"k": kc, "v": vc, "k_s": ksc, "v_s": vsc}
    else:
        k, v = kc, vc
        new_cache = {"k": kc, "v": vc}
    scale = 1.0 / np.sqrt(cfg.head_dim)
    scores = attention._gqa_scores(q, k, cfg) * scale
    valid = jnp.arange(k.shape[1])[None, None, :] <= qpos[:, :, None]
    scores = jnp.where(valid[:, None, None, :, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(x_t.dtype)
    out = qmatmul(attention._tp_gather_heads(
        attention._gqa_out(w, v, cfg)), p["wo"])
    return out, new_cache


def _oracle_decode_step(params, token_t, cache, pos, cfg, active=None):
    if cfg.family == "encdec":
        x = jnp.take(params["embed"], token_t, axis=0)
        x = x + jnp.take(params["pos_embed"], pos, axis=0)[:, None, :]
        stacked, block_fn = params["dec"], blocks.dec_block
    else:
        x = lm._embed(params, token_t, cfg)
        if cfg.learned_pos:
            qpos = pos[:, None] + jnp.arange(x.shape[1], dtype=pos.dtype)
            x = x + jnp.take(params["pos_embed"], qpos, axis=0)
        stacked, block_fn = params["blocks"], lm.BLOCK_FNS[cfg.family][1]

    def body(h, xs):
        layer_params, layer_cache = xs
        h2, new_cache, _ = block_fn(layer_params, h, cfg, mode="decode",
                                    cache=layer_cache, pos=pos,
                                    active=active)
        return h2, new_cache

    x, new_caches = jax.lax.scan(body, x, (stacked, cache))
    x = common.norm_apply(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return lm._lm_head(params, x, cfg), new_caches


# ---------------------------------------------------------------------------
# bit-exactness against the oracle
# ---------------------------------------------------------------------------

def _random_cache(cfg, n_slots, seed):
    """A stacked cache of random contents, so that a write that lands on
    the wrong entries shows."""
    kw = {"s_enc": ENC_LEN} if cfg.family == "encdec" else {}
    cache = lm.init_cache(cfg, n_slots, T, **kw)
    rng = np.random.default_rng(seed)

    def fill(leaf):
        if leaf.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, leaf.shape), jnp.int8)
        if leaf.dtype == jnp.int32:          # encdec's real encoder lengths
            return jnp.asarray(rng.integers(1, ENC_LEN + 1, leaf.shape),
                               jnp.int32)
        return jnp.asarray(rng.normal(size=leaf.shape), leaf.dtype)

    return jax.tree_util.tree_map(fill, cache)


CASES = ([(f, kv, c) for f in ("dense", "moe")
          for kv in ("bfloat16", "int8") for c in (1, 4)]
         + [(f, kv, 1) for f in ("hybrid", "encdec")
            for kv in ("bfloat16", "int8")]
         + [("ssm", "bfloat16", 1)])


@pytest.mark.parametrize("family,kv,c", CASES,
                         ids=[f"{f}-{kv}-c{c}" for f, kv, c in CASES])
def test_decode_step_matches_oracle(family, kv, c):
    """Tokens, logits and the whole returned cache equal the xs/ys oracle,
    bit for bit.  Five slots: two active in the middle of the cache, one
    active whose pos + C overruns the cache (the write start is clamped),
    two inactive -- one of them also past the end."""
    cfg = _cfg(family, kv)
    params = lm.init_params(jax.random.PRNGKey(1), cfg, max_seq=64)
    if family in ("dense", "hybrid"):
        params = quantize_tree_for_serving(params, "w8a8", force=True)
    n = 5
    cache = _random_cache(cfg, n, seed=3)
    tok = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab, size=(n, c)), jnp.int32)
    pos = jnp.asarray([3, 9, T - c + 2, 5, T + 3], jnp.int32)
    active = jnp.asarray([True, True, True, False, False])

    new = jax.jit(lambda p, t, k, q, a: lm.decode_step(
        p, t, k, q, cfg, active=a))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(attention, "attn_decode", _oracle_attn_decode)
        old = jax.jit(lambda p, t, k, q, a: _oracle_decode_step(
            p, t, k, q, cfg, active=a))
        want_logits, want_cache = old(params, tok, cache, pos, active)
    got_logits, got_cache = new(params, tok, cache, pos, active)

    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(want_logits))
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(got_logits, -1)),
        np.asarray(jnp.argmax(want_logits, -1)))
    got = jax.tree_util.tree_leaves_with_path(got_cache)
    want = jax.tree_util.tree_leaves(want_cache)
    assert len(got) == len(want)
    for (path, g), w in zip(got, want):
        assert g.dtype == w.dtype, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))
    # the inactive slots' pages came back as they went in
    for g, before, ba in zip(
            jax.tree_util.tree_leaves(got_cache),
            jax.tree_util.tree_leaves(cache),
            lm.cache_spec(cfg, cache).batch_axes):
        np.testing.assert_array_equal(
            np.take(np.asarray(g), [3, 4], axis=ba),
            np.take(np.asarray(before), [3, 4], axis=ba))


# ---------------------------------------------------------------------------
# the counter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family,kv,want", [
    ("dense", "bfloat16", {"row": 2, "layer": 0}),
    ("dense", "int8", {"row": 4, "layer": 0}),
    ("ssm", "bfloat16", {"row": 0, "layer": 2}),
    ("hybrid", "bfloat16", {"row": 2, "layer": 2}),
    ("encdec", "bfloat16", {"row": 2, "layer": 3}),
])
def test_decode_state_writes_counter(family, kv, want):
    cfg = _cfg(family, kv)
    params = lm.init_params(jax.random.PRNGKey(0), cfg, max_seq=96)
    kw = {"enc_len": ENC_LEN} if family == "encdec" else {}
    eng = ServeEngine(params, cfg, n_slots=2, max_cache_len=32,
                      segment_len=2, chaos=None, **kw)
    assert eng.cache_info()["decode_state_writes"] == want


# ---------------------------------------------------------------------------
# compiled structure of the engine segment
# ---------------------------------------------------------------------------

def _computations(text):
    """{name: [instruction lines]} of an HLO module's text, and the entry's
    name."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
        elif line.startswith("}"):
            cur = None
        elif cur is not None and line.strip():
            comps[cur].append(line.strip())
    return comps, entry


def _loop_computations(text, branches=False):
    """The computations the entry's while loops run: their bodies and
    conditions, nested loops, fusions and called computations, and with
    `branches` the branches of conditionals too."""
    comps, entry = _computations(text)
    todo = [m.group(1) for ins in comps[entry]
            for m in re.finditer(r"body=%?([\w.\-]+)", ins)]
    assert todo, "the segment compiled to no loop"
    calls = r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)"
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for ins in comps[c]:
            todo += [m.group(1) for m in re.finditer(calls, ins)]
            if branches:
                todo += re.findall(
                    r"(?:true|false)_computation=%?([\w.\-]+)", ins)
                m = re.search(r"branch_computations=\{([^}]*)\}", ins)
                if m:
                    todo += [b.strip().lstrip("%")
                             for b in m.group(1).split(",")]
    return comps, seen


def _loop_ops(text):
    """Counter of (shape, op) over every instruction the entry's while
    loops run, nested loops and fusions included (custom calls by their
    target)."""
    comps, seen = _loop_computations(text)
    ops = collections.Counter()
    for c in seen:
        for ins in comps[c]:
            m = re.match(r"%?[\w.\-]+ = (\w+\[[\d,]*\])\S* ([\w\-]+)\(",
                         ins)
            if not m:
                continue
            op = m.group(2)
            if op == "custom-call":
                t = re.search(r'custom_call_target="(\w+)"', ins)
                op = t.group(1) if t else op
            ops[(m.group(1), op)] += 1
    return ops


def segment_lowered(cfg, n_slots, t, sharding=None):
    bundle = engine._build_bundle(cfg, "off", {})
    params = jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, n_slots, t))

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params, cache = jax.tree_util.tree_map(
        lambda a: sd(a.shape, a.dtype), (params, cache))
    b = n_slots
    samp = (sd((b, 2), jnp.uint32), sd((b,), jnp.float32),
            sd((b,), jnp.int32), sd((b,), jnp.float32), sd((b,), jnp.int32))
    segment = bundle.segment.__wrapped__
    return segment.lower(params, sd((b, 1), jnp.int32), cache,
                         sd((b,), jnp.int32), sd((b,), jnp.bool_), samp, 4)


def assert_no_cache_copies(text, cfg, n_slots, t):
    dt = {"bfloat16": "bf16", "float32": "f32"}[cfg.dtype]
    stack = f"{dt}[{cfg.n_layers},{n_slots},{t},{cfg.n_kv},{cfg.head_dim}]"
    layer = f"{dt}[{n_slots},{t},{cfg.n_kv},{cfg.head_dim}]"
    ops = _loop_ops(text)
    assert ops[(stack, "copy")] == 0, ops
    assert ops[(stack, "AllocateBuffer")] == 0, ops
    assert ops[(layer, "select")] == 0, ops
    # the stacked cache is in the loop (the check looks at the right shape)
    assert sum(v for (s, _), v in ops.items() if s == stack) > 0, ops


def assert_sort_only_in_branches(text):
    """The step loop sorts only inside a conditional's branch: an
    all-greedy step skips the sampler's full-vocabulary sort."""
    def sorts(comps, names):
        return sum(" sort(" in ins for c in names for ins in comps[c])

    comps, plain = _loop_computations(text)
    _, reach = _loop_computations(text, branches=True)
    assert sorts(comps, plain) == 0
    assert sorts(comps, reach - plain) > 0


@pytest.fixture(scope="module")
def segment_text_cpu():
    return segment_lowered(_cfg("dense"), 4, 64).compile().as_text()


def test_segment_loop_has_no_cache_copies_cpu(segment_text_cpu):
    assert_no_cache_copies(segment_text_cpu, _cfg("dense"), 4, 64)


def test_segment_loop_sorts_only_in_a_branch_cpu(segment_text_cpu):
    assert_sort_only_in_branches(segment_text_cpu)
