"""Seeded weights for a dense decoder, made by the benchmark.

The benchmark, not the program, makes the weights: the program's
serving path quantizes them, and the plain reference (`reference.py`)
makes the very same bf16 values again, one layer at a time, without
taking anything from the program.

Every leaf is drawn from its own key, `fold_in(fold_in(root, unit),
leaf)`, where `unit` is the layer index (or `GLOBAL` for the embedding,
the head and the final norm), so one layer can be made alone and gives
the same values as the stacked tree.  Values are uniform (bit
manipulation and one multiply, so every device rounds them alike) with
the usual widths: 1/sqrt(fan_in) for projections, 0.02 for the
embedding and biases, 1 +- 0.1 for norm gains.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

GLOBAL = 1 << 20
GAIN_STD = 0.1 / np.sqrt(3.0)      # gains uniform in 1 +- 0.1
LEAVES = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "wi", "wg", "wo_mlp",
          "ln1", "ln2", "embed", "lm_head", "final_norm")


def root_key(seed: int):
    """A threefry key from any non-negative integer seed (64 bits and
    more: the seed is hashed, not cast)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words),
                                    impl="threefry2x32")


def _uniform(key, shape, std, center=0.0):
    a = float(std) * np.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, center - a,
                              center + a)


def _leaf_key(root, unit, name):
    return jax.random.fold_in(jax.random.fold_in(root, unit),
                              LEAVES.index(name))


def layer_weights(root, layer, m: dict) -> dict:
    """One layer's weights, float32 with bf16 values (norm gains float32).
    `m` is the model block of a configuration file."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hd = d // m["num_attention_heads"]
    qd, kvd = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    shapes = {"wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d),
              "wi": (d, f), "wg": (d, f), "wo_mlp": (f, d)}
    out = {}
    for name, shp in shapes.items():
        w = _uniform(_leaf_key(root, layer, name), shp, 1 / np.sqrt(shp[0]))
        out[name] = w.astype(jnp.bfloat16).astype(jnp.float32)
    if m.get("attention_bias", False):
        for name, n in (("bq", qd), ("bk", kvd), ("bv", kvd)):
            b = _uniform(_leaf_key(root, layer, name), (n,), 0.02)
            out[name] = b.astype(jnp.bfloat16).astype(jnp.float32)
    for name in ("ln1", "ln2"):
        out[name] = _uniform(_leaf_key(root, layer, name), (d,), GAIN_STD,
                             1.0)
    return out


def global_weights(root, m: dict) -> dict:
    d, v = m["hidden_size"], m["vocab_size"]
    out = {"embed": _uniform(_leaf_key(root, GLOBAL, "embed"), (v, d), 0.02
                             ).astype(jnp.bfloat16).astype(jnp.float32),
           "final_norm": _uniform(_leaf_key(root, GLOBAL, "final_norm"),
                                  (d,), GAIN_STD, 1.0)}
    if not m.get("tie_word_embeddings", False):
        out["lm_head"] = _uniform(_leaf_key(root, GLOBAL, "lm_head"), (d, v),
                                  1 / np.sqrt(d)
                                  ).astype(jnp.bfloat16).astype(jnp.float32)
    return out


def program_tree(root, m: dict) -> dict:
    """The same weights in the program's dense parameter layout (layers
    stacked on a leading axis, matrices bf16, norm gains float32).  Traced
    inside one jitted program together with the program's quantizer, so
    the bf16 tree never lives whole on the device beside its quantized
    copy."""
    layers = jax.vmap(lambda i: layer_weights(root, i, m))(
        jnp.arange(m["num_hidden_layers"]))
    g = global_weights(root, m)
    bf = lambda x: x.astype(jnp.bfloat16)
    attn = {k: bf(layers[k]) for k in ("wq", "wk", "wv", "wo")}
    if m.get("attention_bias", False):
        attn.update({k: bf(layers[k]) for k in ("bq", "bk", "bv")})
    tree = {
        "embed": bf(g["embed"]),
        "final_norm": {"w": g["final_norm"]},
        "blocks": {
            "ln1": {"w": layers["ln1"]},
            "attn": attn,
            "ln2": {"w": layers["ln2"]},
            "mlp": {"wi": bf(layers["wi"]), "wg": bf(layers["wg"]),
                    "wo": bf(layers["wo_mlp"])},
        },
    }
    if "lm_head" in g:
        tree["lm_head"] = bf(g["lm_head"])
    return tree
