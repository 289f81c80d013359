"""The dense decoder family (`"program": {"family": "dense"}`): what the
harness needs of a model family, for the program's `dense` models.

    model_config(cfg)                  the program's ModelConfig
    make_params(cfg, fmt, seed)        the served weights, on the device
    served_gaps(cfg, fmt, seed, seqs, control_act_bits=None)
                                       the plain reference's comparison
    window_work(ctx)                   operations and bytes of a window

A configuration names its family; the harness loads
`bench/families/<family>.py` by that name, so another family (an ssm, an
encoder-decoder) comes as a file of its own.  A family may also define
`engine_kwargs(cfg, mcfg, params) -> dict`: engine arguments that are
not data (a draft model for speculative decoding), added to the
configuration's `engine` block.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.lib import catalog, reference, weights, work

window_work = work.window_work


def model_config(cfg: dict):
    """The program's ModelConfig, set from the configuration file: the
    published sizes, then the program's own settings in
    `program.overrides` (such as `attn_q_chunk`)."""
    from repro import configs
    base = configs.get_config(cfg["program"]["arch"])
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        qkv_bias=bool(cfg.get("attention_bias", False)),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        **cfg["program"].get("overrides", {}))


def make_params(cfg: dict, fmt: dict, seed: int):
    """Weights from the seed, quantized by the program in one jitted
    program (the bf16 tree is never whole beside its quantized copy)."""
    import jax
    from repro.quant.qtensor import QTensor, quantize_tree_for_serving

    m = catalog.model_block(cfg)
    words = np.asarray(jax.random.key_data(weights.root_key(seed)))
    make = jax.jit(lambda kd: quantize_tree_for_serving(
        weights.program_tree(jax.random.wrap_key_data(
            kd, impl="threefry2x32"), m), fmt["format"],
        force=bool(fmt.get("force", False))))
    params = make(words)
    jax.block_until_ready(params)
    got = sorted(jax.tree_util.keystr(p) for p, leaf in
                 jax.tree_util.tree_leaves_with_path(
                     params, is_leaf=lambda x: isinstance(x, QTensor))
                 if isinstance(leaf, QTensor))
    want = sorted(program_path(n) for n in fmt["quantized"])
    if got != want:
        raise SystemExit(f"the program quantized {got}, the configuration "
                         f"states {want}")
    return params


def program_path(name: str) -> str:
    """Where a projection of the configuration lives in the program's
    parameter tree."""
    if name == "lm_head":
        return "['lm_head']"
    if name == "wo_mlp":
        return "['blocks']['mlp']['wo']"
    part = "attn" if name in ("wq", "wk", "wv", "wo") else "mlp"
    return f"['blocks']['{part}']['{name}']"


def served_gaps(cfg: dict, fmt: dict, seed: int, seqs,
                control_act_bits=None):
    return reference.served_gaps(catalog.model_block(cfg), fmt, seed, seqs,
                                 control_act_bits=control_act_bits)
