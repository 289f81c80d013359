"""The benchmark's weights and plain reference (bench/lib/weights.py,
reference.py) at test size."""
import jax
import jax.numpy as jnp
import numpy as np

import tiny_cell
from bench.lib import reference, weights

M = tiny_cell.MODEL


def test_a_layer_made_alone_equals_its_slice_of_the_tree():
    root = weights.root_key(2 ** 35 + 1)
    tree = weights.program_tree(root, M)
    one = weights.layer_weights(root, 1, M)
    np.testing.assert_array_equal(
        np.asarray(tree["blocks"]["attn"]["wq"][1], np.float32),
        np.asarray(one["wq"]))
    np.testing.assert_array_equal(
        np.asarray(tree["blocks"]["mlp"]["wo"][1], np.float32),
        np.asarray(one["wo_mlp"]))
    np.testing.assert_array_equal(
        np.asarray(tree["blocks"]["ln2"]["w"][1]), np.asarray(one["ln2"]))


def test_the_tree_has_the_programs_layout():
    from repro import configs
    from repro.models import lm
    from bench.families import dense
    cfg = dict(M, program={"arch": "qwen1.5-0.5b",
                           "overrides": {"attn_q_chunk": 16}})
    mcfg = dense.model_config(cfg)
    assert mcfg.attn_q_chunk == 16 and mcfg.n_kv == M["num_key_value_heads"]
    want = jax.eval_shape(lambda k: lm.init_params(k, mcfg),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights.program_tree(
        weights.root_key(0), M))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert configs.get_config("qwen1.5-0.5b").family == "dense"


def test_seeds_past_32_bits_differ():
    a = weights.global_weights(weights.root_key(2 ** 32 + 1), M)["embed"]
    b = weights.global_weights(weights.root_key(1), M)["embed"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_weight_quantization_is_per_output_channel():
    w = jnp.asarray(np.random.default_rng(0).normal(size=(64, 32)),
                    jnp.float32)
    q, s = reference.quantize_cols(w, 8)
    assert q.dtype == jnp.int8 and s.shape == (1, 32)
    assert int(jnp.max(jnp.abs(q))) == 127
    np.testing.assert_allclose(np.asarray(q * s), np.asarray(w),
                               atol=float(jnp.max(s)) / 2 + 1e-6)
    q4, _ = reference.quantize_cols(w, 4)
    assert int(q4.max()) == 7 and int(q4.min()) >= -8


def test_quantized_linear_is_exact_in_integers():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(3, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    wq, ws = reference.quantize_cols(w, 8)
    y = reference.qlinear(x, wq, ws, 8)
    xs = np.max(np.abs(np.asarray(x)), -1, keepdims=True) / 127 + 1e-8
    xq = np.clip(np.rint(np.asarray(x) / xs), -128, 127)
    want = (xq @ np.asarray(wq, np.float64)) * xs * np.asarray(ws)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-6)
    assert float(jnp.max(jnp.abs(y - x @ w))) < 0.2


def test_gaps_do_not_depend_on_padding_and_are_never_negative():
    """Padding after the last real position stays out of every causal
    row, so the block size changes no gap."""
    fmt = {"weight_bits": 8, "act_bits": 8,
           "quantized": ["wq", "wk", "wv", "wo", "wg", "wi", "wo_mlp"]}
    rng = np.random.default_rng(3)
    seqs = [(rng.integers(0, M["vocab_size"], n),
             rng.integers(0, M["vocab_size"], k)) for n, k in ((5, 4),
                                                                (11, 7))]
    a = reference.served_gaps(M, fmt, 7, seqs, block=16)
    b = reference.served_gaps(M, fmt, 7, seqs, block=64)
    assert [len(g) for g in a] == [4, 7]
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)
        assert (x >= 0).all()
    c = reference.served_gaps(M, fmt, 7, seqs, control_act_bits=4)
    assert all((g >= 0).all() for g in c)
