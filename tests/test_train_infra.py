"""Training infrastructure: optimizer, microbatching, data determinism,
checkpoint/restart, failure injection, compression, fault detection."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint, configs
from repro.data import DataConfig, make_stream
from repro.distributed.fault import (Heartbeat,
                                     SimulatedFailure, StragglerDetector)
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         compress_grads, compressed_psum, decompress_grads,
                         global_norm, warmup_cosine)
from repro.training import TrainConfig, make_train_step


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_decreases_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw_init(params, cfg)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(params, grads, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_adamw_bf16_state_dtype():
    cfg = AdamWConfig(state_dtype="bfloat16")
    params = {"w": jnp.ones((4, 4))}
    state = adamw_init(params, cfg)
    assert state["m"]["w"].dtype == jnp.bfloat16
    _, state2, _ = adamw_update(params, {"w": jnp.ones((4, 4))}, state, cfg)
    assert state2["v"]["w"].dtype == jnp.bfloat16


def test_grad_clipping():
    cfg = AdamWConfig(clip_norm=1.0)
    params = {"w": jnp.zeros((3,))}
    state = adamw_init(params, cfg)
    _, _, m = adamw_update(params, {"w": jnp.full((3,), 100.0)}, state, cfg)
    assert float(m["clip_scale"]) < 0.01


def test_schedule_shape():
    assert float(warmup_cosine(0, warmup=10, total=100)) == 0.0
    assert float(warmup_cosine(10, warmup=10, total=100)) == pytest.approx(1.0)
    assert float(warmup_cosine(100, warmup=10, total=100)) == pytest.approx(
        0.1, abs=1e-5)


def test_microbatch_equivalence():
    """mb=1 and mb=2 must produce identical updates (same total batch)."""
    cfg = configs.get_reduced_config("smollm-135m")
    rng = jax.random.PRNGKey(0)
    params = lm.init_params(rng, cfg)
    toks = jax.random.randint(rng, (4, 17), 0, cfg.vocab)
    outs = []
    for mb in (1, 2):
        tcfg = TrainConfig(microbatches=mb, remat=False,
                           optimizer=AdamWConfig(lr=1e-3))
        opt = adamw_init(params, tcfg.optimizer)
        p2, _, m = make_train_step(cfg, tcfg)(params, opt,
                                              {"tokens": toks})
        outs.append((p2, float(m["loss"])))
    (pa, la), (pb, lb) = outs
    assert la == pytest.approx(lb, rel=1e-3)
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()), pa, pb)
    assert max(jax.tree_util.tree_leaves(diffs)) < 2e-2


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_per_step():
    cfg = DataConfig(seq_len=16, global_batch=4, vocab=100, seed=7)
    s1, s2 = make_stream(cfg), make_stream(cfg)
    np.testing.assert_array_equal(s1.batch_at(13), s2.batch_at(13))
    assert not np.array_equal(s1.batch_at(13), s1.batch_at(14))


def test_data_host_sharding():
    h0 = make_stream(DataConfig(16, 4, 100, seed=1, n_hosts=2, host_id=0))
    h1 = make_stream(DataConfig(16, 4, 100, seed=1, n_hosts=2, host_id=1))
    assert h0.batch_at(5).shape == (2, 17)
    assert not np.array_equal(h0.batch_at(5), h1.batch_at(5))


def test_mmap_stream(tmp_path):
    path = tmp_path / "tokens.bin"
    np.arange(10000, dtype=np.int32).tofile(path)
    s = make_stream(DataConfig(16, 2, 100, source="mmap", path=str(path)))
    b = s.batch_at(0)
    assert b.shape == (2, 17)
    # windows are contiguous slices of the file
    assert np.all(np.diff(b, axis=1) == 1)


def test_iterate_resume():
    s = make_stream(DataConfig(8, 2, 50, seed=3))
    it = s.iterate(start_step=5)
    np.testing.assert_array_equal(next(it), s.batch_at(5))
    np.testing.assert_array_equal(next(it), s.batch_at(6))


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16),
                       "step": jnp.int32(7)}}
    checkpoint.save_checkpoint(str(tmp_path), 42, tree)
    like = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x), tree)
    restored, step = checkpoint.restore_checkpoint(str(tmp_path), like)
    assert step == 42
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_checkpoint_keep_last_k(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in (1, 2, 3, 4):
        checkpoint.save_checkpoint(str(tmp_path), s, tree, keep=2)
    assert checkpoint.latest_step(str(tmp_path)) == 4
    steps = sorted(n for n in os.listdir(tmp_path) if n.startswith("step_"))
    assert len(steps) == 2


def test_checkpoint_ignores_uncommitted(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    checkpoint.save_checkpoint(str(tmp_path), 1, tree)
    os.makedirs(tmp_path / "step_000000099")   # no _COMMITTED marker
    assert checkpoint.latest_step(str(tmp_path)) == 1


def test_checkpoint_restores_quantized_tree(tmp_path):
    from repro.quant.qtensor import quantize_tree_for_serving
    w = {"blocks": {"mlp": {"wi": jnp.ones((2, 256, 256), jnp.bfloat16)}}}
    q = quantize_tree_for_serving(w, "w8a8")
    checkpoint.save_checkpoint(str(tmp_path), 5, q)
    like = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x), q)
    restored, _ = checkpoint.restore_checkpoint(str(tmp_path), like)
    qt = restored["blocks"]["mlp"]["wi"]
    assert qt.fmt == "w8a8"
    np.testing.assert_array_equal(np.asarray(qt.q),
                                  np.asarray(q["blocks"]["mlp"]["wi"].q))


# ---------------------------------------------------------------------------
# failure injection + restart (end-to-end via the training driver)
# ---------------------------------------------------------------------------

def test_train_driver_restart_after_failures(tmp_path):
    import argparse

    from repro.launch import train as train_mod

    args = argparse.Namespace(
        arch="smollm-135m", reduced=True, steps=24, batch=2, seq=16,
        lr=1e-3, microbatches=1, mesh="1x1", seed=0,
        ckpt_dir=str(tmp_path), ckpt_every=8, log_every=8,
        simulate_failures="10,18", max_restarts=5, sim_hosts=2)
    out = train_mod.run(args)
    assert out["restores"] == 2          # both failures recovered
    assert np.isfinite(out["final_loss"])
    assert checkpoint.latest_step(str(tmp_path)) == 24


def test_restart_policy_gives_up():
    from repro.distributed.fault import RestartPolicy
    p = RestartPolicy(max_restarts=2)
    exc = SimulatedFailure("x")
    assert p.should_restart(exc)
    assert p.should_restart(exc)
    assert not p.should_restart(exc)


# ---------------------------------------------------------------------------
# straggler / heartbeat / compression
# ---------------------------------------------------------------------------

def test_straggler_detector_flags_slow_host():
    det = StragglerDetector(n_hosts=4, threshold=1.5)
    for step in range(20):
        for h in range(4):
            det.report(step, h, 1.0 if h != 2 else 3.0)
    assert det.stragglers(20) == [2]


def test_heartbeat_dead_hosts():
    hb = Heartbeat(n_hosts=3, timeout_s=10.0)
    now = max(hb.last_seen.values())
    hb.beat(0, t=now + 15)
    assert hb.dead_hosts(now=now + 20) == [1, 2]


def test_compression_error_feedback_unbiased():
    rng = np.random.default_rng(0)
    g_true = {"w": jnp.asarray(rng.normal(0, 1, (256,)), jnp.float32)}
    acc = jnp.zeros((256,))
    err = None
    for _ in range(64):
        q, s, err = compress_grads(g_true, err)
        acc = acc + decompress_grads(q, s)["w"]
    # time-averaged compressed gradient converges to the true gradient
    np.testing.assert_allclose(np.asarray(acc / 64),
                               np.asarray(g_true["w"]), atol=0.02)


def test_compressed_psum_under_shard_map():
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P

    g = {"w": jnp.asarray([1.0, -2.0, 3.0])}

    def f(gl):
        q, s, _ = compress_grads(gl)
        return compressed_psum(q, s, "data")

    out = jax.shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P(),
                        check_vma=False)(g)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=0.05)
