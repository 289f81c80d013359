"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 300 --batch 8 --seq 256 --ckpt-dir /tmp/run1 \
        --ckpt-every 50 [--simulate-failures 120,220] [--mesh 1x1]

Responsibilities beyond the bare train loop, per the large-scale brief:

* checkpoint/restart: periodic atomic checkpoints; on ANY failure the
  driver restores the latest committed step and resumes (the data pipeline
  is a pure function of step, so the token stream replays exactly);
* straggler detection: per-host step-time tracking (simulated hosts on
  CPU), flags logged;
* elastic restart: if the mesh shape changed between runs, params/opt are
  re-sharded onto the new mesh at restore time;
* SILVIA serving flows live in launch/serve.py; training is bf16.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import checkpoint, configs
from repro.data import DataConfig, make_stream
from repro.distributed.fault import (FailureInjector, RestartPolicy,
                                     SimulatedFailure, StragglerDetector)
from repro.distributed.sharding import param_pspecs, to_shardings
from repro.launch import xla_setup
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.optim import AdamWConfig, adamw_init
from repro.training import TrainConfig, make_train_step


def build(cfg, tcfg, mesh, seq, rng):
    params = lm.init_params(rng, cfg, max_seq=seq + 8)
    params = jax.device_put(params,
                            to_shardings(param_pspecs(params, mesh, cfg), mesh))
    opt = adamw_init(params, tcfg.optimizer)
    opt = jax.device_put(opt, to_shardings(param_pspecs(opt, mesh, cfg), mesh))
    step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
    return params, opt, step_fn


def run(args) -> dict:
    cfg = configs.get_reduced_config(args.arch) if args.reduced \
        else configs.get_config(args.arch)
    mesh_shape = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("data", "model")[:len(mesh_shape)] if len(mesh_shape) <= 2 else \
        ("pod", "data", "model")
    mesh = make_mesh(mesh_shape, axes)
    tcfg = TrainConfig(
        microbatches=args.microbatches,
        optimizer=AdamWConfig(lr=args.lr),
        schedule_warmup=min(50, args.steps // 10 + 1),
        schedule_total=args.steps)
    rng = jax.random.PRNGKey(args.seed)
    stream = make_stream(DataConfig(args.seq, args.batch, cfg.vocab,
                                    seed=args.seed))
    injector = FailureInjector(tuple(
        int(s) for s in args.simulate_failures.split(",") if s))
    policy = RestartPolicy(max_restarts=args.max_restarts)
    detector = StragglerDetector(n_hosts=args.sim_hosts)

    history: list[float] = []
    n_restores = 0
    while True:
        try:
            with mesh:
                params, opt, step_fn = build(cfg, tcfg, mesh, args.seq, rng)
                restored, start = checkpoint.restore_checkpoint(
                    args.ckpt_dir, {"params": params, "opt": opt})
                if restored is not None:
                    params, opt = restored["params"], restored["opt"]
                    n_restores += 1
                    print(f"[restore] resumed from step {start}")
                step0 = (start or 0)
                for step in range(step0, args.steps):
                    t0 = time.time()
                    injector.check(step)
                    batch = {"tokens": jnp.asarray(stream.batch_at(step))}
                    params, opt, metrics = step_fn(params, opt, batch)
                    dt = time.time() - t0
                    detector.report(step, step % args.sim_hosts, dt)
                    if step % args.log_every == 0:
                        loss = float(metrics["loss"])
                        history.append(loss)
                        strag = detector.stragglers(step)
                        print(f"step {step:5d} loss {loss:.4f} "
                              f"({dt*1e3:.0f} ms)"
                              + (f" stragglers={strag}" if strag else ""))
                    if args.ckpt_every and step and \
                            step % args.ckpt_every == 0:
                        checkpoint.save_checkpoint(
                            args.ckpt_dir, step,
                            {"params": params, "opt": opt})
                        # committed progress: next incident backs off from
                        # the base again instead of the escalated streak
                        policy.reset()
                if args.ckpt_every:
                    checkpoint.save_checkpoint(
                        args.ckpt_dir, args.steps,
                        {"params": params, "opt": opt})
                final = float(metrics["loss"])
                print(f"done: final loss {final:.4f}, "
                      f"restores={n_restores}, "
                      f"straggler flags={len(detector.flagged)}")
                return {"final_loss": final, "restores": n_restores,
                        "history": history}
        except SimulatedFailure as e:
            print(f"[failure] {e}")
            if not policy.should_restart(e):
                raise
            continue


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--simulate-failures", default="")
    ap.add_argument("--max-restarts", type=int, default=10)
    ap.add_argument("--sim-hosts", type=int, default=4)
    args = ap.parse_args()
    xla_setup.configure()
    run(args)


if __name__ == "__main__":
    main()
