"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch dplr-fwfm \
        --steps 300 --batch 4096 --ckpt-dir /tmp/ckpt [--resume]

Production posture demonstrated on this 1-device container (the same code
paths run under the production mesh — only the mesh constructor differs):

  * checkpoint/restart: async atomic checkpoints every --ckpt-every steps;
    on start, the newest VALID checkpoint is restored (corrupt/partial dirs
    skipped) and the data pipeline resumes at the restored step — the
    (seed, step) -> batch discipline makes the resumed loss trajectory
    bitwise-identical to an uninterrupted run (tested).
  * preemption simulation: --fail-at N kills the process mid-run; rerunning
    with --resume continues.
  * straggler mitigation: bounded prefetch + timeout re-serve (data/pipeline).
  * gradient compression: --compress-grads switches the DP all-reduce to
    int8 with error feedback (optim/compression).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import optim
from repro.checkpoint import CheckpointManager
from repro.configs import REGISTRY
from repro.data.pipeline import ShardedPipeline
from repro.data.synthetic_ctr import SyntheticCTR
from repro.launch.compile_cache import enable_compile_cache


def _recsys_module(name):
    from repro.launch.steps import _recsys_module as rm
    return rm(name)


def build_recsys_trainer(arch_name: str, cfg, batch_size: int, seed: int):
    mod = _recsys_module(arch_name)
    data = SyntheticCTR(cfg.layout, embed_dim=min(cfg.embed_dim, 8),
                        teacher_rank=2, seed=seed)

    def make_batch(step):
        b = data.batch(batch_size, step)
        extra = {}
        if arch_name == "bst":
            rng = np.random.default_rng((seed, 3, step))
            item_vocab = cfg.layout.fields[-1].vocab_size
            extra = {
                "hist_ids": rng.integers(0, item_vocab,
                                         (batch_size, cfg.seq_len)).astype(np.int32),
                "hist_mask": np.ones((batch_size, cfg.seq_len), np.float32),
            }
        if arch_name == "mind":
            rng = np.random.default_rng((seed, 3, step))
            item_vocab = cfg.layout.fields[-1].vocab_size
            return {
                "hist_ids": rng.integers(0, item_vocab,
                                         (batch_size, cfg.seq_len)).astype(np.int32),
                "hist_mask": np.ones((batch_size, cfg.seq_len), np.float32),
                "target_id": rng.integers(0, item_vocab, batch_size).astype(np.int32),
                "neg_ids": rng.integers(0, item_vocab,
                                        (batch_size, cfg.n_neg)).astype(np.int32),
            }
        return {**b, **extra}

    return mod, make_batch


def build_lm_trainer(arch_name: str, cfg, batch_size: int, seq: int, seed: int):
    from repro.models.transformer import model as tm

    def make_batch(step):
        rng = np.random.default_rng((seed, step))
        toks = (rng.zipf(1.2, (batch_size, seq + 1)) - 1) % cfg.vocab
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}

    class Mod:
        init = staticmethod(tm.init)
        loss = staticmethod(lambda p, c, b, take_fn=None: tm.lm_loss(p, c, b))

    return Mod, make_batch


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dplr-fwfm")
    ap.add_argument("--config", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--seq", type=int, default=128, help="LM sequence length")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--optimizer", default=None, choices=[None, "adagrad", "adamw"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="simulate preemption: hard-exit at this step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    spec = REGISTRY[args.arch]
    cfg = spec.make_smoke() if args.config == "smoke" else spec.make_config()

    if spec.family == "recsys":
        mod, make_batch = build_recsys_trainer(args.arch, cfg, args.batch,
                                               args.seed)
        default_opt = "adagrad"
    elif spec.family == "lm":
        mod, make_batch = build_lm_trainer(args.arch, cfg, args.batch,
                                           args.seq, args.seed)
        default_opt = "adamw"
    else:
        raise SystemExit("use examples/gnn_train.py for the gnn family")

    opt_name = args.optimizer or default_opt
    optimizer = optim.adagrad() if opt_name == "adagrad" else optim.adamw()

    params = mod.init(jax.random.PRNGKey(args.seed), cfg)
    opt_state = optimizer.init(params)
    start_step = 0

    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3)
        if args.resume:
            restored, step = mgr.restore({"params": params, "opt": opt_state})
            if restored is not None:
                params, opt_state = restored["params"], restored["opt"]
                start_step = step
                print(f"resumed from step {step}")

    @jax.jit
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(mod.loss)(params, cfg, batch)
        params, opt_state = optimizer.update(grads, opt_state, params, args.lr)
        return loss, params, opt_state

    pipe = ShardedPipeline(make_batch, prefetch=2).start(from_step=start_step)
    losses = []
    step_reached = start_step      # last step whose update actually landed
    last_saved = start_step if start_step else None
    t0 = time.time()
    try:
        for step in range(start_step, args.steps):
            _, batch = pipe.get()
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            loss, params, opt_state = train_step(params, opt_state, batch)
            losses.append(float(loss))
            step_reached = step + 1
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save({"params": params, "opt": opt_state}, step + 1)
                last_saved = step + 1
            if args.fail_at is not None and step + 1 == args.fail_at:
                print(f"[simulated preemption at step {step + 1}]", flush=True)
                import os
                os._exit(42)
            if not args.quiet and (step + 1) % args.log_every == 0:
                rate = (step + 1 - start_step) / (time.time() - t0)
                print(f"step {step+1:5d} loss {float(loss):.5f} "
                      f"({rate:.1f} steps/s)", flush=True)
    finally:
        pipe.stop()
        # save at the step the loop actually REACHED — labeling a partial
        # run (pipeline error, KeyboardInterrupt) as args.steps would make
        # --resume restore "past the end" and silently skip the remaining
        # training.  Skip when nothing new ran or this step is already on
        # disk.
        if mgr and step_reached > start_step and step_reached != last_saved:
            mgr.save({"params": params, "opt": opt_state}, step_reached)
        if mgr:
            mgr.wait()
    if losses:
        print(f"final loss: {losses[-1]:.5f}")
    else:
        print(f"no steps to run: resumed at step {start_step} of "
              f"{args.steps}")
    return losses


if __name__ == "__main__":
    main()
