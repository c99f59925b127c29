"""End-to-end training driver.

Runs any assigned architecture (full or --reduced) with the deterministic
data pipeline, AdamW + warmup-cosine, microbatch gradient accumulation,
atomic async checkpoints, and automatic --resume.  On this CPU container it
drives reduced configs (examples/train_lm.py trains a ~100M model); on real
hardware the same driver jits under the production mesh.

  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.registry import get_config
from repro.data import DataConfig, TokenPipeline
from repro.launch import steps as S
from repro.optim import AdamWConfig, linear_warmup_cosine


def make_accum_train_step(cfg, opt_cfg, schedule, accum: int):
    """Gradient accumulation over `accum` microbatches inside one jit."""
    model, base_step = S.make_train_step(cfg, opt_cfg, schedule)
    if accum <= 1:
        return model, base_step
    from repro.models import LM
    from repro.optim import adamw_update

    def train_step(state, batch):
        def loss_fn(p, mb):
            return model.loss(S.cast_params(p, cfg.compute_dtype), mb)

        def micro(carry, mb):
            gsum, lsum = carry
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                state["params"], mb)
            return (jax.tree.map(jnp.add, gsum, g), lsum + loss), None

        micro_batches = jax.tree.map(
            lambda a: a.reshape((accum, a.shape[0] // accum) + a.shape[1:]),
            batch)
        zeros = jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), state["params"])
        (gsum, lsum), _ = jax.lax.scan(micro, (zeros, 0.0), micro_batches)
        grads = jax.tree.map(lambda g: g / accum, gsum)
        lr = schedule(state["opt"]["step"]) if schedule else opt_cfg.lr
        new_p, new_opt, om = adamw_update(grads, state["opt"],
                                          state["params"], opt_cfg, lr)
        return ({"params": new_p, "opt": new_opt},
                {"loss": lsum / accum, "lr": lr, **om})

    return model, train_step


def train(arch: str, steps: int, batch: int, seq: int, *, reduced=True,
          lr=3e-4, warmup=20, accum=1, ckpt_dir: Optional[str] = None,
          ckpt_every=50, resume=False, seed=0, log_every=10,
          log=print) -> float:
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    opt_cfg = AdamWConfig(lr=lr)
    schedule = linear_warmup_cosine(lr, warmup, steps)
    model, step_fn = make_accum_train_step(cfg, opt_cfg, schedule, accum)
    step_fn = jax.jit(step_fn, donate_argnums=0)

    state = S.init_train_state(cfg, jax.random.key(seed))
    leaves = jax.tree.leaves(state["params"])
    platforms = sorted({d.platform for a in leaves for d in a.devices()})
    log(f"[train] {cfg.name}: {sum(a.size for a in leaves) / 1e6:.1f}M "
        f"params on {','.join(platforms)}")
    start = 0
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if resume and mgr and mgr.latest_step() is not None:
        state, meta = mgr.restore(state)
        start = int(meta["step"]) + 1
        log(f"[train] resumed from step {start - 1}")

    pipe = TokenPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch,
        seed=seed)).start_prefetch(from_step=start)
    last_loss = float("nan")
    t0 = time.time()
    try:
        for t in range(start, steps):
            _, np_batch = pipe.next_prefetched()
            batch_j = jax.tree.map(jnp.asarray, np_batch)
            state, metrics = step_fn(state, batch_j)
            if t % log_every == 0 or t == steps - 1:
                last_loss = float(metrics["loss"])
                rate = (t - start + 1) / (time.time() - t0)
                log(f"[train] step={t} loss={last_loss:.4f} "
                    f"lr={float(metrics['lr']):.2e} "
                    f"gnorm={float(metrics['grad_norm']):.2f} "
                    f"({rate:.2f} it/s)")
                if not np.isfinite(last_loss):
                    raise FloatingPointError(f"loss diverged at step {t}")
            if mgr and ckpt_every and t and t % ckpt_every == 0:
                mgr.save(t, state)
        last_loss = float(metrics["loss"])
    finally:
        pipe.stop_prefetch()
        if mgr:
            mgr.save(steps - 1, state)
            mgr.wait()
    return last_loss


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    loss = train(args.arch, args.steps, args.batch, args.seq,
                 reduced=args.reduced, lr=args.lr, accum=args.accum,
                 ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                 resume=args.resume, seed=args.seed)
    print(f"final loss: {loss:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
