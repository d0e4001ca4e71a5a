"""Training launcher.

Runs a real (CPU-scale) training job end-to-end with the full substrate:
sharded train step, deterministic data, fault-tolerant driver with
checkpoints.  The production meshes are exercised by ``dryrun.py``; this
driver runs on the host's real devices (``--devices`` host mesh).

Example (the ~100M end-to-end run of EXPERIMENTS.md):

  PYTHONPATH=src python -m repro.launch.train \
      --arch qwen3-32b --scale-down 256,8,512 --steps 300 \
      --batch 16 --seq 256 --ckpt-dir /tmp/ckpt --eval-every 50
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, RunConfig, get_arch
from repro.checkpoint.manager import CheckpointManager
from repro.data.synthetic import DataConfig, SyntheticDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.optim.schedules import linear_warmup_cosine
from repro.runtime.fault_tolerance import ResilientTrainer
from repro.runtime.train_loop import (init_train_state, make_eval_step,
                                      make_train_step)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--scale-down", default=None,
                    help="d_model,n_heads,vocab — reduced same-family config")
    ap.add_argument("--periods", type=int, default=2)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args()

    arch = get_arch(args.arch)
    if args.scale_down:
        d, h, v = (int(x) for x in args.scale_down.split(","))
        arch = arch.scaled_down(d_model=d, n_heads=h, vocab=v,
                                n_periods=args.periods)
    model = build_model(arch)
    run = RunConfig(dtype=args.dtype, attention_backend="naive",
                    scan_layers=True, remat=True,
                    microbatch=args.microbatch,
                    learning_rate=args.lr,
                    grad_compression=args.grad_compression, ssm_chunk=32)

    opt_cfg = AdamWConfig(
        learning_rate=linear_warmup_cosine(args.lr, args.steps // 10,
                                           args.steps),
        grad_clip=run.grad_clip, weight_decay=run.weight_decay)
    state = init_train_state(model, jax.random.PRNGKey(args.seed), run)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    print(f"arch={arch.name} params={n_params:,} devices={len(jax.devices())}")

    step_fn = jax.jit(make_train_step(model, run, opt_cfg))
    eval_fn = jax.jit(make_eval_step(model, run))

    ds = SyntheticDataset(DataConfig(vocab_size=arch.vocab_size,
                                     seq_len=args.seq,
                                     global_batch=args.batch,
                                     seed=args.seed))
    enc_shape = ((args.batch, arch.encoder_seq, arch.d_model)
                 if arch.encoder_layers else None)

    def batches(step: int) -> dict:
        b = {"tokens": jnp.asarray(ds.batch(step))}
        if enc_shape:
            b["encoder_input"] = jax.random.normal(
                jax.random.PRNGKey(step), enc_shape, jnp.float32)
        return b

    history: list[dict] = []
    t0 = time.time()

    def metrics_cb(step: int, m: dict) -> None:
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {m['loss']:.4f} "
                  f"gnorm {m.get('grad_norm', 0):.2f} "
                  f"({(time.time()-t0):.0f}s)")
        if args.eval_every and step and step % args.eval_every == 0:
            em = eval_fn(state_holder[0].params, batches(10_000 + step))
            em = {k: float(v) for k, v in em.items()}
            print(f"  eval @ {step}: {em}")
            history.append({"step": step, **m, **em})
        else:
            history.append({"step": step, **m})

    state_holder = [state]
    if args.ckpt_dir:
        trainer = ResilientTrainer(
            lambda s, b: _track(step_fn, state_holder, s, b),
            CheckpointManager(args.ckpt_dir, keep_n=2),
            checkpoint_every=args.ckpt_every)
        state, report = trainer.run(state, batches, args.steps,
                                    metrics_cb=metrics_cb)
        print(f"done: {report}")
    else:
        for step in range(args.steps):
            state, m = step_fn(state, batches(step))
            state_holder[0] = state
            metrics_cb(step, {k: float(v) for k, v in m.items()})

    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=1)


def _track(step_fn, holder, state, batch):
    out = step_fn(state, batch)
    holder[0] = out[0]
    return out


if __name__ == "__main__":
    main()
