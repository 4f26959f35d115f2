"""Training driver: data pipeline -> model -> AdamW -> checkpoints.

On CPU this trains reduced configs (--smoke); on a TPU pod the same code
path shards params/optimizer over the production mesh via in_shardings.

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.training import optimizer as opt_lib


def make_train_step(model: Model, ocfg: opt_lib.AdamWConfig):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params, opt_state, metrics = opt_lib.apply_updates(
            params, grads, opt_state, ocfg)
        return params, opt_state, loss, metrics
    return jax.jit(train_step, donate_argnums=(0, 1))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    ocfg = opt_lib.AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps),
                               total_steps=args.steps)
    params = model.init(jax.random.key(args.seed))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params:,} "
          f"(analytic {cfg.param_count():,})")
    opt_state = opt_lib.init_state(params)
    step0 = 0
    if args.ckpt_dir and store.latest_step(args.ckpt_dir) is not None:
        step0 = store.latest_step(args.ckpt_dir)
        params = store.restore(args.ckpt_dir, params)
        opt_state = store.restore(args.ckpt_dir, opt_state,
                                  name="opt_state.npz")
        print(f"restored step {step0} from {args.ckpt_dir}")

    data = SyntheticTokens(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=args.seed))
    train_step = make_train_step(model, ocfg)

    losses = []
    t0 = time.time()
    for step in range(step0, args.steps):
        batch_np = next(data)
        batch = {"tokens": jnp.asarray(batch_np["tokens"])}
        if cfg.frontend != "none":
            batch["embeds"] = jnp.zeros(
                (args.batch, cfg.frontend_tokens, cfg.d_model), jnp.float32)
        params, opt_state, loss, metrics = train_step(params, opt_state,
                                                      batch)
        losses.append(float(loss))
        if (step + 1) % args.log_every == 0:
            rate = args.batch * args.seq * args.log_every / (
                time.time() - t0)
            print(f"step {step+1:5d} loss {float(loss):.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"tok/s {rate:,.0f}")
            t0 = time.time()
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            store.save(args.ckpt_dir, step + 1, params, opt_state,
                       extra={"loss": float(loss)})
    if losses and losses[-1] < losses[0]:
        print(f"loss improved {losses[0]:.4f} -> {losses[-1]:.4f}")
    else:
        print("WARNING: loss did not improve")


if __name__ == "__main__":
    main()
