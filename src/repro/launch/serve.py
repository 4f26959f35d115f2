"""Serving driver: continuous-batching engine + aging-aware core manager.

  PYTHONPATH=src python -m repro.launch.serve --arch minicpm3-4b \
      --requests 16 --policy proposed

The default model, minicpm3-4b at its published widths (4.26 B
parameters, bf16), fits one 16 GB chip with its KV cache. A model whose
weights and cache exceed the device's memory is refused with the
numbers before anything is allocated.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core import available_policies
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.models.config import ModelConfig
from repro.serving.engine import InferenceEngine, Request


def init_params(model: Model, seed: int):
    """Random weights, drawn inside one jit: the f32 draws fuse into
    their bf16 casts instead of sitting beside the weights on the
    device."""
    return jax.jit(model.init)(jax.random.key(seed))


def check_fits(model: Model, max_batch: int, max_len: int) -> None:
    """Refuse a model whose weights plus two copies of the KV cache (the
    decode step reads one and writes the next) exceed the device, instead
    of letting it die out of memory part-way through init."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:       # the backend reports no limit (host CPU)
        return
    params = model.abstract_params()
    cache = jax.eval_shape(
        lambda p, t: model.prefill(p, t, None, max_len=max_len)[1],
        params, jax.ShapeDtypeStruct((max_batch, 1), jnp.int32))

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    need = nbytes(params) + 2 * nbytes(cache)
    if need > limit:
        raise SystemExit(
            f"{model.cfg.name} needs {need / 2**30:.2f} GiB for weights "
            f"and KV cache (batch {max_batch} x {max_len} tokens) but "
            f"{jax.devices()[0].device_kind} holds {limit / 2**30:.2f} "
            f"GiB; pick a smaller --arch, --max-batch or --max-len")


def build_engine(cfg: ModelConfig, *, max_batch: int, max_len: int,
                 policy: str = "proposed", host_cores: int = 16,
                 seed: int = 0) -> InferenceEngine:
    model = Model(cfg)
    check_fits(model, max_batch, max_len)
    return InferenceEngine(
        model, init_params(model, seed), max_batch=max_batch,
        max_len=max_len, policy=policy, num_host_cores=host_cores)


def serve_requests(engine: InferenceEngine, prompts,
                   new_tokens: int) -> list[Request]:
    """Submit every prompt and run the engine until it drains; return
    the finished requests (`output` holds each one's tokens)."""
    reqs = []
    for prompt in prompts:
        engine.submit(list(prompt), max_new_tokens=new_tokens)
        reqs.append(engine.pending[-1])
    engine.run_until_drained()
    return reqs


def host_cpu_line(engine: InferenceEngine) -> str:
    rep = engine.host_cpu_report()
    return (f"host CPU [{rep['policy']}]: cores_active="
            f"{rep['active_cores']}/{engine.core_manager.num_cores} "
            f"cv={rep['cv']:.4f} "
            f"mean_freq_degradation={rep['mean_degradation']:.5f} "
            f"cpu_tasks={rep['assigns']}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm3-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--policy", default="proposed",
                    choices=list(available_policies()))
    ap.add_argument("--host-cores", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    engine = build_engine(cfg, max_batch=args.max_batch,
                          max_len=args.max_len, policy=args.policy,
                          host_cores=args.host_cores, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.requests, args.prompt_len))
    t0 = time.time()
    serve_requests(engine, prompts.tolist(), args.new_tokens)
    dt = time.time() - t0
    total_tokens = args.requests * args.new_tokens
    print(f"served {args.requests} requests / {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:,.1f} tok/s)")
    print(host_cpu_line(engine))


if __name__ == "__main__":
    main()
