import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, proving the distribution config is coherent
without hardware, and extract roofline terms from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import dataclasses
import importlib
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import ALIASES, all_arch_names
from repro.distributed import hlo_analysis, sharding as shd
from repro.launch import mesh as mesh_lib
from repro.models import INPUT_SHAPES, Model
from repro.models.config import InputShape, ModelConfig
from repro.training import optimizer as opt_lib


def resolve_config(arch: str, shape_name: str,
                   variant: str = "baseline") -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{ALIASES[arch]}")
    cfg = mod.LONG_CONTEXT if shape_name == "long_500k" else mod.FULL
    if variant == "optimized":
        # beyond-paper §Perf knobs (see EXPERIMENTS.md): chunked online-
        # softmax attention, sequence-parallel residuals, ZeRO-1
        # optimizer sharding.
        cfg = dataclasses.replace(cfg, attn_chunk=2048,
                                  seq_parallel=True, zero1=True,
                                  pin_cache_sharding=True, swa_ring=True)
    return cfg


def input_specs(arch: str, shape_name: str, mesh=None, cfg=None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this step —
    weak-type-correct, shardable, no device allocation."""
    cfg = cfg or resolve_config(arch, shape_name)
    shape = INPUT_SHAPES[shape_name]
    mesh = mesh or mesh_lib.make_production_mesh()
    model = Model(cfg)
    b, s = shape.global_batch, shape.seq_len
    b_ax = shd.batch_axes(mesh)
    tok_spec = shd.P(shd.dim_spec(mesh, b, b_ax), None)
    specs: dict = {}

    n_front = front_len(cfg, shape)
    if shape.kind == "train":
        s_text = s - (n_front if cfg.family == "vlm" else 0)
        specs["batch"] = {"tokens": jax.ShapeDtypeStruct(
            (b, s_text), jnp.int32,
            sharding=shd.NamedSharding(mesh, tok_spec))}
        if n_front:
            e_spec = shd.P(shd.dim_spec(mesh, b, b_ax), None, None)
            specs["batch"]["embeds"] = jax.ShapeDtypeStruct(
                (b, n_front, cfg.d_model), jnp.float32,
                sharding=shd.NamedSharding(mesh, e_spec))
    elif shape.kind == "prefill":
        s_text = s - (n_front if cfg.family == "vlm" else 0)
        specs["tokens"] = jax.ShapeDtypeStruct(
            (b, s_text), jnp.int32, sharding=shd.NamedSharding(mesh, tok_spec))
        if n_front:
            e_spec = shd.P(shd.dim_spec(mesh, b, b_ax), None, None)
            specs["embeds"] = jax.ShapeDtypeStruct(
                (b, n_front, cfg.d_model), jnp.float32,
                sharding=shd.NamedSharding(mesh, e_spec))
    else:  # decode: one token + cache of seq_len
        specs["token"] = jax.ShapeDtypeStruct(
            (b, 1), jnp.int32, sharding=shd.NamedSharding(mesh, tok_spec))
        cache_abs = abstract_cache(model, cfg, shape)
        cache_spec = shd.cache_specs(cfg, cache_abs, mesh)
        specs["cache"] = shd.with_sharding(mesh, cache_abs, cache_spec)
    return specs


def front_len(cfg: ModelConfig, shape: InputShape) -> int:
    if cfg.frontend == "none":
        return 0
    if cfg.family == "vlm":
        return cfg.frontend_tokens
    # audio: encoder frames scale with the sequence, bounded for decode
    if shape.kind == "decode":
        return min(shape.seq_len // 4, 4096)
    return shape.seq_len // 4


def abstract_cache(model: Model, cfg: ModelConfig, shape: InputShape):
    """Abstract cache pytree for a decode step (ShapeDtypeStructs)."""
    b, s = shape.global_batch, shape.seq_len
    n_front = front_len(cfg, shape)
    # use eval_shape over the real prefill to derive exact cache shapes;
    # VLM prompts embed n_front patches inside the seq_len budget
    s_text = s - 1 - (n_front if cfg.family == "vlm" else 0)
    tok = jax.ShapeDtypeStruct((b, s_text), jnp.int32)
    emb = (jax.ShapeDtypeStruct((b, n_front, cfg.d_model), jnp.float32)
           if n_front else None)
    params_abs = model.abstract_params()

    def fn(p, t, e):
        _, cache = model.prefill(p, t, e, max_len=s)
        return cache

    return jax.eval_shape(fn, params_abs, tok, emb)


def _zero1_spec(abs_leaf, spec, mesh):
    """ZeRO-1 (§Perf): additionally shard optimizer moments over the
    data axis on the first dim the model axis doesn't already occupy."""
    dax = "data"
    if dax not in mesh.axis_names:
        return spec
    size = mesh.shape[dax]
    dims = list(spec)
    for i, (d, ax) in enumerate(zip(abs_leaf.shape, dims)):
        if ax is None and d % size == 0:
            dims[i] = dax
            break
    return shd.P(*dims)


def build_step(arch: str, shape_name: str, mesh, cfg=None):
    """Returns (step_fn, kwargs of sharded ShapeDtypeStructs)."""
    cfg = cfg or resolve_config(arch, shape_name)
    shape = INPUT_SHAPES[shape_name]
    model = Model(cfg)
    params_abs = model.abstract_params()
    pspecs = shd.param_specs(params_abs, mesh)
    params_in = shd.with_sharding(mesh, params_abs, pspecs)
    specs = input_specs(arch, shape_name, mesh, cfg)

    if shape.kind == "train":
        ocfg = opt_lib.AdamWConfig()
        opt_abs = jax.eval_shape(opt_lib.init_state, params_abs)
        mspec = (jax.tree.map(lambda a, s: _zero1_spec(a, s, mesh),
                              opt_abs.mu, pspecs,
                              is_leaf=lambda x: isinstance(x, shd.P))
                 if cfg.zero1 else jax.tree.map(lambda s: s, pspecs))
        ospecs = opt_lib.AdamWState(
            step=shd.P(),
            mu=mspec,
            nu=jax.tree.map(lambda s: s, mspec,
                            is_leaf=lambda x: isinstance(x, shd.P)))
        opt_in = shd.with_sharding(mesh, opt_abs, ospecs)

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
            params, opt_state, metrics = opt_lib.apply_updates(
                params, grads, opt_state, ocfg)
            return loss, params, opt_state, metrics

        return train_step, (params_in, opt_in, specs["batch"])

    if shape.kind == "prefill":
        def prefill_step(params, tokens, embeds=None):
            return model.prefill(params, tokens, embeds,
                                 max_len=shape.seq_len)
        args = (params_in, specs["tokens"])
        if "embeds" in specs:
            return (lambda p, t, e: prefill_step(p, t, e)), (
                params_in, specs["tokens"], specs["embeds"])
        return (lambda p, t: prefill_step(p, t)), args

    def serve_step(params, cache, token):
        return model.decode_step(params, cache, token)

    return serve_step, (params_in, specs["cache"], specs["token"])


def _measure(arch, shape_name, mesh, cfg):
    """Compile a fully-unrolled variant and return per-device cost dict."""
    cfg_u = dataclasses.replace(cfg, scan_unroll=1_000_000)
    step, args = build_step(arch, shape_name, mesh, cfg_u)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step).lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    coll = hlo_analysis.collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll}


def _coll_comb(a, b, fa=1.0, fb=1.0):
    keys = set(a) | set(b)
    return {k: max(0.0, fa * a.get(k, 0) + fb * b.get(k, 0)) for k in keys}


def _extrapolated_cost(arch, shape_name, mesh, cfg) -> dict:
    """Layer-accurate cost accounting without compiling the full depth:
    compile fully-UNROLLED reduced-depth variants and extrapolate the
    per-layer marginal cost linearly (exact for layer-homogeneous stacks;
    XLA cost_analysis counts a scan body once, so the deployment-form
    compile alone undercounts ~L x)."""
    if cfg.is_encdec:
        base = _measure(arch, shape_name, mesh, dataclasses.replace(
            cfg, num_layers=2, encoder_layers=2))
        d_enc = _measure(arch, shape_name, mesh, dataclasses.replace(
            cfg, num_layers=2, encoder_layers=4))
        d_dec = _measure(arch, shape_name, mesh, dataclasses.replace(
            cfg, num_layers=4, encoder_layers=2))
        n_e, n_d = cfg.encoder_layers - 2, cfg.num_layers - 2
        out = {}
        for k in ("flops", "bytes"):
            se = (d_enc[k] - base[k]) / 2
            sd = (d_dec[k] - base[k]) / 2
            out[k] = base[k] + se * n_e + sd * n_d
        ce = _coll_comb(d_enc["coll"], base["coll"], 0.5, -0.5)
        cd = _coll_comb(d_dec["coll"], base["coll"], 0.5, -0.5)
        coll = _coll_comb(base["coll"], _coll_comb(ce, cd, n_e, n_d))
        out["coll"] = coll
        return out
    if cfg.family == "hybrid":
        per, rem = cfg.hybrid_period, cfg.hybrid_remainder
        l1, l2 = per + rem, 2 * per + rem          # 1 and 2 groups
        steps = float(cfg.num_hybrid_groups - 1)   # extra groups beyond l1
    else:
        l1, l2 = 2, 4
        steps = (cfg.num_layers - l1) / (l2 - l1)  # extra (l2-l1) blocks
    c1 = _measure(arch, shape_name, mesh,
                  dataclasses.replace(cfg, num_layers=l1))
    c2 = _measure(arch, shape_name, mesh,
                  dataclasses.replace(cfg, num_layers=l2))
    out = {k: c1[k] + (c2[k] - c1[k]) * steps for k in ("flops", "bytes")}
    dcoll = _coll_comb(c2["coll"], c1["coll"], 1.0, -1.0)
    out["coll"] = _coll_comb(c1["coll"], dcoll, 1.0, steps)
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool,
            unrolled_cost: bool = True, variant: str = "baseline") -> dict:
    """Dry-run one (arch x shape x mesh).

    Two compiles: (1) the deployment form (lax.scan over layers) proves the
    sharding lowers and yields the memory analysis; (2) a fully-unrolled
    form yields layer-accurate FLOP / bytes / collective accounting
    (cost_analysis counts a scan body once, not trip-count times).
    The unrolled pass runs on the single-pod mesh only (roofline scope).
    """
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    cfg = resolve_config(arch, shape_name, variant)
    t0 = time.time()
    step_fn, args = build_step(arch, shape_name, mesh, cfg)
    with jax.set_mesh(mesh):
        lowered = jax.jit(step_fn).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = hlo_analysis.collective_bytes(hlo)
    t_unroll = 0.0
    if unrolled_cost and not multi_pod:
        tu = time.time()
        del variant  # cfg already carries the variant knobs
        est = _extrapolated_cost(arch, shape_name, mesh, cfg)
        ca = {"flops": est["flops"], "bytes accessed": est["bytes"]}
        coll = est["coll"]
        t_unroll = time.time() - tu

    shape = INPUT_SHAPES[shape_name]
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    n_active = cfg.active_param_count()
    flops_dev = float(ca.get("flops", 0.0))
    bytes_dev = float(ca.get("bytes accessed", 0.0))
    mult = 3 if shape.kind == "train" else 1  # fwd+bwd ~ 3x fwd
    model_flops = 2.0 * n_active * tokens * mult

    rec = {
        "arch": arch,
        "shape": shape_name,
        "variant": ("optimized" if (cfg.attn_chunk or cfg.seq_parallel
                                    or cfg.zero1)
                    else "baseline"),
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "unrolled_compile_s": round(t_unroll, 2),
        "per_device": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes": (ma.argument_size_in_bytes
                           + ma.temp_size_in_bytes),
            "flops": flops_dev,
            "bytes_accessed": bytes_dev,
            "collective_bytes": coll.get("total", 0),
        },
        "collectives": {k: v for k, v in coll.items()
                        if k not in ("total",)},
        "roofline_s": {
            "compute": flops_dev / mesh_lib.PEAK_BF16_FLOPS,
            "memory": bytes_dev / mesh_lib.HBM_BW,
            "collective": coll.get("total", 0) / mesh_lib.ICI_LINK_BW,
        },
        "model_flops_global": model_flops,
        "useful_flops_ratio": (model_flops / (flops_dev * chips)
                               if flops_dev else 0.0),
        "params": cfg.param_count(),
        "active_params": n_active,
    }
    terms = rec["roofline_s"]
    rec["bottleneck"] = max(terms, key=terms.get)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--out", default=None, help="JSON output directory")
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "optimized"])
    args = ap.parse_args()

    archs = all_arch_names() if args.all or not args.arch else [args.arch]
    shapes = (list(INPUT_SHAPES) if args.all or not args.shape
              else [args.shape])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                tag = f"{arch} x {shape_name} x {'2x16x16' if multi else '16x16'}"
                try:
                    rec = run_one(arch, shape_name, multi,
                                  variant=args.variant)
                except Exception as e:  # noqa: BLE001 — report and continue
                    failures.append((tag, repr(e)))
                    print(f"FAIL  {tag}: {e}")
                    continue
                pd = rec["per_device"]
                print(f"OK    {tag}: compile={rec['compile_s']}s "
                      f"peak={pd['peak_bytes']/1e9:.2f}GB "
                      f"flops/dev={pd['flops']:.3e} "
                      f"coll/dev={pd['collective_bytes']/1e6:.1f}MB "
                      f"bottleneck={rec['bottleneck']}")
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    suffix = ("_opt" if args.variant == "optimized"
                              else "")
                    fn = (f"{arch}_{shape_name}_{rec['mesh']}{suffix}.json"
                          .replace("/", "_"))
                    with open(os.path.join(args.out, fn), "w") as f:
                        json.dump(rec, f, indent=1)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(" ", tag, err)
        raise SystemExit(1)
    print("\nALL DRY-RUNS PASSED")


if __name__ == "__main__":
    main()
