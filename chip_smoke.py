"""Smoke run of both accelerator paths on one TPU chip, in one process.

    python chip_smoke.py

Phase 1, the fleet engine: 200 machines (45 prompt + 155 token) x 1
simulated hour at 545 rps, through `run_experiment` on the jax backend
with the compiled Pallas aging kernel, checked against the float64 numpy
backend on the same config.

Phase 2, the serving engine: minicpm3-4b at its published widths and
depth (random bf16 weights from a seed) behind `InferenceEngine`, as
`repro.launch.serve` drives it: 8 requests of 1,024-token prompts and 32
new tokens each (batch 8, 2,048-token cache), plus a check that decoding
after a prefill agrees with prefilling the extended sequence.

Every line but the last reports a phase. The last line is
`{"ok": true, "device": {...}}`, printed only when both phases pass.
With no TPU, or when any check fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Fleet agreement bounds, jax (float32) vs numpy (float64): those of
# tests/test_fleetsim.py::TestBackendAgreement.
FLEET_TOL = {"completed": 0.01, "mean_latency_s": 0.01,
             "mean_degradation_p50": 0.02, "fleet_yearly_kgco2eq": 0.05}
# Decode-after-prefill vs prefill of the extended sequence, as relative L2
# error of the logits. The two paths round bf16 activations in different
# orders through every layer, and the error grows with depth: at the
# published widths it measured 1.0e-2 with 4 layers and 2.1e-2 with 16
# on a host CPU (3e-6 and 7e-6 in float32, so the decode path itself is
# exact), and 0.068-0.070 with all 62 layers on a TPU v5e over three
# prompts. A decode that overwrites the last filled cache slot instead of
# appending measured 0.37-0.39 on the same chip. The bound sits between.
LOGITS_REL_L2 = 0.1

ARCH, N_REQUESTS, PROMPT_LEN, NEW_TOKENS = "minicpm3-4b", 8, 1024, 32
MAX_BATCH, MAX_LEN, SEED = 8, 2048, 0


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def tpu_device() -> dict:
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"needs a TPU; JAX found {devs[0].platform!r}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def fleet_phase() -> None:
    from repro.sim import ExperimentConfig
    from repro.sim.runner import run_experiment
    from repro.telemetry import TelemetryHub

    cfg = ExperimentConfig(n_prompt=45, n_token=155, rate_rps=545.0,
                           duration_s=3600.0)
    hub = TelemetryHub()
    t0 = time.perf_counter()
    jx = run_experiment(cfg.with_engine("fleet", backend="jax"),
                        telemetry=hub)
    wall = time.perf_counter() - t0
    ev = next(e for e in hub.events if e["kind"] == "engine")
    compile_s = hub.gauge("phase/jax_compile_wall_s").value
    log(f"fleet: backend={ev['backend']} use_kernel={ev['use_kernel']} "
        f"interpret={ev['interpret']}")
    check(ev["backend"] == "jax" and ev["use_kernel"]
          and not ev["interpret"],
          "the fleet engine must settle aging through the compiled "
          "Pallas kernel on TPU")
    log(f"fleet: jax wall_s={wall:.3f} (compile_s={compile_s:.3f}, "
        f"rest_s={wall - compile_s:.3f}) for 200 machines x 3600 s")

    t0 = time.perf_counter()
    ref = run_experiment(cfg.with_engine("fleet", backend="numpy"))
    log(f"fleet: numpy f64 reference wall_s={time.perf_counter() - t0:.3f}")
    got = {"completed": (jx.completed, ref.completed),
           "mean_latency_s": (jx.mean_latency_s, ref.mean_latency_s),
           "mean_degradation_p50": (jx.mean_degradation_percentiles[50],
                                    ref.mean_degradation_percentiles[50]),
           "fleet_yearly_kgco2eq": (jx.fleet_yearly_kgco2eq,
                                    ref.fleet_yearly_kgco2eq)}
    bad = []
    for name, (a, b) in got.items():
        d = rel(a, b)
        log(f"fleet: {name} jax={a!r} numpy={b!r} rel={d:.3e} "
            f"(bound {FLEET_TOL[name]})")
        if not d < FLEET_TOL[name]:
            bad.append(name)
    check(not bad, f"fleet jax vs numpy outside bounds: {bad}")


def serving_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import build_engine, host_cpu_line, serve_requests

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    engine = build_engine(cfg, max_batch=MAX_BATCH, max_len=MAX_LEN,
                          seed=SEED)
    jax.block_until_ready(engine.params)
    log(f"serve: {ARCH} full config, {cfg.param_count():,} params, "
        f"init_s={time.perf_counter() - t0:.3f}")

    rng = np.random.default_rng(SEED)
    prompts = rng.integers(0, cfg.vocab_size, (N_REQUESTS, PROMPT_LEN))
    for run in ("cold", "warm"):     # cold includes the compiles
        t0 = time.perf_counter()
        reqs = serve_requests(engine, prompts.tolist(), NEW_TOKENS)
        wall = time.perf_counter() - t0
        n_tok = sum(len(r.output) for r in reqs)
        log(f"serve[{run}]: {len(reqs)} requests / {n_tok} tokens in "
            f"wall_s={wall:.3f}")
        check(all(r.done and len(r.output) == NEW_TOKENS for r in reqs),
              f"every request must complete with {NEW_TOKENS} tokens")
    log(host_cpu_line(engine))

    # Decode one token after a prefill; prefill the extended sequence;
    # the two last-position logits must agree.
    model, params, v = engine.model, engine.params, cfg.vocab_size
    prompt = jnp.asarray(prompts[:1], jnp.int32)
    logits, cache = engine._prefill(params, prompt, MAX_LEN)
    nxt = jnp.argmax(logits[:, -1, :v], -1).astype(jnp.int32)[:, None]
    dec, _ = jax.jit(model.decode_step)(params, cache, nxt)
    full, _ = engine._prefill(params, jnp.concatenate([prompt, nxt], 1),
                              MAX_LEN)
    a = np.asarray(dec[0, 0, :v], np.float32)
    b = np.asarray(full[0, -1, :v], np.float32)
    check(bool(np.isfinite(a).all() and np.isfinite(b).all()),
          "logits must be finite")
    err = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    log(f"serve: decode-vs-prefill logits rel_l2={err:.4g} "
        f"(bound {LOGITS_REL_L2}) max_abs_diff={np.abs(a - b).max():.4g} "
        f"max|logit|={np.abs(b).max():.4g} "
        f"argmax_equal={int(a.argmax()) == int(b.argmax())}")
    check(err < LOGITS_REL_L2,
          "decode logits disagree with prefill of the extended sequence")


def main() -> None:
    device = tpu_device()
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    fleet_phase()
    serving_phase()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
