"""Runs fleet-simulation cells: whole experiments through the
program's entry `repro.sim.runner.run_experiment` on the jax backend.

Experiment i of a run takes the seed `experiment_seed(--seed, i)`, so
the window runs a sweep over seeds, as a user's study does. Set-up
builds the configuration and runs one experiment with an empty trace
on a fixed seed of its own (`WARMUP_SEED`) through
`run_fleet_experiment(cfg, requests=[])`: that warms every program
whose shapes the cell uses, and a run after the checkout's first finds
them in the persistent cache. The program closes its macro scan over
the seed's per-core frequencies, so each experiment of the window
compiles its own scan, as it does for a user; those compiles are not
written to the persistent cache, so that every run pays them alike.
Each experiment generates its trace, bins it, compiles and runs its
scan on the device and collects the result. The experiment running
when the window's time is up runs to its end (a window holds at least
one experiment), and the rate is taken over every experiment started:
simulated machine-seconds over the wall seconds they took together.

The traced run (`--trace 1`) profiles experiment 0, split at the
program's two public seams: `get_scenario(...).generate(...)` under the
span `bench.generate`, then `run_fleet_experiment(cfg, requests=...)`
under `bench.fleet_run`.

`correct`: once the window has closed, `CHECKED` experiments drawn
from the seed (all, where the window held fewer) are each run through
the float64 reference (`bench/reference/fleet.py`) on the trace that
the benchmark's own generator draws for that experiment's seed, and
compared with it, number by number, as relative errors. The traced run
also compares the program's trace with the benchmark's, request by
request.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

from bench import generate

LIMITS_DIR = Path(__file__).resolve().parents[1] / "limits"
WARMUP_SEED = 0
CHECKED = 3
COMPARED = ("completed", "mean_latency_s", "mean_degradation_p50",
            "mean_degradation_p99", "fleet_yearly_kgco2eq")


def experiment_config(config: dict, traffic: dict, seed: int):
    from repro.sim import ExperimentConfig
    cfg = ExperimentConfig(
        policy=config["policy"], num_cores=config["num_cores"],
        idling_period_s=config["idling_period_s"],
        n_prompt=config["n_prompt"], n_token=config["n_token"],
        router=config["router"], carbon_model=config["carbon_model"],
        power_model=config["power_model"], fleet=config["fleet"],
        scenario=traffic["scenario"],
        rate_rps=float(traffic["arrival"]["rate_rps"]),
        duration_s=float(config["duration_s"]), seed=seed)
    return cfg.with_engine("fleet", backend=config["backend"],
                           dt_s=config["dt_s"])


def experiment_seed(seed: int, i: int) -> int:
    """The seed of experiment `i` of a run: a hash of the run's seed
    and `i`."""
    import numpy as np
    state = np.random.SeedSequence([seed % 2**64, i]).generate_state(1)
    return int(state[0])


def scalars(result) -> dict:
    return {"completed": result.completed,
            "mean_latency_s": result.mean_latency_s,
            "mean_degradation_p50": result.mean_degradation_percentiles[50],
            "mean_degradation_p99": result.mean_degradation_percentiles[99],
            "fleet_yearly_kgco2eq": result.fleet_yearly_kgco2eq}


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-300)


def compare(pairs: list[tuple[dict, dict]], limits: dict) -> list[dict]:
    """The worst relative error of each compared number over the
    (program, reference) pairs, beside its limit."""
    checks = []
    for name in COMPARED:
        worst = max((rel(r[name], ref[name]) for r, ref in pairs),
                    default=float("inf"))
        checks.append({"name": f"{name}_rel", "value": worst,
                       "limit": limits[f"{name}_rel"]})
    return checks


def reference(config: dict, traffic: dict, seed: int):
    """The reference's scalars for the experiment on `seed`, and the
    benchmark's own draw of its trace."""
    from bench.reference import fleet as fleet_ref
    trace = generate.sequential(traffic, float(config["duration_s"]), seed)
    return fleet_ref.simulate(config, float(traffic["arrival"]["rate_rps"]),
                              trace, seed), trace


def run(spec: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float | None = None) -> dict:
    import jax
    import numpy as np
    from repro.sim.fleetsim import run_fleet_experiment
    from repro.sim.runner import run_experiment

    from bench.run import memory_peak_bytes

    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    limits = json.loads((LIMITS_DIR / f"{cell['name']}.json").read_text())
    machine_s = ((config["n_prompt"] + config["n_token"])
                 * float(config["duration_s"]))

    t0 = time.perf_counter() if t_start is None else t_start
    run_fleet_experiment(experiment_config(config, traffic, WARMUP_SEED),
                         requests=[])
    # the window's per-experiment compiles stay out of the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float("inf"))
    setup_s = time.perf_counter() - t0
    print(f"fleet: set-up {setup_s:.3f} s", flush=True)

    results, walls, failed = [], [], 0
    observations = None
    if trace:
        results, observations, failed = _traced_experiment(
            experiment_config(config, traffic, experiment_seed(seed, 0)),
            traffic)
        walls.append(observations["experiment_wall_s"])
    else:
        start = time.perf_counter()
        while not walls or time.perf_counter() - start < seconds:
            exp_seed = experiment_seed(seed, len(walls))
            t1 = time.perf_counter()
            try:
                results.append((exp_seed, scalars(run_experiment(
                    experiment_config(config, traffic, exp_seed)))))
            except Exception as exc:             # counted, not hidden
                failed += 1
                print(f"fleet: experiment failed: {exc!r}", flush=True)
            walls.append(time.perf_counter() - t1)
        print(f"fleet: {len(walls)} experiments, walls "
              f"{[round(w, 3) for w in walls]}", flush=True)
    peak = memory_peak_bytes()
    jax.clear_caches()

    t1 = time.perf_counter()
    order = np.random.default_rng([seed % 2**64, 3]).permutation(len(results))
    pairs, ref_trace = [], None
    for k in sorted(order[:CHECKED]):
        exp_seed, got = results[k]
        want, ref_trace = reference(config, traffic, exp_seed)
        print(f"fleet: experiment {k} (seed {exp_seed}) program {got}",
              flush=True)
        print(f"fleet: experiment {k} (seed {exp_seed}) reference {want}",
              flush=True)
        pairs.append((got, want))
    print(f"fleet: reference for {len(pairs)} of {len(results)} "
          f"experiments in {time.perf_counter() - t1:.3f} s", flush=True)
    checks = compare(pairs, limits)
    if trace:
        checks.append({"name": "trace_mismatches",
                       "value": _trace_mismatches(
                           observations.pop("requests"), ref_trace),
                       "limit": limits["trace_mismatches"]})
    correct = bool(pairs) and all(c["value"] <= c["limit"] for c in checks)
    wall = sum(walls)
    out = {"correct": correct, "attempted": len(walls), "failed": failed,
           "checks": checks, "memory_peak_bytes": peak,
           "metrics": {"setup_s": setup_s}}
    if results and wall > 0:
        out["metrics"]["fleet_machine_s_per_s"] = (
            len(results) * machine_s / wall)
    if observations is not None:
        out["observations"] = observations
    return out


def _traced_experiment(cfg, traffic: dict):
    """One experiment under the profiler, split at the generate and the
    engine seams; returns ([(seed, scalars)], observations, failed)."""
    import shutil
    import tempfile

    from repro.sim.fleetsim import run_fleet_experiment
    from repro.telemetry import TelemetryHub
    from repro.workloads import get_scenario

    from bench import flops, trace as trace_mod

    hub = TelemetryHub()
    log_dir = tempfile.mkdtemp(prefix="fleet-trace-")
    results, failed, requests = [], 0, []
    t0 = time.perf_counter()
    with trace_mod.capture(log_dir):
        with trace_mod.span("window"):
            try:
                with trace_mod.span("generate"):
                    requests = get_scenario(
                        cfg.scenario, **cfg.scenario_options).generate(
                        rate_rps=cfg.rate_rps, duration_s=cfg.duration_s,
                        seed=cfg.seed)
                with trace_mod.span("fleet_run"):
                    res = run_fleet_experiment(cfg, telemetry=hub,
                                               requests=requests)
                results.append((cfg.seed, scalars(res)))
            except Exception as exc:
                failed += 1
                print(f"fleet: traced experiment failed: {exc!r}", flush=True)
    wall = time.perf_counter() - t0
    t1 = time.perf_counter()
    summary = trace_mod.reduce(trace_mod.latest_xplane(log_dir))
    shutil.rmtree(log_dir, ignore_errors=True)
    print(f"fleet: trace reduced in {time.perf_counter() - t1:.3f} s; "
          f"window {summary.window_s:.3f} s, busy {summary.busy_s:.6f} s; "
          f"programs {trace_mod.top(summary.modules_s)}", flush=True)
    ev = next((e for e in hub.events if e["kind"] == "engine"), {})
    print(f"fleet: engine {ev}", flush=True)
    gauge = hub.gauge("phase/jax_compile_wall_s")
    shape = flops.FleetShape(n_prompt=cfg.n_prompt, n_token=cfg.n_token,
                             num_cores=cfg.num_cores,
                             n_macro=int(round(cfg.duration_s
                                               / cfg.idling_period_s)),
                             steps_per_period=max(1, round(
                                 cfg.idling_period_s
                                 / float(dict(cfg.engine_opts)["dt_s"]))))
    obs = {"trace": summary, "experiment_wall_s": wall,
           "counters": {"jax_compile_wall_s": (gauge.value if gauge
                                               else None)},
           "fleet_shape": shape, "requests": requests}
    return results, obs, failed


def _trace_mismatches(requests, ref_trace) -> int:
    """Requests whose arrival or lengths differ from the benchmark's own
    draw, plus the difference in their number."""
    import numpy as np
    t, n_in, n_out = ref_trace
    got_t = np.array([r.arrival_s for r in requests])
    got_in = np.array([r.input_tokens for r in requests])
    got_out = np.array([r.output_tokens for r in requests])
    n = min(len(got_t), len(t))
    bad = ((got_t[:n] != t[:n]) | (got_in[:n] != n_in[:n])
           | (got_out[:n] != n_out[:n]))
    return int(bad.sum()) + abs(len(got_t) - len(t))
