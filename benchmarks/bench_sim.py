"""Simulator perf-benchmark harness: events/sec and wall time per
canonical config, emitted to BENCH_sim.json to seed the repo's perf
trajectory.

    PYTHONPATH=src python benchmarks/bench_sim.py            # full (~3 min)
    PYTHONPATH=src python benchmarks/bench_sim.py --smoke    # CI-scale

The committed BASELINE block pins the pre-optimization numbers (PR 4's
"before", captured at commit 94bd8ac on the same canonical default
config) so every future run reports an honest end-to-end speedup next
to its absolute numbers. Wall-time comparisons use the min over runs —
the least-noise estimator on shared machines.

Bit-exactness is NOT this harness's job: tests/test_perf_bitexact.py
pins optimized-vs-golden `ExperimentMetrics`; this file only measures.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import statistics
import time

import numpy as np

from repro.core import CoreManager
from repro.launch.compile_cache import enable_compile_cache
from repro.sim import ExperimentConfig, metrics as metrics_mod
from repro.sim.cluster import Cluster
from repro.sim.fleetstate import FleetAgingSettler
from repro.workloads import get_scenario

HOST_CPU_NOTE = ("host-CPU wall times of the simulator, not chip speed; "
                 "chip measurements live in PERF_LEDGER.jsonl")
DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_sim.json")
# --smoke writes elsewhere by default so a CI-scale run can never
# clobber the committed full-config record README points at.
SMOKE_OUT = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_sim_smoke.json")

# Pre-PR-4 numbers for the canonical default config (ExperimentConfig()
# defaults: proposed / jsq / conversation-poisson, 22 machines x 40
# cores, 120 s @ 60 rps, seed 0), captured at commit 94bd8ac with this
# harness's timing loop (3 runs, min/median). Events counted by the
# event loop; the per-event numpy dispatch these numbers price out is
# exactly what the PR-4 fast paths removed.
BASELINE = {
    "captured_at_commit": "94bd8ac",
    "benchmark": "default-e2e",
    "runs": 3,
    "wall_s_min": 12.148,
    "wall_s_median": 12.839,
    "events": 140488,
    "events_per_sec": 11563.0,
    "completed": 2525,
}


def _run_once(cfg: ExperimentConfig) -> dict:
    """One timed end-to-end experiment; returns wall/events/completed."""
    scenario = get_scenario(cfg.scenario, **cfg.scenario_options)
    trace = scenario.generate(rate_rps=cfg.rate_rps,
                              duration_s=cfg.duration_s, seed=cfg.seed)
    t0 = time.perf_counter()
    cluster = Cluster(cfg)
    cluster.run(list(trace), cfg.duration_s,
                sample_period_s=cfg.sample_period_s)
    wall = time.perf_counter() - t0
    m = metrics_mod.collect(cluster, cfg)
    return {"wall_s": wall, "events": cluster.queue.processed,
            "completed": m.completed}


def bench_end_to_end(cfg: ExperimentConfig, runs: int) -> dict:
    walls, events, completed = [], None, None
    for _ in range(runs):
        r = _run_once(cfg)
        walls.append(r["wall_s"])
        events, completed = r["events"], r["completed"]
    wall_min = min(walls)
    return {
        "runs": runs,
        "wall_s_min": round(wall_min, 4),
        "wall_s_median": round(statistics.median(walls), 4),
        "events": events,
        "events_per_sec": round(events / wall_min, 1),
        "completed": completed,
        "config": {
            "policy": cfg.policy, "router": cfg.router,
            "scenario": cfg.scenario, "num_cores": cfg.num_cores,
            "n_machines": cfg.n_machines, "rate_rps": cfg.rate_rps,
            "duration_s": cfg.duration_s, "seed": cfg.seed,
        },
    }


def bench_telemetry_overhead(duration_s: float = 20.0,
                             runs: int = 2) -> dict:
    """Telemetry-off vs telemetry-on wall time on a short default-config
    run — the price of the hub's event/series recording when enabled,
    and evidence the `is not None` guards are free when disabled (the
    off time here is the same path `bench_end_to_end` measures)."""
    from repro.sim.runner import run_experiment

    def timed(cfg):
        walls = []
        for _ in range(runs):
            t0 = time.perf_counter()
            run_experiment(cfg)
            walls.append(time.perf_counter() - t0)
        return min(walls)

    base = ExperimentConfig(duration_s=duration_s)
    off = timed(base)
    on = timed(base.with_telemetry())
    return {
        "duration_s": duration_s,
        "runs": runs,
        "telemetry_off_s": round(off, 4),
        "telemetry_on_s": round(on, 4),
        "overhead_pct": round(100.0 * (on - off) / off, 2),
    }


def bench_manager_hot_path(n_ops: int = 20_000) -> dict:
    """Raw assign/release throughput of one CoreManager (proposed):
    the per-event cost every simulated CPU task pays."""
    m = CoreManager(40, policy="proposed", rng=np.random.default_rng(0))
    t0 = time.perf_counter()
    t = 0.0
    for tid in range(n_ops):
        t += 0.001
        m.assign(tid, t)
        m.release(tid, t + 0.0005)
    wall = time.perf_counter() - t0
    return {"ops": n_ops, "assign_release_per_sec": round(n_ops / wall, 1)}


def bench_fleet_settle(n_machines: int = 22, num_cores: int = 40,
                       reps: int = 200) -> dict:
    """Fleet-batched periodic settlement vs n_machines sequential
    settle_all chains (what the cluster tick used to do)."""
    def build():
        ms = [CoreManager(num_cores, policy="linux",
                          rng=np.random.default_rng(i))
              for i in range(n_machines)]
        for i, m in enumerate(ms):       # heterogeneous regimes
            for tid in range(i % 7):
                m.assign(tid, 0.0)
        return ms

    ms = build()
    t0 = time.perf_counter()
    for k in range(reps):
        for m in ms:
            m.settle_all(float(k + 1))
    seq = time.perf_counter() - t0

    ms = build()
    settler = FleetAgingSettler(ms)
    t0 = time.perf_counter()
    for k in range(reps):
        settler.settle(float(k + 1))
    batched = time.perf_counter() - t0
    return {"reps": reps, "n_machines": n_machines,
            "sequential_s": round(seq, 4), "batched_s": round(batched, 4),
            "speedup": round(seq / batched, 2)}


def bench_fleet_scale(smoke: bool = False) -> dict:
    """Scale curve: event-loop reference vs the vectorized fleet engine
    (`repro.sim.fleetsim`) at growing fleet sizes and horizons.

    Machines scale with the default 5:17 prompt:token split and
    proportional offered load, so per-machine utilization is comparable
    across the curve. The headline row drives >= 200 machines for >= 1
    simulated hour through the time-stepped engine — a scale where the
    per-event loop is no longer practical (its 22-machine x 120 s wall
    time extrapolates to ~15 min there). `machine_s_per_wall_s` is the
    honest cross-engine throughput unit: simulated machine-seconds per
    wall second."""
    from repro.sim.runner import run_experiment

    def scaled_cfg(n_machines: int, duration_s: float) -> ExperimentConfig:
        n_prompt = max(1, round(n_machines * 5 / 22))
        return ExperimentConfig(
            n_prompt=n_prompt, n_token=n_machines - n_prompt,
            rate_rps=round(60.0 * n_machines / 22, 3),
            duration_s=duration_s)

    if smoke:
        event_points = [(22, 30.0)]
        fleet_points = [("numpy", 22, 30.0), ("jax", 22, 30.0)]
    else:
        event_points = [(22, 120.0)]
        fleet_points = [("numpy", 22, 120.0), ("numpy", 50, 600.0),
                        ("numpy", 200, 3600.0), ("jax", 200, 3600.0)]

    rows = []
    for n, dur in event_points:
        cfg = scaled_cfg(n, dur)
        t0 = time.perf_counter()
        res = run_experiment(cfg)
        wall = time.perf_counter() - t0
        rows.append({"engine": "event", "backend": "python",
                     "n_machines": n, "duration_s": dur,
                     "wall_s": round(wall, 4),
                     "machine_s_per_wall_s": round(n * dur / wall, 1),
                     "completed": res.completed})
    for backend, n, dur in fleet_points:
        cfg = scaled_cfg(n, dur).with_engine("fleet", backend=backend)
        t0 = time.perf_counter()
        res = run_experiment(cfg)
        wall = time.perf_counter() - t0
        rows.append({"engine": "fleet", "backend": backend,
                     "n_machines": n, "duration_s": dur,
                     "wall_s": round(wall, 4),
                     "machine_s_per_wall_s": round(n * dur / wall, 1),
                     "completed": res.completed})
    return {"rows": rows}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-scale run (short trace, 1 timing run); "
                    "skips the pinned-baseline speedup comparison")
    ap.add_argument("--runs", type=int, default=3,
                    help="timing repetitions for the end-to-end bench")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: repo-root "
                    "BENCH_sim.json, or BENCH_sim_smoke.json with "
                    "--smoke)")
    args = ap.parse_args()

    enable_compile_cache()
    if args.out is None:
        args.out = SMOKE_OUT if args.smoke else DEFAULT_OUT
    if args.smoke:
        cfg = ExperimentConfig(duration_s=8.0)
        runs = 1
    else:
        cfg = ExperimentConfig()
        runs = args.runs

    out = {
        "benchmark": "default-e2e" if not args.smoke else "smoke-e2e",
        "note": HOST_CPU_NOTE,
        "env": {
            "python": platform.python_version(),
            "numpy": np.__version__,
            "machine": platform.machine(),
            "cpus": os.cpu_count(),
        },
        "current": bench_end_to_end(cfg, runs),
        "micro": {
            "manager_hot_path": bench_manager_hot_path(),
            "fleet_settle": bench_fleet_settle(),
            "telemetry_overhead": bench_telemetry_overhead(
                duration_s=8.0 if args.smoke else 20.0,
                runs=1 if args.smoke else 2),
        },
        "fleet_scale": bench_fleet_scale(smoke=args.smoke),
    }
    if not args.smoke:
        out["baseline"] = BASELINE
        out["speedup_end_to_end"] = round(
            BASELINE["wall_s_min"] / out["current"]["wall_s_min"], 2)

    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    for k, v in out.items():
        if k != "env":
            print(f"{k}: {json.dumps(v)}")
    print(f"wrote {os.path.normpath(args.out)}")


if __name__ == "__main__":
    main()
