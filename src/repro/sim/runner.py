"""Experiment runner: replay a workload scenario against a cluster
under a policy.

Build a frozen `ExperimentConfig` and pass it to `run_experiment`; the
workload comes from the `repro.workloads` scenario registry
(`cfg.scenario` + `cfg.scenario_opts`), the policy from the
`repro.core.policies` registry. `run_policy_sweep` runs the same
experiment across policies, and — with `scenarios=` — across full
policy x scenario grids:

    sweep = run_policy_sweep(cfg, policies=("linux", "proposed"),
                             scenarios=("conversation-poisson",
                                        "conversation-mmpp"))
    sweep[("proposed", "conversation-mmpp")].p99_latency_s

With `routers=` the cluster-level routing axis (`repro.sim.routing`)
joins the grid, keyed `(policy, router)` or `(policy, scenario,
router)`:

    grid = run_policy_sweep(cfg, policies=("linux", "proposed"),
                            scenarios=("conversation-poisson",
                                       "conversation-mmpp"),
                            routers=("jsq", "least-aged-cpu",
                                     "carbon-greedy"))
    grid[("proposed", "conversation-mmpp", "carbon-greedy")]

The sweep returns a `SweepResult` — a read-only mapping with the same
keys as the dict it historically returned, plus `save`/`load`/`to_rows`
so grids persist and diff across runs (see `repro.sim.results`).
"""
from __future__ import annotations

from repro.carbon import get_carbon_model
from repro.core.policies import canonical_policy_name
from repro.faults.registry import canonical_fault_model_name, get_fault_model
from repro.hardware.inventory import canonical_fleet_name, resolve_fleet
from repro.power import get_power_model
from repro.power.registry import canonical_power_model_name
from repro.sim import metrics as metrics_mod
from repro.sim.cluster import Cluster
from repro.sim.config import ExperimentConfig
from repro.sim.results import ExperimentResult, SweepResult
from repro.sim.routing import canonical_router_name
from repro.workloads import canonical_scenario_name, get_scenario

DEFAULT_SWEEP = ("linux", "least-aged", "proposed")


def run_experiment(cfg: ExperimentConfig,
                   telemetry=None) -> ExperimentResult:
    if not isinstance(cfg, ExperimentConfig):
        raise TypeError(
            "run_experiment takes an ExperimentConfig (the pre-registry "
            "run_experiment(policy, **kwargs) signature was removed); "
            f"got {cfg!r}")
    # Streaming telemetry (repro.telemetry): `cfg.telemetry=True` builds
    # a hub from `cfg.telemetry_opts`; a caller-supplied hub wins (so a
    # long-lived hub can span several runs). None = zero-cost off.
    hub = telemetry
    if hub is None and cfg.telemetry:
        from repro.telemetry import TelemetryHub
        hub = TelemetryHub.from_opts(cfg.telemetry_options)
    # Resolve every axis up front so a typo'd name fails before the
    # simulation runs, not after (policy and router resolve inside
    # Cluster.__init__ below); the resolved carbon model is handed to
    # `collect`, which would otherwise construct it a second time.
    carbon_model = get_carbon_model(cfg.carbon_model, **cfg.carbon_options)
    power_model = get_power_model(cfg.power_model, **cfg.power_options)
    scenario = get_scenario(cfg.scenario, **cfg.scenario_options)
    # Fault axis fail-fast: instantiate once to validate name + opts
    # (the cluster builds its own per-machine instances).
    get_fault_model(cfg.fault_model, **cfg.fault_options)
    # Fleet axis fail-fast: resolve the hardware inventory (None for
    # the bit-exact uniform default) so bad SKU names / row counts fail
    # here; the cluster / fleet engine re-resolve their own copy.
    resolve_fleet(cfg.fleet, cfg.fleet_options, cfg.n_machines)
    if cfg.engine == "fleet":
        # Vectorized time-stepped engine (repro.sim.fleetsim) — the
        # scale path. The event loop below stays the bit-exact
        # small-scale reference.
        from repro.sim.fleetsim import run_fleet_experiment
        return run_fleet_experiment(cfg, telemetry=hub,
                                    carbon_model=carbon_model,
                                    power_model=power_model,
                                    scenario=scenario)
    if hub is None:
        trace = scenario.generate(rate_rps=cfg.rate_rps,
                                  duration_s=cfg.duration_s, seed=cfg.seed)
        cluster = Cluster(cfg)
        cluster.run(trace, cfg.duration_s,
                    sample_period_s=cfg.sample_period_s)
        return metrics_mod.collect(cluster, cfg, carbon_model=carbon_model,
                                   power_model=power_model)
    return _run_with_telemetry(cfg, hub, carbon_model, power_model,
                               scenario)


def _run_with_telemetry(cfg, hub, carbon_model, power_model,
                        scenario) -> ExperimentResult:
    """Telemetry-on path: same simulation, plus per-phase wall-time /
    event-loop-throughput self-profiling and post-run export. Each phase
    is a program span `sim.<phase>` that sets the gauge
    `phase/<phase>_wall_s`. Recording is pure observation, so the
    `ExperimentResult` scalars stay bit-identical to the hub-less path
    (pinned in tests/test_telemetry.py)."""
    import dataclasses

    from repro.telemetry import Phase

    def phase(name, fn):
        with Phase(f"sim.{name}", hub, gauge=name):
            return fn()

    trace = phase("trace_gen", lambda: scenario.generate(
        rate_rps=cfg.rate_rps, duration_s=cfg.duration_s, seed=cfg.seed))
    cluster = phase("cluster_build", lambda: Cluster(cfg, telemetry=hub))
    # Surface the aging settler's *resolved* backend: visible in the
    # event stream and as a gauge in `result.telemetry_summary`. The jax
    # backend settles in float32 — fast, but not bit-exact vs the numpy
    # reference.
    backend = cluster.fleet_settler.backend
    hub.event("engine", 0.0, engine="event", aging_backend=backend)
    hub.set_gauge("engine/aging_backend_is_jax",
                  1.0 if backend == "jax" else 0.0)
    phase("sim_run", lambda: cluster.run(
        trace, cfg.duration_s, sample_period_s=cfg.sample_period_s))
    sim_wall = hub.gauge("phase/sim_run_wall_s").value
    hub.set_gauge("events_processed", cluster.queue.processed)
    if sim_wall > 0:
        hub.set_gauge("events_per_sec", cluster.queue.processed / sim_wall)
    result = phase("collect", lambda: metrics_mod.collect(
        cluster, cfg, carbon_model=carbon_model, power_model=power_model,
        telemetry=hub))

    summary = hub.summary()
    export_dir = cfg.telemetry_options.get("export_dir")
    if export_dir:
        import os
        from repro.telemetry import export_run
        out_dir = os.path.join(
            str(export_dir), f"{cfg.policy}-{cfg.fingerprint()}")
        summary["export"] = export_run(hub, out_dir,
                                       t_end=cfg.duration_s)
    return dataclasses.replace(result, telemetry_summary=summary)


def run_policy_sweep(
    cfg: ExperimentConfig | None = None,
    policies=DEFAULT_SWEEP,
    scenarios=None,
    routers=None,
    power_models=None,
    fault_models=None,
    fleets=None,
    parallel: int | None = None,
) -> SweepResult:
    """Run the same experiment across policies (x scenarios x routers
    x power models x fault models x fleets).

    Policies/scenarios/routers/power models/fault models/fleets are
    given by registry name (fleets by fleet spec — see
    `repro.hardware`). With `scenarios=None`, `routers=None`,
    `power_models=None`, `fault_models=None` and `fleets=None`
    (default) the result is keyed by policy name, preserving the
    single-axis API. Adding `scenarios=` keys by `(policy, scenario)`;
    adding `routers=` keys by `(policy, router)`; adding
    `power_models=` appends a power-model part; adding `fault_models=`
    appends a fault-model part; adding `fleets=` appends a fleet part;
    all together key by `(policy, scenario, router, power_model,
    fault_model, fleet)`. `cfg.policy_opts` / `cfg.scenario_opts` /
    `cfg.router_opts` / `cfg.power_opts` / `cfg.fault_opts` /
    `cfg.fleet_opts` only apply to the sweep entries matching
    `cfg.policy` / `cfg.scenario` / `cfg.router` / `cfg.power_model` /
    `cfg.fault_model` / `cfg.fleet`.

    `parallel=N` fans the grid's host-only cells (event engine, numpy
    fleet backend) across a process pool of N workers. Cells on the jax
    fleet backend run in the calling process: a device belongs to one
    process, and the caller that imported JAX already holds it. Every
    cell is an independent simulation whose seeding is
    carried entirely by its frozen `ExperimentConfig` (each worker
    re-derives all RNG streams from `cell_cfg.seed`), so the result
    dict is identical to the serial sweep — same keys, same metrics —
    regardless of worker count or completion order (pinned by
    tests/test_perf_bitexact.py). One caveat: workers resolve registry
    names on import, so custom policies/scenarios/routers registered at
    runtime (a notebook cell, an `if __name__ == "__main__"` block) are
    only visible to workers under the `fork` start method (Linux
    default); under `spawn` (macOS/Windows default) register them in an
    imported module, or run serially.
    """
    if cfg is None:
        cfg = ExperimentConfig()
    scenario_axis = scenarios is not None
    router_axis = routers is not None
    power_axis = power_models is not None
    fault_axis = fault_models is not None
    fleet_axis = fleets is not None
    axes = (("policy",)
            + (("scenario",) if scenario_axis else ())
            + (("router",) if router_axis else ())
            + (("power_model",) if power_axis else ())
            + (("fault_model",) if fault_axis else ())
            + (("fleet",) if fleet_axis else ()))
    cells: list[tuple[object, ExperimentConfig]] = []
    for s in (scenarios if scenario_axis else (cfg.scenario,)):
        s_name = canonical_scenario_name(s)
        s_cfg = cfg if s_name == cfg.scenario else cfg.with_scenario(s_name)
        for r in (routers if router_axis else (cfg.router,)):
            r_name = canonical_router_name(r)
            r_cfg = s_cfg if r_name == s_cfg.router \
                else s_cfg.with_router(r_name)
            for w in (power_models if power_axis else (cfg.power_model,)):
                w_name = canonical_power_model_name(w)
                w_cfg = r_cfg if w_name == r_cfg.power_model \
                    else r_cfg.with_power_model(w_name)
                for fm in (fault_models if fault_axis
                           else (cfg.fault_model,)):
                    f_name = canonical_fault_model_name(fm)
                    f_cfg = w_cfg if f_name == w_cfg.fault_model \
                        else w_cfg.with_fault_model(f_name)
                    for fl in (fleets if fleet_axis else (cfg.fleet,)):
                        fl_name = canonical_fleet_name(fl)
                        fl_cfg = f_cfg if fl_name == f_cfg.fleet \
                            else f_cfg.with_fleet(fl_name)
                        for p in policies:
                            run_cfg = _with_policy(fl_cfg, p)
                            key = ((run_cfg.policy,)
                                   + ((s_name,) if scenario_axis else ())
                                   + ((r_name,) if router_axis else ())
                                   + ((w_name,) if power_axis else ())
                                   + ((f_name,) if fault_axis else ())
                                   + ((fl_name,) if fleet_axis else ()))
                            cells.append((key if len(key) > 1 else key[0],
                                          run_cfg))
    host = [c for _, c in cells if not _uses_jax(c)]
    if parallel is not None and int(parallel) > 1 and len(host) > 1:
        import concurrent.futures

        with concurrent.futures.ProcessPoolExecutor(
                max_workers=int(parallel)) as pool:
            # `map` preserves submission order, so results zip back.
            done = iter(pool.map(run_experiment, host))
            results = [run_experiment(c) if _uses_jax(c) else next(done)
                       for _, c in cells]
        return SweepResult(zip([k for k, _ in cells], results), axes=axes)
    return SweepResult(((key, run_experiment(run_cfg))
                        for key, run_cfg in cells), axes=axes)


def _uses_jax(cfg: ExperimentConfig) -> bool:
    """True for a cell that runs on the jax fleet backend (the device)."""
    from repro.sim.fleetsim import _resolve_backend
    return cfg.engine == "fleet" and _resolve_backend(
        str(cfg.engine_options.get("backend", "auto"))) == "jax"


def _with_policy(cfg: ExperimentConfig, policy) -> ExperimentConfig:
    name = canonical_policy_name(policy)
    return cfg if name == cfg.policy else cfg.with_policy(name)
