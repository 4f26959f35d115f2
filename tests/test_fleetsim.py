"""Fleet engine (`repro.sim.fleetsim`): parity vs the event-loop
reference, backend agreement, checkpoint/resume exactness, config
plumbing, and a scale smoke.

The fleet engine is a mean-field surrogate, so event-engine parity is
pinned with tolerances (calibrated against the measured deltas on the
default 22-machine config), NOT bit-exactness — that property belongs
to the event engine alone (tests/test_perf_bitexact.py).
"""
from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from repro.sim import ExperimentConfig
from repro.sim.fleetsim import FleetEngine, _resolve_backend
from repro.sim.runner import run_experiment


@pytest.fixture(scope="module")
def event_result():
    return run_experiment(ExperimentConfig())


@pytest.fixture(scope="module")
def fleet_result():
    return run_experiment(
        ExperimentConfig().with_engine("fleet", backend="numpy"))


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


class TestEventParity:
    """Fleet surrogate vs event reference on the default config
    (22 machines x 120 s @ 60 rps). Tolerances bracket the measured
    deltas with headroom, tight enough that a physics regression in
    either engine trips them."""

    def test_engine_labels(self, event_result, fleet_result):
        assert event_result.engine == "event"
        assert fleet_result.engine == "fleet"
        # the label must NOT leak into the diffable scalar row
        assert "engine" not in event_result.scalars()
        assert "engine" not in fleet_result.scalars()

    def test_throughput(self, event_result, fleet_result):
        assert _rel(fleet_result.completed, event_result.completed) < 0.10

    def test_latency(self, event_result, fleet_result):
        assert _rel(fleet_result.mean_latency_s,
                    event_result.mean_latency_s) < 0.10
        assert _rel(fleet_result.p99_latency_s,
                    event_result.p99_latency_s) < 0.10

    def test_aging(self, event_result, fleet_result):
        assert _rel(fleet_result.mean_degradation_percentiles[50],
                    event_result.mean_degradation_percentiles[50]) < 0.15
        assert _rel(fleet_result.freq_cv_percentiles[50],
                    event_result.freq_cv_percentiles[50]) < 0.10

    def test_carbon_and_energy(self, event_result, fleet_result):
        assert _rel(fleet_result.fleet_yearly_kgco2eq,
                    event_result.fleet_yearly_kgco2eq) < 0.15
        assert _rel(fleet_result.fleet_energy_kwh,
                    event_result.fleet_energy_kwh) < 0.05

    def test_shapes_match_fleet(self, event_result, fleet_result):
        n = ExperimentConfig().n_machines
        for res in (event_result, fleet_result):
            assert len(res.per_machine_degradation) == n
            assert len(res.per_machine_residency) == n
            assert np.isfinite(res.per_machine_degradation).all()


class TestBurstyScenarioParity:
    """Cross-validation beyond the Poisson default (ROADMAP 1e): the
    fluid surrogate must track the event reference through bursty
    arrival processes too — MMPP's on/off rate switching and the
    flash-crowd spike both stress the queue-drain approximation in ways
    a constant-rate trace never does. Tolerances bracket the measured
    deltas (mmpp: completed 2.9%, deg50 5.4%; flashcrowd: completed
    3.6%, deg50 7.7%) with headroom."""

    @pytest.fixture(scope="class", params=["conversation-mmpp",
                                           "conversation-flashcrowd"])
    def pair(self, request):
        cfg = ExperimentConfig(scenario=request.param)
        ev = run_experiment(cfg)
        fl = run_experiment(cfg.with_engine("fleet", backend="numpy"))
        return ev, fl

    def test_throughput(self, pair):
        ev, fl = pair
        assert _rel(fl.completed, ev.completed) < 0.08

    def test_latency(self, pair):
        ev, fl = pair
        assert _rel(fl.mean_latency_s, ev.mean_latency_s) < 0.06
        assert _rel(fl.p99_latency_s, ev.p99_latency_s) < 0.02

    def test_aging(self, pair):
        ev, fl = pair
        assert _rel(fl.mean_degradation_percentiles[50],
                    ev.mean_degradation_percentiles[50]) < 0.15
        assert _rel(fl.freq_cv_percentiles[50],
                    ev.freq_cv_percentiles[50]) < 0.05

    def test_carbon_and_energy(self, pair):
        ev, fl = pair
        assert _rel(fl.fleet_yearly_kgco2eq,
                    ev.fleet_yearly_kgco2eq) < 0.10
        assert _rel(fl.fleet_energy_kwh, ev.fleet_energy_kwh) < 0.02


class TestBackendAgreement:
    """numpy (f64 reference) vs jax (f32 lax.scan) run the same
    functional step; agreement is close but not bit-exact."""

    @pytest.fixture(scope="class")
    def pair(self):
        cfg = ExperimentConfig(duration_s=60.0)
        res_np = run_experiment(cfg.with_engine("fleet", backend="numpy"))
        res_jx = run_experiment(cfg.with_engine("fleet", backend="jax"))
        return res_np, res_jx

    def test_throughput_and_latency(self, pair):
        res_np, res_jx = pair
        assert _rel(res_jx.completed, res_np.completed) < 0.01
        assert _rel(res_jx.mean_latency_s, res_np.mean_latency_s) < 0.01

    def test_aging(self, pair):
        res_np, res_jx = pair
        assert _rel(res_jx.mean_degradation_percentiles[50],
                    res_np.mean_degradation_percentiles[50]) < 0.02
        assert _rel(res_jx.fleet_yearly_kgco2eq,
                    res_np.fleet_yearly_kgco2eq) < 0.05


GOLDEN_PATH = os.path.join(os.path.dirname(__file__),
                           "fleet_macro_step_golden.json")
_BASE = ExperimentConfig(duration_s=60.0)
GOLDEN_CFGS = {
    "uniform": _BASE,
    "carbon-aware": _BASE.with_policy(
        "proposed", carbon_aware=True, intensity="diurnal",
        intensity_opts=(("amplitude", 0.8), ("period_s", 40.0),
                        ("phase", 0.0))),
    "ragged": ExperimentConfig(duration_s=60.0, rate_rps=30.0, seed=2,
                               n_prompt=1, n_token=2,
                               fleet="xeon-28c:2+epyc-64c:1"),
    "guardband": _BASE.with_fault_model("guardband", margin=0.012),
    "machine-crash": _BASE.with_fault_model("machine-crash",
                                            mttf_s=120.0),
    "transient-stall": _BASE.with_fault_model("transient-stall",
                                              rate_per_s=0.1),
}
GOLDEN_CASES = ([(name, "numpy") for name in GOLDEN_CFGS]
                + [("uniform", "jax"), ("guardband", "jax")])


def golden_row(case: str, backend: str) -> dict:
    """The scalar row that `fleet_macro_step_golden.json` pins."""
    cfg = GOLDEN_CFGS[case].with_engine("fleet", backend=backend)
    return run_experiment(cfg).scalars()


class TestMacroStepGolden:
    """Scalar rows of six 60-s configurations (uniform, carbon-aware
    gating, a ragged fleet, each fleet fault model) on the numpy
    backend and two on the jax backend, pinned to recorded values.
    numpy (float64) is held to 1e-12 relative, which leaves an ulp of
    a SIMD transcendental on another CPU; jax (float32) to 1e-5."""

    @pytest.fixture(scope="class")
    def golden(self):
        with open(GOLDEN_PATH) as f:
            return json.load(f)

    @pytest.mark.parametrize("case,backend", GOLDEN_CASES)
    def test_scalars(self, golden, case, backend):
        want = golden[f"{case}/{backend}"]
        got = golden_row(case, backend)
        assert got.keys() == want.keys()
        tol = 1e-12 if backend == "numpy" else 1e-5
        for k, b in want.items():
            a = got[k]
            if isinstance(b, str):
                assert a == b, k
            elif b != b:
                assert a != a, k
            else:
                assert abs(a - b) <= tol * abs(b), (k, a, b)


class TestCheckpointResume:
    def _cfg(self, ckpt_dir: str) -> ExperimentConfig:
        return ExperimentConfig(duration_s=60.0).with_engine(
            "fleet", backend="numpy", checkpoint_dir=ckpt_dir,
            checkpoint_every_s=20.0)

    def test_resume_is_exact(self, tmp_path):
        """Kill-and-resume reproduces the uninterrupted run's scalar
        row bit-for-bit (numpy backend contract)."""
        ckpt = str(tmp_path / "ckpt")
        cfg = self._cfg(ckpt)
        uninterrupted = run_experiment(cfg)
        # Simulate the interruption: drop the checkpoints past t=20 s,
        # so the rerun resumes from the earliest retained one.
        steps = sorted(d for d in os.listdir(ckpt)
                       if d.startswith("step_"))
        assert len(steps) >= 2, "expected several periodic checkpoints"
        for d in steps[1:]:
            shutil.rmtree(os.path.join(ckpt, d))
        resumed = run_experiment(cfg)
        a, b = uninterrupted.scalars(), resumed.scalars()
        assert a.keys() == b.keys()
        for k in a:
            assert a[k] == b[k] or (a[k] != a[k] and b[k] != b[k]), k

    def test_resume_refuses_config_mismatch(self, tmp_path):
        ckpt = str(tmp_path / "ckpt")
        run_experiment(self._cfg(ckpt))
        other = ExperimentConfig(duration_s=60.0, seed=7).with_engine(
            "fleet", backend="numpy", checkpoint_dir=ckpt,
            checkpoint_every_s=20.0)
        with pytest.raises(ValueError, match="refusing to resume"):
            run_experiment(other)

    def test_resume_refuses_unstacked_window_sums(self, tmp_path):
        """A checkpoint holding the window sums as eleven separate
        arrays (`mw_cnt` ... `res_fbusy`), not the stacked `mw` and
        `res`, is refused rather than misread."""
        from repro.checkpoint import store
        ckpt = str(tmp_path / "ckpt")
        cfg = self._cfg(ckpt)
        state = dict(FleetEngine(cfg).state)
        for name, rows in (("mw", ("cnt", "wait", "iter", "cpuw", "sp",
                                   "st", "comps")),
                           ("res", ("busy", "idle", "gated", "fbusy"))):
            stacked = state.pop(name)
            state.update({f"{name}_{r}": v for r, v in zip(rows, stacked)})
        state["macro"] = np.asarray(20, dtype=np.int64)
        store.save(ckpt, 20, state,
                   extra={"config": cfg.fingerprint(), "engine": "fleet",
                          "backend": "numpy", "macro": 20})
        with pytest.raises(KeyError, match="mw"):
            run_experiment(cfg)


class TestConfigPlumbing:
    def test_default_fingerprint_unchanged(self):
        """Adding the engine axis must not re-hash existing configs:
        the default (event, no opts) is omitted from the payload, so
        every pre-engine fingerprint — including the pinned drift-gate
        golden — survives."""
        assert ExperimentConfig().fingerprint() == \
            ExperimentConfig(engine="event").fingerprint()

    def test_fleet_fingerprint_differs(self):
        base = ExperimentConfig()
        assert base.with_engine("fleet").fingerprint() != base.fingerprint()
        assert base.with_engine(
            "fleet", backend="numpy").fingerprint() != \
            base.with_engine("fleet").fingerprint()

    def test_unknown_engine_rejected(self):
        with pytest.raises(ValueError, match="unknown engine"):
            ExperimentConfig(engine="warp")

    def test_unknown_engine_opts_rejected(self):
        cfg = ExperimentConfig().with_engine("fleet", warp_factor=9)
        with pytest.raises(ValueError, match="unknown engine_opts"):
            FleetEngine(cfg)

    def test_backend_resolution(self):
        assert _resolve_backend("numpy") == "numpy"
        assert _resolve_backend("jax") == "jax"
        assert _resolve_backend("auto") == "jax"   # no numpy fallback
        with pytest.raises(ValueError, match="unknown fleet backend"):
            _resolve_backend("fortran")

    @pytest.mark.parametrize("platform,opts,expect", [
        ("cpu", {"backend": "numpy"}, (False, False)),
        ("cpu", {"backend": "jax"}, (False, False)),     # jnp oracle
        ("cpu", {"backend": "jax", "use_kernel": True}, (True, True)),
        ("tpu", {"backend": "jax"}, (True, False)),      # compiled kernel
        ("tpu", {"backend": "jax", "use_kernel": False}, (False, False)),
    ])
    def test_kernel_resolution(self, monkeypatch, platform, opts, expect):
        """`use_kernel`/`interpret` are resolved once, from the platform
        and the options; TPU never interprets the kernel."""
        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        eng = FleetEngine(ExperimentConfig(duration_s=8.0).with_engine(
            "fleet", **opts))
        assert (eng.use_kernel, eng.interpret) == expect

    def test_engine_event_reports_kernel_path(self):
        from repro.telemetry import TelemetryHub
        hub = TelemetryHub()
        run_experiment(ExperimentConfig(duration_s=8.0).with_engine(
            "fleet", backend="jax", use_kernel=True), telemetry=hub)
        ev = next(e for e in hub.events if e["kind"] == "engine")
        assert (ev["backend"], ev["use_kernel"], ev["interpret"]) == \
            ("jax", True, True)
        assert hub.gauge("phase/jax_compile_wall_s").value > 0.0

    def test_parallel_sweep_keeps_jax_cells_in_process(self, monkeypatch):
        """A device belongs to one process: jax fleet cells never go to
        pool workers, whatever `parallel` asks for."""
        import concurrent.futures

        from repro.sim import run_policy_sweep

        def no_pool(*a, **kw):
            raise AssertionError("a jax cell was sent to a worker pool")
        monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor",
                            no_pool)
        cfg = ExperimentConfig(duration_s=8.0).with_engine(
            "fleet", backend="jax")
        grid = run_policy_sweep(cfg, policies=("linux", "proposed"),
                                parallel=2)
        assert sorted(grid) == ["linux", "proposed"]
        assert all(m.engine == "fleet" for m in grid.values())


class TestTraceColumns:
    """The engine reads the trace as columns; a `Request` list is turned
    into the same columns, so both give the same bits."""

    CFG = ExperimentConfig(duration_s=60.0).with_engine("fleet",
                                                        backend="numpy")

    @staticmethod
    def _same(a, b, ignore=()):
        a, b = a.scalars(), b.scalars()
        assert a.keys() == b.keys()
        for k in a.keys() - set(ignore):
            assert a[k] == b[k] or (a[k] != a[k] and b[k] != b[k]), k

    def test_columns_and_request_list_agree(self):
        from repro.sim.fleetsim import run_fleet_experiment
        from repro.workloads import get_scenario
        cols = get_scenario("conversation-poisson").generate_columns(
            self.CFG.rate_rps, self.CFG.duration_s, self.CFG.seed)
        as_cols = run_fleet_experiment(self.CFG, requests=cols)
        self._same(as_cols, run_fleet_experiment(
            self.CFG, requests=cols.to_requests()))
        self._same(as_cols, run_fleet_experiment(self.CFG))

    def test_scenario_with_only_generate_runs(self):
        from repro.workloads import get_scenario, register_scenario, registry

        class ListOnly:
            name = "test-list-only"

            def generate(self, rate_rps=60.0, duration_s=120.0, seed=0):
                return get_scenario("conversation-poisson").generate(
                    rate_rps, duration_s, seed)

        register_scenario("test-list-only")(ListOnly)
        try:
            got = run_experiment(self.CFG.with_scenario("test-list-only"))
        finally:
            registry._REGISTRY.pop("test-list-only", None)
        # the same trace under another name: only the labels differ
        self._same(got, run_experiment(self.CFG),
                   ignore=("scenario", "config_hash"))


class TestScaleSmoke:
    def test_200_machines(self):
        """A 200-machine fleet through the vectorized engine at test
        scale (the >= 1 h headline lives in BENCH_sim.json)."""
        cfg = ExperimentConfig(
            n_prompt=45, n_token=155, rate_rps=545.0,
            duration_s=60.0).with_engine("fleet", backend="numpy")
        res = run_experiment(cfg)
        assert res.engine == "fleet"
        assert res.completed > 0
        assert len(res.per_machine_degradation) == 200
        assert np.isfinite(res.fleet_yearly_total_kgco2eq)
