"""Heterogeneous hardware subsystem (`repro.hardware` + the fleet
threading through config / cluster / fleetsim / routing).

Pins the PR's contracts: the SKU catalog behind the shared seventh
registry axis, fleet-spec resolution (`uniform` -> None sentinel, spec
strings, explicit rows), the bit-exactness guarantee — a whole-fleet
reference-SKU run matches the uniform default scalar-for-scalar on both
engines, and fingerprints ignore the default fleet — the ragged
padded-mask fleet engine (numpy vs jax backend parity, event-engine
closeness, the mixed-Vdd refusal wording), the FleetView hardware
columns, and the acceptance scenario: `generation-aware` routing beats
`jsq` on fleet yearly carbon over a mixed 2-SKU fleet with p99 within
10%.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.carbon.base import (BASELINE_LIFESPAN_YEARS,
                               CPU_EMBODIED_KGCO2EQ)
from repro.carbon.intensity import ConstantIntensity, ShiftedIntensity
from repro.core import aging
from repro.hardware import (
    CPU_IMPACT_KGCO2EQ,
    HardwareSKU,
    REFERENCE_CPU_TDP_W,
    available_skus,
    canonical_fleet_name,
    canonical_sku_name,
    embodied_carbon,
    get_cpu_impact,
    get_sku,
    register_sku,
    resolve_fleet,
    sku_carbon_model,
)
from repro.hardware.registry import _REGISTRY
from repro.sim import Cluster, ExperimentConfig, FleetView
from repro.sim.routing import GenerationAwareRouter, get_router
from repro.sim.runner import run_experiment


# ---------------------------------------------------------------------- #
# SKU catalog + registry axis
# ---------------------------------------------------------------------- #
class TestSKURegistry:
    def test_builtins_registered(self):
        assert {"xeon-40c", "legacy-18c", "xeon-28c", "epyc-64c",
                "epyc-128c"} <= set(available_skus())

    def test_canonical_name(self):
        assert canonical_sku_name("Epyc_64c") == "epyc-64c"
        assert get_sku("XEON_40C").name == "xeon-40c"

    def test_fresh_instance_with_opts(self):
        a = get_sku("epyc-64c")
        b = get_sku("epyc-64c", num_cores=32)
        assert a is not b
        assert a.num_cores == 64 and b.num_cores == 32

    def test_unknown_sku_raises(self):
        with pytest.raises(KeyError, match="unknown hardware SKU"):
            get_sku("threadripper-9000")

    def test_decorator_rejects_non_sku(self):
        with pytest.raises(TypeError) as err:
            register_sku("bogus")(object)
        assert err.value.args[0] == (
            "@register_sku('bogus') expects a HardwareSKU subclass, "
            f"got {object!r}")

    def test_custom_sku_registers(self):
        @register_sku("test-4c")
        @dataclasses.dataclass(frozen=True)
        class Tiny(HardwareSKU):
            num_cores: int = 4
        try:
            assert get_sku("test-4c").num_cores == 4
        finally:
            _REGISTRY.pop("test-4c", None)

    def test_field_validation(self):
        with pytest.raises(ValueError, match="num_cores"):
            HardwareSKU(num_cores=0)
        with pytest.raises(ValueError, match="vdd must exceed vth"):
            HardwareSKU(vdd=0.4, vth=0.45)


class TestEmbodiedImpactTable:
    def test_reference_entry_matches_legacy_constant(self):
        assert get_cpu_impact("reference-xeon-40c") == CPU_EMBODIED_KGCO2EQ

    def test_unknown_model_lists_known(self):
        with pytest.raises(KeyError, match="epyc-9554-64c"):
            get_cpu_impact("pentium-ii")

    def test_amortization(self):
        total = CPU_IMPACT_KGCO2EQ["epyc-9554-64c"]
        full_life_h = BASELINE_LIFESPAN_YEARS * 24.0 * 365.0
        assert embodied_carbon("epyc-9554-64c", full_life_h) == \
            pytest.approx(total)
        assert embodied_carbon("epyc-9554-64c", full_life_h,
                               cpu_usage=0.5) == pytest.approx(total / 2)
        with pytest.raises(ValueError, match="duration_used_h"):
            embodied_carbon("epyc-9554-64c", -1.0)

    def test_reference_sku_is_legacy_fleet_machine(self):
        """The catalog reference reproduces every pre-heterogeneity
        fleet-wide constant — the anchor of the bit-exactness story."""
        sku = get_sku("xeon-40c")
        assert sku.num_cores == 40
        assert sku.embodied_kg == CPU_EMBODIED_KGCO2EQ
        assert sku.cpu_tdp_w == REFERENCE_CPU_TDP_W
        assert sku.power_scale == 1.0
        assert sku.base_life_years == BASELINE_LIFESPAN_YEARS
        # identity, not equality: the settler groups machines by params
        assert sku.aging_params() is aging.DEFAULT_PARAMS

    def test_non_reference_aging_params_resolve_k(self):
        p = get_sku("legacy-18c").aging_params()
        assert p.vth == 0.48 and p is not aging.DEFAULT_PARAMS
        assert p.K > 0.0


# ---------------------------------------------------------------------- #
# fleet-spec resolution + inventory
# ---------------------------------------------------------------------- #
class TestFleetResolution:
    def test_uniform_resolves_to_none_sentinel(self):
        assert resolve_fleet("uniform", None, 22) is None
        assert resolve_fleet("Uniform", {}, 3) is None

    def test_bare_sku_name_fills_fleet(self):
        inv = resolve_fleet("epyc-64c", None, 3)
        assert inv.sku_names == ("epyc-64c",) * 3
        assert inv.num_cores == (64, 64, 64)
        assert not inv.ragged

    def test_spec_string_with_rest(self):
        inv = resolve_fleet("xeon-40c:1+epyc-64c:rest", None, 4)
        assert inv.sku_names == ("xeon-40c",) + ("epyc-64c",) * 3
        assert inv.ragged
        assert inv.max_cores == 64
        assert inv.total_cores == 40 + 3 * 64

    def test_canonical_fleet_name_canonicalizes_spec_parts(self):
        assert canonical_fleet_name("Xeon_40c:1+EPYC_64C:rest") == \
            "xeon-40c:1+epyc-64c:rest"

    def test_mixed_rows_with_nested_opts(self):
        inv = resolve_fleet(
            "mixed", {"rows": (("xeon-40c", 1),
                               ("epyc-64c", 2, {"t0_s": 3600.0}))}, 3)
        assert inv.t0_s == (0.0, 3600.0, 3600.0)
        assert inv.generations == (3, 4, 4)

    def test_row_count_must_match_n_machines(self):
        with pytest.raises(ValueError, match="use count='rest' to fill"):
            resolve_fleet("xeon-40c:2", None, 22)
        with pytest.raises(ValueError, match="n_machines=1"):
            resolve_fleet("xeon-40c:2", None, 1)

    def test_single_rest_row_only(self):
        with pytest.raises(ValueError, match="only one fleet row"):
            resolve_fleet("xeon-40c:rest+epyc-64c:rest", None, 4)

    def test_bad_spec_segment(self):
        with pytest.raises(ValueError, match="bad fleet spec segment"):
            resolve_fleet("xeon-40c:", None, 3)

    def test_shared_dynamics_params_identity_on_reference(self):
        inv = resolve_fleet("xeon-40c", None, 3)
        assert inv.shared_dynamics_params() is aging.DEFAULT_PARAMS

    def test_shared_dynamics_allows_f_nominal_spread(self):
        inv = resolve_fleet("xeon-28c:1+epyc-64c:rest", None, 3)
        assert inv.shared_dynamics_params() is inv.aging_params[0]

    def test_shared_dynamics_rejects_mixed_vdd_vth(self):
        inv = resolve_fleet("legacy-18c:1+xeon-40c:rest", None, 3)
        with pytest.raises(ValueError) as err:
            inv.shared_dynamics_params()
        assert err.value.args[0] == (
            "fleet engine cannot vectorize fleets mixing NBTI operating "
            "points (Vdd/Vth); run it under engine='event'")

    def test_per_sku_carbon_models(self):
        inv = resolve_fleet("xeon-40c:1+epyc-64c:rest", None, 3)
        models = inv.carbon_models("linear-extension", None)
        assert len(models) == 3
        # same SKU shares one instance; different SKUs price differently
        assert models[1] is models[2] and models[0] is not models[1]
        ref = models[0].lifetime(0.02, 0.01)
        big = models[1].lifetime(0.02, 0.01)
        assert big.yearly_kgco2eq > ref.yearly_kgco2eq

    def test_intensity_for_phase_shift(self):
        inv = resolve_fleet(
            "mixed", {"rows": (("xeon-40c", 1),
                               ("xeon-40c", "rest", {"t0_s": 7200.0}))}, 3)
        base = ConstantIntensity()
        assert inv.intensity_for(0, base) is base
        shifted = inv.intensity_for(1, base)
        assert isinstance(shifted, ShiftedIntensity)

    def test_sku_carbon_model_embodied_override(self):
        sku = get_sku("epyc-64c")
        m = sku_carbon_model(sku, "linear-extension", {})
        ref = sku_carbon_model(get_sku("xeon-40c"), "linear-extension", {})
        est, est_ref = m.lifetime(0.02, 0.01), ref.lifetime(0.02, 0.01)
        assert est.yearly_kgco2eq / est_ref.yearly_kgco2eq == \
            pytest.approx(sku.embodied_kg / CPU_EMBODIED_KGCO2EQ)


# ---------------------------------------------------------------------- #
# config axis: fingerprint backward-compat
# ---------------------------------------------------------------------- #
class TestConfigFleetAxis:
    def test_with_fleet_and_canonicalization(self):
        cfg = ExperimentConfig(fleet="EPYC_64C")
        assert cfg.fleet == "epyc-64c"
        cfg2 = ExperimentConfig().with_fleet(
            "mixed", rows=(("xeon-40c", 1), ("epyc-64c", "rest")))
        assert cfg2.fleet == "mixed"
        assert dict(cfg2.fleet_opts)["rows"]

    def test_uniform_fleet_fingerprint_invariant(self):
        """Pre-hardware configs hash identically after the fleet axis
        landed — pinned so goldens survive the subsystem."""
        assert ExperimentConfig().fingerprint() == \
            ExperimentConfig(fleet="Uniform").fingerprint() == \
            "8335264983f5"

    def test_non_uniform_fleet_changes_fingerprint(self):
        cfg = ExperimentConfig()
        assert cfg.with_fleet("epyc-64c").fingerprint() != \
            cfg.fingerprint()
        assert cfg.with_fleet("xeon-40c:1+epyc-64c:rest").fingerprint() \
            != cfg.with_fleet("epyc-64c").fingerprint()


# ---------------------------------------------------------------------- #
# bit-exactness: whole-fleet reference SKU == uniform default
# ---------------------------------------------------------------------- #
class TestUniformBitExactness:
    CFG = ExperimentConfig(duration_s=6.0, rate_rps=30.0, seed=0,
                           n_prompt=1, n_token=2)

    @staticmethod
    def _assert_scalars_match(uni, ref_fleet):
        s0, s1 = uni.scalars(), ref_fleet.scalars()
        assert set(s0) - {"fleet"} <= set(s1)
        for k in set(s0) | set(s1):
            if k in ("fleet", "config_hash"):
                continue
            assert s0.get(k) == s1.get(k), k

    def test_event_engine(self):
        uni = run_experiment(self.CFG)
        ref = run_experiment(self.CFG.with_fleet("xeon-40c"))
        self._assert_scalars_match(uni, ref)
        assert uni.per_machine_degradation == ref.per_machine_degradation
        assert uni.per_machine_sku is None
        assert ref.per_machine_sku == ("xeon-40c",) * 3

    def test_fleet_engine(self):
        uni = run_experiment(
            self.CFG.with_engine("fleet", backend="numpy"))
        ref = run_experiment(
            self.CFG.with_fleet("xeon-40c").with_engine(
                "fleet", backend="numpy"))
        self._assert_scalars_match(uni, ref)


# ---------------------------------------------------------------------- #
# ragged fleet engine
# ---------------------------------------------------------------------- #
class TestRaggedFleetEngine:
    CFG = ExperimentConfig(duration_s=120.0, rate_rps=30.0, seed=2,
                           n_prompt=1, n_token=2,
                           fleet="xeon-28c:2+epyc-64c:1")

    def test_numpy_run_is_sane(self):
        r = run_experiment(self.CFG.with_engine("fleet", backend="numpy"))
        assert r.fleet == "xeon-28c:2+epyc-64c:1"
        assert r.per_machine_sku == ("xeon-28c", "xeon-28c", "epyc-64c")
        assert len(r.per_machine_degradation) == 3
        assert np.isfinite(r.fleet_yearly_total_kgco2eq)
        assert r.fleet_yearly_total_kgco2eq > 0.0
        assert 0.0 <= r.availability <= 1.0

    def test_deterministic(self):
        cfg = self.CFG.with_engine("fleet", backend="numpy")
        a, b = run_experiment(cfg), run_experiment(cfg)
        assert a.scalars() == b.scalars()

    def test_close_to_event_engine(self):
        """The vectorized surrogate tracks the per-task reference on a
        mixed fleet (same contract the uniform goldens pin)."""
        ev = run_experiment(self.CFG)
        fl = run_experiment(self.CFG.with_engine("fleet",
                                                 backend="numpy"))
        assert fl.fleet_yearly_total_kgco2eq == pytest.approx(
            ev.fleet_yearly_total_kgco2eq, rel=5e-3)

    def test_numpy_vs_jax_backend_parity(self):
        r_np = run_experiment(self.CFG.with_engine("fleet",
                                                   backend="numpy"))
        r_jx = run_experiment(self.CFG.with_engine("fleet",
                                                   backend="jax"))
        assert r_jx.fleet_yearly_total_kgco2eq == pytest.approx(
            r_np.fleet_yearly_total_kgco2eq, rel=1e-3)
        assert r_jx.availability == pytest.approx(r_np.availability,
                                                  abs=1e-5)
        for a, b in zip(r_np.per_machine_degradation,
                        r_jx.per_machine_degradation):
            assert b == pytest.approx(a, rel=1e-2, abs=1e-6)

    def test_mixed_vdd_fleet_refused(self):
        cfg = self.CFG.with_fleet("legacy-18c:1+xeon-28c:rest")
        with pytest.raises(ValueError, match="mixing NBTI operating "
                           r"points \(Vdd/Vth\); run it under "
                           "engine='event'"):
            run_experiment(cfg.with_engine("fleet", backend="numpy"))

    def test_mixed_vdd_fleet_runs_under_event_engine(self):
        cfg = dataclasses.replace(self.CFG, duration_s=6.0,
                                  fleet="legacy-18c:1+xeon-28c:rest")
        r = run_experiment(cfg)
        assert r.per_machine_sku[0] == "legacy-18c"
        assert np.isfinite(r.fleet_yearly_total_kgco2eq)

    def test_faults_on_ragged_fleet(self):
        cfg = self.CFG.with_engine("fleet", backend="numpy")
        cfg = dataclasses.replace(
            cfg, duration_s=60.0).with_fault_model("machine-crash",
                                                   mttf_s=20.0,
                                                   reboot_s=10.0)
        r = run_experiment(cfg)
        assert r.machine_crashes > 0
        assert 0.0 < r.availability < 1.0


# ---------------------------------------------------------------------- #
# FleetView hardware columns
# ---------------------------------------------------------------------- #
class TestFleetViewHardwareColumns:
    def test_uniform_defaults(self):
        fleet = Cluster(ExperimentConfig(n_prompt=1, n_token=2)).fleet
        assert isinstance(fleet, FleetView)
        assert fleet.generations().tolist() == [0, 0, 0]
        assert fleet.core_counts().tolist() == [40, 40, 40]
        assert fleet.sku_names() == (None, None, None)
        assert fleet.pending_prompt_tokens == 0.0
        assert fleet.pending_decode_tokens == 0.0

    def test_mixed_fleet_columns(self):
        cfg = ExperimentConfig(n_prompt=1, n_token=2,
                               fleet="xeon-28c:1+epyc-64c:1+epyc-128c:1")
        fleet = Cluster(cfg).fleet
        assert fleet.generations().tolist() == [2, 4, 5]
        assert fleet.core_counts().tolist() == [28, 64, 128]
        assert fleet.sku_names() == ("xeon-28c", "epyc-64c", "epyc-128c")
        assert fleet.prompt_generations().tolist() == [2]
        assert fleet.token_generations().tolist() == [4, 5]


# ---------------------------------------------------------------------- #
# generation-aware router
# ---------------------------------------------------------------------- #
class _StubAging:
    def __init__(self, deg):
        self.mean_degradation = deg


class _StubFleet:
    """Minimal FleetView stand-in for unit-testing selection logic."""

    def __init__(self, prompt_loads=(), token_loads=(), prompt_gens=(),
                 token_gens=(), token_deg=(), pending_prompt=0.0,
                 pending_decode=0.0):
        self._pl = np.asarray(prompt_loads, dtype=float)
        self._tl = np.asarray(token_loads, dtype=float)
        self._pg = np.asarray(prompt_gens, dtype=np.int64)
        self._tg = np.asarray(token_gens, dtype=np.int64)
        self._deg = tuple(token_deg)
        self.pending_prompt_tokens = pending_prompt
        self.pending_decode_tokens = pending_decode

    def prompt_depths(self):
        return self._pl

    def token_loads(self):
        return self._tl

    def prompt_generations(self):
        return self._pg

    def token_generations(self):
        return self._tg

    def token_aging(self, indices=None):
        idx = range(len(self._deg)) if indices is None else indices
        return tuple(_StubAging(self._deg[int(i)]) for i in idx)


class TestGenerationAwareRouter:
    def test_registered(self):
        assert isinstance(get_router("Generation_Aware"),
                          GenerationAwareRouter)

    def test_opts_validated(self):
        with pytest.raises(ValueError, match="token_slack must be >= 0"):
            GenerationAwareRouter(token_slack=-1)
        with pytest.raises(ValueError, match="long_prompt_tokens"):
            GenerationAwareRouter(long_prompt_tokens=0.0)

    def test_prompt_prefers_newest_generation(self):
        fleet = _StubFleet(prompt_loads=[1, 1, 1], prompt_gens=[2, 4, 3])
        assert GenerationAwareRouter().select_prompt(fleet) == 1

    def test_token_prefers_oldest_then_most_aged(self):
        fleet = _StubFleet(token_loads=[3, 2, 3], token_gens=[1, 4, 1],
                           token_deg=[0.01, 0.0, 0.03])
        # slack 2 admits all; oldest gen = {0, 2}; most aged wins
        assert GenerationAwareRouter().select_token(fleet) == 2

    def test_long_prompt_widens_feasibility(self):
        fleet = _StubFleet(prompt_loads=[0, 2], prompt_gens=[2, 4],
                           pending_prompt=512.0)
        r = GenerationAwareRouter()
        # short prompt: only the idle old machine is feasible
        short = _StubFleet(prompt_loads=[0, 2], prompt_gens=[2, 4])
        assert r.select_prompt(short) == 0
        # long prompt: extra slack reaches the loaded new-gen machine
        assert r.select_prompt(fleet) == 1

    def test_long_decode_widens_feasibility(self):
        r = GenerationAwareRouter()
        short = _StubFleet(token_loads=[0, 3], token_gens=[4, 1],
                           token_deg=[0.0, 0.02])
        assert r.select_token(short) == 0
        long = _StubFleet(token_loads=[0, 3], token_gens=[4, 1],
                          token_deg=[0.0, 0.02], pending_decode=128.0)
        assert r.select_token(long) == 1

    def test_uniform_fleet_end_to_end(self):
        cfg = ExperimentConfig(duration_s=6.0, rate_rps=30.0, seed=0,
                               n_prompt=1, n_token=2,
                               router="generation-aware")
        r = run_experiment(cfg)
        assert r.completed > 0
        assert r.scalars() == run_experiment(cfg).scalars()

    def test_beats_jsq_on_mixed_fleet_carbon(self):
        """Acceptance pin: decode pinned to old silicon + prefill to the
        new SKU lowers fleet yearly embodied carbon vs jsq, within 10%
        of its p99 latency."""
        base = ExperimentConfig(duration_s=30.0, rate_rps=20.0, seed=1,
                                n_prompt=1, n_token=2,
                                fleet="xeon-28c:2+epyc-64c:1")
        jsq = run_experiment(base.with_router("jsq"))
        gen = run_experiment(base.with_router("generation-aware"))
        assert gen.fleet_yearly_total_kgco2eq < \
            jsq.fleet_yearly_total_kgco2eq
        assert gen.p99_latency_s <= 1.10 * jsq.p99_latency_s
