"""The fleet reference against the program's own float64 engine
(`backend="numpy"`), which documents the same model: at a small size the
two must agree to rounding, so the reference restates the model and not
something else."""
from __future__ import annotations

import pytest

from bench import generate
from bench.drivers import fleet
from bench.reference import fleet as ref


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_reference_matches_the_float64_engine(fleet_spec, seed):
    from repro.sim.runner import run_experiment
    config, traffic = fleet_spec["config"], fleet_spec["traffic"]
    cfg = fleet.experiment_config(config, traffic, seed)
    cfg = cfg.with_engine("fleet", backend="numpy", dt_s=config["dt_s"])
    want = fleet.scalars(run_experiment(cfg))
    trace = generate.sequential(traffic, config["duration_s"], seed)
    got = ref.simulate(config, traffic["arrival"]["rate_rps"], trace, seed)
    assert got["completed"] == want["completed"]
    for name in fleet.COMPARED:
        # float64 on both sides; only summation order differs
        assert got[name] == pytest.approx(want[name], rel=1e-9), name


def test_generator_matches_the_program_scenario(fleet_spec):
    from repro.workloads import get_scenario
    traffic = fleet_spec["traffic"]
    reqs = get_scenario(traffic["scenario"]).generate(
        rate_rps=traffic["arrival"]["rate_rps"], duration_s=30.0, seed=5)
    assert fleet._trace_mismatches(
        reqs, generate.sequential(traffic, 30.0, 5)) == 0
