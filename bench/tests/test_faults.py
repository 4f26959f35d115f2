"""A run whose timed path is broken underneath must come out with
`correct` false: for each fault the cells can have (`bench/calibrate.py`
plants them), the cell runs at a small size on the host CPU (the look
for a chip skipped) with the program patched, and the comparison with
the reference must catch it.

The fleet runs are held to the cell's own limits. The serving runs use
the smoke-size model, whose logits are smaller and whose sound gaps
smaller than at the published widths, so they are held to a limit set
from smoke-size readings (`bench/tests/limits/`)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import calibrate
from bench.drivers import fleet, serve

SEED = 2**31 + 23
SMOKE_LIMITS = Path(__file__).resolve().parent / "limits"


@pytest.fixture
def smoke_serve(serve_spec, monkeypatch):
    monkeypatch.setattr(serve, "LIMITS_DIR", SMOKE_LIMITS)
    return lambda: serve.run(serve_spec, seed=SEED, seconds=4.0, trace=False)


@pytest.fixture
def small_fleet(fleet_spec):
    return lambda: fleet.run(fleet_spec, seed=SEED, seconds=0.0, trace=False)


def test_sound_runs_are_correct(small_fleet, smoke_serve):
    assert small_fleet()["correct"]
    assert smoke_serve()["correct"]


@pytest.mark.parametrize("fault", sorted(calibrate.FAULTS))
def test_fleet_fault_is_caught(small_fleet, fault):
    with calibrate.FAULTS[fault]():
        assert not small_fleet()["correct"]


@pytest.mark.parametrize("fault", sorted(calibrate.FAULTS))
def test_serve_fault_is_caught(smoke_serve, fault):
    with calibrate.FAULTS[fault]():
        assert not smoke_serve()["correct"]
