"""The control of each cell, the reference one precision below what
the configuration states, must fail the cell's comparison. At the
cell's own size it runs on the chip through `bench/calibrate.py`
(readings in PERF.md); here it runs at a size the host CPU holds."""
from __future__ import annotations

import json
from pathlib import Path

from bench import calibrate
from conftest import FLEET_CELL, limits, small_fleet_spec

SMOKE_LIMITS = Path(__file__).resolve().parent / "limits"


def test_fleet_bfloat16_control_fails_a_limit():
    spec = small_fleet_spec(duration_s=300.0)
    row = calibrate.fleet_readings(spec, 2**31 + 31, control=True)
    lim = limits(FLEET_CELL)
    assert row["correct"]
    assert any(row["control"][k] > lim[k] for k in row["control"]), row


def test_serve_fp8_control_fails_its_limit(serve_spec):
    row = calibrate.serve_readings(serve_spec, 2**31 + 31, control=True,
                                   seconds=4.0)
    lim = json.loads((SMOKE_LIMITS / "minicpm3-4b.conversation.json")
                     .read_text())["served_logit_gap"]
    assert row["program"]["served_logit_gap"] <= lim, row
    assert row["control"]["served_logit_gap"] > lim, row
