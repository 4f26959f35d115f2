"""The MoE serving driver (`bench/drivers/serve_moe.py`) at the smoke
size of DeepSeek-V2-Lite on the host CPU: a sound run is correct and
reads both new per-layer metrics, a planted fault is caught, and the
float8 control fails the smoke limit that sound runs keep.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import copy
import importlib.util
import json
from pathlib import Path

import pytest

from bench import calibrate
from bench.drivers import serve_moe

CELL = "deepseek-v2-lite.conversation"
SEED = 2**31 + 23
SMOKE_LIMITS = Path(__file__).resolve().parent / "limits"
METRICS = Path(__file__).resolve().parents[1] / "metrics"


def smoke_moe_spec() -> dict:
    """The cell with the repository's smoke-size DeepSeek-V2-Lite (one
    dense and two MoE layers, 4 of 8 routed experts held, top-2, one
    shared expert), 4 slots of 512 tokens, prompts of 32 to 128 tokens
    and up to 48 output tokens."""
    from bench.run import load_cell
    from repro.configs import get_smoke_config
    c = get_smoke_config("deepseek-v2-lite")
    spec = copy.deepcopy(load_cell(CELL))
    config = spec["config"]
    config["smoke"] = True
    config.update(
        hidden_size=c.d_model, num_hidden_layers=c.num_layers,
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        intermediate_size=c.d_ff, moe_intermediate_size=c.expert_d_ff,
        n_routed_experts=c.num_experts, num_experts_per_tok=c.experts_per_token,
        n_shared_experts=c.shared_experts, vocab_size=c.vocab_size,
        kv_lora_rank=c.kv_lora_rank, qk_nope_head_dim=c.qk_nope_dim,
        qk_rope_head_dim=c.qk_rope_dim, v_head_dim=c.v_head_dim)
    config["share"].update(router_experts=c.router_experts,
                           expert_offset=c.expert_offset)
    config["serving"].update(max_batch=4, max_len=512)
    spec["traffic"]["mix"].update(input_max=128, input_round=32,
                                  output_max=48)
    spec["traffic"]["arrival"]["rate_rps"] = 2.0
    return spec


@pytest.fixture
def spec():
    return smoke_moe_spec()


@pytest.fixture
def smoke_limit(monkeypatch):
    monkeypatch.setattr(serve_moe, "LIMITS_DIR", SMOKE_LIMITS)
    return json.loads((SMOKE_LIMITS / f"{CELL}.json").read_text())[
        "served_logit_gap"]


def _reader(name: str):
    path = METRICS / f"{name}.py"
    s = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def test_traced_run_is_correct_and_counts_its_work(spec, smoke_limit):
    out = serve_moe.run(spec, seed=SEED, seconds=4.0, trace=True)
    assert out["correct"], out["checks"]
    obs = out["observations"]
    moe = obs["moe"]
    assert sum(moe["tokens"]) > 0 and moe["groups"][1] > 0
    # each decode step routes its live rows' tokens k times, over every
    # routed expert; the held half of them, about, lands here
    k, layers = 2, 2
    live = sum(len(c) for c in obs["decode_contexts"])
    assert moe["tokens"][1] <= k * layers * live
    # the trace of a CPU run has no device plane: the readers stand down
    obs["device_kind"] = "TPU v5 lite"
    for name in ("moe_experts.roofline", "moe_model_step.mfu"):
        assert _reader(name).read(obs) is None


@pytest.mark.parametrize("fault", sorted(calibrate.FAULTS))
def test_fault_is_caught(spec, smoke_limit, fault):
    with calibrate.FAULTS[fault]():
        assert not serve_moe.run(spec, seed=SEED, seconds=4.0,
                                 trace=False)["correct"]


def test_fp8_control_fails_the_limit(spec, smoke_limit):
    row = serve_moe.readings(spec, 2**31 + 31, control=True, seconds=4.0)
    assert row["program"]["served_logit_gap"] <= smoke_limit, row
    assert row["control"]["served_logit_gap"] > smoke_limit, row


def test_sweep_stops_past_the_knee(spec):
    rows = serve_moe.sweep(spec, [2.0, 64.0], 2.0, [2**31 + 5],
                           control=False, on_row=lambda line: None)
    assert [r["rate_rps"] for r in rows] == [2.0, 64.0]
    low, high = rows
    assert not low["past_knee"] and low["tracks"] > serve_moe.TRACKS, low
    assert high["past_knee"], high
    assert 0 < low["live_slots_mean"] <= high["live_slots_mean"] <= 4
