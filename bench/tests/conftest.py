"""Small-size fixtures for the benchmark's own tests (host CPU):

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

FLEET_CELL = "fleet-splitwise-200.peak"
SERVE_CELL = "minicpm3-4b.conversation"


def _spec(cell: str) -> dict:
    from bench.run import load_cell
    return copy.deepcopy(load_cell(cell))


def small_fleet_spec(duration_s: float = 60.0, n_prompt: int = 9,
                     n_token: int = 31) -> dict:
    """The fleet cell at a short horizon and a fifth of its machines."""
    spec = _spec(FLEET_CELL)
    spec["config"].update(duration_s=duration_s, n_prompt=n_prompt,
                          n_token=n_token)
    spec["traffic"]["arrival"]["rate_rps"] *= (n_prompt + n_token) / 200
    return spec


def smoke_serve_spec() -> dict:
    """The serving cell with the repository's smoke-size MiniCPM3 (two
    layers, narrow widths), 4 slots of 512 tokens, prompts of 32 to 128
    tokens and up to 48 output tokens."""
    from repro.configs import get_smoke_config
    c = get_smoke_config("minicpm3-4b")
    spec = _spec(SERVE_CELL)
    spec["config"]["smoke"] = True
    spec["config"]["model"].update(
        hidden_size=c.d_model, num_hidden_layers=c.num_layers,
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        intermediate_size=c.d_ff, vocab_size=c.vocab_size,
        q_lora_rank=c.q_lora_rank, kv_lora_rank=c.kv_lora_rank,
        qk_nope_head_dim=c.qk_nope_dim, qk_rope_head_dim=c.qk_rope_dim,
        v_head_dim=c.v_head_dim)
    spec["config"]["serving"].update(max_batch=4, max_len=512)
    spec["traffic"]["mix"].update(input_max=128, input_round=32,
                                  output_max=48)
    spec["traffic"]["arrival"]["rate_rps"] = 2.0
    return spec


@pytest.fixture
def fleet_spec():
    return small_fleet_spec()


@pytest.fixture
def serve_spec():
    return smoke_serve_spec()


def limits(cell: str) -> dict:
    return json.loads((ROOT / "bench" / "limits" / f"{cell}.json").read_text())
