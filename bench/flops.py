"""Operations and bytes of each measured program, from its shapes.

Each function counts the work the algorithm needs, not what a
particular implementation happens to compute: a later change that skips
wasted work then shows as a higher share of the peak, never as a lower
count. Peaks per chip are in `bench/peaks.json`, keyed by
`device_kind`; a device missing from the table is an error.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table[device_kind]


# ------------------------------ fleet scan ------------------------------ #
@dataclasses.dataclass(frozen=True)
class FleetShape:
    n_prompt: int
    n_token: int
    num_cores: int
    n_macro: int
    steps_per_period: int

    @property
    def n_machines(self) -> int:
        return self.n_prompt + self.n_token


AGING_LANE_BLOCK = 1024       # the aging kernel pads its lanes to this
AGING_ARRAYS = 5              # dvth, temperature, stress, tau in; dvth out
AGING_FLOPS_PER_LANE = 16     # ADF (2 exp, 1 pow) and the recursion (2 pow)


def fleet_scan_flops(s: FleetShape) -> float:
    """Arithmetic of one experiment's macro scan: per micro step the
    fluid queues (about 20 operations per prompt machine, 35 per token
    machine, 20 per machine for the CPU layer and the residency sums)
    and 3 per core for the busy-time spread; per idling period 5 per
    core for the settled speeds, the two aging settlements, and the
    double rank selection of the gating (4 sorts of N log2 N
    comparisons)."""
    M, N = s.n_machines, s.num_cores
    micro = 20 * s.n_prompt + 35 * s.n_token + 20 * M + 3 * M * N
    macro = (5 * M * N + 2 * AGING_FLOPS_PER_LANE * M * N
             + 4 * M * N * math.log2(max(N, 2)))
    return s.n_macro * (s.steps_per_period * micro + macro)


def aging_update_bytes(s: FleetShape) -> tuple[float, int]:
    """(bytes per kernel call, calls per experiment): four float32
    inputs and one output over the padded lanes; two settlements
    (busy, then idle share) per idling period."""
    lanes = -(-s.n_machines * s.num_cores // AGING_LANE_BLOCK) \
        * AGING_LANE_BLOCK
    return float(AGING_ARRAYS * lanes * 4), 2 * s.n_macro


def aging_update_flops(s: FleetShape) -> float:
    lanes = -(-s.n_machines * s.num_cores // AGING_LANE_BLOCK) \
        * AGING_LANE_BLOCK
    return float(AGING_FLOPS_PER_LANE * lanes)


# ---------------------------- MLA decoder model ------------------------- #
def mla_token_flops(m: dict) -> float:
    """Per token and layer: the weight matmuls of MLA attention (q down
    and up, kv down, the k and v up-projections of the new latent, the
    output) and of the SwiGLU MLP, 2 operations per multiply-add."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    macs = (d * m["q_lora_rank"] + m["q_lora_rank"] * h * qk
            + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * h * (m["qk_nope_head_dim"]
                                       + m["v_head_dim"])
            + h * m["v_head_dim"] * d
            + 3 * d * m["intermediate_size"])
    return 2.0 * macs


def mla_pair_flops(m: dict) -> float:
    """Per (query, key) pair and layer: scores over the nope and rope
    dimensions and the weighted sum of values, every head."""
    h = m["num_attention_heads"]
    return 2.0 * h * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                      + m["v_head_dim"])


def prefill_flops(m: dict, prompt_len: int) -> float:
    """One prompt: every token through every layer, causal attention
    (S (S + 1) / 2 pairs), and the logits of the last position."""
    s = prompt_len
    per_layer = s * mla_token_flops(m) + s * (s + 1) / 2 * mla_pair_flops(m)
    return (m["num_hidden_layers"] * per_layer
            + 2.0 * m["hidden_size"] * m["vocab_size"])


def decode_flops(m: dict, contexts) -> float:
    """One decode step over the active slots, slot i attending to
    `contexts[i]` positions (its cache plus the new token)."""
    contexts = list(contexts)
    per_layer = (len(contexts) * mla_token_flops(m)
                 + sum(contexts) * mla_pair_flops(m))
    return (m["num_hidden_layers"] * per_layer
            + len(contexts) * 2.0 * m["hidden_size"] * m["vocab_size"])


def decode_bytes(m: dict, contexts, weight_bytes: float) -> float:
    """Least bytes one decode step moves: every weight once and each
    active slot's latent cache (bf16) once."""
    latent = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    return weight_bytes + 2.0 * latent * m["num_hidden_layers"] * sum(contexts)
