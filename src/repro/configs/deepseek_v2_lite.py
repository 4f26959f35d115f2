"""DeepSeek-V2-Lite [hf:deepseek-ai/DeepSeek-V2-Lite; DeepSeek-V2,
arXiv:2405.04434]: 27 layers of MLA attention (no query compression,
`q_lora_rank` null) with a 512-wide latent cache and YaRN rope; layer 0
has a dense FFN of 10,944, layers 1-26 an MoE of 64 routed experts of
width 1,408 (top-6, softmax gates not renormalised, scaling factor 1)
beside 2 shared experts.

FULL is one chip's share of the smallest TPU v5e deployment that holds
the model (~31 GB in bf16): one host of 4 chips with expert parallelism
4 over the routed experts, attention, layer 0, the shared experts and
both embeddings replicated on every chip. This chip holds routed
experts 0-15 (`num_experts` 16 of `router_experts` 64); the router
scores all 64 and the layer computes its own experts' part. The
analytic `param_count` is of the whole model.

Decode uses the absorbed MLA form (`mla_absorb`), as DeepSeek-V2 §2.1
does at inference. HF's rope permutes the rope dimensions from
interleaved to half-split pairs; that is a fixed reordering of the
rope columns of `wq` and `kv_down`, so the program keeps the half-split
convention.
"""
import dataclasses

from repro.models.config import ModelConfig

FULL = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    attn_type="mla",
    rope_theta=10000.0,
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    num_experts=16,
    experts_per_token=6,
    router_experts=64,
    expert_offset=0,
    expert_d_ff=1408,
    shared_experts=2,
    first_dense_layers=1,
    moe_renormalize=False,
    moe_gate_scale=1.0,
    mla_absorb=True,
    norm_eps=1e-6,
    citation="hf:deepseek-ai/DeepSeek-V2-Lite (config.json); arXiv:2405.04434",
)

# one dense layer and two MoE layers; 4 of 8 routed experts held
SMOKE = dataclasses.replace(
    FULL, num_layers=3, d_model=256, num_heads=4, num_kv_heads=4,
    d_ff=512, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    num_experts=4, router_experts=8, experts_per_token=2, expert_d_ff=64,
    shared_experts=1, vocab_size=1000, vocab_pad_mult=128)
