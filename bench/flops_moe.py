"""Operations and bytes of the DeepSeek-V2-style serving programs, from
their shapes and the engine's routing counts.

As in `bench/flops.py`, each function counts the work the algorithm
needs. Attention is MLA: a prompt expands its latents into per-head
keys and values once and attends in the expanded space; a decode step
absorbs the key and value up-projections into the query and the output
(DeepSeek-V2 §2.1) and attends over each slot's own latent cache. The
routed experts' work is counted from the tokens the engine routed to the
experts this chip holds (`InferenceEngine.moe_counts`), not from the
batch: experts held elsewhere and empty groups do no work here.

`m` is the configuration's published model config (Hugging Face key
names) with `router_experts` added.
"""
from __future__ import annotations

PHASES = ("prefill", "decode")
BF16 = 2


def _dims(m: dict):
    return (m["hidden_size"], m["num_attention_heads"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"], m["kv_lora_rank"])


def attn_token_flops(m: dict) -> float:
    """Per token and layer, the weight matmuls of MLA attention: the
    query, the latent, the output, and either the key and value
    up-projections of the token's own latent (prefill) or their
    absorption into the query and the attended latent (decode), which
    are the same size."""
    d, h, nope, rope, v, r = _dims(m)
    return 2.0 * (d * h * (nope + rope) + d * (r + rope) + h * v * d
                  + h * r * (nope + v))


def attn_pair_flops(m: dict, absorbed: bool) -> float:
    """Per (query, key) pair and layer, every head: scores and the
    weighted sum, over the expanded keys and values (prefill) or over
    the latent and the shared rope key (decode)."""
    _, h, nope, rope, v, r = _dims(m)
    if absorbed:
        return 2.0 * h * (r + rope + r)
    return 2.0 * h * (nope + rope + v)


def ffn_token_flops(m: dict) -> float:
    """Per token, every FFN except the routed experts: the dense layers'
    SwiGLU, and each MoE layer's router and shared experts."""
    d = m["hidden_size"]
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense
    shared = m["n_shared_experts"] * m["moe_intermediate_size"]
    return 2.0 * (n_dense * 3 * d * m["intermediate_size"]
                  + n_moe * (d * m["router_experts"] + 3 * d * shared))


def routed_token_flops(m: dict) -> float:
    """One token through one routed expert (one layer)."""
    return 2.0 * 3 * m["hidden_size"] * m["moe_intermediate_size"]


def prefill_flops(m: dict, prompt_len: int) -> float:
    """One prompt outside the routed experts: every token through every
    layer, causal attention, and the logits of the last position."""
    s, n = prompt_len, m["num_hidden_layers"]
    attn = n * (s * attn_token_flops(m)
                + s * (s + 1) / 2 * attn_pair_flops(m, False))
    return attn + s * ffn_token_flops(m) + 2.0 * m["hidden_size"] \
        * m["vocab_size"]


def decode_flops(m: dict, contexts) -> float:
    """One decode step over the active slots outside the routed experts,
    slot i attending to `contexts[i]` positions."""
    contexts = list(contexts)
    n = m["num_hidden_layers"]
    per_token = (n * attn_token_flops(m) + ffn_token_flops(m)
                 + 2.0 * m["hidden_size"] * m["vocab_size"])
    return len(contexts) * per_token + n * sum(contexts) \
        * attn_pair_flops(m, True)


def expert_flops(m: dict, tokens: float) -> float:
    """The grouped matmuls' work for `tokens` routed (token, expert)
    pairs summed over the MoE layers."""
    return tokens * routed_token_flops(m)


def expert_bytes(m: dict, tokens: float, groups: float) -> float:
    """Least bytes the grouped matmuls move: each non-empty (call,
    layer, held expert) group's three bf16 matrices once, and each
    routed token's activation in and out once."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    return groups * 3 * d * f * BF16 + tokens * 2 * d * BF16
