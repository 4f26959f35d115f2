"""Plain float32 reference of a DeepSeek-V2-Lite decoder holding a share
of its routed experts, written from the equations of DeepSeek-V2
(arXiv:2405.04434) and its published config, independent of
`repro.models`.

Per layer, on the residual stream h (all float32, matmuls at "highest"
precision):

    x      = RMSNorm(h; ln1)
    q      = x W_q                                  -> (S, H, nope + rope)
    c, r   = split(x W_kvd, [kv_lora_rank]);  c = RMSNorm(c; kv_ln)
    q_rope, r = YaRN-RoPE(q_rope), YaRN-RoPE(r)     half-split rotation
    k      = [c W_ku | r]   (r shared by every head),   v = c W_vu
    h     += softmax_causal(q k^T * s) v W_o,  s = mscale^2 / sqrt(nope + rope)
    x      = RMSNorm(h; ln2)
    h     += (silu(x W_gate) * (x W_up)) W_down                 (dense layers)
    h     += sum over the held experts e among x's top-k of
             g_e (silu(x W_gate,e) * (x W_up,e)) W_down,e
             + (silu(x S_gate) * (x S_up)) S_down                (MoE layers)

with p = softmax(x W_router) over every routed expert, the top-k of p
chosen greedily, g = those k probabilities (renormalised to sum to 1
only where `norm_topk_prob`) times `routed_scaling_factor`. The experts
held elsewhere add nothing: the reference holds the same share as the
program. Then logits = RMSNorm(h; final_ln) E_out^T over the first
`vocab_size` rows.

YaRN (`rope_scaling`): frequencies of the dimensions that turn fewer
than `beta_slow` times over `original_max_position_embeddings` are
divided by `factor`, those turning more than `beta_fast` times are kept,
with a linear ramp between; cos and sin are scaled by
mscale(factor, mscale) / mscale(factor, mscale_all_dim), and the softmax
scale by mscale(factor, mscale_all_dim)^2, mscale(s, m) = 0.1 m ln s + 1.
HF's implementation first permutes each rope vector from interleaved to
half-split pairs; that is a fixed reordering of the rope columns of W_q
and W_kvd, which random weights do not see, so neither side does it.

The weights are drawn layer by layer from the seed inside scans over
the layers and, within an MoE layer, over the held experts
(`bench/weights_moe.py`), so only one layer's float32 weights exist at
a time. With `fp8`, every matrix (router and embeddings too) is rounded
to float8 (e4m3) with a scale per output channel before use: the
control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights_moe
from bench.reference.minicpm3 import fp8_round, rms_norm

Q_BLOCK = 1024


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(m: dict):
    """(inverse frequencies (rope/2,), cos-sin scale, softmax scale)."""
    d = m["qk_rope_head_dim"]
    theta = m["rope_theta"]
    ys = m["rope_scaling"]
    f, orig = ys["factor"], ys["original_max_position_embeddings"]

    def dim_of(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(dim_of(ys["beta_fast"])), 0)
    high = min(math.ceil(dim_of(ys["beta_slow"])), d - 1)
    high = high + 0.001 if low == high else high
    exps = np.arange(0, d, 2, dtype=np.float32) / d
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv = (1.0 / (f * theta ** exps)) * ramp + (1.0 / theta ** exps) * (1 - ramp)
    cs = _mscale(f, ys["mscale"]) / _mscale(f, ys["mscale_all_dim"])
    s = (m["qk_nope_head_dim"] + d) ** -0.5 * _mscale(
        f, ys["mscale_all_dim"]) ** 2
    return jnp.asarray(inv, jnp.float32), cs, s


def rope(x, positions, inv, cs):
    """x: (S, H, D); rotate the two halves of D by position."""
    d = x.shape[-1]
    ang = positions[:, None].astype(jnp.float32) * inv          # (S, D/2)
    cos = (jnp.cos(ang) * cs)[:, None, :]
    sin = (jnp.sin(ang) * cs)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def swiglu(x, g, u, d):
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def attention(h, w, m, positions):
    eps = m["rms_norm_eps"]
    s = h.shape[0]
    nh, nope, rp = (m["num_attention_heads"], m["qk_nope_head_dim"],
                    m["qk_rope_head_dim"])
    inv, cs, scale = yarn(m)
    x = rms_norm(h, w["ln1"], eps)
    q = (x @ w["wq"]).reshape(s, nh, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], positions, inv, cs)
    lat = x @ w["kv_down"]
    c = rms_norm(lat[:, : m["kv_lora_rank"]], w["kv_ln"], eps)
    r = rope(lat[:, None, m["kv_lora_rank"]:], positions, inv, cs)[:, 0]
    k_nope = (c @ w["k_up"]).reshape(s, nh, nope)
    v = (c @ w["v_up"]).reshape(s, nh, m["v_head_dim"])
    outs = []
    for q0 in range(0, s, Q_BLOCK):                # query blocks
        q1 = min(q0 + Q_BLOCK, s)
        sc = (jnp.einsum("qhd,khd->hqk", q_nope[q0:q1], k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope[q0:q1], r)) * scale
        causal = jnp.arange(q0, q1)[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v))
    o = jnp.concatenate(outs, 0).reshape(s, -1)
    return h + o @ w["wo"]


def gates(x, router, m):
    """(top-k expert ids (S, k), their combine weights (S, k))."""
    p = jax.nn.softmax(x @ router, -1)
    g, idx = jax.lax.top_k(p, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        g = g / jnp.sum(g, -1, keepdims=True)
    return idx, g * m["routed_scaling_factor"]


def routed(x, idx, g, expert_weights, m):
    """The held experts' part: `expert_weights(e)` gives held expert e's
    matrices (e counted from the first held expert)."""
    off = m["expert_offset"]

    def body(y, e):
        w = expert_weights(e)
        weight = jnp.sum(jnp.where(idx == off + e, g, 0.0), -1)   # (S,)
        return y + weight[:, None] * swiglu(x, w["w_gate"], w["w_up"],
                                            w["w_down"]), None
    y, _ = jax.lax.scan(body, jnp.zeros_like(x),
                        jnp.arange(m["n_routed_experts"]))
    return y


def shared(x, w):
    return swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])


def _round(w: dict, fp8: bool) -> dict:
    if not fp8:
        return w
    return {k: (fp8_round(v) if v.ndim == 2 else v) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("model", "padded_vocab", "fp8"))
def _forward(key, tokens, read_at, *, model, padded_vocab, fp8):
    m = dict(model)
    m["rope_scaling"] = dict(m["rope_scaling"])
    t = weights_moe.tables(key, m, padded_vocab)
    positions = jnp.arange(tokens.shape[0])
    h = t["embed"][tokens]
    eps = m["rms_norm_eps"]

    def dense_body(h, index):
        w = _round(weights_moe.layer(key, index, m, dense=True), fp8)
        h = attention(h, w, m, positions)
        x = rms_norm(h, w["ln2"], eps)
        return h + swiglu(x, w["w_gate"], w["w_up"], w["w_down"]), None

    def moe_body(h, index):
        w = _round(weights_moe.layer(key, index, m, dense=False), fp8)
        h = attention(h, w, m, positions)
        x = rms_norm(h, w["ln2"], eps)
        idx, g = gates(x, w["router"], m)

        def expert_weights(e):
            return _round(weights_moe.expert(key, index, m["expert_offset"]
                                             + e, m), fp8)
        return h + routed(x, idx, g, expert_weights, m) + shared(x, w), idx

    n_dense = m["first_k_dense_replace"]
    h, _ = jax.lax.scan(dense_body, h, jnp.arange(n_dense))
    h, idx = jax.lax.scan(moe_body, h,
                          jnp.arange(n_dense, m["num_hidden_layers"]))
    h = rms_norm(h[read_at], t["final_ln"], eps)
    out = t["unembed"][: m["vocab_size"]]
    if fp8:
        out = fp8_round(out.T).T
    return h @ out.T, idx


def _frozen(m: dict):
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in m.items()))


def logits(seed: int, m: dict, padded_vocab: int, tokens, read_at,
           fp8: bool = False):
    """Logits (len(read_at), vocab_size) at positions `read_at` of the
    token sequence `tokens` (pad it at the end to keep one shape: the
    attention is causal)."""
    return forward(seed, m, padded_vocab, tokens, read_at, fp8)[0]


def routing(seed: int, m: dict, padded_vocab: int, tokens):
    """Top-k expert ids of every position in every MoE layer, (MoE
    layers, len(tokens), k)."""
    return forward(seed, m, padded_vocab, tokens, [0])[1]


def forward(seed: int, m: dict, padded_vocab: int, tokens, read_at,
            fp8: bool = False):
    with jax.default_matmul_precision("highest"):
        return _forward(jax.random.key(seed), jnp.asarray(tokens, jnp.int32),
                        jnp.asarray(read_at, jnp.int32), model=_frozen(m),
                        padded_vocab=padded_vocab, fp8=fp8)
