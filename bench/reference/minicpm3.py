"""Plain float32 reference of an MLA decoder (MiniCPM3-style), written
from the equations the configuration states and independent of
`repro.models`.

Per layer, on the residual stream h (all float32, matmuls at "highest"
precision):

    x      = RMSNorm(h; ln1)
    q      = RMSNorm(x W_qd; q_ln) W_qu            -> (S, H, nope + rope)
    c, r   = split(x W_kvd, [kv_lora_rank])         latent and shared rope key
    c      = RMSNorm(c; kv_ln)
    q_rope, r = RoPE(q_rope), RoPE(r)               half-split rotation
    k      = [c W_ku | r]   (r shared by every head),   v = c W_vu
    h     += softmax_causal(q k^T / sqrt(nope + rope)) v  W_o
    x      = RMSNorm(h; ln2)
    h     += (silu(x W_gate) * (x W_up)) W_down

then logits = RMSNorm(h; final_ln) E_out^T over the first `vocab_size`
rows. The weights are drawn layer by layer from the seed inside a scan
over the layers (`bench/weights.py`), so only one layer's float32
weights exist at a time.

With `fp8`, each layer's matrices and the output embedding are rounded
to float8 (e4m3) with a scale per output channel before use: the
control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights

MATRICES = ("q_down", "q_up", "kv_down", "k_up", "v_up", "wo",
            "w_gate", "w_up", "w_down")
Q_BLOCK = 1024


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, positions, theta):
    """x: (S, H, D); rotate the two halves of D by position."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None].astype(jnp.float32) * inv          # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def fp8_round(w):
    """float8 e4m3 with one scale per output channel (last axis)."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _block(h, w, m, positions):
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    s = h.shape[0]
    nh, nope, rp = (m["num_attention_heads"], m["qk_nope_head_dim"],
                    m["qk_rope_head_dim"])
    x = rms_norm(h, w["ln1"], eps)
    q = (rms_norm(x @ w["q_down"], w["q_ln"], eps) @ w["q_up"]).reshape(
        s, nh, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], positions, theta)
    lat = x @ w["kv_down"]
    c = rms_norm(lat[:, : m["kv_lora_rank"]], w["kv_ln"], eps)
    r = rope(lat[:, None, m["kv_lora_rank"]:], positions, theta)[:, 0]
    k_nope = (c @ w["k_up"]).reshape(s, nh, nope)
    v = (c @ w["v_up"]).reshape(s, nh, m["v_head_dim"])
    scale = (nope + rp) ** -0.5
    outs = []
    for q0 in range(0, s, Q_BLOCK):                # query blocks
        q1 = min(q0 + Q_BLOCK, s)
        sc = (jnp.einsum("qhd,khd->hqk", q_nope[q0:q1], k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope[q0:q1], r)) * scale
        causal = jnp.arange(q0, q1)[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v))
    o = jnp.concatenate(outs, 0).reshape(s, -1)
    h = h + o @ w["wo"]
    x = rms_norm(h, w["ln2"], eps)
    return h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("model", "padded_vocab", "fp8"))
def _logits(key, tokens, read_at, *, model, padded_vocab, fp8):
    m = dict(model)
    t = weights.tables(key, m, padded_vocab)
    positions = jnp.arange(tokens.shape[0])
    h = t["embed"][tokens]

    def body(h, index):
        w = weights.layer(key, index, m)
        if fp8:
            w = {k: (fp8_round(v) if k in MATRICES else v)
                 for k, v in w.items()}
        return _block(h, w, m, positions), None

    h, _ = jax.lax.scan(body, h, jnp.arange(m["num_hidden_layers"]))
    h = rms_norm(h[read_at], t["final_ln"], m["rms_norm_eps"])
    out = t["unembed"][: m["vocab_size"]]
    if fp8:
        out = fp8_round(out.T).T
    return h @ out.T


def logits(seed: int, m: dict, padded_vocab: int, tokens, read_at,
           fp8: bool = False):
    """Logits (len(read_at), vocab_size) at positions `read_at` of the
    token sequence `tokens` (pad it at the end to keep one shape: the
    attention is causal)."""
    model = tuple(sorted(m.items()))
    with jax.default_matmul_precision("highest"):
        return _logits(jax.random.key(seed), jnp.asarray(tokens, jnp.int32),
                       jnp.asarray(read_at, jnp.int32), model=model,
                       padded_vocab=padded_vocab, fp8=fp8)
