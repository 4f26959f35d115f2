"""Common neural layers: RMSNorm, RoPE, SwiGLU, embeddings. Pure JAX."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight).astype(dtype)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for rotary embeddings, shape (head_dim//2,)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_frequencies(head_dim: int, theta: float, factor: float,
                     original_max_pos: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies (arXiv:2309.00071, as DeepSeek-V2 uses
    them): dimensions that turn more than `beta_fast` times over the
    original context keep their frequency, those that turn fewer than
    `beta_slow` times are divided by `factor`, with a linear ramp
    between. Shape (head_dim//2,), float32."""
    def correction_dim(rotations):
        return (head_dim * math.log(original_max_pos
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    exps = np.arange(0, head_dim, 2, dtype=np.float32) / head_dim
    extra = 1.0 / theta ** exps
    inter = 1.0 / (factor * theta ** exps)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               inv_freq=None, mscale: float = 1.0) -> jax.Array:
    """Rotary position embedding.

    x: (..., S, H, D) with D even; positions: broadcastable to (..., S).
    Uses the half-split convention (rotate_half), matching Llama.
    `inv_freq` replaces the plain frequencies (YaRN), and `mscale`
    scales cos and sin.
    """
    d = x.shape[-1]
    if inv_freq is None:
        inv_freq = rope_frequencies(d, theta)              # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (...,S,D/2)
    cos = jnp.cos(angles)[..., None, :]                    # (...,S,1,D/2)
    sin = jnp.sin(angles)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    """Token embedding lookup against a (padded_vocab, d_model) table."""
    return jnp.take(table, tokens, axis=0)


def unembed(h: jax.Array, table: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Project to (padded) vocabulary logits; padded ids masked to -inf."""
    logits = jnp.einsum("...d,vd->...v", h, table)
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, jnp.finfo(logits.dtype).min, logits)
    return logits


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: jax.Array | None = None) -> jax.Array:
    """Mean next-token cross entropy. logits (B,S,V), labels (B,S)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
