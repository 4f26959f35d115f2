"""Public wrapper for decode_attention: padding + dispatch."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref_explicit


def decode_bhd(q, k_cache, v_cache, pos, *, window=0, use_kernel=True,
               block_k=512, interpret=False):
    """q: (B,H,D); caches (B,S,Hkv,D); pos () or (B,)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (q.shape[0],))
    if not use_kernel:
        return decode_attention_ref_explicit(q, k_cache, v_cache, pos,
                                             window=window)
    s = k_cache.shape[1]
    block_k = min(block_k, s)
    pad = (-s) % block_k
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # padded positions are masked by `idx < pos` automatically
    return decode_attention(q, k_cache, v_cache, pos, window=window,
                            block_k=block_k, interpret=interpret)
