"""Public wrapper: layout conversion, padding, kernel/ref dispatch."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref


def attention_bhsd(q, k, v, *, causal=True, window=0, use_kernel=True,
                   block_q=128, block_k=128, interpret=False):
    """Flash attention in (B, S, H, D) model layout. GQA-aware."""
    qt = jnp.swapaxes(q, 1, 2)   # (B,H,S,D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if not use_kernel:
        out = flash_attention_ref(qt, kt, vt, causal=causal, window=window)
        return jnp.swapaxes(out, 1, 2)
    s = qt.shape[2]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    pad = (-s) % max(block_q, block_k)
    if pad:
        # pad queries AND keys; padded kv columns are masked by causality
        # for padded q rows only, so mask padded kv explicitly via window
        # -- simpler: pad then slice; padded rows produce garbage that we
        # drop, padded kv columns are masked because k_pos > s-1 >= q_pos
        # only for padded q rows. For causal attention this is exact.
        assert causal, "padding path requires causal masking"
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    out = flash_attention(qt, kt, vt, causal=causal, window=window,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)
    if pad:
        out = out[:, :, :s]
    return jnp.swapaxes(out, 1, 2)
