"""Public wrapper for ssd_scan: padding + dispatch."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan


def ssd(x, dt, a_log, b, c, *, chunk=256, use_kernel=True, interpret=False):
    """Chunked SSD. x (B,L,H,P), dt (B,L,H), a_log (H,), b/c (B,L,N)."""
    if not use_kernel:
        return ssd_scan_ref(x, dt, a_log, b, c)
    l = x.shape[1]
    chunk = min(chunk, l)
    pad = (-l) % chunk
    if pad:
        # zero-dt padding: exp(0)=1 decay, zero update => exact
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    y = ssd_scan(x, dt, a_log, b, c, chunk=chunk, interpret=interpret)
    return y[:, :l]
