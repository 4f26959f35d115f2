"""jit'd public wrapper: pads to the kernel block size, dispatches to the
Pallas kernel or the jnp oracle. Interpret mode (for hosts without a TPU)
is the caller's explicit choice, never inferred here."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.aging_update.aging_update import BLOCK, aging_update
from repro.kernels.aging_update.ref import aging_update_ref


def advance_fleet(dvth, temp_c, stress, tau, params, use_kernel=True,
                  interpret=False):
    """Advance a fleet of cores' dVth. Inputs (N,); returns (N,) f32."""
    dvth = jnp.asarray(dvth, jnp.float32)
    temp_c = jnp.asarray(temp_c, jnp.float32)
    stress = jnp.asarray(stress, jnp.float32)
    tau = jnp.asarray(tau, jnp.float32)
    if not use_kernel:
        return aging_update_ref(dvth, temp_c, stress, tau, params)
    n = dvth.shape[0]
    pad = (-n) % BLOCK
    if pad:
        dvth = jnp.pad(dvth, (0, pad))
        temp_c = jnp.pad(temp_c, (0, pad))
        stress = jnp.pad(stress, (0, pad))
        tau = jnp.pad(tau, (0, pad))
    out = aging_update(dvth, temp_c, stress, tau, params,
                       interpret=interpret)
    return out[:n]
