"""Pallas TPU kernel: fleet-scale batched NBTI dVth update.

The paper's hot loop — advancing every core's threshold-voltage shift by
an interval under its current (temperature, stress) regime — vectorized
over an entire fleet's cores (cluster analytics path / periodic
settlement). Elementwise math, so the kernel is a 1-D VMEM tiling with
128-lane-aligned blocks; on TPU this runs out of VMEM at vector-unit
throughput rather than bouncing per-core scalars through HBM.

    dvth' = ADF * ((dvth/ADF)^(1/n) + tau)^n,  ADF = 0 freezes (deep idle)
    ADF   = K * exp(-E0/kB*T) * exp(C*Vdd/(kB*T)) * Y^n
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 1024  # cores per block; multiple of the 128-lane VPU width


def _kernel(dvth_ref, temp_ref, stress_ref, tau_ref, out_ref, *,
            n, k_fit, e0, kb, c_field, vdd):
    dvth = dvth_ref[...].astype(jnp.float32)
    t_k = temp_ref[...].astype(jnp.float32) + 273.15
    stress = stress_ref[...].astype(jnp.float32)
    tau = tau_ref[...].astype(jnp.float32)
    adf = (k_fit * jnp.exp(-e0 / (kb * t_k))
           * jnp.exp(c_field * vdd / (kb * t_k))
           * jnp.where(stress > 0, stress, 1.0) ** n)
    live = (stress > 0) & (tau > 0)
    safe = jnp.where(live, adf, 1.0)
    eff_t = (dvth / safe) ** (1.0 / n)
    new = safe * (eff_t + tau) ** n
    out_ref[...] = jnp.where(live, new, dvth)


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def aging_update(dvth, temp_c, stress, tau, params, interpret=False):
    """Batched dVth advance. All inputs shape (N,) float32 (N padded to a
    BLOCK multiple by the wrapper in ops.py). `params` is AgingParams."""
    n_cores = dvth.shape[0]
    grid = (pl.cdiv(n_cores, BLOCK),)
    spec = pl.BlockSpec((BLOCK,), lambda i: (i,))
    kernel = functools.partial(
        _kernel, n=params.n, k_fit=params.K, e0=params.E0, kb=params.kB,
        c_field=params.c_field, vdd=params.vdd)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n_cores,), jnp.float32),
        interpret=interpret,
        name="aging_update",
    )(dvth, temp_c, stress, tau)
